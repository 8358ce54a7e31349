"""``overlap_comm`` at ZeRO stage 3: a layer's weight gradients are reduced
under the next layer's backward.

Left to XLA, the TPU compiler reduce-scatters ``dW = X^T dY`` inside the
matmul: it cuts the matmul into one chunk a device and passes a partial shard
round the ring between the chunks ("windowed einsum"), so every hop has only
a quarter of ONE weight's matmul to hide behind, and at a few thousand tokens
a chip the hop outlasts it (``PERF.md``, PR 28: 348 waits a step). Its
stand-alone reduce-scatter is no way out: that one holds the operation lane
for the whole transfer. What this compiler does run beside compute are
all-gathers and collective-permutes.

So where the engine has a ``GatherPlan`` (``plan_for``), it holds a
``BlockGather`` around the model's loss while that is traced (``active``),
and ``models/transformer.py``'s loop over layers, which knows nothing of
ZeRO, asks it for each block (``block_hook``). A block that is taken runs as
each device's own program over its rows of the batch (``gathered_block``):
its parameters are all-gathered at its top, ``dW`` comes out of the backward
as whole matmuls, and the transpose of the gather is a ring of
collective-permutes (``_ring_reduce_scatter``) that depends on nothing in
the layers below. ``tie`` is the bucket's deadline: laid between layer
``i``'s input and layer ``i + 1``'s parameters in the forward, its backward
holds layer ``i``'s activation gradient until layer ``i + 1``'s reduced
gradients are there, so the scheduler has exactly layer ``i``'s backward to
place those hops under. The reference's name for this is ``overlap_comm``,
with a layer as the bucket; ``reduce_bucket_size`` is not read.

What it costs is memory: the gathered bf16 weight is its matmul's residual,
so it stays on every device from the layer's forward to its backward, where
the partitioner's program gathers a second time. ``stage3_max_live_parameters``
bounds that: blocks are taken, first layer first, while the parameters they
gather stay under it, and the layers after that are the partitioner's.
"""

import contextlib
import dataclasses
import functools
from typing import Any, Optional

import jax
from jax.sharding import PartitionSpec as P

from ...models.transformer import block_hook
from ...telemetry.registry import get_registry
from .partition import zero_axes_for


@dataclasses.dataclass(frozen=True)
class GatherPlan:
    """What a block needs to gather its own parameters: the mesh, its one
    axis wider than a device (ZeRO's and the batch's at once), the engine's
    ``PartitionSpec`` tree, shaped like the parameters, and how many
    gathered parameters may stay live (``stage3_max_live_parameters``)."""
    mesh: Any
    axis: str
    param_specs: Any = dataclasses.field(compare=False, hash=False)
    max_live: int = 0

    @property
    def size(self) -> int:
        return self.mesh.shape[self.axis]

    def specs_at(self, path):
        """The specs under ``path`` of the parameter tree; ``None`` where the
        model's tree is not the engine's (a caller that applies a sub-tree)."""
        node = self.param_specs
        for name in path:
            if not isinstance(node, dict) or name not in node:
                return None
            node = node[name]
        return node


def _backend() -> str:
    return jax.default_backend()


def plan_for(config, topo, param_specs) -> Optional[GatherPlan]:
    """The plan, where it applies: a TPU (the choice is about what ITS
    compiler overlaps), stage 3 with ``overlap_comm`` (its default there),
    and a mesh whose only axis wider than one device is ZeRO's, so that a
    block can run manually over the whole mesh. ``None`` everywhere else:
    XLA partitions the block as before."""
    zero = config.zero_config
    axes = zero_axes_for(topo)
    if _backend() != "tpu" or zero.stage != 3 or not zero.overlap_comm:
        return None
    if len(axes) != 1 or topo.n_devices == 1 or topo.axis_size(axes[0]) != topo.n_devices:
        return None
    return GatherPlan(topo.mesh, axes[0], param_specs, zero.stage3_max_live_parameters)


def _ring_reduce_scatter(g, axis: str, size: int, dim: int):
    """Sum of every device's ``g`` over ``axis``, device ``d`` keeping chunk
    ``d`` of ``dim``: ``size - 1`` hops, each a collective-permute of one
    chunk and an add. The two halves of a chunk travel opposite ways round
    the ring, so both directions of a link carry half a hop."""
    rows = g.shape[dim] // size
    me = jax.lax.axis_index(axis)

    def ring(step, offset, length):
        piece = lambda k: jax.lax.dynamic_slice_in_dim(g, (k % size) * rows + offset, length, axis=dim)
        perm = [(d, (d + step) % size) for d in range(size)]
        acc = piece(me + step * (size - 1))
        for hop in range(1, size):
            acc = jax.lax.ppermute(acc, axis, perm) + piece(me + step * (size - 1 - hop))
        return acc

    if rows % 2:
        return ring(1, 0, rows)
    return jax.lax.concatenate([ring(1, 0, rows // 2), ring(-1, rows // 2, rows // 2)], dim)


@functools.cache
def _gather(axis: str, size: int, dim: int):
    @jax.custom_vjp
    def gather(w):
        return jax.lax.all_gather(w, axis, axis=dim, tiled=True)

    # the backward is walked a layer, not a kind of block: jit keeps the ring's trace a shape of weight and replays it
    ring = jax.jit(functools.partial(_ring_reduce_scatter, axis=axis, size=size, dim=dim), inline=True)
    gather.defvjp(lambda w: (gather(w), None), lambda _, g: (ring(g),))
    return gather


def _sharded_dim(spec: P, axis: str) -> Optional[int]:
    for dim, entry in enumerate(spec):
        if entry == axis or (isinstance(entry, (tuple, list)) and axis in entry):
            return dim
    return None


def _gathered_parameters(params, layer_specs, axis: str) -> int:
    sizes = jax.tree_util.tree_map(lambda w, spec: 0 if _sharded_dim(spec, axis) is None else w.size, params, layer_specs)
    return sum(jax.tree_util.tree_leaves(sizes))


@functools.cache
def _block_wrap(plan: GatherPlan, treedef, specs):
    """One ``wrap`` a shape of specs, so that layers of one kind share one trace (``block_fn`` is cached on it)."""
    return functools.partial(gathered_block, plan, jax.tree_util.tree_unflatten(treedef, specs))


class BlockGather:
    """The model's block hook (``models/transformer.py`` ``block_hook``) for
    ONE trace of the model under ``plan``: which blocks gather their own
    parameters, and how many parameters that keeps live."""

    def __init__(self, plan: GatherPlan):
        self.plan = plan
        self.live = 0  # parameters gathered by the blocks taken so far

    def __call__(self, path, layers, i, sows, x):
        """``(wrap, x)`` for the block at ``path`` of the model's tree whose
        parameters are ``layers[i]``, fed ``x``. ``wrap`` is None, and the
        block XLA's partitioner's, where the tree is not the engine's (a
        caller that applies a sub-tree), the block sows (MoE's auxiliary loss
        would have to leave the manual region), nothing in it is sharded, or
        its parameters would not fit under the plan's bound. A block that is
        taken is also the deadline of the next one's bucket: ``tie``."""
        plan = self.plan
        layer_specs = plan.specs_at(path)
        if layer_specs is None or sows:
            return None, x
        n = _gathered_parameters(layers[i], layer_specs, plan.axis)
        if n == 0 or self.live + n > plan.max_live:
            return None, x
        self.live += n
        get_registry().counter("train_bucket_layers_traced_total").inc()
        # what a block returns names the mesh in its type; what it takes has to as well, or a kind is traced twice
        x = jax.lax.with_sharding_constraint(x, jax.sharding.NamedSharding(plan.mesh, P(plan.axis)))
        if i + 1 < len(layers):
            x, layers[i + 1] = tie(x, layers[i + 1])
        leaves, treedef = jax.tree_util.tree_flatten(layer_specs, is_leaf=lambda s: isinstance(s, P))
        return _block_wrap(plan, treedef, tuple(leaves)), x


@contextlib.contextmanager
def active(plan: Optional[GatherPlan]):
    """The engine holds this around the model's loss while that is traced:
    inside, a fresh ``BlockGather`` of ``plan`` is the model's block hook."""
    with block_hook(BlockGather(plan)) if plan is not None else contextlib.nullcontext():
        yield


def gathered_block(plan: GatherPlan, layer_specs, apply):
    """``apply(params, x, positions, kv_cache, segment_ids)`` of one block
    (``models/transformer.py::block_fn``) as every device's own program on
    its rows of the batch, the parameters gathered at its top. A leaf that
    is whole on every device stays as it is: ``shard_map`` sums its
    gradient itself."""
    rows = P(plan.axis)
    rings = get_registry().counter("train_bucket_rings_traced_total")

    def local(params, x, positions, segment_ids):
        def whole(w, spec):
            dim = _sharded_dim(spec, plan.axis)
            if dim is None:
                return w
            rings.inc()  # while the block is traced: one a sharded leaf of a KIND of block
            return _gather(plan.axis, plan.size, dim)(w)

        (y, _), _ = apply(jax.tree_util.tree_map(whole, params, layer_specs), x, positions, None, segment_ids)
        return y

    mapped = jax.shard_map(local, mesh=plan.mesh, in_specs=(layer_specs, rows, rows, rows), out_specs=rows,
                           check_vma=False)

    def call(params, x, positions, kv_cache, segment_ids):  # a plan is a training matter: there is no cache
        return (mapped(params, x, positions, segment_ids), None), {}

    return call


@jax.custom_vjp
def tie(x, later):
    """Identity on the activations entering a layer and on the NEXT layer's
    parameters. In the backward it is the moment by which the next layer's
    reduced gradients must have arrived: this layer's activation gradient
    waits for them, so the scheduler has exactly this layer's backward to
    place their hops under, and cannot pile whole ``dW``s up to the end of
    the program (step 233.12 -> 231.71 ms, temporaries 8.09 -> 7.77 GB at
    OLMo-1B on four v5e chips: my chip run and described-chip compile, PR 28)."""
    return x, later


tie.defvjp(lambda x, later: ((x, later), None), lambda _, g: jax.lax.optimization_barrier(g))


def traced(what: str) -> int:
    """``layers`` taken by the plan, or ``rings`` laid into them (gathers,
    each with a ring for its transpose: one a sharded leaf of a kind of
    block), in the models traced so far."""
    return int(get_registry().peek(f"train_bucket_{what}_traced_total") or 0)
