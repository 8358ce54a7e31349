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
and ``models/transformer.py``, which knows nothing of ZeRO, asks it for each
block of its loop over layers, for the token embedding's look-up and for the
loss head (``block_hook``). A region that is taken runs as each device's own
program under ``shard_map``, and no matmul of it is cut up by the
partitioner's ring, no reduce-scatter of it holds the lane:

- a block (``gathered_block``) works on its rows of the batch: its
  parameters are all-gathered at its top, ``dW`` comes out of the backward
  as whole matmuls, and the transpose of the gather is a ring of
  collective-permutes (``_ring_reduce_scatter``) that depends on nothing in
  the layers below. ``tie`` is the bucket's deadline: laid between layer
  ``i``'s input and layer ``i + 1``'s parameters in the forward, its
  backward holds layer ``i``'s activation gradient until layer ``i + 1``'s
  reduced gradients are there, so the scheduler has exactly layer ``i``'s
  backward to place those hops under;
- the look-up and the head (``sharded_look_up``, ``sharded_head``) work on
  their rows of the EMBEDDING, as the partitioner has them work, because
  the embedding is three times the activations: what is gathered is the
  batch (ids; the head's activations), each device's result is its slice's
  part, and the same ring sums the look-up's rows and the head's
  activation gradient to the device that owns the row of the batch. ``dE``
  needs no reduction: every device has seen every token.

The reference's name for this is ``overlap_comm``, with a layer as the
bucket; ``reduce_bucket_size`` is not read.

What it costs is memory: the gathered bf16 weight is its matmul's residual,
so it stays on every device from the layer's forward to its backward.
``stage3_max_live_parameters`` bounds that, and means what it means in the
reference: how many gathered parameters stay live between forward and
backward. No block leaves the plan for it. The last blocks keep what they
gathered, as many as fit under the bound; the blocks before them keep the
shard and all-gather it a second time in their backward (``_regathering``),
one more hidden all-gather a layer. At 0 nothing is kept and every block
gathers twice. Look-up and head gather no parameter.
"""

import contextlib
import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ...models.transformer import block_hook
from ...telemetry.registry import get_registry
from ...telemetry.tracing import region
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

    def ring(step, offset, length):
        piece = lambda k: jax.lax.dynamic_slice_in_dim(g, (k % size) * rows + offset, length, axis=dim)
        perm = [(d, (d + step) % size) for d in range(size)]
        acc = piece(me + step * (size - 1))
        for hop in range(1, size):
            acc = jax.lax.ppermute(acc, axis, perm) + piece(me + step * (size - 1 - hop))
        return acc

    with region("zero/reduce"):
        me = jax.lax.axis_index(axis)
        if rows % 2:
            return ring(1, 0, rows)
        return jax.lax.concatenate([ring(1, 0, rows // 2), ring(-1, rows // 2, rows // 2)], dim)


@functools.cache
def _ring(axis: str, size: int, dim: int):
    # the backward is walked a layer, not a kind of block: jit keeps the ring's trace a shape of weight and replays it
    return jax.jit(functools.partial(_ring_reduce_scatter, axis=axis, size=size, dim=dim), inline=True)


@functools.cache
def _gather(axis: str, size: int, dim: int):
    @jax.custom_vjp
    def gather(w):
        with region("zero/gather"):
            return jax.lax.all_gather(w, axis, axis=dim, tiled=True)

    gather.defvjp(lambda w: (gather(w), None), lambda _, g: (_ring(axis, size, dim)(g),))
    return gather


@jax.tree_util.register_static
@dataclasses.dataclass(frozen=True)
class _Slots:
    """What a block's forward keeps for its backward, as a tree, and where among its leaves the gathered weights were."""
    kept_def: Any
    at: tuple


def _regathering(body, dims, axis: str, size: int):
    """``body(params, *rows)`` of gathered ``params`` as a function of their
    shards (``dims``: the sharded dimension of each leaf, None for a leaf
    that is whole) that gathers them a SECOND time in its backward: where
    the forward's residuals hold a gathered weight (the activation
    gradient's matmul reads it), the forward keeps its place and the shard,
    and the backward all-gathers the shard again and then reduces the
    gradient round the ring as ``_gather`` does. The shards pass an
    ``optimization_barrier`` on the way, with the gradient that enters the
    backward. Without a barrier XLA merges the second gather with the first
    and the weight stays live after all; with one on the shards alone the
    gathers depend on nothing the backward makes, and the scheduler spreads
    them in pieces over the matmuls of EVERY layer's backward, which made
    each of those 3% slower (step 237.5 ms against 232.9: my chip run, PR
    30). Tied to the gradient they ride on this block's own backward, whose
    weight-gradient matmuls do not read the weights."""
    leaf_dims, treedef = jax.tree_util.tree_flatten(dims, is_leaf=lambda d: d is None)
    gather = lambda w, dim: w if dim is None else jax.lax.all_gather(w, axis, axis=dim, tiled=True)

    def whole(params):
        with region("zero/gather"):
            return jax.tree_util.tree_unflatten(treedef, [gather(w, dim) for w, dim in zip(treedef.flatten_up_to(params), leaf_dims)])

    @jax.custom_vjp
    def local(params, *rows):
        return body(whole(params), *rows)

    def forward(params, *rows):
        gathered = treedef.flatten_up_to(whole(params))
        y, vjp = jax.vjp(body, jax.tree_util.tree_unflatten(treedef, gathered), *rows)
        kept, kept_def = jax.tree_util.tree_flatten(vjp)
        slots = tuple(next((k for k, (w, dim) in enumerate(zip(gathered, leaf_dims)) if dim is not None and leaf is w), None)
                      for leaf in kept)
        return y, (params, [None if slot is not None else leaf for leaf, slot in zip(kept, slots)], _Slots(kept_def, slots))

    def backward(residuals, g):
        params, kept, where = residuals
        slots, kept_def = where.at, where.kept_def
        shards, g = jax.lax.optimization_barrier((treedef.flatten_up_to(params), g))
        with region("zero/regather"):
            again = {k: gather(shards[k], leaf_dims[k]) for k in set(slots) - {None}}
        vjp = jax.tree_util.tree_unflatten(kept_def, [leaf if slot is None else again[slot] for leaf, slot in zip(kept, slots)])
        grads, *rest = vjp(g)
        grads = [dw if dim is None else _ring(axis, size, dim)(dw) for dw, dim in zip(treedef.flatten_up_to(grads), leaf_dims)]
        return (jax.tree_util.tree_unflatten(treedef, grads), *rest)

    local.defvjp(forward, backward)
    return local


def _sharded_dim(spec: P, axis: str) -> Optional[int]:
    for dim, entry in enumerate(spec):
        if entry == axis or (isinstance(entry, (tuple, list)) and axis in entry):
            return dim
    return None


def _gathered_parameters(params, layer_specs, axis: str) -> int:
    sizes = jax.tree_util.tree_map(lambda w, spec: 0 if _sharded_dim(spec, axis) is None else w.size, params, layer_specs)
    return sum(jax.tree_util.tree_leaves(sizes))


@functools.cache
def _block_wrap(plan: GatherPlan, treedef, specs, keep: bool):
    """One ``wrap`` a shape of specs and a way with the gathered weights, so that layers of one kind share one trace
    (``block_fn`` is cached on it)."""
    return functools.partial(gathered_block, plan, jax.tree_util.tree_unflatten(treedef, specs), keep)


class BlockGather:
    """The model's hook (``models/transformer.py`` ``block_hook``) for ONE
    trace of the model under ``plan``: which blocks gather their own
    parameters, which of them keep what they gathered for their backward,
    how many parameters that keeps live, and whether the look-up and the
    head run on each device's rows of the embedding."""

    def __init__(self, plan: GatherPlan):
        self.plan = plan
        self.live = 0  # parameters that the blocks taken so far keep gathered from their forward to their backward
        # of the model being traced: each layer's specs (None: the partitioner's), what it gathers, and the layers that keep
        self.specs = self.sizes = self.kept = None

    def __call__(self, paths, layers, sows, i, x):
        """``(wrap, x)`` for block ``i`` of a model, whose parameters are
        ``layers[i]`` at ``paths[i]`` of the tree, fed ``x``. ``wrap`` is
        None, and the block XLA's partitioner's, where the tree is not the
        engine's (a caller that applies a sub-tree), the block sows (MoE's
        auxiliary loss would have to leave the manual region) or nothing in
        it is sharded. Of the blocks taken, the LAST ones keep what they
        gathered, as many as fit under the plan's bound with what is kept
        already: a block that gathers a second time does so where its
        backward is, and the first layers' backward is where the step holds
        the fewest activations. A block that is taken is also the deadline
        of the next one's bucket: ``tie``."""
        plan = self.plan
        if i == 0:  # the first block of a model: settle who keeps
            self.specs = [None if sow else plan.specs_at(path) for path, sow in zip(paths, sows)]
            self.sizes = [0 if spec is None else _gathered_parameters(layer, spec, plan.axis)
                          for layer, spec in zip(layers, self.specs)]
            room = plan.max_live - self.live
            self.kept = {j for j in range(len(layers)) if 0 < sum(self.sizes[j:]) <= room}
            self.live += sum(self.sizes[j] for j in self.kept)
        if self.sizes[i] == 0:
            return None, x
        keep = i in self.kept
        registry = get_registry()
        registry.counter("train_bucket_layers_traced_total").inc()
        if not keep:
            registry.counter("train_bucket_regathers_traced_total").inc()
        # what a block returns names the mesh in its type; what it takes has to as well, or a kind is traced twice
        x = jax.lax.with_sharding_constraint(x, jax.sharding.NamedSharding(plan.mesh, P(plan.axis)))
        if i + 1 < len(layers):
            x, layers[i + 1] = tie(x, layers[i + 1])
        leaves, treedef = jax.tree_util.tree_flatten(self.specs[i], is_leaf=lambda s: isinstance(s, P))
        return _block_wrap(plan, treedef, tuple(leaves), keep), x

    def look_up(self, path, table, ids, sows):
        """``table[ids]`` for the token embedding at ``path``, every device
        looking the whole batch's ids up in its own rows of the table
        (``sharded_look_up``); None, and the partitioner's, where a block
        would be, or where the table is not sharded by rows."""
        plan = self.plan
        spec = plan.specs_at(path)
        if sows or not isinstance(spec, P) or _sharded_dim(spec, plan.axis) != 0 or ids.shape[0] % plan.size:
            return None
        get_registry().counter("train_bucket_head_traced_total").inc()
        return sharded_look_up(plan, spec)(table, ids)

    def head(self, paths, leaves, fn, vocab_dim, sows):
        """``run(hidden, labels)`` for the loss head (``block_hook``), every
        device computing the whole batch's loss over its own slice of the
        vocabulary (``sharded_head``); None, and the partitioner's, where a
        block would be, or where the weight is not sharded by the
        vocabulary."""
        plan = self.plan
        specs = tuple(plan.specs_at(path) for path in paths)
        if sows or not all(isinstance(spec, P) for spec in specs) or _sharded_dim(specs[0], plan.axis) != vocab_dim:
            return None
        get_registry().counter("train_bucket_head_traced_total").inc()
        return functools.partial(sharded_head(plan, specs, fn), tuple(leaves))


@contextlib.contextmanager
def active(plan: Optional[GatherPlan]):
    """The engine holds this around the model's loss while that is traced:
    inside, a fresh ``BlockGather`` of ``plan`` is the model's block hook."""
    with block_hook(BlockGather(plan)) if plan is not None else contextlib.nullcontext():
        yield


def _whole(plan: GatherPlan, params, specs):
    """``params`` with every leaf that is sharded over the plan's axis
    gathered. A leaf that is whole on every device stays as it is:
    ``shard_map`` sums its gradient itself."""
    def whole(w, spec):
        dim = _sharded_dim(spec, plan.axis)
        return w if dim is None else _gather(plan.axis, plan.size, dim)(w)

    return jax.tree_util.tree_map(whole, params, specs)


def gathered_block(plan: GatherPlan, layer_specs, keep: bool, apply):
    """``apply(params, x, positions, kv_cache, segment_ids, taken)`` of one
    block (``models/transformer.py::block_fn``) as every device's own program
    on its rows of the batch, the parameters gathered at its top, and again
    in its backward unless it is to ``keep`` them. The values a block takes
    from or gives to other blocks pass through by their rows too (a scalar
    whole): none, for a kind whose record names none."""
    rows = P(plan.axis)
    dims = jax.tree_util.tree_map(lambda spec: _sharded_dim(spec, plan.axis), layer_specs, is_leaf=lambda s: isinstance(s, P))
    # once a KIND of block and program (``block_fn``): one a sharded leaf
    get_registry().counter("train_bucket_rings_traced_total").inc(len(jax.tree_util.tree_leaves(dims)))

    def body(params, x, positions, segment_ids, taken):
        (y, _), _, given = apply(params, x, positions, None, segment_ids, taken)
        return y, given

    if keep:
        local = lambda params, *rest: body(_whole(plan, params, layer_specs), *rest)
    else:
        local = _regathering(body, dims, plan.axis, plan.size)

    def call(params, x, positions, kv_cache, segment_ids, taken):  # a plan is a training matter: there is no cache
        by_rows = jax.tree_util.tree_map(lambda value: rows if jnp.ndim(value) else P(), taken)
        mapped = jax.shard_map(local, mesh=plan.mesh, in_specs=(layer_specs, rows, rows, rows, by_rows), out_specs=(rows, rows),
                               check_vma=False)
        # what the manual region adds itself, outside the block's parts: ``shard_map``'s sum of the whole leaves' gradients
        with region("zero/reduce"):
            y, given = mapped(params, x, positions, segment_ids, taken)
        return (y, None), {}, given

    return call


def sharded_head(plan: GatherPlan, specs, fn):
    """``fn(leaves, hidden, labels, vocab_axis=)`` (``block_hook``) as every
    device's own program on its slice of the vocabulary: the activations
    are gathered where the partitioner's program gathers them too, so ``dE``
    comes out of one whole matmul over all tokens, sharded as it is kept,
    and the activation gradient, a sum over the slices, goes to the owner of
    each row of the batch round the ring that is the gather's transpose,
    beside the ``dE`` matmul, where the partitioner cut that matmul in four
    round its own. Gathering the embedding instead (206 MB at OLMo-1B, and
    154 MB a chip of ``dE`` back round a ring) made every bucket's hops wait
    longer: 2.4 ms a step (my chip run, PR 30). Every device ends with the
    whole sums and returns its share of them."""
    axis, size = plan.axis, plan.size

    def local(leaves, hidden, labels):
        hidden = _gather(axis, size, 0)(hidden)
        with region("zero/gather"):
            labels = jax.lax.all_gather(labels, axis, axis=0, tiled=True)
        return tuple(whole / size for whole in fn(leaves, hidden, labels, vocab_axis=axis))

    return jax.shard_map(local, mesh=plan.mesh, in_specs=(specs, P(axis), P(axis)), out_specs=P(axis), check_vma=False)


def sharded_look_up(plan: GatherPlan, spec: P):
    """``table[ids]`` with the table sharded by rows, as every device's own
    program: it gathers the whole batch's ids (a few kilobytes), looks up
    those that fall into its rows, and the partial results, of which one a
    token is not zero, are summed to the device that owns the token's row of
    the batch by the ring. What travels is the activations, a third of what
    gathering OLMo-1B's table moves, and the backward needs no ring: the
    activation gradient is all-gathered and each device scatter-adds into
    its own rows (gathering the table instead held the top of the step for
    1.66 ms: my chip run, PR 30)."""
    axis, size = plan.axis, plan.size

    @jax.custom_vjp
    def to_owner(part):
        return _ring_reduce_scatter(part, axis, size, 0)

    def gather_rows(x):
        with region("zero/gather"):
            return jax.lax.all_gather(x, axis, axis=0, tiled=True)

    to_owner.defvjp(lambda part: (to_owner(part), None), lambda _, g: (gather_rows(g),))

    def local(shard, ids):
        ids = gather_rows(ids) - jax.lax.axis_index(axis) * shard.shape[0]
        mine = (ids >= 0) & (ids < shard.shape[0])
        # an id of another device's rows is out of bounds: it reads a zero, and its gradient is dropped
        return to_owner(shard.at[jnp.where(mine, ids, shard.shape[0])].get(mode="fill", fill_value=0))

    return jax.shard_map(local, mesh=plan.mesh, in_specs=(spec, P(axis)), out_specs=P(axis), check_vma=False)


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
    """``layers`` taken by the plan, those of them that gather a second time
    in their backward (``regathers``), the ``rings`` laid into them (gathers,
    each with a ring for its transpose: one a sharded leaf of a kind of
    block), or the regions that are no block, token look-ups and loss heads
    (``head``), in the models traced so far."""
    return int(get_registry().peek(f"train_bucket_{what}_traced_total") or 0)
