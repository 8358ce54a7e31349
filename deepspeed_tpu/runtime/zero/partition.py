"""ZeRO as sharding: the partition planner.

The reference implements ZeRO by tensor surgery + grad hooks
(``runtime/zero/stage_1_and_2.py``, ``stage3.py``). On TPU the same
lifecycle contract is expressed as *where each pytree leaf lives on the
mesh* (SURVEY.md §7):

- stage 0: params, grads, optimizer state replicated over data axes; XLA
  all-reduces grads (DDP).
- stage 1: optimizer state sharded over the ZeRO axes; grads replicated;
  XLA reduce-scatters into the (sharded) update and all-gathers updated
  params — the reference's ``step()`` allgather (``stage_1_and_2.py:1919``)
  becomes a compiled collective.
- stage 2: additionally the gradient-accumulation buffer is sharded, so
  each micro-batch backward ends in a reduce-scatter (the analogue of the
  hook-driven bucketed RS at ``stage_1_and_2.py:1037``).
- stage 3: parameters themselves are sharded; XLA inserts
  allgather-on-use in forward/backward (the coordinator's fetch/release,
  ``partitioned_param_coordinator.py:262``, becomes compiler scheduling;
  persistence thresholds map to "don't shard small params").

The ZeRO axes are ``('fsdp',)`` when the mesh has a dedicated fsdp axis,
else ``('data',)`` — ZeRO over the DP group, exactly the reference's
default.
"""

from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ...parallel.mesh import _norm, fit_spec, prune_spec  # noqa: F401 (pure functions of a spec and a topology: they live beside it)
from ...utils.logging import logger


def zero_axes_for(topo) -> Tuple[str, ...]:
    """Mesh axes that carry ZeRO shards."""
    if topo.axis_size("fsdp") > 1:
        return ("fsdp",)
    return ("data",)


def _axes_in_spec(spec: P) -> set:
    used = set()
    for entry in spec:
        if entry is None:
            continue
        if isinstance(entry, (tuple, list)):
            used.update(entry)
        else:
            used.add(entry)
    return used


def match_partition_rule(path: Tuple[str, ...], rules: Sequence[Tuple[Tuple[str, ...], P]]) -> Optional[P]:
    """First rule whose key names all appear (in order) in the param path."""
    for key, spec in rules:
        it = iter(path)
        if all(any(k == p for p in it) for k in key):
            return spec
    return None


def shard_leaf_spec(shape: Tuple[int, ...], base_spec: Optional[P], axes: Tuple[str, ...], axes_size: int,
                    min_size: int = 0) -> P:
    """Extend ``base_spec`` by sharding one more dimension over ``axes``.

    Picks the largest dimension that is not already sharded and is
    divisible by the axes product; leaves the param alone if it is smaller
    than ``min_size`` (the persistence-threshold analogue,
    reference ``parameter_offload.py:242``).
    """
    base = tuple(base_spec) if base_spec is not None else ()
    base = base + (None,) * (len(shape) - len(base))
    size = int(np.prod(shape)) if shape else 0
    if size < max(min_size, axes_size) or not shape:
        return _norm(base)
    used = _axes_in_spec(P(*base))
    if any(a in used for a in axes):
        return _norm(base)  # already sharded over the zero axes (e.g. via TP rules)
    candidates = sorted(range(len(shape)), key=lambda i: -shape[i])
    for dim in candidates:
        if base[dim] is not None:
            continue
        if shape[dim] % axes_size == 0:
            new = list(base)
            new[dim] = axes if len(axes) > 1 else axes[0]
            return _norm(new)
    return _norm(base)


def plan_param_specs(param_shapes, config, topo, tp_rules=None):
    """PartitionSpec pytree for the (fp32 master) parameters."""
    stage = config.zero_config.stage
    axes = zero_axes_for(topo)
    axes_size = int(np.prod([topo.axis_size(a) for a in axes]))
    threshold = config.zero_config.stage3_param_persistence_threshold
    rules = tp_rules or []

    def leaf_spec(path, leaf):
        path_names = tuple(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
        base = fit_spec(prune_spec(match_partition_rule(path_names, rules), topo), tuple(leaf.shape), topo)
        if stage == 3 and axes_size > 1:
            return shard_leaf_spec(tuple(leaf.shape), base, axes, axes_size, min_size=threshold)
        return base if base is not None else P()

    return jax.tree_util.tree_map_with_path(leaf_spec, param_shapes)


def plan_grad_specs(param_shapes, param_specs, config, topo):
    """Gradient (accumulation buffer) specs: sharded from stage 2 up."""
    stage = config.zero_config.stage
    axes = zero_axes_for(topo)
    axes_size = int(np.prod([topo.axis_size(a) for a in axes]))
    if stage >= 2 and axes_size > 1:
        return jax.tree_util.tree_map(
            lambda leaf, spec: shard_leaf_spec(tuple(leaf.shape), spec, axes, axes_size),
            param_shapes, param_specs)
    return param_specs


def plan_opt_state_specs(opt, param_shapes, param_specs, config, topo):
    """Optimizer-state specs: every state subtree shaped like the params is
    sharded over the ZeRO axes from stage 1 up (the partitioned optimizer
    states of ``stage_1_and_2.py``); scalars (step counts, hyperparams)
    stay replicated."""
    stage = config.zero_config.stage
    axes = zero_axes_for(topo)
    axes_size = int(np.prod([topo.axis_size(a) for a in axes]))
    opt_state_shapes = jax.eval_shape(opt.init, param_shapes)

    if stage >= 1 and axes_size > 1:
        sharded_specs = jax.tree_util.tree_map(
            lambda leaf, spec: shard_leaf_spec(tuple(leaf.shape), spec, axes, axes_size),
            param_shapes, param_specs)
    else:
        sharded_specs = param_specs

    params_treedef = jax.tree_util.tree_structure(param_shapes)
    param_leaf_shapes = [tuple(l.shape) for l in jax.tree_util.tree_leaves(param_shapes)]

    def looks_like_params(node) -> bool:
        try:
            if jax.tree_util.tree_structure(node) != params_treedef:
                return False
            leaves = jax.tree_util.tree_leaves(node)
            return [tuple(l.shape) for l in leaves] == param_leaf_shapes
        except Exception:
            return False

    def rec(node):
        if looks_like_params(node):
            return sharded_specs
        if isinstance(node, (list, tuple)):
            mapped = [rec(c) for c in node]
            if hasattr(node, "_fields"):  # namedtuple (optax states)
                return type(node)(*mapped)
            return type(node)(mapped)
        if isinstance(node, dict):
            return {k: rec(v) for k, v in node.items()}
        # leaf (ShapeDtypeStruct / scalar state)
        return P()

    return rec(opt_state_shapes), opt_state_shapes


def specs_to_shardings(specs, topo):
    return jax.tree_util.tree_map(lambda s: NamedSharding(topo.mesh, s), specs,
                                  is_leaf=lambda x: isinstance(x, P))


def batch_specs(batch, topo, seq_axis_for_dim1: bool = False):
    """Batch leaves shard dim 0 over the batch axes (and optionally dim 1
    over seq/context axes for sequence parallelism)."""
    baxes = topo.batch_axes

    def leaf(x):
        nd = getattr(x, "ndim", 0)
        if nd == 0:
            return P()
        entries = [baxes if len(baxes) > 1 else baxes[0]]
        if nd >= 2 and seq_axis_for_dim1:
            sp = tuple(a for a in ("seq", "context") if topo.axis_size(a) > 1)
            entries.append(sp if len(sp) > 1 else (sp[0] if sp else None))
        return P(*entries)

    return jax.tree_util.tree_map(leaf, batch)
