"""ZeRO++ : quantized-weight gather (qwZ), hierarchical secondary
partition (hpZ) and quantized gradient reduction (qgZ).

Parity: reference ZeRO++ (``zero/config.py:264-280`` knobs;
``partition_parameters.py:728`` CUDAQuantizer weight allgather;
``runtime/comm/coalesced_collectives.py:81`` qgZ all-to-all;
``groups.py:517`` hpZ secondary groups). The reference bolts these onto
the grad-hook machinery; here they live in ONE manual-SPMD step function
(``shard_map`` over the data/fsdp axes) that makes every ZeRO collective
explicit so its wire format can be chosen:

- params are all-gathered leaf-by-leaf over ``fsdp`` — int8 + per-group
  scales when ``zero_quantized_weights`` (qwZ), bf16 otherwise;
- with ``zero_hpz_partition_size=k``, the gathered weights are re-sliced
  into a *secondary* shard over the k-device intra-node group and saved
  for the backward remat, so the recompute regathers over intra-node ICI
  only (``jax.checkpoint`` policy + ``axis_index_groups``) — hpZ;
- gradients are reduced with int8 all-to-all when
  ``zero_quantized_gradients`` (qgZ), else a plain psum, then sliced to
  this device's shard (stage>=2 reduce-scatter semantics).

The manual path requires the model axes (tensor/pipe/seq/expert) to be
trivial — ZeRO++'s own setting. The engine falls back to the GSPMD path
otherwise.
"""

from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax import shard_map
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ...utils.logging import logger
from ..comm.compressed import all_to_all_quant_reduce

_GROUP = 2048  # elements per quantization scale


def zeropp_requested(config) -> bool:
    z = config.zero_config
    return bool(z.zero_quantized_weights or z.zero_quantized_gradients or z.zero_hpz_partition_size > 1)


def zeropp_applicable(config, topo) -> Tuple[bool, str]:
    z = config.zero_config
    if not zeropp_requested(config):
        return False, "no ZeRO++ feature enabled"
    for axis in ("tensor", "pipe", "seq", "context", "expert"):
        if topo.axis_size(axis) > 1:
            return False, f"ZeRO++ manual path needs axis {axis}=1 (got {topo.axis_size(axis)})"
    if topo.axis_size("fsdp") <= 1:
        return False, "ZeRO++ needs an fsdp axis > 1"
    if z.stage != 3:
        return False, f"ZeRO++ manual path expects stage 3 (got {z.stage})"
    return True, ""


def _spec_fsdp_dim(spec: Optional[P]) -> int:
    """Dim index sharded over 'fsdp' in a param spec, -1 if unsharded
    (-1, not None: None leaves disappear from pytrees)."""
    if spec is None:
        return -1
    for i, entry in enumerate(spec):
        names = entry if isinstance(entry, (tuple, list)) else (entry,)
        if "fsdp" in [n for n in names if n]:
            return i
    return -1


def _quant_int8(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Group-wise int8 quantization of a flat view; returns (q, scales).
    Wire format shared with the qgZ collective (one int8 scheme repo-wide)."""
    from ..comm.compressed import _quantize_int8

    n = x.size
    g = min(_GROUP, n)
    pad = (-n) % g
    flat = jnp.pad(x.reshape(-1), (0, pad)) if pad else x.reshape(-1)
    return _quantize_int8(flat.reshape(-1, g), axis=1)


def _dequant_int8(q: jnp.ndarray, scale: jnp.ndarray, shape, size: int, dtype) -> jnp.ndarray:
    flat = (q.astype(jnp.float32) * scale).reshape(-1)[:size]
    return flat.reshape(shape).astype(dtype)


def _gather_leaf(local: jnp.ndarray, dim: int, dtype, qwz: bool, qgz: bool) -> jnp.ndarray:
    """Allgather one param leaf over 'fsdp'; the transpose is the ZeRO
    gradient reduce-scatter, so this one primitive carries both ZeRO++
    wire formats: qwZ = int8 forward gather, qgZ = int8 backward
    reduce-scatter (all-to-all quantized, ``quant_reduce.cu`` analogue)."""

    @jax.custom_vjp
    def gather(x):
        if not qwz:
            return jax.lax.all_gather(x.astype(dtype), "fsdp", axis=dim, tiled=True)
        q, scale = _quant_int8(x.astype(jnp.float32))
        q_g = jax.lax.all_gather(q, "fsdp")        # (k, rows, GROUP) int8 wire
        s_g = jax.lax.all_gather(scale, "fsdp")    # (k, rows, 1)
        k = q_g.shape[0]
        shards = [_dequant_int8(q_g[i], s_g[i], x.shape, x.size, dtype) for i in range(k)]
        return jnp.concatenate(shards, axis=dim)

    def fwd(x):
        return gather(x), (x.shape[dim],)

    def bwd(res, g):
        (shard_len,) = res
        idx = jax.lax.axis_index("fsdp")
        g = g.astype(jnp.float32)
        if qgz:
            k = jax.lax.axis_size("fsdp")
            n = g.size
            pad = (-n) % k
            flat = jnp.pad(g.reshape(-1), (0, pad)) if pad else g.reshape(-1)
            # quant_reduce returns the mean; the gather transpose is a SUM
            g_sum = (all_to_all_quant_reduce(flat, "fsdp") * k)[:n].reshape(g.shape)
        else:
            g_sum = jax.lax.psum(g, "fsdp")
        start = [idx * shard_len if d == dim else 0 for d in range(g.ndim)]
        sizes = [shard_len if d == dim else g.shape[d] for d in range(g.ndim)]
        return (jax.lax.dynamic_slice(g_sum, start, sizes),)

    gather.defvjp(fwd, bwd)
    return gather(local)


def _hpz_groups(fsdp_size: int, k: int):
    """Intra-node groups of size k over the fsdp axis ranks."""
    return [list(range(i, i + k)) for i in range(0, fsdp_size, k)]


def build_zeropp_fwd_bwd(loss_fn: Callable, param_specs, grad_specs, topo, config,
                         compute_dtype) -> Callable:
    """Manual-SPMD (fwd+bwd) step with explicit, compressible collectives.

    Returns ``fn(params32, batch, rng, scale) -> (raw_loss, grads)`` with
    the same contract as the engine's GSPMD ``fwd_bwd``. The shard_map is
    specialized (and cached) per batch pytree structure, using the same
    ``batch_specs`` planner as the GSPMD path (scalar leaves replicated).
    """
    z = config.zero_config
    qwz = z.zero_quantized_weights
    qgz = z.zero_quantized_gradients
    hpz_k = z.zero_hpz_partition_size
    fsdp = topo.axis_size("fsdp")
    data = topo.axis_size("data")
    if hpz_k > 1 and fsdp % hpz_k != 0:
        raise ValueError(f"zero_hpz_partition_size {hpz_k} must divide the fsdp axis size {fsdp}")

    is_spec = lambda x: isinstance(x, P) or x is None
    fsdp_dims = jax.tree_util.tree_map(_spec_fsdp_dim, param_specs, is_leaf=is_spec)
    logger.info(f"ZeRO++ manual step: qwZ={qwz} qgZ={qgz} hpZ={hpz_k} over fsdp={fsdp} data={data}")

    def gather_params(params_local):
        def leaf(local, dim):
            if dim < 0:  # unsharded (persistence threshold) leaf
                return local.astype(compute_dtype)
            return _gather_leaf(local, dim, compute_dtype, qwz, qgz)

        return jax.tree_util.tree_map(leaf, params_local, fsdp_dims)

    def hpz_resplit(full_tree):
        """Slice the gathered params into the intra-node secondary shard and
        mark it; backward remat regathers within the k-group only."""
        groups = _hpz_groups(fsdp, hpz_k)

        def leaf(full, dim):
            if dim < 0:
                return full
            if full.shape[dim] % hpz_k != 0:
                raise ValueError(f"hpZ: gathered dim {dim} of size {full.shape[dim]} (leaf shape {full.shape}) "
                                 f"is not divisible by zero_hpz_partition_size={hpz_k}")
            intra = jax.lax.axis_index("fsdp") % hpz_k
            shard_len = full.shape[dim] // hpz_k
            start = [intra * shard_len if d == dim else 0 for d in range(full.ndim)]
            sizes = [shard_len if d == dim else full.shape[d] for d in range(full.ndim)]
            secondary = checkpoint_name(jax.lax.dynamic_slice(full, start, sizes), "hpz_secondary")
            return jax.lax.all_gather(secondary, "fsdp", axis=dim, tiled=True, axis_index_groups=groups)

        return jax.tree_util.tree_map(leaf, full_tree, fsdp_dims)

    def reduce_grads(grads):
        """Finish the gradient reduction. Grads w.r.t. the *local* shards
        already carry the fsdp-sum (the gather transpose = reduce-scatter,
        quantized when qgZ); what remains is the data-axis average and the
        1/fsdp factor that turns the fsdp-sum into the global mean."""
        def leaf(g, dim):
            g = g.astype(jnp.float32)
            if dim < 0:
                # unsharded leaf: no gather happened, reduce over everything
                return jax.lax.pmean(g, ("data", "fsdp"))
            if data > 1:
                if qgz:
                    n = g.size
                    pad = (-n) % data
                    flat = jnp.pad(g.reshape(-1), (0, pad)) if pad else g.reshape(-1)
                    g = all_to_all_quant_reduce(flat, "data")[:n].reshape(g.shape)
                else:
                    g = jax.lax.pmean(g, "data")
            return g / fsdp

        return jax.tree_util.tree_map(leaf, grads, fsdp_dims)

    def local_step(params_local, batch_local, rng, scale):
        def scaled_loss(p_local):
            full = gather_params(p_local)
            if hpz_k > 1:
                full = hpz_resplit(full)
            loss = loss_fn(full, batch_local, rng)
            return (loss * scale).astype(jnp.float32), loss

        if hpz_k > 1:
            policy = jax.checkpoint_policies.save_only_these_names("hpz_secondary")
            scaled_loss = jax.checkpoint(scaled_loss, policy=policy)
        (scaled, raw_loss), grads = jax.value_and_grad(scaled_loss, has_aux=True)(params_local)
        grads = reduce_grads(grads)
        # each device's loss covers its batch shard; report the global mean
        loss_avg = jax.lax.pmean(raw_loss, ("data", "fsdp"))
        return loss_avg, grads

    # local grads have exactly the PARAM layout: fsdp shards for sharded
    # leaves, replicated for persistence-threshold leaves (grad_specs may
    # shard the latter further — the engine reshards on first use)
    from .partition import batch_specs as plan_batch_specs

    cache: Dict[Any, Callable] = {}

    def stepped(params32, batch, rng, scale):
        treedef = jax.tree_util.tree_structure(batch)
        if treedef not in cache:
            bspecs = plan_batch_specs(batch, topo)
            cache[treedef] = jax.jit(shard_map(
                local_step, mesh=topo.mesh,
                in_specs=(param_specs, bspecs, P(), P()),
                out_specs=(P(), param_specs),
                check_vma=False))
        return cache[treedef](params32, batch, rng, scale)

    return stepped
