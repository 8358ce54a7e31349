"""Ring attention — blockwise context parallelism over ICI.

SUPERSET of the reference: DeepSpeed 0.14.3 ships only Ulysses all-to-all
sequence parallelism (verified in SURVEY §2.3 — no ring/blockwise CP
in-tree). On TPU, a ring over the ``context`` mesh axis maps directly onto
ICI neighbor links (``lax.ppermute``), letting sequence length scale past
what one chip's KV fits, with communication overlapped against blockwise
attention compute.

Algorithm: flash-style online softmax across KV blocks; each of the P
members starts with its own (B, S/P, H, D) shard and rotates KV around the
ring P times. Causality is enforced at block granularity (full block,
diagonal block = triangular, future block = skipped via masking).
"""

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

NEG_INF = -1e30


def ring_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, axis_name: str = "context",
                   causal: bool = True, scale: Optional[float] = None) -> jnp.ndarray:
    """Call inside shard_map with the sequence dim sharded over ``axis_name``.

    q, k, v: (B, S/P, H, D) local shards (KV may carry fewer heads — GQA),
    sequence order == axis index order. Returns the local (B, S/P, H, D)
    attention output, numerically matching full (unsharded) softmax
    attention.

    GQA stays collapsed through the ring: the rotating KV shards keep
    their (B, C, KVH, D) shape and q is grouped as (KVH, n_rep) instead —
    at 8:1 grouping that is 8x less ppermute traffic per hop, which is
    the cost this op exists to hide.
    """
    size = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    KVH = k.shape[2]
    n_rep = q.shape[2] // KVH

    B, C, H, D = q.shape
    scale = scale if scale is not None else 1.0 / (D**0.5)
    # group q heads by their KV head: (B, C, KVH, n_rep, D)
    qf = (q.astype(jnp.float32) * scale).reshape(B, C, KVH, n_rep, D)

    perm = [(i, (i + 1) % size) for i in range(size)]

    # per-(B,KVH,n_rep,C) running max / denom, fp32 accumulate.
    # the carry must be device-varying over the ring axis for shard_map
    def _vary(x):
        try:
            return lax.pcast(x, (axis_name,), to="varying")
        except (AttributeError, TypeError):
            return lax.pvary(x, (axis_name,))

    m0 = _vary(jnp.full((B, KVH, n_rep, C), NEG_INF, jnp.float32))
    l0 = _vary(jnp.zeros((B, KVH, n_rep, C), jnp.float32))
    o0 = _vary(jnp.zeros((B, C, KVH, n_rep, D), jnp.float32))

    # local (diagonal-relative) causal structure within a block
    qi = lax.broadcasted_iota(jnp.int32, (C, C), 0)
    ki = lax.broadcasted_iota(jnp.int32, (C, C), 1)
    tri = ki <= qi  # (C, C)

    def body(i, carry):
        o, m, l, k_cur, v_cur = carry
        kb = (my - i) % size  # block id of the kv we currently hold
        logits = jnp.einsum("bqhrd,bkhd->bhrqk", qf, k_cur.astype(jnp.float32))
        if causal:
            # kb < my: attend fully; kb == my: lower-triangular; kb > my: skip
            block_mask = jnp.where(kb < my, jnp.ones((C, C), bool),
                                   jnp.where(kb == my, tri, jnp.zeros((C, C), bool)))
            logits = jnp.where(block_mask[None, None, None], logits, NEG_INF)
        bmax = jnp.max(logits, axis=-1)
        new_m = jnp.maximum(m, bmax)
        m_safe = jnp.where(new_m <= NEG_INF, 0.0, new_m)
        p = jnp.exp(logits - m_safe[..., None])
        p = jnp.where(logits <= NEG_INF, 0.0, p)
        corr = jnp.exp(jnp.clip(m - m_safe, max=0.0))
        corr = jnp.where(m <= NEG_INF, 0.0, corr)
        new_l = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhrqk,bkhd->bqhrd", p, v_cur.astype(jnp.float32))
        new_o = o * jnp.transpose(corr, (0, 3, 1, 2))[..., None] + pv
        k_next = lax.ppermute(k_cur, axis_name, perm)
        v_next = lax.ppermute(v_cur, axis_name, perm)
        return new_o, new_m, new_l, k_next, v_next

    o, m, l, _, _ = lax.fori_loop(0, size, body, (o0, m0, l0, k, v))
    denom = jnp.transpose(jnp.where(l == 0.0, 1.0, l), (0, 3, 1, 2))[..., None]
    return (o / denom).reshape(B, C, H, D).astype(q.dtype)


def ring_sharded_attention(q, k, v, mesh, axis_name: str = "context", **kwargs):
    """Eager/jit wrapper for global arrays sharded (B, S@context, H, D)."""
    spec = P(None, axis_name, None, None)

    @partial(jax.shard_map, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    def fn(ql, kl, vl):
        return ring_attention(ql, kl, vl, axis_name=axis_name, **kwargs)

    return fn(q, k, v)
