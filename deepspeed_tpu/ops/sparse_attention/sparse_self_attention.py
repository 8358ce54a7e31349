"""Block-sparse attention (Pallas TPU kernel + XLA fallback).

Parity: reference ``deepspeed/ops/sparse_attention/`` — Triton block-
sparse ``MatMul``/``Softmax`` composed by ``SparseSelfAttention``. The
TPU design is a splash-attention-style kernel: the static block layout
(``sparsity_config.py``) compiles into per-(head, q-block) active key-
block index lists; the kernel runs the flash online-softmax loop over
ONLY those blocks, so compute and HBM traffic scale with layout density,
not seq^2. Forward + backward (dq and dkv passes) are Pallas kernels
stitched with ``custom_vjp``; the dkv pass uses the transposed lists
(active q-blocks per key block).
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..registry import pallas_available
from .sparsity_config import SparsityConfig

NEG_INF = -1e30
LANES = 128


# ----------------------------------------------------------------------
# static layout -> active block lists
# ----------------------------------------------------------------------
def _active_lists(layout: np.ndarray, causal: bool):
    """(kidx, qidx) padded active-block index arrays, -1 padded.

    kidx[h, i]: key blocks query block i attends; qidx[h, j]: query
    blocks that attend key block j (for the dkv pass)."""
    H, nq, nk = layout.shape
    lay = layout.copy()
    if causal:
        tri = np.tril(np.ones((nq, nk), dtype=bool))
        lay &= tri[None]
    a_k = max(1, int(lay.sum(axis=2).max()))
    a_q = max(1, int(lay.sum(axis=1).max()))
    kidx = np.full((H, nq, a_k), -1, np.int32)
    qidx = np.full((H, nk, a_q), -1, np.int32)
    for h in range(H):
        for i in range(nq):
            js = np.nonzero(lay[h, i])[0]
            kidx[h, i, :len(js)] = js
        for j in range(nk):
            is_ = np.nonzero(lay[h, :, j])[0]
            qidx[h, j, :len(is_)] = is_
    return kidx, qidx


# ----------------------------------------------------------------------
# kernels
# ----------------------------------------------------------------------
def _sp_fwd_kernel(q_ref, k_ref, v_ref, kidx_ref, o_ref, lse_ref, *, blk: int, n_active: int, scale: float,
                   causal: bool, H: int):
    qi = pl.program_id(1)
    h = pl.program_id(0) % H
    q = q_ref[0]  # (blk, D)
    D = q.shape[-1]

    def body(t, carry):
        acc, m, l = carry
        j = kidx_ref[h, qi, t]
        valid = j >= 0
        jc = jnp.maximum(j, 0)
        k = k_ref[0, pl.dslice(jc * blk, blk), :]
        v = v_ref[0, pl.dslice(jc * blk, blk), :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * scale
        if causal:
            rows = qi * blk + jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 0)
            cols = jc * blk + jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 1)
            s = jnp.where(cols <= rows, s, NEG_INF)
        s = jnp.where(valid, s, NEG_INF)
        bmax = jnp.max(s, axis=-1)
        new_m = jnp.maximum(m, bmax)
        p = jnp.exp(s - new_m[:, None])
        p = jnp.where(s <= NEG_INF, 0.0, p)
        corr = jnp.exp(m - new_m)
        new_l = l * corr + jnp.sum(p, axis=-1)
        new_acc = acc * corr[:, None] + jax.lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                                                           preferred_element_type=jnp.float32)
        return new_acc, new_m, new_l

    acc0 = jnp.zeros((q.shape[0], D), jnp.float32)
    m0 = jnp.full((q.shape[0],), NEG_INF, jnp.float32)
    l0 = jnp.zeros((q.shape[0],), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, n_active, body, (acc0, m0, l0))
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc / l_safe[:, None]).astype(o_ref.dtype)
    lse = jnp.where(l == 0.0, NEG_INF, m + jnp.log(l_safe)).astype(jnp.float32)
    lse_ref[0] = jax.lax.broadcast_in_dim(lse, (lse.shape[0], LANES), (0,))


def _sp_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, kidx_ref, dq_ref, *, blk, n_active, scale,
                  causal, H):
    qi = pl.program_id(1)
    h = pl.program_id(0) % H
    q = q_ref[0]
    do = do_ref[0]
    lse = lse_ref[0, :, 0]
    delta = delta_ref[0, :, 0]
    D = q.shape[-1]

    def body(t, dq):
        j = kidx_ref[h, qi, t]
        valid = j >= 0
        jc = jnp.maximum(j, 0)
        k = k_ref[0, pl.dslice(jc * blk, blk), :]
        v = v_ref[0, pl.dslice(jc * blk, blk), :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * scale
        if causal:
            rows = qi * blk + jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 0)
            cols = jc * blk + jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 1)
            s = jnp.where(cols <= rows, s, NEG_INF)
        s = jnp.where(valid, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])
        p = jnp.where(s <= NEG_INF, 0.0, p)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = (p * (dp - delta[:, None]) * scale).astype(k.dtype)
        return dq + jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(0, n_active, body, jnp.zeros((q.shape[0], D), jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _sp_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qidx_ref, dk_ref, dv_ref, *, blk, n_active,
                   scale, causal, H):
    kj = pl.program_id(1)
    h = pl.program_id(0) % H
    k = k_ref[0]
    v = v_ref[0]
    D = k.shape[-1]

    def body(t, carry):
        dk, dv = carry
        i = qidx_ref[h, kj, t]
        valid = i >= 0
        ic = jnp.maximum(i, 0)
        q = q_ref[0, pl.dslice(ic * blk, blk), :]
        do = do_ref[0, pl.dslice(ic * blk, blk), :]
        lse = lse_ref[0, pl.dslice(ic * blk, blk), 0]
        delta = delta_ref[0, pl.dslice(ic * blk, blk), 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * scale
        if causal:
            rows = ic * blk + jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 0)
            cols = kj * blk + jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 1)
            s = jnp.where(cols <= rows, s, NEG_INF)
        s = jnp.where(valid, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])
        p = jnp.where(s <= NEG_INF, 0.0, p)
        pc = p.astype(do.dtype)
        dv = dv + jax.lax.dot_general(pc, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = (p * (dp - delta[:, None]) * scale).astype(q.dtype)
        dk = dk + jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        return dk, dv

    dk0 = jnp.zeros((k.shape[0], D), jnp.float32)
    dv0 = jnp.zeros((k.shape[0], D), jnp.float32)
    dk, dv = jax.lax.fori_loop(0, n_active, body, (dk0, dv0))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


# ----------------------------------------------------------------------
# pallas_call plumbing ((B*H, S, D) layout like flash_attention)
# ----------------------------------------------------------------------
def _idx_spec(shape):
    # the whole active-list table rides in SMEM un-blocked (kernels read one
    # scalar per fori_loop step, indexed by program ids). Real TPU lowering
    # applies the (8, 128) tiling rule to every spec WITH a block shape —
    # even in SMEM — so a (1, 1, A) block is rejected; only full-array
    # scalar-memory specs are exempt.
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _sp_fwd(q, k, v, kidx, H, blk, scale, causal, interpret):
    BH, S, D = q.shape
    nq, A = kidx.shape[1], kidx.shape[2]
    kernel = functools.partial(_sp_fwd_kernel, blk=blk, n_active=A, scale=scale, causal=causal, H=H)
    o, lse = pl.pallas_call(
        kernel,
        grid=(BH, nq),
        in_specs=[
            pl.BlockSpec((1, blk, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, S, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, S, D), lambda b, i: (b, 0, 0)),
            _idx_spec(kidx.shape),
        ],
        out_specs=[
            pl.BlockSpec((1, blk, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, blk, LANES), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, D), q.dtype),
            jax.ShapeDtypeStruct((BH, S, LANES), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, kidx)
    return o, lse


def _sp_bwd(q, k, v, o, lse, do, kidx, qidx, H, blk, scale, causal, interpret):
    BH, S, D = q.shape
    nq, A = kidx.shape[1], kidx.shape[2]
    nk, Aq = qidx.shape[1], qidx.shape[2]
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(delta[..., None], (BH, S, LANES))

    dq = pl.pallas_call(
        functools.partial(_sp_dq_kernel, blk=blk, n_active=A, scale=scale, causal=causal, H=H),
        grid=(BH, nq),
        in_specs=[
            pl.BlockSpec((1, blk, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, S, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, S, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, blk, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, blk, LANES), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, blk, LANES), lambda b, i: (b, i, 0)),
            _idx_spec(kidx.shape),
        ],
        out_specs=pl.BlockSpec((1, blk, D), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q.dtype),
        interpret=interpret,
    )(q, k, v, do, lse, delta, kidx)

    dk, dv = pl.pallas_call(
        functools.partial(_sp_dkv_kernel, blk=blk, n_active=Aq, scale=scale, causal=causal, H=H),
        grid=(BH, nk),
        in_specs=[
            pl.BlockSpec((1, S, D), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, blk, D), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, blk, D), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, S, D), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, S, LANES), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, S, LANES), lambda b, j: (b, 0, 0)),
            _idx_spec(qidx.shape),
        ],
        out_specs=[
            pl.BlockSpec((1, blk, D), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, blk, D), lambda b, j: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, D), k.dtype),
            jax.ShapeDtypeStruct((BH, S, D), v.dtype),
        ],
        interpret=interpret,
    )(q, k, v, do, lse, delta, qidx)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _sparse(q, k, v, kidx, qidx, H, blk, scale, causal, interpret):
    o, _ = _sparse_core(q, k, v, kidx, H, blk, scale, causal, interpret)
    return o


def _sparse_core(q, k, v, kidx, H, blk, scale, causal, interpret):
    B, S, H_, D = q.shape
    to_bh = lambda x: jnp.transpose(x, (0, 2, 1, 3)).reshape(B * H_, S, D)
    o, lse = _sp_fwd(to_bh(q), to_bh(k), to_bh(v), kidx, H_, blk, scale, causal, interpret)
    return o.reshape(B, H_, S, D).transpose(0, 2, 1, 3), lse


def _sparse_vjp_fwd(q, k, v, kidx, qidx, H, blk, scale, causal, interpret):
    o, lse = _sparse_core(q, k, v, kidx, H, blk, scale, causal, interpret)
    return o, (q, k, v, o, lse, kidx, qidx)


def _sparse_vjp_bwd(H, blk, scale, causal, interpret, res, do):
    q, k, v, o, lse, kidx, qidx = res
    B, S, H_, D = q.shape
    to_bh = lambda x: jnp.transpose(x, (0, 2, 1, 3)).reshape(B * H_, S, D)
    dq, dk, dv = _sp_bwd(to_bh(q), to_bh(k), to_bh(v), to_bh(o), lse, to_bh(do), kidx, qidx, H_, blk, scale,
                         causal, interpret)
    back = lambda x: x.reshape(B, H_, S, D).transpose(0, 2, 1, 3)
    return back(dq), back(dk), back(dv), None, None


_sparse.defvjp(_sparse_vjp_fwd, _sparse_vjp_bwd)


# ----------------------------------------------------------------------
# public API
# ----------------------------------------------------------------------
def layout_to_token_mask(layout: np.ndarray, block: int, causal: bool) -> np.ndarray:
    """Expand a block layout to a (H, S, S) token mask (oracle path)."""
    H, nq, nk = layout.shape
    mask = np.repeat(np.repeat(layout, block, axis=1), block, axis=2)
    if causal:
        S = nq * block
        mask = mask & np.tril(np.ones((S, S), dtype=bool))[None]
    return mask


def sparse_attention_xla(q, k, v, layout: np.ndarray, block: int, *, causal: bool = True,
                         scale: Optional[float] = None):
    """Dense-masked reference implementation (CPU path / numerics oracle)."""
    scale = scale if scale is not None else 1.0 / (q.shape[-1] ** 0.5)
    mask = jnp.asarray(layout_to_token_mask(layout, block, causal))  # (H, S, S)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    logits = jnp.where(mask[None], logits, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1)
    # fully-masked rows (possible in exotic layouts) -> zero output
    probs = jnp.where(jnp.any(mask[None], axis=-1, keepdims=True), probs, 0.0)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
    return out.astype(q.dtype)


def sparse_attention(q, k, v, config: SparsityConfig, *, causal: bool = True, scale: Optional[float] = None,
                     interpret: Optional[bool] = None):
    """Block-sparse attention per a :class:`SparsityConfig` layout.

    q/k/v: (B, S, H, D); the layout block is ``config.block``. GQA is
    handled by expanding KV heads (as in flash_attention)."""
    B, S, H, D = q.shape
    if config.num_heads not in (1, H):
        raise ValueError(f"config.num_heads {config.num_heads} != attention heads {H}")
    n_rep = H // k.shape[2]
    if n_rep > 1:
        b, s, h, d = k.shape
        k = jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(b, s, H, d)
        v = jnp.broadcast_to(v[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(b, s, H, d)
    layout = config.make_layout(S)
    if layout.shape[0] == 1 and H > 1:
        layout = np.broadcast_to(layout, (H,) + layout.shape[1:])
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    if interpret is None:
        interpret = not pallas_available()
    kidx, qidx = _active_lists(layout, causal)
    return _sparse(q, k, v, jnp.asarray(kidx), jnp.asarray(qidx), H, config.block, scale, causal, interpret)


class SparseSelfAttention:
    """Reference ``sparse_self_attention.py SparseSelfAttention`` — holds a
    sparsity config, applies block-sparse attention to (B, S, H, D) qkv."""

    def __init__(self, sparsity_config: SparsityConfig, causal: bool = True, scale: Optional[float] = None):
        self.sparsity_config = sparsity_config
        self.causal = causal
        self.scale = scale

    def __call__(self, q, k, v):
        return sparse_attention(q, k, v, self.sparsity_config, causal=self.causal, scale=self.scale)
