"""Pallas flash attention (TPU).

Capability parity: the reference's fused attention kernels
(``csrc/transformer/inference/csrc/softmax.cu``, training softmax/
transform kernels in ``csrc/transformer``, blocked flash in
``inference/v2/kernels/ragged_ops/blocked_flash``). On TPU the win is the
same as on GPU: never materialize the (S, S) probability matrix in HBM —
blocked online softmax in VMEM feeding the MXU.

Forward and backward are both Pallas kernels, stitched with
``jax.custom_vjp``. Layout: inputs (B, S, H, D) are transposed to
(B, H, S, D); grid is (B*H, Sq/bq) for fwd/dq and (B*KVH, Sk/bk, n_rep)
for dkv. GQA is native: KV stays collapsed at (B, S, KVH, D) in HBM and
the kernels route each q head to its group's KV head by BlockSpec index
map — at llama-70B-class 8:1 grouping that is 8x less KV HBM traffic
than pre-expanding, and dk/dv accumulate across the group in-kernel
instead of materializing expanded cotangents.
"""

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P

from ...analysis import knobs
from ..registry import REGISTRY, pallas_available
from ._utils import block_that_divides, compiler_params as _compiler_params, on_mesh

NEG_INF = -1e30
LANES = 128  # min lane width for fp32 stores (canonical TPU l/m layout)

# Default blocks are large: the grid runs sequentially on the (single)
# tensor core, and every program pays the VPU online-softmax chain between
# short MXU ops — many tiny (128,128) programs are latency-bound, not
# FLOP-bound. (512, 512) keeps the fp32 score block at 1 MB of VMEM,
# amortizes the chain over 16x more MXU work, and stays causal-efficient
# at the block boundary. Overridable for autotuning.
DEFAULT_BQ = knobs.get_int("DS_TPU_FLASH_BQ")
DEFAULT_BK = knobs.get_int("DS_TPU_FLASH_BK")


_WARNED: set = set()


def _blk(seq: int, want: int) -> int:
    if want < 1:
        want = 512
    got = block_that_divides(seq, want)
    if got * 4 < min(want, seq) and (seq, want) not in _WARNED:
        # e.g. DS_TPU_FLASH_BQ=384 with seq 1024 halves down to 1 — a
        # per-row grid that is orders of magnitude slower than intended
        _WARNED.add((seq, want))
        from ...utils.logging import logger

        logger.warning(f"flash_attention: requested block {want} does not divide seq {seq}; "
                       f"degraded to {got} — pick a power-of-two block that divides the sequence")
    return got



def _scores(q, k, slope, row0, col0, bq, bk, scale, causal, has_alibi, window, btile=None):
    """(bq, bk) fp32 masked scores — the ONE definition of the mask/bias
    math; fwd and both bwd kernels recompute s through this so they can
    never drift apart. ``btile``: additive bias tile (evoformer pair/mask
    bias, reference DS4Sci_EvoformerAttention) — added before masking so
    masked entries stay exactly NEG_INF."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * scale
    cols = col0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    if has_alibi:  # shift-invariant ALiBi: slope * key_position
        s = s + slope * cols.astype(jnp.float32)
    if btile is not None:
        s = s + btile.astype(jnp.float32)
    if causal:  # window implies causal (non-causal windows fall back to XLA)
        rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        mask = cols <= rows
        if window > 0:
            mask = mask & (cols > rows - window)
        s = jnp.where(mask, s, NEG_INF)
    return s


# ----------------------------------------------------------------------
# forward
# ----------------------------------------------------------------------
def _bias_bh_fn(bias_meta, H: int):
    """b = batch*H + head -> collapsed bias leading index.

    ``bias_meta`` = (Bb, Hb, Sqb, repeat): the bias's own batch/head/row
    sizes (each 1 or the full size) plus the lead-repeat factor (q batch
    = Bb * repeat — e.g. evoformer MSA rows sharing one pair bias).
    """
    Bb, Hb, Sqb, repeat = bias_meta

    def bias_bh(b):
        batch = b // H
        head = b % H
        bb_idx = 0 if Bb == 1 else batch // repeat
        h_idx = 0 if Hb == 1 else head
        return bb_idx * Hb + h_idx

    return bias_bh


def _fwd_kernel(q_ref, k_ref, v_ref, slopes_ref, bias_ref, o_ref, lse_ref, *, bq: int, bk: int, seq_q: int,
                seq_k: int, scale: float, causal: bool, has_alibi: bool, window: int, has_bias: bool):
    qi = pl.program_id(1)
    q = q_ref[0]  # (bq, D) input dtype — MXU runs bf16 operands w/ fp32 accumulation
    D = q.shape[-1]
    slope = slopes_ref[0, 0, 0]  # per-head ALiBi slope (0 when disabled)

    # queries align to the END of the kv sequence (matches attention_xla)
    offset = seq_k - seq_q
    nk = seq_k // bk
    j0 = 0
    if causal:
        # last kv block that any row of this q block can see (qi is traced)
        nk = jnp.minimum(pl.cdiv(offset + (qi + 1) * bq, bk), seq_k // bk)
    if window > 0:
        # first kv block any row of this q block can see: row r attends
        # cols in (r - window, r]; the block's min row is offset + qi*bq
        j0 = jnp.maximum(offset + qi * bq - window + 1, 0) // bk

    def body(j, carry):
        acc, m, l = carry
        k = k_ref[0, pl.dslice(j * bk, bk), :]  # (bk, D)
        v = v_ref[0, pl.dslice(j * bk, bk), :]
        # sq-broadcast biases carry one row that broadcasts over the block
        btile = bias_ref[0, :, pl.dslice(j * bk, bk)] if has_bias else None
        s = _scores(q, k, slope, offset + qi * bq, j * bk, bq, bk, scale, causal, has_alibi, window, btile)
        bmax = jnp.max(s, axis=-1)
        new_m = jnp.maximum(m, bmax)
        p = jnp.exp(s - new_m[:, None])
        # fully-masked rows (possible when seq_q > seq_k) have new_m == NEG_INF
        # and would get p == exp(0) == 1 on masked columns; keep bwd-consistent
        p = jnp.where(s <= NEG_INF, 0.0, p)
        corr = jnp.exp(m - new_m)
        new_l = l * corr + jnp.sum(p, axis=-1)
        new_acc = acc * corr[:, None] + jax.lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                                                           preferred_element_type=jnp.float32)
        return new_acc, new_m, new_l

    acc0 = jnp.zeros((bq, D), jnp.float32)
    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    acc, m, l = jax.lax.fori_loop(j0, nk, body, (acc0, m0, l0))
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc / l_safe[:, None]).astype(o_ref.dtype)
    lse = (m + jnp.log(l_safe)).astype(jnp.float32)
    lse_ref[0] = jax.lax.broadcast_in_dim(lse, (lse.shape[0], LANES), (0,))


def _kv_of_fn(H: int, KVH: int):
    """q-head program index -> KV head index (GQA stays collapsed in HBM:
    the index map routes each q head to its group's KV head — no
    broadcast/materialize of the expanded (B, S, H, D) KV)."""
    n_rep = H // KVH

    def kv_of(b):
        return (b // H) * KVH + (b % H) // n_rep

    return kv_of


def _flash_fwd(q, k, v, slopes, bias, scale: float, causal: bool, interpret: bool, has_alibi: bool,
               window: int, bias_meta, H: int, KVH: int):
    BH, Sq, D = q.shape
    Sk = k.shape[1]
    has_bias = bias_meta is not None
    kv_of = _kv_of_fn(H, KVH)
    bq, bk = _blk(Sq, DEFAULT_BQ), _blk(Sk, DEFAULT_BK)
    kernel = functools.partial(_fwd_kernel, bq=bq, bk=bk, seq_q=Sq, seq_k=Sk, scale=scale, causal=causal,
                               has_alibi=has_alibi, window=window, has_bias=has_bias)
    # without bias a (1,1,LANES) dummy rides along so the kernel arity is
    # fixed; with bias, broadcast dims stay COLLAPSED in HBM and the index
    # map routes every program to its shared block
    if has_bias:
        bias_bh = _bias_bh_fn(bias_meta, H)
        sq_rows = 1 if bias_meta[2] == 1 else bq
        bias_spec = pl.BlockSpec((1, sq_rows, Sk),
                                 lambda b, i: (bias_bh(b), 0 if sq_rows == 1 else i, 0))
    else:
        bias_spec = pl.BlockSpec((1, 1, LANES), lambda b, i: (0, 0, 0))
    o, lse = pl.pallas_call(
        kernel,
        grid=(BH, Sq // bq),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, Sk, D), lambda b, i: (kv_of(b), 0, 0)),
            pl.BlockSpec((1, Sk, D), lambda b, i: (kv_of(b), 0, 0)),
            pl.BlockSpec((1, 1, LANES), lambda b, i: (b, 0, 0)),
            bias_spec,
        ],
        out_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, bq, LANES), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Sq, D), q.dtype),
            jax.ShapeDtypeStruct((BH, Sq, LANES), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=_compiler_params("parallel", "arbitrary", interpret=interpret),
    )(q, k, v, slopes, bias)
    return o, lse


# ----------------------------------------------------------------------
# backward
# ----------------------------------------------------------------------
def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, slopes_ref, bias_ref, dq_ref, dbias_ref, *,
               bq, bk, seq_q, seq_k, scale, causal, has_alibi, window, has_bias):
    qi = pl.program_id(1)
    slope = slopes_ref[0, 0, 0]
    q = q_ref[0]
    do = do_ref[0]
    lse = lse_ref[0, :, 0]
    delta = delta_ref[0, :, 0]
    D = q.shape[-1]

    offset = seq_k - seq_q
    nk = seq_k // bk
    j0 = 0
    if causal:
        nk = jnp.minimum(pl.cdiv(offset + (qi + 1) * bq, bk), nk)
    if window > 0:
        j0 = jnp.maximum(offset + qi * bq - window + 1, 0) // bk
    if has_bias:
        # blocks the loop skips contribute zero dbias; clear the whole row
        # band first so skipped tiles don't hold stale VMEM contents
        dbias_ref[0] = jnp.zeros_like(dbias_ref[0])

    def body(j, dq):
        k = k_ref[0, pl.dslice(j * bk, bk), :]
        v = v_ref[0, pl.dslice(j * bk, bk), :]
        btile = bias_ref[0, :, pl.dslice(j * bk, bk)] if has_bias else None
        s = _scores(q, k, slope, offset + qi * bq, j * bk, bq, bk, scale, causal, has_alibi, window, btile)
        p = jnp.exp(s - lse[:, None])
        p = jnp.where(s <= NEG_INF, 0.0, p)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)  # (bq, bk)
        dlogits = p * (dp - delta[:, None])
        if has_bias:  # dbias = dlogits (bias enters the logits additively, unscaled)
            dbias_ref[0, :, pl.dslice(j * bk, bk)] = dlogits.astype(dbias_ref.dtype)
        ds = (dlogits * scale).astype(k.dtype)
        return dq + jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(j0, nk, body, jnp.zeros((bq, D), jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _dq_kernel_collapsed(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, slopes_ref, bias_ref, dq_ref,
                         dbias_ref, *, bq, bk, seq_q, seq_k, scale, causal, has_alibi, window, sqb1: bool):
    """dq + ACCUMULATED dbias for a collapsed (broadcast) bias.

    Grid (n_bh, Sq//bq, n_rep) with the repeat dim innermost: every program
    sharing one bias row visits the same dbias block consecutively, so the
    block stays resident and read-modify-write accumulates — dbias never
    expands past the bias's own (collapsed) shape in HBM. First visit
    zeroes the block (``rep==0``, and ``qi==0`` too when rows broadcast).
    """
    qi = pl.program_id(1)
    rep = pl.program_id(2)
    slope = slopes_ref[0, 0, 0]
    q = q_ref[0]
    do = do_ref[0]
    lse = lse_ref[0, :, 0]
    delta = delta_ref[0, :, 0]
    D = q.shape[-1]

    first = jnp.logical_and(qi == 0, rep == 0) if sqb1 else (rep == 0)

    @pl.when(first)
    def _zero():
        dbias_ref[0] = jnp.zeros_like(dbias_ref[0])

    offset = seq_k - seq_q
    nk = seq_k // bk
    j0 = 0
    if causal:
        nk = jnp.minimum(pl.cdiv(offset + (qi + 1) * bq, bk), nk)
    if window > 0:
        j0 = jnp.maximum(offset + qi * bq - window + 1, 0) // bk

    def body(j, dq):
        k = k_ref[0, pl.dslice(j * bk, bk), :]
        v = v_ref[0, pl.dslice(j * bk, bk), :]
        btile = bias_ref[0, :, pl.dslice(j * bk, bk)]
        s = _scores(q, k, slope, offset + qi * bq, j * bk, bq, bk, scale, causal, has_alibi, window, btile)
        p = jnp.exp(s - lse[:, None])
        p = jnp.where(s <= NEG_INF, 0.0, p)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        dlogits = p * (dp - delta[:, None])
        contrib = jnp.sum(dlogits, axis=0, keepdims=True) if sqb1 else dlogits
        cur = dbias_ref[0, :, pl.dslice(j * bk, bk)]
        dbias_ref[0, :, pl.dslice(j * bk, bk)] = cur + contrib.astype(dbias_ref.dtype)
        ds = (dlogits * scale).astype(k.dtype)
        return dq + jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(j0, nk, body, jnp.zeros((bq, D), jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _dkv_accumulate(q_ref, k, v, do_ref, lse_ref, delta_ref, slope, btile_fn, kj, *,
                    bq, bk, seq_q, seq_k, scale, causal, has_alibi, window):
    """(bk, D) dk/dv for one kv block — the ONE definition of the dkv
    gradient algebra (visible-q-block bounds + ds formula), shared by the
    per-q-head and GQA-revisit kernels so they can never drift apart.
    ``btile_fn(i)`` returns the additive-bias tile for q block i (or None)."""
    D = k.shape[-1]
    offset = seq_k - seq_q
    nq = seq_q // bq
    start = 0
    if causal:
        # first q block that can see this kv block (row offset+r sees col c iff c <= offset+r)
        start = jnp.maximum(kj * bk - offset, 0) // bq
    nq_end = nq
    if window > 0:
        # last q block whose rows still see this kv block: row <= col + window - 1
        last_row = jnp.minimum((kj + 1) * bk - 1 + window - 1 - offset, seq_q - 1)
        nq_end = jnp.minimum(last_row // bq + 1, nq)

    def body(i, carry):
        dk, dv = carry
        q = q_ref[0, pl.dslice(i * bq, bq), :]
        do = do_ref[0, pl.dslice(i * bq, bq), :]
        lse = lse_ref[0, pl.dslice(i * bq, bq), 0]
        delta = delta_ref[0, pl.dslice(i * bq, bq), 0]
        s = _scores(q, k, slope, offset + i * bq, kj * bk, bq, bk, scale, causal, has_alibi, window,
                    btile_fn(i))
        p = jnp.exp(s - lse[:, None])
        p = jnp.where(s <= NEG_INF, 0.0, p)
        pc = p.astype(do.dtype)
        dv = dv + jax.lax.dot_general(pc, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = (p * (dp - delta[:, None]) * scale).astype(q.dtype)
        dk = dk + jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        return dk, dv

    dk0 = jnp.zeros((bk, D), jnp.float32)
    dv0 = jnp.zeros((bk, D), jnp.float32)
    return jax.lax.fori_loop(start, nq_end, body, (dk0, dv0))


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, slopes_ref, bias_ref, dk_ref, dv_ref, *,
                bq, bk, seq_q, seq_k, scale, causal, has_alibi, window, has_bias, sqb1: bool = False):
    kj = pl.program_id(1)

    def btile_fn(i):
        if not has_bias:
            return None
        return bias_ref[0, :, :] if sqb1 else bias_ref[0, pl.dslice(i * bq, bq), :]

    dk, dv = _dkv_accumulate(q_ref, k_ref[0], v_ref[0], do_ref, lse_ref, delta_ref, slopes_ref[0, 0, 0],
                             btile_fn, kj, bq=bq, bk=bk, seq_q=seq_q, seq_k=seq_k, scale=scale,
                             causal=causal, has_alibi=has_alibi, window=window)
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _dkv_kernel_gqa(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, slopes_ref, dk_ref, dv_ref, *,
                    bq, bk, seq_q, seq_k, scale, causal, has_alibi, window):
    """dk/dv with GQA collapsed: grid (B*KVH, Sk//bk, n_rep), the group
    dim INNERMOST so every program sharing a KV head revisits the same
    dk/dv block consecutively and accumulates in place (the same
    revisit pattern as ``_dq_kernel_collapsed``'s dbias). n_rep == 1 is
    plain MHA and degenerates to a single visit."""
    kj = pl.program_id(1)
    rep = pl.program_id(2)

    @pl.when(rep == 0)
    def _zero():
        dk_ref[0] = jnp.zeros_like(dk_ref[0])
        dv_ref[0] = jnp.zeros_like(dv_ref[0])

    dk, dv = _dkv_accumulate(q_ref, k_ref[0], v_ref[0], do_ref, lse_ref, delta_ref, slopes_ref[0, 0, 0],
                             lambda i: None, kj, bq=bq, bk=bk, seq_q=seq_q, seq_k=seq_k, scale=scale,
                             causal=causal, has_alibi=has_alibi, window=window)
    dk_ref[0] = dk_ref[0] + dk  # fp32 outputs: cross-group accumulation stays exact
    dv_ref[0] = dv_ref[0] + dv


def _flash_bwd(q, k, v, o, lse, do, slopes, bias, scale: float, causal: bool, interpret: bool,
               has_alibi: bool, window: int, bias_meta, H: int, KVH: int):
    BH, Sq, D = q.shape
    Sk = k.shape[1]
    has_bias = bias_meta is not None
    kv_of = _kv_of_fn(H, KVH)
    n_rep = H // KVH
    bq, bk = _blk(Sq, DEFAULT_BQ), _blk(Sk, DEFAULT_BK)
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)  # (BH, Sq)
    delta = jnp.broadcast_to(delta[..., None], (BH, Sq, LANES))

    if has_bias:
        Bb, Hb, Sqb, repeat = bias_meta
        bias_bh = _bias_bh_fn(bias_meta, H)
        sqb1 = Sqb == 1
        n_bh = Bb * Hb
        collapsed = n_bh < BH or sqb1
        sq_rows = 1 if sqb1 else bq
        bias_spec_q3 = pl.BlockSpec((1, sq_rows, Sk),
                                    lambda bh, i, rep: (bh, 0 if sqb1 else i, 0))
        bias_spec_q2 = pl.BlockSpec((1, sq_rows, Sk),
                                    lambda b, i: (bias_bh(b), 0 if sqb1 else i, 0))
        bias_spec_k = pl.BlockSpec((1, 1 if sqb1 else Sq, bk), lambda b, j: (bias_bh(b), 0, j))
        dbias_shape = (n_bh, 1 if sqb1 else Sq, Sk)
    else:
        collapsed = False
        bias_spec_q2 = pl.BlockSpec((1, 1, LANES), lambda b, i: (0, 0, 0))
        bias_spec_k = pl.BlockSpec((1, 1, LANES), lambda b, j: (0, 0, 0))
        dbias_shape = (1, 1, LANES)

    if not collapsed:
        # one dbias block per (b, i) program — plain tiled writes
        dbias_spec = (pl.BlockSpec((1, bq, Sk), lambda b, i: (b, i, 0)) if has_bias
                      else pl.BlockSpec((1, 1, LANES), lambda b, i: (0, 0, 0)))
        dq, dbias = pl.pallas_call(
            functools.partial(_dq_kernel, bq=bq, bk=bk, seq_q=Sq, seq_k=Sk, scale=scale, causal=causal,
                              has_alibi=has_alibi, window=window, has_bias=has_bias),
            grid=(BH, Sq // bq),
            in_specs=[
                pl.BlockSpec((1, bq, D), lambda b, i: (b, i, 0)),
                pl.BlockSpec((1, Sk, D), lambda b, i: (kv_of(b), 0, 0)),
                pl.BlockSpec((1, Sk, D), lambda b, i: (kv_of(b), 0, 0)),
                pl.BlockSpec((1, bq, D), lambda b, i: (b, i, 0)),
                pl.BlockSpec((1, bq, LANES), lambda b, i: (b, i, 0)),
                pl.BlockSpec((1, bq, LANES), lambda b, i: (b, i, 0)),
                pl.BlockSpec((1, 1, LANES), lambda b, i: (b, 0, 0)),
                bias_spec_q2,
            ],
            out_specs=[
                pl.BlockSpec((1, bq, D), lambda b, i: (b, i, 0)),
                dbias_spec,
            ],
            out_shape=[
                jax.ShapeDtypeStruct((BH, Sq, D), q.dtype),
                jax.ShapeDtypeStruct(dbias_shape, jnp.float32),
            ],
            interpret=interpret,
            compiler_params=_compiler_params("parallel", "arbitrary", interpret=interpret),
        )(q, k, v, do, lse, delta, slopes, bias)
    else:
        # broadcast bias: repeat dim innermost so every program sharing a
        # bias row revisits its dbias block consecutively and accumulates
        n_rep = BH // n_bh

        def q_b(bh, rep):
            if Bb == 1 and Hb == 1:
                return rep
            if Hb == 1:  # batch collapsed by `repeat`, heads all share
                return (bh * repeat + rep // H) * H + rep % H
            if Bb == 1:  # only heads distinct
                return rep * H + bh
            return ((bh // H) * repeat + rep) * H + bh % H

        dq, dbias = pl.pallas_call(
            functools.partial(_dq_kernel_collapsed, bq=bq, bk=bk, seq_q=Sq, seq_k=Sk, scale=scale,
                              causal=causal, has_alibi=has_alibi, window=window, sqb1=sqb1),
            grid=(n_bh, Sq // bq, n_rep),
            in_specs=[
                pl.BlockSpec((1, bq, D), lambda bh, i, rep: (q_b(bh, rep), i, 0)),
                pl.BlockSpec((1, Sk, D), lambda bh, i, rep: (q_b(bh, rep), 0, 0)),
                pl.BlockSpec((1, Sk, D), lambda bh, i, rep: (q_b(bh, rep), 0, 0)),
                pl.BlockSpec((1, bq, D), lambda bh, i, rep: (q_b(bh, rep), i, 0)),
                pl.BlockSpec((1, bq, LANES), lambda bh, i, rep: (q_b(bh, rep), i, 0)),
                pl.BlockSpec((1, bq, LANES), lambda bh, i, rep: (q_b(bh, rep), i, 0)),
                pl.BlockSpec((1, 1, LANES), lambda bh, i, rep: (q_b(bh, rep), 0, 0)),
                bias_spec_q3,
            ],
            out_specs=[
                pl.BlockSpec((1, bq, D), lambda bh, i, rep: (q_b(bh, rep), i, 0)),
                pl.BlockSpec((1, sq_rows, Sk), lambda bh, i, rep: (bh, 0 if sqb1 else i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((BH, Sq, D), q.dtype),
                jax.ShapeDtypeStruct(dbias_shape, jnp.float32),
            ],
            interpret=interpret,
            compiler_params=_compiler_params("parallel", "arbitrary", "arbitrary", interpret=interpret),
        )(q, k, v, do, lse, delta, slopes, bias)

    if has_bias:
        # bias path: KV arrives expanded (flash_attention falls back to
        # expansion when bias x GQA combine), so the per-q-head grid stands
        dk, dv = pl.pallas_call(
            functools.partial(_dkv_kernel, bq=bq, bk=bk, seq_q=Sq, seq_k=Sk, scale=scale, causal=causal,
                              has_alibi=has_alibi, window=window, has_bias=has_bias,
                              sqb1=bias_meta[2] == 1),
            grid=(BH, Sk // bk),
            in_specs=[
                pl.BlockSpec((1, Sq, D), lambda b, j: (b, 0, 0)),
                pl.BlockSpec((1, bk, D), lambda b, j: (b, j, 0)),
                pl.BlockSpec((1, bk, D), lambda b, j: (b, j, 0)),
                pl.BlockSpec((1, Sq, D), lambda b, j: (b, 0, 0)),
                pl.BlockSpec((1, Sq, LANES), lambda b, j: (b, 0, 0)),
                pl.BlockSpec((1, Sq, LANES), lambda b, j: (b, 0, 0)),
                pl.BlockSpec((1, 1, LANES), lambda b, j: (b, 0, 0)),
                bias_spec_k,
            ],
            out_specs=[
                pl.BlockSpec((1, bk, D), lambda b, j: (b, j, 0)),
                pl.BlockSpec((1, bk, D), lambda b, j: (b, j, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((BH, Sk, D), k.dtype),
                jax.ShapeDtypeStruct((BH, Sk, D), v.dtype),
            ],
            interpret=interpret,
            compiler_params=_compiler_params("parallel", "arbitrary", interpret=interpret),
        )(q, k, v, do, lse, delta, slopes, bias)
        return dq, dk, dv, dbias

    BKV = k.shape[0]  # B * KVH (collapsed GQA)

    def q_of(bkv, rep):
        return (bkv // KVH) * H + (bkv % KVH) * n_rep + rep

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel_gqa, bq=bq, bk=bk, seq_q=Sq, seq_k=Sk, scale=scale, causal=causal,
                          has_alibi=has_alibi, window=window),
        grid=(BKV, Sk // bk, n_rep),
        in_specs=[
            pl.BlockSpec((1, Sq, D), lambda b, j, r: (q_of(b, r), 0, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j, r: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j, r: (b, j, 0)),
            pl.BlockSpec((1, Sq, D), lambda b, j, r: (q_of(b, r), 0, 0)),
            pl.BlockSpec((1, Sq, LANES), lambda b, j, r: (q_of(b, r), 0, 0)),
            pl.BlockSpec((1, Sq, LANES), lambda b, j, r: (q_of(b, r), 0, 0)),
            pl.BlockSpec((1, 1, LANES), lambda b, j, r: (q_of(b, r), 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, D), lambda b, j, r: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j, r: (b, j, 0)),
        ],
        out_shape=[  # fp32: cross-group revisit accumulation stays exact
            jax.ShapeDtypeStruct((BKV, Sk, D), jnp.float32),
            jax.ShapeDtypeStruct((BKV, Sk, D), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=_compiler_params("parallel", "arbitrary", "arbitrary", interpret=interpret),
    )(q, k, v, do, lse, delta, slopes)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype), dbias


# ----------------------------------------------------------------------
# public op: (B, S, H, D) layout + GQA + custom_vjp
# ----------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10, 11, 12))
def _flash(q, k, v, slopes, bias, scale, causal, interpret, has_alibi, window, bias_meta, H, KVH):
    o, _ = _flash_core(q, k, v, slopes, bias, scale, causal, interpret, has_alibi, window, bias_meta, H, KVH)
    return o


def _bh_slopes(slopes, B, H):
    """(H,) per-head slopes -> (B*H, 1, LANES) per-program rows.

    3D on purpose: real TPU lowering requires the last two block dims to be
    divisible by (8, 128) or equal the array dims — a (1, LANES) block over
    a 2D (B*H, LANES) array is rejected (only interpret mode accepts it).
    With a leading program dim the (1, LANES) tail matches exactly."""
    flat = jnp.tile(jnp.asarray(slopes, jnp.float32), B)  # (B*H,)
    return jnp.broadcast_to(flat[:, None, None], (B * H, 1, LANES))


def _flash_core(q, k, v, slopes, bias, scale, causal, interpret, has_alibi, window, bias_meta, H, KVH):
    B, Sq, _, D = q.shape
    to_bh = lambda x: jnp.transpose(x, (0, 2, 1, 3)).reshape(B * x.shape[2], x.shape[1], D)
    o, lse = _flash_fwd(to_bh(q), to_bh(k), to_bh(v), _bh_slopes(slopes, B, H), bias,
                        scale, causal, interpret, has_alibi, window, bias_meta, H, KVH)
    o = o.reshape(B, H, Sq, D).transpose(0, 2, 1, 3)
    return o, lse


def _flash_vjp_fwd(q, k, v, slopes, bias, scale, causal, interpret, has_alibi, window, bias_meta, H, KVH):
    o, lse = _flash_core(q, k, v, slopes, bias, scale, causal, interpret, has_alibi, window, bias_meta, H, KVH)
    return o, (q, k, v, slopes, bias, o, lse)


def _flash_vjp_bwd(scale, causal, interpret, has_alibi, window, bias_meta, H, KVH, res, do):
    q, k, v, slopes, bias, o, lse = res
    B, Sq, _, D = q.shape
    Sk = k.shape[1]
    to_bh = lambda x: jnp.transpose(x, (0, 2, 1, 3)).reshape(B * x.shape[2], x.shape[1], D)
    dq, dk, dv, dbias = _flash_bwd(to_bh(q), to_bh(k), to_bh(v), to_bh(o), lse, to_bh(do),
                                   _bh_slopes(slopes, B, H), bias,
                                   scale, causal, interpret, has_alibi, window, bias_meta, H, KVH)
    back = lambda x, S, nh: x.reshape(B, nh, S, D).transpose(0, 2, 1, 3)
    # cotangent matches the (collapsed, flat) bias argument; the outer
    # 4D->flat reshape in flash_attention transposes automatically
    dbias_out = dbias.astype(bias.dtype) if bias_meta is not None else jnp.zeros_like(bias)
    return (back(dq, Sq, H), back(dk, Sk, KVH), back(dv, Sk, KVH), jnp.zeros_like(slopes), dbias_out)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(q, k, v, *, causal: bool = True, scale: Optional[float] = None, bias=None, segment_ids=None,
                    kv_len=None, window=None, alibi_slopes=None, interpret: bool = False,
                    bias_repeat: int = 1):
    """Drop-in for ``attention_xla`` on the fast path; handles ALiBi,
    causal sliding windows, and additive bias natively, and falls back to
    XLA for the rest (segments, padded kv, non-causal windows).

    ``bias``: additive logits bias broadcastable to ``(B, H, Sq, Sk)`` —
    the batch/head/row dims may each be 1 and stay COLLAPSED in HBM (the
    kernels route shared blocks by index map, and dbias accumulates in the
    collapsed shape — reference evoformer_attn reads its ``(B,1,1,1,K)``
    mask bias in place). ``bias_repeat``: the q batch is
    ``bias.shape[0] * bias_repeat`` (consecutive q-batch groups share one
    bias slice — evoformer MSA rows over one pair bias).
    """
    if segment_ids is not None or kv_len is not None or (
            alibi_slopes is not None and not causal) or (window is not None and not causal):
        from ..attention import attention_xla

        if bias is not None and bias_repeat != 1:
            bias = jnp.asarray(bias)
            while bias.ndim < 4:  # pad first so axis 0 is batch, not heads
                bias = bias[None]
            bias = jnp.repeat(bias, bias_repeat, axis=0)
        return attention_xla(q, k, v, causal=causal, scale=scale, bias=bias, segment_ids=segment_ids,
                             kv_len=kv_len, window=window, alibi_slopes=alibi_slopes)
    local = functools.partial(_flash_local, causal=causal, scale=scale, window=window, interpret=interpret,
                              bias_repeat=bias_repeat)
    if bias is not None:
        # a collapsed bias does not split along a mesh; those callers
        # (evoformer) run on one device or inside their own shard_map
        return local(q, k, v, alibi_slopes, bias)
    # several chips: the kernel sits in a shard_map over the batch axes and,
    # where they divide the heads, the tensor axis (on_mesh says why)
    spec = _mesh_spec(q, k)
    if alibi_slopes is None:
        return on_mesh(lambda q, k, v: local(q, k, v, None, None), (spec, spec, spec), spec)(q, k, v)
    heads = P(spec[2]) if len(spec) > 2 else P()
    return on_mesh(lambda q, k, v, sl: local(q, k, v, sl, None), (spec, spec, spec, heads), spec)(
        q, k, v, jnp.asarray(alibi_slopes, jnp.float32))


def _mesh_spec(q, k) -> P:
    """How (B, S, H, D) operands split over the live mesh: batch over the
    data axes, heads over ``tensor`` — each only where it divides (the
    tensor axis must divide the query AND the KV heads)."""
    from ...parallel.mesh import get_mesh_topology
    from ...runtime.zero.partition import fit_spec, prune_spec

    topo = get_mesh_topology(required=False)
    if topo is None:
        return P()
    B, S, H, D = q.shape
    return fit_spec(prune_spec(P(topo.batch_axes, None, "tensor", None), topo),
                    (B, S, math.gcd(H, k.shape[2]), D), topo)


def _flash_local(q, k, v, alibi_slopes, bias, *, causal, scale, window, interpret, bias_repeat):
    """The kernel call on whole (or shard-local) operands."""
    n_rep = q.shape[2] // k.shape[2]
    if n_rep > 1 and bias is not None:
        # bias x GQA: the collapsed-bias index maps assume per-q-head KV;
        # expand for this (evoformer-class) corner. The main GQA path keeps
        # KV collapsed — the kernels route q heads to their group's KV head
        # by index map, so HBM holds (and the vjp returns) (B, S, KVH, D)
        b, s, h, d = k.shape
        k = jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(b, s, h * n_rep, d)
        v = jnp.broadcast_to(v[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(b, s, h * n_rep, d)
    scale = scale if scale is not None else 1.0 / (q.shape[-1]**0.5)
    if window is not None and window < 1:
        raise ValueError(f"window must be >= 1 (got {window}); pass None to disable the sliding window")
    has_alibi = alibi_slopes is not None
    slopes = jnp.asarray(alibi_slopes, jnp.float32) if has_alibi else jnp.zeros((q.shape[2],), jnp.float32)
    B, Sq, H, _ = q.shape
    Sk = k.shape[1]
    if bias is not None:
        bias = jnp.asarray(bias, jnp.float32)
        while bias.ndim < 4:
            bias = bias[None]
        Bb, Hb, Sqb, Skb = bias.shape
        if (Skb != Sk or Sqb not in (1, Sq) or Hb not in (1, H)
                or (Bb != 1 and Bb * bias_repeat != B)):
            raise ValueError(f"bias shape {bias.shape} is not broadcastable to ({B},{H},{Sq},{Sk}) "
                             f"with bias_repeat={bias_repeat}")
        bias_meta = (Bb, Hb, Sqb, bias_repeat if Bb > 1 else 1)
        bias_flat = bias.reshape(Bb * Hb, Sqb, Sk)
    else:
        bias_meta = None
        bias_flat = jnp.zeros((1, 1, LANES), jnp.float32)
    return _flash(q, k, v, slopes, bias_flat, scale, causal, interpret, has_alibi, int(window or 0),
                  bias_meta, H, k.shape[2])


REGISTRY.register("attention", "pallas", flash_attention, is_available=pallas_available, priority=10)
