"""Pallas chunked scan for the gated delta rule with a per-channel decay (KDA).

For one head, with a state ``S`` in R^{d_k x d_v} that starts at zero::

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

The token-by-token form is ``ops/kda.py::kda_recurrence`` (the XLA path and
this kernel's oracle). Here the sequence is cut into chunks of ``CHUNK``
tokens. Inside a chunk, with ``G_t`` the cumulative log-decay from the chunk's
start (the log-decays times a triangle of ones, on the MXU), ``u_t = beta_t (v_t - S_{t-1}^T (exp(g_t) k_t))`` solves the
unit-lower-triangular system ``(I + A) U = beta V - (beta K exp(G)) S_0``,
``A_ts = beta_t sum_c k_t[c] k_s[c] exp(G_t[c] - G_s[c])`` for s < t (the
WY / UT transform), the output is ``(Q exp(G)) S_0 + tril(B) U`` with ``B`` the
same product of q and k, and the state leaves as ``Diag(exp(G_C)) S_0 +
(K exp(G_C - G))^T U``. The state is carried over the chunks in VMEM, in
float32.

A decay near zero makes ``exp(-G)`` overflow, so no exponent here is ever
positive: a block of ``SUB`` tokens on the diagonal of ``A`` and ``B`` forms
every pair's ``exp(G_t - G_s)`` directly (a (SUB, SUB, d_k) tensor on the
VPU), and a block below the diagonal is a matrix product of rows scaled by
``exp(G_t - G_ref)`` with columns scaled by ``exp(G_ref - G_s)``, ``G_ref`` the
decay just before the row block, which lies between the two. ``(I + A)^-1``
is made by blocks (``_inverse_unit_lower``): small products on the MXU, in
float32 at the highest precision, instead of a substitution row by row.

The backward kernel walks the chunks from the last to the first with the
state's cotangent in VMEM, and gets a chunk's gradients as ``jax.vjp`` of the
SAME chunk function on what the forward saved for that chunk: the state it
started from and its ``(I + A)^-1``. JAX differentiates, inside the kernel
body, everything but the solve: the decays, the pairs on the diagonal, the
blocks below it, the state's products in and out; Mosaic lowers that like
any other kernel code. The solve's adjoint is written out
(``solve_unit_lower``): with ``T = (I + A)^-1`` and ``u = T r``, ``dU = T dr -
T dA u``, so ``r_bar = T^T u_bar`` and ``A_bar = -r_bar u^T`` below the
diagonal. So the twelve products that make ``T`` are run once a chunk, in the
forward, and differentiated nowhere; the backward makes ``u = T r`` again and
those two products (each three bf16 passes under bf16 operands). ``T`` costs
``CHUNK^2`` float32 a chunk and head beside the state's ``d_v d_k``. What
enters is q, k, beta*k, beta*v and the log-decay, so beta's own gradient, the
normalisations, convolutions and gates around the scan are XLA's to
differentiate (``ops/kda.py``).

**One decay a head and token** (Gated DeltaNet; ``chunk_fn_per_head``, calls
named ``gdn_scan_fwd`` / ``gdn_scan_bwd``): ``Diag(exp(g_t))`` is ``exp(g_t) I``,
so the pairs' decays are ONE (CHUNK, CHUNK) matrix ``exp(G_t - G_s)`` (masked to
t >= s, so no exponent is positive and no reference decay is needed) that
multiplies ``[beta K; Q] K^T``, one product on the MXU, where the per-channel
form makes (SUB, SUB, d_k) tensors on the VPU; the log-decay arrives along the
lanes, (1, CHUNK) float32 a chunk, CHUNK floats where the per-channel form reads
CHUNK x d_k. The solve, its adjoint, the saved state and inverse and the
backward by ``jax.vjp`` are the same code. Several value heads may share a key
head's q and k (``q.shape[0]`` divides ``vb.shape[0]``): the grid walks value
heads, a head's q and k blocks are its key head's, and dq, dk leave a value head
each for the caller to add.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._utils import compiler_params as _compiler_params

CHUNK = 128  # tokens a grid step: the triangular system's size
SUB = 16    # tokens a block of the diagonal: pairs formed on the VPU

_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))
_HIGHEST = jax.lax.Precision.HIGHEST


def _beside(*parts):
    return jnp.concatenate([p for p in parts if p.shape[1]], axis=1)


def _dot3(a, b, dims=_NN):
    """A product of float32 matrices in three bf16 passes where full float32
    takes six: each factor is its bf16 rounding plus a bf16 remainder, and the
    product of the two remainders (2^-16 of the result) is left out."""
    bf16, f32 = jnp.bfloat16, jnp.float32
    a_hi, b_hi = a.astype(bf16), b.astype(bf16)
    a_lo, b_lo = (a - a_hi.astype(f32)).astype(bf16), (b - b_hi.astype(f32)).astype(bf16)
    dot = lambda x, y: jax.lax.dot_general(x, y, dims, preferred_element_type=f32)
    return dot(a_hi, b_hi) + dot(a_hi, b_lo) + dot(a_lo, b_hi)


def _dot32(a, b, mm, dims=_NN):
    """A product around the triangular inverse: float32's own under float32
    operands (``mm``), 2^-16 of it under bf16 ones."""
    if mm == jnp.float32:
        return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32, precision=_HIGHEST)
    return _dot3(a, b, dims)


def _inverse_unit_lower(A, dot):
    """``(I + A)^-1`` for a strictly lower triangular ``A`` (n, n), n a power
    of two times ``SUB``, in products of whole (n, n) matrices. First the
    diagonal blocks of ``SUB`` at once: the block-diagonal part ``D`` of ``A``
    is nilpotent at ``SUB``, so ``(I + D)^-1`` is the product of ``I +
    (-D)^(2^j)``, log2(SUB) pairs of products whose terms stay small. Then
    the blocks are joined two by two, ``[[T1, 0], [-T2 A21 T1, T2]]``: with
    ``M`` the inverse of the block-diagonal part so far and ``L`` the part of
    ``A`` that couples each pair, that is ``M - M L M``, once a doubling of the
    block size: forward substitution by blocks. Squaring the whole chunk's
    ``A`` instead, up to ``A^(n/2)``, loses everything to cancellation once
    keys within a chunk are alike (in float32 at 64 tokens the output's error
    was 1e31 for keys with cosine 0.9; at 128 tokens and products of 2^-16 the
    gradients on the chip were 15 times too large; PERF.md, PR 32)."""
    n = A.shape[0]
    row, col = (jax.lax.broadcasted_iota(jnp.int32, (n, n), d) for d in (0, 1))
    same = lambda b: jnp.right_shift(row, b.bit_length() - 1) == jnp.right_shift(col, b.bit_length() - 1)  # one block of b
    D = jnp.where(same(SUB), A, 0.0)
    power, M = -D, (row == col).astype(A.dtype) - D
    for _ in range(SUB.bit_length() - 2):
        power = dot(power, power)
        M = M + dot(M, power)
    b = SUB
    while b < n:
        L = jnp.where(same(2 * b) & ~same(b), A, 0.0)
        M = M - dot(M, dot(L, M))
        b *= 2
    return M


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def solve_unit_lower(A, r, T, mm):
    """``(u, T)``: ``u = (I + A)^-1 r`` for a strictly lower triangular ``A``
    (n, n) and ``T = (I + A)^-1``, made here by ``_inverse_unit_lower`` where
    ``T`` is None and taken as given otherwise (the forward's own, which is a
    value and carries no gradient). The adjoint is written out, so that no
    product of the inverse's construction is ever differentiated: from ``dU =
    T dr - T dA u``, ``r_bar = T^T u_bar`` and ``A_bar = -r_bar u^T``, kept
    where ``A`` has entries at all, below the diagonal."""
    if T is None:
        T = _inverse_unit_lower(A, functools.partial(_dot32, mm=mm))
    return _dot32(T, r, mm), T


def _solve_fwd(A, r, T, mm):
    u, T = solve_unit_lower(A, r, T, mm)
    return (u, T), (u, T)


def _solve_bwd(mm, saved, bars):
    u, T = saved
    r_bar = _dot32(T, bars[0], mm, _TN)  # contracts T's rows: no float32 transpose
    row, col = (jax.lax.broadcasted_iota(jnp.int32, T.shape, d) for d in (0, 1))
    return jnp.where(row > col, -_dot32(r_bar, u, mm, _NT), 0.0), r_bar, None


solve_unit_lower.defvjp(_solve_fwd, _solve_bwd)


def chunk_fn(q, k, kb, vb, g, St0, mm, T=None):
    """One chunk: ``q, k, kb = beta * k`` (C, d_k), ``vb = beta * v`` (C, d_v),
    ``g`` (C, d_k) float32, each token's log-decay (<= 0), ``St0`` (d_v, d_k)
    float32, the incoming state TRANSPOSED (the decay then scales lanes).
    Returns the outputs (C, d_v), the outgoing state and ``(I + A)^-1``,
    float32. ``mm``: the operand type of the large products; the triangular
    inverse and the system's solution are float32 matrices, multiplied exactly
    where ``mm`` is float32 and by ``_dot3`` under bf16 (the inverse is made
    by substitution, so 2^-16 a product is 2^-16 of it). ``T``: the inverse
    as an earlier call on the same operands returned it, which is then not
    made again."""
    f32 = jnp.float32
    C = q.shape[0]
    q, k, kb, vb = (x.astype(f32) for x in (q, k, kb, vb))

    def dot(a, b, dims):
        return jax.lax.dot_general(a.astype(mm), b.astype(mm), dims, preferred_element_type=f32,
                                   precision=_HIGHEST if mm == f32 else None)

    row, col = (jax.lax.broadcasted_iota(jnp.int32, (C, C), d) for d in (0, 1))
    # G_t = sum_{s <= t} g_s, the cumulative log-decay from the chunk's start: a triangle of ones times g, exactly
    G = jax.lax.dot_general((row >= col).astype(f32), g, _NN, preferred_element_type=f32, precision=_HIGHEST)
    gam = jnp.exp(G)
    from_state = dot(jnp.concatenate([q * gam, kb * gam], axis=0), St0, _NT)  # (2C, d_v)
    o_in, r = from_state[:C], vb - from_state[C:]

    G_ref = jax.lax.stop_gradient(G)  # a reference cancels in exp(G_t - ref) exp(ref - G_s): no gradient is its
    t = jax.lax.broadcasted_iota(jnp.int32, (SUB, SUB), 0)
    s = jax.lax.broadcasted_iota(jnp.int32, (SUB, SUB), 1)
    pairs = (SUB, SUB, q.shape[1])  # the mask as the pairs' tensor has it: Mosaic does not reshape a 2-D mask to 3-D
    t_ge_s = jax.lax.broadcasted_iota(jnp.int32, pairs, 0) >= jax.lax.broadcasted_iota(jnp.int32, pairs, 1)
    a_rows, b_rows = [], []
    for lo in range(0, C, SUB):
        Gi, ki, kbi, qi = (x[lo:lo + SUB] for x in (G, k, kb, q))
        dec = jnp.exp(jnp.where(t_ge_s, Gi[:, None, :] - Gi[None, :, :], 0.0))  # (SUB, SUB, d_k)
        kd = ki[None, :, :] * dec
        a_ii = jnp.where(t > s, jnp.sum(kbi[:, None, :] * kd, axis=-1), 0.0)
        b_ii = jnp.where(t >= s, jnp.sum(qi[:, None, :] * kd, axis=-1), 0.0)
        after = jnp.zeros((SUB, C - lo - SUB), f32)
        if lo == 0:  # the first block has nothing below its diagonal (and Mosaic no vector of no width)
            a_rows.append(_beside(a_ii, after))
            b_rows.append(_beside(b_ii, after))
            continue
        ref = G_ref[lo - 1:lo]  # the decay just before this block: G_s >= ref >= G_t for s before and t inside
        left = jnp.concatenate([kbi, qi], axis=0) * jnp.exp(jnp.concatenate([Gi, Gi], axis=0) - ref)
        below = dot(left, k[:lo] * jnp.exp(ref - G[:lo]), _NT)  # (2 SUB, lo)
        a_rows.append(_beside(below[:SUB], a_ii, after))
        b_rows.append(_beside(below[SUB:], b_ii, after))
    A, B = jnp.concatenate(a_rows, axis=0), jnp.concatenate(b_rows, axis=0)  # (C, C)

    u, T = solve_unit_lower(A, r, T, mm)  # (C, d_v), (C, C)
    o = o_in + dot(B, u, _NN)
    last = G[C - 1:C]
    St1 = St0 * jnp.exp(last) + dot(u, k * jnp.exp(last - G), _TN)  # (d_v, d_k)
    return o, St1, T


def chunk_fn_per_head(q, k, kb, vb, g, St0, mm, T=None):
    """``chunk_fn`` for a decay that is one number a token: ``g`` (1, C)
    float32, the chunk's log-decays along the lanes; everything else as there.
    ``A`` and ``B`` are ``[kb; q] k^T`` (exact under bf16 operands: float32
    accumulation of bf16 products) times ``exp(G_t - G_s)``."""
    f32 = jnp.float32
    C = q.shape[0]
    q, k, kb, vb = (x.astype(f32) for x in (q, k, kb, vb))

    def dot(a, b, dims):
        return jax.lax.dot_general(a.astype(mm), b.astype(mm), dims, preferred_element_type=f32,
                                   precision=_HIGHEST if mm == f32 else None)

    row, col = (jax.lax.broadcasted_iota(jnp.int32, (C, C), d) for d in (0, 1))
    # G_t down the sublanes (a masked sum along the lanes, exact) and the same numbers along the lanes (the diagonal of
    # their broadcast, summed down): no transpose, no product
    G = jnp.sum(jnp.where(row >= col, jnp.broadcast_to(g, (C, C)), 0.0), axis=1, keepdims=True)  # (C, 1)
    G_lanes = jnp.sum(jnp.where(row == col, jnp.broadcast_to(G, (C, C)), 0.0), axis=0, keepdims=True)  # (1, C)
    decay = jnp.exp(jnp.where(row >= col, G - G_lanes, 0.0))  # exp(G_t - G_s) for s <= t
    gam = jnp.exp(G)
    from_state = dot(jnp.concatenate([q * gam, kb * gam], axis=0), St0, _NT)  # (2C, d_v)
    o_in, r = from_state[:C], vb - from_state[C:]
    pairs = dot(jnp.concatenate([kb, q], axis=0), k, _NT)  # (2C, C)
    A = jnp.where(row > col, pairs[:C] * decay, 0.0)
    B = jnp.where(row >= col, pairs[C:] * decay, 0.0)

    u, T = solve_unit_lower(A, r, T, mm)
    o = o_in + dot(B, u, _NN)
    last = jnp.sum(g, axis=1, keepdims=True)  # G_C, (1, 1)
    St1 = St0 * jnp.exp(last) + dot(u, k * jnp.exp(last - G), _TN)
    return o, St1, T


def _fwd_kernel(q_ref, k_ref, kb_ref, vb_ref, g_ref, o_ref, st_ref, t_ref, state, *, chunk, mm):
    @pl.when(pl.program_id(1) == 0)
    def _zero():
        state[...] = jnp.zeros_like(state)

    st0 = state[...]
    st_ref[0, 0] = st0  # what this chunk started from: the backward's residual
    o, st1, T = chunk(q_ref[0], k_ref[0], kb_ref[0], vb_ref[0], g_ref[0], st0, mm)
    o_ref[0] = o.astype(o_ref.dtype)
    t_ref[0, 0] = T  # and the inverse it made: the backward neither makes nor differentiates it again
    state[...] = st1


def chunk_bwd(q, k, kb, vb, g, St0, T, do, dSt1, mm, chunk=chunk_fn):
    """A chunk's gradients to q, k, kb, vb, g and the incoming state, from the
    cotangents of its outputs (float32) and of its outgoing state: ``jax.vjp``
    of ``chunk`` on the state and the inverse the forward saved for it."""
    _, vjp = jax.vjp(lambda *operands: chunk(*operands, mm, T)[:2], q, k, kb, vb, g, St0)
    return vjp((do, dSt1))


def _bwd_kernel(q_ref, k_ref, kb_ref, vb_ref, g_ref, st_ref, t_ref, do_ref, dq_ref, dk_ref, dkb_ref, dvb_ref, dg_ref,
                dstate, *, chunk, mm):
    @pl.when(pl.program_id(1) == 0)
    def _zero():  # the last chunk: nothing reads the state after it
        dstate[...] = jnp.zeros_like(dstate)

    dq, dk, dkb, dvb, dg, dst0 = chunk_bwd(q_ref[0], k_ref[0], kb_ref[0], vb_ref[0], g_ref[0], st_ref[0, 0], t_ref[0, 0],
                                           do_ref[0].astype(jnp.float32), dstate[...], mm, chunk)
    for ref, value in ((dq_ref, dq), (dk_ref, dk), (dkb_ref, dkb), (dvb_ref, dvb), (dg_ref, dg)):
        ref[0] = value.astype(ref.dtype)
    dstate[...] = dst0


def _mm_dtype(x):
    return jnp.float32 if x.dtype == jnp.float32 else jnp.bfloat16


def _form(q, vb, g, chunk_at):
    """What tells the two forms apart is the decay's shape: (BH, S, d_k), a
    number a channel, or (BH * S / CHUNK, 1, CHUNK), a number a token along
    the lanes. -> (the chunk function, the calls' name, the BlockSpecs of
    (BH, S, d) rows of a key head and of a value head, of a (BH, S / CHUNK,
    m, n) float32 save, and of the decay), for a grid (value head, step)
    whose step works on chunk ``chunk_at(step)``."""
    nc, rep = q.shape[1] // CHUNK, vb.shape[0] // q.shape[0]
    rows = lambda d: pl.BlockSpec((1, CHUNK, d), lambda b, c: (b, chunk_at(c), 0))
    key_rows = rows if rep == 1 else lambda d: pl.BlockSpec((1, CHUNK, d), lambda b, c: (b // rep, chunk_at(c), 0))
    whole = lambda m, n: pl.BlockSpec((1, 1, m, n), lambda b, c: (b, chunk_at(c), 0, 0))
    if g.shape[1:] == (1, CHUNK):
        return chunk_fn_per_head, "gdn_scan", key_rows, rows, whole, \
            pl.BlockSpec((1, 1, CHUNK), lambda b, c: (b * nc + chunk_at(c), 0, 0))
    return chunk_fn, "kda_scan", key_rows, rows, whole, rows(q.shape[-1])


def scan_fwd(q, k, kb, vb, g, interpret: bool):
    """(BH, S, d) operands, S a multiple of ``CHUNK`` -> outputs (BH, S, d_v)
    in ``vb``'s type, and for the backward every chunk's incoming state (BH,
    S/CHUNK, d_v, d_k) and its ``(I + A)^-1`` (BH, S/CHUNK, CHUNK, CHUNK),
    float32. ``g``: (BH, S, d_k), or (BH * S/CHUNK, 1, CHUNK) for one decay
    a token, where q and k may have fewer heads than kb and vb (``_form``)."""
    BH, S, dv = vb.shape
    dk = q.shape[-1]
    nc = S // CHUNK
    chunk, name, key_rows, rows, whole, decay = _form(q, vb, g, lambda c: c)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk, mm=_mm_dtype(q)),
        name=f"{name}_fwd",
        grid=(BH, nc),
        in_specs=[key_rows(dk), key_rows(dk), rows(dk), rows(dv), decay],
        out_specs=[rows(dv), whole(dv, dk), whole(CHUNK, CHUNK)],
        out_shape=[jax.ShapeDtypeStruct((BH, S, dv), vb.dtype), jax.ShapeDtypeStruct((BH, nc, dv, dk), jnp.float32),
                   jax.ShapeDtypeStruct((BH, nc, CHUNK, CHUNK), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((dv, dk), jnp.float32)],
        interpret=interpret,
        compiler_params=_compiler_params("parallel", "arbitrary", interpret=interpret),
    )(q, k, kb, vb, g)


def scan_bwd(q, k, kb, vb, g, states, inverses, do, interpret: bool):
    """Gradients of ``scan_fwd``'s outputs' cotangent ``do`` to q, k, kb, vb
    (their types) and g (float32), chunks walked from the last to the first,
    each on the state and the inverse ``scan_fwd`` returned for it. dq and dk
    have kb's heads: where value heads share a key head, one each."""
    BH, S, dv = vb.shape
    dk = q.shape[-1]
    nc = S // CHUNK
    chunk, name, key_rows, rows, whole, decay = _form(q, vb, g, lambda c: nc - 1 - c)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk, mm=_mm_dtype(q)),
        name=f"{name}_bwd",
        grid=(BH, nc),
        in_specs=[key_rows(dk), key_rows(dk), rows(dk), rows(dv), decay, whole(dv, dk), whole(CHUNK, CHUNK), rows(dv)],
        out_specs=[rows(dk), rows(dk), rows(dk), rows(dv), decay],
        out_shape=[jax.ShapeDtypeStruct(kb.shape, x.dtype) for x in (q, k, kb)]
        + [jax.ShapeDtypeStruct(vb.shape, vb.dtype), jax.ShapeDtypeStruct(g.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((dv, dk), jnp.float32)],
        interpret=interpret,
        compiler_params=_compiler_params("parallel", "arbitrary", interpret=interpret),
    )(q, k, kb, vb, g, states, inverses, do)
