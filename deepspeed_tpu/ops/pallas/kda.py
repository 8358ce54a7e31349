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

**Several heads a grid step.** A chunk of one head is a chain: the state's
product in, the blocks under the diagonal, twelve products of (CHUNK, CHUNK)
float32 matrices for the inverse, ``T r``, ``B u``, the state's product out,
each waiting for the one before and each far too small to fill the MXU (0.14 us
a product of which 0.06 is the MXU's; 3.4 us a chunk). The next CHUNK of the
same head cannot help: it needs this one's state. Another HEAD needs nothing
of it: heads share no state, no inverse and no operand but a key head's q and
k. So the grid is (heads / H, chunks), a block holds H heads' rows, the state
scratch is (H, d_v, d_k), and the body runs H chains abreast: a chunk function
is a generator that yields after each link, ``_abreast`` steps the H generators
in turn, and the traced program has link i of every head before link i + 1 of
any (the backward is ``jax.vjp`` of that same function, so its transpose is
abreast too). The scheduler then has an independent product to issue while
one is on its way back. Written head after head, without the yields, the
same H heads in one step ran no faster than one a step (per-channel forward
6.93 ms a layer at H = 1, 6.68 at 2, 6.53 at 4; abreast 4.46 and 3.63); under
``jax.vmap`` the forward ran slower than abreast (5.76 and 4.70) and the
backward of four a little faster (6.95 beside 7.14), the per-head form brought
Mosaic down, and the CPU's batched products round otherwise than one head's,
so no test could hold it to the kernel of one head (PERF.md, PR 47). Nothing in a head's arithmetic moves: the same products in
the same order and precision, so every output, save and gradient is, bit for
bit, the kernel of one head a step. ``heads_a_step`` chooses H from the
operands of the call; H = 1 is the kernel as it was.
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


def _abreast(heads):
    """Run generators in turn, each to its next ``yield``, until all have returned -> their return values. A head's
    chunk is written as a generator that yields after each link of its chain (a product that the next one waits for),
    so the program's text has link i of every head before link i + 1 of any: the heads share nothing, and the MXU
    finds one's product to run while another's is on its way back."""
    heads = list(heads)
    results, live = [None] * len(heads), list(range(len(heads)))
    while live:
        for i in list(live):
            try:
                next(heads[i])
            except StopIteration as done:
                results[i] = done.value
                live.remove(i)
    return results


def _links(chain):
    """A chain written as a generator -> the plain function of one head; the generator stays at ``.links``."""
    @functools.wraps(chain)
    def alone(*args, **kwargs):
        return _abreast([chain(*args, **kwargs)])[0]

    alone.links = chain
    return alone


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
        yield
        M = M + dot(M, power)
        yield
    b = SUB
    while b < n:
        L = jnp.where(same(2 * b) & ~same(b), A, 0.0)
        LM = dot(L, M)
        yield
        M = M - dot(M, LM)
        yield
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
        T, = _abreast([_inverse_unit_lower(A, functools.partial(_dot32, mm=mm))])
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


def _solve_links(A, r, T, mm):
    """``solve_unit_lower`` as links of a chunk's chain: the inverse, where it is not given, a product a link (it is
    a value: the solve's adjoint needs no gradient through its construction), then ``u = T r``."""
    if T is None:
        T = jax.lax.stop_gradient((yield from _inverse_unit_lower(A, functools.partial(_dot32, mm=mm))))
    result = solve_unit_lower(A, r, T, mm)
    yield
    return result


@_links
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
    yield

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
    # no link ends in these eight blocks, nor between them and the solve: the pairs' tensors fill the vector unit by
    # themselves and the blocks below wait for nothing, and with four heads' ``A`` and ``B`` held at once the backward
    # took 8.06 ms a layer where it takes 7.15 (a link a block: 8.41; PERF.md, PR 47)

    u, T = yield from _solve_links(A, r, T, mm)  # (C, d_v), (C, C)
    o = o_in + dot(B, u, _NN)
    yield
    last = G[C - 1:C]
    St1 = St0 * jnp.exp(last) + dot(u, k * jnp.exp(last - G), _TN)  # (d_v, d_k)
    return o, St1, T


@_links
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
    yield
    pairs = dot(jnp.concatenate([kb, q], axis=0), k, _NT)  # (2C, C)
    A = jnp.where(row > col, pairs[:C] * decay, 0.0)
    B = jnp.where(row >= col, pairs[C:] * decay, 0.0)
    yield

    u, T = yield from _solve_links(A, r, T, mm)
    o = o_in + dot(B, u, _NN)  # and the state's product out with it, one link (a link each: 2.47 ms a layer forward, so 2.42)
    last = jnp.sum(g, axis=1, keepdims=True)  # G_C, (1, 1)
    St1 = St0 * jnp.exp(last) + dot(u, k * jnp.exp(last - G), _TN)
    return o, St1, T


def _head(ref, i, heads):
    """Head ``i`` of a step of ``heads`` in a block: a save's block has a chunk axis of one, and a key head's block
    fewer heads than the step has value heads (each serves ``heads // ref.shape[0]`` of them, in order)."""
    at = i * ref.shape[0] // heads
    return ref[at, 0] if len(ref.shape) == 4 else ref[at]


def _fwd_kernel(q_ref, k_ref, kb_ref, vb_ref, g_ref, o_ref, st_ref, t_ref, state, *, chunk, mm):
    @pl.when(pl.program_id(1) == 0)
    def _zero():
        state[...] = jnp.zeros_like(state)

    H = state.shape[0]
    for i in range(H):
        st_ref[i, 0] = state[i]  # what this chunk started from: the backward's residual
    operands = [[_head(ref, i, H) for ref in (q_ref, k_ref, kb_ref, vb_ref, g_ref)] + [state[i]] for i in range(H)]
    for i, (o, st1, T) in enumerate(_abreast(chunk.links(*xs, mm) for xs in operands)):
        o_ref[i] = o.astype(o_ref.dtype)
        t_ref[i, 0] = T  # and the inverse it made: the backward neither makes nor differentiates it again
        state[i] = st1


def heads_bwd(heads, mm, chunk=chunk_fn):
    """For each head of a grid step, ``(q, k, kb, vb, g, St0, T, do, dSt1)``: its chunk's gradients to q, k, kb, vb, g
    and the incoming state, from the cotangents of its outputs (float32) and of its outgoing state: ``jax.vjp`` of
    ``chunk`` on the state and the inverse the forward saved for it, the heads' chains abreast (``_abreast``) in the
    function and so in its transpose."""
    def forward(*operands):
        return [out[:2] for out in _abreast(chunk.links(*xs, mm, head[6]) for xs, head in zip(operands, heads))]

    _, vjp = jax.vjp(forward, *(head[:6] for head in heads))
    return vjp([head[7:] for head in heads])


def chunk_bwd(q, k, kb, vb, g, St0, T, do, dSt1, mm, chunk=chunk_fn):
    """``heads_bwd`` of one head."""
    return heads_bwd([(q, k, kb, vb, g, St0, T, do, dSt1)], mm, chunk)[0]


def _bwd_kernel(q_ref, k_ref, kb_ref, vb_ref, g_ref, st_ref, t_ref, do_ref, dq_ref, dk_ref, dkb_ref, dvb_ref, dg_ref,
                dstate, *, chunk, mm):
    @pl.when(pl.program_id(1) == 0)
    def _zero():  # the last chunk: nothing reads the state after it
        dstate[...] = jnp.zeros_like(dstate)

    H = dstate.shape[0]
    heads = [tuple(_head(ref, i, H) for ref in (q_ref, k_ref, kb_ref, vb_ref, g_ref, st_ref, t_ref))
             + (do_ref[i].astype(jnp.float32), dstate[i]) for i in range(H)]
    for i, (*to_blocks, dst0) in enumerate(heads_bwd(heads, mm, chunk)):
        for ref, value in zip((dq_ref, dk_ref, dkb_ref, dvb_ref, dg_ref), to_blocks):
            ref[(i, 0) if len(ref.shape) == 4 else i] = value.astype(ref.dtype)  # dg is walked as a save is
        dstate[i] = dst0


def _mm_dtype(x):
    return jnp.float32 if x.dtype == jnp.float32 else jnp.bfloat16


def _per_head(g):
    """What tells the two forms apart is the decay's shape: (BH, S, d_k), a number a channel, or (BH * S / CHUNK, 1,
    CHUNK), a number a token along the lanes."""
    return g.shape[1:] == (1, CHUNK)


def _vmem_a_head(q, vb, g, backward: bool) -> int:
    """What a head of a grid step holds in VMEM, from the shapes: its blocks, each twice (the pipeline fetches the next
    step's while this one's are worked on), and the body's temporaries, (CHUNK, CHUNK) float32 matrices that have left
    the 64 registers: 24 of them in the forward and 72 in the ``vjp`` (at the cells' shapes the compiler asked 22.5 MB
    for four heads of the per-channel backward, 4.4 of them blocks)."""
    dk, dv, f32 = q.shape[-1], vb.shape[-1], 4
    rows = CHUNK * q.dtype.itemsize
    decay = CHUNK * f32 if _per_head(g) else CHUNK * dk * f32
    blocks = rows * (3 * dk + 2 * dv) + decay + (dv * dk + CHUNK * CHUNK) * f32  # q, k, kb, vb, o or do, g, the two saves
    if backward:
        blocks += rows * (3 * dk + dv) + decay  # dq, dk, dkb, dvb, dg
    return 2 * blocks + (72 if backward else 24) * CHUNK * max(CHUNK, dk, dv) * f32


def heads_a_step(q, vb, g, backward: bool) -> int:
    """How many (value) heads a grid step of the call on these (shard-local) operands works on: the most of 4, 2 and
    1 that divides the heads, keeps a step's value heads with whole key heads (one key head's where its repetition
    allows, so that its q and k are fetched once a step) and fits the kernel's share of VMEM. Chosen here, from what
    the call can see, by nobody else: the table this was written from (TPU v5e, ms a layer at 32 heads x 8,192 x 128,
    H = 1 | 2 | 4; PERF.md, PR 47) falls with H in all four calls, per-channel 6.93 | 4.46 | 3.63 forward and 8.98 |
    7.61 | 7.14 backward, per-head 5.74 | 3.40 | 2.42 and 3.80 | 2.11 | 1.75; at 8 the per-head calls gain 7-10% more,
    the per-channel ones nothing, and the bodies compile for more than twice as long. 1 is the kernel as it was: the
    same code, one chain a step."""
    from ._utils import vmem_budget

    heads, rep = vb.shape[0], vb.shape[0] // q.shape[0]
    for H in (4, 2):
        if heads % H == 0 and (rep % H == 0 or H % rep == 0) and H * _vmem_a_head(q, vb, g, backward) <= vmem_budget():
            return H
    return 1


def _form(q, vb, g, chunk_at, H, backward):
    """-> (the chunk function, the calls' name, the BlockSpecs of (BH, S, d) rows of the step's key heads and of its
    ``H`` value heads, of a (BH, S / CHUNK, m, n) float32 save and of the decay as the grid walks it (``_walked``),
    the compiler's parameters), for a grid (step's value heads, step) whose step works on chunk ``chunk_at(step)``."""
    rep = vb.shape[0] // q.shape[0]
    key_heads = max(1, H // rep)  # of one step: H value heads are one key head's, or H / rep whole key heads'
    rows = lambda d: pl.BlockSpec((H, CHUNK, d), lambda b, c: (b, chunk_at(c), 0))
    key_rows = lambda d: pl.BlockSpec((key_heads, CHUNK, d), lambda b, c: (b * H // (rep * key_heads), chunk_at(c), 0))
    whole = lambda m, n: pl.BlockSpec((H, 1, m, n), lambda b, c: (b, chunk_at(c), 0, 0))
    params = functools.partial(_compiler_params, "parallel", "arbitrary", vmem_bytes=H * _vmem_a_head(q, vb, g, backward))
    if _per_head(g):
        return chunk_fn_per_head, "gdn_scan", key_rows, rows, whole, whole(1, CHUNK), params
    return chunk_fn, "kda_scan", key_rows, rows, whole, rows(q.shape[-1]), params


def _walked(g, nc):
    """A decay that is a number a token, (value head, chunk, 1, CHUNK) as a save is: a head's chunks lie ``nc`` rows
    apart, and a step's heads are one block."""
    return g.reshape(-1, nc, 1, CHUNK) if _per_head(g) else g


def scan_fwd(q, k, kb, vb, g, interpret: bool):
    """(BH, S, d) operands, S a multiple of ``CHUNK`` -> outputs (BH, S, d_v)
    in ``vb``'s type, and for the backward every chunk's incoming state (BH,
    S/CHUNK, d_v, d_k) and its ``(I + A)^-1`` (BH, S/CHUNK, CHUNK, CHUNK),
    float32. ``g``: (BH, S, d_k), or (BH * S/CHUNK, 1, CHUNK) for one decay
    a token, where q and k may have fewer heads than kb and vb (``_form``)."""
    return _scan_fwd(q, k, kb, vb, g, heads_a_step(q, vb, g, False), interpret)


# jitted for the trace's sake, not the program's (it is inlined where it is called): a body of four heads is four times
# the Python to trace, and a model's step meets the call once a kind of block and again wherever its loss is traced
# anew: the layers of one shape then share ONE trace of the body a process (the hybrid cell's set-up: 49 s with one head
# a step, 75 with four traced at every call, PERF.md, PR 47). H is an argument, so whoever asks the rule is the caller.
@functools.partial(jax.jit, static_argnums=(5, 6))
def _scan_fwd(q, k, kb, vb, g, H, interpret):
    BH, S, dv = vb.shape
    dk = q.shape[-1]
    nc = S // CHUNK
    chunk, name, key_rows, rows, whole, decay, params = _form(q, vb, g, lambda c: c, H, False)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk, mm=_mm_dtype(q)),
        name=f"{name}_fwd",
        grid=(BH // H, nc),
        in_specs=[key_rows(dk), key_rows(dk), rows(dk), rows(dv), decay],
        out_specs=[rows(dv), whole(dv, dk), whole(CHUNK, CHUNK)],
        out_shape=[jax.ShapeDtypeStruct((BH, S, dv), vb.dtype), jax.ShapeDtypeStruct((BH, nc, dv, dk), jnp.float32),
                   jax.ShapeDtypeStruct((BH, nc, CHUNK, CHUNK), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((H, dv, dk), jnp.float32)],
        interpret=interpret,
        compiler_params=params(interpret=interpret),
    )(q, k, kb, vb, _walked(g, nc))


def scan_bwd(q, k, kb, vb, g, states, inverses, do, interpret: bool):
    """Gradients of ``scan_fwd``'s outputs' cotangent ``do`` to q, k, kb, vb
    (their types) and g (float32), chunks walked from the last to the first,
    each on the state and the inverse ``scan_fwd`` returned for it. dq and dk
    have kb's heads: where value heads share a key head, one each."""
    return _scan_bwd(q, k, kb, vb, g, states, inverses, do, heads_a_step(q, vb, g, True), interpret)


@functools.partial(jax.jit, static_argnums=(8, 9))
def _scan_bwd(q, k, kb, vb, g, states, inverses, do, H, interpret):
    BH, S, dv = vb.shape
    dk = q.shape[-1]
    nc = S // CHUNK
    chunk, name, key_rows, rows, whole, decay, params = _form(q, vb, g, lambda c: nc - 1 - c, H, True)
    walked = _walked(g, nc)
    *grads, dg = pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk, mm=_mm_dtype(q)),
        name=f"{name}_bwd",
        grid=(BH // H, nc),
        in_specs=[key_rows(dk), key_rows(dk), rows(dk), rows(dv), decay, whole(dv, dk), whole(CHUNK, CHUNK), rows(dv)],
        out_specs=[rows(dk), rows(dk), rows(dk), rows(dv), decay],
        out_shape=[jax.ShapeDtypeStruct(kb.shape, x.dtype) for x in (q, k, kb)]
        + [jax.ShapeDtypeStruct(vb.shape, vb.dtype), jax.ShapeDtypeStruct(walked.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((H, dv, dk), jnp.float32)],
        interpret=interpret,
        compiler_params=params(interpret=interpret),
    )(q, k, kb, vb, walked, states, inverses, do)
    return (*grads, dg.reshape(g.shape))
