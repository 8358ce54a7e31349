"""Pallas selective scan (Mamba-1): a state a channel and state column that decays by its own factor a token.

For one sequence, with a state ``h`` in R^{channels x N} that starts at zero (``N`` = 16 here; ``a = -exp(A_log)``)::

    h_t[d, n] = exp(delta_t[d] a[d, n]) h_{t-1}[d, n] + delta_t[d] u_t[d] B_t[n]
    y_t[d]    = sum_n h_t[d, n] C_t[n] + D[d] u_t[d]

The decay differs by channel, state column AND token, so nothing here is a matrix product (the delta rules of
``kda.py`` are MXU work chunk by chunk; Mamba-2 made the decay one number a head to get there: ``ssd.py``, beside this file): it is one multiply-add
a state entry and token on the vector unit, ``exp`` on the transcendental unit beside it. The token-by-token form is
``ops/ssm.py::ssm_recurrence`` (the XLA path and this kernel's oracle).

Layout: channels along the lanes, the ``N`` state columns down the sublanes, so a tile of ``W`` channels' state is
``(N, W)`` float32 (two vregs a 128 channels) and a token's ``delta``, ``delta u`` and ``dy`` are rows ``(1, W)`` that
broadcast down the sublanes. ``B_t`` and ``C_t`` are one number a SUBLANE: they arrive with time along the lanes
(``(N, CHUNK)`` a chunk) and each chunk first spreads every token's column over 128 lanes into VMEM (``_spread``: a
masked lane sum a token, 2% of the chunk's work), after which a step reads ``(N, 128)`` tiles like any other operand.

The grid is (sequence, chunk of ``CHUNK`` tokens); a step holds ALL channels' state ``(N, channels)`` float32 in VMEM
(0.33 MB at 5,120) across the chunks and walks the channels in tiles of ``W``, each tile token by token (``_tokens``:
unrolled so that the next tokens' ``exp`` and products, which do not wait for the state, fill the slots beside the
chain). ``y_t`` is a sum down the sublanes a token and tile. Rows are gathered in float32 scratch and leave as one
whole-block store in the operand's type.

The backward walks the chunks from the last to the first with the state's cotangent in VMEM. The forward saves every
chunk's INCOMING state (``(S / CHUNK, N, channels)`` float32: 21 MB a sequence of 8,192 at 5,120 channels, a quarter
of ``y``'s own bf16 bytes) and nothing else; a chunk's backward makes the tile's ``CHUNK`` states again from it (kept
in VMEM: ``CHUNK x N x W`` float32) and then steps back through them. Per token and tile: two sums down the sublanes
(``d(delta u)`` and ``d delta``), the decay's and ``a``'s gradients elementwise, and ``B``'s and ``C``'s gradients as
lane-wise PARTIAL sums ``(N, 128)`` (a token's ``(N, W)`` folded by whole vregs), which leave the kernel as
``(S, N, 128)`` float32 for XLA to sum: a sum along the lanes a token would cost the cross-lane unit as much as the
rest of the step. ``a``'s and ``D``'s gradients accumulate in their output blocks over the chunks, one a sequence.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._utils import compiler_params as _compiler_params

CHUNK = 128  # tokens a grid step: B and C arrive with time along the lanes, so a block of them is 128 tokens wide
LANES = 128
UNROLL = 8   # tokens of the inner loop laid out side by side for the scheduler


def _tile_width(channels: int, want: int) -> int:
    """The widest tile of whole vregs, no wider than ``want``, that divides the channels."""
    if channels % LANES:
        raise ValueError(f"the selective-scan kernel takes channels in whole vregs of {LANES}, got {channels}")
    w = min(want, channels)
    while channels % w:
        w -= LANES
    return w


def _tokens(n: int, body, carry, unroll: int = UNROLL):
    """``fori_loop(0, n, body, carry)`` with ``unroll`` tokens written out a trip (Mosaic's own ``unroll`` is all or none)."""
    def trip(g, carry):
        for j in range(unroll):
            carry = body(g * unroll + j, carry)
        return carry

    return jax.lax.fori_loop(0, n // unroll, trip, carry)


def _spread(cols, out):
    """``cols`` (N, CHUNK), a token a lane -> ``out`` (CHUNK, N, 128) with token t's column on every lane: a masked sum
    along the lanes picks the column (any ``t``, no lane slice), and a column broadcasts along the lanes for free."""
    lane = jax.lax.broadcasted_iota(jnp.int32, cols.shape, 1)

    def put(t, _):
        col = jnp.sum(jnp.where(lane == t, cols, 0.0), axis=1, keepdims=True)  # (N, 1)
        out[t] = jnp.broadcast_to(col, (cols.shape[0], LANES))
        return _

    _tokens(cols.shape[1], put, 0)


def _across(tile, width: int):
    """An (N, 128) tile repeated along the lanes to (N, width): whole vregs side by side."""
    return tile if width == LANES else jnp.concatenate([tile] * (width // LANES), axis=1)


def _fold(x):
    """(N, W) -> (N, 128): the W / 128 vreg columns added up (a partial sum along the lanes, by whole vregs)."""
    parts = [x[:, lo:lo + LANES] for lo in range(0, x.shape[1], LANES)]
    return functools.reduce(jnp.add, parts)


def _fwd_kernel(u_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, y_ref, st_ref, state, bb, cb, du, rows, *, width, state_dtype):
    f32 = jnp.float32
    T, channels = du.shape

    @pl.when(pl.program_id(1) == 0)
    def _zero():
        state[...] = jnp.zeros_like(state)

    st_ref[0, 0] = state[...]  # what this chunk started from: the backward's one residual
    _spread(b_ref[0].astype(f32), bb)
    _spread(c_ref[0].astype(f32), cb)
    u = u_ref[0].astype(f32)
    du[...] = dt_ref[0] * u
    for lo in range(0, channels, width):  # static: a tile's columns are whole vregs at a fixed place
        cols = slice(lo, lo + width)
        a = a_ref[:, cols]

        def step(t, h):
            dt, x = dt_ref[0, pl.ds(t, 1), cols], du[pl.ds(t, 1), cols]  # (1, W) rows, broadcast down the sublanes
            h = (jnp.exp(dt * a) * h + x * _across(bb[t], width)).astype(state_dtype).astype(f32)
            rows[pl.ds(t, 1), cols] = jnp.sum(h * _across(cb[t], width), axis=0, keepdims=True)
            return h

        state[:, cols] = _tokens(T, step, state[:, cols])
    y_ref[0] = (rows[...] + d_ref[...] * u).astype(y_ref.dtype)


def _bwd_kernel(u_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, st_ref, dy_ref, du_ref, ddt_ref, da_ref, dbp_ref, dcp_ref, dd_ref,
                dstate, bb, cb, du, dx, dyf, hs, *, width):
    f32 = jnp.float32
    T, channels = du.shape
    first = pl.program_id(1) == 0  # the LAST chunk of the sequence: nothing reads the state after it

    @pl.when(first)
    def _zero():
        dstate[...] = jnp.zeros_like(dstate)
        da_ref[...] = jnp.zeros_like(da_ref)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    _spread(b_ref[0].astype(f32), bb)
    _spread(c_ref[0].astype(f32), cb)
    u = u_ref[0].astype(f32)
    du[...] = dt_ref[0] * u
    dyf[...] = dy_ref[0].astype(f32)  # rows are read one a token: float32 rows, whatever type the cotangent came in
    dbp_ref[...] = jnp.zeros_like(dbp_ref)
    dcp_ref[...] = jnp.zeros_like(dcp_ref)
    for lo in range(0, channels, width):
        cols = slice(lo, lo + width)
        a = a_ref[:, cols]

        def again(t, h):  # the forward's steps once more, each token's INCOMING state kept
            hs[t] = h
            dt, x = dt_ref[0, pl.ds(t, 1), cols], du[pl.ds(t, 1), cols]
            return jnp.exp(dt * a) * h + x * _across(bb[t], width)

        _tokens(T, again, st_ref[0, 0, :, cols])

        def back(i, carry):
            dh, da = carry  # the cotangent of the state AFTER token t, from the tokens behind it; a's gradient so far
            t = T - 1 - i
            dt, x, g = dt_ref[0, pl.ds(t, 1), cols], du[pl.ds(t, 1), cols], dyf[pl.ds(t, 1), cols]
            b, c, before = _across(bb[t], width), _across(cb[t], width), hs[t]
            decay = jnp.exp(dt * a)
            dh = dh + g * c
            dcp_ref[0, t] += _fold(g * (decay * before + x * b))  # dy_t h_t, summed along the lanes by XLA
            dbp_ref[0, t] += _fold(dh * x)
            dx[pl.ds(t, 1), cols] = jnp.sum(dh * b, axis=0, keepdims=True)  # d(delta u)_t
            slope = dh * before * decay  # dL / d(delta_t a)
            ddt_ref[0, pl.ds(t, 1), cols] = jnp.sum(slope * a, axis=0, keepdims=True)
            return dh * decay, da + slope * dt

        dh, da = _tokens(T, back, (dstate[:, cols], da_ref[0, :, cols]), UNROLL // 2)
        dstate[:, cols] = dh
        da_ref[0, :, cols] = da
    moved = dx[...]
    ddt_ref[0] = ddt_ref[0] + moved * u
    du_ref[0] = (moved * dt_ref[0] + dyf[...] * d_ref[...]).astype(du_ref.dtype)
    dd_ref[0] = dd_ref[0] + jnp.sum(dyf[...] * u, axis=0, keepdims=True)


def _blocks(S: int, channels: int, N: int, chunk_at):
    rows = pl.BlockSpec((1, CHUNK, channels), lambda b, c: (b, chunk_at(c), 0))      # u, delta, y and their gradients
    cols = pl.BlockSpec((1, N, CHUNK), lambda b, c: (b, 0, chunk_at(c)))             # B, C: time along the lanes
    a = pl.BlockSpec((N, channels), lambda b, c: (0, 0))
    d = pl.BlockSpec((1, channels), lambda b, c: (0, 0))
    states = pl.BlockSpec((1, 1, N, channels), lambda b, c: (b, chunk_at(c), 0, 0))
    return rows, cols, a, d, states


def scan_fwd(u, delta, a_t, b_t, c_t, d, interpret: bool, state_dtype=jnp.float32):
    """u (Bt, S, channels), delta the same in float32, ``a_t`` (N, channels) float32 (``a`` transposed), ``b_t``, ``c_t``
    (Bt, N, S), ``d`` (1, channels) float32; S a multiple of ``CHUNK`` -> y (Bt, S, channels) in u's type and every
    chunk's incoming state (Bt, S / CHUNK, N, channels) float32. ``state_dtype``: the state is rounded to it after every
    token (float32: not at all; a test's control)."""
    Bt, S, channels = u.shape
    N, nc = a_t.shape[0], S // CHUNK
    width = _tile_width(channels, 512)
    rows, cols, a, dspec, states = _blocks(S, channels, N, lambda c: c)
    f32 = jnp.float32
    item = u.dtype.itemsize
    vmem = 2 * CHUNK * channels * (2 * item + 4) + 2 * CHUNK * channels * 4 + 5 * N * channels * 4 + 2 * CHUNK * N * LANES * 4
    return pl.pallas_call(
        functools.partial(_fwd_kernel, width=width, state_dtype=state_dtype),
        name="ssm_scan_fwd",
        grid=(Bt, nc),
        in_specs=[rows, rows, a, cols, cols, dspec],
        out_specs=[rows, states],
        out_shape=[jax.ShapeDtypeStruct(u.shape, u.dtype), jax.ShapeDtypeStruct((Bt, nc, N, channels), f32)],
        scratch_shapes=[pltpu.VMEM((N, channels), f32), pltpu.VMEM((CHUNK, N, LANES), f32), pltpu.VMEM((CHUNK, N, LANES), f32),
                        pltpu.VMEM((CHUNK, channels), f32), pltpu.VMEM((CHUNK, channels), f32)],
        interpret=interpret,
        compiler_params=_compiler_params("parallel", "arbitrary", interpret=interpret, vmem_bytes=vmem),
    )(u, delta, a_t, b_t, c_t, d)


def scan_bwd(u, delta, a_t, b_t, c_t, d, states, dy, interpret: bool):
    """The gradients of ``scan_fwd``'s y to u (its type), delta (float32), ``a_t`` and ``d`` (one a sequence: (Bt, N,
    channels) and (Bt, 1, channels), for the caller to add up) and, as partial sums along the lanes, to B and C: (Bt, S,
    N, 128) float32 whose last dimension the caller sums. Chunks are walked from the last to the first, each from the
    incoming state ``scan_fwd`` saved for it."""
    Bt, S, channels = u.shape
    N, nc = a_t.shape[0], S // CHUNK
    width = _tile_width(channels, 256)
    rows, cols, a, dspec, st = _blocks(S, channels, N, lambda c: nc - 1 - c)
    per_seq = lambda n: pl.BlockSpec((1, n, channels), lambda b, c: (b, 0, 0))
    partial = pl.BlockSpec((1, CHUNK, N, LANES), lambda b, c: (b, nc - 1 - c, 0, 0))
    f32 = jnp.float32
    item = u.dtype.itemsize
    vmem = (2 * CHUNK * channels * (3 * item + 8) + 3 * CHUNK * channels * 4 + 6 * N * channels * 4 + 6 * CHUNK * N * LANES * 4
            + CHUNK * N * width * 4)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, width=width),
        name="ssm_scan_bwd",
        grid=(Bt, nc),
        in_specs=[rows, rows, a, cols, cols, dspec, st, rows],
        out_specs=[rows, rows, per_seq(N), partial, partial, per_seq(1)],
        out_shape=[jax.ShapeDtypeStruct(u.shape, u.dtype), jax.ShapeDtypeStruct(u.shape, f32),
                   jax.ShapeDtypeStruct((Bt, N, channels), f32), jax.ShapeDtypeStruct((Bt, S, N, LANES), f32),
                   jax.ShapeDtypeStruct((Bt, S, N, LANES), f32), jax.ShapeDtypeStruct((Bt, 1, channels), f32)],
        scratch_shapes=[pltpu.VMEM((N, channels), f32), pltpu.VMEM((CHUNK, N, LANES), f32), pltpu.VMEM((CHUNK, N, LANES), f32),
                        pltpu.VMEM((CHUNK, channels), f32), pltpu.VMEM((CHUNK, channels), f32), pltpu.VMEM((CHUNK, channels), f32),
                        pltpu.VMEM((CHUNK, N, width), f32)],
        interpret=interpret,
        compiler_params=_compiler_params("parallel", "arbitrary", interpret=interpret, vmem_bytes=vmem),
    )(u, delta, a_t, b_t, c_t, d, states, dy)
