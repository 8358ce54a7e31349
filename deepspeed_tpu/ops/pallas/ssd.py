"""Pallas chunked scan for a Mamba-2 layer (state-space duality, SSD): ONE decay a head and token, B and C shared by the
heads of a group, no delta rule and so no inverse.

For one head of ``P`` channels with a state ``S`` in R^{P x N} that starts at zero, ``a_t = delta_t A <= 0``::

    S_t = exp(a_t) S_{t-1} + delta_t x_t B_t^T,    y_t = S_t C_t + D x_t

The token-by-token form is ``ops/ssd.py::ssd_recurrence`` (the path off the TPU and this kernel's oracle). Here the
sequence is cut into chunks of ``CHUNK`` tokens. With ``G_t`` the cumulative log-decay from the chunk's start and ``L_ts
= exp(G_t - G_s)`` for ``s <= t`` (no exponent is ever positive), a chunk is three kinds of product on the MXU::

    Y  = ((C B^T) * L * delta_s) X  +  exp(G) * (C S_0^T)  +  D X
    S_1 = exp(G_last) S_0 + (X * w)^T B,    w_s = exp(G_last - G_s) delta_s

``C B^T`` is made once a GROUP and serves its heads; the state is carried over the chunks in VMEM, in float32. A grid
step is one (sequence, group, chunk): the group's ``heads * P`` channels lie along the lanes as the projection left them
(no transpose around the call), in tiles of 128 lanes, ``128 / P`` heads a tile: a head's ``(C B^T) * L`` times the whole
tile costs the MXU what its own 64 columns would, and a select by lane keeps each head's half.

What a head and token carry (``G``, ``w``, ``delta``) is a few floats: ``ops/ssd.py`` makes them in XLA, the cumulative
sums included, and hands them in both ways round, a token a row (``cols``: a head's column scales rows) and a token a lane
(``lanes``: a head's row scales columns), so the kernel transposes nothing. The backward walks the chunks from the last
to the first with the state's cotangent in VMEM and gets a chunk's gradients as ``jax.vjp`` of the SAME chunk function
on the state the forward saved for that chunk (``ops/pallas/kda.py`` does likewise); the function takes and returns its
tiles, columns and rows as lists, so the transpose assembles nothing along the lanes."""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._utils import compiler_params as _compiler_params

CHUNK = 128  # tokens a grid step: the published ``chunk_size``
LANES = 128  # channels a tile

_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


def fits(heads: int, head_dim: int, groups: int, state: int) -> bool:
    """Whether the kernels take these sizes: a head's channels divide a tile of 128 lanes and fill whole sublanes, a
    group's fill whole tiles, and a group's B and C are whole tiles of lanes."""
    per_group = heads // max(groups, 1) * head_dim
    return heads % max(groups, 1) == 0 and LANES % head_dim == 0 and head_dim % 8 == 0 and per_group % LANES == 0 and state % LANES == 0


def _dot(a, b, dims, mm):
    return jax.lax.dot_general(a.astype(mm), b.astype(mm), dims, preferred_element_type=jnp.float32,
                               precision=jax.lax.Precision.HIGHEST if mm == jnp.float32 else None)


def _by_head(values, which):
    """Each head's value where ``which`` (an iota over a tile's lanes or rows, divided by ``P``) says the head is."""
    out = values[-1]
    for k in range(len(values) - 2, -1, -1):
        out = jnp.where(which == k, values[k], out)
    return out


def chunk_fn(xs, Bm, Cm, gcs, wcs, gls, dts, ds, states, P, mm):
    """One chunk of one group. ``xs``: its tiles (Q, 128) of x; ``Bm``, ``Cm`` (Q, N); a head's ``gcs`` (Q, 1) cumulative
    log-decay and ``wcs`` (Q, 1) ``exp(G_last - G) delta`` down the sublanes, its ``gls`` (1, Q) cumulative log-decay and
    ``dts`` (1, Q) step along the lanes, all float32; ``ds``: ``D`` a channel, (1, 128) a tile; ``states``: the incoming
    state's tiles (128, N) float32, a channel a row. Returns (the tiles of y (Q, 128) float32, the outgoing state's
    tiles). ``mm``: the operand type of the products."""
    f32 = jnp.float32
    Q, N = Bm.shape
    hpt = LANES // P
    row, col = (jax.lax.broadcasted_iota(jnp.int32, (Q, Q), d) for d in (0, 1))
    causal = row >= col
    lane_head = jax.lax.broadcasted_iota(jnp.int32, (Q, LANES), 1) // P
    row_head = jax.lax.broadcasted_iota(jnp.int32, (LANES, 1), 0) // P
    pairs = _dot(Cm, Bm, _NT, mm)  # (Q, Q): C_t . B_s, once a group
    ys, out = [], []
    for j, (x, S0) in enumerate(zip(xs, states)):
        heads = range(j * hpt, (j + 1) * hpt)
        x32 = x.astype(f32)
        parts = []
        for i in heads:
            decay = jnp.exp(jnp.where(causal, gcs[i] - gls[i], 0.0))  # exp(G_t - G_s), s <= t
            parts.append(_dot(jnp.where(causal, pairs * decay * dts[i], 0.0), x, _NN, mm))  # the whole tile; its own lanes are kept
        from_state = _dot(Cm, S0, _NT, mm) * _by_head([jnp.exp(gcs[i]) for i in heads], lane_head)
        ys.append(_by_head(parts, lane_head) + from_state + ds[j] * x32)
        into = _dot(x32 * _by_head([wcs[i] for i in heads], lane_head), Bm, _TN, mm)  # (128, N)
        # (a head's last decay goes down the sublanes first and along the lanes in the product: Mosaic has no broadcast both ways at once)
        out.append(S0 * _by_head([jnp.broadcast_to(jnp.exp(gcs[i][Q - 1:Q]), (LANES, 1)) for i in heads], row_head) + into)
    return ys, out


def _operands(x_ref, b_ref, c_ref, cols_ref, lanes_ref, d_ref, state, P):
    """The chunk function's lists, read off a grid step's blocks and the state (a ref, or the saved state's block)."""
    W = x_ref.shape[-1]
    hpg, tiles = W // P, [slice(j * LANES, (j + 1) * LANES) for j in range(W // LANES)]
    cols, lanes = cols_ref[0, 0], lanes_ref[0, 0]  # (Q, 2 hpg), (2 hpg, Q)
    return ([x_ref[0, :, t] for t in tiles], b_ref[0], c_ref[0], [cols[:, i:i + 1] for i in range(hpg)],
            [cols[:, hpg + i:hpg + i + 1] for i in range(hpg)], [lanes[i:i + 1] for i in range(hpg)],
            [lanes[hpg + i:hpg + i + 1] for i in range(hpg)], [d_ref[:, t] for t in tiles], [state[t, :] for t in tiles]), tiles


def _fwd_kernel(x_ref, b_ref, c_ref, cols_ref, lanes_ref, d_ref, y_ref, st_ref, state, *, P, mm):
    @pl.when(pl.program_id(2) == 0)
    def _zero():
        state[...] = jnp.zeros_like(state)

    st_ref[0, 0, 0] = state[...]  # what this chunk started from: the backward's residual
    operands, tiles = _operands(x_ref, b_ref, c_ref, cols_ref, lanes_ref, d_ref, state, P)
    ys, out = chunk_fn(*operands, P, mm)
    for t, y, s in zip(tiles, ys, out):
        y_ref[0, :, t] = y.astype(y_ref.dtype)
        state[t, :] = s


def _placed(pieces, shape, axis):
    """(1, Q) rows or (Q, 1) columns, one a place along ``axis`` of ``shape``, as one array: selects against an iota, so
    nothing is joined along the lanes."""
    at = jax.lax.broadcasted_iota(jnp.int32, shape, axis)
    out = jnp.zeros(shape, jnp.float32)
    for k, piece in enumerate(pieces):
        out = jnp.where(at == k, piece, out)
    return out


def _bwd_kernel(x_ref, b_ref, c_ref, cols_ref, lanes_ref, d_ref, st_ref, dy_ref, dx_ref, db_ref, dc_ref, dcols_ref, dlanes_ref,
                dd_ref, dstate, *, P, mm):
    @pl.when(pl.program_id(2) == 0)
    def _zero():  # the last chunk: nothing reads the state after it
        dstate[...] = jnp.zeros_like(dstate)

    operands, tiles = _operands(x_ref, b_ref, c_ref, cols_ref, lanes_ref, d_ref, st_ref.at[0, 0, 0], P)
    _, vjp = jax.vjp(lambda *xs: chunk_fn(*xs, P, mm), *operands)
    dxs, dB, dC, dgcs, dwcs, dgls, ddts, dds, dstates = vjp(([dy_ref[0, :, t].astype(jnp.float32) for t in tiles], [dstate[t, :] for t in tiles]))
    for t, dx, dd, ds in zip(tiles, dxs, dds, dstates):
        dx_ref[0, :, t] = dx.astype(dx_ref.dtype)
        dd_ref[0, 0, 0, :, t] = dd
        dstate[t, :] = ds
    db_ref[0] = dB.astype(db_ref.dtype)
    dc_ref[0] = dC.astype(dc_ref.dtype)
    dcols_ref[0, 0] = _placed(dgcs + dwcs, dcols_ref.shape[2:], 1)
    dlanes_ref[0, 0] = _placed(dgls + ddts, dlanes_ref.shape[2:], 0)


def _mm_dtype(x):
    return jnp.float32 if x.dtype == jnp.float32 else jnp.bfloat16


def _vmem(W: int, N: int, itemsize: int, backward: bool) -> int:
    """What a grid step holds in VMEM, from the shapes: its blocks, each twice (the pipeline fetches the next step's while
    this one's are worked on), the state, and the body's (CHUNK, CHUNK) and (CHUNK, 128) float32 temporaries."""
    rows, f32 = CHUNK * itemsize, 4
    blocks = rows * (2 * W + 2 * N) + W * N * f32 + 4 * CHUNK * LANES * f32  # x, y or dy, B, C, the saved state, cols and lanes (padded)
    if backward:
        blocks += rows * (W + 2 * N) + 4 * CHUNK * LANES * f32  # dx, dB, dC, dcols and dlanes
    return 2 * blocks + W * N * f32 + (96 if backward else 32) * CHUNK * LANES * f32


def _specs(W, N, hpg, chunk_at):
    rows = lambda width: pl.BlockSpec((1, CHUNK, width), lambda b, g, c: (b, chunk_at(c), g))
    cols = pl.BlockSpec((1, 1, CHUNK, 2 * hpg), lambda b, g, c: (b, g, chunk_at(c), 0))
    lanes = pl.BlockSpec((1, 1, 2 * hpg, CHUNK), lambda b, g, c: (b, g, 0, chunk_at(c)))
    d = pl.BlockSpec((1, W), lambda b, g, c: (0, g))
    saved = pl.BlockSpec((1, 1, 1, W, N), lambda b, g, c: (b, g, chunk_at(c), 0, 0))
    return rows, cols, lanes, d, saved


# jitted for the trace's sake, not the program's (they are inlined where they are called): the layers of one shape share
# ONE trace of the body a process (``ops/pallas/kda.py`` says what that is worth)
@functools.partial(jax.jit, static_argnums=(6, 7))
def scan_fwd(x, Bm, Cm, cols, lanes, d, P, interpret):
    """x (Bt, S, H P), S a multiple of ``CHUNK``; Bm, Cm (Bt, S, G N); cols (Bt, G, S, 2 H/G) float32, a head's cumulative
    log-decay (from its chunk's start) then its ``exp(G_last - G) delta``; lanes (Bt, G, 2 H/G, S) float32, its cumulative
    log-decay then ``delta``; d (1, H P) float32 -> y (Bt, S, H P) in x's type and every chunk's incoming state (Bt, G,
    S / CHUNK, H P / G, N) float32."""
    Bt, S, width = x.shape
    G, hpg = cols.shape[1], cols.shape[3] // 2
    W, N, nc = width // G, Bm.shape[2] // G, S // CHUNK
    rows, col_spec, lane_spec, d_spec, saved = _specs(W, N, hpg, lambda c: c)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, P=P, mm=_mm_dtype(x)),
        name="ssd_scan_fwd",
        grid=(Bt, G, nc),
        in_specs=[rows(W), rows(N), rows(N), col_spec, lane_spec, d_spec],
        out_specs=[rows(W), saved],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype), jax.ShapeDtypeStruct((Bt, G, nc, W, N), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((W, N), jnp.float32)],
        interpret=interpret,
        compiler_params=_compiler_params("parallel", "parallel", "arbitrary", interpret=interpret, vmem_bytes=_vmem(W, N, x.dtype.itemsize, False)),
    )(x, Bm, Cm, cols, lanes, d)


@functools.partial(jax.jit, static_argnums=(8, 9))
def scan_bwd(x, Bm, Cm, cols, lanes, d, states, dy, P, interpret):
    """Gradients of ``scan_fwd``'s y's cotangent ``dy`` to x, Bm, Cm (their types), cols and lanes (float32) and, a chunk
    (Bt, G, S / CHUNK, 1, H P / G) for the caller to add, to d: chunks walked from the last to the first, each on the state
    ``scan_fwd`` saved for it."""
    Bt, S, width = x.shape
    G, hpg = cols.shape[1], cols.shape[3] // 2
    W, N, nc = width // G, Bm.shape[2] // G, S // CHUNK
    rows, col_spec, lane_spec, d_spec, saved = _specs(W, N, hpg, lambda c: nc - 1 - c)
    dd_spec = pl.BlockSpec((1, 1, 1, 1, W), lambda b, g, c: (b, g, nc - 1 - c, 0, 0))
    like = lambda a, dtype=None: jax.ShapeDtypeStruct(a.shape, dtype or a.dtype)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, P=P, mm=_mm_dtype(x)),
        name="ssd_scan_bwd",
        grid=(Bt, G, nc),
        in_specs=[rows(W), rows(N), rows(N), col_spec, lane_spec, d_spec, saved, rows(W)],
        out_specs=[rows(W), rows(N), rows(N), col_spec, lane_spec, dd_spec],
        out_shape=[like(x), like(Bm), like(Cm), like(cols, jnp.float32), like(lanes, jnp.float32),
                   jax.ShapeDtypeStruct((Bt, G, nc, 1, W), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((W, N), jnp.float32)],
        interpret=interpret,
        compiler_params=_compiler_params("parallel", "parallel", "arbitrary", interpret=interpret, vmem_bytes=_vmem(W, N, x.dtype.itemsize, True)),
    )(x, Bm, Cm, cols, lanes, d, states, dy)
