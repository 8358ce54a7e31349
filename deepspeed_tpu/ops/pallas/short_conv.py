"""The gated short convolution of a ``conv`` layer, between its two products: ``x = [B, C, u]`` (Bt, S, 3 D), three
chunks of ``D`` channels in that order, and one filter of ``K`` taps a channel ``w`` (K, D)::

    g = B * u;    c_t = sum_j w_j g_{t - (K - 1) + j};    out = C * c        (Bt, S, D), in x's type

Elementwise but for the ``K - 1`` rows a token reaches back, so the least it can cost is its traffic: ``x`` read and
``out`` written once forward (8 bytes a channel and token in bf16); ``x`` and the output's cotangent read and ``x``'s
cotangent written once backward (14). One Pallas call each way (``short_conv_fwd``, ``short_conv_bwd``), a tile of rows
at the full width a grid step. No state is carried between tiles: the rows a tile reaches back to (and, backward, the
rows ahead whose cotangents reach back into it) come as a halo block of ``HALO`` rows of the same arrays, 6% more
traffic at 256 rows a tile, so every tile is its own program. Arithmetic in float32, rounded once.

The wrap-around of a rotated tile is wrong in its first (backward: also its last) ``K - 1`` rows only: the whole tile
is computed from rotations and the ``HALO`` rows at that end are made again from the halo block and stored over it,
so no select runs over the tile.

Off the TPU and on a mesh of several chips the layer runs the definition under XLA's fusions
(``models/mixers.py::gated_conv``, which is also the kernel's oracle): ``path_for`` says which."""

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import placement
from ._utils import compiler_params

# The name the forward call's output carries for a checkpoint policy: a checkpointed block keeps it
# (``models/transformer.py::remat_keeps``), so its backward does not run the forward call a second time
SAVED = "short_conv"
HALO = 16  # rows of a halo block, and of a tile's end that is made again from it: one bf16 tile's sublanes
TAPS = 8  # the most taps a filter may have: the rows of the block its gradient is summed in, one float32 tile's


def rows_a_tile(S: int) -> int:
    """The rows of a grid step: the largest listed count that divides ``S``, 0 where none does."""
    return next((t for t in (256, 128, 64, 32, 16) if S % t == 0), 0)


def fits(S: int, D: int, K: int) -> bool:
    """Whether the kernels take these shapes: whole tiles of rows, whole vregs of lanes, a filter of at most ``TAPS``."""
    return rows_a_tile(S) > 0 and D % 128 == 0 and 2 <= K <= TAPS


def path_for(S: int, D: int, K: int) -> str:
    """The rule's word (``placement.kernel_path``) at these shapes: the kernels sit in no ``shard_map`` yet."""
    return placement.kernel_path(fits(S, D, K), has_specs=False)


def _lanes(D: int) -> int:
    return next(n for n in (512, 256, 128) if D % n == 0)


def _back(x, before, back: int):
    """``x`` (T, L) a tile's rows: row t of the result is x[t - back], wrong in the first ``back`` rows; and those
    HALO rows made right from ``before`` (HALO, L), the rows just ahead of the tile."""
    row = jax.lax.broadcasted_iota(jnp.int32, before.shape, 0)
    edge = jnp.where(row < back, pltpu.roll(before, back, 0), pltpu.roll(x[:HALO], back, 0))
    return pltpu.roll(x, back, 0), edge


def _ahead(x, after, ahead: int):
    """Row t of the result is x[t + ahead], wrong in the last ``ahead`` rows; and the last HALO rows made right from
    ``after`` (HALO, L), the rows just past the tile."""
    T = x.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, after.shape, 0)
    edge = jnp.where(row >= HALO - ahead, pltpu.roll(after, HALO - ahead, 0), pltpu.roll(x[T - HALO:], HALO - ahead, 0))
    return pltpu.roll(x, T - ahead, 0), edge


def _taps(x, edge_rows, w, shift, K: int):
    """``sum_j w_j shift(x, K - 1 - j)`` over a tile and over its edge: ((T, L), (HALO, L)), and the shifted tiles and
    edges by tap (the backward's filter gradient reads them)."""
    T = x.shape[0]
    own_edge = x[:HALO] if shift is _back else x[T - HALO:]
    tiles, edges = [None] * K, [None] * K
    tiles[K - 1], edges[K - 1] = x, own_edge
    for j in range(K - 1):
        tiles[j], edges[j] = shift(x, edge_rows, K - 1 - j)
    total = lambda parts: functools.reduce(lambda a, b: a + b, (part * w[j:j + 1] for j, part in enumerate(parts)))
    return total(tiles), total(edges), tiles, edges


def _fwd_kernel(x_ref, prev_ref, w_ref, o_ref, *, D: int, K: int):
    T, L = x_ref.shape[1], _lanes(D)
    first = pl.program_id(1) == 0
    for at in range(0, D, L):
        part = lambda ref, n: ref[0, :, pl.ds(n * D + at, L)].astype(jnp.float32)
        g = part(x_ref, 0) * part(x_ref, 2)
        before = jnp.where(first, 0.0, part(prev_ref, 0) * part(prev_ref, 2))
        c, c_edge, _, _ = _taps(g, before, w_ref[:, pl.ds(at, L)], _back, K)
        C = part(x_ref, 1)
        o_ref[0, :, pl.ds(at, L)] = (C * c).astype(o_ref.dtype)
        o_ref[0, :HALO, pl.ds(at, L)] = (C[:HALO] * c_edge).astype(o_ref.dtype)


def _bwd_kernel(x_ref, prev_ref, next_ref, dy_ref, dy_next_ref, w_ref, dx_ref, dw_ref, *, D: int, K: int):
    T, L = x_ref.shape[1], _lanes(D)
    first, last = pl.program_id(1) == 0, pl.program_id(1) == pl.num_programs(1) - 1

    @pl.when(first)
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    for at in range(0, D, L):
        part = lambda ref, n: ref[0, :, pl.ds(n * D + at, L)].astype(jnp.float32)
        w = w_ref[:, pl.ds(at, L)]
        B, C, u, dy = part(x_ref, 0), part(x_ref, 1), part(x_ref, 2), part(dy_ref, 0)
        g = B * u
        before = jnp.where(first, 0.0, part(prev_ref, 0) * part(prev_ref, 2))
        c, c_edge, g_back, g_back_edge = _taps(g, before, w, _back, K)
        dc = dy * C
        after = jnp.where(last, 0.0, part(dy_next_ref, 0) * part(next_ref, 1))
        # g's cotangent: the filter's transpose, which reaches AHEAD: tap j of row t + (K - 1 - j)
        dg, dg_edge, _, _ = _taps(dc, after, w, _ahead, K)

        def put(n, value, rows=slice(None)):  # chunk n of x's cotangent: B's, C's, u's
            dx_ref[0, rows, pl.ds(n * D + at, L)] = value.astype(dx_ref.dtype)

        head, tail = slice(0, HALO), slice(T - HALO, T)
        put(0, dg * u), put(0, dg_edge * u[tail], tail)
        put(1, dy * c), put(1, dy[head] * c_edge, head)
        put(2, dg * B), put(2, dg_edge * B[tail], tail)
        # the filter's: tap j meets dc_t with g_{t - (K - 1 - j)}; the rotated tile's first rows are put right by the edge
        for j in range(K):
            wrong = 0.0 if j == K - 1 else jnp.sum(dc[:HALO] * (g_back_edge[j] - g_back[j][:HALO]), axis=0, keepdims=True)
            dw_ref[0, j:j + 1, pl.ds(at, L)] += jnp.sum(dc * g_back[j], axis=0, keepdims=True) + wrong


def _halo(T: int, S: int, ahead: bool):
    """The index map of a halo block of HALO rows: the rows just past a tile, or just before it (clamped at the
    sequence's ends, where the kernel takes zeros instead)."""
    per = T // HALO
    if ahead:
        return lambda b, s: (b, jnp.minimum((s + 1) * per, S // HALO - 1), 0)
    return lambda b, s: (b, jnp.maximum(s * per - 1, 0), 0)


def _vmem(T: int, D: int, blocks: int, itemsize: int) -> int:
    """Double-buffered blocks of ``blocks`` widths of D at T rows, their halos, and a dozen float32 tiles of a chunk."""
    return 2 * blocks * (T + 2 * HALO) * D * itemsize + 12 * T * _lanes(D) * 4


@functools.partial(jax.jit, static_argnames=("interpret",))
def fwd(x, w, interpret: bool = False):
    Bt, S, D3 = x.shape
    K, D = w.shape
    T = rows_a_tile(S)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, D=D, K=K),
        grid=(Bt, S // T),
        in_specs=[pl.BlockSpec((1, T, D3), lambda b, s: (b, s, 0)), pl.BlockSpec((1, HALO, D3), _halo(T, S, False)),
                  pl.BlockSpec((K, D), lambda b, s: (0, 0))],
        out_specs=pl.BlockSpec((1, T, D), lambda b, s: (b, s, 0)),
        out_shape=jax.ShapeDtypeStruct((Bt, S, D), x.dtype),
        compiler_params=compiler_params("parallel", "parallel", interpret=interpret, vmem_bytes=_vmem(T, D, 4, x.dtype.itemsize)),
        interpret=interpret, name="short_conv_fwd",
    )(x, x, w.astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("interpret",))
def bwd(x, w, dy, interpret: bool = False):
    """-> (x's cotangent in x's type, w's (K, D) float32)."""
    Bt, S, D3 = x.shape
    K, D = w.shape
    T = rows_a_tile(S)
    rows = lambda width: pl.BlockSpec((1, T, width), lambda b, s: (b, s, 0))
    dx, dw = pl.pallas_call(
        functools.partial(_bwd_kernel, D=D, K=K),
        grid=(Bt, S // T),
        in_specs=[rows(D3), pl.BlockSpec((1, HALO, D3), _halo(T, S, False)), pl.BlockSpec((1, HALO, D3), _halo(T, S, True)),
                  rows(D), pl.BlockSpec((1, HALO, D), _halo(T, S, True)), pl.BlockSpec((K, D), lambda b, s: (0, 0))],
        # the filter's gradient is summed over a sequence's tiles in its resident block, a sequence a block
        out_specs=[rows(D3), pl.BlockSpec((1, TAPS, D), lambda b, s: (b, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype), jax.ShapeDtypeStruct((Bt, TAPS, D), jnp.float32)],
        compiler_params=compiler_params("parallel", "arbitrary", interpret=interpret, vmem_bytes=_vmem(T, D, 7, x.dtype.itemsize)),
        interpret=interpret, name="short_conv_bwd",
    )(x, x, x, dy, dy, w.astype(jnp.float32))
    return dx, jnp.sum(dw, axis=0)[:K]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def short_conv(x, w, interpret: bool = False):
    """``out = C * conv(B * u)`` by the kernels; differentiated, its backward is the one call ``bwd``."""
    return fwd(x, w, interpret)


def _short_conv_fwd(x, w, interpret):
    return checkpoint_name(fwd(x, w, interpret), SAVED), (x, w)


def _short_conv_bwd(interpret, kept, dy):
    x, w = kept
    dx, dw = bwd(x, w, dy.astype(x.dtype), interpret)
    return dx, dw.astype(w.dtype)


short_conv.defvjp(_short_conv_fwd, _short_conv_bwd)
