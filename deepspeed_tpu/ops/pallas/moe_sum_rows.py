"""Each token's sum of its own rows in a buffer sorted by group: by held expert, or by the chip a row travels to.

A routed FFN (``moe/sharded_moe.py::held_experts``) sorts its (token, choice)
pairs by expert with a stable sort, and a pair's index is ``token * k +
choice`` with distinct experts a token, so inside one expert's group the rows'
tokens ascend strictly. For ``TOKENS`` consecutive tokens and one held expert,
the rows that belong to them are therefore ONE contiguous span of the buffer,
of at most ``TOKENS`` rows and a few tens in the usual case. ``out[t] = sum
over the rows r of token t of w[r] * rows[r]`` is then, a token tile, one
short read an expert: no (tokens, k, d) tensor, and no scatter.

Where the rows are exchanged between chips (``exchanged_experts``) the sender
sorts its pairs the same way by the CHIP that holds the expert, and the
groups are the chips: inside a chip's rows the tokens ascend, not strictly (a
token may send one chip as many rows as it has experts there, side by side:
two weights in its row of the placing matrix), and a tile's rows are again one
span, of ``TOKENS`` times that many rows at most. Two layouts, which differ in
where a group begins and in nothing the kernel reads but ``spans``: PACKED
(``held_experts``), a group begins where the ones before it end and the rows
past the last group are not defined; PADDED (the exchange's slabs), group
``c`` begins at ``c * slab`` and is cut at ``(c + 1) * slab``, and every slot
holds numbers, an empty one too (what came back for it, or its cotangent, is
the other chip's sum over no row: zeros).

A grid step is a token tile. It copies, for each of the ``n`` groups (held experts, or chips), the
``ROWS`` rows (``window_rows(n)``: 128 up to eight held experts, fewer beyond, so
that the placing product stays (TOKENS, 1024) x (1024, d) and the windows of
32 experts take the VMEM that those of 8 do; a tile of 256 tokens sends an
expert a few tens of rows at most where the router is near balance) from the span's start (rounded down to ``SHIFT`` rows, a bf16
tile's sublanes) into its own slice of one (n * ROWS, d) buffer in VMEM, and
places every row by ONE product on the MXU: a (TOKENS, n * ROWS) matrix that
holds a row's weight where the row is the token's and inside the span, times
the buffer, accumulated in float32. The weight is rounded to the rows' type
(as the backward of the combine applies it), so each product is exact in
float32. The next tile's windows are copied while this one's product runs. A
span that passes its first window (more than ``ROWS - SHIFT`` rows of one
expert in one tile: a router far from balance) takes the rest a window at a
time, added to the float32 accumulator.

A row's token and weight lie along the lanes of the placing matrix, so they
are read as one (2, ROWS) block, and a window may start at any multiple of
``SHIFT``: ``_along_lanes`` lays each of the ``ROWS / SHIFT`` shifts out as
blocks of its own (a few MB in all). The backward of the rows' gather is the
same sum with weights of one, and the same kernel.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._utils import compiler_params as _compiler_params
from ._utils import vmem_budget

TOKENS = 256  # tokens a tile
SHIFT = 16    # a window starts at a multiple of this many rows
LANES = 128   # a block of rows' tokens and weights along the lanes: the most rows a window has


def window_rows(n: int) -> int:
    """Rows a window, for ``n`` held experts: 128 while the experts' windows side by side are at most 1024 rows (n <=
    8), then the power of two that keeps them so, and no fewer than two shifts."""
    return max(2 * SHIFT, min(LANES, 1 << max(0, (1024 // n).bit_length() - 1)))


def _vmem_bytes(n: int, d: int, itemsize: int) -> int:
    """Two sets of windows, the output's two blocks, the accumulator, and the placing matrix with its int32 makings."""
    return 2 * n * window_rows(n) * d * itemsize + 2 * TOKENS * d * itemsize + TOKENS * d * 4 + 4 * TOKENS * n * window_rows(n) * 4


def fits(n_tokens: int, rows: int, d: int, n: int, dtype) -> bool:
    """Whether the kernel takes these shapes: whole token tiles (their indices exact in float32), whole windows, whole
    lanes, and VMEM for the windows of ``n`` experts."""
    return (n_tokens % TOKENS == 0 and n_tokens <= 1 << 24 and rows % LANES == 0 and d % 128 == 0
            and _vmem_bytes(n, d, jnp.dtype(dtype).itemsize) <= vmem_budget())


def spans(key, n: int, k: int, rows: int, slab: int = None):
    """For pairs (tokens * k,) keyed by group (a held expert, or the chip a
    pair travels to; ``n`` for a pair of no group), in token order: where in
    the sorted buffer the rows of each (token tile, group) begin and end,
    (2, tiles * n) int32. Inside a group a tile's rows come after those of
    the tiles before. ``slab`` None, the packed buffer (``held_experts``): a
    group begins where the ones before it end, and the spans are cut at the
    buffer's ``rows``. ``slab``, the padded one (``exchanged_experts``,
    ``rows == n * slab``): group ``c`` begins at ``c * slab`` whatever the
    others hold and is cut at ``(c + 1) * slab``, where the next begins."""
    count = jnp.sum(key.reshape(-1, TOKENS * k, 1) == jnp.arange(n), axis=1, dtype=jnp.int32)  # (tiles, n)
    if slab is None:
        sizes = jnp.sum(count, axis=0)
        begins, ends = jnp.cumsum(sizes) - sizes, rows
    else:
        begins = slab * jnp.arange(n, dtype=jnp.int32)
        ends = jnp.tile(begins + slab, count.shape[0])  # (tiles * n,): each span's own group's
    lo = begins[None] + jnp.cumsum(count, axis=0) - count
    return jnp.minimum(jnp.stack([lo, lo + count]).reshape(2, -1), ends)


def _along_lanes(tok_of_row, w_row):
    """(R,) tokens and weights -> (LANES / SHIFT, R / LANES, 2, LANES) float32:
    copy s holds both from row ``SHIFT * s`` on, so the ``LANES`` rows from
    any multiple of ``SHIFT`` are one (2, LANES) block of one copy, their
    tokens above their weights (a token index is exact in float32: ``fits``);
    a window is the first ``ROWS`` lanes of its block."""
    both = jnp.stack([tok_of_row.astype(jnp.float32), w_row.astype(jnp.float32)])
    shifted = lambda s: jnp.pad(both[:, SHIFT * s:], ((0, 0), (0, SHIFT * s))).reshape(2, -1, LANES).swapaxes(0, 1)
    return jnp.stack([shifted(s) for s in range(LANES // SHIFT)])


def _first_row(lo, R: int, ROWS: int):
    """Where a span's first window starts: the span's start rounded down to ``SHIFT`` rows, kept inside the buffer.
    Every count here is >= 0, so the truncating division is the floor, and lowers to one operation."""
    return jnp.minimum(jax.lax.div(lo, SHIFT) * SHIFT, R - ROWS)


def _kernel(lo_ref, hi_ref, rows_hbm, lanes_hbm, inside_ref, out_ref, xs, tw, sems, acc, *, n, tiles):
    R, ROWS = rows_hbm.shape[0], window_rows(n)
    i = pl.program_id(0)
    half = jax.lax.rem(i, 2)  # which set of windows this tile's were copied into
    # the last span's end (the last group's rows end it in either layout): no span reaches past it, and past it a
    # PACKED buffer is not defined (a PADDED one is, and the slots zeroed there are empty ones)
    routed = hi_ref[tiles * n - 1]

    def span(tile, e):
        lo, hi = lo_ref[tile * n + e], hi_ref[tile * n + e]
        return lo, hi, _first_row(lo, R, ROWS)

    def window(at):
        return pl.ds(pl.multiple_of(at * ROWS, ROWS), ROWS)

    def copies(start, at):
        start = pl.multiple_of(start, SHIFT)
        shift, block = jax.lax.div(jax.lax.rem(start, LANES), SHIFT), jax.lax.div(start, LANES)
        return (pltpu.make_async_copy(rows_hbm.at[pl.ds(start, ROWS)], xs.at[window(at)], sems.at[0, at]),
                pltpu.make_async_copy(lanes_hbm.at[shift, block], tw.at[at], sems.at[1, at]))

    def each_span(tile, half, do):
        """``do(first row, slot)`` for every expert with rows in the tile."""
        def one(e, carry):
            lo, hi, first = span(tile, e)
            pl.when(hi > lo)(lambda: do(first, half * n + e))
            return carry

        jax.lax.fori_loop(0, n, one, 0)

    def start(first, at):
        for copy in copies(first, at):
            copy.start()

    def arrived(first, at):
        """Wait for the window; past the routed rows a packed buffer is not defined
        (a grouped product writes its groups' rows only): zeros, not 0 x NaN."""
        for copy in copies(first, at):
            copy.wait()

        @pl.when(first + ROWS > routed)
        def _():
            x = xs[window(at), :]
            xs[window(at), :] = jnp.where(first + jax.lax.broadcasted_iota(jnp.int32, (ROWS, 1), 0) < routed, x, jnp.zeros_like(x))

    @pl.when(i == 0)
    def _first():
        xs[...] = jnp.zeros_like(xs)  # a window never copied is multiplied by zeros: it must hold numbers
        each_span(0, 0, start)

    pl.when(i + 1 < tiles)(lambda: each_span(i + 1, 1 - half, start))
    each_span(i, half, arrived)

    def places(inside, lanes):
        """The placing matrix, in the rows' type: a row's weight where it is inside its span and is token t's."""
        tok = jnp.where(inside, lanes[0:1].astype(jnp.int32) - i * TOKENS, -1)
        mine = tok == jax.lax.broadcasted_iota(jnp.int32, (TOKENS, tok.shape[1]), 0)
        return jnp.where(mine, lanes[1:2], 0.0).astype(xs.dtype)

    dot = functools.partial(jnp.dot, preferred_element_type=jnp.float32,
                            precision=jax.lax.Precision.HIGHEST if xs.dtype == jnp.float32 else None)
    lanes = (lambda at: tw[at]) if ROWS == LANES else (lambda at: tw[at][:, :ROWS])  # a window's rows lead its block
    beside = jnp.concatenate([lanes(half * n + e) for e in range(n)], axis=1)  # the experts' (2, ROWS) blocks, along the lanes
    acc[...] = dot(places(inside_ref[...] != 0, beside), xs[pl.ds(pl.multiple_of(half * n * ROWS, ROWS), n * ROWS), :])

    def rest_of(e, carry):  # a span that passes its first window: the rest of it, a window at a time
        lo, hi, first = span(i, e)
        at = half * n + e

        def more(j, carry):
            nominal = first + j * ROWS
            begin = jnp.minimum(nominal, R - ROWS)
            start(begin, at)
            arrived(begin, at)
            r = begin + jax.lax.broadcasted_iota(jnp.int32, (1, ROWS), 1)
            acc[...] += dot(places((r >= nominal) & (r < hi), lanes(at)), xs[window(at), :])
            return carry

        return jax.lax.fori_loop(1, jnp.where(hi > lo, jax.lax.div(hi - first + ROWS - 1, ROWS), 0), more, carry)

    jax.lax.fori_loop(0, n, rest_of, 0)
    out_ref[...] = acc[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("n_tokens", "interpret"))  # a step calls it a dozen times at two shapes: traced and lowered once a shape
def sum_rows(rows, tok_of_row, w_row, spans, n_tokens: int, interpret: bool = False):
    """``out[t] = sum of w_row[r] * rows[r]`` over the rows r of token t inside
    the spans (``spans`` above; ``fits`` holds): rows (R, d) sorted by group
    in either layout, ``tok_of_row`` (R,) int32, ``w_row`` (R,) float32 (both
    finite in every slot, whatever an empty one holds) -> (n_tokens, d) in
    the rows' type, summed in float32."""
    R, d = rows.shape
    tiles = n_tokens // TOKENS
    n = spans.shape[1] // tiles
    ROWS = window_rows(n)
    lo, hi = spans[0][:, None], spans[1][:, None]
    r = _first_row(lo, R, ROWS) + jnp.arange(ROWS)  # (tiles * n, ROWS): the rows of every span's first window ...
    inside = ((r >= lo) & (r < hi)).astype(jnp.int32).reshape(tiles, 1, n * ROWS)  # ... and whether each is in its span
    return pl.pallas_call(
        functools.partial(_kernel, n=n, tiles=tiles),
        name="moe_sum_rows",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(tiles,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY), pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec((None, 1, n * ROWS), lambda i, lo_ref, hi_ref: (i, 0, 0))],
            out_specs=pl.BlockSpec((TOKENS, d), lambda i, lo_ref, hi_ref: (i, 0)),
            scratch_shapes=[pltpu.VMEM((2 * n * ROWS, d), rows.dtype), pltpu.VMEM((2 * n, 2, LANES), jnp.float32),
                            pltpu.SemaphoreType.DMA((2, 2 * n)), pltpu.VMEM((TOKENS, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((n_tokens, d), rows.dtype),
        interpret=interpret,
        # "arbitrary": a tile's windows are copied during the tile before it, so the tiles run in order
        compiler_params=_compiler_params("arbitrary", interpret=interpret, vmem_bytes=_vmem_bytes(n, d, rows.dtype.itemsize)),
    )(spans[0], spans[1], rows, _along_lanes(tok_of_row, w_row), inside)
