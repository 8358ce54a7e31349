"""What a Mamba layer's scan reads, made from the product its checkpointed block keeps, in one Pallas pass each way:
``y = silu(causal_conv(x, w) + b)`` over a RANGE OF COLUMNS of that product, ``w`` (K, D) one filter of ``K`` taps a
channel (``models/mixers.py::causal_conv``), ``b`` (D,), and the result split into the arrays its reader takes::

    c_t = sum_j w_j x_{t - (K - 1) + j} + b;    y = silu(c);    [y_0, y_1, ...] = y      (Bt, S, widths[i]) in x's type

``x`` is the WHOLE product (Bt, S, columns): the range starts at ``start`` and is ``sum(widths)`` wide, and the blocks'
index maps reach it in place, so neither a slice of the product nor a split of the result is a pass of its own. A Mamba-2
layer (``SSDMixer``) takes x, B and C from the middle of ``[z, xBC, dt]``; a Mamba-1 layer (``SSMMixer``) takes ``u`` from
the first half of ``[u, z]``: one tile program, its column range, outputs and tile count read off the operands' shapes.

Elementwise but for the ``K - 1`` rows a filter reaches back, so the least it can cost is its traffic: the columns read
and written once forward (4 bytes a channel and token in bf16); the columns and the cotangents read and the columns'
cotangent written once backward (6). One call each way (``conv_silu_fwd``, ``conv_silu_bwd``). A grid step is a tile of
rows (``scan_operands.py``'s rule: 512 where it divides) of one of ``column_steps`` equal parts of EVERY output, so a step
writes a block of each output; ``short_conv.py``'s halo blocks (``_halo``, ``HALO``) bring the rows before a tile and,
backward, the rows after it, whose pre-activation is made again from their own halo, the tile's last rows: every tile is
its own program. Float32 inside, each output rounded once.

A tile is worked on in STRIPS of ``STRIP`` rows by ``LANES`` lanes, a strip's temporaries a few vregs each, so nothing of
a tile lives in VMEM but its blocks: as whole rotated tiles (``short_conv.py``'s ``_taps``) these bodies, with twenty to
fifty vector operations a channel and token for 4 to 6 bytes, spilled every temporary through the core's one store slot,
and the forward ran at 38% of its traffic's time (``PERF.md`` section 6, PR 60). A strip reads the ``HALO`` rows before
it from the block itself (the tile's first strip from the halo block), rotates the whole and drops them, so no row is
wrong and none is patched; backward the strips go UPWARDS, a strip's cotangent reaching ahead into the rows the strip
below it just made. The strips under the first are a LOOP (``lax.fori_loop``) and not sixteen copies of its body: unrolled
the calls are a seventh to a quarter faster and the step a quarter of a percent, but every start of a program traces,
lowers and loads them, 4 s of a warm start (same section).

The filter and the bias enter as ONE float32 block (``_pack``: HALF the taps in rows [0, K), half the bias in row ``TAPS``:
``silu(c) = h tanh(h) + h`` at ``h = c / 2``) and their gradients leave in the same rows, summed over a sequence's tiles in
the resident block. The columns' cotangent leaves as one array an output; the caller's cotangent is their concatenation
between zeros (XLA fills a buffer and writes the parts into it in place, then adds the product's other columns' own). The outputs carry
no checkpoint name of their own and the residuals are the inputs: a checkpointed block keeps what it kept and its
backward runs the forward call a second time, unless the layer names the outputs (``SSDMixer`` does, by its scan's name).

Off the TPU, on several chips, or where the shapes do not fit (``fits``) the layers run their plain lines (XLA's fusions,
which are also these kernels' oracle): ``path_for`` says which."""

import functools
import itertools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import placement
from ._utils import compiler_params
from .scan_operands import rows_a_tile
from .short_conv import HALO, TAPS, _halo

ROWS = TAPS + 8  # of the packed block: the taps from row 0, the bias in row TAPS, whole float32 tiles
LANES_A_STEP = 2048  # the most lanes of all outputs together in a grid step: 2 MB an array and buffer at 512 rows of bf16
LANES = (256, 128)  # of a block's lanes worked on at once, the first that divides it
STRIP = 32  # rows of a tile worked on at once (HALO or more; every tile is whole strips): with 256 lanes 8 float32 vregs a temporary


def _firsts(widths):
    """The first column of each output among the range's."""
    return list(itertools.accumulate(widths, initial=0))[:-1]


def column_steps(start: int, widths) -> int:
    """The parts every output's columns are cut into, a part of each a grid step: the fewest for which a step's lanes are
    ``LANES_A_STEP`` at most, every block whole vregs of lanes, and every block's first column in the product (and in the
    packed filter) a multiple of its width, so that an index map reaches it in place. 0 where no count does."""
    for n in range(1, min(widths) // 128 + 1):
        blocks = [W // n for W in widths]
        if (sum(blocks) <= LANES_A_STEP and all(W % n == 0 and blk % 128 == 0 and at % blk == 0 and (start + at) % blk == 0
                                                for W, blk, at in zip(widths, blocks, _firsts(widths)))):
            return n
    return 0


def fits(S: int, start: int, widths, K: int) -> bool:
    """Whether the kernels take these shapes: whole tiles of rows, column blocks that are whole vregs of lanes and lie
    where an index map reaches them, a filter of at most ``TAPS``."""
    return rows_a_tile(S) > 0 and column_steps(start, widths) > 0 and 2 <= K <= TAPS


def path_for(S: int, start: int, widths, K: int) -> str:
    """The rule's word (``placement.kernel_path``) at these shapes: the kernels sit in no ``shard_map`` yet."""
    return placement.kernel_path(fits(S, start, widths, K), has_specs=False)


def _pack(w, b):
    """-> (ROWS, D) float32: HALF the ``K`` taps in rows [0, K), half the bias in row TAPS. ``silu(c) = h tanh(h) + h`` at
    ``h = c / 2``, one pass through the transcendental unit and no division, so the kernels work on ``h`` and multiply by
    no half: the forward's taps give ``h``; backward they carry TWICE ``c``'s cotangent to x's (``_twice_silus_slope``), and
    ``bwd`` halves the block's gradient, which is summed from the same."""
    K, D = w.shape
    return jnp.zeros((ROWS, D), jnp.float32).at[:K].set(0.5 * w.astype(jnp.float32)).at[TAPS].set(0.5 * b.astype(jnp.float32))


def _lanes(width: int) -> int:
    return next(n for n in LANES if width % n == 0)


def _twice_silus_slope(h):
    """``2 silu'(c)`` at ``h = c / 2``: ``silu' = s (1 + c (1 - s))`` with ``s = (1 + t) / 2``, ``t = tanh(h)``."""
    t = jnp.tanh(h)
    return (1.0 + t) * (1.0 + h - h * t)


def _filtered(xs, taps, K: int):
    """``xs`` (HALO + R, L): the HALO rows before a strip, then its R rows -> ``sum_j w_j x_{t - (K - 1 - j)}`` over the
    strip's rows (R, L): the whole rotated, the rows before dropped, so no row is wrong and none is made twice."""
    return sum((xs if j == K - 1 else pltpu.roll(xs, K - 1 - j, 0))[HALO:] * taps[j:j + 1] for j in range(K))


def _fwd_kernel(*refs, K: int, outputs: int):
    first = pl.program_id(2) == 0
    for x_ref, prev_ref, small, o_ref in zip(*(refs[n * outputs:(n + 1) * outputs] for n in range(4))):
        T, L, R = x_ref.shape[1], _lanes(x_ref.shape[2]), STRIP
        for at in range(0, x_ref.shape[2], L):
            rows = lambda ref, r0, n: ref[0, pl.ds(r0, n), pl.ds(at, L)].astype(jnp.float32)
            taps, bias = small[:K, pl.ds(at, L)], small[TAPS:TAPS + 1, pl.ds(at, L)]

            def strip(r0, before):
                h = _filtered(jnp.concatenate([before, rows(x_ref, r0, R)], axis=0), taps, K) + bias
                o_ref[0, pl.ds(r0, R), pl.ds(at, L)] = (h * jnp.tanh(h) + h).astype(o_ref.dtype)

            def below(i, _):  # a strip under the first reads the rows before it from the block itself
                r0 = pl.multiple_of(i * R, R)
                strip(r0, rows(x_ref, pl.multiple_of(r0 - HALO, HALO), HALO))

            strip(0, jnp.where(first, 0.0, rows(prev_ref, 0, HALO)))
            jax.lax.fori_loop(1, T // R, below, None)


def _bwd_kernel(*refs, K: int, outputs: int):
    first, last = pl.program_id(2) == 0, pl.program_id(2) == pl.num_programs(2) - 1
    by_output = zip(*(refs[n * outputs:(n + 1) * outputs] for n in range(8)))
    for x_ref, prev_ref, next_ref, dy_ref, dy_next, small, dx_ref, dsmall in by_output:
        T, L, R = x_ref.shape[1], _lanes(x_ref.shape[2]), STRIP

        @pl.when(first)
        def _():
            dsmall[...] = jnp.zeros_like(dsmall)

        for at in range(0, x_ref.shape[2], L):
            rows = lambda ref, r0, n: ref[0, pl.ds(r0, n), pl.ds(at, L)].astype(jnp.float32)
            taps, bias = small[:K, pl.ds(at, L)], small[TAPS:TAPS + 1, pl.ds(at, L)]

            def strip(r0, before, carry):
                """A strip's rows: (twice) c's cotangent from half the pre-activation made again, x's cotangent, which
                reaches AHEAD into ``after``, the first rows of the strip below, and the strip's part of the filter's and
                the bias's gradients (twice them) -> (its own first rows, the sums so far, a sublane apart)."""
                after, sums = carry
                x = rows(x_ref, r0, R)
                dc = rows(dy_ref, r0, R) * _twice_silus_slope(_filtered(jnp.concatenate([before, x], axis=0), taps, K) + bias)
                dcs = jnp.concatenate([dc, after], axis=0)
                # tap j of row t + (K - 1 - j): half a tap times twice c's cotangent, and the strip's OWN rows of x against it
                ahead = [dc if j == K - 1 else pltpu.roll(dcs, R + HALO - (K - 1 - j), 0)[:R] for j in range(K)]
                dx_ref[0, pl.ds(r0, R), pl.ds(at, L)] = sum(ahead[j] * taps[j:j + 1] for j in range(K)).astype(dx_ref.dtype)
                by_sublane = lambda v: jnp.sum(v.reshape(R // 8, 8, L), axis=0)
                return dc[:HALO], tuple(acc + by_sublane(v) for acc, v in zip(sums, [x * ahead[j] for j in range(K)] + [dc]))

            def above(i, carry):  # upwards from the tile's last strip: a strip reads the rows before it from the block itself
                r0 = pl.multiple_of(T - R - i * R, R)
                return strip(r0, rows(x_ref, pl.multiple_of(r0 - HALO, HALO), HALO), carry)

            # the HALO rows past the tile reach back into its last rows: their half pre-activation from the tile's last
            # rows and the halo after it; nothing lies past the sequence's end
            h_past = _filtered(jnp.concatenate([rows(x_ref, T - HALO, HALO), rows(next_ref, 0, HALO)], axis=0), taps, K) + bias
            after = jnp.where(last, 0.0, rows(dy_next, 0, HALO) * _twice_silus_slope(h_past))
            carry = jax.lax.fori_loop(0, T // R - 1, above, (after, (jnp.zeros((8, L), jnp.float32),) * (K + 1)))
            _, sums = strip(0, jnp.where(first, 0.0, rows(prev_ref, 0, HALO)), carry)
            for row, acc in zip((*range(K), TAPS), sums):
                dsmall[0, row:row + 1, pl.ds(at, L)] += jnp.sum(acc, axis=0, keepdims=True)


def _specs(S: int, start: int, widths):
    """-> (T, n, lists an output each of the BlockSpecs of a grid (Bt, n, S / T)): its part of the product's columns in place
    with the halo blocks before and after that; the same three of an array of the output's own width (the cotangents, the
    results); its part of the packed filter; its part of the filter's gradient, a sequence a block."""
    T, n = rows_a_tile(S), column_steps(start, widths)
    before, after = _halo(T, S, False), _halo(T, S, True)

    def rows(blk, col):
        tile = pl.BlockSpec((1, T, blk), lambda b, j, s: (b, s, col + j))
        return tile, *(pl.BlockSpec((1, HALO, blk), lambda b, j, s, at=at: (b, at(b, s)[1], col + j)) for at in (before, after))

    blocks, firsts = [W // n for W in widths], _firsts(widths)
    product = [rows(blk, (start + at) // blk) for blk, at in zip(blocks, firsts)]
    own = [rows(blk, 0) for blk in blocks]
    small = [pl.BlockSpec((ROWS, blk), lambda b, j, s, col=at // blk: (0, col + j)) for blk, at in zip(blocks, firsts)]
    dsmall = [pl.BlockSpec((1, ROWS, blk), lambda b, j, s: (b, 0, j)) for blk in blocks]
    return T, n, product, own, small, dsmall


def _pick(specs, *which):
    """Entries ``which`` of every output's triple (tile, halo before, halo after), an entry at a time."""
    return [spec[i] for i in which for spec in specs]


def _vmem(T: int, lanes: int, arrays: int, itemsize: int) -> int:
    """Double-buffered blocks of ``arrays`` arrays of a step's ``lanes`` with their halos: a strip's temporaries are registers."""
    return 2 * arrays * (T + 2 * HALO) * lanes * itemsize


@functools.partial(jax.jit, static_argnames=("start", "widths", "K", "interpret"))
def fwd(x, small, start: int, widths, K: int, interpret: bool = False):
    """x (Bt, S, columns) the product, small (ROWS, sum(widths)) (``_pack``, of ``K`` taps) -> a (Bt, S, width) of x's type
    an entry of ``widths``."""
    Bt, S, _ = x.shape
    T, n, product, own, packed, _ = _specs(S, start, widths)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, K=K, outputs=len(widths)),
        grid=(Bt, n, S // T),
        in_specs=_pick(product, 0, 1) + packed,
        out_specs=_pick(own, 0),
        out_shape=[jax.ShapeDtypeStruct((Bt, S, W), x.dtype) for W in widths],
        compiler_params=compiler_params("parallel", "parallel", "parallel", interpret=interpret,
                                        vmem_bytes=_vmem(T, sum(widths) // n, 2, x.dtype.itemsize)),
        interpret=interpret, name="conv_silu_fwd",
    )(*[x] * (2 * len(widths)), *[small] * len(widths))


@functools.partial(jax.jit, static_argnames=("start", "widths", "K", "interpret"))
def bwd(x, small, dys, start: int, widths, K: int, interpret: bool = False):
    """-> (the cotangent of each output's columns of x, a (Bt, S, width) of x's type each; the filter's and the bias's
    gradients in ``_pack``'s rows, (ROWS, sum(widths)) float32)."""
    Bt, S, _ = x.shape
    T, n, product, own, packed, summed = _specs(S, start, widths)
    outs = pl.pallas_call(
        functools.partial(_bwd_kernel, K=K, outputs=len(widths)),
        grid=(Bt, n, S // T),
        in_specs=_pick(product, 0, 1, 2) + _pick(own, 0, 2) + packed,
        # the packed block's gradient is summed over a sequence's tiles in its resident block
        out_specs=_pick(own, 0) + summed,
        out_shape=[jax.ShapeDtypeStruct((Bt, S, W), x.dtype) for W in widths] + [jax.ShapeDtypeStruct((Bt, ROWS, W), jnp.float32) for W in widths],
        compiler_params=compiler_params("parallel", "parallel", "arbitrary", interpret=interpret,
                                        vmem_bytes=_vmem(T, sum(widths) // n, 3, x.dtype.itemsize)),
        interpret=interpret, name="conv_silu_bwd",
    )(*[x] * (3 * len(widths)), *dys, *dys, *[small] * len(widths))
    return outs[:len(widths)], 0.5 * jnp.sum(jnp.concatenate(outs[len(widths):], axis=-1), axis=0)  # (``_pack``: summed from twice c's)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def conv_silu(x, w, b, start: int, widths, interpret: bool = False):
    """``silu(causal_conv(x[..., start:start + sum(widths)], w) + b)`` split by ``widths``, by the kernels; differentiated,
    the backward is the one call ``bwd``. x (Bt, S, columns) the kept product, w (K, sum(widths)), b (sum(widths),)."""
    return tuple(fwd(x, _pack(w, b), start, tuple(widths), w.shape[0], interpret))


def _conv_silu_fwd(x, w, b, start, widths, interpret):
    return tuple(fwd(x, _pack(w, b), start, tuple(widths), w.shape[0], interpret)), (x, w, b)


def _conv_silu_bwd(start, widths, interpret, kept, dys):
    x, w, b = kept
    with placement.counted("conv_silu", "kernel", "bwd", name="mixer/conv"):
        dxs, dsmall = bwd(x, _pack(w, b), tuple(dy.astype(x.dtype) for dy in dys), start, tuple(widths), w.shape[0], interpret)
    # the product's other columns have no cotangent from here: XLA fills a buffer with zeros and writes the parts into it
    zeros = lambda width: jnp.zeros((*x.shape[:2], width), x.dtype)
    dx = jnp.concatenate([zeros(start), *dxs, zeros(x.shape[2] - start - sum(widths))], axis=-1)
    return dx, dsmall[:w.shape[0]].astype(w.dtype), dsmall[TAPS].astype(b.dtype)


conv_silu.defvjp(_conv_silu_fwd, _conv_silu_bwd)
