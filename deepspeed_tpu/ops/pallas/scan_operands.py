"""What a delta-rule layer's scan reads, made from what its checkpointed block keeps, in one Pallas pass each way.

A ``kda`` or ``gdn`` layer keeps the results of its products over the model width and makes everything between them and
``ops/pallas/kda.py::scan_fwd`` again in its backward. That chain, for a key head and its ``rep`` value heads, ``c(x, w)``
the depthwise causal filter of ``K`` taps (``models/mixers.py::causal_conv``) and ``l2n`` ``l2_normalize``::

    q  = l2n(silu(c(x_q, w_q))) * D**-0.5          k  = l2n(silu(c(x_k, w_k)))
    kb = sigmoid(b) * k                            vb = sigmoid(b) * silu(c(x_v, w_v))       (a value head each)
    g  = -exp(A_log) * softplus(f + dt_bias)                                                 (a decay a channel alone)

Elementwise but for the ``K - 1`` rows a filter reaches back and the two row sums of a norm, so the least it can cost is
its traffic: the projections and ``f`` read and the five operands written once forward; the five cotangents and the inputs
read and four large cotangents written once backward. One call each way (``scan_operands_fwd``, ``scan_operands_bwd``), a
grid step a key head's tile of rows at the head's width, with ``short_conv.py``'s halo blocks before it (backward: also
after it: the filter's transpose reaches ahead, and the rows ahead are made again from their own halo, the tile's last
rows), so every tile is its own program. Float32 inside, each output rounded once; ``kb`` is made from the ROUNDED ``k``
(as ``ops/kda.py::kda_chunked`` makes it: the scan's ``k`` and ``kb`` stay one number times ``beta``).

Two forms of ONE tile program, read off the operands' shapes: with ``f`` (B, H, S, D), the decay a channel, ``g`` is made
here (KDA); without, the decay is a number a head and token and stays its caller's (Gated DeltaNet), and ``x_v`` may have
``rep`` times the heads of ``x_q``: a step then writes ``kb`` and ``vb`` for each value head of its key head. ``beta``
(B, S, H_v) float32 comes in the layout the product left its pre-activation in (the sigmoid of that small array stays
XLA's: ``scan_operands`` says why): a head's column is picked out of a tile's rows by its lane, and its cotangent leaves
along the lanes, (B H_v, 1, S), by a product with ones on the otherwise idle MXU.

The filters, ``dt_bias`` and ``A_log`` enter as ONE block a key head (``_pack``: rows of taps, then the decay's two), and
their gradients leave in the same rows, summed over a head's tiles in the resident block; XLA packs, and transposes the
packing. The outputs carry no checkpoint name and the residuals are the inputs: a checkpointed block keeps what it kept.

Off the TPU, on several chips, or where the shapes do not fit (``fits``) the layers run their plain lines (XLA's fusions,
which are also these kernels' oracle): ``path_for`` says which."""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .. import placement
from ._utils import compiler_params
from .kda import _NT
from .short_conv import HALO, TAPS, _ahead, _back, _halo, _taps

EPS = 1e-6  # ``models/mixers.py::l2_normalize``'s


def rows_a_tile(S: int) -> int:
    """The rows of a grid step: the largest listed count that divides ``S``, 0 where none does. Whole vregs of LANES, for
    ``beta``'s cotangent leaves with a tile's rows along them; 512 where it divides: a tile's fixed part (its halos, the
    edges made again, some 130 bundles of waits and addresses) is then paid half as often, 6% and 8% fewer bundles a row
    than at 256 by the compiled bodies (``PERF.md`` section 6, PR 58)."""
    return next((t for t in (512, 256, 128) if S % t == 0), 0)


def fits(S: int, D: int, K: int) -> bool:
    """Whether the kernels take these shapes: whole tiles of rows, whole vregs of lanes, a filter of at most ``TAPS``."""
    return rows_a_tile(S) > 0 and D % 128 == 0 and 2 <= K <= TAPS


def path_for(S: int, D: int, K: int) -> str:
    """The rule's word (``placement.kernel_path``) at these shapes: the kernels sit in no ``shard_map`` yet."""
    return placement.kernel_path(fits(S, D, K), has_specs=False)


def _pack(w_q, w_k, w_v, decay):
    """-> (H_k, (2 + rep) TAPS [+ 8], D) float32, a key head a block: the taps of q's filter in rows [0, K), k's from
    TAPS, value head r's from (2 + r) TAPS, then ``dt_bias`` and ``A_log`` (along the lanes) where there is a decay a channel."""
    f32 = jnp.float32
    K, Hk, D = w_q.shape
    taps = lambda w: jnp.pad(jnp.swapaxes(w.astype(f32), 0, 1), ((0, 0), (0, TAPS - K), (0, 0)))
    parts = [taps(w_q), taps(w_k), taps(w_v).reshape(Hk, -1, D)]
    if decay is not None:
        a_log, dt_bias = decay
        rows = jnp.stack([dt_bias.astype(f32), jnp.broadcast_to(a_log.astype(f32)[:, None], (Hk, D))], axis=1)
        parts.append(jnp.pad(rows, ((0, 0), (0, 6), (0, 0))))
    return jnp.concatenate(parts, axis=1)


def _conv(x_ref, prev_ref, i, w, first, K: int):
    """Head ``i`` of a block's rows through its filter: c (T, D) float32, its first HALO rows made right from the rows
    before the tile (zeros before the sequence)."""
    before = jnp.where(first, 0.0, prev_ref[i].astype(jnp.float32))
    c, c_edge, _, _ = _taps(x_ref[i].astype(jnp.float32), before, w, _back, K)
    return jnp.concatenate([c_edge, c[HALO:]], axis=0)


def _sigmoid(x):
    """1 / (1 + exp(-x)) as XLA's own division makes it on this chip, the estimate and one Newton step, less that
    division's tests for a zero, an infinite or a denormal divisor (a third of a forward tile's VPU work): below -80 the
    argument stays there, so the divisor stays finite; the result there is under 2e-35 either way."""
    d = 1.0 + jnp.exp(-jnp.maximum(x, -80.0))
    r = pl.reciprocal(d, approx=True)
    return r * (2.0 - d * r)


def _silu(c):
    """-> (silu(c), its derivative)."""
    s = _sigmoid(c)
    return c * s, s * (1.0 + c * (1.0 - s))


def _l2n(s):
    """-> (s / |s| along the lanes, 1 / |s| a row)."""
    r = jax.lax.rsqrt(jnp.sum(s * s, axis=-1, keepdims=True) + EPS)
    return s * r, r


def _column(b, head):
    """``b`` (rows, H): column ``head`` as (rows, 1)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, b.shape, 1)
    return jnp.sum(jnp.where(lane == head, b, 0.0), axis=1, keepdims=True)


def _softplus(z):
    return jnp.maximum(z, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(z)))


def _fwd_kernel(*refs, K: int, rep: int, Hk: int, decay: bool):
    xq, xq_prev, xk, xk_prev, xv, xv_prev, b_ref, small, *rest = refs
    f_ref, (q_ref, k_ref, kb_ref, vb_ref, *g_ref) = (rest[0], rest[1:]) if decay else (None, rest)
    f32, D = jnp.float32, xq.shape[-1]
    first, w = pl.program_id(1) == 0, small[0]
    taps = lambda n: w[n * TAPS:n * TAPS + K]
    q, _ = _l2n(_silu(_conv(xq, xq_prev, 0, taps(0), first, K))[0])
    k, _ = _l2n(_silu(_conv(xk, xk_prev, 0, taps(1), first, K))[0])
    q_ref[0] = (q * D**-0.5).astype(q_ref.dtype)
    k = k.astype(k_ref.dtype)
    k_ref[0] = k
    head = pl.program_id(0) % Hk * rep
    for r in range(rep):
        beta = _column(b_ref[0], head + r)
        kb_ref[r] = (beta * k.astype(f32)).astype(kb_ref.dtype)
        vb_ref[r] = (beta * _silu(_conv(xv, xv_prev, r, taps(2 + r), first, K))[0]).astype(vb_ref.dtype)
    if decay:
        at = (2 + rep) * TAPS  # the packed block's rows of ``dt_bias`` and ``A_log``
        g_ref[0][0] = -jnp.exp(w[at + 1:at + 2]) * _softplus(f_ref[0] + w[at:at + 1])


def _bwd_kernel(*refs, K: int, rep: int, Hk: int, decay: bool):
    (xq, xq_prev, xq_next, xk, xk_prev, xk_next, xv, xv_prev, xv_next, b_ref, b_next, small,
     dq_ref, dq_next, dk_ref, dk_next, dkb_ref, dkb_next, dvb_ref, dvb_next, *rest) = refs
    (f_ref, dg_ref), rest = (rest[:2], rest[2:]) if decay else ((None, None), rest)
    dxq_ref, dxk_ref, dxv_ref, db_ref, dsmall, *df_ref = rest
    f32, (T, D) = jnp.float32, xq.shape[1:]
    first, last = pl.program_id(1) == 0, pl.program_id(1) == pl.num_programs(1) - 1
    head, w = pl.program_id(0) % Hk * rep, small[0]

    @pl.when(first)
    def _():
        dsmall[...] = jnp.zeros_like(dsmall)

    def through(x_ref, prev_ref, next_ref, i, n):
        """Head ``i``'s rows through filter ``n`` again -> (c of the tile, c of the HALO rows past it, which reach back
        into the tile's last rows; ``back(dx_ref, dc, dc_past)``: the filter's transpose, which writes x's cotangent, and
        the filter's own gradient, added to its rows)."""
        taps = w[n * TAPS:n * TAPS + K]
        c = _conv(x_ref, prev_ref, i, taps, first, K)
        c_past = _taps(next_ref[i].astype(f32), x_ref[i, T - HALO:].astype(f32), taps, _back, K)[1]  # of HALO rows every one is an edge's

        def back(dx_ref, dc, dc_past):
            # x's cotangent reaches AHEAD: tap j of row t + (K - 1 - j); nothing lies past the sequence's end
            dx, dx_edge, ahead, ahead_edge = _taps(dc, jnp.where(last, 0.0, dc_past), taps, _ahead, K)
            dx_ref[i] = dx.astype(dx_ref.dtype)
            dx_ref[i, T - HALO:] = dx_edge.astype(dx_ref.dtype)
            # the filter's: tap j meets x_t with dc_{t + (K - 1 - j)}, the pairs of a tile's OWN rows of x (so nothing of
            # the forward's shifted tiles lives on to here); the rotated tile's last rows are put right by the edge
            x = x_ref[i].astype(f32)
            for j in range(K):
                wrong = 0.0 if j == K - 1 else jnp.sum(x[T - HALO:] * (ahead_edge[j] - ahead[j][T - HALO:]), axis=0, keepdims=True)
                dsmall[0, n * TAPS + j:n * TAPS + j + 1] += jnp.sum(x * ahead[j], axis=0, keepdims=True) + wrong

        return c, c_past, back

    def normed(c, dy):
        """-> (y = l2n(silu(c)), c's cotangent from y's)."""
        s, ds_dc = _silu(c)
        y, r = _l2n(s)
        return y, r * (dy - y * jnp.sum(dy * y, axis=-1, keepdims=True)) * ds_dc

    rows = lambda ref, i=0: ref[i].astype(f32)
    betas = [[_column(ref[0], head + r) for r in range(rep)] for ref in (b_ref, b_next)]
    # k's cotangent: its own and, through ``kb``, each of its value heads'
    dk, dk_past = (rows(own) + sum(beta * rows(through_kb, r) for r, beta in enumerate(beta_by_head))
                   for own, through_kb, beta_by_head in ((dk_ref, dkb_ref, betas[0]), (dk_next, dkb_next, betas[1])))

    c, c_past, back = through(xq, xq_prev, xq_next, 0, 0)
    scale = D**-0.5
    back(dxq_ref, normed(c, rows(dq_ref) * scale)[1], normed(c_past, rows(dq_next) * scale)[1])
    c, c_past, back = through(xk, xk_prev, xk_next, 0, 1)
    k, dc = normed(c, dk)
    back(dxk_ref, dc, normed(c_past, dk_past)[1])
    k = k.astype(dq_ref.dtype).astype(f32)  # as the forward rounded it
    ones = jnp.ones((8, D), f32)
    for r in range(rep):
        c, c_past, back = through(xv, xv_prev, xv_next, r, 2 + r)
        (v, dv_dc), (_, dv_dc_past) = _silu(c), _silu(c_past)
        dvb, beta = rows(dvb_ref, r), betas[0][r]
        back(dxv_ref, beta * dvb * dv_dc, betas[1][r] * rows(dvb_next, r) * dv_dc_past)
        # beta's: a number a row, handed on along the lanes: the row sums are a product with ones
        db = rows(dkb_ref, r) * k + dvb * v
        db_ref[r] = jax.lax.dot_general(ones, db, _NT, precision=jax.lax.Precision.HIGHEST, preferred_element_type=f32)[:1]
    if decay:
        at = (2 + rep) * TAPS
        z, a, dg = f_ref[0] + w[at:at + 1], -jnp.exp(w[at + 1:at + 2]), dg_ref[0]
        df = dg * a * _sigmoid(z)
        df_ref[0][0] = df
        dsmall[0, at:at + 1] += jnp.sum(df, axis=0, keepdims=True)
        dsmall[0, at + 1:at + 2] += jnp.sum(dg * a * _softplus(z), axis=0, keepdims=True)  # d g / d A_log = g


def _specs(T: int, S: int, D: int, rep: int, Hk: int, Hv: int, R: int):
    """The BlockSpecs of a grid (B H_k, S / T): a key head's rows, its value heads' rows (``rep`` heads of the arrays of
    H_v), each with the halo block before and after; ``b``'s rows and those after; the packed block of the head."""
    def rows(heads):
        tile = pl.BlockSpec((heads, T, D), lambda i, s: (i, s, 0))
        return tile, pl.BlockSpec((heads, HALO, D), _halo(T, S, False)), pl.BlockSpec((heads, HALO, D), _halo(T, S, True))

    of_batch = lambda at: (lambda i, s: (i // Hk, *at(0, s)[1:]))
    b = pl.BlockSpec((1, T, Hv), of_batch(lambda i, s: (i, s, 0))), pl.BlockSpec((1, HALO, Hv), of_batch(_halo(T, S, True)))
    return rows(1), rows(rep), b, pl.BlockSpec((1, R, D), lambda i, s: (i % Hk, 0, 0))


def _vmem(T: int, D: int, blocks: int, itemsize: int, tiles: int) -> int:
    """Double-buffered ``blocks`` blocks of a tile's rows with their halos, and ``tiles`` float32 tiles of temporaries."""
    return 2 * blocks * (T + 2 * HALO) * D * itemsize + tiles * T * D * 4


@functools.partial(jax.jit, static_argnames=("K", "interpret"))
def fwd(x_q, x_k, x_v, b, small, f, K: int, interpret: bool = False):
    """x_q, x_k (B H_k, S, D), x_v (B H_v, S, D), b (B, S, H_v) float32 beta, small (H_k, R, D) (``_pack``, of ``K`` taps), f
    None or (B H_k, S, D) float32 -> q, k, kb, vb (kb, vb of H_v heads) in x's type[, g float32]."""
    (BHk, S, D), Hk, Hv = x_q.shape, small.shape[0], b.shape[-1]
    rep, T, decay = Hv // Hk, rows_a_tile(S), f is not None
    key, value, (b_rows, _), packed = _specs(T, S, D, rep, Hk, Hv, small.shape[1])
    wide, like = key[0], jax.ShapeDtypeStruct  # a float32 operand a key head (``f``, ``g``) walks as x_q does
    return pl.pallas_call(
        functools.partial(_fwd_kernel, K=K, rep=rep, Hk=Hk, decay=decay),
        grid=(BHk, S // T),
        in_specs=[*key[:2], *key[:2], *value[:2], b_rows, packed] + [wide] * decay,
        out_specs=[key[0], key[0], value[0], value[0]] + [wide] * decay,
        out_shape=[like(x.shape, x.dtype) for x in (x_q, x_q, x_v, x_v)] + [like(x_q.shape, jnp.float32)] * decay,
        compiler_params=compiler_params("parallel", "parallel", interpret=interpret,
                                        vmem_bytes=_vmem(T, D, 4 + 3 * rep + 4 * decay, x_q.dtype.itemsize, 16)),
        interpret=interpret, name="scan_operands_fwd",
    )(x_q, x_q, x_k, x_k, x_v, x_v, b, small, *([f] if decay else []))


@functools.partial(jax.jit, static_argnames=("K", "interpret"))
def bwd(x_q, x_k, x_v, b, small, f, dq, dk, dkb, dvb, dg, K: int, interpret: bool = False):
    """-> the cotangents of x_q, x_k, x_v (their type), b (B, S, H_v) float32, small (H_k, R, D)[, f float32]."""
    (BHk, S, D), Hk, Hv = x_q.shape, small.shape[0], b.shape[-1]
    rep, T, decay, R = Hv // Hk, rows_a_tile(S), f is not None, small.shape[1]
    key, value, b_rows, packed = _specs(T, S, D, rep, Hk, Hv, R)
    wide, like = key[0], jax.ShapeDtypeStruct
    past = lambda specs: (specs[0], specs[2])
    dx_q, dx_k, dx_v, db, dsmall, *df = pl.pallas_call(
        functools.partial(_bwd_kernel, K=K, rep=rep, Hk=Hk, decay=decay),
        grid=(BHk, S // T),
        in_specs=[*key, *key, *value, *b_rows, packed, *past(key), *past(key), *past(value), *past(value)] + [wide, wide] * decay,
        # beta's cotangent a value head along the lanes; the packed block's gradient is summed over a head's tiles in its
        # resident block, a head of a sequence a block
        out_specs=[key[0], key[0], value[0], pl.BlockSpec((rep, 1, T), lambda i, s: (i, 0, s)),
                   pl.BlockSpec((1, R, D), lambda i, s: (i, 0, 0))] + [wide] * decay,
        out_shape=[like(x.shape, x.dtype) for x in (x_q, x_k, x_v)] + [like((x_v.shape[0], 1, S), jnp.float32), like((BHk, R, D), jnp.float32)]
        + [like(x_q.shape, jnp.float32)] * decay,
        compiler_params=compiler_params("parallel", "arbitrary", interpret=interpret,
                                        vmem_bytes=_vmem(T, D, 6 + 4 * rep + 6 * decay, x_q.dtype.itemsize, 48)),
        interpret=interpret, name="scan_operands_bwd",
    )(x_q, x_q, x_q, x_k, x_k, x_k, x_v, x_v, x_v, b, b, small, dq, dq, dk, dk, dkb, dkb, dvb, dvb, *([f, dg] if decay else []))
    db = jnp.swapaxes(db.reshape(-1, Hv, S), 1, 2)
    return (dx_q, dx_k, dx_v, db, jnp.sum(dsmall.reshape(-1, Hk, R, D), axis=0), *df)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _operands(x_q, x_k, x_v, b, small, f, K, interpret):
    return tuple(fwd(x_q, x_k, x_v, b, small, f, K, interpret))


def _operands_fwd(x_q, x_k, x_v, b, small, f, K, interpret):
    return tuple(fwd(x_q, x_k, x_v, b, small, f, K, interpret)), (x_q, x_k, x_v, b, small, f)


def _operands_bwd(K, interpret, kept, cotangents):
    narrow, dg = cotangents[:4], cotangents[4] if len(cotangents) == 5 else None
    with placement.counted("scan_operands", "kernel", "bwd", name="mixer/proj"):
        grads = bwd(*kept, *(ct.astype(kept[0].dtype) for ct in narrow), dg, K, interpret)
    return grads if dg is not None else (*grads, None)  # ``f``'s, where there was an ``f``


_operands.defvjp(_operands_fwd, _operands_bwd)


def scan_operands(x_q, x_k, x_v, b, w_q, w_k, w_v, f=None, a_log=None, dt_bias=None, interpret: bool = False):
    """The scan's operands by the kernels; differentiated, the backward is the one call ``bwd``. x_q, x_k (B, H_k, S, D)
    and x_v (B, H_v, S, D) the kept projections, heads before the sequence; b (B, S, H_v) float32 ``beta``'s
    pre-activation; w_* (K, H, D) the filters. With ``f`` (B, H, S, D) float32, the pre-activation of a decay a channel,
    and its ``a_log`` (H,) and ``dt_bias`` (H, D): -> (q, k, kb, vb, g), ``ops/kda.py::kda_scan``'s operands. Without
    (a decay a head and token is its caller's to make): -> (q, k, kb, vb), with kb and vb of x_v's heads."""
    (B, Hk, S, D), Hv = x_q.shape, x_v.shape[1]
    flat = lambda x: x.reshape(-1, S, D)
    small = _pack(w_q, w_k, w_v, None if f is None else (a_log, dt_bias))
    # beta itself is XLA's, a pass over a number a head and token: in a tile a column takes the registers of the whole
    # tile, so the sigmoid of a tile's 256 numbers would cost what the sigmoid of its 32,768 does
    q, k, kb, vb, *g = _operands(flat(x_q), flat(x_k), flat(x_v), jax.nn.sigmoid(b.astype(jnp.float32)), small,
                                 None if f is None else flat(f.astype(jnp.float32)), w_q.shape[0], interpret)
    return tuple(x.reshape(B, -1, S, D) for x in (q, k, kb, vb, *g))
