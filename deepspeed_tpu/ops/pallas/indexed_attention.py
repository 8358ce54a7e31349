"""Pallas kernels for attention over keys the model chose (TPU): a learned
indexer scores every visible key of a query, the ``topk`` best are its key
set, and softmax attention runs over that set alone (the DeepSeek-Sparse-
Attention family; ``models/mixers.py::SparseMixer`` has the equations).

Everything that is square in the sequence is kept KEY-MAJOR, ``(B, Sk, Sq)``:
the flash kernels this file follows work on (bk, bq) tiles with the key
position on sublanes, so a query's statistics (its threshold, its softmax's
max and sum) are (1, bq) rows that reduce and broadcast along sublanes. Six
calls, named apart from the flash kernels' on the device's clock:

- ``index_scores``: ``I^T[s, t] = sum_j w[t, j] relu(kI[s] . qI[t, j])`` in
  float32, the blocks above the diagonal skipped and filled with ``NEG_INF``;
- ``index_scores_bwd``: its transpose, from the cotangent of ``I^T``: dqI and
  dw stay in VMEM over a query block's walk along the keys, dkI leaves as one
  partial sum a query block, added outside;
- ``index_select``: a query's ``min(topk, t + 1)`` best visible keys as an
  int8 mask. A band of 128 queries' scores stays in VMEM; the k-th largest
  is found bit by bit on the scores' order-preserving integer form (32
  counts), ties at the threshold go to the lower index (14 more). A count
  walks, a chunk of rows at a time, the rows at or below the band's diagonal
  and no others (band ``i`` sees its first ``128 (i + 1)`` keys), and a band
  whose every query takes every visible key (``128 (i + 1) <= topk``) makes
  no count at all: ``share_walked`` of all rows, 0.480 at 8,192 positions,
  2,048 keys a query and chunks of 256 rows;
- ``sparse_fwd`` / ``sparse_bwd``: the flash forward and fused backward with
  the mask, cut into the kernels' own (bk, bq) tiles, in place of the causal
  compare: every block at or below the diagonal is visited (a learned choice
  leaves no block empty), the chosen pairs alone enter the softmax;
- ``index_loss``: the indexer's own loss, finished where its target is made.
  A query block walks its key blocks twice. The first sweep makes the target
  a tile at a time, ``P[s, t] = sum_h exp(q_h[t] . k[s] * scale - lse_h[t])``
  over the chosen pairs (from the forward's row statistics), keeps the
  block's column of it in VMEM, (Sk, bq) float32, and adds up a query's
  statistics along its keys: ``Z = sum P``, the chosen scores' online max and
  sum (``lse_I``) and ``sum P (log P - I)``, from which
  ``KL_t = sum P (log P - I) / Z - log Z + lse_I``. The second sweep reads
  the column and the scores' and mask's tiles again and writes the gradient of
  the queries' mean in the scores, ``(softmax_{S_t} I - P / Z) / queries``,
  rounded once to the model's dtype: the one square array the backward
  keeps. The head-summed probabilities never reach HBM.

How the two calls with a sum over heads (``index_scores``, ``index_loss``) walk
a (bk, bq) tile: in STRIPS of every row by 128 lanes (``strip_for``), the heads
innermost, eight a trip (``_sum_over_heads``). A strip's 128 queries are one
weight tile of ``q_h^T`` in the MXU, which then serves the longest stream of
key rows the tile has; a trip's eight products go to the four MXUs abreast and
each head's term is added as it leaves its MXU; the sum is read from and
written to VMEM (the output block, the column of ``P``) ONCE A TRIP, and a
strip's statistics along the keys are made when its heads are done. Carried
over the heads as one (bk, bq) value, 256 vregs of the file's 64, the sum went
through the one store slot a bundle once a HEAD, beside a product made whole
before anything read it (667 spill stores in the 994 bundles a head and tile
of ``index_scores``; 1.91 -> 1.23 ms a call and 4.24 -> 2.78 for
``index_loss`` at 8,192 positions: PERF.md section 6, PR 62). The strips are a
rolled loop and the heads another, never Python copies of a body: sixteen and
thirty-two copies of a head, traced and lowered at every call site, cost the
cell 5 s of every warm start (PR 61), and a trip of 1, 2 or 4 heads waits on
its own product's way through the MXU (2.25, 1.66 ms a call). The sums over
heads keep their order (``index_scores`` is bit for bit what it was), and so
do a strip's sums along the keys. ``index_scores_bwd`` keeps its whole-tile
products, one head a trip: its three products a head are what bounds it
(1,536 MXU cycles of its 1,774 bundles a head and tile), and every form of it
in strips under a rolled loop is slower (PERF.md, PR 62). The six entry points
are jitted for the trace's sake, not the program's (they are inlined where
they are called): ``model.init``'s plain loop over the layers and the step's
checkpointed block share one trace of a body a shape.

A masked score is ``NEG_INF`` (finite): a query whose first visited blocks hold
none of its keys carries ``m = NEG_INF`` and a sum of ones until its first key
arrives, whose correction ``exp(NEG_INF - m)`` is exactly zero.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._utils import block_that_divides, compiler_params as _compiler_params, vmem_budget
from .flash_attention import _NN, _NT, _TN, NEG_INF, _fused_bwd_vmem, _rows, _tile_bytes

BLOCK = 512  # the (bk, bq) tile of the attention and indexer kernels, and of the mask's tiled form
BAND = 128   # queries whose scores ``index_select`` holds at once: (Sk, 128) float32 are 4 MB at 8,192 keys
CHUNK = 256  # rows of a band a count pass of ``index_select`` takes at a time: 32 vregs (128 and 512 rows are slower: PERF.md, PR 44)
INT_MIN = -2**31
HEADS_A_TRIP = 8  # heads a trip of the rolled loop over heads writes out: two a v5e's MXU, their products abreast (1, 2 and 4 a trip: PERF.md, PR 62)


def block_for(seq: int, want: int = 0) -> int:
    return block_that_divides(seq, want or BLOCK)


def strip_for(blk: int) -> tuple:
    """The (rows, lanes) strip in which a loop over heads walks a (blk, blk) tile, read off the block: one vreg row of
    lanes across, which is one weight tile of ``q_h^T`` in the MXU, and EVERY row of the tile, the longest stream a
    weight tile can serve here (a tile's load costs the MXU what 128 streamed rows cost)."""
    return blk, block_that_divides(blk, BAND)


def _strips(blk: int, body):
    """``body(lanes)`` on every strip of a (blk, blk) tile: ONE rolled loop whatever the count (sixteen copies of a body
    are sixteen bodies to trace, lower and compile on every start)."""
    L = strip_for(blk)[1]

    def step(t, _):
        body(pl.ds(pl.multiple_of(t * L, L), L))

    jax.lax.fori_loop(0, blk // L, step, None)


def _sum_over_heads(heads: int, add, ref, at):
    """``ref[at] = term(0) + term(1) + ...``, ``add(h, acc)`` adding head ``h``'s term, in the heads' order, as ONE
    rolled loop of ``HEADS_A_TRIP`` heads a trip written out: within a trip the heads' products go to the MXUs abreast
    and each term is added as it leaves its MXU, and the sum is read from and written to VMEM once a TRIP (carried over
    the heads as a whole tile it went through the one store slot once a head). The code is a trip's whatever the heads."""
    a = next(a for a in (HEADS_A_TRIP, 4, 2, 1) if heads % a == 0)
    ref[at] = jnp.zeros_like(ref[at])

    def trip(t, _):
        acc = ref[at]
        for g in range(a):
            acc = add(t * a + g, acc)
        ref[at] = acc

    jax.lax.fori_loop(0, heads // a, trip, None)


def kernels_take(seq: int, topk: int) -> bool:
    """Whether these kernels take a sequence: whole 128-lane tiles."""
    return seq % BAND == 0 and block_for(seq) % BAND == 0 and topk >= 1


def tiled(mask_t, blk: int):
    """(B, Sk, Sq) -> (B, Sk/blk, Sq/blk, blk, blk): one leading index a (bk, bq) tile."""
    B, Sk, Sq = mask_t.shape
    return mask_t.reshape(B, Sk // blk, blk, Sq // blk, blk).transpose(0, 1, 3, 2, 4)


# ----------------------------------------------------------------------
# the indexer's scores
# ----------------------------------------------------------------------
def _index_scores_kernel(q_ref, k_ref, w_ref, o_ref, *, blk: int, heads: int):
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j > i)
    def _above():
        o_ref[0] = jnp.full((blk, blk), NEG_INF, jnp.float32)

    @pl.when(j <= i)
    def _scores():
        def walk(lanes):
            def add(h, acc):  # the one key head's rows serve every indexer head
                s = jax.lax.dot_general(k_ref[0], q_ref[0, h, lanes, :], _NT, preferred_element_type=jnp.float32)  # (bk, L)
                return acc + jnp.maximum(s, 0.0) * w_ref[0, h, :, lanes]

            _sum_over_heads(heads, add, o_ref, (0, slice(None), lanes))
            keys = j * blk + jax.lax.broadcasted_iota(jnp.int32, strip_for(blk), 0)
            queries = i * blk + lanes.start + jax.lax.broadcasted_iota(jnp.int32, strip_for(blk), 1)
            o_ref[0, :, lanes] = jnp.where(keys <= queries, o_ref[0, :, lanes], NEG_INF)

        _strips(blk, walk)


@functools.partial(jax.jit, static_argnames=("interpret", "blk"))
def index_scores(q_i, k_i, w, *, interpret: bool = False, blk: int = 0):
    """q_i (B, J, S, Di), k_i (B, S, Di), w (B, J, S) float32 -> I^T (B, S, S) float32, key-major."""
    B, J, S, Di = q_i.shape
    blk = block_for(S, blk)
    n = S // blk
    return pl.pallas_call(
        functools.partial(_index_scores_kernel, blk=blk, heads=J),
        grid=(B, n, n),
        in_specs=[
            pl.BlockSpec((1, J, blk, Di), lambda b, i, j: (b, 0, i, 0)),
            pl.BlockSpec((1, blk, Di), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, J, 1, blk), lambda b, i, j: (b, 0, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, blk, blk), lambda b, i, j: (b, j, i)),
        out_shape=jax.ShapeDtypeStruct((B, S, S), jnp.float32),
        interpret=interpret,
        name="index_scores",
        compiler_params=_compiler_params("parallel", "parallel", "arbitrary", interpret=interpret,
                                         vmem_bytes=2 * (J * blk * Di * q_i.dtype.itemsize + blk * blk * 4) + _tile_bytes(blk, blk)),
    )(q_i, k_i, w.reshape(B, J, 1, S))


def _index_scores_bwd_kernel(g_ref, q_ref, k_ref, w_ref, dq_ref, dw_ref, dk_ref, dq_acc, dw_acc, *, blk: int, heads: int):
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _zero():
        dq_acc[...] = jnp.zeros_like(dq_acc)
        dw_acc[...] = jnp.zeros_like(dw_acc)

    @pl.when(j > i)
    def _above():
        dk_ref[0, 0] = jnp.zeros_like(dk_ref[0, 0])

    @pl.when(j <= i)
    def _block():
        k = k_ref[0]
        g0 = g_ref[0].astype(jnp.float32)  # (bk, bq): zero off the chosen pairs

        def head(h, dk):
            q = q_ref[0, h]
            s = jax.lax.dot_general(k, q, _NT, preferred_element_type=jnp.float32)
            dw_acc[h] = dw_acc[h] + jnp.sum(g0 * jnp.maximum(s, 0.0), axis=0, keepdims=True)
            g = jnp.where(s > 0.0, g0 * w_ref[0, h], 0.0).astype(k.dtype)
            dq_acc[h] = dq_acc[h] + jax.lax.dot_general(g, k, _TN, preferred_element_type=jnp.float32)
            return dk + jax.lax.dot_general(g, q, _NN, preferred_element_type=jnp.float32)

        dk_ref[0, 0] = jax.lax.fori_loop(0, heads, head, jnp.zeros(k.shape, jnp.float32))

    @pl.when(j == pl.num_programs(2) - 1)
    def _out():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)
        dw_ref[0] = dw_acc[...]


@functools.partial(jax.jit, static_argnames=("interpret", "blk"))
def index_scores_bwd(g, q_i, k_i, w, *, interpret: bool = False, blk: int = 0):
    """The cotangent ``g`` of I^T (B, S, S) -> (dq_i, dk_i, dw), each its operand's shape; dw float32."""
    B, J, S, Di = q_i.shape
    blk = block_for(S, blk)
    n = S // blk
    dq, dw, dk = pl.pallas_call(
        functools.partial(_index_scores_bwd_kernel, blk=blk, heads=J),
        grid=(B, n, n),
        in_specs=[
            pl.BlockSpec((1, blk, blk), lambda b, i, j: (b, j, i)),
            pl.BlockSpec((1, J, blk, Di), lambda b, i, j: (b, 0, i, 0)),
            pl.BlockSpec((1, blk, Di), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, J, 1, blk), lambda b, i, j: (b, 0, 0, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, J, blk, Di), lambda b, i, j: (b, 0, i, 0)),
            pl.BlockSpec((1, J, 1, blk), lambda b, i, j: (b, 0, 0, i)),
            pl.BlockSpec((1, 1, blk, Di), lambda b, i, j: (b, i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, J, S, Di), q_i.dtype),
            jax.ShapeDtypeStruct((B, J, 1, S), jnp.float32),
            jax.ShapeDtypeStruct((B, n, S, Di), jnp.float32),  # a partial sum a query block
        ],
        scratch_shapes=[pltpu.VMEM((J, blk, Di), jnp.float32), pltpu.VMEM((J, 1, blk), jnp.float32)],
        interpret=interpret,
        name="index_scores_bwd",
        compiler_params=_compiler_params("parallel", "parallel", "arbitrary", interpret=interpret,
                                         vmem_bytes=4 * J * blk * Di * q_i.dtype.itemsize + J * blk * Di * 4
                                         + 2 * blk * blk * g.dtype.itemsize + _tile_bytes(blk, blk)),
    )(g, q_i, k_i, w.reshape(B, J, 1, S))
    return dq, jnp.sum(dk, axis=1).astype(k_i.dtype), dw.reshape(B, J, S)


# ----------------------------------------------------------------------
# the choice
# ----------------------------------------------------------------------
def _ordered(x):
    """float32 -> int32 with the same order (-0.0 taken as 0.0)."""
    bits = jax.lax.bitcast_convert_type(jnp.where(x == 0.0, 0.0, x), jnp.int32)
    return bits ^ ((bits >> 31) & 0x7FFFFFFF)


def chunk_for(seq: int, rows: int = 0) -> int:
    """The rows of a band that ``index_select`` walks at a time."""
    return block_that_divides(seq, rows or CHUNK)


def chunks_walked(seq: int, topk: int, band: int, rows: int):
    """The chunks of ``rows`` keys a count pass of ``index_select`` walks, a band of ``band`` queries: the ones that hold
    a key the band's last query sees, and none in a band whose every query takes every visible key."""
    return [-(-last // rows) if last > topk else 0 for last in range(band, seq + 1, band)]


def share_walked(seq: int, topk: int, band: int = 0, rows: int = 0) -> float:
    """The rows a count pass walks over the rows the bands hold, summed over the bands: at 8,192 positions, 2,048 keys
    a query and bands of 128 queries 0.475 in chunks of 128 rows, 0.480 in chunks of 256."""
    rows = chunk_for(seq, rows)
    walked = chunks_walked(seq, topk, band or min(BAND, seq), rows)
    return sum(walked) * rows / (len(walked) * seq)


def _index_select_kernel(s_ref, o_ref, key_ref, *, topk: int, seq: int, band: int, rows: int):
    i = pl.program_id(1)
    last = band * (i + 1)  # the keys the band's last query sees
    n = pl.cdiv(last, rows)  # the chunks that hold one of them
    queries = i * band + jax.lax.broadcasted_iota(jnp.int32, (rows, band), 1)
    want = jnp.minimum(queries[:1] + 1, topk).astype(jnp.float32)  # (1, band): min(topk, t + 1)

    def chunk(c):
        return pl.ds(pl.multiple_of(c * rows, rows), rows)

    def keys(c):
        return c * rows + jax.lax.broadcasted_iota(jnp.int32, (rows, band), 0)

    def write(c, chosen):
        o_ref[0, chunk(c), :] = chosen.astype(jnp.int32).astype(jnp.int8)

    def count(hit):
        """How many of the band's keys ``hit(their ordered scores, their positions)`` a query: (1, band) float32, exact
        to 2**24. The walk is over the chunks at or below the band's diagonal; a chunk adds its rows eight at a time
        and the eight sublanes are summed once a pass."""
        def add(c, acc):
            return acc + jnp.sum(hit(key_ref[chunk(c), :], keys(c)).astype(jnp.float32).reshape(rows // 8, 8, band), axis=0)

        return jnp.sum(jax.lax.fori_loop(0, n, add, jnp.zeros((8, band), jnp.float32)), axis=0, keepdims=True)

    def each(first, stop, body):
        jax.lax.fori_loop(first, stop, lambda c, _: body(c), None)

    each(n, seq // rows, lambda c: write(c, jnp.zeros((rows, band), jnp.bool_)))  # no key past the diagonal

    @pl.when(last <= topk)
    def _every_visible_key():  # want == t + 1 for every query of the band
        each(0, n, lambda c: write(c, keys(c) <= queries))

    @pl.when(last > topk)
    def _search():
        def ordered(c):  # an entry past the diagonal in the last chunk: INT_MIN, which no pass counts
            key_ref[chunk(c), :] = jnp.where(keys(c) <= queries, _ordered(s_ref[0, chunk(c), :]), INT_MIN)

        each(0, n, ordered)
        # the largest T with count(key >= T) >= want: the sign first, then bit 30 down to 0
        t0 = jnp.where(count(lambda key, _: key >= 0) >= want, 0, INT_MIN).astype(jnp.int32)

        def bit(b, t):
            cand = t | jnp.left_shift(jnp.int32(1), 30 - b)
            return jnp.where(count(lambda key, _: key >= cand) >= want, cand, t)

        thr = jax.lax.fori_loop(0, 31, bit, t0)
        need = want - count(lambda key, _: key > thr)  # of the ties, the ones of lowest index: at least one

        # the largest P with count(ties below P) < need is the need-th tie's index
        def index_bit(b, p):
            cand = p | jnp.left_shift(jnp.int32(1), (seq - 1).bit_length() - 1 - b)
            return jnp.where(count(lambda key, at: (key == thr) & (at < cand)) < need, cand, p)

        tie = jax.lax.fori_loop(0, (seq - 1).bit_length(), index_bit, jnp.zeros((1, band), jnp.int32))

        def choose(c):
            key, at = key_ref[chunk(c), :], keys(c)
            write(c, (at <= queries) & ((key > thr) | ((key == thr) & (at <= tie))))

        each(0, n, choose)


@functools.partial(jax.jit, static_argnames=("topk", "interpret", "band", "rows"))
def index_select(scores_t, topk: int, *, interpret: bool = False, band: int = 0, rows: int = 0):
    """I^T (B, Sk, Sq) float32 -> the choice as an int8 mask (B, Sk, Sq): a query's ``min(topk, t + 1)``
    largest visible scores, ties to the lower index."""
    B, Sk, Sq = scores_t.shape
    band = band or min(BAND, Sq)
    rows = chunk_for(Sk, rows)
    return pl.pallas_call(
        functools.partial(_index_select_kernel, topk=int(topk), seq=Sk, band=band, rows=rows),
        grid=(B, Sq // band),
        in_specs=[pl.BlockSpec((1, Sk, band), lambda b, i: (b, 0, i))],
        out_specs=pl.BlockSpec((1, Sk, band), lambda b, i: (b, 0, i)),
        out_shape=jax.ShapeDtypeStruct((B, Sk, Sq), jnp.int8),
        scratch_shapes=[pltpu.VMEM((Sk, band), jnp.int32)],
        interpret=interpret,
        name="index_select",
        # the scores and the mask twice (the pipeline's), their integer form once, and a chunk's temporaries
        compiler_params=_compiler_params("parallel", "parallel", interpret=interpret, vmem_bytes=Sk * band * (2 * 4 + 2 + 4) + 8 * rows * band * 4),
    )(scores_t)


# ----------------------------------------------------------------------
# attention over the chosen keys
# ----------------------------------------------------------------------
def _chosen(mask_tile):
    return mask_tile.astype(jnp.int32) != 0


def _fwd_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref, *, blk: int, scale: float):
    i = pl.program_id(1)
    q = q_ref[0]
    D = v_ref.shape[-1]

    def body(j, carry):
        acc, m, l = carry  # (D, bq), (1, bq), (1, bq)
        rows = pl.dslice(j * blk, blk)
        k, v = k_ref[0, rows, :], v_ref[0, rows, :]
        s = jax.lax.dot_general(k, q, _NT, preferred_element_type=jnp.float32) * scale
        s = jnp.where(_chosen(mask_ref[0, j, 0]), s, NEG_INF)
        new_m = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
        p = jnp.exp(s - new_m)
        corr = jnp.exp(m - new_m)
        new_l = l * corr + jnp.sum(p, axis=0, keepdims=True)
        new_acc = acc * corr + jax.lax.dot_general(v, p.astype(v.dtype), _TN, preferred_element_type=jnp.float32)
        return new_acc, new_m, new_l

    init = (jnp.zeros((D, blk), jnp.float32), jnp.full((1, blk), NEG_INF, jnp.float32), jnp.zeros((1, blk), jnp.float32))
    acc, m, l = jax.lax.fori_loop(0, i + 1, body, init)
    o_ref[0] = (acc / l).T.astype(o_ref.dtype)
    lse_ref[0, 0] = m + jnp.log(l)


@functools.partial(jax.jit, static_argnames=("scale", "H", "KVH", "interpret"))
def sparse_fwd(q, k, v, mask_tiles, scale: float, H: int, KVH: int, *, interpret: bool = False):
    """q (B*H, S, D), k and v (B*KVH, S, D), the mask in tiles (B, S/blk, S/blk, blk, blk) -> o, lse (B*H, S)."""
    BH, S, D = q.shape
    blk, n, n_rep = mask_tiles.shape[-1], mask_tiles.shape[1], H // KVH
    kv_of = lambda b, h: b * KVH + h // n_rep
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, blk=blk, scale=scale),
        grid=(BH // H, n, H),  # heads innermost: a query block's mask stays, a KV head's keys change every n_rep steps
        in_specs=[
            pl.BlockSpec((1, blk, D), lambda b, i, h: (b * H + h, i, 0)),
            pl.BlockSpec((1, S, D), lambda b, i, h: (kv_of(b, h), 0, 0)),
            pl.BlockSpec((1, S, D), lambda b, i, h: (kv_of(b, h), 0, 0)),
            pl.BlockSpec((1, n, 1, blk, blk), lambda b, i, h: (b, 0, i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, blk, D), lambda b, i, h: (b * H + h, i, 0)),
            pl.BlockSpec((1, 1, 1, blk), lambda b, i, h: (b * H + h, i, 0, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((BH, S, D), q.dtype), jax.ShapeDtypeStruct((BH, n, 1, blk), jnp.float32)],
        interpret=interpret,
        name="sparse_fwd",
        compiler_params=_compiler_params("parallel", "parallel", "arbitrary", interpret=interpret,
                                         vmem_bytes=4 * (blk + S) * D * q.dtype.itemsize + 2 * n * blk * blk + _tile_bytes(blk, blk)),
    )(q, k, v, mask_tiles)
    return o, lse.reshape(BH, S)


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref, dq_ref, dk_ref, dv_ref, dq_acc, *kv_acc,
                n_rep: int, blk: int, scale: float):
    """``flash_attention._bwd_fused_kernel`` with the mask's tiles for the causal compare: grid (B*KVH, n_rep, S/blk),
    kv blocks innermost; a head's q, do, lse, delta and dq stay in VMEM over the walk."""
    rep, kj = pl.program_id(1), pl.program_id(2)
    last_kj = pl.num_programs(2) - 1

    @pl.when(kj == 0)
    def _zero():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    k, v = k_ref[0], v_ref[0]

    def body(i, carry):
        dk, dv = carry
        rows = pl.dslice(i * blk, blk)
        q, do = q_ref[0, rows, :], do_ref[0, rows, :]
        s = jax.lax.dot_general(k, q, _NT, preferred_element_type=jnp.float32) * scale
        p = jnp.exp(jnp.where(_chosen(mask_ref[0, 0, i]), s, NEG_INF) - lse_ref[0, i])
        dv = dv + jax.lax.dot_general(p.astype(do.dtype), do, _NN, preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(v, do, _NT, preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_ref[0, i])).astype(q.dtype)
        dk = dk + jax.lax.dot_general(ds, q, _NN, preferred_element_type=jnp.float32)
        dq_acc[rows, :] = dq_acc[rows, :] + jax.lax.dot_general(ds, k, _TN, preferred_element_type=jnp.float32)
        return dk, dv

    dk, dv = jax.lax.fori_loop(kj, pl.num_programs(2), body, (jnp.zeros(k.shape, jnp.float32), jnp.zeros(v.shape, jnp.float32)))
    dk = dk * scale

    @pl.when(kj == last_kj)
    def _dq_out():
        dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)

    if n_rep == 1:
        dk_ref[0] = dk.astype(dk_ref.dtype)
        dv_ref[0] = dv.astype(dv_ref.dtype)
        return
    rows = pl.dslice(kj * blk, blk)

    @pl.when(rep == 0)
    def _first():
        for acc, value in zip(kv_acc, (dk, dv)):
            acc[rows, :] = value

    @pl.when(rep > 0)
    def _add():
        for acc, value in zip(kv_acc, (dk, dv)):
            acc[rows, :] = acc[rows, :] + value

    @pl.when(jnp.logical_and(rep == n_rep - 1, kj == last_kj))
    def _dkv_out():
        for ref, acc in zip((dk_ref, dv_ref), kv_acc):
            ref[0] = acc[...].astype(ref.dtype)


def bwd_vmem(S: int, D: int, item: int, blk: int, n_rep: int) -> int:
    """What ``sparse_bwd`` holds in VMEM at once: the fused flash backward's count and a kv block's mask tiles, twice."""
    return _fused_bwd_vmem(S, S, D, item, blk, blk, n_rep) + 2 * S * blk


def bwd_budget() -> int:
    """Half the core's VMEM, where the flash kernels plan with three eighths: at 8,192 positions, 8 query heads a KV
    head of 128 and 512-blocks the fused backward's own count is 41.5 MiB and the mask's tiles add 8."""
    return vmem_budget() * 4 // 3


@functools.partial(jax.jit, static_argnames=("scale", "H", "KVH", "interpret"))
def sparse_bwd(q, k, v, o, lse, do, mask_tiles, scale: float, H: int, KVH: int, *, interpret: bool = False):
    BH, S, D = q.shape
    BKV = k.shape[0]
    blk, n, n_rep = mask_tiles.shape[-1], mask_tiles.shape[1], H // KVH
    need = bwd_vmem(S, D, q.dtype.itemsize, blk, n_rep)
    if need > bwd_budget():
        raise NotImplementedError(f"sparse_attention backward: a head's q, do and dq and its group's dk and dv at {S} positions, D={D}, "
                                  f"{n_rep} q heads a KV head take {need >> 20} MiB of VMEM, over {bwd_budget() >> 20} MiB")
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    q_of = lambda b, r: (b // KVH) * H + (b % KVH) * n_rep + r
    whole_q = lambda b, r, j: (q_of(b, r), 0, 0)
    rows_q = lambda b, r, j: (q_of(b, r), 0, 0, 0)
    kv_blk = pl.BlockSpec((1, blk, D), lambda b, r, j: (b, j, 0))
    if n_rep == 1:
        kv_out, kv_scratch = [kv_blk, kv_blk], []
    else:
        kv_out = [pl.BlockSpec((1, S, D), lambda b, r, j: (b, 0, 0))] * 2
        kv_scratch = [pltpu.VMEM((S, D), jnp.float32)] * 2
    return pl.pallas_call(
        functools.partial(_bwd_kernel, n_rep=n_rep, blk=blk, scale=scale),
        grid=(BKV, n_rep, n),
        in_specs=[
            pl.BlockSpec((1, S, D), whole_q), kv_blk, kv_blk, pl.BlockSpec((1, S, D), whole_q),
            pl.BlockSpec((1, n, 1, blk), rows_q), pl.BlockSpec((1, n, 1, blk), rows_q),
            pl.BlockSpec((1, 1, n, blk, blk), lambda b, r, j: (b // KVH, j, 0, 0, 0)),
        ],
        out_specs=[pl.BlockSpec((1, S, D), whole_q), *kv_out],
        out_shape=[jax.ShapeDtypeStruct((BH, S, D), q.dtype), jax.ShapeDtypeStruct((BKV, S, D), k.dtype),
                   jax.ShapeDtypeStruct((BKV, S, D), v.dtype)],
        scratch_shapes=[pltpu.VMEM((S, D), jnp.float32)] + kv_scratch,
        interpret=interpret,
        name="sparse_bwd",
        compiler_params=_compiler_params("parallel", "arbitrary", "arbitrary", interpret=interpret, vmem_bytes=need),
    )(q, k, v, do, _rows(lse, blk), _rows(delta, blk), mask_tiles)


def _loss_kernel(q_ref, k_ref, lse_ref, mask_ref, s_ref, kl_ref, g_ref, kept, z_ref, m_ref, l_ref, d_ref, *,
                 blk: int, heads: int, n_rep: int, scale: float, queries: int):
    """A query block's walk along its key blocks, twice: grid (B, S/blk, 2 S/blk). Steps ``j < n`` make the target's
    tiles ``P`` (in ``kept``, summed over the heads a strip at a time) and the rows' statistics; steps ``n + j`` write
    the gradient's tiles."""
    i, j = pl.program_id(1), pl.program_id(2)
    n = pl.num_programs(2) // 2

    @pl.when(j == 0)
    def _start():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        for ref in (z_ref, l_ref, d_ref):
            ref[...] = jnp.zeros_like(ref)

    @pl.when(j <= i)
    def _target():
        def walk(lanes):
            def add(h, p):  # a KV head's keys serve its ``n_rep`` query heads
                s = jax.lax.dot_general(k_ref[h // n_rep], q_ref[h, lanes, :], _NT, preferred_element_type=jnp.float32) * scale
                return p + jnp.exp(jnp.where(_chosen(mask_ref[0, 0, 0, :, lanes]), s, NEG_INF) - lse_ref[h, 0, :, lanes])

            _sum_over_heads(heads, add, kept, (j, slice(None), lanes))
            # the strip's statistics, over all its rows at once: a sum along the keys keeps the order it had a tile
            p = kept[j, :, lanes]
            scores = jnp.where(_chosen(mask_ref[0, 0, 0, :, lanes]), s_ref[0, :, lanes], NEG_INF)
            m = m_ref[:, lanes]
            new_m = jnp.maximum(m, jnp.max(scores, axis=0, keepdims=True))
            l_ref[:, lanes] = l_ref[:, lanes] * jnp.exp(m - new_m) + jnp.sum(jnp.exp(scores - new_m), axis=0, keepdims=True)
            m_ref[:, lanes] = new_m
            z_ref[:, lanes] += jnp.sum(p, axis=0, keepdims=True)
            some = p > 0.0  # a chosen pair's p can underflow: it adds nothing, as in XLA's form
            d_ref[:, lanes] += jnp.sum(jnp.where(some, p * (jnp.log(jnp.where(some, p, 1.0)) - scores), 0.0), axis=0, keepdims=True)

        _strips(blk, walk)

    @pl.when(j == n)
    def _loss():  # sum_s p (log p - log softmax I) with p = P / Z
        z = z_ref[...]
        kl_ref[0, 0] = d_ref[...] / z - jnp.log(z) + m_ref[...] + jnp.log(l_ref[...])

    @pl.when(jnp.logical_and(j >= n, j - n <= i))
    def _gradient():
        def gradient(lanes):
            lse_i = m_ref[:, lanes] + jnp.log(l_ref[:, lanes])
            soft = jnp.where(_chosen(mask_ref[0, 0, 0, :, lanes]), jnp.exp(s_ref[0, :, lanes] - lse_i), 0.0)
            g_ref[0, :, lanes] = ((soft - kept[j - n, :, lanes] * (1.0 / z_ref[:, lanes])) * (1.0 / queries)).astype(g_ref.dtype)

        _strips(blk, gradient)

    @pl.when(j - n > i)
    def _above():
        g_ref[0] = jnp.zeros_like(g_ref[0])


def loss_vmem(S: int, D: int, H: int, KVH: int, item: int, blk: int) -> int:
    """What ``index_loss`` holds in VMEM at once: a query block's strip of ``P``, the heads' q and the KV heads' block
    twice (the pipeline's), the tiles of the scores, the mask and the gradient twice, and a tile's temporaries."""
    return S * blk * 4 + 2 * (H + KVH) * blk * D * item + 2 * blk * blk * (4 + 1 + 4) + _tile_bytes(blk, blk)


@functools.partial(jax.jit, static_argnames=("scale", "H", "KVH", "dtype", "interpret"))
def index_loss(q, k, lse, mask_tiles, scores_t, scale: float, H: int, KVH: int, dtype, *, interpret: bool = False):
    """The indexer's loss where its target is made: q (B*H, S, D), k (B*KVH, S, D), the forward's lse (B*H, S), the mask
    in tiles and I^T (B, S, S) float32 -> every query's ``KL(p || softmax_{S_t} I)`` (B, S) float32, ``p`` the heads'
    probabilities over the chosen pairs summed and renormalised, and the gradient of the queries' MEAN in I^T, (softmax
    - p) / queries, key-major (B, S, S) in ``dtype``. The head-summed probabilities never leave VMEM."""
    BH, S, D = q.shape
    B = BH // H
    blk, n = mask_tiles.shape[-1], mask_tiles.shape[1]
    need = loss_vmem(S, D, H, KVH, q.dtype.itemsize, blk)
    if need > vmem_budget():
        raise NotImplementedError(f"index_loss: a query block's strip of probabilities at {S} positions and {H} heads' q of D={D} "
                                  f"take {need >> 20} MiB of VMEM, over {vmem_budget() >> 20} MiB")
    # the key block a step works on: ``j`` in the first sweep, ``j - n`` in the second, and the diagonal's where it has
    # passed the diagonal (a step that fetches nothing new)
    key = lambda i, j: jnp.minimum(j % n, i)
    kl, grad = pl.pallas_call(
        functools.partial(_loss_kernel, blk=blk, heads=H, n_rep=H // KVH, scale=scale, queries=B * S),
        grid=(B, n, 2 * n),
        in_specs=[
            pl.BlockSpec((H, blk, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((KVH, blk, D), lambda b, i, j: (b, jnp.minimum(j, i), 0)),  # the first sweep's alone
            pl.BlockSpec((H, 1, 1, blk), lambda b, i, j: (b, i, 0, 0)),
            pl.BlockSpec((1, 1, 1, blk, blk), lambda b, i, j: (b, key(i, j), i, 0, 0)),
            pl.BlockSpec((1, blk, blk), lambda b, i, j: (b, key(i, j), i)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, blk), lambda b, i, j: (b, i, 0, 0)),
            # the first sweep stays on the tile the second writes first: no tile leaves before it is made
            pl.BlockSpec((1, blk, blk), lambda b, i, j: (b, jnp.maximum(j - n, 0), i)),
        ],
        out_shape=[jax.ShapeDtypeStruct((B, n, 1, blk), jnp.float32), jax.ShapeDtypeStruct((B, S, S), dtype)],
        scratch_shapes=[pltpu.VMEM((n, blk, blk), jnp.float32)] + [pltpu.VMEM((1, blk), jnp.float32)] * 4,
        interpret=interpret,
        name="index_loss",
        compiler_params=_compiler_params("parallel", "parallel", "arbitrary", interpret=interpret, vmem_bytes=need),
    )(q, k, _rows(lse, blk), mask_tiles, scores_t)
    return kl.reshape(B, S), grad
