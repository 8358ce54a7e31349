"""Pallas flash attention (TPU).

Capability parity: the reference's fused attention kernels
(``csrc/transformer/inference/csrc/softmax.cu``, training softmax/
transform kernels in ``csrc/transformer``, blocked flash in
``inference/v2/kernels/ragged_ops/blocked_flash``). On TPU the win is the
same as on GPU: never materialize the (S, S) probability matrix in HBM —
blocked online softmax in VMEM feeding the MXU.

Forward and backward are both Pallas kernels, stitched with
``jax.custom_vjp``: two calls a layer, forward and ONE backward. Layout:
inputs (B, S, H, D) are transposed to (B, H, S, D). The forward runs a
(B*H, Sq/bq) grid and walks the kv blocks a q block sees. The backward
runs (B*KVH, n_rep, Sk/bk), kv blocks innermost: a head's q, do, lse and
delta stay in VMEM over the walk and so does its dq, so s, p, dp and ds are
formed once a block and dq, dk, dv all leave the one kernel in the input
dtype (``_bwd_fused_kernel``). Every kernel works on kv-major (bk, bq)
tiles, key position on sublanes: the per-row softmax statistics are (1, bq)
rows that reduce and broadcast along sublanes, and of the backward's five
products only dq's takes a transposed operand. A call's mask is a record
(``ops/masks.py``: none, causal, causal under a window, block diffusion over
a doubled row) that gives the elementwise test and, from both sides, the
tiles a walk visits as ``(first, end, masked)`` runs: mask arithmetic runs
only on the tiles that cross an edge of the mask (the diagonal, a window's
far edge, a block's), the tiles between take the same ``_scores`` with the
mask statically off, and a tile wholly outside is never visited.

What the code observes to choose a path (``program_regions_traced_total{region=
"mixer/kernel", op, pass, path, tiles_a_trip}`` counts the choice, docs/OBSERVABILITY.md): a bias takes the split backward,
the query-major dq kernels (dbias is written there) on a (B*H, Sq/bq) grid
and a dkv kernel on (B*H, Sk/bk). Without a bias there is the fused kernel
alone: a head whose q, do and dq do not fit ``vmem_budget()`` at once
(about 19,000 positions at D=128 in bf16 on a v5e, about 9,500 at D=256) is
refused by name when its backward is traced; no caller sends one yet. Under
GQA the kernel also holds a group's dk and dv of the whole sequence (past
about 9,000 positions at D=128, 4,700 at D=256): where that does not fit and
a head alone does, the backward runs a head at a time on copies of its KV
head and the group's gradients are added outside (``_flash_bwd``). GQA is native: KV stays
collapsed at (B, S, KVH, D) in HBM and the kernels route each q head to its
group's KV head by BlockSpec index map — at llama-70B-class 8:1 grouping
that is 8x less KV HBM traffic than pre-expanding, and dk/dv accumulate
across the group in-kernel, in float32, instead of materializing expanded
cotangents. The softmax scale multiplies the float32 scores, as in
``attention_xla``: scaling a bf16 operand first saves 4% of the forward and
doubles the kernels' distance from float32 (PERF.md, PR 26).

**Two tiles a trip in the forward.** A tile of the forward's walk is a chain:
``s = k q^T`` -> the column max over ALL of s -> ``exp`` -> the column sum ->
``v^T p``, every link waiting for the one before, and a loop trip a tile
gives Mosaic's scheduler nothing else to issue meanwhile: it overlaps what
the program's text puts side by side in one loop body and little else
(``ops/pallas/kda.py``, PR 47: heads written one after the other ran no
faster than one a step, 6.93 -> 6.53 ms, the same heads interleaved 3.63). So
over an unmasked run a trip takes tiles j and j + 1 (``_walk``, ``pair`` in
``_fwd_kernel``): both score products first, then tile j's online-softmax
update, then tile j + 1's against the updated m, l, acc. The second product
stands beside the first chain and ``v1^T p1`` beside the second; nothing in a
tile's arithmetic or in the order of the updates moves, so o and lse are, bit
for bit, one tile a trip's (on the chip too: the same digests at nine
shapes). A run's odd last tile and the masked runs (a mask's edges: the
diagonal's tile, a window's, a block's) take the body of one tile.
``tiles_a_trip`` chooses from what the call can see (the mask's longest
unmasked run at these shapes, the VMEM count) and counts its choice
(``program_regions_traced_total{tiles_a_trip}``). What it buys, by the
compiler's own bundles for the described v5e at SDAR's shape (PERF.md, PR 50):
an unmasked tile is 1,453 bundles alone (a masked one 2,435) and 1,270 in a
pair, where the two products' 1,024 MXU cycles are the floor: the pair's body
is ``s1 | s2 beside chain 1 | v1^T p1 beside chain 2 | v2^T p2`` at about
350 | 650 | 700 | 800 bundles, and ``v^T p``, whose weights are the sixteen
(128, 128) pieces of p with 128 rows of v^T each, is the long link. On the
chip the forward call falls by 9% at SDAR's shape (12.45 -> 11.31 ms), 5-11%
under a causal mask at 8,192, 2% at 2,048. The fused backward keeps one tile:
its unmasked tile is 2,517 bundles for five products (2,560 MXU cycles), two
abreast 2,297 each, and on the chip two ran no faster (21.78 | 22.06 ms at
SDAR's shape, 7.996 | 7.91 at Kimi-VL's, 0.840 | 0.918 at OLMo's): it is
bound by its products as Mosaic lowers them, not by its chain.

**A band narrower than a block.** A tile's time follows its SIDE, not its
pairs: a masked forward tile costs 820 cycles at (128, 128), 1,360 at (256,
256) and 2,530 at (512, 512) (the chip, PR 69: the window call of 64 heads x
8,192 x 128 under 128 keys by the two knobs), since every link of the chain
waits for the one before whatever it holds. So a window of 128 keys, whose
512 x 128 kept pairs a q block lie in two (512, 512) tiles today, gains
nothing from smaller programs (forward | backward 3.34 | 6.31 ms at (512,
512), 4.47 | 6.08 at (128, 128), 3.22 | 5.84 at best) and little from a
rolled loop over strips inside a program (4.45 | 5.40): four times fewer
pairs in tiles a third as long, one after the other. What follows the band
is a walk with NO loop (``_tiles``, ``masks.band_strip``, ``Causal.band``):
a program keeps its block of 512 rows and walks it as four strips of 128,
each against the diagonal's tile and the one before it, all eight tiles in
one basic block, where the scheduler lays the strips' chains side by side
(1,306 bundles a forward program for the described v5e where two masked (512,
512) tiles are 4,870). The mask's test, ``_scores``, the online softmax and
``_dkv_accumulate`` are the ones every other walk uses.
"""

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from ...analysis import knobs
from .. import masks, placement
from ..registry import REGISTRY
from ._utils import block_that_divides, compiler_params as _compiler_params, vmem_budget

NEG_INF = -1e30
# The name the forward's output and row statistics carry for a checkpoint policy. EVERY checkpointed block keeps them
# (``models/transformer.py::remat_keeps``), so its backward runs no second forward kernel; q, k and v carry no name here:
# a hybrid block keeps the projections they follow from elementwise, any other makes them again from its input
SAVED = "flash_attention"
LANES = 128  # min lane width for fp32 stores (canonical TPU l/m layout)
# for a kind's record (``LayerKind.joined``): the series that say how many tiles a trip of the kernels' walks takes, a pass
# (``tiles_a_trip`` gives the forward's; the backward's walk keeps one)
TILES_A_TRIP = {f"tiles_a_trip_{pass_}": ("mixer/kernel", ("1", "2"), "tiles_a_trip", {"pass": pass_}) for pass_ in ("fwd", "bwd")}

# Default blocks are large: the grid runs sequentially on the (single)
# tensor core, and a tile's VPU online-softmax chain stands between its two
# MXU products (one tile a trip: nothing runs beside it; two a trip, the
# forward's unmasked runs since PR 50: the other tile's product does) — many
# tiny (128,128) programs are latency-bound, not FLOP-bound. (512, 512) keeps
# the fp32 score block at 1 MB of VMEM, amortizes the chain over 16x more MXU
# work, and stays causal-efficient at the block boundary; it is also the
# fastest of the shapes from 256 to 2048 for the forward and the fused
# backward alike at S=2048, D=128 (PERF.md, PR 26). Overridable for autotuning.
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


_NT = (((1,), (1,)), ((), ()))  # a @ b^T: both operands contract their minor dim (native on the MXU)
_NN = (((1,), (0,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))  # a^T @ b: the transposed-lhs products (forward: v^T p; backward: ds^T k)


def _scores(q, k, slope, row0, col0, scale, mask, has_alibi, btile=None, *, masked=True, kv_major=False):
    """fp32 masked scores of one block: the ONE definition of the mask/bias
    math; fwd and every bwd kernel recompute s through this so they can
    never drift apart.

    Query-major (the dq kernels) gives (bq, bk); ``kv_major`` gives the
    transpose (bk, bq), key position on sublanes, which is what the forward
    and the kv-block-major backward want: the softmax's max and sum reduce
    along sublanes, dv and dk are plain products, and the per-row lse/delta
    broadcast along sublanes. ``masked=False`` is the same
    function for a block the caller knows lies wholly inside the mask (below
    the diagonal, inside the window): no iota, compare or select. ``mask``:
    the call's record (``ops/masks.py``), whose ``keep`` is the test.
    ``btile``: additive bias tile in the block's orientation (evoformer
    pair/mask bias, reference DS4Sci_EvoformerAttention), added before
    masking so masked entries stay exactly NEG_INF."""
    if kv_major:
        s = jax.lax.dot_general(k, q, _NT, preferred_element_type=jnp.float32) * scale
        col_dim, row_dim = 0, 1
    else:
        s = jax.lax.dot_general(q, k, _NT, preferred_element_type=jnp.float32) * scale
        col_dim, row_dim = 1, 0
    mask_here = mask.masks and masked
    if has_alibi or mask_here:
        cols = col0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, col_dim)
    if has_alibi:  # shift-invariant ALiBi: slope * key_position
        s = s + slope * cols.astype(jnp.float32)
    if btile is not None:
        s = s + btile.astype(jnp.float32)
    if mask_here:
        rows = row0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, row_dim)
        s = jnp.where(mask.keep(rows, cols), s, NEG_INF)
    return s


def _walk(runs, body, carry, pair=None):
    """``body(block, carry, masked)`` over every block of ``runs`` in order. With ``pair``, an unmasked run goes two
    tiles a trip, ``pair(block, carry)`` taking ``block`` and ``block + 1``, and its odd last tile alone (a run's bounds
    are traced values: a loop over its pairs, then the loop over what is left, one trip at most). The masked runs are a
    mask's edges, a tile or two each, and keep one tile a trip."""
    for first, end, masked in runs:
        if pair is not None and not masked:
            length = end - first  # a run may end before it starts (no tile)
            trips = (max(length, 0) if isinstance(length, int) else jnp.maximum(length, 0)) // 2
            carry = jax.lax.fori_loop(0, trips, lambda t, c, first=first: pair(first + 2 * t, c), carry)
            first = first + 2 * trips
        carry = jax.lax.fori_loop(first, end, functools.partial(body, masked=masked), carry)
    return carry


def _walk_band(band, body, carry):
    """``body(block, carry, masked=True)`` over the ``n`` blocks from ``first`` on, ``band = (first, n)`` with n static:
    straight-line text, so the tiles' products stand side by side for Mosaic's scheduler."""
    first, n = band
    for d in range(n):
        carry = body(first + d, carry, masked=True)
    return carry


def _needs_empty_guard(seq_q: int, seq_k: int, has_bias: bool) -> bool:
    """Whether ``p`` must be zeroed where ``s <= NEG_INF``. A row with no
    visible column has m == lse == NEG_INF, so exp(s - m) is 1 on its masked
    columns; such rows exist only when ``seq_q > seq_k`` (queries before the
    first key), or when a bias of -inf hides a whole row. Everywhere else a
    masked score underflows to exactly 0 in the exp and the per-element
    compare-and-select is work for nothing."""
    return has_bias or seq_q > seq_k


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


def _part(t, rows: int, whole: bool):
    """Rows ``[t * rows, (t + 1) * rows)`` of a program's block ``ref[0]``, as an index; the block itself where the
    program walks it ``whole`` (``t`` is 0 then, and the kernel's text is what it was before a block had strips)."""
    return (0,) if whole else (0, pl.dslice(t * rows, rows), slice(None))


def _fwd_kernel(q_ref, k_ref, v_ref, slopes_ref, bias_ref, o_ref, lse_ref, *, bq: int, bk: int, strips: int, seq_q: int,
                seq_k: int, scale: float, mask, has_alibi: bool, has_bias: bool, sqb1: bool, tiles_a_trip: int):
    """One q block against the kv blocks it sees, on kv-major (bk, bq) tiles
    (``_scores``): the running max and sum are (1, bq) rows that reduce and
    broadcast along sublanes, the accumulator is (D, bq) and turned once at
    the end, and lse leaves as the row the backward reads (``_rows``). With
    ``tiles_a_trip`` = 2 a trip of an unmasked run takes two tiles (the
    module's docstring): both score products stand first in the text, then the
    two online-softmax updates, one after the other as two trips make them.
    Under a band narrower than half the program's block (the module's
    docstring; ``masks.band_strip``) the block is ``strips`` q tiles of ``bq``
    rows, each against the two kv tiles the band crosses: the same ``scored``
    and ``update``, in straight-line text."""
    block = pl.program_id(1)
    D = v_ref.shape[-1]  # the value head size: q and k may have another (latent attention: 192 beside 128)
    guard = _needs_empty_guard(seq_q, seq_k, has_bias)
    whole = strips == 1

    def q_tile(t, qi):  # the program's t-th q tile, the call's qi-th
        q = q_ref[_part(t, bq, whole)]  # (bq, D) input dtype — MXU runs bf16 operands w/ fp32 accumulation
        slope = slopes_ref[0, 0, 0]
        row0 = seq_k - seq_q + qi * bq

        def scored(j, masked):
            k = k_ref[0, pl.dslice(j * bk, bk), :]
            btile = None
            if has_bias:  # the (bk, 1) column all rows share, or the (bq, bk) tile turned kv-major
                btile = bias_ref[0, pl.dslice(j * bk, bk), :] if sqb1 else bias_ref[0, :, pl.dslice(j * bk, bk)].T
            return _scores(q, k, slope, row0, j * bk, scale, mask, has_alibi, btile, masked=masked, kv_major=True)

        def update(j, s, carry, masked):
            acc, m, l = carry  # (D, bq), (1, bq), (1, bq)
            v = v_ref[0, pl.dslice(j * bk, bk), :]
            new_m = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
            p = jnp.exp(s - new_m)
            if guard and (masked or has_bias):
                p = jnp.where(s <= NEG_INF, 0.0, p)
            corr = jnp.exp(m - new_m)
            new_l = l * corr + jnp.sum(p, axis=0, keepdims=True)
            new_acc = acc * corr + jax.lax.dot_general(v, p.astype(v.dtype), _TN, preferred_element_type=jnp.float32)
            return new_acc, new_m, new_l

        def body(j, carry, masked):
            return update(j, scored(j, masked), carry, masked)

        def pair(j, carry):  # tiles j and j + 1 of an unmasked run: the second score product beside the first chain
            s0, s1 = scored(j, False), scored(j + 1, False)
            return update(j + 1, s1, update(j, s0, carry, False), False)

        acc0 = jnp.zeros((D, bq), jnp.float32)
        m0 = jnp.full((1, bq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((1, bq), jnp.float32)
        if not whole:  # a band: the same few kv tiles a q tile, each across an edge, and no loop (``masks.Causal.kv_band``)
            acc, m, l = _walk_band(mask.kv_band(qi, tile=bq, seq_q=seq_q, seq_k=seq_k), body, (acc0, m0, l0))
        else:
            runs = mask.kv_runs(qi, bq=bq, bk=bk, seq_q=seq_q, seq_k=seq_k)
            acc, m, l = _walk(runs, body, (acc0, m0, l0), pair if tiles_a_trip == 2 else None)
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[_part(t, bq, whole)] = (acc / l_safe).T.astype(o_ref.dtype)
        lse_ref[0, t] = m + jnp.log(l_safe)

    _tiles_of(block, strips, q_tile)


def _tiles_of(block, tiles: int, body):
    """``body(t, tile)`` for each of a program's ``tiles`` walk tiles, ``tile`` its number in the call: the block's own
    (no arithmetic on the program's index) where the program walks its block whole; else the strips one after the other
    in the program's text, with no loop between them (a strip's walk has none either: ``_walk_band``), so that Mosaic's
    scheduler, which overlaps what stands in one basic block, runs their chains side by side. Under ``lax.fori_loop`` the
    same strips took 2.71 | 4.80 ms forward | backward where these take 1.55 | 4.45 (``masks.band_strip`` has the
    table), and the text is SHORTER than the blocks' walk it replaces: eight tiles of (128, 128) in 1,306 bundles where
    the three runs' bodies of (512, 512) are 5,232 (forward, the described v5e), so set-up does not pay for it."""
    for t in range(tiles):
        body(t, block if tiles == 1 else block * tiles + t)


def _kv_of_fn(H: int, KVH: int):
    """q-head program index -> KV head index (GQA stays collapsed in HBM:
    the index map routes each q head to its group's KV head — no
    broadcast/materialize of the expanded (B, S, H, D) KV)."""
    n_rep = H // KVH

    def kv_of(b):
        return (b // H) * KVH + (b % H) // n_rep

    return kv_of


def _count_traced(pass_: str, path: str, unequal_heads: bool = False, mask=masks.Causal(), **choice):
    """The kernels are chosen while a program is traced, so that is where the
    choice is counted (docs/OBSERVABILITY.md): one a call site a trace, as
    ``op="flash"``, ``"mla"`` for a call whose values have another head size
    than its queries and keys (latent attention's), or the mask's own
    (``"blockdiff"``, whose ``path`` is ``"kernel"``: XLA's form is counted by
    its caller). The scope is the one the call already runs under
    (``ops/attention.py``; a ``custom_vjp``'s backward is traced under its
    forward's name stack)."""
    placement.count(masks.counted_op(mask, unequal_heads), path if mask.op == "flash" else "kernel", pass_, **choice)


def _tiles(mask, Sq: int, Sk: int, has_bias: bool):
    """``(bq, bk, sq, sk)``: the rows of a program's block along q and k (the grids' steps, and what a program holds of
    the operands it takes by the block), and the tiles of the kernels' walks. They are the same but under a band
    narrower than a block (``masks.band_strip``), where a program walks its block as strips: the forward a q block as q
    tiles of ``sq`` rows against kv tiles of ``sk``, the fused backward a kv block as kv tiles of ``sk`` against q tiles of
    ``sq``. A bias comes by the block, so its kernels keep the block."""
    bq, bk = mask.tile(Sq, DEFAULT_BQ, _blk), mask.tile(Sk, DEFAULT_BK, _blk)
    sq, sk = mask.strip(bq), mask.strip(bk)
    if has_bias or sq != sk or mask.band(tile=sq, seq_q=Sq, seq_k=Sk) is None:
        return bq, bk, bq, bk
    return bq, bk, sq, sk


def _flash_fwd(q, k, v, slopes, bias, scale: float, mask, interpret: bool, has_alibi: bool, bias_meta, H: int, KVH: int):
    BH, Sq, D = q.shape
    Sk, Dv = k.shape[1], v.shape[-1]
    has_bias = bias_meta is not None
    kv_of = _kv_of_fn(H, KVH)
    bq, bk, sq, sk = _tiles(mask, Sq, Sk, has_bias)
    strips = bq // sq
    # a mask with a walk of its own also says, static at trace time, what the walk visits (``masks.py::walk_labels``): a
    # walk that visits more shows on the trainer's first-call line without a capture
    visited = (Sq // sq) * mask.band(tile=sq, seq_q=Sq, seq_k=Sk)[1] if strips > 1 else masks.tiles_visited(mask, bq=sq, bk=sk, seq_q=Sq, seq_k=Sk)
    walk = mask.walk_labels(f"{sq}x{sk}", f"{visited}/{(Sq // sq) * (Sk // sk)}")
    # without bias a (1,1,LANES) dummy rides along so the kernel arity is
    # fixed; with bias, broadcast dims stay COLLAPSED in HBM and the index
    # map routes every program to its shared block
    sqb1 = has_bias and bias_meta[2] == 1
    bias_bh = _bias_bh_fn(bias_meta, H) if has_bias else None
    if sqb1:  # the kv-major kernel adds a row-broadcast bias as a (Sk, 1) column
        bias = bias.reshape(-1, Sk, 1)
        bias_spec = pl.BlockSpec((1, Sk, 1), lambda b, i: (bias_bh(b), 0, 0))
    elif has_bias:
        bias_spec = pl.BlockSpec((1, bq, Sk), lambda b, i: (bias_bh(b), i, 0))
    else:
        bias_spec = pl.BlockSpec((1, 1, LANES), lambda b, i: (0, 0, 0))
    vmem = (2 * (bq * (D + Dv) + Sk * (D + Dv)) * q.dtype.itemsize + _tile_bytes(sq, sk)
            + (2 * (LANES if sqb1 else bq) * Sk * 4 if has_bias else 0))
    tiles = 1 if strips > 1 else tiles_a_trip(masks.longest_whole_run(mask, bq=sq, bk=sk, seq_q=Sq, seq_k=Sk), vmem)
    _count_traced("fwd", "single", Dv != D, mask, tiles_a_trip=str(tiles), **walk)
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, bq=sq, bk=sk, strips=strips, seq_q=Sq, seq_k=Sk, scale=scale, mask=mask,
                          has_alibi=has_alibi, has_bias=has_bias, sqb1=sqb1, tiles_a_trip=tiles),
        grid=(BH, Sq // bq),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, Sk, D), lambda b, i: (kv_of(b), 0, 0)),
            pl.BlockSpec((1, Sk, Dv), lambda b, i: (kv_of(b), 0, 0)),
            pl.BlockSpec((1, 1, LANES), lambda b, i: (b, 0, 0)),
            bias_spec,
        ],
        out_specs=[
            pl.BlockSpec((1, bq, Dv), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, bq // sq, 1, sq), lambda b, i: (b, i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Sq, Dv), q.dtype),
            jax.ShapeDtypeStruct((BH, Sq // sq, 1, sq), jnp.float32),  # one row a q tile: _rows
        ],
        interpret=interpret,
        name=f"{mask.kernel}_fwd",  # the custom call's name on the device's clock
        compiler_params=_compiler_params("parallel", "arbitrary", interpret=interpret, vmem_bytes=vmem),
    )(q, k, v, slopes, bias)
    return o, lse.reshape(BH, Sq)


# ----------------------------------------------------------------------
# backward
# ----------------------------------------------------------------------
def _dq_accumulate(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, slope, bias_ref, on_dlogits, qi, *,
                   bq, bk, seq_q, seq_k, scale, mask, has_alibi):
    """(bq, D) dq of one q block under a bias: the ONE definition of the
    query-major gradient algebra, shared by both dq kernels.
    ``on_dlogits(j, dlogits)`` receives each visited block's (bq, bk) logit
    gradient (dbias). A bias can hide a whole row, so the empty-row guard
    (``_needs_empty_guard``) is always on here."""
    q = q_ref[0]
    do = do_ref[0]
    lse = lse_ref[0, :, 0]
    delta = delta_ref[0, :, 0]

    def body(j, dq, masked):
        k = k_ref[0, pl.dslice(j * bk, bk), :]
        v = v_ref[0, pl.dslice(j * bk, bk), :]
        btile = bias_ref[0, :, pl.dslice(j * bk, bk)]
        s = _scores(q, k, slope, seq_k - seq_q + qi * bq, j * bk, scale, mask, has_alibi, btile, masked=masked)
        p = jnp.where(s <= NEG_INF, 0.0, jnp.exp(s - lse[:, None]))
        dp = jax.lax.dot_general(do, v, _NT, preferred_element_type=jnp.float32)  # (bq, bk)
        dlogits = p * (dp - delta[:, None])
        on_dlogits(j, dlogits)
        return dq + jax.lax.dot_general(dlogits.astype(k.dtype), k, _NN, preferred_element_type=jnp.float32)

    runs = mask.kv_runs(qi, bq=bq, bk=bk, seq_q=seq_q, seq_k=seq_k)
    return _walk(runs, body, jnp.zeros((bq, q.shape[-1]), jnp.float32)) * scale


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, slopes_ref, bias_ref, dq_ref, dbias_ref,
               **statics):
    """dq and per-program dbias tiles on a (B*H, Sq/bq) grid. The bias path
    is its only caller (dbias is written here); without a bias dq comes out
    of ``_bwd_fused_kernel``."""
    # blocks the loop skips contribute zero dbias; clear the whole row
    # band first so skipped tiles don't hold stale VMEM contents
    dbias_ref[0] = jnp.zeros_like(dbias_ref[0])

    def on_dlogits(j, dlogits):  # dbias = dlogits (bias enters the logits additively, unscaled)
        dbias_ref[0, :, pl.dslice(j * statics["bk"], statics["bk"])] = dlogits.astype(dbias_ref.dtype)

    dq = _dq_accumulate(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, slopes_ref[0, 0, 0], bias_ref,
                        on_dlogits, pl.program_id(1), **statics)
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _dq_kernel_collapsed(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, slopes_ref, bias_ref, dq_ref,
                         dbias_ref, *, sqb1: bool, **statics):
    """dq + ACCUMULATED dbias for a collapsed (broadcast) bias.

    Grid (n_bh, Sq//bq, n_rep) with the repeat dim innermost: every program
    sharing one bias row visits the same dbias block consecutively, so the
    block stays resident and read-modify-write accumulates — dbias never
    expands past the bias's own (collapsed) shape in HBM. First visit
    zeroes the block (``rep==0``, and ``qi==0`` too when rows broadcast).
    """
    qi = pl.program_id(1)
    rep = pl.program_id(2)
    bk = statics["bk"]
    first = jnp.logical_and(qi == 0, rep == 0) if sqb1 else (rep == 0)

    @pl.when(first)
    def _zero():
        dbias_ref[0] = jnp.zeros_like(dbias_ref[0])

    def on_dlogits(j, dlogits):
        contrib = jnp.sum(dlogits, axis=0, keepdims=True) if sqb1 else dlogits
        cur = dbias_ref[0, :, pl.dslice(j * bk, bk)]
        dbias_ref[0, :, pl.dslice(j * bk, bk)] = cur + contrib.astype(dbias_ref.dtype)

    dq = _dq_accumulate(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, slopes_ref[0, 0, 0], bias_ref,
                        on_dlogits, qi, **statics)
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _dkv_accumulate(q_ref, k, v, do_ref, lse_ref, delta_ref, slope, btile_fn, kj, on_ds=None, *,
                    bq, bk, seq_q, seq_k, scale, mask, has_alibi, has_bias=False, band=False):
    """(bk, D) dk/dv for one kv block — the ONE definition of the dkv
    gradient algebra (visible-q-block runs + ds formula), shared by the
    fused and the per-q-head (bias) kernels so they can never drift apart.
    Works on transposed (bk, bq) tiles (``_scores``).
    ``lse_ref`` / ``delta_ref`` hold one (1, bq) row a q block (``_rows``).
    ``btile_fn(i)`` returns the (bk, bq) additive-bias tile for q block i (or
    None). ``on_ds(i, ds)``, where given, receives each visited block's
    (bk, bq) logit gradient in the input dtype: dq's share is ``ds^T @ k``
    (times ``scale``), which the fused kernel adds up instead of a second
    kernel recomputing s, p and dp to get there."""
    guard = _needs_empty_guard(seq_q, seq_k, has_bias)

    def body(i, carry, masked):
        dk, dv = carry
        q = q_ref[0, pl.dslice(i * bq, bq), :]
        do = do_ref[0, pl.dslice(i * bq, bq), :]
        s = _scores(q, k, slope, seq_k - seq_q + i * bq, kj * bk, scale, mask, has_alibi, btile_fn(i), masked=masked,
                    kv_major=True)
        p = jnp.exp(s - lse_ref[0, i])
        if guard and (masked or has_bias):
            p = jnp.where(s <= NEG_INF, 0.0, p)
        dv = dv + jax.lax.dot_general(p.astype(do.dtype), do, _NN, preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(v, do, _NT, preferred_element_type=jnp.float32)  # (bk, bq)
        ds = (p * (dp - delta_ref[0, i])).astype(q.dtype)  # the logits' gradient; ``scale`` goes onto the sums
        dk = dk + jax.lax.dot_general(ds, q, _NN, preferred_element_type=jnp.float32)
        if on_ds is not None:
            on_ds(i, ds)
        return dk, dv

    if band:  # the same few q tiles a kv tile, and no loop (``masks.Causal.q_band``)
        walk = functools.partial(_walk_band, mask.q_band(kj, tile=bk, seq_q=seq_q, seq_k=seq_k))
    else:
        walk = functools.partial(_walk, mask.q_runs(kj, bq=bq, bk=bk, seq_q=seq_q, seq_k=seq_k))
    dk, dv = walk(body, (jnp.zeros(k.shape, jnp.float32), jnp.zeros(v.shape, jnp.float32)))
    return dk * scale, dv


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, slopes_ref, bias_ref, dk_ref, dv_ref, *,
                sqb1: bool, **statics):
    """dk/dv on a (B*H, Sk/bk) grid: the bias path's (KV arrives expanded)."""
    kj = pl.program_id(1)
    bq = statics["bq"]

    def btile_fn(i):  # kv-major: the (bq, bk) tile transposed, or the (bk, 1) column all q rows share
        return bias_ref[0] if sqb1 else bias_ref[0, pl.dslice(i * bq, bq), :].T

    dk, dv = _dkv_accumulate(q_ref, k_ref[0], v_ref[0], do_ref, lse_ref, delta_ref, slopes_ref[0, 0, 0],
                             btile_fn, kj, has_bias=True, **statics)
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, slopes_ref, dq_ref, dk_ref, dv_ref,
                      dq_acc, *kv_acc, n_rep: int, strips: int, **statics):
    """dq, dk and dv in ONE walk: grid (B*KVH, n_rep, Sk//bk), kv blocks
    innermost. A head's q, do, lse and delta stay in VMEM over the walk (as
    in the dkv kernels) and so does its dq, as a float32 scratch that every
    kv block adds ``ds^T @ k`` into and the last one writes out in the input
    dtype: s, p, dp and ds are formed once a block instead of once in each of
    two kernels. With n_rep == 1 each dk/dv block has one visit and leaves in
    the input dtype; a GQA group adds its heads up in float32 scratch over
    the whole (Sk, D) and writes once, after its last head. Under a band
    narrower than half the program's block (``masks.band_strip``) the block is
    ``strips`` kv tiles of ``bk`` rows, each against the two q tiles that see
    it: the same ``_dkv_accumulate``, in straight-line text."""
    rep, block = pl.program_id(1), pl.program_id(2)
    last = pl.num_programs(2) - 1
    bq, bk = statics["bq"], statics["bk"]
    whole = strips == 1

    @pl.when(block == 0)
    def _zero():  # a window or seq_q < seq_k leaves q blocks that kv block 0 never visits
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def kv_tile(t, kj):  # the program's t-th kv tile, the call's kj-th
        k = k_ref[_part(t, bk, whole)]

        def on_ds(i, ds):
            rows = pl.dslice(i * bq, bq)
            dq_acc[rows, :] = dq_acc[rows, :] + jax.lax.dot_general(ds, k, _TN, preferred_element_type=jnp.float32)

        return _dkv_accumulate(q_ref, k, v_ref[_part(t, bk, whole)], do_ref, lse_ref, delta_ref, slopes_ref[0, 0, 0],
                               lambda i: None, kj, on_ds, band=not whole, **statics)

    def dq_out():
        @pl.when(block == last)
        def _dq_out():
            dq_ref[0] = (dq_acc[...] * statics["scale"]).astype(dq_ref.dtype)

    def dkv_out(t, kj, dk, dv):
        if n_rep == 1:
            dk_ref[_part(t, bk, whole)] = dk.astype(dk_ref.dtype)
            dv_ref[_part(t, bk, whole)] = dv.astype(dv_ref.dtype)
            return
        # the float32 sum over the group's q heads: the first assigns (no zero fill), the rest add
        rows = pl.dslice(kj * bk, bk)

        @pl.when(rep == 0)
        def _first():
            for acc, value in zip(kv_acc, (dk, dv)):
                acc[rows, :] = value

        @pl.when(rep > 0)
        def _add():
            for acc, value in zip(kv_acc, (dk, dv)):
                acc[rows, :] = acc[rows, :] + value

    if whole:  # (the text of the kernel before a block had strips: dq leaves ahead of the block's dk and dv)
        dk, dv = kv_tile(0, block)
        dq_out()
        dkv_out(0, block, dk, dv)
    else:
        _tiles_of(block, strips, lambda t, kj: dkv_out(t, kj, *kv_tile(t, kj)))
        dq_out()
    if n_rep == 1:
        return

    @pl.when(jnp.logical_and(rep == n_rep - 1, block == last))
    def _dkv_out():
        for ref, acc in zip((dk_ref, dv_ref), kv_acc):
            ref[0] = acc[...].astype(ref.dtype)


def _tile_bytes(bq: int, bk: int) -> int:
    """VMEM for a trip's (bq, bk) temporaries: s, p, dp, ds in float32 and
    their casts. The generous count for one tile, and room for the forward's
    two: by Mosaic's own allocation for the described v5e at (512, 512) (the
    least limit a call compiles under, one tile a trip | two; PERF.md, PR 50)
    the second tile adds 2.0 MiB to a first of about 2 (SDAR's forward 19.2 |
    21.2 MiB beside our count of 24, OLMo's 4.7 | 5.9 beside 10.5)."""
    return 8 * bq * bk * 4


def tiles_a_trip(whole_run: int, vmem: int) -> int:
    """How many tiles a trip of the forward's walk takes over an unmasked run, from what the call can see: 2 where the
    mask has an unmasked run of two tiles or more at these shapes (``whole_run``: ``masks.longest_whole_run``) and the
    call's count of VMEM (``_tile_bytes`` has room for the pair) is within ``vmem_budget()``; else 1, the kernel as it
    was (the same code, one tile a trip). Written from this table (TPU v5e, the forward call alone in ms, one | two;
    PERF.md, PR 50): SDAR's mask at 32 x 16,384 x 128, runs of up to 16: 12.45 | 11.31; causal at 16 x 8,192: 2.745 |
    2.43 at 128, 3.421 | 3.251 at 192/128, 3.447 | 3.09 at 64/128 under GQA 20/10, 4.195 | 4.012 at 256 under GQA 16/2;
    at 4,096 (runs of up to 7) 0.833 | 0.78; OLMo's 32 x 2,048 (up to 3) 0.4564 | 0.4463. Under a window of one tile
    (512 at 8,192: no unmasked run) the pair's loops are never entered and cost 1.115 | 1.137: there the rule says 1."""
    return 2 if whole_run >= 2 and vmem <= vmem_budget() else 1


def _fused_bwd_vmem(Sq: int, Sk: int, D: int, item: int, bq: int, bk: int, n_rep: int, Dv: int = 0) -> int:
    """Bytes of VMEM the fused backward holds at once: what the grid keeps
    resident (inputs and outputs double-buffered by the pipeline), the
    float32 scratch, and the block temporaries. A head size is counted as the
    whole vregs of lanes it takes there: a block of 64 columns fills 128 (this
    count holds whole sequences and decides the backward's form; the
    forward's and the split kernels' hold a q tile and stay far under)."""
    lanes = lambda d: -(-d // LANES) * LANES
    D, Dv = lanes(D), lanes(Dv or D)  # the value head size, where it is not the keys'
    head = 2 * (Sq * (D + Dv) * item + 2 * 8 * Sq * 4 + Sq * D * item) + Sq * D * 4  # q, do, lse, delta, dq out; dq_acc
    kv_in = 2 * bk * (D + Dv) * item
    kv_out = kv_in if n_rep == 1 else 2 * Sk * (D + Dv) * item + Sk * (D + Dv) * 4
    return head + kv_in + kv_out + _tile_bytes(bq, bk)


def _rows(x, bq: int):
    """(BH, Sq) per-row statistics as one (1, bq) row a q block,
    (BH, Sq//bq, 1, bq): what the kv-major kernels subtract from a (bk, bq)
    tile along sublanes, at Sq floats a head in VMEM where the lane-broadcast
    (Sq, LANES) form the query-major kernels read takes 128 times that."""
    BH, Sq = x.shape
    return x.reshape(BH, Sq // bq, 1, bq)


def _flash_bwd(q, k, v, o, lse, do, slopes, bias, scale: float, mask, interpret: bool, has_alibi: bool, bias_meta,
               H: int, KVH: int):
    BH, Sq, D = q.shape
    BKV, Sk, _ = k.shape  # B * KVH (GQA stays collapsed)
    Dv = v.shape[-1]
    has_bias = bias_meta is not None
    kv_of = _kv_of_fn(H, KVH)
    n_rep = H // KVH
    bq, bk, sq, sk = _tiles(mask, Sq, Sk, has_bias)  # (under a bias the walk's tiles are the programs' blocks: bq, bk below)
    item = q.dtype.itemsize
    statics = dict(bq=sq, bk=sk, seq_q=Sq, seq_k=Sk, scale=scale, mask=mask, has_alibi=has_alibi)
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)  # (BH, Sq)
    lse_rows, delta_rows = _rows(lse, sq), _rows(delta, sq)
    nq = Sq // sq

    def q_of(bkv, rep):
        return (bkv // KVH) * H + (bkv % KVH) * n_rep + rep

    if not has_bias:
        fused_vmem = _fused_bwd_vmem(Sq, Sk, D, item, bq, bk, n_rep, Dv)
        if fused_vmem > vmem_budget() >= _fused_bwd_vmem(Sq, Sk, D, item, bq, bk, 1, Dv) and n_rep > 1:
            # a group's dk and dv of the whole sequence, in float32 and as outputs, do not fit beside a head's q, do
            # and dq, and a head alone does (16 q heads on 2 KV heads of 256 at 8,192 positions: 74 MiB against 43):
            # every q head gets a copy of its KV head, and the group's dk and dv are added outside the kernel
            per_head = lambda x: jnp.repeat(x, n_rep, axis=0)
            dq, dk, dv, dbias = _flash_bwd(q, per_head(k), per_head(v), o, lse, do, slopes, bias, scale, mask, interpret,
                                           has_alibi, bias_meta, H, H)
            group = lambda x, like: x.reshape(BKV, n_rep, *x.shape[1:]).astype(jnp.float32).sum(1).astype(like.dtype)
            return dq, group(dk, k), group(dv, v), dbias
        if fused_vmem > vmem_budget():
            raise NotImplementedError(
                f"flash_attention backward: a head's q, do and dq at seq_q={Sq}, seq_k={Sk}, D={D}, {q.dtype.name}, "
                f"{n_rep} q heads a KV head take {fused_vmem >> 20} MiB of VMEM, over this device's budget of "
                f"{vmem_budget() >> 20} MiB: split the sequence over the mesh (sequence or context parallelism)")
        _count_traced("bwd", "fused", Dv != D, mask, tiles_a_trip="1", **mask.walk_labels(f"{sq}x{sk}"))
        whole_q = lambda b, r, j: (q_of(b, r), 0, 0)
        rows_q = lambda b, r, j: (q_of(b, r), 0, 0, 0)
        kv_blk = [pl.BlockSpec((1, bk, d), lambda b, r, j: (b, j, 0)) for d in (D, Dv)]
        if n_rep == 1:
            kv_out, kv_scratch = kv_blk, []
        else:
            kv_out = [pl.BlockSpec((1, Sk, d), lambda b, r, j: (b, 0, 0)) for d in (D, Dv)]
            kv_scratch = [pltpu.VMEM((Sk, d), jnp.float32) for d in (D, Dv)]
        dq, dk, dv = pl.pallas_call(
            functools.partial(_bwd_fused_kernel, n_rep=n_rep, strips=bk // sk, **statics),
            grid=(BKV, n_rep, Sk // bk),
            in_specs=[
                pl.BlockSpec((1, Sq, D), whole_q),
                *kv_blk,
                pl.BlockSpec((1, Sq, Dv), whole_q),
                pl.BlockSpec((1, nq, 1, sq), rows_q),
                pl.BlockSpec((1, nq, 1, sq), rows_q),
                pl.BlockSpec((1, 1, LANES), whole_q),
            ],
            out_specs=[pl.BlockSpec((1, Sq, D), whole_q), *kv_out],
            out_shape=[
                jax.ShapeDtypeStruct((BH, Sq, D), q.dtype),
                jax.ShapeDtypeStruct((BKV, Sk, D), k.dtype),
                jax.ShapeDtypeStruct((BKV, Sk, Dv), v.dtype),
            ],
            scratch_shapes=[pltpu.VMEM((Sq, D), jnp.float32)] + kv_scratch,
            interpret=interpret,
            name=f"{mask.kernel}_bwd",  # the custom call's name on the device's clock
            compiler_params=_compiler_params("parallel", "arbitrary", "arbitrary", interpret=interpret,
                                             vmem_bytes=fused_vmem),
        )(q, k, v, do, lse_rows, delta_rows, slopes)
        return dq, dk, dv, jnp.zeros((1, 1, LANES), jnp.float32)

    # a bias: the query-major dq kernels (dbias is written there; they read the lane-broadcast form) and a
    # dkv kernel a q head
    if Dv != D:
        raise NotImplementedError(f"flash_attention backward under a bias takes one head size, got {D} and {Dv}")
    _count_traced("bwd", "split", tiles_a_trip="1")
    lse, delta = (jnp.broadcast_to(x[..., None], (BH, Sq, LANES)) for x in (lse, delta))
    Bb, Hb, Sqb, repeat = bias_meta
    bias_bh = _bias_bh_fn(bias_meta, H)
    sqb1 = Sqb == 1
    n_bh = Bb * Hb
    collapsed = n_bh < BH or sqb1
    sq_rows = 1 if sqb1 else bq
    bias_spec_q3 = pl.BlockSpec((1, sq_rows, Sk), lambda bh, i, rep: (bh, 0 if sqb1 else i, 0))
    bias_spec_q2 = pl.BlockSpec((1, sq_rows, Sk), lambda b, i: (bias_bh(b), 0 if sqb1 else i, 0))
    # the kv-major dkv kernel adds a row-broadcast bias as a (bk, 1) column
    bias_k = bias.reshape(n_bh, Sk, 1) if sqb1 else bias
    bias_spec_k = (pl.BlockSpec((1, bk, 1), lambda b, j: (bias_bh(b), j, 0)) if sqb1
                   else pl.BlockSpec((1, Sq, bk), lambda b, j: (bias_bh(b), 0, j)))
    dbias_shape = (n_bh, 1 if sqb1 else Sq, Sk)
    dq_vmem = (2 * (3 * bq * D * item + 2 * Sk * D * item + 2 * bq * LANES * 4) + _tile_bytes(bq, bk)
               + 4 * sq_rows * Sk * 4)
    dkv_vmem = (2 * (2 * Sq * D * item + 2 * 8 * Sq * 4 + 4 * bk * D * 4) + _tile_bytes(bq, bk)
                + 2 * (LANES if sqb1 else Sq) * bk * 4)

    if not collapsed:
        # one dbias block per (b, i) program — plain tiled writes
        dq, dbias = pl.pallas_call(
            functools.partial(_dq_kernel, **statics),
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
                pl.BlockSpec((1, bq, Sk), lambda b, i: (b, i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((BH, Sq, D), q.dtype),
                jax.ShapeDtypeStruct(dbias_shape, jnp.float32),
            ],
            interpret=interpret,
            name="flash_dq",  # the custom call's name on the device's clock
            compiler_params=_compiler_params("parallel", "arbitrary", interpret=interpret, vmem_bytes=dq_vmem),
        )(q, k, v, do, lse, delta, slopes, bias)
    else:
        # broadcast bias: repeat dim innermost so every program sharing a
        # bias row revisits its dbias block consecutively and accumulates
        n_share = BH // n_bh

        def q_b(bh, rep):
            if Bb == 1 and Hb == 1:
                return rep
            if Hb == 1:  # batch collapsed by `repeat`, heads all share
                return (bh * repeat + rep // H) * H + rep % H
            if Bb == 1:  # only heads distinct
                return rep * H + bh
            return ((bh // H) * repeat + rep) * H + bh % H

        dq, dbias = pl.pallas_call(
            functools.partial(_dq_kernel_collapsed, sqb1=sqb1, **statics),
            grid=(n_bh, Sq // bq, n_share),
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
            name="flash_dq",  # the custom call's name on the device's clock
            compiler_params=_compiler_params("parallel", "arbitrary", "arbitrary", interpret=interpret,
                                             vmem_bytes=dq_vmem),
        )(q, k, v, do, lse, delta, slopes, bias)

    # bias path: KV arrives expanded (flash_attention falls back to
    # expansion when bias x GQA combine), so the per-q-head grid stands
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, sqb1=sqb1, **statics),
        grid=(BH, Sk // bk),
        in_specs=[
            pl.BlockSpec((1, Sq, D), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, Sq, D), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, nq, 1, bq), lambda b, j: (b, 0, 0, 0)),
            pl.BlockSpec((1, nq, 1, bq), lambda b, j: (b, 0, 0, 0)),
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
        name="flash_dkv",  # the custom call's name on the device's clock
        compiler_params=_compiler_params("parallel", "arbitrary", interpret=interpret, vmem_bytes=dkv_vmem),
    )(q, k, v, do, lse_rows, delta_rows, slopes, bias_k)
    return dq, dk, dv, dbias


# ----------------------------------------------------------------------
# public op: (B, S, H, D) layout + GQA + custom_vjp
# ----------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10, 11))
def _flash(q, k, v, slopes, bias, scale, mask, interpret, has_alibi, bias_meta, H, KVH):
    o, _ = _flash_core(q, k, v, slopes, bias, scale, mask, interpret, has_alibi, bias_meta, H, KVH)
    return o


def _bh_slopes(slopes, B, H):
    """(H,) per-head slopes -> (B*H, 1, LANES) per-program rows.

    3D on purpose: real TPU lowering requires the last two block dims to be
    divisible by (8, 128) or equal the array dims — a (1, LANES) block over
    a 2D (B*H, LANES) array is rejected (only interpret mode accepts it).
    With a leading program dim the (1, LANES) tail matches exactly."""
    flat = jnp.tile(jnp.asarray(slopes, jnp.float32), B)  # (B*H,)
    return jnp.broadcast_to(flat[:, None, None], (B * H, 1, LANES))


def _flash_core(q, k, v, slopes, bias, scale, mask, interpret, has_alibi, bias_meta, H, KVH):
    B, Sq, _, _ = q.shape
    to_bh = lambda x: jnp.transpose(x, (0, 2, 1, 3)).reshape(B * x.shape[2], x.shape[1], x.shape[3])
    o, lse = _flash_fwd(to_bh(q), to_bh(k), to_bh(v), _bh_slopes(slopes, B, H), bias,
                        scale, mask, interpret, has_alibi, bias_meta, H, KVH)
    o = o.reshape(B, H, Sq, v.shape[-1]).transpose(0, 2, 1, 3)
    return o, lse


def _flash_vjp_fwd(q, k, v, slopes, bias, scale, mask, interpret, has_alibi, bias_meta, H, KVH):
    o, lse = _flash_core(q, k, v, slopes, bias, scale, mask, interpret, has_alibi, bias_meta, H, KVH)
    # named: a block under jax.checkpoint whose policy lists the name keeps them and runs no second forward kernel
    o, lse = checkpoint_name(o, SAVED), checkpoint_name(lse, SAVED)
    return o, (q, k, v, slopes, bias, o, lse)


def _flash_vjp_bwd(scale, mask, interpret, has_alibi, bias_meta, H, KVH, res, do):
    q, k, v, slopes, bias, o, lse = res
    B, Sq, _, _ = q.shape
    Sk = k.shape[1]
    to_bh = lambda x: jnp.transpose(x, (0, 2, 1, 3)).reshape(B * x.shape[2], x.shape[1], x.shape[3])
    dq, dk, dv, dbias = _flash_bwd(to_bh(q), to_bh(k), to_bh(v), to_bh(o), lse, to_bh(do),
                                   _bh_slopes(slopes, B, H), bias,
                                   scale, mask, interpret, has_alibi, bias_meta, H, KVH)
    back = lambda x, S, nh: x.reshape(B, nh, S, x.shape[-1]).transpose(0, 2, 1, 3)
    # cotangent matches the (collapsed, flat) bias argument; the outer
    # 4D->flat reshape in flash_attention transposes automatically
    dbias_out = dbias.astype(bias.dtype) if bias_meta is not None else jnp.zeros_like(bias)
    return (back(dq, Sq, H), back(dk, Sk, KVH), back(dv, Sk, KVH), jnp.zeros_like(slopes), dbias_out)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(q, k, v, *, causal: bool = True, scale: Optional[float] = None, bias=None, segment_ids=None,
                    kv_len=None, window=None, alibi_slopes=None, interpret: bool = False,
                    bias_repeat: int = 1, mask=None, count_as=None):
    """Drop-in for ``attention_xla`` on the fast path. The kernels take these
    masks natively, each by a walk that visits no tile wholly outside it and
    masks only the tiles that cross an edge (``ops/masks.py``): none
    (``causal=False``), causal, causal under a sliding ``window``, and a
    ``mask`` record of its own walk (``masks.BlockDiffusion``, whose calls are
    named ``blockdiff_fwd`` / ``blockdiff_bwd``). ALiBi and an additive bias
    ride on the first three (a bias walks the same tiles and takes the split
    backward). Falling back to XLA's form, with the same record: packed
    segments, a padded kv (``kv_len``), a window or ALiBi without ``causal``,
    and a ``mask`` record together with a bias, ALiBi, segments or ``kv_len``.
    ``count_as`` (``ops/attention.py::attention``): the caller's words for this call site's count,
    made here as ``path="kernel"`` where the kernels take the call and by XLA's form where it falls.

    ``bias``: additive logits bias broadcastable to ``(B, H, Sq, Sk)`` —
    the batch/head/row dims may each be 1 and stay COLLAPSED in HBM (the
    kernels route shared blocks by index map, and dbias accumulates in the
    collapsed shape — reference evoformer_attn reads its ``(B,1,1,1,K)``
    mask bias in place). ``bias_repeat``: the q batch is
    ``bias.shape[0] * bias_repeat`` (consecutive q-batch groups share one
    bias slice — evoformer MSA rows over one pair bias).
    """
    falls = segment_ids is not None or kv_len is not None
    if mask is not None and mask.op != "flash":  # a record with a walk of its own (``causal`` and ``window`` are not read beside it)
        falls = falls or bias is not None or alibi_slopes is not None
    else:
        falls = falls or (not causal and (alibi_slopes is not None or window is not None))
    if falls:
        from ..attention import attention_xla

        if bias is not None and bias_repeat != 1:
            bias = jnp.asarray(bias)
            while bias.ndim < 4:  # pad first so axis 0 is batch, not heads
                bias = bias[None]
            bias = jnp.repeat(bias, bias_repeat, axis=0)
        return attention_xla(q, k, v, causal=causal, scale=scale, bias=bias, segment_ids=segment_ids,
                             kv_len=kv_len, window=window, alibi_slopes=alibi_slopes, mask=mask, count_as=count_as)
    if window is not None and window < 1:
        raise ValueError(f"window must be >= 1 (got {window}); pass None to disable the sliding window")
    if count_as:  # a call without the caller's words is counted by the kernels alone, with what they chose (``_count_traced``)
        placement.count(path="kernel", **count_as)
    local = functools.partial(_flash_local, mask=mask if mask is not None else masks.of(causal, window), scale=scale,
                              interpret=interpret, bias_repeat=bias_repeat)
    if bias is not None:
        # a collapsed bias does not split along a mesh; those callers
        # (evoformer) run on one device or inside their own shard_map
        return local(q, k, v, alibi_slopes, bias)
    # several chips: the kernel sits in a shard_map over the batch axes and, where it divides the query AND the KV heads,
    # the tensor axis (``placement.on_mesh`` says why)
    B, S, H, D = q.shape
    spec = placement.batch_spec((B, S, math.gcd(H, k.shape[2]), D), None, "tensor", None)
    if alibi_slopes is None:
        return placement.on_mesh(lambda q, k, v: local(q, k, v, None, None), (spec, spec, spec), spec)(q, k, v)
    heads = P(spec[2]) if len(spec) > 2 else P()
    return placement.on_mesh(lambda q, k, v, sl: local(q, k, v, sl, None), (spec, spec, spec, heads), spec)(
        q, k, v, jnp.asarray(alibi_slopes, jnp.float32))


def _flash_local(q, k, v, alibi_slopes, bias, *, mask, scale, interpret, bias_repeat):
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
    return _flash(q, k, v, slopes, bias_flat, scale, mask, interpret, has_alibi, bias_meta, H, k.shape[2])


REGISTRY.register("attention", "pallas", flash_attention, is_available=placement.pallas_available, priority=10)
