"""What an attention call keeps of the (query, key) square, as a small record that every form of the op reads: the
flash kernels (``ops/pallas/flash_attention.py``), XLA's form and the chunked one (``ops/attention.py``).

A record gives the elementwise test (``keep(rows, cols)``: the ONE definition of the mask, which the kernels apply on
the tiles that cross an edge and XLA's forms on the whole square) and, for a kernel's walk, the tiles a q tile or a kv
tile visits as ``(first, end, masked)`` runs: a run's tiles are consecutive, and only a ``masked`` run has a tile with
an element the test throws away. A walk visits no tile that lies wholly outside the mask.

Rows are counted in key positions: a call's queries align to the END of its keys (``row = seq_k - seq_q + query``).
Records are frozen and hashable: they are static arguments of the kernels' ``custom_vjp``.

``kernel`` is the prefix of the Mosaic calls' names on the device's clock (``flash_fwd``, ``blockdiff_bwd``) and ``op``
the label the calls are counted under (``program_regions_traced_total{region="mixer/kernel", op}``).
"""

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas import cdiv as _cdiv


def counted_op(mask, unequal_heads: bool) -> str:
    """The ``op`` a call under ``mask`` is counted under by whichever form takes it: ``mla`` where its values have another head size than its q and k."""
    return "mla" if unequal_heads else mask.op


class _Record:
    """What the older masks share: the flash kernels' own names, and tiles chosen from the sequence itself."""

    kernel, op, masks = "flash", "flash", True

    def tile(self, seq: int, want: int, divides):
        """The kernels' tile along a sequence of ``seq``: ``divides(n, want)``, the largest tile that divides n."""
        return divides(seq, want)

    def strip(self, tile: int) -> int:
        """The tile of the kernels' WALK along an axis whose program holds ``tile`` rows: the program's own, but under a
        band narrower than it (``Causal.strip``)."""
        return tile

    def band(self, *, tile, seq_q, seq_k):
        """A walk without a loop (``Causal.band``): none."""
        return None

    def walk_labels(self, tile: str, tiles: str = "") -> dict:
        """What a call under this mask adds to its count (``program_regions_traced_total{region="mixer/kernel"}``), given
        ``tile``, "BQxBK" of its walk, and, a forward call, ``tiles``, "visited/of the square" at its shapes: nothing,
        for a mask whose walk is the whole or half the square; a mask with a walk of its own says it, so a walk that
        visits more shows without a capture."""
        return {}


@dataclass(frozen=True)
class Full(_Record):
    """Every pair: bidirectional attention (an encoder's)."""

    masks = False

    def keep(self, rows, cols):
        return None

    def kv_runs(self, qi, *, bq, bk, seq_q, seq_k):
        return [(0, seq_k // bk, False)]

    def q_runs(self, kj, *, bq, bk, seq_q, seq_k):
        return [(0, seq_q // bq, False)]


@dataclass(frozen=True)
class Causal(_Record):
    """A key at or before its query; with ``window`` > 0 also after ``query - window`` (a sliding window implies the
    causal bound)."""

    window: int = 0

    def keep(self, rows, cols):
        mask = cols <= rows
        if self.window > 0:
            mask = mask & (cols > rows - self.window)
        return mask

    def walk_labels(self, tile: str, tiles: str = "") -> dict:  # a band's walk, under labels of its own
        return {"window_tile": tile, **({"window_tiles": tiles} if tiles else {})} if self.window > 0 else {}

    def strip(self, tile: int) -> int:
        return band_strip(self.window, tile)

    def band(self, *, tile, seq_q, seq_k):
        """Under a band no wider than a ``tile`` (of both axes) whose queries begin at a tile's edge: ``(shift, n)``,
        static: q tile i's keys lie in the n kv tiles that end with tile ``i + shift`` (the diagonal's and the one
        before it), kv tile j's queries in the n q tiles from ``j - shift`` on. None where the band is wider, the queries
        begin before the keys or inside a tile, or a sequence has fewer tiles than n."""
        off, n = seq_k - seq_q, min(self.window, 2)
        if not 0 < self.window <= tile or off < 0 or off % tile or seq_q % tile or seq_k % tile or n > seq_q // tile:
            return None
        return off // tile, n

    def kv_band(self, qi, *, tile, seq_q, seq_k):
        """``(first, n)``: the n kv tiles q tile ``qi`` walks where ``band`` holds, each across an edge of the mask (the
        first q tile's n tiles begin with the first kv tile, and the test throws its spare one away). ``qi`` is traced,
        n is not: a walk with no loop."""
        shift, n = self.band(tile=tile, seq_q=seq_q, seq_k=seq_k)
        return jnp.maximum(qi + shift - (n - 1), 0), n

    def q_band(self, kj, *, tile, seq_q, seq_k):
        """``kv_band`` seen from a kv tile: the q tiles that visit it (a kv tile ahead of the first query, or the last:
        n tiles inside the sequence, which the test throws away where they are not its own)."""
        shift, n = self.band(tile=tile, seq_q=seq_q, seq_k=seq_k)
        return jnp.clip(kj - shift, 0, seq_q // tile - n), n

    def kv_runs(self, qi, *, bq, bk, seq_q, seq_k):
        """Only a block that crosses the diagonal (or, with a window, the window's far edge) has a masked element; the
        blocks between lie wholly inside the mask. ``qi`` is traced."""
        nk, window = seq_k // bk, self.window
        r0 = seq_k - seq_q + qi * bq  # the block's first row, in key positions
        end = jnp.minimum(_cdiv(r0 + bq, bk), nk)  # past the last block any row sees
        first = jnp.maximum(r0 - window + 1, 0) // bk if window > 0 else 0
        # blocks [.., full) end at or before the first row's own position
        full = jnp.clip(jnp.maximum(r0 + 1, 0) // bk, first, end)
        if window <= 0:
            return [(first, full, False), (full, end, True)]
        # blocks [inside, ..) start after the last row's window has begun
        inside = jnp.clip(jnp.maximum(r0 + bq - 1 - window + bk, 0) // bk, first, full)
        return [(first, inside, True), (inside, full, False), (full, end, True)]

    def q_runs(self, kj, *, bq, bk, seq_q, seq_k):
        """``kv_runs`` seen from a kv block: the q blocks that visit it."""
        nq, window = seq_q // bq, self.window
        c0 = kj * bk - (seq_k - seq_q)  # the block's first column, in query positions
        first = jnp.maximum(c0, 0) // bq  # row r sees column c iff c <= r
        end = nq
        if window > 0:  # ... and c > r - window: the last column is seen up to row c0 + bk + window - 2
            end = jnp.minimum(jnp.maximum(c0 + bk + window - 2 + bq, 0) // bq, nq)
        # blocks [full, ..) start at or after the block's last column
        full = jnp.clip(jnp.maximum(c0 + bk + bq - 2, 0) // bq, first, end)
        if window <= 0:
            return [(first, full, True), (full, end, False)]
        # blocks [.., inside) end before the first column leaves their last row's window
        inside = jnp.clip(jnp.maximum(c0 + window, 0) // bq, full, end)
        return [(first, full, True), (full, inside, False), (inside, end, True)]


@dataclass(frozen=True)
class BlockDiffusion(_Record):
    """Block-diffusion training over a doubled row ``[noised ; clean]`` of ``2 * seq_len`` positions (BD3-LM's vectorised
    form): index p has ``pos = p mod seq_len`` and ``blk = pos // block``; a noised query keeps the noised keys of its
    own block and the clean keys of EARLIER blocks, a clean query the clean keys of its own and earlier blocks, and no
    query a noised key of another block. ``seq_len**2 + seq_len * block`` of the ``4 seq_len**2`` pairs.

    The kernels' tiles are chosen from ``seq_len`` (``tile``), so no tile straddles the two halves and every piece of
    the mask is a staircase in a tile's own half: per query an interval of keys whose ends do not fall as the query
    advances."""

    block: int
    seq_len: int  # L: a half of the row
    kernel, op = "blockdiff", "blockdiff"

    def __post_init__(self):
        if self.block < 1 or self.seq_len % self.block:
            raise ValueError(f"a block-diffusion row's halves are whole blocks: {self.seq_len} positions are not a multiple of "
                             f"block_length={self.block}")

    def keep(self, rows, cols):
        L, B = self.seq_len, self.block
        q_clean, k_clean = rows >= L, cols >= L
        qb, kb = (rows - jnp.where(q_clean, L, 0)) // B, (cols - jnp.where(k_clean, L, 0)) // B
        # (no select between boolean vectors: Mosaic has none) a clean key while blk(k) < blk(q), or <= for a clean query
        return (k_clean & (kb < qb + jnp.where(q_clean, 1, 0))) | ~(k_clean | q_clean) & (kb == qb)

    def tile(self, seq: int, want: int, divides):
        return divides(self.seq_len, want)  # of a HALF: no tile straddles the two

    def _check(self, bq, bk, seq_q, seq_k):
        L = self.seq_len
        if seq_q != 2 * L or seq_k != 2 * L or L % bq or L % bk:
            raise ValueError(f"the block-diffusion mask of {L} positions a half takes {2 * L} queries and keys in tiles that "
                             f"divide a half, got {seq_q} x {seq_k} in tiles of {bq} x {bk}")

    def _own_masked(self, bq, bk) -> bool:
        """Whether a noised tile of a q tile's own blocks can cross a block's edge: not where both tiles divide a block."""
        return bool(self.block % bq or self.block % bk)

    def kv_runs(self, qi, *, bq, bk, seq_q, seq_k):
        """A noised q tile: the noised tiles its rows' own blocks lie in (masked), then the clean tiles wholly ahead of
        its first row's block (unmasked) and those that reach into its rows' blocks (masked). A clean q tile: the last
        two, one block further."""
        self._check(bq, bk, seq_q, seq_k)
        L, B = self.seq_len, self.block
        nq, nk = L // bq, L // bk
        noised = qi < nq
        p0 = (qi - jnp.where(noised, 0, nq)) * bq  # the tile's first position in its half; its last is p0 + bq - 1
        b0, b1 = p0 // B, (p0 + bq - 1) // B
        own_first = b0 * B // bk
        own_end = jnp.where(noised, jnp.minimum(_cdiv((b1 + 1) * B, bk), nk), own_first)
        reach = jnp.where(noised, 0, 1)  # a clean key is kept while blk(k) < blk(q) + reach
        full = jnp.minimum((b0 + reach) * B // bk, nk)
        end = jnp.clip(_cdiv((b1 + reach) * B, bk), full, nk)
        return [(own_first, own_end, self._own_masked(bq, bk)), (nk, nk + full, False), (nk + full, nk + end, True)]

    def q_runs(self, kj, *, bq, bk, seq_q, seq_k):
        """``kv_runs`` seen from a kv tile. A noised one: the noised q tiles of its columns' blocks. A clean one: of each
        half the q tiles that reach into its columns' blocks (masked) and those wholly past them (unmasked)."""
        self._check(bq, bk, seq_q, seq_k)
        L, B = self.seq_len, self.block
        nq, nk = L // bq, L // bk
        noised = kj < nk
        c0 = (kj - jnp.where(noised, 0, nk)) * bk
        b0, b1 = c0 // B, (c0 + bk - 1) // B
        own_first = b0 * B // bq
        own_end = jnp.where(noised, jnp.minimum(_cdiv((b1 + 1) * B, bq), nq), own_first)
        runs = [(own_first, own_end, self._own_masked(bq, bk))]
        for half, reach in ((0, 1), (nq, 0)):  # noised queries keep blk(k) < blk(q), clean ones blk(k) <= blk(q)
            first = jnp.minimum((b0 + reach) * B // bq, nq)
            full = jnp.clip(_cdiv((b1 + reach) * B, bq), first, nq)
            first, full = (jnp.where(noised, nq, x) for x in (first, full))  # a noised key: none of these
            runs += [(half + first, half + full, True), (half + full, half + nq, False)]
        return runs

    @property
    def pairs(self) -> int:
        return self.seq_len**2 + self.seq_len * self.block

    def walk_labels(self, tile: str, tiles: str = "") -> dict:
        return {"tiles": tiles, "pairs": str(self.pairs)} if tiles else {}


STRIP = 128  # the narrowest strip: the lanes of the kernels' row statistics and of a (bk, bq) score tile


def band_strip(window: int, tile: int) -> int:
    """The tile of the kernels' walk along an axis whose programs hold ``tile`` rows, under a causal band of ``window``
    keys: where ``0 < window <= tile // 2`` the power of two that holds the band, never under ``STRIP``; else ``tile``, the
    walk as it was. It reads the window and the block, nothing else. A program keeps its block and walks it as strips
    (``ops/pallas/flash_attention.py::_tiles``), each against the two tiles the band crosses (``Causal.band``).

    Written from this table (TPU v5e, PR 69: the window call alone at K-EXAONE's shape, ``(1, 8192, 64 / 8, 128)`` bf16
    under 128 keys, ms forward | fused backward, twenty calls a program; kept pairs need 0.2 | 0.4 at the peak).
    Programs AND tiles by the two knobs, the kernels as they were: (512, 512) **3.34 | 6.31**; (512, 128) 3.46 | 7.36;
    (256, 128) 3.72 | 7.36; (128, 128) 4.47 | 6.08; (128, 256) 4.31 | 5.70; (256, 256) 3.65 | 6.07; (512, 256) 3.22 |
    5.84; (256, 512) 4.13 | 6.15: a masked tile costs 820 | 1,360 | 2,530 cycles at a side of 128 | 256 | 512 forward,
    its side and not its pairs, so fewer pairs in more tiles buy nothing. Blocks of 512 walked as strips under a ROLLED
    loop, each strip the walk of ``kv_runs`` / ``q_runs`` with its three loops: strips of (128, 128) 4.45 | 5.40; (128,
    256) 4.29 | 5.35; (256, 128) 3.75 | 6.57; (256, 256) 3.66 | 5.70. Strips of (128, 128) whose walk is two tiles and
    no loop (``Causal.band``): the strips under a rolled loop 2.71 | 4.80; **in straight-line text 1.55 | 4.45, kept**.
    Under 64 keys the same strips read 1.54 | 4.45; under 256 keys the strips are (256, 256), two tiles of 256 where
    three of 128 would cross the band: 2.15 | 4.15 (the blocks' walk 3.34 | 6.31 under either). The same error against
    float32 as the blocks' walk (max and rms equal to four digits, o, dq, dk, dv: PERF.md section 6, PR 69)."""
    strip = max(STRIP, 1 << max(window - 1, 0).bit_length())  # the power of two that holds the band
    return strip if 0 < window and 2 * strip <= tile and tile % strip == 0 else tile


def of(causal: bool, window=None):
    """The record of the older arguments: ``causal`` with an optional sliding ``window`` (which implies it)."""
    return Causal(int(window or 0)) if causal or window else Full()


@functools.lru_cache(maxsize=None)
def _walks(mask, bq, bk, seq_q, seq_k):
    """Every q tile's forward runs as host integers, by the record's own ``kv_runs`` (static shapes: worked out while a
    program is traced, where the runs' arithmetic is the host's; once a record and shape)."""
    with jax.ensure_compile_time_eval():
        return tuple(tuple((int(first), int(end), masked) for first, end, masked in mask.kv_runs(np.int32(qi), bq=bq, bk=bk, seq_q=seq_q, seq_k=seq_k))
                     for qi in range(seq_q // bq))


def tiles_visited(mask, *, bq, bk, seq_q, seq_k) -> int:
    """How many tiles a forward walk visits."""
    return sum(max(end - first, 0) for runs in _walks(mask, bq, bk, seq_q, seq_k) for first, end, _ in runs)


def longest_whole_run(mask, *, bq, bk, seq_q, seq_k) -> int:
    """The most tiles any unmasked run of a forward walk has: tiles that lie wholly inside the mask, one after another
    (none under a window of a tile's width, whose every visited tile crosses an edge)."""
    return max((end - first for runs in _walks(mask, bq, bk, seq_q, seq_k) for first, end, masked in runs if not masked), default=0)
