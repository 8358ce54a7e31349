"""The attention op's mask as a record (``ops/masks.py``): causal and causal-under-a-window walk the tiles they walked
before the record (the older formulas are copied here as the oracle, and the square's own truth beside them), the
block-diffusion mask's runs cover exactly the tiles its elementwise test touches and mask exactly those that cross an
edge, and the kernels under it (interpret mode) give XLA's result with the mask as a bias, forward and gradients.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import masks
from deepspeed_tpu.ops.attention import attention_chunked, attention_xla
from deepspeed_tpu.ops.pallas import flash_attention as F
from deepspeed_tpu.telemetry.tracing import regions_traced, regions_traced_by


def _cdiv(a, b):
    return -(-a // b)


def _old_kv_runs(qi, bq, bk, seq_q, seq_k, window):
    """``flash_attention._kv_runs`` as it stood before the record (PR 48), in plain integers."""
    nk = seq_k // bk
    r0 = seq_k - seq_q + qi * bq
    end = min(_cdiv(r0 + bq, bk), nk)
    first = max(r0 - window + 1, 0) // bk if window > 0 else 0
    full = min(max(max(r0 + 1, 0) // bk, first), end)
    if window <= 0:
        return [(first, full, False), (full, end, True)]
    inside = min(max(max(r0 + bq - 1 - window + bk, 0) // bk, first), full)
    return [(first, inside, True), (inside, full, False), (full, end, True)]


def _old_q_runs(kj, bq, bk, seq_q, seq_k, window):
    nq = seq_q // bq
    c0 = kj * bk - (seq_k - seq_q)
    first = max(c0, 0) // bq
    end = nq
    if window > 0:
        end = min(max(c0 + bk + window - 2 + bq, 0) // bq, nq)
    full = min(max(max(c0 + bk + bq - 2, 0) // bq, first), end)
    if window <= 0:
        return [(first, full, True), (full, end, False)]
    inside = min(max(max(c0 + window, 0) // bq, full), end)
    return [(first, full, True), (full, inside, False), (inside, end, True)]


def _ints(runs):
    return [(int(a), int(b), bool(m)) for a, b, m in runs]


def _walked(runs_of, n_outer, n_inner):
    """(visited, masked) boolean (outer, inner) arrays of a walk; no tile may be visited twice."""
    visited, masked = np.zeros((n_outer, n_inner), bool), np.zeros((n_outer, n_inner), bool)
    for i in range(n_outer):
        for first, end, m in _ints(runs_of(np.int32(i))):
            assert 0 <= first and end <= n_inner, (i, first, end)
            for j in range(first, end):
                assert not visited[i, j], (i, j)
                visited[i, j], masked[i, j] = True, m
    return visited, masked


def _tiles(keep, bq, bk):
    """(touched, wholly kept) by tile, from the square's own truth."""
    t = np.asarray(keep).reshape(keep.shape[0] // bq, bq, keep.shape[1] // bk, bk)
    return t.any((1, 3)), t.all((1, 3))


CAUSAL_GRID = [(seq_q, seq_k, bq, bk, window)
               for seq_q, seq_k in ((64, 64), (128, 128), (32, 128), (96, 96))
               for bq, bk in ((16, 16), (32, 16), (16, 32), (8, 32))
               for window in (0, 1, 7, 16, 40)
               if seq_q % bq == 0 and seq_k % bk == 0]


@pytest.mark.parametrize("seq_q,seq_k,bq,bk,window", CAUSAL_GRID)
def test_causal_and_window_runs_are_the_older_ones_tile_for_tile(seq_q, seq_k, bq, bk, window):
    mask = masks.Causal(window)
    shape = dict(bq=bq, bk=bk, seq_q=seq_q, seq_k=seq_k)
    for qi in range(seq_q // bq):
        assert _ints(mask.kv_runs(np.int32(qi), **shape)) == _old_kv_runs(qi, bq, bk, seq_q, seq_k, window), qi
    for kj in range(seq_k // bk):
        assert _ints(mask.q_runs(np.int32(kj), **shape)) == _old_q_runs(kj, bq, bk, seq_q, seq_k, window), kj
    # and both walks are the square's own truth: every touched tile visited once, a tile not wholly kept masked
    rows, cols = np.arange(seq_q)[:, None] + seq_k - seq_q, np.arange(seq_k)[None, :]
    touched, whole = _tiles(mask.keep(rows, cols), bq, bk)
    for visited, masked in (_walked(lambda i: mask.kv_runs(i, **shape), seq_q // bq, seq_k // bk),
                            tuple(a.T for a in _walked(lambda j: mask.q_runs(j, **shape), seq_k // bk, seq_q // bq))):
        assert (touched <= visited).all() and (masked | whole | ~visited).all()


def test_the_record_of_the_older_arguments():
    assert masks.of(True) == masks.Causal(0) and masks.of(True, 512) == masks.Causal(512) and masks.of(False, 8) == masks.Causal(8)
    assert masks.of(False) == masks.Full() and not masks.Full().masks and masks.Full().kv_runs(0, bq=8, bk=4, seq_q=16, seq_k=16) == [(0, 4, False)]
    assert {hash(masks.Causal(4)), hash(masks.BlockDiffusion(4, 64))} and masks.BlockDiffusion(4, 64) == masks.BlockDiffusion(4, 64)
    with pytest.raises(ValueError, match="whole blocks"):
        masks.BlockDiffusion(16, 72)


def _definition(L, B):
    """The (2 L, 2 L) mask from its four sentences, nothing shared with the record."""
    p = np.arange(2 * L)
    clean, blk = p >= L, (p % L) // B
    keep = np.zeros((2 * L, 2 * L), bool)
    for q in range(2 * L):
        for k in range(2 * L):
            if not clean[q] and not clean[k]:
                keep[q, k] = blk[k] == blk[q]
            elif not clean[q] and clean[k]:
                keep[q, k] = blk[k] < blk[q]
            elif clean[q] and clean[k]:
                keep[q, k] = blk[k] <= blk[q]
    return keep


# tiles that divide a block boundary (16 | 16, 8 | 16, 4 | 4), that hold several blocks (16 and 32 over 4), that straddle
# one (24 and 12 over 16, 8 x 32 mixed), and one tile a half
BLOCK_GRID = [(64, 4, 16, 16), (64, 4, 4, 4), (64, 16, 16, 16), (64, 16, 8, 32), (64, 16, 32, 8), (96, 4, 24, 8), (96, 16, 24, 12),
              (96, 16, 12, 24), (64, 4, 64, 64), (48, 16, 8, 8), (128, 4, 32, 16), (96, 12, 8, 24)]


@pytest.mark.parametrize("L,B,bq,bk", BLOCK_GRID)
def test_the_block_masks_runs_cover_exactly_the_tiles_keep_touches(L, B, bq, bk):
    mask, S = masks.BlockDiffusion(B, L), 2 * L
    keep = np.asarray(mask.keep(np.arange(S)[:, None], np.arange(S)[None, :]))
    assert (keep == _definition(L, B)).all() and keep.sum() == mask.pairs == L * L + L * B
    touched, whole = _tiles(keep, bq, bk)
    shape = dict(bq=bq, bk=bk, seq_q=S, seq_k=S)
    forward = _walked(lambda i: mask.kv_runs(i, **shape), S // bq, S // bk)
    backward = tuple(a.T for a in _walked(lambda j: mask.q_runs(j, **shape), S // bk, S // bq))
    for visited, masked in (forward, backward):
        assert (visited == touched).all()  # no tile wholly outside the mask, every tile with a kept pair
        assert (masked == (visited & ~whole)).all()  # mask arithmetic on the tiles that cross an edge, and on no other
    assert masks.tiles_visited(mask, **shape) == touched.sum()


def test_the_cells_walk_is_288_of_1024_tiles_and_a_tile_never_straddles_the_halves():
    mask = masks.BlockDiffusion(4, 8192)
    assert mask.tile(16384, 512, F._blk) == 512 and masks.BlockDiffusion(4, 96).tile(192, 512, F._blk) == 96
    assert masks.tiles_visited(mask, bq=512, bk=512, seq_q=16384, seq_k=16384) == 288 and mask.pairs == 67141632
    assert masks.tiles_visited(masks.Causal(), bq=512, bk=512, seq_q=16384, seq_k=16384) == 528
    with pytest.raises(ValueError, match="tiles that\\s+divide a half"):
        masks.BlockDiffusion(4, 96).kv_runs(0, bq=64, bk=64, seq_q=192, seq_k=192)


def _operands(L, H=4, KVH=2, D=32, batch=2, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    S = 2 * L
    return (jax.random.normal(ks[0], (batch, S, H, D)), jax.random.normal(ks[1], (batch, S, KVH, D)),
            jax.random.normal(ks[2], (batch, S, KVH, D)), jax.random.normal(ks[3], (batch, S, H, D)))


def _as_bias(mask, S):
    keep = np.asarray(mask.keep(np.arange(S)[:, None], np.arange(S)[None, :]))
    return jnp.where(keep, 0.0, -jnp.inf)[None, None]


# float32 operands on both sides: what is left is the order of float32 sums (a row's softmax by tiles against whole)
@pytest.mark.parametrize("L,B,blk", [(64, 4, 16), (64, 16, 32), (96, 16, 24), (64, 4, 64), (96, 12, 8)])
def test_the_kernels_under_the_block_mask_are_xla_with_the_mask_as_a_bias(L, B, blk, monkeypatch):
    monkeypatch.setattr(F, "DEFAULT_BQ", blk)
    monkeypatch.setattr(F, "DEFAULT_BK", blk)
    q, k, v, do = _operands(L)
    mask = masks.BlockDiffusion(B, L)
    ours = lambda q, k, v: F.flash_attention(q, k, v, mask=mask, interpret=True)
    plain = lambda q, k, v: attention_xla(q, k, v, causal=False, bias=_as_bias(mask, 2 * L))
    before = regions_traced("mixer/kernel", op="blockdiff", path="kernel")
    np.testing.assert_allclose(ours(q, k, v), plain(q, k, v), atol=5e-6)
    got = jax.grad(lambda *a: jnp.sum(ours(*a) * do), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(plain(*a) * do), (0, 1, 2))(q, k, v)
    for a, b, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(a, b, atol=2e-5, err_msg=f"d{name}")
    assert regions_traced("mixer/kernel", op="blockdiff", path="kernel") > before
    visited = masks.tiles_visited(mask, bq=blk, bk=blk, seq_q=2 * L, seq_k=2 * L)
    assert regions_traced_by("mixer/kernel", "tiles").get(f"{visited}/{(2 * L // blk) ** 2}", 0) >= 1
    assert regions_traced_by("mixer/kernel", "pairs").get(str(L * L + L * B), 0) >= 1


@pytest.mark.parametrize("form", ["xla", "chunked"])
@pytest.mark.parametrize("mask", [masks.BlockDiffusion(4, 48), masks.BlockDiffusion(16, 48), masks.Causal(), masks.Causal(20)], ids=str)
def test_xlas_forms_take_the_same_record(form, mask):
    q, k, v, _ = _operands(48)
    op = attention_xla if form == "xla" else lambda *a, **kw: attention_chunked(*a, chunk=32, **kw)
    want = attention_xla(q, k, v, causal=False, bias=_as_bias(mask, 96))
    np.testing.assert_allclose(op(q, k, v, mask=mask), want, atol=5e-6)
    if isinstance(mask, masks.Causal):  # the older arguments are the record's two oldest instances
        np.testing.assert_allclose(op(q, k, v, causal=True, window=mask.window or None), want, atol=5e-6)


def test_a_wrong_mask_is_far_from_the_block_masks_result():
    q, k, v, _ = _operands(64)
    sound = attention_xla(q, k, v, mask=masks.BlockDiffusion(4, 64))
    for wrong in (masks.Causal(), masks.BlockDiffusion(16, 64), masks.Full()):
        assert float(jnp.linalg.norm(attention_xla(q, k, v, mask=wrong) - sound) / jnp.linalg.norm(sound)) > 0.1


def test_the_kernel_names_its_calls_after_the_mask_and_the_fallbacks_take_the_record():
    q, k, v, _ = _operands(64, batch=1)
    mask = masks.BlockDiffusion(4, 64)
    text = str(jax.make_jaxpr(jax.grad(lambda q: jnp.sum(F.flash_attention(q, k, v, mask=mask, interpret=True))))(q))
    assert "blockdiff_fwd" in text and "blockdiff_bwd" in text and "flash_fwd" not in text
    causal = str(jax.make_jaxpr(jax.grad(lambda q: jnp.sum(F.flash_attention(q, k, v, interpret=True))))(q))
    assert "flash_fwd" in causal and "flash_bwd" in causal and "blockdiff" not in causal
    # with segments the call falls to XLA's form, which applies the same record
    seg = jnp.zeros((1, 128), jnp.int32)
    np.testing.assert_allclose(F.flash_attention(q, k, v, mask=mask, segment_ids=seg), attention_xla(q, k, v, mask=mask), atol=1e-6)
