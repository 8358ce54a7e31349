"""Pallas kernel tests (interpret mode on CPU; Mosaic-compiled on real TPU).

Reference coverage model: per-kernel numeric tests vs the framework
reference implementation (``tests/unit/ops/...``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.attention import attention_xla
from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
from deepspeed_tpu.ops.pallas.fused_adam import adam_xla, fused_adam_flat
from deepspeed_tpu.ops.pallas.norms import layer_norm, layer_norm_xla, rms_norm, rms_norm_xla
from deepspeed_tpu.ops.pallas.quantization import (dequantize_groupwise, quantize_groupwise, quantize_groupwise_xla)


def _qkv(B=2, S=128, H=2, D=64, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(B, S, H, D).astype(np.float32))
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [True, False])
def test_flash_fwd_matches_xla(causal):
    q, k, v = _qkv()
    ref = attention_xla(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=2e-5)


def test_flash_fwd_small_seq():
    q, k, v = _qkv(S=16, D=8)
    ref = attention_xla(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=2e-5)


def test_flash_gqa():
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(1, 64, 4, 16).astype(np.float32))
    k = jnp.asarray(rng.randn(1, 64, 2, 16).astype(np.float32))
    v = jnp.asarray(rng.randn(1, 64, 2, 16).astype(np.float32))
    ref = attention_xla(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=2e-5)


@pytest.mark.parametrize("extra", ["plain", "alibi", "window"])
def test_flash_gqa_bwd_matches_xla(extra):
    """GQA-native backward: dk/dv accumulate across the q-head group inside
    the kernel (fused: grid (B*KVH, n_rep, Sk/bk), float32 scratch over the
    group) and come back collapsed at (B, S, KVH, D) — parity vs XLA's
    expand-and-reduce."""
    from deepspeed_tpu.models.transformer import alibi_slopes

    rng = np.random.RandomState(3)
    B, S, H, KVH, D = 2, 64, 4, 2, 16
    q = jnp.asarray(rng.randn(B, S, H, D).astype(np.float32))
    k = jnp.asarray(rng.randn(B, S, KVH, D).astype(np.float32))
    v = jnp.asarray(rng.randn(B, S, KVH, D).astype(np.float32))
    kw = {}
    if extra == "alibi":
        kw["alibi_slopes"] = alibi_slopes(H)
    elif extra == "window":
        kw["window"] = 16

    def loss_ref(q, k, v):
        return jnp.sum(attention_xla(q, k, v, causal=True, **kw)**2)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, interpret=True, **kw)**2)

    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    assert gf[1].shape == (B, S, KVH, D) and gf[2].shape == (B, S, KVH, D)
    for a, b in zip(gr, gf):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-3, rtol=1e-3)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_bwd_matches_xla(causal):
    q, k, v = _qkv(S=64, D=16)

    def loss_ref(q, k, v):
        return jnp.sum(attention_xla(q, k, v, causal=causal)**2)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal, interpret=True)**2)

    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gr, gf):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-3, rtol=1e-3)


# every static that chooses a backward path or a block's treatment: name -> (shapes, kwargs, traced path)
_S = dict(B=1, Sq=256, Sk=256, H=2, KVH=2, D=16)
_BWD_PATH_CASES = {
    "mha_causal_2_blocks": (dict(_S, Sq=128, Sk=128), {}, "fused"),  # one diagonal and one interior block a row band
    "mha_causal_4_blocks": (_S, {}, "fused"),
    "mha_noncausal": (_S, {"causal": False}, "fused"),  # no masked block at all
    "gqa_4_to_1": (dict(_S, H=4, KVH=1), {}, "fused"),  # dk/dv add up over the group in scratch
    "gqa_2_to_1_window": (dict(_S, H=4, KVH=2), {"window": 40}, "fused"),
    "window_smaller_than_block": (_S, {"window": 24}, "fused"),  # the far edge and the diagonal in one block
    "window_larger_than_block": (_S, {"window": 100}, "fused"),  # far-edge, interior and diagonal blocks
    "alibi": (dict(_S, H=4, KVH=4), {"alibi": True}, "fused"),
    "seq_q_lt_seq_k": (dict(_S, Sq=64), {}, "fused"),  # whole kv blocks ahead of the first query
    "seq_q_lt_seq_k_window": (dict(_S, Sq=128), {"window": 70}, "fused"),  # kv blocks no q block visits
    "seq_q_gt_seq_k": (dict(_S, Sk=96), {}, "fused"),  # rows before the first key inside a visited block: the guard
    "bias": (_S, {"bias": (1, 2, 256, 256)}, "split"),  # dbias is written by the dq kernels
    "bias_row": (_S, {"bias": (1, 1, 1, 256), "causal": False}, "split"),
    "bias_gqa_2_to_1": (dict(_S, H=4, KVH=2), {"bias": (1, 4, 256, 256)}, "split"),  # bias x GQA: KV arrives expanded
    "mha_no_room_in_vmem": (_S, {"vmem_budget": 1}, "refused"),  # a head's q/do/dq too large to keep resident
}


@pytest.mark.parametrize("case", list(_BWD_PATH_CASES))
def test_flash_bwd_paths_match_xla(case, monkeypatch):
    """dq, dk, dv of every path the backward can take, against attention_xla's
    vjp, and through ``program_regions_traced_total{region="mixer/kernel", pass, path}`` which path was traced.
    Blocks of 64 so that interior, diagonal and window-edge blocks all run."""
    import deepspeed_tpu.ops.pallas.flash_attention as fa
    from deepspeed_tpu.models.transformer import alibi_slopes
    from deepspeed_tpu.telemetry.tracing import regions_traced

    shape, kw, path = _BWD_PATH_CASES[case]
    kw = dict(kw)
    monkeypatch.setattr(fa, "DEFAULT_BQ", 64)
    monkeypatch.setattr(fa, "DEFAULT_BK", 64)
    if "vmem_budget" in kw:
        monkeypatch.setattr(fa, "vmem_budget", lambda budget=kw.pop("vmem_budget"): budget)
    B, Sq, Sk, H, KVH, D = (shape[n] for n in ("B", "Sq", "Sk", "H", "KVH", "D"))
    rng = np.random.RandomState(5)
    mk = lambda *s: jnp.asarray(rng.randn(*s).astype(np.float32))
    q, k, v, w = mk(B, Sq, H, D), mk(B, Sk, KVH, D), mk(B, Sk, KVH, D), mk(B, Sq, H, D)
    if kw.pop("alibi", False):
        kw["alibi_slopes"] = jnp.asarray(alibi_slopes(H))
    if "bias" in kw:
        kw["bias"] = mk(*kw["bias"])
    kw.setdefault("causal", True)
    # a row before the first key sees nothing: flash gives it 0 (and no gradient), plain softmax a uniform
    # average, so the reference leaves those rows out of the loss; w != 0 there is what the guard answers to
    seen = jnp.arange(Sq)[None, :, None, None] >= (Sq - Sk if kw["causal"] else 0)

    def grads(attn, rows):
        return jax.grad(lambda q, k, v: jnp.sum(jnp.where(rows, attn(q, k, v, **kw) * w, 0.0)), argnums=(0, 1, 2))(q, k, v)

    counter = lambda p: regions_traced("mixer/kernel", **{"pass": "bwd", "path": p})  # whichever ``op``
    before = {p: counter(p) for p in ("fused", "split")}
    flash = lambda *a, **kws: flash_attention(*a, interpret=True, **kws)
    if path == "refused":  # by name, with the shape, and before any kernel is chosen
        with pytest.raises(NotImplementedError, match=f"seq_q={Sq}, seq_k={Sk}, D={D}.*VMEM"):
            grads(flash, True)
        assert {p: counter(p) - before[p] for p in before} == {"fused": 0.0, "split": 0.0}
        return
    got = grads(flash, True)
    assert {p: counter(p) - before[p] for p in before} == {"fused": 0.0, "split": 0.0, path: 1.0}
    want = grads(attention_xla, seen)
    for name, a, b in zip(("dq", "dk", "dv"), got, want):
        assert a.shape == b.shape, name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-3, rtol=1e-3, err_msg=name)


# two tiles a trip against one: name -> (mask, Sq, Sk, H, KVH, D, Dv, tile, how the backward runs a GQA group[, the
# rule's word where it is not 2]). Tiles of 32 under sequences of 2 to 10 tiles: a row band's unmasked runs are of every
# length from none to seven, so even, odd and single runs all occur in a walk (a causal row band i has i whole tiles and
# the diagonal's one)
def _pair_cases():
    from deepspeed_tpu.ops import masks

    return {
        "causal_mha": (masks.Causal(), 256, 256, 2, 2, 32, 32, 32, "fused"),
        "causal_mha_two_tiles": (masks.Causal(), 64, 64, 2, 2, 32, 32, 32, "fused", 1),  # every run a single tile: no pair to take
        "causal_gqa_summed_in_the_kernel": (masks.Causal(), 256, 256, 4, 2, 32, 32, 32, "fused"),
        "causal_gqa_a_head_at_a_time": (masks.Causal(), 256, 256, 4, 1, 32, 32, 32, "per_head"),
        "causal_latent_192_128": (masks.Causal(), 256, 256, 2, 2, 192, 128, 64, "fused"),  # kimi's head sizes
        "window_of_a_tile_64_128_gqa": (masks.Causal(64), 320, 320, 4, 2, 64, 128, 64, "fused", 1),  # phi-4's: window = tile, as 512 at 512: every tile crosses an edge
        "window_of_four_tiles_64_128_gqa": (masks.Causal(256), 640, 640, 4, 2, 64, 128, 64, "fused"),  # ... and one with whole tiles between its edges
        "window_of_three_tiles": (masks.Causal(96), 256, 256, 2, 2, 32, 32, 32, "fused"),  # masked, whole and masked runs
        "blockdiff_4_over_a_doubled_row": (masks.BlockDiffusion(4, 160), 320, 320, 4, 2, 32, 32, 32, "fused"),
        "blockdiff_4_a_head_at_a_time": (masks.BlockDiffusion(4, 128), 256, 256, 4, 1, 32, 32, 32, "per_head"),  # sdar's backward
        "no_mask": (masks.Full(), 128, 192, 2, 2, 32, 32, 32, "fused"),  # runs of 6 (forward) and 4 (backward), their bounds static
        # runs of 7 and 5: a static last trip. Op by op (``disable_jit``): jitted, XLA's CPU compiler inlines a loop of one
        # static trip beside the kernel's last lines and rounds one lse of 320 another way (1 ulp at optimisation level 0)
        "no_mask_odd_runs": (masks.Full(), 160, 224, 2, 2, 32, 32, 32, "eager"),
        "no_mask_gqa_64_128": (masks.Full(), 128, 128, 4, 2, 64, 128, 32, "fused"),
        "seq_q_lt_seq_k": (masks.Causal(), 128, 256, 2, 2, 32, 32, 32, "fused"),  # whole kv tiles ahead of the first query
        "seq_q_lt_seq_k_window": (masks.Causal(120), 128, 288, 2, 1, 32, 32, 32, "fused"),  # kv tiles no q tile visits
        "seq_q_gt_seq_k": (masks.Causal(), 256, 160, 2, 2, 32, 32, 32, "fused"),  # rows before the first key: the guard, and runs that end before they start
    }


@pytest.mark.parametrize("case", list(_pair_cases()))
def test_two_tiles_a_trip_are_one_tile_a_trip_bit_for_bit(case, monkeypatch):
    """``_flash_fwd`` on bf16 operands (the cells'), two tiles a trip over its unmasked runs against one, the kernel
    as it was: o and lse are the same bits, since a pair changes no product, no chain and no order of an update; and so
    are dq, dk, dv of ``_flash_bwd``, which reads them and keeps one tile a trip (PERF.md, PR 50: its pair gained
    nothing on the chip). ``program_regions_traced_total{tiles_a_trip}`` says what the rule gave: 2 where the mask has
    an unmasked run of two tiles; where it has none the pair is forced, and its loops of no trip change nothing."""
    import deepspeed_tpu.ops.pallas.flash_attention as fa
    from deepspeed_tpu.telemetry.tracing import regions_traced

    mask, Sq, Sk, H, KVH, D, Dv, tile, group, *word = _pair_cases()[case]
    monkeypatch.setattr(fa, "DEFAULT_BQ", tile)
    monkeypatch.setattr(fa, "DEFAULT_BK", tile)
    if group == "per_head":  # a budget that holds a head's backward and not its group's sums over the whole sequence
        monkeypatch.setattr(fa, "vmem_budget", lambda: fa._fused_bwd_vmem(Sq, Sk, D, 2, tile, tile, 1, Dv))
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    bf16 = lambda key, *shape: jax.random.normal(key, shape, jnp.float32).astype(jnp.bfloat16)
    q, k, v, do = bf16(ks[0], H, Sq, D), bf16(ks[1], KVH, Sk, D), bf16(ks[2], KVH, Sk, Dv), bf16(ks[3], H, Sq, Dv)
    slopes, bias = jnp.zeros((H, 1, fa.LANES), jnp.float32), jnp.zeros((1, 1, fa.LANES), jnp.float32)
    rest = (D ** -0.5, mask, True, False, None, H, KVH)

    def results(tiles=None):
        if tiles:
            monkeypatch.setattr(fa, "tiles_a_trip", lambda *a: tiles)
        o, lse = fa._flash_fwd(q, k, v, slopes, bias, *rest)
        return (o, lse) + tuple(fa._flash_bwd(q, k, v, o, lse, do, slopes, bias, *rest)[:3])

    counted = lambda: [regions_traced("mixer/kernel", **{"pass": p, "tiles_a_trip": n}) for p in ("fwd", "bwd") for n in ("1", "2")]
    before = counted()
    with jax.disable_jit(group == "eager"):
        pair = results()
        took_two = float(word != [1])
        assert [now - was for now, was in zip(counted(), before)] == [1.0 - took_two, took_two, 1.0, 0.0]  # the backward keeps one
        if not took_two:
            pair = results(2)
        single = results(1)
    for name, a, b in zip(("o", "lse", "dq", "dk", "dv"), pair, single):
        assert a.dtype == b.dtype and np.array_equal(np.asarray(a.astype(jnp.float32)), np.asarray(b.astype(jnp.float32))), name
    assert float(jnp.abs(pair[2].astype(jnp.float32)).max()) > 0 and bool(jnp.all(jnp.isfinite(pair[1])))


def test_the_rule_takes_two_tiles_where_a_whole_run_has_two_and_they_fit():
    """``tiles_a_trip`` from what a call can see: the mask's longest unmasked run at the call's shapes
    (``masks.longest_whole_run``) and the call's own count of VMEM."""
    import deepspeed_tpu.ops.pallas.flash_attention as fa
    from deepspeed_tpu.ops import masks

    run = lambda mask, S, tile=512: masks.longest_whole_run(mask, bq=tile, bk=tile, seq_q=S, seq_k=S)
    assert [run(masks.Causal(), S) for S in (512, 1024, 2048, 8192)] == [0, 1, 3, 15]  # row band i: i whole tiles, then the diagonal's
    assert run(masks.Causal(512), 8192) == 0 and run(masks.Causal(1024), 8192) == 1 and run(masks.Causal(2048), 8192) == 3  # phi-4's window: none
    assert run(masks.Full(), 8192) == 16 and run(masks.BlockDiffusion(4, 8192), 16384) == 15 and run(masks.BlockDiffusion(4, 512), 1024) == 0
    assert masks.longest_whole_run(masks.Causal(), bq=512, bk=512, seq_q=512, seq_k=8192) == 15  # a chunk of queries at the end of its keys
    assert fa.tiles_a_trip(2, 24 << 20) == 2 and fa.tiles_a_trip(16, 24 << 20) == 2
    assert fa.tiles_a_trip(1, 24 << 20) == 1 and fa.tiles_a_trip(0, 24 << 20) == 1  # no run holds a pair: its loops would cost and hide nothing
    assert fa.tiles_a_trip(16, fa.vmem_budget()) == 2 and fa.tiles_a_trip(16, fa.vmem_budget() + 1) == 1  # no room
    assert fa._tile_bytes(512, 512) == 8 << 20  # ``ops/pallas/indexed_attention.py`` counts with it too


# a band narrower than a program's block (PR 69): window x query heads a key head x (seq_q, seq_k), at the blocks the
# cells run (512 x 512): one q block, one and two kv blocks
_BAND_CASES = {f"w{window}_rep{n_rep}_q{Sq}_k{Sk}": (window, n_rep, Sq, Sk)
               for window in (64, 128, 256) for n_rep in (1, 4, 8) for Sq, Sk in ((512, 512), (512, 1024))}


@pytest.mark.parametrize("case", list(_BAND_CASES))
def test_a_band_narrower_than_a_block_is_walked_in_strips_and_matches_xla(case):
    """``flash_attention`` under ``Causal(window)`` with ``window <= 256``, whose programs walk their block of 512 rows as
    strips (``masks.band_strip``: 128 rows under 64 and 128 keys, 256 under 256): o, dq, dk and dv in float32 against XLA's
    form, under GQA in the kernel's own sums too, with queries that begin at key 512 as well. The walk's tile rides on
    both passes' series, the walk's count on the forward's."""
    from deepspeed_tpu.ops import masks
    from deepspeed_tpu.telemetry.tracing import regions_traced

    window, n_rep, Sq, Sk = _BAND_CASES[case]
    strip = 256 if window == 256 else 128
    ks = jax.random.split(jax.random.PRNGKey(window + n_rep), 4)
    q, do = (jax.random.normal(key, (1, Sq, n_rep, 32), jnp.float32) for key in ks[:2])
    k, v = (jax.random.normal(key, (1, Sk, 1, 32), jnp.float32) for key in ks[2:])
    tile = f"{strip}x{strip}"
    visited = 2 * Sq // strip  # the walk without a loop: two kv tiles a q tile (the first one's second lies past its diagonal: the test throws it away)
    assert visited - masks.tiles_visited(masks.Causal(window), bq=strip, bk=strip, seq_q=Sq, seq_k=Sk) == int(Sq == Sk)
    series = lambda pass_, **labels: regions_traced("mixer/kernel", op="flash", window_tile=tile, **{"pass": pass_}, **labels)
    before = series("fwd", window_tiles=f"{visited}/{(Sq // strip) * (Sk // strip)}"), series("bwd", path="fused")

    def both(attn):
        o, pull = jax.vjp(lambda q, k, v: attn(q, k, v, causal=True, window=window), q, k, v)
        return (o,) + pull(do)

    with jax.default_matmul_precision("highest"):
        got, want = both(lambda *a, **kw: flash_attention(*a, interpret=True, **kw)), both(attention_xla)
    for name, a, b in zip(("o", "dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5, err_msg=name)
    after = series("fwd", window_tiles=f"{visited}/{(Sq // strip) * (Sk // strip)}"), series("bwd", path="fused")
    assert (after[0] - before[0], after[1] - before[1]) == (1.0, 1.0)


def _band_rule_cases():
    """The rule as data: name -> (mask, seq, ``_tiles``: a program's block along q and k, then the walk's tiles)."""
    from deepspeed_tpu.ops import masks

    whole, cases = (512, 512, 512, 512), {}
    for seq in (2048, 8192, 16384):  # today's tiles for every mask a listed cell has but K-EXAONE's band
        for name, mask in (("causal", masks.Causal()), ("phi4_w512", masks.Causal(512)), ("smallthinker_w4096", masks.Causal(4096)),
                           ("sdar_blockdiff", masks.BlockDiffusion(4, seq // 2)), ("w257", masks.Causal(257)), ("full", masks.Full())):
            cases[f"{name}_s{seq}"] = (mask, seq, whole)
        cases[f"kexaone_w128_s{seq}"] = (masks.Causal(128), seq, (512, 512, 128, 128))
        cases[f"w64_s{seq}"] = (masks.Causal(64), seq, (512, 512, 128, 128))  # never under the lanes' 128
        cases[f"w256_s{seq}"] = (masks.Causal(256), seq, (512, 512, 256, 256))  # half a block: the widest band with strips
    cases["w128_one_block"] = (masks.Causal(128), 512, (512, 512, 128, 128))
    cases["w128_s256"] = (masks.Causal(128), 256, (256, 256, 128, 128))
    cases["w128_s128"] = (masks.Causal(128), 128, (128, 128, 128, 128))  # one tile: nothing to walk in strips
    return cases


@pytest.mark.parametrize("case", list(_band_rule_cases()))
def test_the_walks_tiles_follow_the_band_and_nothing_else(case):
    """``_tiles`` (``masks.band_strip``): a program's blocks are what they were for every mask; the WALK's tiles are the
    blocks but under ``0 < window <= block // 2``, where they are the power of two that holds the band, never under 128.
    The rule reads the window and the block: Phi-4's window of 512, SmallThinker's of 4,096 and SDAR's mask cannot drift."""
    import deepspeed_tpu.ops.pallas.flash_attention as fa

    mask, seq, want = _band_rule_cases()[case]
    assert fa._tiles(mask, seq, seq, False) == want
    assert fa._tiles(mask, seq, seq, True) == want[:2] * 2  # a bias comes by the block


def test_the_bands_walk_at_k_exaones_shape():
    """8,192 x 8,192 under a window of 128 (PERF.md section 6, PR 69): what a walk visits by its tile, in tiles and in
    pairs a head, beside the 1,040,448 pairs the band keeps; the walk without a loop visits two kv tiles a q tile, one
    more than the band crosses (the first q tile's spare one), and has no unmasked run to pair."""
    from deepspeed_tpu.ops import masks

    band, S = masks.Causal(128), 8192
    visited = lambda bq, bk: masks.tiles_visited(band, bq=bq, bk=bk, seq_q=S, seq_k=S)
    assert [(visited(bq, bk), (S // bq) * (S // bk)) for bq, bk in ((512, 512), (512, 128), (256, 128), (128, 128))] == [(31, 256), (79, 1024), (95, 2048), (127, 4096)]
    assert [visited(bq, bk) * bq * bk for bq, bk in ((512, 512), (512, 128), (256, 128), (128, 128))] == [8126464, 5177344, 3112960, 2080768]
    assert sum(min(r + 1, 128) for r in range(S)) == 1040448 and S * (S + 1) // 2 == 33558528
    assert masks.longest_whole_run(band, bq=128, bk=128, seq_q=S, seq_k=S) == masks.longest_whole_run(band, bq=512, bk=512, seq_q=S, seq_k=S) == 0
    assert band.band(tile=128, seq_q=S, seq_k=S) == (0, 2) and band.band(tile=128, seq_q=512, seq_k=1024) == (4, 2)
    assert masks.Causal(1).band(tile=128, seq_q=S, seq_k=S) == (0, 1)  # a band of one key: the diagonal's tile alone
    # no walk without a loop: a wider band, queries ahead of the keys, fewer tiles than the walk takes, any other mask
    assert masks.Causal(129).band(tile=128, seq_q=S, seq_k=S) is None and band.band(tile=128, seq_q=1024, seq_k=512) is None
    assert band.band(tile=128, seq_q=128, seq_k=S) is None and masks.Causal().band(tile=128, seq_q=S, seq_k=S) is None
    assert masks.Full().band(tile=128, seq_q=S, seq_k=S) is None and masks.BlockDiffusion(4, S // 2).band(tile=128, seq_q=S, seq_k=S) is None
    assert [masks.band_strip(w, 512) for w in (0, 1, 64, 128, 129, 256, 257, 512, 4096)] == [512, 128, 128, 128, 256, 256, 512, 512, 512]
    assert masks.band_strip(128, 256) == 128 and masks.band_strip(128, 128) == 128 and masks.band_strip(64, 64) == 64 and masks.band_strip(16, 16) == 16


def test_fused_adam_matches_reference():
    rng = np.random.RandomState(0)
    n = 1000
    p = jnp.asarray(rng.randn(n).astype(np.float32))
    g = jnp.asarray(rng.randn(n).astype(np.float32))
    m = jnp.zeros(n, jnp.float32)
    v = jnp.zeros(n, jnp.float32)
    p1, m1, v1 = fused_adam_flat(p, g, m, v, lr=1e-2, step=1, weight_decay=0.01, block=256, interpret=True)
    p2, m2, v2 = adam_xla(p, g, m, v, lr=1e-2, step=1, weight_decay=0.01)
    np.testing.assert_allclose(np.asarray(p1), np.asarray(p2), atol=1e-6)
    np.testing.assert_allclose(np.asarray(m1), np.asarray(m2), atol=1e-6)
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), atol=1e-6)


def test_fused_adam_multi_step_matches_optax():
    import optax

    rng = np.random.RandomState(1)
    p = jnp.asarray(rng.randn(300).astype(np.float32))
    opt = optax.adam(1e-2)
    state = opt.init(p)
    p_opt = p
    p_pal = p
    m = jnp.zeros_like(p)
    v = jnp.zeros_like(p)
    for step in range(1, 4):
        g = jnp.asarray(rng.randn(300).astype(np.float32))
        upd, state = opt.update(g, state, p_opt)
        p_opt = optax.apply_updates(p_opt, upd)
        p_pal, m, v = fused_adam_flat(p_pal, g, m, v, lr=1e-2, step=step, weight_decay=0.0, block=128, interpret=True)
    np.testing.assert_allclose(np.asarray(p_pal), np.asarray(p_opt), atol=1e-5)


def test_rms_norm_matches():
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(4, 32, 128).astype(np.float32))
    w = jnp.asarray(rng.randn(128).astype(np.float32))
    np.testing.assert_allclose(np.asarray(rms_norm(x, w, interpret=True)),
                               np.asarray(rms_norm_xla(x, w)), atol=1e-5)


def test_layer_norm_matches():
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(8, 128).astype(np.float32))
    w = jnp.asarray(rng.randn(128).astype(np.float32))
    b = jnp.asarray(rng.randn(128).astype(np.float32))
    np.testing.assert_allclose(np.asarray(layer_norm(x, w, b, interpret=True)),
                               np.asarray(layer_norm_xla(x, w, b)), atol=1e-5)


def test_quantize_roundtrip_error_bounded():
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(64, 128).astype(np.float32))
    q, s = quantize_groupwise(x, group_size=128, interpret=True)
    assert q.dtype == jnp.int8
    back = dequantize_groupwise(q, s, out_shape=x.shape, interpret=True)
    err = np.abs(np.asarray(back) - np.asarray(x))
    scale_bound = np.asarray(s).max() / 2 + 1e-6
    assert err.max() <= scale_bound + 1e-5
    # int8 groupwise: relative error small
    assert err.mean() < 0.02


def test_quantize_pallas_matches_xla():
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(32, 128).astype(np.float32))
    q1, s1 = quantize_groupwise(x, group_size=128, interpret=True)
    q2, s2 = quantize_groupwise_xla(x, group_size=128)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=1e-6)
    assert (np.asarray(q1) == np.asarray(q2)).mean() > 0.999  # rounding ties only


def test_registry_prefers_pallas_on_tpu_only():
    from deepspeed_tpu.ops.registry import REGISTRY

    assert REGISTRY.selected("attention") == "xla"  # CPU test env
    report = REGISTRY.report()
    assert "attention" in report and "fused_adam" in report


@pytest.mark.parametrize("causal", [True, False])
def test_flash_cross_attention_sq_ne_sk(causal):
    """Sq != Sk: queries align to the END of the kv sequence (chunked
    prefill / suffix decode), matching attention_xla's offset convention."""
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(1, 32, 2, 16).astype(np.float32))
    k = jnp.asarray(rng.randn(1, 128, 2, 16).astype(np.float32))
    v = jnp.asarray(rng.randn(1, 128, 2, 16).astype(np.float32))
    ref = attention_xla(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=2e-5)


def test_flash_cross_attention_bwd_sq_ne_sk():
    rng = np.random.RandomState(4)
    q = jnp.asarray(rng.randn(1, 32, 2, 16).astype(np.float32))
    k = jnp.asarray(rng.randn(1, 64, 2, 16).astype(np.float32))
    v = jnp.asarray(rng.randn(1, 64, 2, 16).astype(np.float32))
    gr = jax.grad(lambda *a: jnp.sum(attention_xla(*a, causal=True)**2), argnums=(0, 1, 2))(q, k, v)
    gf = jax.grad(lambda *a: jnp.sum(flash_attention(*a, causal=True, interpret=True)**2), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gr, gf):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-3, rtol=1e-3)


def test_fused_adam_traced_step_under_jit():
    """step may be a traced array: one compile serves every step."""
    rng = np.random.RandomState(5)
    p = jnp.asarray(rng.randn(300).astype(np.float32))
    g = jnp.asarray(rng.randn(300).astype(np.float32))
    m = jnp.zeros(300, jnp.float32)
    v = jnp.zeros(300, jnp.float32)

    @jax.jit
    def step_fn(p, g, m, v, step):
        return fused_adam_flat(p, g, m, v, 1e-3, step, block=256, interpret=True)

    p1, m1, v1 = step_fn(p, g, m, v, jnp.asarray(1, jnp.int32))
    ref = adam_xla(p, g, m, v, 1e-3, 1)
    for a, b in zip((p1, m1, v1), ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_pallas_norm_grads_match_xla():
    """jax.grad must flow through the priority-10 pallas norms."""
    rng = np.random.RandomState(6)
    x = jnp.asarray(rng.randn(4, 32, 64).astype(np.float32))
    w = jnp.asarray(rng.randn(64).astype(np.float32))
    b = jnp.asarray(rng.randn(64).astype(np.float32))

    gr = jax.grad(lambda x, w: jnp.sum(rms_norm_xla(x, w)**2), argnums=(0, 1))(x, w)
    gp = jax.grad(lambda x, w: jnp.sum(rms_norm(x, w, interpret=True)**2), argnums=(0, 1))(x, w)
    for a, b_ in zip(gr, gp):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=2e-4, rtol=2e-4)

    gr = jax.grad(lambda x, w, b: jnp.sum(layer_norm_xla(x, w, b)**2), argnums=(0, 1, 2))(x, w, b)
    gp = jax.grad(lambda x, w, b: jnp.sum(layer_norm(x, w, b, interpret=True)**2), argnums=(0, 1, 2))(x, w, b)
    for a, b_ in zip(gr, gp):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=2e-4, rtol=2e-4)


def test_paged_attention_decode_matches_ref():
    """Pallas paged decode (block-table scalar prefetch) vs gather reference."""
    from deepspeed_tpu.ops.pallas.paged_attention import (paged_attention_decode, paged_attention_ref,
                                                          update_kv_pages)

    rng = np.random.RandomState(11)
    B, H, KVH, D, bs, P, N = 3, 4, 2, 16, 8, 4, 16
    ctx = np.array([5, 17, 8], np.int32)
    bt = np.zeros((B, P), np.int32)
    k_pages = jnp.zeros((N, bs, KVH, D), jnp.float32)
    v_pages = jnp.zeros_like(k_pages)
    nxt, slots, ks, vs = 1, [], [], []
    for b in range(B):
        nb = -(-int(ctx[b]) // bs)
        blocks = list(range(nxt, nxt + nb))
        nxt += nb
        bt[b, :nb] = blocks
        for t in range(int(ctx[b])):
            slots.append(blocks[t // bs] * bs + t % bs)
            ks.append(rng.randn(KVH, D))
            vs.append(rng.randn(KVH, D))
    k_pages, v_pages = update_kv_pages(k_pages, v_pages, jnp.asarray(np.stack(ks), jnp.float32),
                                       jnp.asarray(np.stack(vs), jnp.float32), jnp.asarray(slots, jnp.int32))
    q = jnp.asarray(rng.randn(B, H, D), jnp.float32)
    ctx_j, bt_j = jnp.asarray(ctx), jnp.asarray(bt)
    out_ref = paged_attention_ref(q[:, None], k_pages, v_pages, bt_j, ctx_j, (ctx_j - 1)[:, None])[:, 0]
    out_pal = paged_attention_decode(q, k_pages, v_pages, bt_j, ctx_j, interpret=True)
    np.testing.assert_allclose(np.asarray(out_pal), np.asarray(out_ref), atol=2e-6, rtol=2e-6)


# ---------------- fused LAMB ----------------
def test_fused_lamb_matches_xla_reference():
    from deepspeed_tpu.ops.pallas.fused_lamb import fused_lamb_flat, lamb_xla

    rng = np.random.RandomState(0)
    p = jnp.asarray(rng.randn(300).astype(np.float32))
    g = jnp.asarray(rng.randn(300).astype(np.float32))
    m = jnp.zeros(300, jnp.float32)
    v = jnp.zeros(300, jnp.float32)
    for step in (1, 2, 3):
        p1, m1, v1 = fused_lamb_flat(p, g, m, v, 1e-2, step, weight_decay=0.01, block=128, interpret=True)
        p2, m2, v2 = lamb_xla(p, g, m, v, 1e-2, step, weight_decay=0.01)
        np.testing.assert_allclose(np.asarray(p1), np.asarray(p2), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(m1), np.asarray(m2), rtol=1e-6)
        p, m, v = p1, m1, v1


def test_fused_lamb_trust_ratio_bounds():
    from deepspeed_tpu.ops.pallas.fused_lamb import lamb_xla

    p = jnp.ones(64) * 1e6  # huge weights -> ratio clamps at max_trust
    g = jnp.ones(64)
    p1, _, _ = lamb_xla(p, g, jnp.zeros(64), jnp.zeros(64), 1.0, 1, max_trust=10.0)
    assert float(jnp.max(jnp.abs(p - p1))) <= 10.0 + 1e-3


# ---------------- fp6/fp8/fp12 minifloat quantizer ----------------
def test_fp_quantizer_roundtrip_error_shrinks_with_bits():
    from deepspeed_tpu.ops.pallas.quantization import dequantize_fp, quantize_fp

    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(8, 128).astype(np.float32))
    errs = {}
    for qb in (6, 8, 12):
        q, s = quantize_fp(x, q_bits=qb)
        back = dequantize_fp(q, s, out_shape=x.shape)
        errs[qb] = float(jnp.max(jnp.abs(back - x)))
    assert errs[12] < errs[8] < errs[6]
    assert errs[12] < 0.01


def test_fp_quantizer_exact_on_grid():
    from deepspeed_tpu.ops.pallas.quantization import dequantize_fp, quantize_fp

    # powers of two are exactly representable in every format
    x = jnp.asarray([[1.0, 0.5, 0.25, 2.0] * 32], jnp.float32)
    q, s = quantize_fp(x, q_bits=6, group_size=128)
    back = dequantize_fp(q, s, out_shape=x.shape)
    np.testing.assert_allclose(np.asarray(back), np.asarray(x), rtol=1e-6)


def test_fp_quantizer_rejects_bad_bits():
    from deepspeed_tpu.ops.pallas.quantization import quantize_fp

    with pytest.raises(ValueError):
        quantize_fp(jnp.zeros(128), q_bits=7)


# ---------------- muon ----------------
def test_muon_orthogonalizes_and_converges():
    from deepspeed_tpu.runtime.muon import muon, newton_schulz_orthogonalize

    rng = np.random.RandomState(2)
    g = jnp.asarray(rng.randn(16, 8).astype(np.float32))
    o = newton_schulz_orthogonalize(g)
    # columns approximately orthonormal: o.T @ o ~ I
    gram = np.asarray(o.T @ o)
    np.testing.assert_allclose(gram, np.eye(8), atol=0.35)

    # trains a quadratic (2D weight via muon, bias via adam)
    import optax

    A = jnp.asarray(rng.randn(32, 8).astype(np.float32))
    w_true = jnp.asarray(rng.randn(8, 4).astype(np.float32))
    Y = A @ w_true  # realizable: loss can actually go to 0
    params = {"w": jnp.zeros((8, 4)), "b": jnp.zeros((4,))}
    opt = muon(learning_rate=0.05, adam_lr=0.05)
    state = opt.init(params)

    @jax.jit
    def step(params, state):
        loss, grads = jax.value_and_grad(lambda p: jnp.mean((A @ p["w"] + p["b"] - Y) ** 2))(params)
        updates, state = opt.update(grads, state, params)
        return optax.apply_updates(params, updates), state, loss

    l0 = None
    for i in range(60):
        params, state, loss = step(params, state)
        l0 = l0 if l0 is not None else float(loss)
    assert float(loss) < l0 * 0.5


def test_muon_via_engine_config():
    import deepspeed_tpu
    from deepspeed_tpu.runtime.optimizers import create_optimizer

    opt = create_optimizer("muon", {"lr": 0.02})
    assert opt is not None


# ---------------- evoformer (DS4Science) attention ----------------
def test_evoformer_attention_matches_naive():
    from deepspeed_tpu.ops.evoformer import DS4Sci_EvoformerAttention

    rng = np.random.RandomState(5)
    B, S_msa, S_res, H, D = 2, 3, 8, 2, 4
    q = jnp.asarray(rng.randn(B, S_msa, S_res, H, D).astype(np.float32))
    k = jnp.asarray(rng.randn(B, S_msa, S_res, H, D).astype(np.float32))
    v = jnp.asarray(rng.randn(B, S_msa, S_res, H, D).astype(np.float32))
    mask_bias = jnp.asarray((rng.rand(B, 1, 1, 1, S_res) > 0.2).astype(np.float32)) * 0 - \
        jnp.asarray((rng.rand(B, 1, 1, 1, S_res) > 0.8).astype(np.float32)) * 1e9
    pair_bias = jnp.asarray(rng.randn(B, 1, H, S_res, S_res).astype(np.float32))

    out = DS4Sci_EvoformerAttention(q, k, v, [mask_bias, pair_bias])
    assert out.shape == q.shape

    # naive oracle
    logits = np.einsum("bmqhd,bmkhd->bmhqk", np.asarray(q), np.asarray(k)) / np.sqrt(D)
    logits = logits + np.asarray(mask_bias) + np.asarray(pair_bias)
    e = np.exp(logits - logits.max(-1, keepdims=True))
    p = e / e.sum(-1, keepdims=True)
    ref = np.einsum("bmhqk,bmkhd->bmqhd", p, np.asarray(v))
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4, atol=2e-5)


def test_evoformer_attention_grads_flow():
    from deepspeed_tpu.ops.evoformer import evoformer_attention

    rng = np.random.RandomState(6)
    q = jnp.asarray(rng.randn(1, 4, 2, 4).astype(np.float32))
    bias = jnp.asarray(rng.randn(1, 2, 4, 4).astype(np.float32))
    g = jax.grad(lambda qq, bb: jnp.sum(evoformer_attention(qq, qq, qq, [bb]) ** 2),
                 argnums=(0, 1))(q, bias)
    assert all(np.isfinite(np.asarray(x)).all() for x in g)
    assert float(jnp.sum(jnp.abs(g[1]))) > 0


class TestFlashAlibi:
    """Native ALiBi in the flash kernel (bloom fast path) vs the XLA oracle."""

    def test_fwd_matches_xla(self):
        from deepspeed_tpu.models.transformer import alibi_slopes
        from deepspeed_tpu.ops.attention import attention_xla
        from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

        q, k, v = _qkv(S=256, H=4, seed=7)
        sl = jnp.asarray(alibi_slopes(4))
        o = flash_attention(q, k, v, causal=True, alibi_slopes=sl, interpret=True)
        ref = attention_xla(q, k, v, causal=True, alibi_slopes=sl)
        np.testing.assert_allclose(np.asarray(o), np.asarray(ref), rtol=1e-5, atol=2e-5)
        # and differs from the no-alibi output (the slope actually applies)
        o0 = flash_attention(q, k, v, causal=True, interpret=True)
        assert float(jnp.max(jnp.abs(o - o0))) > 1e-3

    def test_bwd_matches_xla(self):
        from deepspeed_tpu.models.transformer import alibi_slopes
        from deepspeed_tpu.ops.attention import attention_xla
        from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

        q, k, v = _qkv(S=128, H=4, seed=7)
        sl = jnp.asarray(alibi_slopes(4))

        def loss_flash(q, k, v):
            return flash_attention(q, k, v, causal=True, alibi_slopes=sl, interpret=True).sum()

        def loss_xla(q, k, v):
            return attention_xla(q, k, v, causal=True, alibi_slopes=sl).sum()

        g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss_xla, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=5e-5)


class TestFlashWindow:
    """Native sliding-window (mistral) flash path vs the XLA oracle."""

    @pytest.mark.parametrize("window", [3, 64, 100])
    def test_fwd_matches_xla(self, window):
        from deepspeed_tpu.ops.attention import attention_xla
        from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

        q, k, v = _qkv(S=256, H=2, seed=11)
        o = flash_attention(q, k, v, causal=True, window=window, interpret=True)
        ref = attention_xla(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(np.asarray(o), np.asarray(ref), rtol=1e-5, atol=2e-5)

    def test_bwd_matches_xla(self):
        from deepspeed_tpu.ops.attention import attention_xla
        from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

        q, k, v = _qkv(S=128, H=2, seed=12)
        g1 = jax.grad(lambda q, k, v: flash_attention(q, k, v, causal=True, window=40,
                                                      interpret=True).sum(), argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(lambda q, k, v: attention_xla(q, k, v, causal=True, window=40).sum(),
                      argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=5e-5)

    def test_window_with_alibi_composes(self):
        from deepspeed_tpu.models.transformer import alibi_slopes
        from deepspeed_tpu.ops.attention import attention_xla
        from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

        q, k, v = _qkv(S=128, H=4, seed=13)
        sl = jnp.asarray(alibi_slopes(4))
        o = flash_attention(q, k, v, causal=True, window=32, alibi_slopes=sl, interpret=True)
        ref = attention_xla(q, k, v, causal=True, window=32, alibi_slopes=sl)
        np.testing.assert_allclose(np.asarray(o), np.asarray(ref), rtol=1e-5, atol=2e-5)


class TestFlashMultiBlock:
    """Force small blocks so the j0/nq_end skip arithmetic and multi-block
    online accumulation actually execute (defaults collapse small seqs to
    one block)."""

    @pytest.fixture(autouse=True)
    def small_blocks(self, monkeypatch):
        import deepspeed_tpu.ops.pallas.flash_attention as fa

        monkeypatch.setattr(fa, "DEFAULT_BQ", 64)
        monkeypatch.setattr(fa, "DEFAULT_BK", 64)

    @pytest.mark.parametrize("window", [3, 40, 100, None])
    def test_window_fwd_multiblock(self, window):
        q, k, v = _qkv(S=256, H=2, seed=21)
        o = flash_attention(q, k, v, causal=True, window=window, interpret=True)
        ref = attention_xla(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(np.asarray(o), np.asarray(ref), rtol=1e-5, atol=2e-5)

    def test_window_bwd_multiblock(self):
        q, k, v = _qkv(S=256, H=2, seed=22)
        g1 = jax.grad(lambda q, k, v: flash_attention(q, k, v, causal=True, window=70,
                                                      interpret=True).sum(), argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(lambda q, k, v: attention_xla(q, k, v, causal=True, window=70).sum(),
                      argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=5e-5)

    def test_window_cross_attention_sq_ne_sk(self):
        """Suffix queries (chunked prefill) with a window: offset path."""
        rng = np.random.RandomState(23)
        q = jnp.asarray(rng.randn(1, 64, 2, 64).astype(np.float32))
        k = jnp.asarray(rng.randn(1, 256, 2, 64).astype(np.float32))
        v = jnp.asarray(rng.randn(1, 256, 2, 64).astype(np.float32))
        o = flash_attention(q, k, v, causal=True, window=48, interpret=True)
        ref = attention_xla(q, k, v, causal=True, window=48)
        np.testing.assert_allclose(np.asarray(o), np.asarray(ref), rtol=1e-5, atol=2e-5)

    def test_alibi_multiblock(self):
        from deepspeed_tpu.models.transformer import alibi_slopes

        q, k, v = _qkv(S=256, H=4, seed=24)
        sl = jnp.asarray(alibi_slopes(4))
        o = flash_attention(q, k, v, causal=True, alibi_slopes=sl, interpret=True)
        ref = attention_xla(q, k, v, causal=True, alibi_slopes=sl)
        np.testing.assert_allclose(np.asarray(o), np.asarray(ref), rtol=1e-5, atol=2e-5)


def test_window_zero_rejected_consistently():
    q, k, v = _qkv(S=64)
    with pytest.raises(ValueError, match="window must be >= 1"):
        attention_xla(q, k, v, causal=True, window=0)
    with pytest.raises(ValueError, match="window must be >= 1"):
        flash_attention(q, k, v, causal=True, window=0, interpret=True)


class TestEvoformerKernelPath:
    """evoformer_attention through the Pallas flash kernel (additive bias
    + in-kernel dbias), interpret mode — vs the jnp fallback oracle."""

    def test_msa_shapes_match_fallback(self):
        from deepspeed_tpu.ops.evoformer import evoformer_attention

        rng = np.random.RandomState(7)
        B, S_msa, S_res, H, D = 2, 3, 8, 2, 4
        q = jnp.asarray(rng.randn(B, S_msa, S_res, H, D).astype(np.float32))
        k = jnp.asarray(rng.randn(B, S_msa, S_res, H, D).astype(np.float32))
        v = jnp.asarray(rng.randn(B, S_msa, S_res, H, D).astype(np.float32))
        mask_bias = jnp.asarray(rng.randn(B, 1, 1, 1, S_res).astype(np.float32))
        pair_bias = jnp.asarray(rng.randn(B, 1, H, S_res, S_res).astype(np.float32))
        ref = evoformer_attention(q, k, v, [mask_bias, pair_bias], interpret=False)
        out = evoformer_attention(q, k, v, [mask_bias, pair_bias], interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)

    def test_dbias_matches_fallback(self):
        from deepspeed_tpu.ops.evoformer import evoformer_attention

        rng = np.random.RandomState(8)
        q = jnp.asarray(rng.randn(2, 8, 2, 4).astype(np.float32))
        pair = jnp.asarray(rng.randn(1, 2, 8, 8).astype(np.float32))   # broadcast over batch
        loss = lambda interp: (lambda qq, bb: jnp.sum(
            evoformer_attention(qq, qq, qq, [bb], interpret=interp) ** 2))
        g_ref = jax.grad(loss(False), argnums=(0, 1))(q, pair)
        g_ker = jax.grad(loss(True), argnums=(0, 1))(q, pair)
        for a, b in zip(g_ker, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5)


class TestFlashBias:
    """Native additive bias in the flash kernel vs the XLA oracle."""

    @pytest.mark.parametrize("causal", [False, True])
    def test_fwd_bwd_match_xla(self, causal):
        from deepspeed_tpu.ops.attention import attention_xla
        from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

        rng = jax.random.PRNGKey(3)
        k1, k2, k3, k4 = jax.random.split(rng, 4)
        B, S, H, D = 2, 64, 4, 16
        q = jax.random.normal(k1, (B, S, H, D))
        k = jax.random.normal(k2, (B, S, H, D))
        v = jax.random.normal(k3, (B, S, H, D))
        bias = jax.random.normal(k4, (B, H, S, S)) * 0.5
        o_ref = attention_xla(q, k, v, causal=causal, bias=bias)
        o = flash_attention(q, k, v, causal=causal, bias=bias, interpret=True)
        np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=3e-6)
        g_ref = jax.grad(lambda *a: attention_xla(*a[:3], causal=causal, bias=a[3]).sum(),
                         argnums=(0, 1, 2, 3))(q, k, v, bias)
        g = jax.grad(lambda *a: flash_attention(*a[:3], causal=causal, bias=a[3], interpret=True).sum(),
                     argnums=(0, 1, 2, 3))(q, k, v, bias)
        for a, b in zip(g, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


class TestFlashBiasCollapsed:
    """Broadcast biases stay collapsed in HBM: index-mapped reads + dbias
    accumulated in the bias's own shape (3D grid, repeat dim innermost)."""

    def _qkv(self, B=4, S=32, H=2, D=8, seed=0):
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
        return (jax.random.normal(k1, (B, S, H, D)), jax.random.normal(k2, (B, S, H, D)),
                jax.random.normal(k3, (B, S, H, D)))

    @pytest.mark.parametrize("shape,label", [
        ((1, 1, 1, 32), "mask-row"),         # fully collapsed (B,H,Sq all broadcast)
        ((4, 1, 1, 32), "per-batch-mask"),   # H,Sq collapsed
        ((1, 2, 32, 32), "shared-pair"),     # batch collapsed
        ((4, 2, 32, 32), "full"),            # no collapse (2D-grid path)
    ])
    def test_fwd_and_dbias_match_oracle(self, shape, label):
        from deepspeed_tpu.ops.attention import attention_xla
        from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

        q, k, v = self._qkv()
        bias = jax.random.normal(jax.random.PRNGKey(7), shape) * 0.5
        full = jnp.broadcast_to(bias, (4, 2, 32, 32))
        o_ref = attention_xla(q, k, v, causal=False, bias=full)
        o = flash_attention(q, k, v, causal=False, bias=bias, interpret=True)
        np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=3e-6, err_msg=label)
        # dbias in the COLLAPSED shape must equal the reduced full-gradient
        g_ref = jax.grad(lambda b: attention_xla(q, k, v, causal=False,
                                                 bias=jnp.broadcast_to(b, (4, 2, 32, 32))).sum())(bias)
        g = jax.grad(lambda b: flash_attention(q, k, v, causal=False, bias=b, interpret=True).sum())(bias)
        assert g.shape == bias.shape
        np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), atol=1e-4, err_msg=label)

    def test_bias_repeat_msa_rows(self):
        """bias_repeat: consecutive q-batch groups (MSA rows) share one
        bias slice; dbias sums over the repeat."""
        from deepspeed_tpu.ops.attention import attention_xla
        from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

        B_outer, msa, S, H, D = 2, 3, 16, 2, 8
        q, k, v = self._qkv(B=B_outer * msa, S=S, H=H, D=D, seed=1)
        bias = jax.random.normal(jax.random.PRNGKey(9), (B_outer, H, S, S)) * 0.5
        full = jnp.repeat(bias, msa, axis=0)
        o_ref = attention_xla(q, k, v, causal=False, bias=full)
        o = flash_attention(q, k, v, causal=False, bias=bias, bias_repeat=msa, interpret=True)
        np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=3e-6)
        g_ref = jax.grad(lambda b: attention_xla(q, k, v, causal=False,
                                                 bias=jnp.repeat(b, msa, axis=0)).sum())(bias)
        g = jax.grad(lambda b: flash_attention(q, k, v, causal=False, bias=b, bias_repeat=msa,
                                               interpret=True).sum())(bias)
        assert g.shape == bias.shape
        np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), atol=1e-4)

    def test_causal_with_collapsed_bias(self):
        from deepspeed_tpu.ops.attention import attention_xla
        from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

        q, k, v = self._qkv(seed=2)
        bias = jax.random.normal(jax.random.PRNGKey(11), (1, 2, 32, 32)) * 0.5
        o_ref = attention_xla(q, k, v, causal=True, bias=jnp.broadcast_to(bias, (4, 2, 32, 32)))
        o = flash_attention(q, k, v, causal=True, bias=bias, interpret=True)
        np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=3e-6)
        g_ref = jax.grad(lambda b: attention_xla(q, k, v, causal=True,
                                                 bias=jnp.broadcast_to(b, (4, 2, 32, 32))).sum())(bias)
        g = jax.grad(lambda b: flash_attention(q, k, v, causal=True, bias=b, interpret=True).sum())(bias)
        np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), atol=1e-4)

    def test_bad_bias_shape_rejected(self):
        from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

        q, k, v = self._qkv()
        with pytest.raises(ValueError, match="broadcastable"):
            flash_attention(q, k, v, causal=False, bias=jnp.zeros((3, 2, 32, 32)), interpret=True)


# ---------------- int8-quantized paged KV ----------------
class TestPagedAttentionInt8:
    """Fused-dequant paged attention vs the fp32 gather oracle.

    Two-tier check per kernel: (a) the Pallas int8 kernel must match the
    int8 *reference* (same codes, dequant in the oracle) to float
    round-off — the fused dequant itself adds no error; (b) against the
    fp32 oracle the end-to-end error is bounded by the quantizer's
    1/254-of-amax step propagated through softmax-weighted averaging."""

    def _pools(self, seed=11, B=3, KVH=2, D=16, bs=8, P=4, N=16,
               ctx=(5, 17, 8)):
        from deepspeed_tpu.ops.pallas.paged_attention import (make_kv_pool,
                                                              update_kv_pages)
        rng = np.random.RandomState(seed)
        ctx = np.asarray(ctx, np.int32)
        bt = np.zeros((B, P), np.int32)
        nxt, slots, ks, vs = 1, [], [], []
        for b in range(B):
            nb = -(-int(ctx[b]) // bs)
            blocks = list(range(nxt, nxt + nb))
            nxt += nb
            bt[b, :nb] = blocks
            for t in range(int(ctx[b])):
                slots.append(blocks[t // bs] * bs + t % bs)
                ks.append(rng.randn(KVH, D))
                vs.append(rng.randn(KVH, D))
        kn = jnp.asarray(np.stack(ks), jnp.float32)
        vn = jnp.asarray(np.stack(vs), jnp.float32)
        sm = jnp.asarray(slots, jnp.int32)
        kf, vf = update_kv_pages(jnp.zeros((N, bs, KVH, D), jnp.float32),
                                 jnp.zeros((N, bs, KVH, D), jnp.float32), kn, vn, sm)
        k8, v8 = update_kv_pages(make_kv_pool((N, bs, KVH, D), jnp.float32, 8),
                                 make_kv_pool((N, bs, KVH, D), jnp.float32, 8), kn, vn, sm)
        return rng, jnp.asarray(ctx), jnp.asarray(bt), (kf, vf), (k8, v8)

    def test_quantize_roundtrip_error_bounded(self):
        from deepspeed_tpu.ops.pallas.paged_attention import dequantize_kv, quantize_kv
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(64, 2, 32) * 3.0, jnp.float32)
        codes, scales = quantize_kv(x)
        assert codes.dtype == jnp.int8 and scales.shape == (64, 2)
        back = dequantize_kv((codes, scales))
        # symmetric rounding: per-row error <= half a step = amax / 254
        step = np.asarray(jnp.max(jnp.abs(x), axis=-1))[..., None] / 254.0
        assert np.all(np.abs(np.asarray(back - x)) <= step + 1e-7)
        # all-zero rows stay exact (scale pinned to 1.0, not 0/0)
        z = jnp.zeros((4, 2, 32), jnp.float32)
        np.testing.assert_array_equal(np.asarray(dequantize_kv(quantize_kv(z))), np.asarray(z))

    def test_decode_int8_matches_quantized_ref(self):
        from deepspeed_tpu.ops.pallas.paged_attention import (paged_attention_decode,
                                                              paged_attention_ref)
        rng, ctx, bt, _, (k8, v8) = self._pools()
        q = jnp.asarray(rng.randn(3, 4, 16), jnp.float32)
        o_ref = paged_attention_ref(q[:, None], k8, v8, bt, ctx, (ctx - 1)[:, None])[:, 0]
        o_pal = paged_attention_decode(q, k8, v8, bt, ctx, interpret=True)
        np.testing.assert_allclose(np.asarray(o_pal), np.asarray(o_ref), atol=2e-6, rtol=2e-6)

    def test_decode_int8_error_vs_fp32_oracle_bounded(self):
        from deepspeed_tpu.ops.pallas.paged_attention import (paged_attention_decode,
                                                              paged_attention_ref)
        rng, ctx, bt, (kf, vf), (k8, v8) = self._pools()
        q = jnp.asarray(rng.randn(3, 4, 16), jnp.float32)
        o_fp = paged_attention_ref(q[:, None], kf, vf, bt, ctx, (ctx - 1)[:, None])[:, 0]
        o_q = paged_attention_decode(q, k8, v8, bt, ctx, interpret=True)
        err = np.abs(np.asarray(o_q) - np.asarray(o_fp))
        # V error: one quant step of the ~N(0,1) values; K error perturbs
        # softmax weights by ~scale*|q|/254 per logit — both well under 5e-2
        assert float(err.max()) < 5e-2, f"int8 decode error {err.max():.3e}"

    def test_prefill_int8_matches_quantized_ref_and_fp32_bound(self):
        from deepspeed_tpu.ops.pallas.paged_attention import (paged_attention_prefill,
                                                              paged_attention_ref)
        rng, ctx0, bt, (kf, vf), (k8, v8) = self._pools(ctx=(8, 24, 16))
        B, S, H, D = 3, 8, 4, 16
        ctx = ctx0 + S  # S new tokens atop each context
        # extend block tables to cover the appended tokens (pages already
        # big enough at P=4 for ctx<=32); positions are the last S slots
        pos = (ctx[:, None] - S + jnp.arange(S)[None, :]).astype(jnp.int32)
        q = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
        bt2 = np.asarray(bt).copy()
        nxt = int(np.asarray(bt).max()) + 1
        for b in range(B):
            nb0, nb1 = -(-int(ctx0[b]) // 8), -(-int(ctx[b]) // 8)
            for p in range(nb0, nb1):
                bt2[b, p] = nxt
                nxt += 1
        bt2 = jnp.asarray(bt2)
        # write the S new tokens into both pools so context is complete
        from deepspeed_tpu.ops.pallas.paged_attention import update_kv_pages
        slots, ks, vs = [], [], []
        for b in range(B):
            for i, t in enumerate(range(int(ctx0[b]), int(ctx[b]))):
                slots.append(int(bt2[b, t // 8]) * 8 + t % 8)
                ks.append(rng.randn(2, D))
                vs.append(rng.randn(2, D))
        kn, vn = jnp.asarray(np.stack(ks), jnp.float32), jnp.asarray(np.stack(vs), jnp.float32)
        sm = jnp.asarray(slots, jnp.int32)
        kf, vf = update_kv_pages(kf, vf, kn, vn, sm)
        k8, v8 = update_kv_pages(k8, v8, kn, vn, sm)

        o_qref = paged_attention_ref(q, k8, v8, bt2, ctx, pos)
        o_pal = paged_attention_prefill(q, k8, v8, bt2, ctx, pos, interpret=True)
        np.testing.assert_allclose(np.asarray(o_pal), np.asarray(o_qref), atol=2e-6, rtol=2e-6)
        o_fp = paged_attention_ref(q, kf, vf, bt2, ctx, pos)
        err = np.abs(np.asarray(o_pal) - np.asarray(o_fp))
        assert float(err.max()) < 5e-2, f"int8 prefill error {err.max():.3e}"

    def test_mixed_routes_quantized_pools(self):
        from deepspeed_tpu.ops.pallas.paged_attention import (paged_attention_mixed,
                                                              paged_attention_ref)
        rng, ctx, bt, _, (k8, v8) = self._pools()
        T, H, D = 3, 4, 16
        q = jnp.asarray(rng.randn(T, H, D), jnp.float32)
        o_mix = paged_attention_mixed(q, k8, v8, bt, ctx, (ctx - 1), n_dec=T, chunk=0)
        o_ref = paged_attention_ref(q[:, None], k8, v8, bt, ctx, (ctx - 1)[:, None])[:, 0]
        np.testing.assert_allclose(np.asarray(o_mix), np.asarray(o_ref), atol=2e-6, rtol=2e-6)

    def test_layer_helpers_roundtrip(self):
        from deepspeed_tpu.ops.pallas.paged_attention import (kv_layer, kv_pool_is_quantized,
                                                              kv_pool_shape, kv_set_layer,
                                                              make_kv_pool, quantize_kv)
        pool = make_kv_pool((2, 4, 3, 2, 8), jnp.float32, 8)
        assert kv_pool_is_quantized(pool) and not kv_pool_is_quantized(jnp.zeros(3))
        assert kv_pool_shape(pool) == (2, 4, 3, 2, 8)
        x = jnp.asarray(np.random.RandomState(3).randn(4, 3, 2, 8), jnp.float32)
        pool = kv_set_layer(pool, 1, quantize_kv(x))
        got = kv_layer(pool, 1)
        np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(quantize_kv(x)[0]))
        assert kv_layer(pool, 0)[0].shape == (4, 3, 2, 8)


# ------------------------------------------------------------------
# kernels on a mesh of several devices (GSPMD cannot partition a Mosaic
# kernel: each one sits in a shard_map that is manual over every axis)
# ------------------------------------------------------------------
class TestKernelsOnAMesh:

    @pytest.mark.parametrize("mesh,extra", [({"data": 2, "fsdp": 2, "tensor": 2}, "plain"),
                                            ({"fsdp": 4, "data": 2}, "alibi"),
                                            ({"data": 8}, "indivisible")])
    def test_flash_fwd_bwd_under_sharded_jit(self, mesh, extra):
        from jax.sharding import NamedSharding, PartitionSpec as P

        from deepspeed_tpu.parallel.mesh import initialize_mesh
        from deepspeed_tpu.runtime.config import MeshConfig

        topo = initialize_mesh(MeshConfig.from_dict(mesh), force=True)
        B = 4 if extra == "indivisible" else 8  # 8 devices do not divide a batch of 4: it stays whole
        rng = np.random.RandomState(0)
        q = jnp.asarray(rng.randn(B, 64, 4, 16), jnp.float32)
        k, v = (jnp.asarray(rng.randn(B, 64, 2, 16), jnp.float32) for _ in range(2))
        kw = {"alibi_slopes": np.geomspace(0.25, 0.01, 4).astype(np.float32)} if extra == "alibi" else {}
        batch = NamedSharding(topo.mesh, P(topo.batch_axes) if extra != "indivisible" else P())
        q, k, v = (jax.device_put(x, batch) for x in (q, k, v))

        def grads(attn):
            return jax.jit(jax.value_and_grad(lambda q, k, v: (attn(q, k, v, causal=True, **kw) ** 2).sum(),
                                              argnums=(0, 1, 2)))(q, k, v)

        (l_ref, g_ref) = grads(attention_xla)
        (l_out, g_out) = grads(lambda *a, **kws: flash_attention(*a, interpret=True, **kws))
        np.testing.assert_allclose(float(l_out), float(l_ref), rtol=1e-5)
        for a, b in zip(g_out, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)

    def test_norms_under_a_multi_device_jit(self):
        from deepspeed_tpu.parallel.mesh import initialize_mesh
        from deepspeed_tpu.runtime.config import MeshConfig

        topo = initialize_mesh(MeshConfig.from_dict({"data": 4, "tensor": 2}), force=True)
        rng = np.random.RandomState(0)
        x = jax.device_put(jnp.asarray(rng.randn(2, 16, 64), jnp.float32), topo.replicated())
        w, b = jnp.asarray(rng.randn(64), jnp.float32), jnp.asarray(rng.randn(64), jnp.float32)
        out = jax.jit(lambda x: layer_norm(x, w, b, 1e-5, interpret=True))(x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(layer_norm_xla(x, w, b)), atol=1e-5)
        out = jax.jit(lambda x: rms_norm(x, w, 1e-5, interpret=True))(x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(rms_norm_xla(x, w)), atol=1e-5)
