"""Performance-accounting tests (docs/OBSERVABILITY.md "Performance
accounting"): cost-card construction and steady reuse, wall-window
attribution and goodput math, mode-2 AOT XLA analysis, the goodput
ledger's spec/prefix/COW pricing, the HBM pressure detector, the
accelerator peak-memory reset, engine integration, and the warm path's
counts.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.telemetry import PerfAccountant, get_perf_accountant
from deepspeed_tpu.telemetry.costs import resolve_peaks


def _mm_accountant(mode=1):
    acct = PerfAccountant(mode=mode, use_telemetry=False)
    fn = jax.jit(lambda a, b: a @ b)
    w = acct.wrap("mm", fn, meta={"kind": "test"})
    return acct, w


# ---------------------------------------------------------------- cards

def test_cost_card_exact_flops_and_steady_reuse():
    acct, w = _mm_accountant()
    x = jnp.ones((8, 16), jnp.float32)
    y = jnp.ones((16, 4), jnp.float32)
    jax.block_until_ready(w(x, y))
    acct.attribute(useful_tokens=6, slot_tokens=8)
    (card,) = acct.cards().values()
    assert card.flops == 2 * 8 * 16 * 4  # the jaxpr walker's matmul count
    assert card.macs == 8 * 16 * 4
    assert card.source == "analytic"
    assert card.meta["kind"] == "test"
    # analytic HBM lower bound: args read once + outputs written once
    assert card.bytes_accessed == (8 * 16 + 16 * 4 + 8 * 4) * 4
    # warm path: same signature is a dict hit, not a new card
    w(x, y)
    acct.attribute(6, 8)
    assert len(acct.cards()) == 1 and card.calls == 2 and card.timed_calls == 2
    # a new bucket signature gets its own card
    w(jnp.ones((4, 16), jnp.float32), y)
    acct.attribute(3, 4)
    assert len(acct.cards()) == 2


def test_mode2_aot_xla_analysis():
    acct, w = _mm_accountant(mode=2)
    x = jnp.ones((8, 16), jnp.float32)
    y = jnp.ones((16, 4), jnp.float32)
    jax.block_until_ready(w(x, y))
    (card,) = acct.cards().values()
    assert card.source == "xla"
    assert card.xla_flops > 0
    assert card.bytes_accessed > 0
    assert card.arg_bytes == (8 * 16 + 16 * 4) * 4
    assert card.out_bytes == 8 * 4 * 4


def test_disabled_mode_is_identity():
    acct = PerfAccountant(mode=0, use_telemetry=False)
    fn = jax.jit(lambda a: a + 1)
    assert acct.wrap("noop", fn) is fn
    acct.attribute(1, 1)  # no-op, no crash
    assert acct.totals()["flops"] == 0


def test_cost_meta_rides_the_wrapped_fn():
    """model_runner factories stamp ``_cost_meta`` on their jits; wrap()
    merges it into the card so the roofline report can label buckets."""
    acct = PerfAccountant(mode=1, use_telemetry=False)
    fn = jax.jit(lambda a: a * 2)
    fn._cost_meta = {"kind": "fused_step", "chunk": 16}
    w = acct.wrap("fused", fn)
    w(jnp.ones((4,), jnp.float32))
    (card,) = acct.cards().values()
    assert card.meta == {"kind": "fused_step", "chunk": 16}


# ---------------------------------------------------------- attribution

def test_attribution_and_goodput_math():
    acct, w = _mm_accountant()
    x = jnp.ones((8, 16), jnp.float32)
    y = jnp.ones((16, 4), jnp.float32)
    jax.block_until_ready(w(x, y))
    acct.attribute(useful_tokens=5, slot_tokens=8)
    jax.block_until_ready(w(x, y))
    acct.attribute(useful_tokens=3, slot_tokens=8)
    tot = acct.totals()
    assert tot["useful_tokens"] == 8 and tot["slot_tokens"] == 16
    assert tot["flops"] == 2 * (2 * 8 * 16 * 4)
    assert tot["time_s"] > 0
    led = acct.ledger()
    assert led["goodput_fraction"] == pytest.approx(0.5)


def test_untimed_wrap_cannot_clobber_a_window():
    """The COW page copy dispatches *inside* another quantum's window;
    wrapped with timed=False it must never open (or steal) attribution."""
    acct = PerfAccountant(mode=1, use_telemetry=False)
    quantum = acct.wrap("fused", jax.jit(lambda a: a * 2))
    cow = acct.wrap("cow_copy", jax.jit(lambda a: a + 1), timed=False)
    x = jnp.ones((4,), jnp.float32)
    quantum(x)
    cow(x)  # mid-window dispatch, like _copy_block during a quantum
    acct.attribute(4, 4)
    cards = {c.program: c for c in acct.cards().values()}
    assert cards["fused"].timed_calls == 1
    assert cards["cow_copy"].timed_calls == 0 and cards["cow_copy"].calls == 1
    # with no window open, attribute() is a silent drop
    acct.attribute(1, 1)
    assert acct.totals()["useful_tokens"] == 4


def test_ledger_prices_spec_prefix_and_cow():
    acct = PerfAccountant(mode=1, use_telemetry=False)
    verify = acct.wrap("spec4", jax.jit(lambda a, b: a @ b))
    prefill = acct.wrap("prefill", jax.jit(lambda a, b: a @ b))
    x = jnp.ones((8, 16), jnp.float32)
    y = jnp.ones((16, 4), jnp.float32)
    jax.block_until_ready(verify(x, y))
    acct.attribute(4, 8)
    acct.note_spec(proposed=10, accepted=6)
    jax.block_until_ready(prefill(x, y))
    acct.attribute(8, 16)
    acct.note_prefix_hit(32)
    acct.note_cow(4096)
    led = acct.ledger()
    flops = 2 * 8 * 16 * 4
    assert led["spec_rejected_tokens"] == 4
    assert led["spec_rejected_flops"] == int(flops * 4 / 10)
    # prefix hits priced at the prefill-class FLOPs-per-slot-token rate
    assert led["prefix_saved_prefill_flops"] == int(32 * flops / 16)
    assert led["cow_copy_bytes"] == 4096


# --------------------------------------------------- peaks / mfu / hbm

def test_resolve_peaks_declared_knobs_win(monkeypatch):
    monkeypatch.setenv("DS_TPU_PEAK_TFLOPS", "100")
    monkeypatch.setenv("DS_TPU_PEAK_GBPS", "1000")
    assert resolve_peaks() == (100e12, 1000e9)


def test_mfu_and_roofline_against_declared_peak(monkeypatch):
    monkeypatch.setenv("DS_TPU_PEAK_TFLOPS", "1e-3")  # 1 GF/s: tiny, reachable
    monkeypatch.setenv("DS_TPU_PEAK_GBPS", "1")
    acct, w = _mm_accountant()
    x = jnp.ones((8, 16), jnp.float32)
    y = jnp.ones((16, 4), jnp.float32)
    jax.block_until_ready(w(x, y))
    acct.attribute(8, 8)
    assert acct.mfu(flops=1e9, time_s=2.0) == pytest.approx(0.5)
    (card,) = acct.cards().values()
    # machine balance = 1e9 / 1e9 = 1 F/B; this matmul's intensity is
    # 1024F / 896B ≈ 1.14 F/B — just over the ridge, compute-bound
    assert card.intensity() == pytest.approx(1024 / 896)
    assert card.bound(*acct.peaks()) == "compute"
    snap = acct.snapshot()
    assert snap["peaks"]["machine_balance_flops_per_byte"] == pytest.approx(1.0)
    assert snap["cards"][0]["pct_peak_flops"] > 0


def test_unknown_peak_degrades_to_none():
    acct, w = _mm_accountant()  # CPU: no spec-table match, knobs unset
    if resolve_peaks()[0] > 0:
        pytest.skip("peak knobs set in this environment")
    assert acct.mfu(flops=1e9, time_s=1.0) is None
    x = jnp.ones((8, 16), jnp.float32)
    y = jnp.ones((16, 4), jnp.float32)
    w(x, y)
    (card,) = acct.cards().values()
    assert card.bound(*acct.peaks()) == "unknown"


def test_hbm_pools_and_pressure():
    acct = PerfAccountant(mode=1, use_telemetry=False)
    p = acct.set_hbm(limit=1000, weights=500, kv_pages=300, prefix=100)
    assert p == pytest.approx(0.8)  # prefix is a subset of kv_pages, not added
    hbm = acct.hbm()
    assert hbm["weights"] == 500 and hbm["kv_pages"] == 300 and hbm["prefix"] == 100
    assert hbm["pressure"] == pytest.approx(0.8) and hbm["limit"] == 1000
    # no limit known (CPU): pressure 0, detector can never fire
    acct2 = PerfAccountant(mode=1, use_telemetry=False)
    assert acct2.set_hbm(weights=10**12, kv_pages=10**12) == 0.0


def test_snapshot_serializable_and_resets():
    acct, w = _mm_accountant(mode=2)
    x = jnp.ones((8, 16), jnp.float32)
    y = jnp.ones((16, 4), jnp.float32)
    jax.block_until_ready(w(x, y))
    acct.attribute(6, 8)
    snap = acct.snapshot()
    json.dumps(snap)  # GET /perf serializes it as-is
    assert snap["cards"][0]["program"] == "mm"
    assert snap["totals"]["useful_tokens"] == 6
    # reset_counts keeps cards (no re-trace/re-compile after warmup)...
    acct.reset_counts()
    assert len(acct.cards()) == 1
    assert acct.totals()["flops"] == 0
    (card,) = acct.cards().values()
    assert card.calls == 0 and card.source == "xla"
    # ...full reset drops them
    acct.reset()
    assert not acct.cards()


# ------------------------------------------------------ health detector

def test_hbm_pressure_detector_fires_latches_and_rearms():
    from deepspeed_tpu.telemetry.health import HBMPressureDetector

    d = HBMPressureDetector(threshold=0.9, hysteresis=0.8, cooldown_s=0.0)
    assert d.observe(0.85) is None          # below threshold
    alert = d.observe(0.95)
    assert alert is not None and alert.detector == "hbm_pressure"
    assert alert.attrs["fraction"] == pytest.approx(0.95)
    assert d.observe(0.99) is None          # latched while firing
    assert d.observe(0.85) is None          # between hysteresis and threshold
    assert d.firing                         # still latched
    d.observe(0.5)                          # below hysteresis: re-arms
    assert not d.firing
    assert d.observe(0.95) is not None      # fires again
    assert d.observe(float("nan")) is None  # non-finite ignored


def test_health_monitor_observe_hbm_dispatches():
    from deepspeed_tpu.telemetry import MetricsRegistry
    from deepspeed_tpu.telemetry.health import HBMPressureDetector, HealthMonitor

    seen = []
    hm = HealthMonitor(registry=MetricsRegistry(), sinks=[seen.append])
    hm.ensure_detector(HBMPressureDetector(threshold=0.9, cooldown_s=0.0))
    hm.observe_hbm(0.5, weights_bytes=100)
    assert hm.healthy and not seen
    hm.observe_hbm(0.95, weights_bytes=100)
    assert not hm.healthy
    assert seen and seen[0].detector == "hbm_pressure"
    assert seen[0].attrs["weights_bytes"] == 100


# ------------------------------------------------- accelerator satellite

def test_accelerator_peak_memory_reset(monkeypatch):
    """reset_peak_memory_stats was a silent no-op (XLA's counter is
    monotonic); it now rebases so max_memory_allocated is peak-since-reset."""
    from deepspeed_tpu.accelerator.tpu_accelerator import TPU_Accelerator

    acc = TPU_Accelerator()
    stats = {"peak_bytes_in_use": 1000, "bytes_in_use": 400}
    monkeypatch.setattr(acc, "_stats", lambda device_index=None: dict(stats))
    assert acc.max_memory_allocated() == 1000
    acc.reset_peak_memory_stats()
    assert acc.max_memory_allocated() == 0  # monotonic peak rebased away
    stats["peak_bytes_in_use"] = 1500       # new allocation spike
    assert acc.max_memory_allocated() == 500
    # live bytes above the stale peak stat also anchor the baseline
    stats.update(peak_bytes_in_use=0, bytes_in_use=2000)
    acc.reset_peak_memory_stats()
    stats.update(peak_bytes_in_use=2600)
    assert acc.max_memory_allocated() == 600
    # per-device baselines are independent
    assert acc.max_memory_allocated(device_index=1) == 2600


# ------------------------------------------------------- engine wiring

def test_engine_attributes_serving_dispatches():
    """End to end on the CPU v2 engine: a generate() leaves cost cards
    with attributed time, goodput tokens, and populated HBM pools on the
    process-wide accountant (default mode: analytic, on)."""
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2, RaggedBatchConfig,
                                            RaggedInferenceEngineConfig)
    from deepspeed_tpu.models import CausalLM, TransformerConfig

    acct = get_perf_accountant()
    if not acct.enabled:
        pytest.skip("DS_TPU_PERF_ACCOUNT=0 in this environment")
    cfg_model = TransformerConfig(vocab_size=128, n_layers=2, n_heads=4, n_kv_heads=2,
                                  d_model=32, max_seq_len=128, norm="rmsnorm",
                                  activation="swiglu", pos_emb="rope", tie_embeddings=False)
    model = CausalLM(cfg_model)
    params = model.init(jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 8), np.int32)})
    smc = RaggedBatchConfig(kv_block_size=8, max_context=128, num_kv_blocks=64)
    eng = InferenceEngineV2(model, params,
                            RaggedInferenceEngineConfig(state_manager=smc, dtype="float32"))
    before = acct.totals()
    prompts = [[1, 2, 3, 4, 5], [7, 8, 9]]
    out = eng.generate(prompts, max_new_tokens=4)
    assert all(len(o) == 4 for o in out)
    after = acct.totals()
    assert after["flops"] > before["flops"]
    d_useful = after["useful_tokens"] - before["useful_tokens"]
    d_slot = after["slot_tokens"] - before["slot_tokens"]
    assert 0 < d_useful <= d_slot  # padding can only add slots
    hbm = acct.hbm()
    assert hbm["weights"] > 0 and hbm["kv_pages"] > 0
    # every serving card carries its program-class label
    kinds = {c.meta.get("kind") for c in acct.cards().values()
             if c.program.startswith(("fused", "prefill", "decode"))}
    assert kinds & {"fused_step", "prefill", "decode"}


# ------------------------------------------------------------ warm path

def test_warm_dispatches_reuse_one_card_and_count_every_call():
    """Steady-state accounting is a signature lookup, a dict hit and an
    attribute: after the card is built, n more dispatches build no other
    card and each is counted once. (What the path costs on the chip is
    PERF.md's business; a CPU loop's timing says nothing about it.)"""
    acct = PerfAccountant(mode=1, use_telemetry=False)
    w = acct.wrap("hot", jax.jit(lambda a: a * 2 + 1))
    x = jnp.ones((64, 64), jnp.float32)
    jax.block_until_ready(w(x))
    acct.attribute(1, 1)  # card built; everything after is the warm path
    n = 300
    for _ in range(n):
        w(x)
        acct.attribute(1, 1)
    (card,) = acct.cards().values()
    assert card.calls == card.timed_calls == n + 1
    tot = acct.totals()
    assert tot["useful_tokens"] == tot["slot_tokens"] == n + 1
    assert tot["flops"] == (n + 1) * card.flops
