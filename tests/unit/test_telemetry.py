"""Telemetry subsystem tests: registry semantics, Prometheus exposition,
span tracer, MonitorBridge, and the end-to-end engine wiring.

Unit tests construct their own ``MetricsRegistry``/``SpanTracer`` so they
are hermetic; the integration tests measure DELTAS on the process-wide
singletons (other tests in the suite legitimately bump the same
counters).
"""

import json
import math
import sys
import threading
import time
import types

import numpy as np
import pytest

from deepspeed_tpu.telemetry import (DEFAULT_BUCKETS, MetricsRegistry, MonitorBridge, SpanTracer,
                                     get_registry)
from deepspeed_tpu.telemetry.tracing import _NULL_SPAN


# ---------------------------------------------------------------- registry

def test_counter_and_labels():
    reg = MetricsRegistry()
    c = reg.counter("requests_total")
    c.inc()
    c.inc(2.5)
    assert reg.peek("requests_total") == 3.5
    # labeled series are independent; same (name, labels) is the same handle
    a = reg.counter("ops_total", op="all_reduce")
    b = reg.counter("ops_total", op="all_gather")
    assert a is not b
    assert reg.counter("ops_total", op="all_reduce") is a
    a.inc(4)
    assert reg.peek("ops_total", op="all_reduce") == 4
    assert reg.peek("ops_total", op="all_gather") == 0
    assert reg.peek("ops_total", op="broadcast") is None  # peek never creates


def test_gauge_set_inc_dec():
    reg = MetricsRegistry()
    g = reg.gauge("queue_depth")
    g.set(7)
    g.inc()
    g.dec(3)
    assert reg.peek("queue_depth") == 5.0


def test_histogram_bucket_semantics():
    reg = MetricsRegistry()
    h = reg.histogram("latency_seconds", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.1, 0.5, 5.0, 50.0):
        h.observe(v)
    # le-semantics: a value equal to a boundary lands in that bucket
    assert h.cumulative() == [(0.1, 2), (1.0, 3), (10.0, 4), (math.inf, 5)]
    assert h.count == 5
    assert h.sum == pytest.approx(55.65)
    assert reg.peek("latency_seconds") == 5  # histogram peek = count


def test_registry_rejects_conflicts():
    reg = MetricsRegistry()
    reg.counter("a_total")
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("a_total")
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("a_total", op="x")  # kind conflict across label sets too
    reg.histogram("h_seconds", buckets=(1.0, 2.0))
    with pytest.raises(ValueError, match="buckets"):
        reg.histogram("h_seconds", buckets=(1.0, 3.0))
    with pytest.raises(ValueError, match="must match"):
        reg.counter("Bad-Name")
    with pytest.raises(ValueError, match="must match"):
        reg.counter("ok_total", **{"bad-label": "x"})
    with pytest.raises(ValueError, match="increasing"):
        reg.histogram("h2_seconds", buckets=(2.0, 1.0))


def test_disabled_registry_records_nothing():
    reg = MetricsRegistry(enabled=False)
    c = reg.counter("c_total")
    g = reg.gauge("g")
    h = reg.histogram("h_seconds")
    c.inc(100)
    g.set(100)
    h.observe(100)
    assert reg.peek("c_total") == 0
    assert reg.peek("g") == 0
    assert h.count == 0
    # re-enable: the same handles become live (one attribute flip)
    reg.enabled = True
    c.inc()
    assert reg.peek("c_total") == 1


def test_reset_keeps_handles_wired():
    reg = MetricsRegistry()
    c = reg.counter("c_total")
    h = reg.histogram("h_seconds", buckets=(1.0,))
    c.inc(5)
    h.observe(0.5)
    reg.reset()
    assert reg.peek("c_total") == 0
    assert h.count == 0 and h.counts == [0, 0]
    c.inc()          # the pre-reset handle still feeds the registry
    h.observe(2.0)
    assert reg.peek("c_total") == 1
    assert h.cumulative() == [(1.0, 0), (math.inf, 1)]


def test_render_prometheus_golden():
    reg = MetricsRegistry()
    reg.counter("comm_bytes_total", op="all_reduce").inc(1024)
    reg.gauge("kv_block_occupancy").set(0.25)
    h = reg.histogram("step_seconds", buckets=(0.5, 1.0))
    h.observe(0.25)
    h.observe(0.75)
    reg.describe("step_seconds", "wall time per train step")
    help_default = "see docs/OBSERVABILITY.md"
    assert reg.render_prometheus() == (
        f'# HELP comm_bytes_total {help_default}\n'
        '# TYPE comm_bytes_total counter\n'
        'comm_bytes_total{op="all_reduce"} 1024\n'
        f'# HELP kv_block_occupancy {help_default}\n'
        '# TYPE kv_block_occupancy gauge\n'
        'kv_block_occupancy 0.25\n'
        '# HELP step_seconds wall time per train step\n'
        '# TYPE step_seconds histogram\n'
        'step_seconds_bucket{le="0.5"} 1\n'
        'step_seconds_bucket{le="1"} 2\n'
        'step_seconds_bucket{le="+Inf"} 2\n'
        'step_seconds_sum 1\n'
        'step_seconds_count 2\n'
    )


def _parse_prometheus(text):
    """Hold a text exposition to what a scraper depends on: every series has
    a legal name and appears at most once, under a TYPE that follows its
    HELP. Returns ``{family: kind}``."""
    import re

    name_re = re.compile(r"^[a-z_][a-z0-9_]*$")
    seen = set()
    types = {}
    helps = set()
    for line in text.splitlines():
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split(" ")
            assert name_re.match(name), line
            assert name not in types, f"duplicate TYPE line: {line}"
            assert name in helps, f"TYPE without preceding HELP: {line}"
            types[name] = kind
            continue
        if line.startswith("# HELP "):
            name = line.split(" ")[2]
            assert name_re.match(name), line
            helps.add(name)
            continue
        if line.startswith("#"):  # other comments: legal, ignored
            continue
        series, value = line.rsplit(" ", 1)
        float(value)  # every sample value parses
        name = series.split("{", 1)[0]
        bare = re.sub(r"_(bucket|sum|count)$", "", name)
        assert name_re.match(name), line
        assert name in types or bare in types, f"sample without TYPE family: {line}"
        assert series not in seen, f"duplicate series: {line}"
        seen.add(series)
    return types


def test_render_prometheus_parses_clean():
    """Every emitted series must use a legal Prometheus name and appear at
    most once — the properties a scraper actually depends on."""
    reg = MetricsRegistry()
    reg.counter("train_steps_total").inc(3)
    reg.counter("comm_bytes_total", op="all_reduce").inc(1 << 20)
    reg.counter("comm_bytes_total", op="all_gather").inc(7)
    reg.gauge("kv_block_occupancy").set(0.5)
    reg.histogram("infer_ttft_seconds", buckets=(0.1, 1.0)).observe(0.2)
    assert _parse_prometheus(reg.render_prometheus()) == {
        "train_steps_total": "counter", "comm_bytes_total": "counter",
        "kv_block_occupancy": "gauge", "infer_ttft_seconds": "histogram"}


def test_snapshot_is_json_able():
    reg = MetricsRegistry()
    reg.counter("c_total", op="x").inc(2)
    reg.gauge("g").set(1.5)
    reg.histogram("h_seconds").observe(0.01)
    snap = json.loads(json.dumps(reg.snapshot()))
    assert snap["enabled"] is True
    assert snap["counters"] == {'c_total{op="x"}': 2}
    assert snap["gauges"] == {"g": 1.5}
    assert snap["histograms"]["h_seconds"]["count"] == 1
    assert snap["histograms"]["h_seconds"]["buckets"]["+Inf"] == 1


def test_series_flattening():
    reg = MetricsRegistry()
    reg.counter("c_total", op="x").inc(3)
    reg.histogram("h_seconds").observe(2.0)
    got = dict(reg.series())
    assert got == {"c_total.op.x": 3.0, "h_seconds_count": 1.0, "h_seconds_sum": 2.0}


def test_concurrent_creation_single_handle():
    reg = MetricsRegistry()
    out = []

    def make():
        out.append(reg.counter("racy_total"))

    threads = [threading.Thread(target=make) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert all(m is out[0] for m in out)


# ----------------------------------------------------------------- tracing

def test_span_nesting_and_ring_eviction():
    tr = SpanTracer(capacity=3)
    with tr.span("train/step"):
        with tr.span("train/forward", micro=0):
            pass
        with tr.span("train/backward"):
            pass
    spans = tr.spans()
    assert [s["name"] for s in spans] == ["train/forward", "train/backward", "train/step"]
    assert [s["parent"] for s in spans] == [spans[2]["id"], spans[2]["id"], 0]
    assert spans[0]["attrs"] == {"micro": 0}
    assert all(s["dur_s"] >= 0 for s in spans)
    # step started before its children and outlived them
    assert spans[2]["start_s"] <= spans[0]["start_s"]
    assert spans[2]["dur_s"] >= spans[0]["dur_s"]
    with tr.span("extra"):
        pass
    assert [s["name"] for s in tr.spans()] == ["train/backward", "train/step", "extra"]  # ring of 3
    tr.clear()
    assert tr.spans() == []


def test_span_exception_still_recorded():
    tr = SpanTracer()
    with pytest.raises(RuntimeError):
        with tr.span("boom"):
            raise RuntimeError("x")
    assert [s["name"] for s in tr.spans()] == ["boom"]
    # the next span is a root again
    with tr.span("after"):
        pass
    assert tr.spans()[-1]["parent"] == 0


def test_dump_trace_chrome_and_jsonl(tmp_path):
    tr = SpanTracer()
    with tr.span("train/step"):
        with tr.span("train/forward"):
            time.sleep(0.001)
    chrome = tmp_path / "trace.json"
    tr.dump_trace(chrome)
    doc = json.loads(chrome.read_text())
    assert doc["displayTimeUnit"] == "ms"
    events = doc["traceEvents"]
    assert {e["name"] for e in events} == {"train/forward", "train/step"}
    for e in events:
        assert e["ph"] == "X" and e["cat"] == "train" and e["dur"] >= 0
    jsonl = tmp_path / "trace.jsonl"
    tr.dump_trace(jsonl)
    lines = [json.loads(l) for l in jsonl.read_text().splitlines()]
    assert [l["name"] for l in lines] == ["train/forward", "train/step"]


def test_disabled_tracer_allocates_nothing():
    tr = SpanTracer(enabled=False)
    assert tr.span("a") is tr.span("b", k=1) is _NULL_SPAN  # one shared singleton
    if not hasattr(sys, "getallocatedblocks"):
        return
    import gc
    def loop():
        for _ in range(1000):
            with tr.span("x"):
                pass
    loop()  # warm
    gc.collect()
    before = sys.getallocatedblocks()
    loop()
    gc.collect()
    after = sys.getallocatedblocks()
    assert after - before < 50  # interpreter noise only, no per-span allocation
    assert tr.spans() == []


# ------------------------------------------------------------------ bridge

class _FakeMonitor:
    def __init__(self, enabled=True):
        self.enabled = enabled
        self.calls = []

    def write_events(self, events):
        self.calls.append(list(events))


def test_bridge_flush_prefix_and_extras():
    reg = MetricsRegistry()
    reg.counter("train_steps_total").inc(3)
    mon = _FakeMonitor()
    MonitorBridge(reg, mon).maybe_flush(1, extra_events=[("Train/Samples/lr", 0.01, 8)])
    (events,) = mon.calls
    assert ("Train/Samples/lr", 0.01, 8) in events
    assert ("Telemetry/train_steps_total", 3.0, 1) in events


def test_bridge_throttles_and_degrades():
    reg = MetricsRegistry()
    reg.counter("c_total").inc()
    mon = _FakeMonitor()
    bridge = MonitorBridge(reg, mon, every_n_steps=3)
    for step in (1, 2, 3, 4, 5, 6):
        bridge.maybe_flush(step)
    assert len(mon.calls) == 2  # steps 3 and 6
    # disabled registry: extras still flow, registry series do not
    reg.enabled = False
    bridge.flush(7, extra_events=[("Train/Samples/train_loss", 2.0, 7)])
    assert mon.calls[-1] == [("Train/Samples/train_loss", 2.0, 7)]
    # no monitor / disabled monitor: plain no-op
    MonitorBridge(reg, None).maybe_flush(1)
    MonitorBridge(reg, _FakeMonitor(enabled=False)).maybe_flush(1)


# ----------------------------------------------------------------- monitor

def test_csv_monitor_rename_and_alias():
    from deepspeed_tpu.monitor import CsvMonitor, csvMonitor
    assert csvMonitor is CsvMonitor


def test_monitor_master_all_disabled_is_noop():
    from deepspeed_tpu.monitor import MonitorMaster
    off = types.SimpleNamespace(enabled=False)
    cfg = types.SimpleNamespace(tensorboard=off, wandb=off, csv_monitor=off)
    m = MonitorMaster(cfg)
    assert not m.enabled
    m.write_events([("a", 1.0, 0)])  # must not raise


# ---------------------------------------------------------------- watchdog

def test_watchdog_timeout_counts_and_env_default(monkeypatch):
    from deepspeed_tpu.utils.watchdog import default_timeout, run_with_watchdog
    monkeypatch.setenv("DS_TPU_WATCHDOG_TIMEOUT_S", "0.05")
    assert default_timeout() == 0.05
    reg = get_registry()
    before = reg.peek("watchdog_timeouts_total") or 0.0
    status, result = run_with_watchdog(lambda: time.sleep(5))  # env default applies
    assert (status, result) == ("timeout", None)
    assert reg.peek("watchdog_timeouts_total") == before + 1
    # ok / error paths unchanged
    assert run_with_watchdog(lambda: 42, timeout_s=5) == ("ok", 42)
    status, err = run_with_watchdog(lambda: 1 / 0, timeout_s=5)
    assert status == "error" and isinstance(err, ZeroDivisionError)
    monkeypatch.setenv("DS_TPU_WATCHDOG_TIMEOUT_S", "not-a-number")
    assert default_timeout() == 180.0


# ----------------------------------------------------------- compile cache

def test_compile_cache_listener_counts_events():
    import jax

    from jax import monitoring

    from deepspeed_tpu.utils.compile_cache import register_cache_metrics
    assert register_cache_metrics(jax)
    reg = get_registry()
    hits0 = reg.peek("compile_cache_hits_total") or 0.0
    miss0 = reg.peek("compile_cache_misses_total") or 0.0
    monitoring.record_event("/jax/compilation_cache/cache_hits")
    monitoring.record_event("/jax/compilation_cache/cache_misses")
    assert reg.peek("compile_cache_hits_total") == hits0 + 1
    assert reg.peek("compile_cache_misses_total") == miss0 + 1


# ------------------------------------------------------- engine integration

def test_engine_train_step_telemetry(tmp_path):
    """After real train steps: step/microbatch/token counters move, the
    fwd/bwd/step spans have durations, the estimated grad-sync bytes
    count (dp=8 under the fake-device conftest), and the bridge lands
    both Telemetry/* and legacy Train/Samples/* series in CSV files."""
    import jax

    import deepspeed_tpu
    from deepspeed_tpu.models import CausalLM, gpt2_tiny
    from deepspeed_tpu.runtime.dataloader import RepeatingLoader
    from deepspeed_tpu.telemetry import get_tracer

    cfg = {
        "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": 2,
        "optimizer": {"type": "adam", "params": {"lr": 1e-2}},
        "zero_optimization": {"stage": 0},
        "steps_per_print": 100,
        "csv_monitor": {"enabled": True, "output_path": str(tmp_path), "job_name": "tele"},
    }
    model = CausalLM(gpt2_tiny())
    params = model.init(jax.random.PRNGKey(42), {"input_ids": np.zeros((1, 16), dtype=np.int32)})
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params, config=cfg)
    assert engine.monitor is not None and engine.monitor.enabled

    reg = engine.telemetry
    base = {n: reg.peek(n) or 0.0 for n in
            ("train_steps_total", "train_tokens_total")}
    comm_base = reg.peek("comm_bytes_total", op="grad_sync_estimated") or 0.0

    tracer = get_tracer()
    tracer.clear()

    rng = np.random.RandomState(0)
    data = [{"input_ids": rng.randint(0, 1024, size=(16,)).astype(np.int32)} for _ in range(16)]
    it = RepeatingLoader(engine.deepspeed_io(data))
    for _ in range(2):
        loss = engine.train_batch(it)
    assert np.isfinite(float(loss))

    dp = engine.topology.data_parallel_size
    assert reg.peek("train_steps_total") == base["train_steps_total"] + 2
    assert engine.micro_steps == 4 and engine.global_samples == 4 * dp  # micro-batches and samples are the engine's own counts
    assert reg.peek("train_tokens_total") == base["train_tokens_total"] + 4 * dp * 16
    assert (reg.peek("last_step_completed_unix") or 0.0) > 0
    assert (reg.peek("train_loss_scale") or 0.0) >= 1.0
    if dp > 1:
        assert (reg.peek("comm_bytes_total", op="grad_sync_estimated") or 0.0) > comm_base

    names = {s["name"] for s in tracer.spans()}
    assert {"train/forward", "train/backward", "train/step"} <= names
    fwd = [s for s in tracer.spans() if s["name"] == "train/forward"]
    assert len(fwd) >= 4 and all(s["dur_s"] > 0 for s in fwd)

    # bridge -> CsvMonitor: telemetry series and legacy series both land
    job = tmp_path / "tele"
    assert (job / "Telemetry_train_steps_total.csv").exists()
    assert (job / "Train_Samples_lr.csv").exists()
    assert (job / "Train_Samples_train_loss.csv").exists()
    steps_csv = (job / "Telemetry_train_steps_total.csv").read_text().splitlines()
    assert steps_csv[0] == "step,Telemetry_train_steps_total"
    assert float(steps_csv[-1].split(",")[1]) >= 2

    # exporters stay coherent with the live registry
    prom = reg.render_prometheus()
    assert "# TYPE train_steps_total counter" in prom
    assert "comm_bytes_total" in prom
    trace_path = tmp_path / "trace.json"
    tracer.dump_trace(trace_path)
    assert any(e["name"] == "train/step" for e in
               json.loads(trace_path.read_text())["traceEvents"])


# ------------------------------------------------------------- span drops

def test_span_ring_drop_counter():
    """Evicting a span off the trace ring counts into
    telemetry_spans_dropped_total (docs/OBSERVABILITY.md catalog)."""
    reg = MetricsRegistry()
    tracer = SpanTracer(capacity=2, registry=reg)
    for i in range(5):
        with tracer.span(f"s{i}"):
            pass
    assert len(tracer.spans()) == 2
    assert reg.peek("telemetry_spans_dropped_total") == 3


# -------------------------------------------------------------- event log

def _mk_event_log(capacity=64):
    from deepspeed_tpu.telemetry import EventLog
    reg = MetricsRegistry()
    return EventLog(capacity=capacity, registry=reg), reg


def test_event_log_ring_bounds_and_counters():
    ev, reg = _mk_event_log(capacity=4)
    for i in range(6):
        ev.emit("decode", i, k=1)
    assert len(ev) == 4
    assert [e["uid"] for e in ev.events()] == [2, 3, 4, 5]  # oldest evicted
    assert reg.peek("telemetry_events_total") == 6
    assert reg.peek("telemetry_events_dropped_total") == 2


def test_event_log_disabled_records_nothing():
    ev, reg = _mk_event_log()
    ev.enabled = False
    ev.emit("enqueue", 1)
    assert len(ev) == 0 and reg.peek("telemetry_events_total") == 0


def test_event_log_filters_and_explicit_ts():
    ev, _ = _mk_event_log()
    ev.emit("enqueue", 7, ts=1.25, prompt=4)
    ev.emit("admit", 7, ts=1.5, hit=0)
    ev.emit("enqueue", 8, ts=2.0)
    assert [e["kind"] for e in ev.events(uid=7)] == ["enqueue", "admit"]
    assert [e["uid"] for e in ev.events(kind="enqueue")] == [7, 8]
    assert ev.events(uid=7)[0]["ts"] == 1.25  # explicit ts wins over the clock


def test_event_log_jsonl_sink(tmp_path):
    ev, _ = _mk_event_log()
    path = tmp_path / "events.jsonl"
    ev.open_sink(str(path))
    for i in range(10):
        ev.emit("decode", i, k=2)
    ev.close_sink()
    lines = [json.loads(l) for l in path.read_text().splitlines()]
    assert [e["uid"] for e in lines] == list(range(10))
    assert all(e["kind"] == "decode" and e["k"] == 2 for e in lines)


def test_event_log_listener_and_exception_isolation():
    ev, _ = _mk_event_log()
    got = []
    ev.add_listener(lambda ts, kind, uid, attrs: got.append((kind, uid, attrs)))
    ev.add_listener(lambda *a: 1 / 0)  # broken listener must be swallowed
    ev.emit("admit", 3, hit=8)
    assert got == [("admit", 3, {"hit": 8})]


# ------------------------------------------------------ timeline derivation

def _synthetic_request(uid, t0, hit=0, chunks=(4,), n_new=3, k_per_decode=1):
    """One well-formed lifecycle as raw event dicts."""
    evs = [{"ts": t0, "kind": "enqueue", "uid": uid, "prompt": sum(chunks)}]
    t = t0 + 0.01
    evs.append({"ts": t, "kind": "admit", "uid": uid, "hit": hit})
    for c in chunks:
        t += 0.01
        evs.append({"ts": t, "kind": "prefill_chunk", "uid": uid, "q": 1, "tokens": c})
    t += 0.01
    evs.append({"ts": t, "kind": "first_token", "uid": uid})
    for _ in range((n_new - 1) // k_per_decode):
        t += 0.01
        evs.append({"ts": t, "kind": "decode", "uid": uid, "q": 2, "k": k_per_decode})
    t += 0.01
    evs.append({"ts": t, "kind": "finish", "uid": uid, "n_new": n_new})
    return evs


def test_request_timelines_uid_reuse_and_orphans():
    from deepspeed_tpu.telemetry import request_timelines
    evs = _synthetic_request(0, 1.0) + _synthetic_request(0, 2.0)
    evs.append({"ts": 3.0, "kind": "decode", "uid": 99, "k": 1})  # no enqueue: orphan
    evs.append({"ts": 3.0, "kind": "evict", "uid": -1, "blocks": 2})  # global record
    tls = request_timelines(evs)
    assert set(tls) == {0} and len(tls[0]) == 2  # one timeline per enqueue
    from deepspeed_tpu.telemetry import validate_timeline
    assert validate_timeline(tls[0][0]) == [] and validate_timeline(tls[0][1]) == []


def test_validate_timeline_catches_malformations():
    from deepspeed_tpu.telemetry import validate_timeline
    good = _synthetic_request(1, 0.0)
    assert validate_timeline(good) == []
    assert "missing 'finish'" in validate_timeline(good[:-1])[0]
    bad_order = [good[0], good[3], good[1]]  # admit after first_token, ts regression
    assert any("regression" in p for p in validate_timeline(bad_order))
    no_enq = good[1:]
    assert any("enqueue" in p for p in validate_timeline(no_enq))


def test_lifecycle_signature_merges_bursts():
    """A fused 4-token burst and 4 single decode steps must produce the
    SAME signature — the fused/unfused parity invariant rides on this."""
    from deepspeed_tpu.telemetry import lifecycle_signature
    single = _synthetic_request(0, 0.0, chunks=(4,), n_new=5, k_per_decode=1)
    burst = _synthetic_request(0, 9.0, chunks=(4,), n_new=5, k_per_decode=4)
    sig = lifecycle_signature(single)
    assert sig == lifecycle_signature(burst)
    assert sig == (("enqueue",), ("admit", 0), ("prefill_chunk", 4),
                   ("first_token",), ("decode", 4), ("finish",))


def test_request_metrics_and_latency_summary():
    from deepspeed_tpu.telemetry import latency_summary, request_metrics
    tl = _synthetic_request(5, 10.0, chunks=(4, 4), n_new=3)
    m = request_metrics(tl)
    assert m["queue_s"] == pytest.approx(0.01)
    assert m["ttft_s"] == pytest.approx(0.04)
    assert m["prefill_s"] == pytest.approx(0.03)
    assert m["decode_s"] == pytest.approx(0.03)
    assert m["tpot_s"] == pytest.approx(0.015)
    assert m["total_s"] == pytest.approx(m["queue_s"] + m["prefill_s"] + m["decode_s"])
    assert request_metrics(tl[:-1]) is None  # incomplete -> None, not garbage
    evs = _synthetic_request(0, 0.0) + _synthetic_request(1, 0.5) + [
        {"ts": 9.0, "kind": "enqueue", "uid": 2, "prompt": 4}]  # never finishes
    s = latency_summary(evs)
    assert s["n_requests"] == 3.0 and s["n_complete"] == 2.0
    assert s["ttft_p50_s"] == pytest.approx(0.03)  # single-chunk requests: first at t0+0.03
    assert 0.0 < s["queue_time_fraction"] < 1.0


def test_latency_summary_empty_stream():
    """The bench rungs call latency_summary unconditionally; an empty
    event window must yield zeros, not NaNs or IndexErrors."""
    from deepspeed_tpu.telemetry import latency_summary
    s = latency_summary([])
    assert s["n_requests"] == 0.0 and s["n_complete"] == 0.0
    assert s["ttft_p50_s"] == 0.0 and s["ttft_p99_s"] == 0.0
    assert s["tpot_p50_s"] == 0.0 and s["tpot_p99_s"] == 0.0
    assert s["queue_time_fraction"] == 0.0


def test_latency_summary_single_request():
    """One complete request: every percentile collapses to its sample,
    and a one-token finish contributes no TPOT sample (not a div-by-zero)."""
    from deepspeed_tpu.telemetry import latency_summary

    def stream(n_new):
        return [
            {"kind": "enqueue", "uid": 1, "ts": 0.0},
            {"kind": "admit", "uid": 1, "ts": 0.1},
            {"kind": "first_token", "uid": 1, "ts": 0.3},
            {"kind": "finish", "uid": 1, "ts": 0.5, "n_new": n_new},
        ]

    s = latency_summary(stream(3))
    assert s["n_requests"] == 1.0 and s["n_complete"] == 1.0
    assert s["ttft_p50_s"] == pytest.approx(0.3)
    assert s["ttft_p99_s"] == pytest.approx(0.3)  # singleton: p99 == p50
    assert s["tpot_p50_s"] == pytest.approx(0.2 / 2)  # (finish-first)/(n_new-1)
    assert s["queue_time_fraction"] == pytest.approx(0.1 / 0.5)
    # n_new == 1: TTFT is the whole story, TPOT has no samples
    s1 = latency_summary(stream(1))
    assert s1["n_complete"] == 1.0
    assert s1["tpot_p50_s"] == 0.0 and s1["tpot_p99_s"] == 0.0


# --------------------------------------------------------------- detectors

def test_nonfinite_loss_detector_latch_and_cooldown():
    from deepspeed_tpu.telemetry import NonFiniteLossDetector
    d = NonFiniteLossDetector(cooldown_s=3600.0)
    assert d.observe(1.0) is None
    alert = d.observe(float("nan"))
    assert alert is not None and alert.detector == "nan_loss"
    # latched: persistent NaN raises exactly one alert
    assert all(d.observe(float("nan")) is None for _ in range(50))
    # a finite loss re-arms, but cooldown suppresses an immediate refire
    assert d.observe(2.0) is None
    assert d.observe(float("inf")) is None  # within cooldown
    d.reset()
    assert d.observe(float("inf")) is not None  # reset clears the cooldown


def test_nonfinite_loss_detector_zero_cooldown_refires():
    from deepspeed_tpu.telemetry import NonFiniteLossDetector
    d = NonFiniteLossDetector(cooldown_s=0.0)
    assert d.observe(float("nan")) is not None
    assert d.observe(1.0) is None
    assert d.observe(float("nan")) is not None  # new episode, no cooldown


def test_grad_norm_spike_detector_threshold_and_hysteresis():
    from deepspeed_tpu.telemetry import GradNormSpikeDetector
    d = GradNormSpikeDetector(spike_ratio=10.0, warmup=4, cooldown_s=0.0)
    for _ in range(6):
        assert d.observe(1.0) is None  # builds the EMA baseline
    ema_before = d._ema
    alert = d.observe(100.0)
    assert alert is not None and alert.attrs["ratio"] == pytest.approx(100.0, rel=0.1)
    assert d._ema == ema_before  # spike excluded from the EMA
    assert d.observe(100.0) is None  # latched while still spiking
    assert d.observe(1.0) is None    # recovery re-arms
    assert d.observe(100.0) is not None  # next spike is a new episode
    assert d.observe(float("nan")) is None  # latched again; non-finite path


def test_grad_norm_spike_detector_warmup_suppresses():
    from deepspeed_tpu.telemetry import GradNormSpikeDetector
    d = GradNormSpikeDetector(spike_ratio=10.0, warmup=8, cooldown_s=0.0)
    assert d.observe(1.0) is None
    assert d.observe(50.0) is None  # only 1 sample seen: still warming up


def test_queue_stall_detector_event_feed_and_poll():
    from deepspeed_tpu.telemetry import QueueStallDetector
    d = QueueStallDetector(stall_s=0.05, cooldown_s=0.0)
    assert d.poll(now=100.0) is None  # idle queue never stalls
    d.on_event(100.0, "enqueue", 1, {})
    d.on_event(100.0, "enqueue", 2, {})
    assert d.stalled_for(now=100.04) == pytest.approx(0.04)
    assert d.poll(now=100.04) is None  # under threshold
    alert = d.poll(now=100.2)
    assert alert is not None and alert.attrs["pending"] == 2
    assert d.poll(now=100.3) is None  # latched
    d.on_event(100.35, "admit", 1, {})  # progress re-arms
    assert d.poll(now=100.36) is None  # clock restarted from the admit
    assert d.poll(now=100.5) is not None  # uid 2 still waiting -> new episode


def test_slo_burn_detector_window_and_rearm():
    from deepspeed_tpu.telemetry import SLOBurnRateDetector
    d = SLOBurnRateDetector(ttft_sla_s=1.0, tpot_sla_s=0.25, window=8,
                            burn_threshold=0.5, min_count=4, cooldown_s=0.0)
    assert d.observe(5.0, 5.0) is None  # below min_count: no verdict yet
    assert d.observe(5.0, 5.0) is None
    assert d.observe(5.0, 5.0) is None
    alert = d.observe(5.0, 5.0)
    assert alert is not None and alert.attrs["burn_rate"] == 1.0
    assert d.observe(5.0, 5.0) is None  # latched
    for _ in range(8):
        d.observe(0.1, 0.01)  # healthy requests flush the window
    assert not d.firing  # re-armed at low burn rate
    for _ in range(8):
        alert = d.observe(9.0, 9.0) or alert
    assert alert.attrs["burn_rate"] >= 0.5  # fires again on the next burn


# ---------------------------------------------------------- health monitor

def _mk_monitor():
    from deepspeed_tpu.telemetry import CallbackAlertSink, EventLog, HealthMonitor
    reg = MetricsRegistry()
    ev = EventLog(registry=reg)
    got = []
    hm = HealthMonitor(registry=reg, event_log=ev,
                       sinks=[CallbackAlertSink(got.append)])
    ev.add_listener(hm.on_event)
    return hm, reg, ev, got


def test_health_monitor_nan_loss_exactly_one_alert():
    from deepspeed_tpu.telemetry import NonFiniteLossDetector
    hm, reg, ev, got = _mk_monitor()
    hm.ensure_detector(NonFiniteLossDetector(cooldown_s=0.0))
    assert reg.peek("health_status") == 1.0 and hm.healthy
    for _ in range(20):
        hm.observe_loss(float("nan"))
    assert len(got) == 1 and got[0].detector == "nan_loss"
    assert reg.peek("health_status") == 0.0 and not hm.healthy
    assert reg.peek("health_alerts_total", detector="nan_loss") == 1
    # the alert also lands in the event log as a structured record
    assert [e["detector"] for e in ev.events(kind="alert")] == ["nan_loss"]
    hm.observe_loss(0.5)  # recovery re-arms and restores the gauge
    assert reg.peek("health_status") == 1.0 and hm.healthy


def test_health_monitor_queue_stall_exactly_one_alert():
    from deepspeed_tpu.telemetry import QueueStallDetector
    hm, reg, ev, got = _mk_monitor()
    hm.ensure_detector(QueueStallDetector(stall_s=0.03, cooldown_s=0.0))
    ev.emit("enqueue", 1, ts=50.0, prompt=4)  # listener feeds the detector
    for now in (50.1, 50.2, 50.3):
        hm.poll(now=now)
    assert len(got) == 1 and got[0].detector == "queue_stall"
    assert reg.peek("health_status") == 0.0
    ev.emit("admit", 1, ts=50.4, hit=0)
    hm.poll(now=50.41)
    assert reg.peek("health_status") == 1.0 and hm.healthy


def test_health_monitor_external_alert_and_sink_isolation():
    from deepspeed_tpu.telemetry import CallbackAlertSink
    hm, reg, ev, got = _mk_monitor()
    hm.add_sink(CallbackAlertSink(lambda a: 1 / 0))  # broken sink: swallowed
    hm.raise_alert("dataloader", "shard unreadable", severity="error", shard=3)
    assert len(got) == 1 and got[0].attrs == {"shard": 3}
    assert not hm.healthy
    hm.resolve("dataloader")
    assert hm.healthy
    hm.raise_alert("x", "y")
    hm.reset()
    assert hm.healthy and hm.alerts() == []


def test_health_monitor_ensure_detector_idempotent():
    from deepspeed_tpu.telemetry import NonFiniteLossDetector
    hm, _, _, _ = _mk_monitor()
    first = hm.ensure_detector(NonFiniteLossDetector())
    second = hm.ensure_detector(NonFiniteLossDetector())
    assert first is second  # repeated engine construction keeps one state


def test_jsonl_alert_sink(tmp_path):
    from deepspeed_tpu.telemetry import Alert, JsonlAlertSink
    path = tmp_path / "alerts.jsonl"
    sink = JsonlAlertSink(str(path))
    sink(Alert(detector="d1", severity="error", message="m", attrs={"k": 1}))
    sink(Alert(detector="d2", severity="warning", message="n"))
    recs = [json.loads(l) for l in path.read_text().splitlines()]
    assert [r["detector"] for r in recs] == ["d1", "d2"]
    assert recs[0]["k"] == 1 and recs[0]["severity"] == "error"


def test_watchdog_timeout_raises_structured_alert():
    """A wedged call trips the watchdog with a structured health alert
    (not just a bare counter): docs/OBSERVABILITY.md health section."""
    from deepspeed_tpu.telemetry import get_health_monitor
    from deepspeed_tpu.utils.watchdog import run_with_watchdog
    hm = get_health_monitor()
    hm.reset()
    hm.resolve("watchdog_timeout")
    n0 = len([a for a in hm.alerts() if a.detector == "watchdog_timeout"])
    status, _ = run_with_watchdog(lambda: time.sleep(5), timeout_s=0.05)
    assert status == "timeout"
    alerts = [a for a in hm.alerts() if a.detector == "watchdog_timeout"]
    assert len(alerts) == n0 + 1
    assert alerts[-1].attrs["timeout_s"] == pytest.approx(0.05)
    assert not hm.healthy  # external alert holds status at 0 until resolved
    hm.resolve("watchdog_timeout")
    hm.reset()
    assert hm.healthy


# ----------------------------------------------------------- doc drift

_METRIC_PREFIXES = ("train_", "comm_", "infer_", "kv_", "sched_", "spec_",
                    "compile_cache_", "watchdog_", "telemetry_", "health_",
                    "journal_", "replay_", "autotune_", "program_",
                    "paged_attention_")
# profile_* metrics are listed explicitly: a bare "profile_" prefix would
# also match the `profile_captures` knob-default directory name in docs
_EXTRA_METRICS = {"last_step_completed_unix", "tp_degree", "sparse_keys_chosen_total", "sparse_keys_visible_total", "sparse_index_loss",
                  "moe_rows_routed_here_total", "moe_rows_dropped_total", "moe_expert_rows_max", "moe_expert_rows_min",
                  "moe_fallback_layers_total", "moe_buffer_rung_layers_total", "moe_rows_over_uniform_max", "moe_rows_sent_total", "moe_chip_rows_max", "moe_chip_rows_min",
                  "diffusion_masked_positions_total", "diffusion_positions_total", "diffusion_weight_sum",
                  "engine_init_seconds_total", "import_seconds",
                  "profile_captures_total", "profile_captures_dropped_total",
                  "profile_collective_exposed_fraction",
                  "profile_device_busy_fraction",
                  "profile_host_gap_fraction"}


def test_metric_catalog_matches_docs():
    """Doc-drift guard: every metric name registered by package code must
    appear in docs/OBSERVABILITY.md's catalog, and every catalog name must
    exist in code — a rename or addition that skips the docs fails here."""
    import pathlib
    import re

    root = pathlib.Path(__file__).resolve().parents[2]
    pkg = root / "deepspeed_tpu"
    code_names = set()
    call_re = re.compile(r'\.(?:counter|gauge|histogram)\(\s*"([a-z0-9_]+)"')
    for py in pkg.rglob("*.py"):
        code_names |= set(call_re.findall(py.read_text()))
    assert code_names, "metric scan found nothing — pattern rotted?"

    doc = (root / "docs" / "OBSERVABILITY.md").read_text()
    doc_names = {m for m in re.findall(r"`([a-z][a-z0-9_]*)[`{]", doc)
                 if m.startswith(_METRIC_PREFIXES) or m in _EXTRA_METRICS}

    undocumented = code_names - doc_names
    assert not undocumented, f"metrics registered in code but absent from docs/OBSERVABILITY.md: {sorted(undocumented)}"
    phantom = doc_names - code_names
    assert not phantom, f"metrics documented but not registered anywhere in code: {sorted(phantom)}"


# ----------------------------------------- instrumentation left switched on
# What a hot loop leaves behind in each piece of instrumentation that is on
# but has nothing to do. What the pieces cost is measured on the chip
# (PERF.md); a CPU loop's timing is not asserted anywhere.

def _ring_only_event_log(tmp_path, monkeypatch):
    from deepspeed_tpu.telemetry import EventLog
    monkeypatch.chdir(tmp_path)
    ev = EventLog(capacity=4096, registry=MetricsRegistry())
    for i in range(2000):  # the two events a decode dispatch and its commit emit
        ev.emit("decode", i, q=1, k=1)
        ev.emit("finish", i, n_new=4)
    assert len(ev) == 4000
    assert [e["kind"] for e in ev.events(uid=1999)] == ["decode", "finish"]
    # no sink was asked for: no drain thread, and no file anywhere
    assert ev._thread is None and ev._queue is None
    assert list(tmp_path.iterdir()) == []


def _profiler_idle_after_finish(tmp_path, monkeypatch):
    from deepspeed_tpu.telemetry import profiler
    profiler._reset_for_tests()
    try:
        prof, armed = profiler.request_capture(quanta=1)
        assert armed
        assert prof.finish() is None  # armed -> idle, and no trace was started
        assert prof.state == "idle"
        for i in range(2000):  # the hook a fused quantum's dispatch calls
            profiler.note_quantum("fused_step", rows=8, tokens=i)
        status = prof.status()
        assert status["state"] == "idle" and status["n_markers"] == 0
        assert status["captures"] == 0
    finally:
        profiler._reset_for_tests()


def _journal_reads_back_every_record(tmp_path, monkeypatch):
    from deepspeed_tpu.telemetry.journal import Journal, read_journal
    reg = MetricsRegistry()
    path = tmp_path / "journal.jsonl"
    journal = Journal(str(path), registry=reg)
    journal.begin_session({}, kind="generate")
    n = 2000  # far more lines than one write buffer holds
    for i in range(n):  # what one decode quantum and its commit write
        journal.record_quantum(i, [i % 8], [])
        journal.record_commit(i % 8, i, [42])
    journal.close()
    (session,) = read_journal(str(path))
    assert [q["q"] for q in session.quanta] == list(range(n))
    assert len(session.commits) == n
    assert {u: len(t) for u, t in session.tokens_by_uid().items()} == {u: n // 8 for u in range(8)}
    assert reg.peek("journal_records_total") == 2 * n + 2  # and the session's two ends
    assert reg.peek("journal_bytes_total") == path.stat().st_size


def _ops_plane_scrape_of_a_populated_registry(tmp_path, monkeypatch):
    import deepspeed_tpu.telemetry.registry as registry_mod
    from deepspeed_tpu.telemetry.ops_plane import OpsPlane
    reg = MetricsRegistry()
    for i in range(64):  # the series mix a serving engine accumulates
        reg.counter("infer_requests_total", model=f"m{i % 4}").inc(i)
        reg.gauge("kv_block_occupancy", pool=f"p{i % 8}").set(i / 64)
        reg.histogram("infer_ttft_seconds", buckets=(0.01, 0.1, 1.0),
                      model=f"m{i % 4}").observe(0.02 * (i % 5 + 1))
    monkeypatch.setattr(registry_mod, "get_registry", lambda: reg)
    plane = OpsPlane()
    for _ in range(2):  # a scrape changes nothing: the second reads the same
        status, ctype, body = plane.handle("GET", "/metrics")
        assert status == 200 and ctype.startswith("text/plain")
        assert _parse_prometheus(body.decode()) == {
            "infer_requests_total": "counter", "kv_block_occupancy": "gauge",
            "infer_ttft_seconds": "histogram"}
    assert reg.peek("infer_requests_total", model="m3") == sum(range(3, 64, 4))


@pytest.mark.parametrize("case", [_ring_only_event_log, _profiler_idle_after_finish,
                                  _journal_reads_back_every_record,
                                  _ops_plane_scrape_of_a_populated_registry],
                         ids=lambda f: f.__name__.lstrip("_"))
def test_instrumentation_left_on_keeps_its_state(case, tmp_path, monkeypatch):
    case(tmp_path, monkeypatch)
