"""A stalled training step's name (docs/OBSERVABILITY.md, "Span convention",
"Health monitor", "Device timeline"): a span's phases, the stall detector,
the engine's alert and its split, and the capture that hunts for a stall, on a
recorded trace in the plain form of ``tests/benchmarks/recorded_trace_v5e.json``
made into the ``.xplane.pb`` a session leaves."""

import dataclasses
import glob
import os
import time

import jax
import numpy as np
import pytest

from deepspeed_tpu.telemetry import MetricsRegistry, SpanTracer, StepStallDetector, get_health_monitor, profiler, self_times, tracing
from deepspeed_tpu.telemetry.health import STALL_KEEP, STALL_WARMUP, STALL_X, HealthMonitor
from tests.unit.test_span_tree import _host_events

MS = 1e6  # a recorded trace's times are nanoseconds


# ------------------------------------------------------------------- phases

def test_a_phase_adds_its_seconds_to_the_span_and_no_record_to_the_ring():
    plain, phased = SpanTracer(), SpanTracer()
    for tr, phases in ((plain, False), (phased, True)):
        with tr.span("train/forward") as sp:
            with tr.span("program/first_call"):
                time.sleep(0.002)
            for name in ("put_batch", "dispatch", "dispatch") if phases else ():
                with sp.phase(name):
                    time.sleep(0.001 if name == "put_batch" else 0.004)
    (first, fwd), (_, fwd_plain) = phased.spans(), plain.spans()
    assert [s["name"] for s in phased.spans()] == [s["name"] for s in plain.spans()] == ["program/first_call", "train/forward"]
    assert set(fwd["attrs"]["phase_s"]) == {"put_batch", "dispatch"} and fwd_plain["attrs"] == {}
    assert fwd["attrs"]["phase_s"]["dispatch"] >= 0.008 and fwd["attrs"]["phase_s"]["put_batch"] >= 0.001  # a phase entered twice adds up
    assert self_times(phased.spans())[fwd["id"]] == pytest.approx(fwd["dur_s"] - first["dur_s"])  # the phases took nothing off it
    with tracing._NULL_SPAN.phase("put_batch") as nothing:  # the disabled path takes the same call
        assert nothing is tracing._NULL_SPAN
    assert tracing.open_span("train/forward") is tracing._NULL_SPAN


def test_a_phase_is_on_a_profiler_sessions_host_lane_under_the_spans_name(tmp_path):
    tr = SpanTracer()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with tr.span("train/forward") as sp:
            with sp.phase("put_batch"):
                time.sleep(0.001)
    finally:
        jax.profiler.stop_trace()
    events = {e[0]: e for e in _host_events(tmp_path)}
    outer, inner = events["train/forward"], events["train/forward/put_batch"]
    assert outer[1] <= inner[1] and inner[2] <= outer[2] and inner[2] - inner[1] >= 1e6  # same clock, nested
    assert profiler.HOST_SPAN.match("train/forward/put_batch")  # so the idle gaps' readers name it as the innermost


# ----------------------------------------------------------------- detector

def _detector():
    reg = MetricsRegistry()
    return StepStallDetector(registry=reg), reg


@pytest.mark.parametrize("case", ["one_of_forty", "a_first_call_step", "among_the_first_eight", "a_slow_drift", "a_new_regime"])
def test_the_stall_detector(case):
    det, reg = _detector()
    alerts = []
    feed = lambda period, **kw: alerts.append(det.observe(period, **kw))
    if case == "one_of_forty":
        for i in range(40):
            feed(0.5 if i == 30 else 0.1, step=i, split=lambda: {"forward.put_batch": 0.39, "outside_s": 0.01, "first_calls": 0})
        (alert,) = [a for a in alerts if a]
        assert reg.peek("train_step_stalls_total") == 1 and reg.peek("train_step_stall_seconds_total") == pytest.approx(0.4)
        assert reg.peek("train_step_period_median_seconds") == pytest.approx(0.1)
        assert (alert.attrs["step"], alert.attrs["period_s"], alert.attrs["median_s"]) == (30, 0.5, 0.1)
        assert alert.attrs["split"]["forward.put_batch"] == 0.39 and "forward.put_batch" in alert.message
        assert not det.firing  # the usual period after it re-armed
    elif case == "a_first_call_step":
        for i in range(40):
            feed(5.0 if i == 30 else 0.1, passed_by=i == 30)
        assert not any(alerts) and reg.peek("train_step_stalls_total") == 0 and 5.0 not in det._kept
    elif case == "among_the_first_eight":
        for i in range(2 * STALL_WARMUP + 20):  # the first eight are passed by, the next eight kept and not judged
            feed(0.5 if i in (STALL_WARMUP - 1, 2 * STALL_WARMUP - 1) else 0.004 if i < STALL_WARMUP else 0.1)
        assert not any(alerts) and reg.peek("train_step_stalls_total") == 0  # 0 from construction, and still
        assert 0.004 not in det._kept and reg.peek("train_step_period_median_seconds") == pytest.approx(0.1)  # a pipeline's filling is not the usual
    elif case == "a_slow_drift":
        for i in range(200):
            feed(0.1 * 1.01 ** i)  # 7.3 x over the run, a hundredth a step
        assert not any(alerts) and reg.peek("train_step_stalls_total") == 0
        assert reg.peek("train_step_period_median_seconds") > 0.5
    else:  # every step twice as long from one step on: counted until the median has followed, and ONE alert
        for i in range(80):
            feed(0.1 if i < 40 else 0.2)
        assert len([a for a in alerts if a]) == 1 and reg.peek("train_step_stalls_total") == STALL_KEEP // 2
        assert reg.peek("train_step_period_median_seconds") == pytest.approx(0.2) and 0.2 <= STALL_X * 0.2


def test_the_monitor_delivers_a_stall_like_any_alert():
    reg, seen = MetricsRegistry(), []
    mon = HealthMonitor(registry=reg, sinks=[seen.append])
    mon.observe_step_period(1.0)  # no detector registered: nothing
    mon.ensure_detector(StepStallDetector(registry=reg))
    for i in range(30):
        mon.observe_step_period(0.7 if i == 25 else 0.1, step=i)
    assert [a.detector for a in seen] == ["step_stall"] and mon.healthy
    assert reg.peek("health_alerts_total", detector="step_stall") == 1


# ------------------------------------------------------------------- engine

@pytest.fixture(scope="module")
def engine():
    import deepspeed_tpu
    from deepspeed_tpu.models import CausalLM, gpt2_tiny
    from deepspeed_tpu.parallel.mesh import initialize_mesh
    from deepspeed_tpu.runtime.config import MeshConfig

    model = CausalLM(dataclasses.replace(gpt2_tiny(), vocab_size=256))
    params = model.init(jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 16), np.int32)})
    topo = initialize_mesh(MeshConfig.from_dict({}), devices=jax.devices()[:1], force=True)
    eng, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params, mesh=topo, config={
        "train_micro_batch_size_per_gpu": 2, "optimizer": {"type": "adam", "params": {"lr": 1e-3}}, "steps_per_print": 10 ** 6})
    return eng


@pytest.mark.parametrize("where", ["put_batch", "the_callers_loop"])
def test_the_engine_counts_a_stalled_step_and_says_where_it_was(engine, where, monkeypatch):
    batch = {"input_ids": np.random.RandomState(0).randint(0, 256, (2, 16)).astype(np.int32)}
    health, reg = get_health_monitor(), engine.telemetry
    health.detector("step_stall").reset()
    put, n_alerts = engine._put_batch, len(health.alerts())
    base = {k: reg.peek(k) for k in ("train_step_stalls_total", "train_step_stall_seconds_total")}
    assert None not in base.values()  # created with the engine: a clean run reads 0, not "no series"
    step = [0]
    monkeypatch.setattr(engine, "_put_batch", lambda b: (time.sleep(0.3 if (where, step[0]) == ("put_batch", 21) else 0.0), put(b))[1])
    for step[0] in range(24):
        loss = engine.forward(batch)
        engine.backward(loss)
        engine.step()
        time.sleep(0.3 if (where, step[0]) == ("the_callers_loop", 20) else 0.04)  # the caller's own share of a period
    # a loaded machine may stretch another period past 60 ms: the injected one is the longest
    alert = max((a for a in health.alerts()[n_alerts:] if a.detector == "step_stall"), key=lambda a: a.attrs["period_s"])
    split = alert.attrs["split"]
    assert reg.peek("train_step_stalls_total") >= base["train_step_stalls_total"] + 1
    assert reg.peek("train_step_stall_seconds_total") - base["train_step_stall_seconds_total"] >= 0.25
    blamed = "forward.put_batch" if where == "put_batch" else "outside_s"
    assert max((k for k in split if k != "first_calls"), key=split.get) == blamed and split[blamed] == pytest.approx(0.3, abs=0.06)
    assert split["first_calls"] == 0 and {"forward.dispatch", "forward.self", "backward", "step.self"} <= set(split)
    assert sum(v for k, v in split.items() if k != "first_calls") == pytest.approx(alert.attrs["period_s"], abs=2e-3)
    assert blamed in alert.message and alert.attrs["period_s"] > STALL_X * alert.attrs["median_s"]


def test_the_report_and_the_monitors_read_of_a_loss_are_phases_of_train_step(engine, monkeypatch):
    from deepspeed_tpu.telemetry import get_tracer

    batch = {"input_ids": np.zeros((2, 16), np.int32)}
    monkeypatch.setattr(engine.config, "steps_per_print", 1)
    for _ in range(3):  # the third step of this shape makes no first call (the second makes ``overflow_sum``'s: a ``program/first_call`` span)
        get_tracer().clear()
        engine.backward(engine.forward(batch))
        engine.step()
    fwd, bwd, step = [s for s in get_tracer().spans() if s["name"].startswith("train/")]
    assert set(fwd["attrs"]["phase_s"]) == {"put_batch", "dispatch"} and bwd["attrs"] == {}  # this model reports no device counts
    assert "report" in step["attrs"]["phase_s"] and "apply" not in step["attrs"]["phase_s"]  # the fused step applied its update in forward
    assert len(get_tracer().spans()) == 3  # no record for a phase


# ----------------------------------------------------------------- the hunt

def _op(name, opcode="fusion"):
    return f"%{name} = bf16[8,128]{{1,0:T(8,128)(2,1)}} {opcode}(bf16[8,128]{{1,0:T(8,128)(2,1)}} %p.1)"


def recorded(stalled: bool):
    """Six steps of 100 ms: 95 of a fusion, 2 of a copy, 3 idle. With
    ``stalled`` the fourth is 400: its copy takes 40, then the device is idle
    for 262 under the caller's wait, while a thread of the runtime waits on a
    buffer; a poll of that thread in the first step lies over no idle stretch."""
    edges = [0, 100, 200, 300, 700 if stalled else 400]
    edges += [edges[-1] + 100, edges[-1] + 200]
    ops, python, runtime = [], [], [["Poll", 50 * MS, 1 * MS, {}]]
    for k, (lo, hi) in enumerate(zip(edges, edges[1:])):
        long = hi - lo > 100
        ops += [[_op("fusion.1"), (lo + 3) * MS, 95 * MS, {}], [_op("copy.2", "copy"), (lo + 98) * MS, (40 if long else 2) * MS, {}]]
        python += [["bench/train_step", lo * MS, 4 * MS, {}], ["train/forward", (lo + 1) * MS, 2 * MS, {}],
                   ["train/forward/dispatch", (lo + 1.5) * MS, 1 * MS, {}], ["profile/quantum", hi * MS - 1000, 1000, {}]]
        if long:
            python.append(["bench/wait_loss", (lo + 140) * MS, 260 * MS, {}])
            runtime.append(["PjRtCApiBuffer::Await", (lo + 141) * MS, 258 * MS, {}])
    return {"planes": [{"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": ops}]},
                       {"name": "/host:CPU", "lines": [{"name": "python3", "events": python}, {"name": "tpu-runtime/17", "events": runtime}]}]}


def _land(trace, trace_dir):
    """A trace in the plain form as the ``.xplane.pb`` a session would leave."""
    from jax.profiler import ProfileData

    text = []
    for p, plane in enumerate(trace["planes"]):
        names = sorted({ev[0] for line in plane["lines"] for ev in line["events"]})
        lines = "".join(f'lines {{ id: {i + 1} name: "{line["name"]}" timestamp_ns: 0 '
                        + "".join(f"events {{ metadata_id: {names.index(n) + 1} offset_ps: {int(s * 1000)} duration_ps: {int(d * 1000)} }} "
                                  for n, s, d, _ in line["events"]) + "} " for i, line in enumerate(plane["lines"]))
        meta = "".join(f'event_metadata {{ key: {i + 1} value {{ id: {i + 1} name: "{n}" }} }} ' for i, n in enumerate(names))
        text.append(f'planes {{ id: {p + 1} name: "{plane["name"]}" {lines}{meta}}}')
    dst = os.path.join(trace_dir, "plugins", "profile", "2026_10_01")
    os.makedirs(dst, exist_ok=True)
    with open(os.path.join(dst, "host.xplane.pb"), "wb") as out:
        out.write(ProfileData.text_proto_to_serialized_xspace("\n".join(text)))


def test_a_clean_trace_keeps_no_event_of_the_runtimes_and_a_stalled_one_those_over_the_gap(tmp_path):
    for stalled in (False, True):
        _land(recorded(stalled), str(tmp_path / str(stalled)))
        trace = profiler.load_xplane(profiler.find_xplane(str(tmp_path / str(stalled))))
        runtime = [ev[0] for p in trace["planes"] for line in p["lines"] if line["name"] == "tpu-runtime/17" for ev in line["events"]]
        assert runtime == (["PjRtCApiBuffer::Await"] if stalled else [])
        under = profiler.idle_by_span(trace)  # the steps' own 3 ms lie under the phase, the innermost; no name of the runtime's is a span
        assert under["train/forward/dispatch"] == pytest.approx(0.005) and not any(":" in k for k in under)


def test_the_hunt_drops_a_capture_with_no_stalled_quantum_and_keeps_and_names_the_one_with_it(tmp_path):
    from deepspeed_tpu.telemetry import get_registry

    reg = get_registry()
    dropped_before = reg.peek("profile_captures_dropped_total") or 0.0
    prof = profiler.DeviceProfiler(out_dir=str(tmp_path), quanta=6, hunt=True)
    stamps = iter([0.0, .1, .2, .3, .4, .5, .6, .6,          # a capture of six even quanta, and its end
                   10.0, 10.1, 10.2, 10.3, 10.7, 10.8, 10.9, 10.9])  # and one whose fourth took four
    prof._now = lambda: next(stamps)
    prof._stop_trace = lambda: None
    prof._start_trace = lambda d: _land(recorded(stalled=False), d)
    prof.arm()
    for _ in range(7):  # the one that starts the trace, and six markers
        prof.note_quantum("train/step")
    assert prof.state == "armed" and prof.hunt and prof.captures == 1  # dropped, and armed again
    assert reg.peek("profile_captures_dropped_total") == dropped_before + 1
    assert glob.glob(str(tmp_path / "capture-*")) == [] and prof.summary()["trace"] == "dropped"
    assert [h["kept"] for h in prof.hunted] == [False] and {"start", "stop", "drop"} <= set(prof.hunted[0])
    prof._start_trace = lambda d: _land(recorded(stalled=True), d)
    for _ in range(7):
        prof.note_quantum("train/step")
    assert prof.state == "idle" and not prof.hunt and prof.captures == 2  # kept: the hunt is over
    summary = prof.summary()
    stall = summary["stall"]
    assert (stall["quantum"], stall["period_s"], stall["median_s"]) == (3, pytest.approx(0.4), pytest.approx(0.1))
    assert stall["device_idle_s"] == pytest.approx(0.265, abs=1e-5) and stall["device_busy_s"] == pytest.approx(0.135, abs=1e-5)
    (gap,) = stall["idle"]  # the one stretch of 10 ms or more
    assert (gap["start_s"], gap["dur_s"]) == (pytest.approx(0.138, abs=1e-5), pytest.approx(0.262, abs=1e-5))
    assert list(gap["under"])[0] == "bench/wait_loss" and gap["under"]["bench/wait_loss"] == pytest.approx(0.260, abs=1e-5)
    assert gap["host"] == [["tpu-runtime/17", "PjRtCApiBuffer::Await", pytest.approx(0.258, abs=1e-5)]]
    assert gap["quiet_s"] == pytest.approx(0.004, abs=1e-5)  # the runtime's wait covers all of it but its ends
    assert stall["long_ops"] == [["copy.2", pytest.approx(0.040), pytest.approx(0.002)]]
    assert [h["kept"] for h in summary["hunted"]] == [False, True] and set(summary["capture_cost_s"]) == {"start", "stop", "reduce"}
    assert summary["idle_by_span"]["bench/wait_loss"] == pytest.approx(0.260)  # the whole capture's: its span is not cut to a quantum


@pytest.mark.parametrize("word,armed,hunt", [("0", False, False), ("1", True, False), ("stall", True, True), ("", False, False)])
def test_the_knob_is_a_word(word, armed, hunt, monkeypatch):
    profiler._reset_for_tests()
    monkeypatch.setenv("DS_TPU_PROFILE", word)
    prof = profiler.maybe_arm_profiler()
    assert (prof is not None and prof.state == "armed") == armed and bool(prof and prof.hunt) == hunt
    profiler._reset_for_tests()


def test_the_report_prints_a_stall():
    from tests.unit.test_profiler import _load_tool

    text = _load_tool("trace_report").render({"stall": {
        "quantum": 3, "period_s": 0.4, "median_s": 0.1, "device_busy_s": 0.135, "device_idle_s": 0.265, "long_ops": [["copy.2", 0.04, 0.002]],
        "idle": [{"start_s": 0.138, "dur_s": 0.262, "quiet_s": 0.004, "under": {"bench/wait_loss": 0.26}, "host": [["tpu-runtime/17", "PjRtCApiBuffer::Await", 0.258]]}]}})
    assert "quantum 3" in text and "PjRtCApiBuffer::Await" in text and "bench/wait_loss" in text and "copy.2" in text
