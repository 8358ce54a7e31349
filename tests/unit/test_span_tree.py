"""The program's span tree (docs/OBSERVABILITY.md "Span convention"): ids and
parents, the mirror into a profiler session, a program's first call, the
serving quantum, and the request timeline on the path ``_drive_sla`` drives."""

import dataclasses
import glob
import logging
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import InferenceEngineV2, RaggedBatchConfig
from deepspeed_tpu.inference.v2.replay import _drive_sla
from deepspeed_tpu.telemetry import (PerfAccountant, SpanTracer, current_span, get_event_log, get_registry,
                                     get_tracer, request_metrics, request_timelines, self_times, validate_timeline)
from deepspeed_tpu.telemetry import tracing
from deepspeed_tpu.telemetry.journal import Session
from deepspeed_tpu.utils.compile_cache import PHASE_COUNTERS
from tests.unit.test_inference_v2 import v2_setup  # noqa: F401  (module-scoped fixture)


# ------------------------------------------------------------------ the tree

def test_spans_carry_ids_parents_and_self_times():
    tr = SpanTracer()
    with tr.span("infer/fused_step", q=7) as outer:
        assert current_span() is outer
        with tr.span("fused/operands", q=7):
            time.sleep(0.002)
        with tr.span("fused/dispatch", q=7) as disp:
            with tr.span("program/first_call") as first:
                assert (first.parent, disp.parent) == (disp.id, outer.id)
                time.sleep(0.002)
    assert current_span() is None
    spans = {s["name"]: s for s in tr.spans()}
    root = spans["infer/fused_step"]
    assert root["parent"] == 0 and root["attrs"] == {"q": 7}
    assert spans["fused/operands"]["parent"] == spans["fused/dispatch"]["parent"] == root["id"]
    assert len({s["id"] for s in spans.values()}) == 4
    own = self_times(tr.spans())
    children = spans["fused/operands"]["dur_s"] + spans["fused/dispatch"]["dur_s"]
    assert own[root["id"]] == pytest.approx(root["dur_s"] - children)
    assert own[spans["fused/dispatch"]["id"]] == pytest.approx(
        spans["fused/dispatch"]["dur_s"] - spans["program/first_call"]["dur_s"])
    assert own[spans["program/first_call"]["id"]] == spans["program/first_call"]["dur_s"]
    assert sum(own.values()) == pytest.approx(root["dur_s"])  # every second of the tree is somebody's own


def test_self_time_ignores_a_child_whose_parent_left_the_ring():
    tr = SpanTracer(capacity=2)
    with tr.span("a"):
        with tr.span("b"):
            with tr.span("c"):
                pass
    with tr.span("d"):
        pass
    names = [s["name"] for s in tr.spans()]
    assert names == ["a", "d"]  # b and c fell off
    assert set(self_times(tr.spans())) == {s["id"] for s in tr.spans()}


def test_late_attributes_reach_the_ring_and_the_parent_is_restored_after_an_exception():
    tr = SpanTracer()
    with pytest.raises(RuntimeError):
        with tr.span("outer"):
            with tr.span("fused/program") as sp:
                sp.set(miss=True)
                raise RuntimeError("x")
    assert current_span() is None
    assert {s["name"]: s["attrs"] for s in tr.spans()}["fused/program"] == {"miss": True}
    tracing._NULL_SPAN.set(miss=True)  # the disabled path takes the same call


def _host_events(trace_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats)) for e in line.events]
    return out


@pytest.mark.parametrize("case", ["plain", "attributes", "nested_in_the_callers_annotation", "disabled"])
def test_a_span_is_in_a_profiler_sessions_trace_with_no_knob(case, tmp_path):
    """The mirror that DS_TPU_TRACE_XLA used to gate: always on while the
    tracer is, on the profiler's clock, inside the caller's annotations."""
    tr = SpanTracer(enabled=case != "disabled")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench/train_step"):
            with tr.span("train/forward", **({"q": 3, "kind": "mixed"} if case == "attributes" else {})):
                time.sleep(0.001)
    finally:
        jax.profiler.stop_trace()
    events = _host_events(tmp_path)
    outer = [e for e in events if e[0] == "bench/train_step"]
    inner = [e for e in events if e[0] == "train/forward"]
    assert len(outer) == 1
    if case == "disabled":
        assert inner == [] and tr.spans() == []
        return
    assert len(inner) == 1
    assert outer[0][1] <= inner[0][1] and inner[0][2] <= outer[0][2]  # same clock, nested
    assert inner[0][2] - inner[0][1] >= 1e6
    if case == "attributes":
        assert {k: str(v) for k, v in inner[0][3].items()} == {"q": "3", "kind": "mixed"}


# ------------------------------------------------------- a program's first call

class _Lines(logging.Handler):
    def __init__(self):
        super().__init__()
        self.lines = []

    def emit(self, record):
        self.lines.append(record.getMessage())


@pytest.fixture
def first_call_lines():
    handler = _Lines()
    logger = logging.getLogger("deepspeed_tpu")
    logger.addHandler(handler)
    yield handler.lines
    logger.removeHandler(handler)


def _counters():
    reg = get_registry()
    return {c: reg.peek(c) or 0.0 for c in PHASE_COUNTERS + ("program_first_calls_total",)}


@pytest.mark.parametrize("mode", [0, 1])
def test_first_call_is_a_span_five_counters_and_one_line_per_new_signature(mode, first_call_lines):
    """With the accountant off (mode 0) as with it on: the span and the
    counters do not depend on DS_TPU_PERF_ACCOUNT."""
    tracer = get_tracer()
    tracer.clear()
    acct = PerfAccountant(mode=mode, use_telemetry=False)
    salt = float(np.random.default_rng().integers(1 << 30))  # a program no cache has seen
    fn = acct.wrap("toy", jax.jit(lambda a: jnp.tanh(a) * salt), family="toy", bucket=(8, 0, 0))
    x8, x16 = jnp.ones((8,)), jnp.ones((16,))  # their own helper programs first
    before = _counters()
    with tracer.span("fused/dispatch", q=11, steps=4):
        fn(x8)
    once = _counters()
    firsts = [s for s in tracer.spans() if s["name"] == "program/first_call"]
    assert len(firsts) == 1 and len(first_call_lines) == 1
    attrs = firsts[0]["attrs"]
    assert [attrs[k] for k in ("family", "bucket", "q", "steps", "programs")] == ["toy", (8, 0, 0), 11, 4, 1]
    assert once["program_first_calls_total"] - before["program_first_calls_total"] == 1
    for c in ("program_lower_seconds_total", "program_compile_seconds_total"):
        assert once[c] > before[c]
    phases = ("cost_card", "trace", "lower", "compile", "other") if mode else ("trace", "lower", "compile", "other")
    assert attrs["total_s"] == pytest.approx(sum(attrs[p + "_s"] for p in phases))
    assert attrs["compile_s"] == pytest.approx(once["program_compile_seconds_total"] - before["program_compile_seconds_total"])
    line = first_call_lines[0]
    assert line.startswith("program first call: family=toy bucket=(8, 0, 0) q=11 steps=4 total_s=")
    assert all(f" {p}_s=" in line for p in phases + ("cache_fetch",))
    cards = [s for s in tracer.spans() if s["name"] == "program/cost_card"]
    assert [s["parent"] for s in cards] == ([firsts[0]["id"]] if mode else [])

    with tracer.span("fused/dispatch", q=12, steps=4):
        fn(x8)  # the same signature again: nothing
    assert _counters() == once and len(first_call_lines) == 1
    assert len([s for s in tracer.spans() if s["name"] == "program/first_call"]) == 1
    fn(x16)  # a new signature of the same program: a first call again
    assert len(first_call_lines) == 2 and _counters()["program_first_calls_total"] == once["program_first_calls_total"] + 1


def test_a_trace_nested_in_another_programs_trace_is_not_counted_twice():
    @jax.jit
    def inner(x):
        time.sleep(0.2)  # runs while tracing only
        return x * 3 + 1

    salt = float(np.random.default_rng().integers(1 << 30))
    outer = jax.jit(lambda x: inner(x) + salt)
    x = jnp.ones((3,))
    before = _counters()
    t0 = time.perf_counter()
    outer(x)
    wall = time.perf_counter() - t0
    spent = {c: v - before[c] for c, v in _counters().items()}
    assert spent["program_trace_seconds_total"] >= 0.2
    # the phases follow one another: counted once each they fit the call, with inner's 0.2 s twice they do not
    assert sum(spent[c] for c in PHASE_COUNTERS[:3]) <= wall


def test_program_caches_count_builds_and_evictions_by_family(v2_setup, monkeypatch):  # noqa: F811
    from deepspeed_tpu.inference.v2 import engine_v2 as ev2

    model, params, cfg = v2_setup
    reg = get_registry()
    count = lambda name, fam: reg.peek(name, family=fam) or 0.0
    base = {(n, f): count(n, f) for n in ("program_builds_total", "program_evictions_total")
            for f in ("prefill", "decode", "fused", "burst")}
    eng = InferenceEngineV2(model, params, dataclasses.replace(cfg, decode_burst=8))
    monkeypatch.setattr(ev2, "make_fused_step_fn", lambda *a, **kw: object())
    monkeypatch.setattr(ev2, "make_burst_fn", lambda *a, **kw: object())
    cap = eng._max_program_variants
    for i in range(cap + 2):
        eng._fused_for(8, 1, 16 * (i + 1), None)
    eng._fused_for(8, 1, 16 * (cap + 2), None)  # a hit: neither
    eng._burst_for(None)
    delta = {k: count(*k) - v for k, v in base.items()}
    assert delta[("program_builds_total", "fused")] == cap + 2 and delta[("program_evictions_total", "fused")] == 2
    assert delta[("program_builds_total", "burst")] == 1 and delta[("program_evictions_total", "burst")] == 0
    assert delta[("program_builds_total", "prefill")] == delta[("program_builds_total", "decode")] == 1
    assert eng._program_builds == cap + 3 and len(eng._fused_fns) == cap


# ------------------------------------------------ the quantum and the timeline

PROMPTS = [[3, 17, 42, 9, 88, 5, 23], [7, 7, 19], [90, 14, 2, 61, 33, 8, 12, 54, 27, 6, 71], [11, 5], [64, 3, 99, 18, 4]]


@pytest.fixture(scope="module")
def driven(v2_setup):  # noqa: F811
    """_drive_sla, recorded pacing, over a tiny fused engine; then the same
    session once more on the warm engine."""
    model, params, cfg = v2_setup
    smc = RaggedBatchConfig(kv_block_size=8, max_context=64, num_kv_blocks=96)
    # no prefix cache: a later pass would find the prompts' first blocks cached and prefill fewer tokens
    eng = InferenceEngineV2(model, params, dataclasses.replace(cfg, state_manager=smc, fused_step=True,
                                                               enable_prefix_cache=False))
    session = Session({})
    for i, p in enumerate(PROMPTS):
        session.requests[i] = {"prompt": p, "arrival_s": 0.02 * i, "max_new_tokens": 3 + i}
    tracer, log, reg = get_tracer(), get_event_log(), get_registry()
    names = ("sched_slot_tokens_total", "sched_useful_tokens_total", "sched_prefill_slot_tokens_total",
             "paged_attention_context_tokens_total", "infer_fused_quanta_total", "program_first_calls_total")
    out = {}
    # recorded pacing meets other shapes when warm than when cold (it no longer stalls on compiles);
    # the quantum clock repeats itself exactly, so its second pass has no first call left
    for run, timing in (("cold", "recorded"), ("warm", "recorded"), ("logical", "logical"), ("repeat", "logical")):
        tracer.clear()
        log.clear()
        before = {n: reg.peek(n) or 0.0 for n in names}
        results, stats = _drive_sla(eng, session, timing=timing)
        out[run] = {"results": results, "stats": stats, "spans": tracer.spans(), "events": log.events(),
                    "counters": {n: (reg.peek(n) or 0.0) - before[n] for n in names}}
    tracer.clear()
    log.clear()
    return out


@pytest.mark.parametrize("run", ["cold", "warm"])
def test_drive_sla_leaves_a_whole_valid_timeline_for_every_request(driven, run):
    tls = request_timelines(driven[run]["events"])
    assert set(tls) == set(range(len(PROMPTS)))
    for uid, stat in enumerate(driven[run]["stats"]):
        (tl,) = tls[uid]
        assert validate_timeline(tl) == [], f"uid {uid}"
        kinds = [e["kind"] for e in tl]
        assert kinds[0] == "enqueue" and "admit" in kinds and "prefill_chunk" in kinds and kinds[-1] == "finish"
        m = request_metrics(tl)
        assert m["n_new"] == 3 + uid == len(driven[run]["results"][uid])
        assert m["queue_s"] >= 0 and m["prefill_s"] >= 0 and m["decode_s"] >= 0
        assert m["ttft_s"] == pytest.approx(stat.first_token - stat.arrival)  # enqueue is stamped with the arrival
        assert m["total_s"] == pytest.approx(stat.done - stat.arrival)


STAMP_EPS = 1e-6  # start_s + dur_s is the exit stamp up to rounding


def _end(span):
    return span["start_s"] + span["dur_s"]


@pytest.mark.parametrize("run", ["cold", "warm"])
def test_every_quantum_is_one_fused_step_span_that_its_children_cover(driven, run):
    spans = driven[run]["spans"]
    quanta = [s for s in spans if s["name"] == "infer/fused_step"]
    assert len(quanta) == driven[run]["counters"]["infer_fused_quanta_total"] > 0
    children = ("fused/validate", "fused/operands", "fused/program", "fused/dispatch", "fused/account", "fused/readback")
    for quantum in quanta:
        mine = [s for s in spans if s["parent"] == quantum["id"]]
        assert [s["name"] for s in mine] == list(children)  # one each, in order
        # inside the quantum and one after another; what share of it they take is the machine's load, not the program's
        edges = [quantum["start_s"]] + [t for s in mine for t in (s["start_s"], _end(s))] + [_end(quantum)]
        assert all(t0 <= t1 + STAMP_EPS for t0, t1 in zip(edges, edges[1:]))
        assert {s["attrs"]["q"] for s in mine} == {quantum["attrs"]["q"]}
        a = quantum["attrs"]
        assert a["kind"] == ("decode" if not a["n_pre"] else "mixed" if a["n_dec"] else "prefill")
        assert a["tokens"] == a["n_dec"] * a["steps"] + a["prefill_tokens"] <= a["slots"]
        D, P, S = a["bucket"]
        assert a["slots"] == D * a["steps"] + P * S
    # the loop's own spans carry the same quantum ids
    for name in ("serve/schedule", "serve/commit"):
        assert [s["attrs"]["q"] for s in spans if s["name"] == name] == [s["attrs"]["q"] for s in quanta]
    sched = [s for s in spans if s["name"] == "serve/schedule"]
    assert all(s["attrs"]["rows"] == q["attrs"]["n_dec"] + q["attrs"]["n_pre"] for s, q in zip(sched, quanta))
    assert any(s["name"] == "serve/admit" for s in spans)
    # The engine sleeps only where it has outrun the arrivals (20 ms apart: a loaded machine's never does, warm or cold).
    # A turn that idles began with every request either finished or yet to arrive, sleeps until an arrival, and the next
    # turn's admit follows; cold, the request that arrived at 0 cannot have finished before the first program's first call.
    events = driven[run]["events"]
    arrived = {e["uid"]: e["ts"] for e in events if e["kind"] == "enqueue"}  # stamped with the recorded arrival
    finished = {e["uid"]: e["ts"] for e in events if e["kind"] == "finish"}
    turns = [s for s in spans if s["name"] in ("serve/admit", "serve/idle_wait")]
    first_call = next((s for s in spans if s["name"] == "program/first_call"), None)
    for i, wait in enumerate(turns):
        if wait["name"] != "serve/idle_wait":
            continue
        admit, following = turns[i - 1], turns[i + 1]
        assert admit["name"] == following["name"] == "serve/admit" and admit["attrs"]["q"] == wait["attrs"]["q"]
        assert _end(wait) <= following["start_s"] + STAMP_EPS
        assert all(finished[uid] <= wait["start_s"] + STAMP_EPS or arrived[uid] >= admit["start_s"] - STAMP_EPS
                   for uid in arrived)
        assert any(admit["start_s"] - STAMP_EPS <= t <= _end(wait) + STAMP_EPS for t in arrived.values())
        if run == "cold":
            assert _end(first_call) <= wait["start_s"] + STAMP_EPS
    for quantum in quanta:  # the budget of 12 a quantum: ten, but for first calls and the turns that slept before it
        names = [s["name"] for s in spans if s["attrs"].get("q") == quantum["attrs"]["q"]]
        assert len(names) - 2 * names.count("serve/idle_wait") - names.count("program/first_call") == 10


def test_first_calls_show_under_dispatch_when_cold_and_not_at_all_on_a_repeat(driven):
    cold, warm = driven["cold"]["spans"], driven["warm"]["spans"]
    firsts = [s for s in cold if s["name"] == "program/first_call"]
    by_id = {s["id"]: s for s in cold}
    assert firsts and all(by_id[s["parent"]]["name"] == "fused/dispatch" for s in firsts)
    assert all(s["attrs"]["family"] == "fused" and s["attrs"]["q"] == by_id[s["parent"]]["attrs"]["q"] for s in firsts)
    misses = [s for s in cold if s["name"] == "fused/program" and s["attrs"]["miss"]]
    assert 0 < len(misses) <= len(firsts)  # a program built once has a first call for each number of steps
    assert len({(s["attrs"]["bucket"], s["attrs"]["steps"]) for s in firsts}) == len(firsts)
    assert driven["cold"]["counters"]["program_first_calls_total"] >= sum(s["attrs"]["programs"] for s in firsts)
    assert not [s for s in warm if s["name"] == "fused/program" and s["attrs"]["miss"]] or \
        [s for s in warm if s["name"] == "program/first_call"]  # a build is followed by a first call
    repeat = driven["repeat"]["spans"]
    assert not [s for s in repeat if s["name"] in ("program/first_call", "program/cost_card")]
    assert not [s for s in repeat if s["name"] == "fused/program" and s["attrs"]["miss"]]
    assert driven["repeat"]["counters"]["program_first_calls_total"] == 0
    assert driven["repeat"]["results"] == driven["logical"]["results"] == driven["cold"]["results"]


@pytest.mark.parametrize("run", ["cold", "warm"])
def test_scheduler_fill_and_context_token_counters(driven, run):
    c = driven[run]["counters"]
    quanta = [s["attrs"] for s in driven[run]["spans"] if s["name"] == "infer/fused_step"]
    assert c["sched_slot_tokens_total"] == 768 * len(quanta)  # the default budget of every non-empty quantum
    assert c["sched_prefill_slot_tokens_total"] == sum(q["prefill_tokens"] for q in quanta) == sum(map(len, PROMPTS))
    assert c["sched_useful_tokens_total"] == sum(q["n_dec"] + q["prefill_tokens"] for q in quanta)
    # each request reads its prompt once, then a context one longer for every further token but the last
    expect = sum(sum(range(len(p), len(p) + n)) for p, n in zip(PROMPTS, (3 + i for i in range(len(PROMPTS)))))
    assert c["paged_attention_context_tokens_total"] == expect


def test_put_is_a_span_around_its_prefill_and_decode(v2_setup):  # noqa: F811
    model, params, cfg = v2_setup
    eng = InferenceEngineV2(model, params, dataclasses.replace(cfg, fused_step=False))
    tracer = get_tracer()
    tracer.clear()
    eng.put([0, 1], [PROMPTS[0], PROMPTS[1]])
    eng.put([0, 1], [[5], [6]])
    spans = tracer.spans()
    puts = [s for s in spans if s["name"] == "infer/put"]
    assert [p["attrs"] for p in puts] == [{"rows": 2, "tokens": 10}, {"rows": 2, "tokens": 2}]
    by_id = {s["id"]: s for s in spans}
    for name, put in (("infer/prefill", puts[0]), ("infer/decode", puts[1])):
        inner = [s for s in spans if s["name"] == name]
        assert inner and all(s["parent"] == put["id"] for s in inner)
    firsts = [s for s in spans if s["name"] == "program/first_call"]
    assert {s["attrs"]["family"] for s in firsts} == {"prefill", "decode"}
    assert all(by_id[s["parent"]]["name"] in ("infer/prefill", "infer/decode") for s in firsts)
    tracer.clear()
