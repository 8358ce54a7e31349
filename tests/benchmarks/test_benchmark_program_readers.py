"""The readers of what the program records about itself (its span ring, its
event log, its counters): on hand-made spans, where the arithmetic can be
checked by hand, and on the record of a CPU rehearsal of each driver, where the
program's own spans are read in process as a measuring run reads them."""

import os
import time

import pytest

from benchmarks import run as bench_run
from benchmarks.lib import manifest as mf, program, runtime

MANIFEST = mf.load_manifest()
NEW = ("setup_program_s", "host_step_share.train", "queue_wait_p95_ms", "host_share_of_quantum.serve",
       "first_calls_in_window")
COUNTERS = dict({c: 0.0 for c in program.COUNTERS}, program_trace_seconds_total=3.0, program_lower_seconds_total=4.0,
                program_compile_seconds_total=20.0, program_cache_fetch_seconds_total=6.0, program_first_calls_total=9.0)


def span(name, start, dur, sid, parent=0, **attrs):
    return {"name": name, "start_s": start, "dur_s": dur, "id": sid, "parent": parent, "attrs": attrs, "tid": 1, "depth": 0}


def read(metric, record):
    return mf.metric_module(metric).read(record)


# ------------------------------------------------------------ hand-made spans

def train_spans(n=4, period=0.25):
    out = [span("init/optimizer", 0.0, 5.0, 1)]
    for i in range(n):
        t, base = 10.0 + period * i, 10 * (i + 1)
        out += [span("train/forward", t, 0.004, base), span("inner", t + 0.001, 0.001, base + 1, parent=base),
                span("train/backward", t + 0.005, 0.0005, base + 2), span("train/step", t + 0.006, 0.0005, base + 3)]
    return out


def test_host_step_share_is_the_spans_self_time_over_the_steps_period():
    record = {"train": {"steps": 3}, "program": {"spans": train_spans(4), "counters": COUNTERS, "events": []}}
    # forward 4 ms less its 1 ms child, backward and step 0.5 ms each: 4 ms of a 250 ms period
    assert read("host_step_share.train", record) == pytest.approx(100 * 0.004 / 0.25)


@pytest.mark.parametrize("why,record", [
    ("the ring holds fewer steps than the window had",
     {"train": {"steps": 9}, "program": {"spans": train_spans(4), "counters": COUNTERS, "events": []}}),
    ("spans fell off the ring inside the window",
     {"train": {"steps": 4}, "program": {"spans": train_spans(4)[1:], "events": [],
                                         "counters": dict(COUNTERS, telemetry_spans_dropped_total=3.0)}}),
    ("the program has no span ids (the parent commit)",
     {"train": {"steps": 3}, "program": {"spans": None, "counters": {c: None for c in program.COUNTERS}, "events": []}}),
    ("not a training record", {"end_to_end": {}, "summary": {"tokens_total": 0}}),
])
def test_a_reader_of_spans_returns_none_never_a_partial_number(why, record):
    for metric in ("host_step_share.train", "host_share_of_quantum.serve", "first_calls_in_window"):
        assert read(metric, dict(record)) is None, why


def test_setup_program_s_is_the_counters_less_the_first_calls_of_the_window():
    spans = train_spans(3) + [span("program/first_call", 10.3, 2.0, 99, programs=1, trace_s=0.5, lower_s=0.25,
                                   compile_s=1.0, cache_fetch_s=0.75, total_s=2.0)]
    record = {"end_to_end": {"setup_s": 60.0}, "train": {"steps": 3}, "compiles_in_window": 1,
              "program": {"spans": spans, "counters": COUNTERS, "events": []}}
    assert read("setup_program_s", record) == pytest.approx((3.0 - 0.5) + (4.0 - 0.25) + (20.0 - 1.0))
    assert record["extras"]["setup_program_split_s"] == {"trace": 2.5, "lower": 3.75, "compile": 19.0, "cache_fetch": 5.25}
    record["compiles_in_window"] = 2  # one more than the program's spans account for: no number
    assert read("setup_program_s", record) is None
    quiet = {"end_to_end": {"setup_s": 60.0}, "train": {"steps": 3}, "compiles_in_window": 0,
             "program": {"spans": None, "counters": COUNTERS, "events": []}}
    assert read("setup_program_s", quiet) == 27.0  # nothing compiled in the window: the counters alone say it
    parent = dict(quiet, program={"spans": None, "counters": {c: None for c in program.COUNTERS}, "events": []})
    assert read("setup_program_s", parent) is None


def quantum_spans(q, t, sid, first_call=False, idle_before=False):
    out = []
    if idle_before:
        out += [span("serve/admit", t - 0.5, 0.001, sid + 20, q=q), span("serve/idle_wait", t - 0.499, 0.4, sid + 21, q=q)]
    out += [span("serve/admit", t, 0.001, sid, q=q), span("serve/schedule", t + 0.001, 0.002, sid + 1, q=q),
            span("infer/fused_step", t + 0.003, 0.095, sid + 2, q=q),
            span("fused/validate", t + 0.003, 0.001, sid + 3, sid + 2, q=q),
            span("fused/operands", t + 0.004, 0.004, sid + 4, sid + 2, q=q),
            span("fused/program", t + 0.008, 0.0005, sid + 5, sid + 2, q=q, miss=first_call),
            span("fused/dispatch", t + 0.0085, 0.0015, sid + 6, sid + 2, q=q),
            span("fused/account", t + 0.010, 0.001, sid + 7, sid + 2, q=q),
            span("fused/readback", t + 0.011, 0.087, sid + 8, sid + 2, q=q),
            span("serve/commit", t + 0.098, 0.002, sid + 9, q=q)]
    if first_call:
        out.append(span("program/first_call", t + 0.0086, 0.001, sid + 10, sid + 6, q=q, family="fused", bucket=(8, 0, 0),
                        programs=2, total_s=0.001))
    return out


def test_host_share_of_quantum_leaves_out_the_readback_the_sleep_and_first_calls():
    earlier = quantum_spans(5, 1.0, 100)  # another engine's q=5, before the window
    window = quantum_spans(5, 10.0, 200, idle_before=True) + quantum_spans(6, 10.2, 300, first_call=True) \
        + quantum_spans(7, 10.4, 400)
    record = {"quanta": [{}, {}, {}], "compiles_in_window": 3,
              "program": {"spans": earlier + window, "counters": COUNTERS, "events": []}}
    # admit 1 + schedule 2 + validate 1 + operands 4 + program 0.5 + dispatch 1.5 + account 1 + commit 2 = 13 ms
    # of the 100 ms from the quantum's own admit to the end of its commit; the quantum with a first call is left out
    assert read("host_share_of_quantum.serve", record) == pytest.approx(13.0)
    assert read("first_calls_in_window", record) == 2
    assert record["extras"]["first_calls_in_window"]["outside_program_caches"] == 1
    assert record["extras"]["first_calls_in_window"]["calls"][0]["bucket"] == (8, 0, 0)


def test_queue_wait_is_first_prefill_chunk_less_enqueue_of_each_uids_last_timeline():
    ev = lambda kind, uid, ts: {"kind": kind, "uid": uid, "ts": ts}
    events = [ev("enqueue", 0, 1.0), ev("prefill_chunk", 0, 9.0),  # a warm-up pass: the uid comes again
              ev("enqueue", 0, 20.0), ev("enqueue", 1, 20.1), ev("quantum", -1, 20.2), ev("prefill_chunk", 0, 20.25),
              ev("prefill_chunk", 0, 20.5), ev("prefill_chunk", 1, 20.6), ev("finish", 0, 21.0)]
    record = {"requests": [{}, {}], "program": {"spans": [], "counters": COUNTERS, "events": events}}
    assert read("queue_wait_p95_ms", record) == pytest.approx(250 + 0.95 * 250)
    record["requests"].append({})  # a request that never reached the scheduler: no number
    assert read("queue_wait_p95_ms", record) is None


@pytest.mark.parametrize("metric", NEW)
def test_a_new_reader_states_the_facts_the_manifest_needs(metric):
    mod = mf.metric_module(metric)
    assert mod.SOURCE in ("program_span", "program_counter") and mod.BETTER == "lower" and mod.MOVES and mod.LAYER
    assert mod.read({"end_to_end": {}, "summary": {"tokens_total": 0}}) is None
    listed = {m["name"]: m for m in MANIFEST["per_layer"]}
    if metric in ("setup_program_s", "host_step_share.train"):  # the two PR 25 listed: still listed, OLMo's cell among their cells
        assert metric in listed and "olmo-1b.pretrain-z3" in listed[metric]["workloads"]
    if metric in listed:  # wherever a later PR lists one of these, the manifest says what the reader says
        assert tuple(listed[metric][k] for k in ("unit", "better", "source", "layer", "moves")) == \
            (mod.UNIT, mod.BETTER, mod.SOURCE, mod.LAYER, mod.MOVES)


# ------------------------------------------------------- a rehearsal's record

@pytest.fixture
def clean_env():
    saved = dict(os.environ)
    yield
    os.environ.clear()
    os.environ.update(saved)


def rehearsal_record(cell):
    t0 = time.perf_counter()
    cell = bench_run._rehearsal(cell)
    for knob, value in cell["config"].get("env", {}).items():
        os.environ[knob] = str(value)
    opts = {"seed": 2**31 + 7, "seconds": 2.0, "rehearse": True, "t0": t0, "tracer": runtime.Tracer(False, ""),
            "compiles": runtime.CompileCounter(), "say": lambda msg: None, "cache_counts": runtime.cache_counts}
    driver = mf.load_module(os.path.join(mf.BENCH, "drivers", f"{cell['config']['kind']}.py"))
    return driver.run(cell, opts)


def test_the_serving_readers_on_a_rehearsals_record(clean_env):
    from deepspeed_tpu.telemetry import get_event_log, get_tracer, request_timelines, validate_timeline

    get_tracer().clear()
    get_event_log().clear()
    record = rehearsal_record(mf.compose(MANIFEST, "mistral-7b-l16", "chat-steady", 1))
    assert record["correct"] and len(record["quanta"]) > 0
    prog = program.snapshot()
    quanta = program.last(prog, "infer/fused_step", len(record["quanta"]))
    assert quanta is not None  # every quantum of the window has its span
    by_parent = {}
    for s in prog["spans"]:
        by_parent.setdefault(s["parent"], []).append(s)
    for quantum, logged in zip(quanta, record["quanta"]):
        assert sum(c["dur_s"] for c in by_parent[quantum["id"]]) >= 0.95 * quantum["dur_s"]
        assert (quantum["attrs"]["kind"], quantum["attrs"]["steps"]) == (logged["kind"], logged["steps"])
    timelines = request_timelines(prog["events"])
    for uid in range(len(record["requests"])):
        assert validate_timeline(timelines[uid][-1]) == []
    wait = read("queue_wait_p95_ms", record)
    share = read("host_share_of_quantum.serve", record)
    firsts = read("first_calls_in_window", record)
    assert wait is not None and wait >= 0 and 0 < share < 100
    # what the driver's own listener counted in the window is what the program's spans account for
    assert firsts == record["compiles_in_window"] - record["extras"]["first_calls_in_window"]["outside_program_caches"]
    assert record["extras"]["first_calls_in_window"]["outside_program_caches"] == 0
    # the counters run from the start of the process (here: of the test session, not of the cell)
    in_window = sum(a["trace_s"] + a["lower_s"] + a["compile_s"] for a in record["extras"]["first_calls_in_window"]["calls"])
    assert read("setup_program_s", record) == pytest.approx(
        sum(prog["counters"][c] for c in program.PHASE_COUNTERS[:3]) - in_window)
    # every first call of the run is one (family, bucket, steps) and they are all different
    calls = [s["attrs"] for s in prog["spans"] if s["name"] == "program/first_call"]
    assert len({(a["family"], a["bucket"], a.get("steps")) for a in calls}) == len(calls) >= 3


def test_the_training_readers_on_a_rehearsals_record(clean_env):
    import jax

    from deepspeed_tpu.telemetry import get_tracer

    if len(jax.devices()) < 4:
        pytest.skip("the training cell shards over four devices")
    get_tracer().clear()
    record = rehearsal_record(mf.cell(MANIFEST, "olmo-1b.pretrain-z3"))
    assert record["correct"] and record["train"]["steps"] >= 2
    names = {s["name"] for s in get_tracer().spans()}
    assert {"init/mesh", "init/shard_state", "init/optimizer", "train/forward", "train/backward", "train/step"} <= names
    share = read("host_step_share.train", record)
    assert share is not None and 0 < share < 100
    setup = read("setup_program_s", record)
    assert record["compiles_in_window"] == 0 and setup > 0
    split = record["extras"]["setup_program_split_s"]
    assert setup == pytest.approx(split["trace"] + split["lower"] + split["compile"]) and split["trace"] > 0
    assert split == {p: program.snapshot()["counters"][c] for p, c in zip(("trace", "lower", "compile", "cache_fetch"),
                                                                           program.PHASE_COUNTERS)}
