"""The two readers of PR 51 on hand-made records (``stall_share.train``: the
trainer's stall counters over the window; ``host_dispatch_ms.train``: the
phases of ``train/forward``), their entries in the manifest, and the older
reader of the same spans, which the phases must not move."""

import pytest

from benchmarks.lib import manifest as mf
from tests.benchmarks.test_benchmark_program_readers import COUNTERS, read, span, train_spans

MANIFEST = mf.load_manifest()
CELLS = ["olmo-1b.pretrain-z3", "qwen3-next-80b-l4e32.pretrain-8k", "keye-vl2-30b-l4e16.pretrain-8k"]
PHASES = [{"put_batch": 0.0002, "dispatch": 0.003, "device_counts": 0.0001}, {"put_batch": 0.0004, "dispatch": 0.002},
          {"put_batch": 0.0003, "dispatch": 0.004, "device_counts": 0.0003}]


def phased_spans(phases):
    spans = train_spans(len(phases) + 1)
    for s, by in zip([s for s in spans if s["name"] == "train/forward"][1:], phases):  # the window's are the last ones
        s["attrs"] = dict(s["attrs"], phase_s=by)
    return spans


def record(spans, steps=3, **more):
    return dict({"train": {"steps": steps}, "elapsed_s": 40.0, "program": {"spans": spans, "counters": COUNTERS, "events": []}}, **more)


@pytest.mark.parametrize("case,counters,want,stalls", [
    ("the parent's record has no such counter", {"train_steps_total": 117.0}, None, None),
    ("a clean window", {"train_step_stall_seconds_total": 0.0, "train_step_stalls_total": 0.0}, 0.0, 0.0),
    ("a window that lost 1.6 s to one stall", {"train_step_stall_seconds_total": 1.6, "train_step_stalls_total": 1.0}, 4.0, 1.0),
])
def test_stall_share_is_the_stalled_seconds_over_the_window(case, counters, want, stalls):
    rec = record(train_spans(4), counters=counters)
    assert read("stall_share.train", rec) == (want if want is None else pytest.approx(want)), case
    assert rec.get("extras", {}).get("stalls") == stalls


@pytest.mark.parametrize("case,spans,steps,want", [
    ("the parent's spans carry no phases", train_spans(4), 3, None),
    ("one of the window's steps has none", phased_spans(PHASES[:2] + [{}]), 3, None),
    ("the ring holds fewer steps than the window had", phased_spans(PHASES), 9, None),
    ("three steps", phased_spans(PHASES), 3, 3.3),  # sums of 3.3, 2.4 and 4.6 ms: the median
])
def test_host_dispatch_ms_is_the_median_of_the_three_phases_sum(case, spans, steps, want):
    rec = record(spans, steps=steps)
    assert read("host_dispatch_ms.train", rec) == (want if want is None else pytest.approx(want)), case
    if want is not None:  # a step with no device counts to look at has no such phase: it counts as nothing
        assert rec["extras"]["host_dispatch_split_ms"] == {"put_batch": pytest.approx(0.3), "dispatch": pytest.approx(3.0),
                                                           "device_counts": pytest.approx(0.1)}


def test_the_phases_leave_host_step_share_where_it_was():
    plain, phased = record(train_spans(4)), record(phased_spans(PHASES))
    assert read("host_step_share.train", phased) == read("host_step_share.train", plain) == pytest.approx(100 * 0.004 / 0.25)
    assert len(phased["program"]["spans"]) == len(plain["program"]["spans"])  # a phase is no record of the ring


@pytest.mark.parametrize("metric,source,unit", [("stall_share.train", "program_counter", "%"), ("host_dispatch_ms.train", "program_span", "ms")])
def test_the_manifest_lists_each_reader_last_and_in_the_three_cells_whose_sets_no_test_pins(metric, source, unit):
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == metric)  # by name: the title's "last" and "three" were PR 51's day
    mod = mf.metric_module(metric)
    assert {k: v for k, v in entry.items() if k != "workloads"} == {"name": metric, "unit": unit, "better": "lower", "source": source,
                                                                    "layer": "trainer step loop (runtime/engine.py)", "moves": "train_tokens_per_s"}
    assert set(CELLS) <= set(entry["workloads"]) and entry["workloads"][0] == CELLS[0]  # OLMo first: a test's one-cell base keeps a metric by it
    assert len(set(entry["workloads"])) == len(entry["workloads"])
    assert (mod.UNIT, mod.BETTER, mod.SOURCE, mod.LAYER, mod.MOVES) == (unit, "lower", source, entry["layer"], entry["moves"])
    assert mod.read({"end_to_end": {}, "summary": {"tokens_total": 0}}) is None
    assert not [p for p in mf.problems(MANIFEST) if metric in p]
