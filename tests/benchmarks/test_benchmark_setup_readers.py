"""The four readers of set-up's timeline (``setup_engine_init_s``,
``setup_step_trace_s``, ``setup_step_compile_s``, ``setup_callers_programs_s``;
``benchmarks/lib/setup_timeline.py``): on a hand-made record, where the
arithmetic can be checked by hand; on the record of a CPU rehearsal of one dense
and one routed cell, where the program's own counters are read in process; and
their entries in the manifest, by name and by rule: nothing here counts the
manifest's cells or says where an entry stands."""

import pytest

from benchmarks.lib import manifest as mf, program, setup_timeline
from tests.benchmarks.test_benchmark_program_readers import clean_env, read, rehearsal_record  # noqa: F401  (clean_env: a fixture)

MANIFEST = mf.load_manifest()
READERS = {"setup_engine_init_s": "trainer construction (runtime/engine.py)",
           "setup_step_trace_s": "program caches and jax.jit (first calls)",
           "setup_step_compile_s": "program caches and jax.jit (first calls)",
           "setup_callers_programs_s": "the caller's programs (model.init, the plain reference: benchmarks/lib/weights.py, lib/reference.py)"}


@pytest.fixture(scope="module", autouse=True)
def _compiled_programs_dropped():
    """The two rehearsals compile whole cells: what the worker holds is dropped before this module and after it
    (``tests/unit/test_moe_sum_rows.py``: a worker that has compiled enough dies inside XLA's CPU compiler)."""
    import jax

    jax.clear_caches()
    yield
    jax.clear_caches()


def call(family, phase):
    return f'program_first_call_seconds_total{{family="{family}",phase="{phase}"}}'


def part(name):
    return f'engine_init_seconds_total{{part="{name}"}}'


# totals since the process started, as ``lib/program.py::totals`` names the series
TOTALS = {
    part("mesh"): 0.25, part("shard_state"): 1.5, part("optimizer"): 2.0, part("rest"): 0.5, part("after"): 0.75,
    call("init", "trace"): 0.25, call("init", "lower"): 0.25, call("init", "compile"): 2.0, call("init", "cache_fetch"): 1.5, call("init", "other"): 0.5,
    call("train", "trace"): 0.5, call("train", "lower"): 3.0, call("train", "compile"): 12.0, call("train", "cache_fetch"): 9.0,
    call("train", "other"): 0.25, call("train", "flops_count"): 4.0,
    "program_trace_seconds_total": 6.75, "program_lower_seconds_total": 4.25, "program_compile_seconds_total": 20.0,
    "program_cache_fetch_seconds_total": 12.0, "train_steps_total": 120.0,
}


def record(rise=None, **more):
    return dict({"end_to_end": {"setup_s": 50.0}, "train": {"steps": 100}, "counters": rise or {},
                 "program": {"counters": dict(TOTALS), "gauges": {"import_seconds": 2.5}, "spans": None, "events": []}}, **more)


@pytest.mark.parametrize("metric,seconds", [
    ("setup_engine_init_s", 0.25 + 1.5 + 2.0 + 0.5 + 0.75),  # ``after``: family init's first calls outside the root
    ("setup_step_trace_s", 0.5 + 3.0 + 0.25 + 4.0),  # every phase of the step's first calls but compile
    ("setup_step_compile_s", 12.0),  # the fetch from the persistent cache is inside it
    ("setup_callers_programs_s", 31.0 - 2.5 - 15.5),  # trace + lower + compile of the whole process less what lay in the two families' spans
])
def test_a_reader_on_a_hand_made_record_and_the_parts_sum_to_setup_s(metric, seconds):
    assert list(READERS) == ["setup_engine_init_s", "setup_step_trace_s", "setup_step_compile_s", "setup_callers_programs_s"]  # a case each
    rec = record()
    assert read(metric, rec) == seconds
    line = rec["extras"]["setup_timeline_s"]
    assert line["callers_programs"] == 13.0 and line["import"] == 2.5
    assert line["harness"] == 50.0 - (5.0 + 7.75 + 12.0 + 2.5 + 13.0)
    assert sum(line[k] for k in ("engine_init", "step_trace", "step_compile", "import", "callers_programs", "harness")) == 50.0
    # with four readers on a line the remainder is ``setup_s`` less their sum and the import: no reader, a remainder is no measurement
    assert line["harness"] == 50.0 - sum(read(m, record()) for m in READERS) - line["import"]
    assert line["engine_init_parts"] == {"mesh": 0.25, "shard_state": 1.5, "optimizer": 2.0, "rest": 0.5, "after": 0.75}
    assert line["step_first_calls"]["cache_fetch"] == 9.0 and line["init_first_calls"]["compile"] == 2.0


def test_a_first_call_inside_the_window_is_not_in_the_result():
    quiet = record()
    # a new shape of batch met in the window: 5 s of the step's first calls, 3 of them compile, and the process's totals with them
    rose = {call("train", "lower"): 1.5, call("train", "compile"): 3.0, call("train", "other"): 0.5,
            "program_lower_seconds_total": 1.5, "program_compile_seconds_total": 3.0, "train_steps_total": 100.0}
    busy = record(rise=rose)
    for series, seconds in rose.items():
        busy["program"]["counters"][series] += seconds
    for metric in READERS:
        assert read(metric, busy) == read(metric, quiet)
    assert busy["extras"]["setup_timeline_s"] == quiet["extras"]["setup_timeline_s"]


@pytest.mark.parametrize("metric", list(READERS))
@pytest.mark.parametrize("why,rec", [
    ("the parent's program has neither family of counters", record(program={"counters": {"train_steps_total": 120.0, "program_compile_seconds_total": 20.0}, "gauges": {}})),
    ("the first calls are counted and the construction is not", record(program={"counters": {k: v for k, v in TOTALS.items() if not k.startswith("engine_init")}, "gauges": {}})),
    ("a record with no set-up", dict(record(), end_to_end={})),
    ("not a training record", {"end_to_end": {}, "summary": {"tokens_total": 0}}),
])
def test_a_reader_gives_none_without_the_counters_never_a_partial_number(why, rec, metric):
    rec = dict(rec)  # the cases of one ``why`` share the parametrised record
    assert read(metric, rec) is None, why
    assert "setup_timeline_s" not in rec.get("extras", {})


@pytest.mark.parametrize("metric", list(READERS))
def test_a_setup_reader_states_the_facts_the_manifest_needs(metric):
    mod = mf.metric_module(metric)
    assert mod.SOURCE == "program_counter" and mod.BETTER == "lower" and mod.MOVES == "setup_s" and mod.UNIT == "s"
    assert mod.LAYER == READERS[metric]
    assert mod.read({"end_to_end": {}, "summary": {"tokens_total": 0}}) is None
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == metric)
    assert tuple(entry[k] for k in ("unit", "better", "source", "layer", "moves")) == (mod.UNIT, mod.BETTER, mod.SOURCE, mod.LAYER, mod.MOVES)
    assert sorted(entry["workloads"]) == sorted(w["name"] for w in MANIFEST["workloads"])  # every cell of the manifest, each once: the rule, no count
    assert set(entry) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}


def test_the_manifest_lists_the_readers_by_name_and_has_no_problems():
    assert mf.problems(MANIFEST) == []
    listed = [m["name"] for m in MANIFEST["per_layer"]]
    assert all(listed.count(metric) == 1 for metric in READERS)  # found by name: where an entry stands is nobody's rule
    assert next(m for m in MANIFEST["per_layer"] if m["name"] == "setup_program_s")["workloads"] == ["olmo-1b.pretrain-z3"]  # stays as it was, OLMo's alone


@pytest.mark.parametrize("cell", ["olmo-1b.pretrain-z3", "smallthinker-21b-l4e8.pretrain-16k"])  # one dense, one routed
def test_the_readers_on_a_rehearsals_record_give_numbers_that_sum_to_setup_s(cell, clean_env):
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("the dense cell shards over four devices")
    before = program.totals()  # the process is the test session's: what earlier tests' engines counted is in the totals too
    rec = rehearsal_record(mf.cell(MANIFEST, cell))
    assert rec["correct"] and rec["compiles_in_window"] == 0
    values = {metric: read(metric, rec) for metric in READERS}
    assert all(isinstance(v, float) for v in values.values())
    line = rec["extras"]["setup_timeline_s"]
    assert sum(line[k] for k in ("engine_init", "step_trace", "step_compile", "import", "callers_programs", "harness")) == pytest.approx(rec["end_to_end"]["setup_s"], abs=1e-3)
    at_start = setup_timeline.at_window_start(rec)
    mine = lambda series: at_start[series] - before.get(series, 0.0)  # this rehearsal's own seconds, before its window
    assert set(line["engine_init_parts"]) >= {"mesh", "shard_state", "optimizer", "rest"}
    assert all(mine(part(p)) > 0 for p in ("mesh", "shard_state", "optimizer", "rest"))
    assert mine(call("train", "compile")) > 0 and mine(call("train", "flops_count")) > 0 and mine(call("init", "compile")) > 0
    # the model's init and the plain reference are the caller's: they reached the backend in no span of the program's
    caller = sum(mine(f"program_{p}_seconds_total") for p in setup_timeline.BACKEND) \
        - sum(mine(call(f, p)) for f in ("init", "train") for p in setup_timeline.BACKEND)
    assert caller > 0 and values["setup_callers_programs_s"] >= caller - 1e-6  # the reader's is the process's: this rehearsal's and what earlier tests' callers compiled
    own = sum(mine(part(p)) for p in line["engine_init_parts"]) + sum(mine(call("train", p)) for p in line["step_first_calls"] if p != "cache_fetch")
    assert own + caller < rec["end_to_end"]["setup_s"]  # nothing is counted twice: this cell's parts fit inside its set-up
    # nothing of the window is in the result: the window's rise is what the totals have gained since
    assert program.totals()["train_steps_total"] - at_start["train_steps_total"] == rec["train"]["steps"]
