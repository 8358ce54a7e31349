"""Set-up's timeline inside the program (docs/OBSERVABILITY.md, "Set-up: what it
writes, and who reads it"): ``init/engine`` over the whole of construction, its
parts counted where they end, every program the engine sends to the backend
inside a ``program/first_call`` span of family ``init`` or ``train`` with its
seconds on ``program_first_call_seconds_total``, the package's import on a
gauge, and the capture of set-up (``DS_TPU_PROFILE=setup``), on toy models on
the CPU."""

import dataclasses
import json
import time

import jax
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import CausalLM, gpt2_tiny
from deepspeed_tpu.parallel.mesh import initialize_mesh
from deepspeed_tpu.runtime.config import MeshConfig
from deepspeed_tpu.runtime.engine import DeepSpeedEngine
from deepspeed_tpu.telemetry import get_registry, get_tracer, profiler
from deepspeed_tpu.telemetry.costs import first_call
from deepspeed_tpu.utils.compile_cache import register_cache_metrics

PARTS = ("mesh", "shard_state", "optimizer", "rest")
BACKEND = ("trace", "lower", "compile")
BATCH = {"input_ids": np.zeros((2, 16), np.int32)}
CASES = {
    "fused, bf16 with a carried copy": {"bf16": {"enabled": True}},
    "not fused: two micro-batches a step, float32": {"gradient_accumulation_steps": 2},
}


@pytest.fixture(scope="module", autouse=True)
def _compiled_programs_dropped():
    """A worker that has compiled enough large CPU programs dies inside XLA's CPU compiler at whichever test compiles the
    next (``tests/unit/test_moe_sum_rows.py`` has the story; PR 68's whole run lost one here the same way): what the
    process holds is dropped before this module and after it."""
    jax.clear_caches()
    yield
    jax.clear_caches()


def counters():
    return get_registry().snapshot()["counters"]


def rise(before, name, **labels):
    """What the series of ``name`` whose labels include ``labels`` rose by since ``before``, summed."""
    want = {f'{k}="{v}"' for k, v in labels.items()}
    return sum(v - before.get(series, 0.0) for series, v in counters().items()
               if series.split("{")[0] == name and want <= set(series.partition("{")[2].rstrip("}").split(",")))


def toy():
    model = CausalLM(dataclasses.replace(gpt2_tiny(), vocab_size=256))
    return model, model.init(jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 16), np.int32)})


def build(config, cls=None, made=None, **more):
    model, params = made or toy()
    topo = initialize_mesh(MeshConfig.from_dict({}), devices=jax.devices()[:1], force=True)
    config = dict({"train_micro_batch_size_per_gpu": 2, "optimizer": {"type": "adam", "params": {"lr": 1e-3}}, "steps_per_print": 10 ** 6}, **config)
    if cls is not None:
        return cls(model=model, model_parameters=params, mesh=topo, config=config, **more)
    return deepspeed_tpu.initialize(model=model, model_parameters=params, mesh=topo, config=config)[0]


@pytest.fixture(scope="module", params=list(CASES))
def run(request):
    """An engine built and stepped twice, the caller compiling nothing between: the spans and the counters' rise."""
    made = toy()  # the caller's own programs and traces (``model.init``) lie ahead of the stretch that is looked at
    register_cache_metrics(jax)
    get_tracer().clear()
    before = counters()
    engine = build(CASES[request.param], made=made)
    for _ in range(2 * engine.gradient_accumulation_steps):
        engine.backward(engine.forward(BATCH))
        engine.step()
    return {"case": request.param, "engine": engine, "before": before, "spans": get_tracer().spans()}


def test_init_engine_is_the_parent_of_the_three_parts_and_the_parts_sum_to_it(run):
    by_name = {s["name"]: s for s in run["spans"] if s["name"].startswith("init/")}
    root = by_name["init/engine"]
    assert root["parent"] == 0 and {by_name[f"init/{p}"]["parent"] for p in PARTS[:3]} == {root["id"]}
    assert set(by_name["init/shard_state"]["attrs"]["phase_s"]) == {"cast", "plan", "place"}
    assert set(by_name["init/optimizer"]["attrs"]["phase_s"]) == {"build", "init_state"}
    took = {part: rise(run["before"], "engine_init_seconds_total", part=part) for part in PARTS}
    assert all(s > 0 for s in took.values())
    assert sum(took.values()) == pytest.approx(root["dur_s"], abs=2e-3)  # the root's stamps lie a few lines outside the span's
    for part in PARTS[:3]:
        assert took[part] == pytest.approx(by_name[f"init/{part}"]["dur_s"], abs=2e-3)


def test_every_program_between_initialize_and_the_second_step_is_inside_a_first_call(run):
    calls = [s for s in run["spans"] if s["name"] == "program/first_call"]
    assert {s["attrs"]["family"] for s in calls} == {"init", "train"}
    for phase in BACKEND:  # the process-wide counter's rise is the families' sum: nothing reached the backend outside a span
        everywhere = rise(run["before"], f"program_{phase}_seconds_total")
        in_spans = rise(run["before"], "program_first_call_seconds_total", phase=phase)
        assert in_spans == pytest.approx(everywhere, abs=1e-9), (run["case"], phase)
    assert rise(run["before"], "program_compile_seconds_total") > 0
    assert rise(run["before"], "program_first_calls_total") == sum(s["attrs"]["programs"] for s in calls)
    buckets = {(s["attrs"]["family"], s["attrs"]["bucket"]) for s in calls}
    assert {("init", "cast"), ("init", "plan"), ("init", "place"), ("init", "optimizer_state")} <= buckets
    # family ``init`` outside construction: what sets the engine's state up once it is built, counted as the part ``after``
    root = next(s for s in run["spans"] if s["name"] == "init/engine")
    later = [s for s in calls if s["attrs"]["family"] == "init" and s["start_s"] > root["start_s"] + root["dur_s"]]
    assert {s["attrs"]["bucket"] for s in later} == {"overflow_count", "overflow_sum"} | ({"compute_copy"} if run["engine"]._cast_copy is not None else set())
    assert rise(run["before"], "engine_init_seconds_total", part="after") == pytest.approx(sum(s["dur_s"] for s in later), abs=2e-3)
    assert [s["attrs"]["bucket"] for s in calls if s["attrs"]["family"] == "train"][0] in ("fused_step", "fwd_bwd")  # the first line of family train is the step's
    if run["engine"].gradient_accumulation_steps == 1:
        assert [b for f, b in buckets if f == "train"] == ["fused_step"]
    else:
        assert {("train", "fwd_bwd"), ("train", "accumulate"), ("train", "apply_updates")} <= buckets


def test_the_first_calls_counter_is_the_spans_seconds_by_family_and_phase(run):
    calls = [s["attrs"] for s in run["spans"] if s["name"] == "program/first_call"]
    for family in ("init", "train"):
        phases = {k[:-2] for a in calls if a["family"] == family for k in a if k.endswith("_s") and k not in ("total_s", "region_trace_s")}
        assert {"trace", "lower", "compile", "cache_fetch", "other"} <= phases
        for phase in phases:
            on_spans = sum(a.get(phase + "_s", 0.0) for a in calls if a["family"] == family)
            assert rise(run["before"], "program_first_call_seconds_total", family=family, phase=phase) == pytest.approx(on_spans, abs=1e-9)
        total = sum(a["total_s"] for a in calls if a["family"] == family)  # the phases but the fetch, which lies inside compile
        assert sum(rise(run["before"], "program_first_call_seconds_total", family=family, phase=p) for p in phases - {"cache_fetch"}) == pytest.approx(total)
    step = next(a for a in calls if a["bucket"] in ("fused_step", "fwd_bwd"))
    assert step["flops_count_s"] > 0 and step["other_s"] >= 0 and "phase_s" not in step  # the model's one Python trace has its own name


def test_a_first_call_inside_another_raises_nothing_of_its_own():
    register_cache_metrics(jax)
    before = counters()
    with first_call("init", "outer"):
        with first_call("train", "inner"):
            time.sleep(0.002)
    assert rise(before, "program_first_call_seconds_total", family="train") == 0
    assert rise(before, "program_first_call_seconds_total", family="init", phase="other") >= 0.002


def test_a_subclass_constructor_is_under_the_one_root_with_its_own_work_in_the_rest():
    class Slow(DeepSpeedEngine):
        def __init__(self, *args, wait=0.0, **kwargs):
            time.sleep(wait)  # ahead of the base class's constructor, as the pipeline engine builds its stages
            super().__init__(*args, **kwargs)
            time.sleep(wait)  # and after it, as the hybrid engine wires its generation

    get_tracer().clear()
    before = counters()
    engine = build({}, cls=Slow, wait=0.05)
    roots = [s for s in get_tracer().spans() if s["name"] == "init/engine"]
    assert len(roots) == 1 and roots[0]["parent"] == 0 and engine._init_parts_s is None
    assert rise(before, "engine_init_seconds_total", part="rest") >= 0.1
    assert sum(rise(before, "engine_init_seconds_total", part=p) for p in PARTS) == pytest.approx(roots[0]["dur_s"], abs=2e-3)


def test_the_packages_import_is_on_a_gauge():
    assert 0 < get_registry().peek("import_seconds") < 600


# ------------------------------------------------------ the capture of set-up

def test_profile_setup_captures_from_construction_to_the_first_step_that_made_no_first_call(tmp_path, monkeypatch):
    from tests.unit.test_step_stall import _land, recorded

    monkeypatch.setenv("DS_TPU_PROFILE", "setup")
    monkeypatch.setenv("DS_TPU_PROFILE_DIR", str(tmp_path))
    monkeypatch.setattr(profiler.DeviceProfiler, "_start_trace", lambda self, d: _land(recorded(stalled=False), d))
    monkeypatch.setattr(profiler.DeviceProfiler, "_stop_trace", lambda self: None)
    profiler._reset_for_tests()
    keyed_on_metadata = jax.config.jax_compilation_cache_include_metadata_in_key
    try:
        engine = build({})
        prof = profiler.get_device_profiler()
        assert prof.setup and prof.state == "tracing" and prof.quanta_target == 1  # the trace began ahead of ``init/engine``
        states = []
        for _ in range(3):  # two steps with a first call (the step's, the overflow count's sum), then one with none
            engine.backward(engine.forward(BATCH))
            engine.step()
            states.append((prof.state, prof.captures))
        assert states == [("tracing", 0), ("tracing", 0), ("idle", 1)]
        summary = json.load(open(next(tmp_path.glob("capture-*/summary.json"))))
        assert summary["trace"] == "ok" and summary["n_quanta"] == 1 and "regions" not in summary
        assert summary["idle_by_span"]["train/forward/dispatch"] == pytest.approx(0.005)  # the first device's idle time, by innermost span or phase
        assert summary["setup"]["programs"] == []  # the recorded trace has operations and no ``XLA Modules`` line
        assert jax.config.jax_compilation_cache_include_metadata_in_key == keyed_on_metadata  # the cache's key is the run's own
        build({})  # a second engine does not arm the one-shot capture again
        assert (prof.state, prof.captures) == ("idle", 1)
    finally:
        profiler._reset_for_tests()


def test_module_executions_sets_a_programs_first_run_beside_its_later_ones():
    ms = 1e6
    modules = [["jit_fused_step(123)", 100 * ms, 900 * ms, {}], ["jit_init(7)", 10 * ms, 5 * ms, {}],
               ["jit_fused_step(123)", 1100 * ms, 300 * ms, {}], ["jit_fused_step(123)", 1500 * ms, 310 * ms, {}]]
    trace = {"planes": [{"name": "/device:TPU:0", "lines": [{"name": "XLA Modules", "events": modules}]}]}
    assert profiler.module_executions(trace) == [["jit_init(7)", 1, 0.0, 0.005, None], ["jit_fused_step(123)", 3, 0.09, 0.9, 0.305]]
