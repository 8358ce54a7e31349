"""Named regions inside the compiled training step (``telemetry/tracing.py::region``,
``telemetry/profiler.py::region_card`` / ``region_times``): every device operation of the three configurations' steps
belongs to a part of a layer and to a phase; the names survive ``block_fn``'s replay, ``jax.checkpoint``,
``custom_vjp``, ``shard_map`` and ``lax.cond``; and a recorded trace reduces to the table by hand arithmetic.
All on the CPU, at each configuration's rehearsal width (``benchmarks/configs/*.json``, ``rehearse``)."""

import collections
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import CausalLM, TransformerConfig
from deepspeed_tpu.models.transformer import block_fn
from deepspeed_tpu.parallel.mesh import initialize_mesh, reset_mesh
from deepspeed_tpu.runtime.config import MeshConfig
from deepspeed_tpu.runtime.zero import overlap
from deepspeed_tpu.telemetry import get_registry, get_tracer, profiler, tracing

ROOT = os.path.join(os.path.dirname(__file__), "..", "..")
FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
CELLS = {"olmo-1b": dict(remat=False, zero=True), "kimi-linear-48b-l5e8": dict(remat=True, zero=False),
         "kimi-vl-a3b-l6e8": dict(remat=True, zero=False)}
# the closed list (docs/OBSERVABILITY.md, "Regions")
REGIONS = {"embed", "norm", "mixer/proj", "mixer/rope", "mixer/kernel", "mixer/index", "mixer/select", "mixer/index_loss", "mixer/conv", "mixer/diff", "mixer/memory", "ffn/dense", "ffn/shared", "ffn/router", "ffn/rows",
           "ffn/cond", "branch/usual", "branch/every_pair", "ffn/experts", "head", "optimizer", "zero/gather", "zero/reduce",
           "zero/regather", "block"}
REGIONS |= {"exit_gate", "loop_step"}  # PR 63: a looped stack's passes (the one body of the scan over them) and its gate
REGIONS |= {"ffn/exchange"}  # PR 66: how a routed layer's held experts meet their rows on a mesh: the rows' two exchanges, or the parts' sum


@pytest.fixture(scope="module", autouse=True)
def _compiled_programs_dropped():
    """A worker that has compiled enough large CPU programs dies inside XLA's CPU compiler at whichever test compiles the
    next (``tests/unit/test_moe_sum_rows.py`` has the story; PR 68's whole run lost one of this module's the same way, in
    the hybrid cell's step): what the process holds is dropped before this module and after it."""
    jax.clear_caches()
    yield
    jax.clear_caches()


HEAVY = ("dot", "convolution", "custom-call")
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "collective-permute", "all-to-all")


@pytest.fixture(scope="module", autouse=True)
def _names_in_the_cache_key():
    """JAX leaves metadata out of the persistent compile cache's key: an entry another tree wrote for the same
    program would come back without this tree's names. These tests read names, so they key the cache on them."""
    was = jax.config.jax_compilation_cache_include_metadata_in_key
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    yield
    jax.config.update("jax_compilation_cache_include_metadata_in_key", was)


def _hashable(v):
    return tuple(_hashable(x) for x in v) if isinstance(v, list) else v


def _rehearsal(name):
    """(model, the trainer's dictionary, its mesh, chips) of a configuration at its rehearsal width."""
    with open(os.path.join(ROOT, "benchmarks", "configs", f"{name}.json")) as f:
        cfg = json.load(f)
    program = dict(cfg["program"], **cfg["rehearse"].get("program", {}))
    dtype = jnp.bfloat16 if program.pop("dtype", None) == "bfloat16" else jnp.float32
    model = CausalLM(TransformerConfig(**{k: _hashable(v) for k, v in program.items()}, dtype=dtype))
    trainer = dict(cfg["trainer"], **cfg["rehearse"].get("trainer", {}))
    mesh = trainer.pop("mesh")
    return model, trainer, mesh, int(np.prod(list(mesh.values())))


_STEPS = {}


def _step(name, monkeypatch=None):
    """One optimizer step of the engine at the rehearsal width, once a configuration: (the model, the step program,
    its arguments' shapes, the first-call span's attributes). OLMo's runs under ``zero/overlap.py``'s plan, as on a TPU
    (the rehearsal's leaves are under the persistence threshold, which is therefore 0 here)."""
    if name in _STEPS:
        return _STEPS[name]
    model, trainer, mesh, chips = _rehearsal(name)
    if CELLS[name]["zero"]:
        trainer["zero_optimization"] = dict(trainer["zero_optimization"], stage3_param_persistence_threshold=0,
                                            stage3_max_live_parameters=20000)
    seq = 64
    params = model.init(jax.random.PRNGKey(0), {"input_ids": np.zeros((1, seq), np.int32)})
    backend = overlap._backend
    overlap._backend = lambda: "tpu"
    reset_mesh()
    try:
        topo = initialize_mesh(MeshConfig.from_dict(mesh), devices=jax.devices()[:chips], force=True)
        engine, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params, mesh=topo, config=trainer)
        kept, run = {}, engine._step_program

        def keep(kind, program, args, batch):
            kept["program"], kept["args"] = program, jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding) if isinstance(x, jax.Array) else x, args)
            return run(kind, program, args, batch)

        engine._step_program = keep
        ids = np.random.RandomState(0).randint(0, model.cfg.vocab_size, (trainer["train_micro_batch_size_per_gpu"] * chips, seq))
        loss = engine.forward({"input_ids": ids.astype(np.int32)})
        engine.backward(loss)
        engine.step()
        assert np.isfinite(float(loss))
    finally:
        overlap._backend = backend
        reset_mesh()
    said = [s["attrs"] for s in get_tracer().spans() if s["name"] == "program/first_call" and s["attrs"].get("family") == "train"][-1]
    _STEPS[name] = (model, kept["program"], kept["args"], said)
    return _STEPS[name]


def _card(name):
    model, program, shapes, _ = _step(name)
    return profiler.region_card(program.lower(*shapes).compile().as_text())


# ------------------------------------------------------------------ (a) the compiled step
@pytest.mark.parametrize("name", list(CELLS))
def test_every_product_call_and_collective_of_the_compiled_step_has_a_region(name):
    card = _card(name)
    assert card["module"] == "jit_fused_step" and set(card["regions"]) <= REGIONS
    entries = card["instructions"]
    of = lambda *opcodes: {own: e for own, e in entries.items() if e["opcode"].replace("-start", "").replace("-done", "") in opcodes}
    heavy, collectives = of(*HEAVY), of(*COLLECTIVES)
    assert len(of("dot", "convolution")) > 10
    assert not {own: e["opcode"] for own, e in {**heavy, **collectives}.items() if e["region"] is None}
    phases = {e["phase"] for e in entries.values() if e["region"]}
    assert phases == ({"forward", "recomputed", "backward", "update"} if CELLS[name]["remat"] else {"forward", "backward", "update"})
    assert {e["region"] for e in entries.values() if e["phase"] == "update" and e["region"]} >= {"optimizer"}
    found = {e["region"] for e in entries.values()}
    if CELLS[name]["zero"]:  # the bucket plan's gathers, rings and second gathers, as collectives of their own regions
        assert {"zero/gather", "zero/reduce"} <= {e["region"] for e in collectives.values()}
        assert "zero/regather" in found  # the CPU's compiler makes that all-gather of a small shard a copy
        assert {"embed", "norm", "mixer/proj", "mixer/rope", "mixer/kernel", "ffn/dense", "head", "optimizer", "block"} <= found
    else:
        assert {"embed", "norm", "mixer/proj", "mixer/rope", "mixer/kernel", "ffn/dense", "ffn/shared", "ffn/router", "ffn/rows",
                "ffn/experts", "head", "optimizer", "block"} <= found


def _equations(jaxpr, stack=""):
    """(primitive, the name stack under its enclosing equations') of every equation, sub-jaxprs too."""
    for eqn in jaxpr.eqns:
        here = f"{stack}/{eqn.source_info.name_stack}"
        yield eqn.primitive.name, here
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (tuple, list)) else (value,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _equations(sub, here)


def _products_by_region(jaxpr, phase):
    counts = collections.Counter()
    for primitive, stack in _equations(jaxpr):
        names, said = profiler.regions_of(stack, REGIONS)
        if primitive in ("dot_general", "conv_general_dilated", "ragged_dot_general", "ragged_dot") and said == phase and "block" in names:
            counts[names[-1]] += 1
    return counts


@pytest.mark.parametrize("name", ["kimi-linear-48b-l5e8", "kimi-vl-a3b-l6e8"])
def test_replayed_layers_carry_the_names_of_the_traced_one(name):
    """``block_fn`` traces a kind once and replays its equations for every further layer: the step's forward has, a
    region, as many products as ONE trace of each kind has, times that kind's layers."""
    model, program, shapes, said = _step(name)
    cfg = model.cfg
    assert said["block_traces"] == len(set(cfg.kinds)) < cfg.n_layers
    whole = _products_by_region(jax.make_jaxpr(program)(*shapes).jaxpr, "forward")
    layers = model.init(jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 64), np.int32)})
    x, positions = jnp.zeros((1, 64, cfg.d_model), cfg.dtype), jnp.zeros((1, 64), jnp.int32)
    want = collections.Counter()
    for kind, n in collections.Counter(cfg.kinds).items():
        i = cfg.kinds.index(kind)
        one = jax.make_jaxpr(jax.grad(lambda p: jnp.sum(block_fn(cfg, kind, True, False)(p, x, positions, None, None, {})[0][0].astype(jnp.float32))))(
            layers[f"layer_{i}"])
        for region, products in _products_by_region(one.jaxpr, "forward").items():
            want[region] += n * products
    assert whole == want and set(whole) >= {"mixer/proj", "ffn/dense", "ffn/shared", "ffn/router", "ffn/experts"}


def test_the_first_call_line_says_what_share_of_the_products_is_the_second_forward():
    for name, cell in CELLS.items():
        said = _step(name)[3]
        flops = {phase: said[f"flops_{phase}"] for phase in tracing.PHASES}
        assert flops["forward"] > 0 and flops["backward"] > flops["forward"] and flops["update"] > 0
        # a checkpointed hybrid block keeps its projections' results (PR 40): the second forward is still a phase, of
        # elementwise work and, at this width off the chip, the attention fallback's own products over 64 positions (which
        # the flash kernel, whose output is kept, does not make again): 21% and 26% of the forward here where 78% and 81% were
        assert (flops["recomputed"] > 0) == cell["remat"] and flops["recomputed"] <= 0.3 * flops["forward"]
        assert said.get("remat_keeps") == {"kimi-linear-48b-l5e8": "flash_attention+kda_scan+projection+routed_ffn", "kimi-vl-a3b-l6e8": "flash_attention+projection+routed_ffn"}.get(name)
        assert set(said["region_trace_s"]) <= REGIONS and said["region_trace_s"]["optimizer"] > 0
        assert sum(said["region_trace_s"].values()) < said["total_s"]


def test_the_routed_layers_conditional_names_its_branches():
    """Above the buffer's size the routed part is a ``lax.cond``: what the conditional adds itself falls to
    ``ffn/cond``, and the two branches' rows and products lie under ``branch/usual`` and ``branch/every_pair``."""
    from deepspeed_tpu.moe.sharded_moe import routed_part

    n, d, k, held, experts = 2048, 16, 2, 2, 64
    tokens, wg = jnp.ones((n, d)), jnp.ones((held, d, 32))
    idx = jnp.zeros((n, k), jnp.int32).at[:, 1].set(1)
    fn = lambda t, w: jnp.sum(routed_part(t, idx, jnp.ones((n, k)), w, w, jnp.ones((held, 32, d)), 0, experts, False)[0])
    stacks = [stack for _, stack in _equations(jax.make_jaxpr(jax.grad(fn, argnums=(0, 1)))(tokens, wg).jaxpr)]
    within = collections.Counter(tuple(profiler.regions_of(stack, REGIONS)[0]) for stack in stacks)
    assert within[("ffn/cond",)] >= 1
    for branch in ("branch/usual", "branch/every_pair"):
        assert {("ffn/cond", branch, part) for part in ("ffn/router", "ffn/rows", "ffn/experts")} <= set(within)


# ------------------------------------------------------------------ (b) a recorded trace
def _recorded():
    with open(os.path.join(FIXTURES, "step_regions.trace.json")) as f:
        trace = json.load(f)
    with open(os.path.join(FIXTURES, "step_regions.hlo.txt")) as f:
        return trace, profiler.region_card(f.read(), REGIONS)


def test_the_card_reads_fused_instructions_and_what_the_compiler_left_unnamed():
    _, card = _recorded()
    entry = card["instructions"]
    assert card["module"] == "jit_step"
    # a fusion is its product's region, whatever instruction the compiler named it after, and says what else it holds
    assert entry["fusion.1"]["region"] == "mixer/proj" and entry["fusion.1"]["mixed"] and entry["fusion.1"]["members"] == {"norm": 1, "mixer/proj": 1}
    assert (entry["fusion.2"]["region"], entry["fusion.2"]["phase"], entry["fusion.2"]["mixed"]) == ("optimizer", "update", False)
    assert (entry["fusion.3"]["region"], entry["fusion.3"]["phase"]) == ("ffn/dense", "backward")
    # the innermost region, the enclosing ones kept; the forward made a second time is a phase of its own
    assert entry["fusion.4"] == {"opcode": "fusion", "region": "ffn/rows", "phase": "recomputed", "within": ["block", "ffn/cond", "branch/usual"],
                                 "how": "members", "members": {"ffn/rows": 1}, "mixed": False}
    assert (entry["conditional.1"]["region"], entry["conditional.1"]["phase"]) == ("ffn/cond", "recomputed")
    assert (entry["copy.9"]["region"], entry["copy.9"]["how"]) == ("mixer/proj", "operand")  # a layout copy: its producer's
    assert entry["mystery.1"]["region"] is None and "p0.1" not in entry and "tuple.0" not in entry


def test_region_times_of_a_recorded_trace():
    """Two steps on four chips (ns a step and chip): fusion.1 100, attn.5 200, a ``while`` of 300 around two fusion.3
    of 100, a ``conditional`` of 200 around a fusion.4 of 160, copy.9 50, fusion.2 100 (200 on one chip), mystery.1
    50; between the steps another program whose operation is also called fusion.1."""
    trace, card = _recorded()
    got = profiler.region_times(trace, card)
    ns = lambda table: {region: {phase: round(s * 1e9, 3) for phase, s in row.items()} for region, row in table.items()}
    assert (got["module"], got["devices"], got["steps"]) == ("jit_step", 4, 2)
    assert ns(got["table"]) == {
        "mixer/proj": {"forward": 150.0},     # the fusion and the copy that inherits from it; NOT the other program's 300
        "mixer/kernel": {"forward": 200.0},
        "block": {"backward": 100.0},         # the while's self time: 300 less its body's two operations
        "ffn/dense": {"backward": 200.0},
        "ffn/cond": {"recomputed": 40.0},     # the conditional's self time
        "ffn/rows": {"recomputed": 160.0},
        "optimizer": {"update": 125.0},       # the mean over the four chips
        "unattributed": {"update": 50.0}}
    total = sum(s for row in got["table"].values() for s in row.values())
    assert total == pytest.approx(got["step_self_s"]) and got["step_self_s"] == pytest.approx(got["step_module_s"]) == pytest.approx(1025e-9)
    assert got["unattributed_share"] == pytest.approx(50 / 1025, abs=1e-5) and "note" not in got
    assert got["mixed_s"] == pytest.approx(100e-9) and got["mixed"][0]["members"] == {"norm": 1, "mixer/proj": 1}
    assert ns({"k": got["kernels"]})["k"] == {"attn": 200.0}
    assert ns(got["within"])["branch/usual"] == {"ffn/rows": 160.0} and ns(got["within"])["ffn/cond"] == {"ffn/rows": 160.0}
    # an executable fetched from a cache that another tree wrote carries no names: the summary says so in words
    bare = profiler.region_times(trace, {"module": "jit_step", "instructions": {}})
    assert bare["unattributed_share"] == 1.0 and "persistent compile cache" in bare["note"]
    assert profiler.region_times(trace, dict(card, module="jit_absent"))["steps"] == 0
    # the first chip idles from 1000 to 1200 and from 1500 to 2000 ns: 60 of it under ``train/step`` [1900, 1960]
    assert profiler.idle_by_span(trace) == {"between spans": pytest.approx(640e-9), "train/step": pytest.approx(60e-9)}


def test_a_trainers_capture_is_reduced_with_its_programs_regions(tmp_path, monkeypatch):
    """The trainer's wiring: armed, the capture starts at the first step that makes no first call, takes
    ``quanta`` steps and is reduced with the card of the program the trainer described."""
    trace, card = _recorded()
    prof = profiler.DeviceProfiler(out_dir=str(tmp_path), quanta=2)
    prof._start_trace = lambda trace_dir: None
    prof._stop_trace = lambda: None
    with open(os.path.join(FIXTURES, "step_regions.hlo.txt")) as f:
        text = f.read()
    asked = []
    prof.describe(lambda: asked.append(1) or text)
    monkeypatch.setattr(profiler, "load_xplane", lambda path, **how: trace)
    monkeypatch.setattr(profiler, "find_xplane", lambda root: "recorded")
    monkeypatch.setattr(tracing, "_REGIONS_SEEN", set(REGIONS))
    prof.arm()
    for step in range(3):
        assert not asked  # the card is built when a capture is reduced, never before
        assert prof.closes_next() == (step == 2)
        prof.note_quantum("train/step", step=step)
    summary = prof.summary()
    assert asked == [1] and summary["n_quanta"] == 2 and summary["trace"] == "ok"
    assert summary["regions"]["table"]["optimizer"] == {"update": pytest.approx(125e-9)}
    assert set(summary["capture_cost_s"]) == {"start", "stop", "reduce"} and "train/step" in summary["idle_by_span"]


# ------------------------------------------------------------------ (c) what the instruments cost
def test_a_region_outside_a_first_call_is_the_scope_alone(monkeypatch):
    reg, tracer = get_registry(), get_tracer()
    series = lambda: {k: v for k, v in reg.series() if k.startswith("program_regions_traced_total")}
    before, ring = series(), len(tracer.spans())
    def doubled(x):
        scope = tracing.region("norm")
        assert type(scope) is type(jax.named_scope("norm"))  # no first call is open: nothing to time
        with scope:
            return x * 2

    assert doubled(1.0) == 2.0  # outside any trace: a scope that nothing reads
    assert "norm" in str(jax.make_jaxpr(doubled)(1.0).eqns[0].source_info.name_stack)
    assert series() == before and len(tracer.spans()) == ring  # no choice was made: nothing is counted
    with tracing.region("ffn/experts", path="xla"):
        pass
    assert tracing.regions_traced("ffn/experts", path="xla") >= 1
    # telemetry off: the scope and nothing else, whatever the site hands over
    counted = tracing.regions_traced("ffn/experts")
    monkeypatch.setattr(tracer, "enabled", False)
    with tracer.span("program/first_call"):
        scope = tracing.region("ffn/experts", path="xla")
    assert type(scope) is type(jax.named_scope("x")) and tracing.regions_traced("ffn/experts") == counted


def test_the_flash_kernels_say_how_many_tiles_a_trip_takes_and_the_first_call_line_repeats_it(monkeypatch):
    """``program_regions_traced_total{region="mixer/kernel", tiles_a_trip}``: the forward's choice (``tiles_a_trip``: 2
    where the mask has an unmasked run of two tiles, and they fit) and the backward's one, a call site a pass; the
    trainer's line reads the two series that rose as ``tiles_a_trip_fwd`` / ``tiles_a_trip_bwd``, for a model of one
    plain kind too (OLMo's), which says nothing else of its kinds."""
    from deepspeed_tpu.ops import masks
    from deepspeed_tpu.ops.pallas import flash_attention as F
    from deepspeed_tpu.runtime import engine as trainer

    assert F.TILES_A_TRIP == {f"tiles_a_trip_{p}": ("mixer/kernel", ("1", "2"), "tiles_a_trip", {"pass": p}) for p in ("fwd", "bwd")}
    cfg = TransformerConfig(vocab_size=64, n_layers=2, n_heads=2, n_kv_heads=2, d_model=32, d_ff=48, max_seq_len=64)
    notes_since = lambda cfg, before: trainer.DeepSpeedEngine._layer_kind_notes(type("E", (), {"module": type("M", (), {"cfg": cfg})}), before)
    assert set(cfg.kinds) == {("full", "dense")} and notes_since(cfg, trainer._paths_traced()) == {}
    monkeypatch.setattr(F, "DEFAULT_BQ", 16)
    monkeypatch.setattr(F, "DEFAULT_BK", 16)
    q = jnp.ones((1, 64, 2, 8), jnp.bfloat16)
    cases = [(p, n) for p in ("fwd", "bwd") for n in ("1", "2")]
    series = lambda pass_, n, op="flash": tracing.regions_traced("mixer/kernel", op=op, tiles_a_trip=n, **{"pass": pass_})
    grad = lambda q, **kw: jax.grad(lambda q: jnp.sum(F.flash_attention(q, q, q, interpret=True, **kw).astype(jnp.float32)))(q)
    before, was = trainer._paths_traced(), [series(*case) for case in cases]
    grad(q)  # a causal walk of four tiles: whole runs of up to three, so the forward pairs; the fused backward keeps one
    assert [series(*case) - w for case, w in zip(cases, was)] == [0, 1, 1, 0]
    assert notes_since(cfg, before) == {"tiles_a_trip_fwd": "2", "tiles_a_trip_bwd": "1"}
    before = trainer._paths_traced()
    grad(q, window=16)  # a window of a tile's width: every visited tile crosses an edge, one tile a trip
    assert notes_since(cfg, before) == {"tiles_a_trip_fwd": "1", "tiles_a_trip_bwd": "1", "window_tiles": "7/16", "window_tile": "16x16"}  # (PR 53) the band's walk: 1 + 2 + 2 + 2; (PR 69) and its tile
    before, blockdiff = trainer._paths_traced(), series("fwd", "2", op="blockdiff")
    grad(jnp.ones((1, 128, 2, 8), jnp.bfloat16), mask=masks.BlockDiffusion(4, 64))  # under a mask of its own walk the label rides on that op's series
    grad(q[:, :32], bias=jnp.zeros((1, 1, 32, 32)))  # a bias of two tiles a side: no run of two, and the split backward
    assert series("fwd", "2", op="blockdiff") == blockdiff + 1
    assert notes_since(cfg, before) == {"tiles_a_trip_fwd": "1+2", "tiles_a_trip_bwd": "1"}  # the series that rose, "+" between


def test_regions_inside_a_first_call_add_their_python_seconds_to_its_span():
    tracer = get_tracer()
    with tracer.span("program/first_call", family="toy") as sp:
        with tracing.region("ffn/router"):
            with tracing.region("ffn/rows", path="xla"):
                sum(range(20000))
        with tracing.region("ffn/router"):
            pass
    took = sp.attrs["region_trace_s"]
    assert set(took) == {"ffn/router", "ffn/rows"} and took["ffn/rows"] > 0 and took["ffn/router"] >= 0
    assert sum(took.values()) <= [s for s in tracer.spans() if s["id"] == sp.id][0]["dur_s"]  # self times: nothing is counted twice


def test_an_unarmed_trainer_step_touches_the_profiler_once(monkeypatch):
    from deepspeed_tpu.models import gpt2_tiny
    from deepspeed_tpu.runtime import engine as engine_module
    import dataclasses

    profiler._reset_for_tests()
    reset_mesh()
    model = CausalLM(dataclasses.replace(gpt2_tiny(), vocab_size=128, n_layers=1))
    ids = np.zeros((len(jax.devices()), 16), np.int32)
    params = model.init(jax.random.PRNGKey(0), {"input_ids": ids})
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params, config={
        "train_micro_batch_size_per_gpu": 1, "optimizer": {"type": "adam", "params": {"lr": 1e-3}}, "steps_per_print": 10**9})
    asked = []
    monkeypatch.setattr(engine_module.device_profiler, "get_device_profiler", lambda: asked.append(1))
    for step in range(3):
        loss = engine.forward({"input_ids": ids})
        engine.backward(loss)
        engine.step()
        # the first step also asks once where it makes its first call, to describe the program to a profiler that is there
        assert len(asked) == (2 if step == 0 else 1), (step, asked)
        asked.clear()
    reset_mesh()


# ------------------------------------------------------------------ the compute copy and the gradient's dtype (PR 56)
@pytest.mark.parametrize("case,extra,chips,want", [
    ("carried", {"bf16": {"enabled": True}}, 1, ("fused_step", "carried", "bfloat16")),
    # the split path differentiates at the carried copy too, and its gradient leaves the program in float32, widened inside it
    ("accumulated", {"bf16": {"enabled": True}, "gradient_accumulation_steps": 2}, 1, ("fwd_bwd", "carried", "float32")),
    ("fp32", {}, 1, ("fused_step", "cast", "float32")),  # one dtype: there is no copy
    # the optimizer's state on shards of chips that each hold the whole master: the update's results are gathered, no copy with them
    ("state_on_shards", {"bf16": {"enabled": True}, "zero_optimization": {"stage": 1}}, 4, ("fused_step", "cast", "bfloat16")),
])
def test_the_first_call_line_says_where_the_compute_copy_came_from_and_the_gradients_dtype(case, extra, chips, want):
    """``program_regions_traced_total{region="optimizer", path, grads}`` is counted where ``_build_compiled_fns``
    takes the copy it differentiates at (once a trace of a step program), and the trainer's first-call line and span
    say it as ``compute_copy=carried|cast grads=<dtype>``, ahead of ``grad_reduce=``."""
    import logging

    class Lines(logging.Handler):
        lines = []

        def emit(self, record):
            self.lines.append(record.getMessage())

    model = CausalLM(TransformerConfig(vocab_size=128, n_layers=1, n_heads=2, d_model=32, max_seq_len=32,
                                       dtype=jnp.bfloat16 if "bf16" in extra else jnp.float32))
    params = model.init(jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 16), np.int32)})
    bucket, copy, grads = want
    series = lambda: get_registry().total("program_regions_traced_total", region="optimizer", path=copy + "_copy" * (copy == "carried"), grads=grads)
    every = lambda: tracing.regions_traced("optimizer")
    handler, logger = Lines(), logging.getLogger("deepspeed_tpu")
    reset_mesh()
    logger.addHandler(handler)
    try:
        topo = initialize_mesh(MeshConfig.from_dict({"data": chips}), devices=jax.devices()[:chips], force=True)
        engine, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params, mesh=topo, config={
            "train_micro_batch_size_per_gpu": 1, "optimizer": {"type": "adam", "params": {"lr": 1e-3}}, "steps_per_print": 10**9, **extra})
        before, all_before = series(), every()
        for _ in range(2 * engine.gradient_accumulation_steps):
            loss = engine.forward({"input_ids": np.zeros((chips, 16), np.int32)})
            engine.backward(loss)
            engine.step()
    finally:
        logger.removeHandler(handler)
        reset_mesh()
    assert (series() - before, every() - all_before) == (1.0, 1.0)  # one trace of one step program, and no other series rose
    assert (engine._params_c is not None) == (copy == "carried")
    lines = [l for l in handler.lines if l.startswith(f"program first call: family=train bucket={bucket} ")]  # a step that is not fused has the accumulator's and the update's beside it
    assert len(lines) == 1 and f" compute_copy={copy} grads={grads} grad_reduce=xla " in lines[0], lines
    said = [s["attrs"] for s in get_tracer().spans() if s["name"] == "program/first_call" and s["attrs"].get("bucket") == bucket][-1]
    assert (said["family"], said["bucket"], said["compute_copy"], said["grads"]) == ("train",) + want
