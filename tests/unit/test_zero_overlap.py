"""``overlap_comm`` at ZeRO stage 3 (``runtime/zero/overlap.py``): where the
gather plan applies, that a block which gathers its own parameters computes
what XLA's partitioner computes, and what the trainer's first-call line says
of the compiled step. The TPU compiler's side is in ``test_chip_compile.py``.
"""

import dataclasses
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import deepspeed_tpu
from deepspeed_tpu.models import CausalLM, gpt2_tiny
from deepspeed_tpu.parallel.mesh import MeshTopology
from deepspeed_tpu.runtime.config import DeepSpeedConfig, MeshConfig
from deepspeed_tpu.runtime.zero import overlap


# a toy's leaves are all under the default persistence threshold: at 0 every one that four devices divide is sharded
SHARD_ALL = {"stage": 3, "stage3_param_persistence_threshold": 0}


# gpt2_tiny's block: sixteen leaves, every one sharded at persistence threshold 0, 12 d^2 + 13 d parameters at d = 64
BLOCK_PARAMS = 12 * 64 * 64 + 13 * 64

N = 8  # the suite's virtual devices (tests/conftest.py): an engine's mesh spans them all, and a plan wants ONE wide axis


def _topo(mesh, n):
    return MeshTopology(MeshConfig.from_dict(mesh), devices=jax.devices()[:n])


def _config(zero, mesh):
    return DeepSpeedConfig({"zero_optimization": zero, "mesh": mesh})


@pytest.fixture
def on_tpu(monkeypatch):
    """The plan is a matter of what the TPU's compiler overlaps, so it asks
    for the backend; the arithmetic is the same on any: told "tpu", the
    virtual CPU devices run it."""
    monkeypatch.setattr(overlap, "_backend", lambda: "tpu")


@pytest.mark.parametrize("backend,zero,mesh,n,planned", [
    ("tpu", {"stage": 3}, {"fsdp": 4}, 4, True),                          # overlap_comm defaults to true at stage 3
    ("tpu", {"stage": 3, "overlap_comm": True}, {"data": 4}, 4, True),    # ZeRO over the data axis
    ("cpu", {"stage": 3}, {"fsdp": 4}, 4, False),
    ("gpu", {"stage": 3}, {"fsdp": 4}, 4, False),
    ("tpu", {"stage": 3}, {"fsdp": 1}, 1, False),                         # one device: nothing to reduce across
    ("tpu", {"stage": 0}, {"data": 4}, 4, False),
    ("tpu", {"stage": 1}, {"fsdp": 4}, 4, False),
    ("tpu", {"stage": 2, "overlap_comm": True}, {"fsdp": 4}, 4, False),   # parameters whole: no gather to transpose
    ("tpu", {"stage": 3, "overlap_comm": False}, {"fsdp": 4}, 4, False),
    ("tpu", {"stage": 3}, {"fsdp": 2, "tensor": 2}, 4, False),            # a block cannot be manual over the whole mesh
    ("tpu", {"stage": 3}, {"data": 2, "fsdp": 2}, 4, False),
])
def test_plan_only_where_it_applies(monkeypatch, backend, zero, mesh, n, planned):
    monkeypatch.setattr(overlap, "_backend", lambda: backend)
    topo = _topo(mesh, n)
    specs = {"w": P("fsdp")}
    plan = overlap.plan_for(_config(zero, mesh), topo, specs)
    assert (plan is not None) == planned
    if planned:
        assert (plan.axis, plan.size, plan.param_specs) == ("data" if "data" in mesh else "fsdp", 4, specs)
        assert plan.max_live == 1_000_000_000  # stage3_max_live_parameters, as the reference has it


@pytest.mark.parametrize("shape,dim", [((8, 6), 0), ((6, 16), 1), ((2, 3, 8), 2), ((12, 5), 0)])
def test_ring_reduce_scatter_is_a_reduce_scatter(shape, dim):
    """Two-way where a chunk has an even number of rows, one-way where not
    ((12, 5) over four devices: three rows a chunk)."""
    mesh = _topo({"fsdp": 4}, 4).mesh
    g = jax.random.normal(jax.random.PRNGKey(0), (4,) + shape, jnp.float32)  # one full gradient a device

    def both(g):
        g = g[0]
        return (overlap._ring_reduce_scatter(g, "fsdp", 4, dim)[None],
                jax.lax.psum_scatter(g, "fsdp", scatter_dimension=dim, tiled=True)[None])

    ring, want = jax.jit(jax.shard_map(both, mesh=mesh, in_specs=P("fsdp"), out_specs=P("fsdp"), check_vma=False))(g)
    np.testing.assert_allclose(ring, want, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("max_live,sows,path,taken", [
    (10**9, False, ("layer_",), [True, True, True]),
    (2 * 48, False, ("layer_",), [True, True, False]),      # two blocks' sharded leaves fit under the bound, not three
    (0, False, ("layer_",), [False, False, False]),
    (10**9, True, ("layer_",), [False, False, False]),      # a block that sows is the partitioner's
    (10**9, False, ("body", "layer_"), [False, False, False]),  # a caller's sub-tree: not the engine's paths
])
def test_hook_takes_blocks_while_their_gathered_parameters_fit(max_live, sows, path, taken):
    """A block of 48 sharded parameters and a whole one of 3: layers are
    taken first to last while what they gather stays under
    ``stage3_max_live_parameters``; layers of one shape of specs get ONE
    ``wrap``, so they share a trace."""
    topo = _topo({"fsdp": 4}, 4)
    layer = {"w": P("fsdp"), "v": P(None, "fsdp"), "b": P()}
    params = {"w": jnp.zeros((8, 4)), "v": jnp.zeros((2, 8)), "b": jnp.zeros((3,))}
    plan = overlap.GatherPlan(topo.mesh, "fsdp", {f"layer_{i}": layer for i in range(3)}, max_live)
    hook, x = overlap.BlockGather(plan), jnp.zeros((4, 2))
    wraps = [hook(path[:-1] + (f"layer_{i}",), [params] * 3, i, sows, x)[0] for i in range(3)]
    assert [w is not None for w in wraps] == taken
    assert len({id(w) for w in wraps if w is not None}) <= 1
    assert hook.live == 48 * sum(taken)
    whole = overlap.GatherPlan(topo.mesh, "fsdp", {"layer_0": {"w": P(), "v": P(), "b": P()}}, 10**9)
    assert overlap.BlockGather(whole)(("layer_0",), [params], 0, False, x)[0] is None  # nothing sharded: nothing to gather


def test_tie_is_the_identity_both_ways():
    x, later = jnp.arange(4.0), {"w": jnp.ones((2, 2))}
    (y, same), vjp = jax.vjp(overlap.tie, x, later)
    assert (y == x).all() and (same["w"] == later["w"]).all()
    gx, gl = vjp((2 * x, {"w": 3 * later["w"]}))
    assert (gx == 2 * x).all() and (gl["w"] == 3).all()
    assert "optimization_barrier" in str(jax.make_jaxpr(lambda x, l: vjp((x, l)))(x, later))


def _engine(overlap_comm, gas, layers, remat, **zero):
    cfg = dataclasses.replace(gpt2_tiny(), vocab_size=512, n_layers=layers, remat=remat)
    model = CausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 32), np.int32)})
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params, config={
        "train_micro_batch_size_per_gpu": 2, "gradient_accumulation_steps": gas,
        "optimizer": {"type": "adam", "params": {"lr": 1e-3}}, "steps_per_print": 10**9,
        "zero_optimization": dict(SHARD_ALL, overlap_comm=overlap_comm, **zero), "mesh": {"fsdp": N}})
    return engine


def _losses(overlap_comm, gas, layers=3, remat=False, **zero):
    engine = _engine(overlap_comm, gas, layers, remat, **zero)
    rng = np.random.default_rng(0)
    it = iter([{"input_ids": rng.integers(0, 512, (2 * N, 32)).astype(np.int32)} for _ in range(3 * gas)])
    out = []
    for _ in range(3):
        loss = engine.train_batch(it)
        out.append((float(loss), float(engine.get_global_grad_norm())))
    return np.array(out)


@pytest.mark.parametrize("gas,remat,max_live", [(1, False, 10**9), (2, False, 10**9), (1, True, 10**9),
                                                 (1, False, 2 * BLOCK_PARAMS), (2, True, BLOCK_PARAMS)])
def test_engine_with_the_plan_trains_as_without(on_tpu, gas, remat, max_live):
    """ZeRO-3 over the devices, fused step (gas 1) and accumulation (gas 2),
    a rematerialized block (which gathers again in its backward), and a
    bound that leaves the last one or two of three layers to the
    partitioner: the block that gathers its own parameters and reduces their
    gradients round a ring gives the partitioner's three losses and gradient
    norms."""
    laid = overlap.traced("layers")
    with_plan = _losses(True, gas, remat=remat, stage3_max_live_parameters=max_live)
    # the plan was live: so many blocks gathered for themselves in each of the step's programs
    assert overlap.traced("layers") - laid == min(3, max_live // BLOCK_PARAMS)
    without = _losses(False, gas, remat=remat)
    np.testing.assert_allclose(with_plan[:, 0], without[:, 0], rtol=1e-6)
    np.testing.assert_allclose(with_plan[:, 1], without[:, 1], rtol=1e-5)


def test_off_tpu_overlap_comm_changes_nothing():
    """No plan on this backend: true and false are one program."""
    assert (_losses(True, 1, layers=2) == _losses(False, 1, layers=2)).all()


def test_the_plan_reaches_the_model_and_is_traced_once_a_kind(on_tpu):
    """Three layers of one kind: the block's body runs once under the plan
    too, and its jaxpr has the gathers and the ring's hops."""
    from deepspeed_tpu.utils.compile_cache import block_traces

    cfg = dataclasses.replace(gpt2_tiny(), vocab_size=512, n_layers=3)
    model = CausalLM(cfg)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 32), np.int32)}))
    topo = _topo({"fsdp": 4}, 4)
    from deepspeed_tpu.parallel import mesh as mesh_mod
    from deepspeed_tpu.runtime.zero.partition import plan_param_specs

    config = _config(SHARD_ALL, {"fsdp": 4})
    specs = plan_param_specs(params, config, topo, model.partition_rules())
    plan = overlap.plan_for(config, topo, specs)
    batch = {"input_ids": jnp.zeros((8, 32), jnp.int32)}

    def grads(p):
        with overlap.active(plan):
            return jax.grad(lambda p: model.loss_fn(p, batch, None))(p)

    prev = mesh_mod._TOPOLOGY
    mesh_mod._TOPOLOGY = topo
    try:
        before = block_traces()
        text = str(jax.make_jaxpr(grads)(params))
    finally:
        mesh_mod._TOPOLOGY = prev
    assert block_traces() - before == 1
    assert text.count("all_gather") >= 3 and "ppermute" in text and "optimization_barrier" in text
    from deepspeed_tpu.models import transformer
    assert transformer._BLOCK_HOOK.get() is None  # the hook does not outlive the trace


class _Lines(logging.Handler):
    def __init__(self):
        super().__init__()
        self.lines = []

    def emit(self, record):
        self.lines.append(record.getMessage())


@pytest.mark.parametrize("told,max_live,form,layers,rings,traces", [
    ("tpu", 10**9, "bucket", 2, 16, 1),
    ("tpu", BLOCK_PARAMS, "bucket", 1, 16, 2),  # the bound holds one block: the second is the partitioner's, a trace of its own
    ("tpu", BLOCK_PARAMS - 1, "xla", 0, 0, 1),
    ("cpu", 10**9, "xla", 0, 0, 1),
])
def test_trainer_first_call_says_how_the_step_reduces_gradients(monkeypatch, told, max_live, form, layers, rings, traces):
    """One ``program/first_call`` span and line a step program and batch
    shape, with the block's traces, the layers that gather for themselves
    and the rings laid into them: one a sharded leaf of the ONE kind of
    block (gpt2's sixteen), whatever the depth."""
    monkeypatch.setattr(overlap, "_backend", lambda: told)
    from deepspeed_tpu.telemetry import get_tracer

    tracer, handler, logger = get_tracer(), _Lines(), logging.getLogger("deepspeed_tpu")
    was = tracer.enabled
    tracer.enabled = True
    logger.addHandler(handler)
    try:
        _losses(True, 1, layers=2, stage3_max_live_parameters=max_live)
    finally:
        tracer.enabled = was
        logger.removeHandler(handler)
    lines = [l for l in handler.lines if l.startswith("program first call: family=train")]
    assert len(lines) == 1, lines
    assert "bucket=fused_step" in lines[0] and f"block_traces={traces}" in lines[0]
    assert lines[0].endswith(f"grad_reduce={form} bucket_layers={layers} bucket_rings={rings}")
    span = [s for s in tracer.spans() if s["name"] == "program/first_call" and s["attrs"].get("family") == "train"][-1]
    assert [span["attrs"][k] for k in ("grad_reduce", "bucket_layers", "bucket_rings", "block_traces")] == \
        [form, layers, rings, traces]


@pytest.mark.parametrize("max_live", [10**9, BLOCK_PARAMS])
def test_step_flops_count_every_device_of_a_gathered_block(on_tpu, monkeypatch, max_live):
    """The MFU gauge walks the step's jaxpr; a gathered block is one
    device's program there, at one device's shapes, and counts once a
    device: the step's FLOPs are the partitioner's program's (to the ring's
    adds)."""
    monkeypatch.setenv("DS_TPU_PERF_ACCOUNT", "1")
    flops = {}
    for overlap_comm in (True, False):
        engine = _engine(overlap_comm, 1, 2, False, stage3_max_live_parameters=max_live)
        engine.train_batch(iter([{"input_ids": np.zeros((2 * N, 32), np.int32)}]))
        flops[overlap_comm] = engine._step_flops
    assert flops[False] > 0 and abs(flops[True] - flops[False]) < 0.01 * flops[False], flops
