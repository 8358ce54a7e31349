"""``overlap_comm`` at ZeRO stage 3 (``runtime/zero/overlap.py``): where the
gather plan applies, that a block which gathers its own parameters computes
what XLA's partitioner computes, and what the trainer's first-call line says
of the compiled step. The TPU compiler's side is in ``test_chip_compile.py``.
"""

import dataclasses
import functools
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


@pytest.mark.parametrize("max_live,sows,path,taken,again", [
    (10**9, False, ("layer_",), [True, True, True], [False, False, False]),
    (2 * 48, False, ("layer_",), [True, True, True], [True, False, False]),  # two blocks' sharded leaves fit: the LAST two keep
    (48, False, ("layer_",), [True, True, True], [True, True, False]),
    (47, False, ("layer_",), [True, True, True], [True, True, True]),        # none fits: every block gathers twice
    (0, False, ("layer_",), [True, True, True], [True, True, True]),         # 0 keeps nothing; no block leaves the plan for it
    (10**9, True, ("layer_",), [False, False, False], [False, False, False]),      # a block that sows is the partitioner's
    (10**9, False, ("body", "layer_"), [False, False, False], [False, False, False]),  # a caller's sub-tree: not the engine's paths
])
def test_hook_takes_blocks_while_their_gathered_parameters_fit(max_live, sows, path, taken, again):
    """A block of 48 sharded parameters and a whole one of 3: every block
    with a sharded leaf is taken, and the last ones keep what they gathered
    while that stays under ``stage3_max_live_parameters``; the ones before
    them gather a second time in their backward. Layers of one shape of
    specs and one way with their weights get ONE ``wrap``, so they share a
    trace."""
    topo = _topo({"fsdp": 4}, 4)
    layer = {"w": P("fsdp"), "v": P(None, "fsdp"), "b": P()}
    params = {"w": jnp.zeros((8, 4)), "v": jnp.zeros((2, 8)), "b": jnp.zeros((3,))}
    plan = overlap.GatherPlan(topo.mesh, "fsdp", {f"layer_{i}": layer for i in range(3)}, max_live)
    hook, x = overlap.BlockGather(plan), jnp.zeros((4, 2))
    paths = [path[:-1] + (f"layer_{i}",) for i in range(3)]
    before = overlap.traced("regathers")
    wraps = [hook(paths, [params] * 3, [sows] * 3, i, x)[0] for i in range(3)]
    assert [w is not None for w in wraps] == taken
    assert [w is not None and not w.args[2] for w in wraps] == again  # ``gathered_block``'s ``keep``
    assert overlap.traced("regathers") - before == sum(again)
    assert len({id(w) for w in wraps if w is not None}) == len({a for a, t in zip(again, taken) if t})
    assert hook.live == 48 * (sum(taken) - sum(again)) <= max_live
    whole = overlap.GatherPlan(topo.mesh, "fsdp", {"layer_0": {"w": P(), "v": P(), "b": P()}}, 10**9)
    assert overlap.BlockGather(whole)([("layer_0",)], [params], [False], 0, x)[0] is None  # nothing sharded: nothing to gather


@pytest.mark.parametrize("paths,specs,vocab_dim,sows,taken", [
    ((("wte",),), (P("fsdp"),), 0, False, True),
    ((("lm_head", "kernel"), ("lm_head", "bias")), (P(None, "fsdp"), P()), 1, False, True),  # a whole bias rides along
    ((("lm_head", "kernel"), ("lm_head", "bias")), (P("fsdp"), P()), 1, False, False),       # sharded, but not by the vocabulary
    ((("lm_head", "kernel"), ("lm_head", "bias")), (P(None, "fsdp"), P("fsdp")), 1, False, True),        # or its own slice
    ((("wte",),), (P("fsdp"),), 0, True, False),             # a model that sows keeps the partitioner's head
    ((("body", "wte"),), (P("fsdp"),), 0, False, False),     # a caller's sub-tree
    ((("wte",),), (P(),), 0, False, False),                  # nothing sharded
])
def test_hook_takes_the_head_where_its_weight_is_sharded_by_the_vocabulary(paths, specs, vocab_dim, sows, taken):
    """``head``: every device computes the whole batch's loss over its slice
    of the vocabulary; the summed shares are the loss's sum and the count,
    and the gradients those of the plain head, with no gather of the
    weight."""
    from deepspeed_tpu.models.transformer import _head_sums

    topo = _topo({"fsdp": 4}, 4)
    tree = {"wte": specs[0], "lm_head": {"kernel": specs[0], "bias": specs[-1]}}
    hook = overlap.BlockGather(overlap.GatherPlan(topo.mesh, "fsdp", tree, 0))
    tied = vocab_dim == 0
    fn = functools.partial(_head_sums, dtype=jnp.float32, vd_layout=tied)
    w = jax.random.normal(jax.random.PRNGKey(0), (32, 8) if tied else (8, 32), jnp.float32)
    leaves = (w,) if len(paths) == 1 else (w, jax.random.normal(jax.random.PRNGKey(1), (32,), jnp.float32))
    hidden = jax.random.normal(jax.random.PRNGKey(2), (8, 6, 8), jnp.float32)
    labels = jax.random.randint(jax.random.PRNGKey(3), (8, 6), 0, 32).at[:, -1].set(-100)
    before = overlap.traced("head")
    if not taken:
        assert hook.head(paths, leaves, fn, vocab_dim, sows) is None and overlap.traced("head") == before
        return

    def loss(run, leaves, hidden):
        total, count = run(leaves, hidden, labels)
        return jnp.sum(total) / jnp.maximum(jnp.sum(count), 1)

    mine = jax.jit(jax.value_and_grad(lambda l, h: loss(lambda l, h, y: hook.head(paths, l, fn, vocab_dim, sows)(h, y), l, h),
                                      argnums=(0, 1)))(leaves, hidden)
    want = jax.value_and_grad(functools.partial(loss, fn), argnums=(0, 1))(leaves, hidden)
    assert overlap.traced("head") - before == 1 and hook.live == 0
    for got, plain in zip(jax.tree_util.tree_leaves(mine), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(got, plain, rtol=2e-5, atol=1e-6)
    text = str(jax.make_jaxpr(jax.grad(lambda l, h: loss(lambda l, h, y: hook.head(paths, l, fn, vocab_dim, sows)(h, y), l, h),
                                       argnums=(0, 1)))(leaves, hidden))
    assert text.count(" all_gather[") == 2 and "ppermute" in text  # activations and labels: the weight stays where it is


@pytest.mark.parametrize("spec,sows,path,taken", [
    (P("fsdp"), False, ("wte",), True),
    (P("fsdp"), True, ("wte",), False),          # a model that sows keeps the partitioner's look-up
    (P("fsdp"), False, ("body", "wte"), False),  # a caller's sub-tree
    (P(None, "fsdp"), False, ("wte",), False),   # not sharded by rows
    (P(), False, ("wte",), False),
])
def test_hook_looks_tokens_up_in_a_table_sharded_by_rows(spec, sows, path, taken):
    """``look_up``: each device finds the batch's ids that fall into its
    rows and the ring sums the partial results to the device that owns the
    row of the batch: ``table[ids]``, and its gradient, with no gather of
    the table."""
    topo = _topo({"fsdp": 4}, 4)
    hook = overlap.BlockGather(overlap.GatherPlan(topo.mesh, "fsdp", {"wte": spec}, 0))
    table = jax.random.normal(jax.random.PRNGKey(0), (32, 8), jnp.float32)
    ids = jax.random.randint(jax.random.PRNGKey(1), (8, 5), 0, 32)
    if not taken:
        assert hook.look_up(path, table, ids, sows) is None
        return
    mine = lambda t: jnp.sum(jnp.sin(hook.look_up(path, t, ids, sows)))
    np.testing.assert_array_equal(jax.jit(lambda t: hook.look_up(path, t, ids, sows))(table), table[ids])
    np.testing.assert_allclose(jax.jit(jax.grad(mine))(table), jax.grad(lambda t: jnp.sum(jnp.sin(t[ids])))(table), rtol=1e-6)
    text = str(jax.make_jaxpr(jax.grad(mine))(table))
    assert "f32[32,8] = all_gather" not in text and "ppermute" in text  # the table stays where it is


@pytest.mark.parametrize("shape,dim", [((8, 6), 0), ((6, 16), 1)])
def test_a_block_that_gathers_twice_computes_what_one_that_keeps_does(shape, dim):
    """``_regathering``: the same value and the same gradient as the keeping
    gather and as ``all_gather`` / ``psum_scatter``; what its forward keeps
    for its backward is the shard, not the gathered weight, and its backward
    gathers again."""
    mesh = _topo({"fsdp": 4}, 4).mesh
    spec = P(*([None] * dim + ["fsdp"]))
    w = jax.random.normal(jax.random.PRNGKey(0), shape, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, shape[0]), jnp.float32)

    body = lambda w, x: jnp.sum(jnp.tanh(x @ w) ** 2, axis=1)

    def loss(local):
        mapped = jax.shard_map(local, mesh=mesh, in_specs=(spec, P("fsdp")), out_specs=P("fsdp"), check_vma=False)
        return lambda w, x: jnp.sum(mapped(w, x))

    plain = loss(lambda w, x: body(jax.lax.all_gather(w, "fsdp", axis=dim, tiled=True), x))
    want = jax.jit(jax.value_and_grad(plain, argnums=(0, 1)))(w, x)
    for again in (False, True):
        keeping = lambda w, x: body(overlap._gather("fsdp", 4, dim)(w), x)
        fn = loss(overlap._regathering(body, dim, "fsdp", 4) if again else keeping)
        got = jax.jit(jax.value_and_grad(fn, argnums=(0, 1)))(w, x)
        np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
        for g, g_want in zip(got[1], want[1]):
            np.testing.assert_allclose(g, g_want, rtol=1e-5, atol=1e-6)
        text = str(jax.make_jaxpr(jax.grad(fn, argnums=(0, 1)))(w, x))  # the activation's gradient is what reads the weight
        assert text.count(" all_gather[") == (2 if again else 1) and ("optimization_barrier" in text) == again
        # what passes from the forward's region to the backward's, stacked over the four devices: the whole weight or not
        assert (f"f32[{4 * shape[0]},{shape[1]}]" in text) != again


def test_tie_is_the_identity_both_ways():
    x, later = jnp.arange(4.0), {"w": jnp.ones((2, 2))}
    (y, same), vjp = jax.vjp(overlap.tie, x, later)
    assert (y == x).all() and (same["w"] == later["w"]).all()
    gx, gl = vjp((2 * x, {"w": 3 * later["w"]}))
    assert (gx == 2 * x).all() and (gl["w"] == 3).all()
    assert "optimization_barrier" in str(jax.make_jaxpr(lambda x, l: vjp((x, l)))(x, later))


def _engine(overlap_comm, gas, layers, remat, model=None, **zero):
    cfg = dataclasses.replace(gpt2_tiny(), vocab_size=512, n_layers=layers, remat=remat, **(model or {}))
    model = CausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 32), np.int32)})
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params, config={
        "train_micro_batch_size_per_gpu": 2, "gradient_accumulation_steps": gas,
        "optimizer": {"type": "adam", "params": {"lr": 1e-3}}, "steps_per_print": 10**9,
        "zero_optimization": dict(SHARD_ALL, overlap_comm=overlap_comm, **zero), "mesh": {"fsdp": N}})
    return engine


def _losses(overlap_comm, gas, layers=3, remat=False, model=None, **zero):
    engine = _engine(overlap_comm, gas, layers, remat, model, **zero)
    rng = np.random.default_rng(0)
    it = iter([{"input_ids": rng.integers(0, 512, (2 * N, 32)).astype(np.int32)} for _ in range(3 * gas)])
    out = []
    for _ in range(3):
        loss = engine.train_batch(it)
        out.append((float(loss), float(engine.get_global_grad_norm())))
    return np.array(out)


UNTIED = {"tie_embeddings": False, "lm_head_bias": True}  # its own ``lm_head.kernel`` (d x V), and a bias that stays whole


@pytest.mark.parametrize("gas,remat,max_live,model", [
    (1, False, 10**9, None), (2, False, 10**9, None), (1, True, 10**9, None),
    (1, False, 2 * BLOCK_PARAMS, None), (2, True, BLOCK_PARAMS, None),
    (1, False, 0, None),                      # nothing kept: every block gathers twice
    (1, False, 10**9, UNTIED), (2, False, BLOCK_PARAMS, UNTIED),
])
def test_engine_with_the_plan_trains_as_without(on_tpu, gas, remat, max_live, model):
    """ZeRO-3 over the devices, fused step (gas 1) and accumulation (gas 2),
    a rematerialized block (which gathers again in its backward), a bound
    under which the first one, two or all of three layers gather a second
    time, and a tied and an untied head: blocks, look-ups and head that
    gather their own parameters and reduce their gradients round a ring give
    the partitioner's three losses and gradient norms."""
    laid = [overlap.traced(what) for what in ("layers", "regathers", "head")]
    with_plan = _losses(True, gas, remat=remat, model=model, stage3_max_live_parameters=max_live)
    # the plan was live: every block gathered for itself in each of the step's programs, and so did the head, beside the
    # token look-up; as many blocks as do not fit under the bound gathered twice
    assert [overlap.traced(what) - was for what, was in zip(("layers", "regathers", "head"), laid)] == \
        [3, 3 - min(3, max_live // BLOCK_PARAMS), 2]
    without = _losses(False, gas, remat=remat, model=model)
    np.testing.assert_allclose(with_plan[:, 0], without[:, 0], rtol=1e-6)
    np.testing.assert_allclose(with_plan[:, 1], without[:, 1], rtol=1e-5)


def test_off_tpu_overlap_comm_changes_nothing():
    """No plan on this backend: true and false are one program."""
    assert (_losses(True, 1, layers=2) == _losses(False, 1, layers=2)).all()


def _traced_grads(cfg, nest=False):
    """The jaxpr of the loss's gradient under the plan of a ZeRO-3 engine
    over four devices, and how often a block's body ran for it. ``nest``: the
    engine's tree holds the model's one level down, as for a caller that
    applies a sub-tree."""
    from deepspeed_tpu.parallel import mesh as mesh_mod
    from deepspeed_tpu.runtime.zero.partition import plan_param_specs
    from deepspeed_tpu.utils.compile_cache import block_traces

    model = CausalLM(cfg)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 32), np.int32)}))
    topo = _topo({"fsdp": 4}, 4)
    config = _config(SHARD_ALL, {"fsdp": 4})
    specs = plan_param_specs(params, config, topo, model.partition_rules())
    plan = overlap.plan_for(config, topo, {"body": specs} if nest else specs)
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
    return text, block_traces() - before


def test_the_plan_reaches_the_model_and_is_traced_once_a_kind(on_tpu):
    """Three layers of one kind: the block's body runs once under the plan
    too, and its jaxpr has the gathers (three blocks', the head's, the
    look-up's of the batch's ids) and the ring's hops."""
    text, traces = _traced_grads(dataclasses.replace(gpt2_tiny(), vocab_size=512, n_layers=3))
    assert traces == 1
    assert text.count(" all_gather[") >= 5 and "ppermute" in text and "optimization_barrier" in text
    from deepspeed_tpu.models import transformer
    assert transformer._BLOCK_HOOK.get() is None  # the hook does not outlive the trace


@pytest.mark.parametrize("model,nest", [
    ({"moe_num_experts": 4, "moe_layer_freq": 1}, False),  # every block sows, and the loss has a term the head cannot carry
    ({}, True),                                            # the engine's tree is not the model's
])
def test_a_model_that_sows_or_is_a_sub_tree_stays_the_partitioners(on_tpu, model, nest):
    """Blocks, look-ups and head alike: nothing of the model is a manual
    region, nothing is counted."""
    counted = ("layers", "regathers", "rings", "head")
    before = [overlap.traced(what) for what in counted]
    text, _ = _traced_grads(dataclasses.replace(gpt2_tiny(), vocab_size=512, n_layers=2, **model), nest)
    assert "shard_map" not in text and "ppermute" not in text
    assert [overlap.traced(what) for what in counted] == before


class _Lines(logging.Handler):
    def __init__(self):
        super().__init__()
        self.lines = []

    def emit(self, record):
        self.lines.append(record.getMessage())


@pytest.mark.parametrize("told,max_live,form,layers,rings,traces,again,head", [
    ("tpu", 10**9, "bucket", 2, 16, 1, 0, 1),
    # the bound holds one block: the first gathers twice, a kind of its own, traced for its value and for its backward
    ("tpu", BLOCK_PARAMS, "bucket", 2, 32, 3, 1, 1),
    ("tpu", BLOCK_PARAMS - 1, "bucket", 2, 16, 2, 2, 1),
    ("tpu", 0, "bucket", 2, 16, 2, 2, 1),             # 0 keeps nothing gathered; the plan stands
    ("cpu", 10**9, "xla", 0, 0, 1, 0, 0),
])
def test_trainer_first_call_says_how_the_step_reduces_gradients(monkeypatch, told, max_live, form, layers, rings, traces,
                                                                again, head):
    """One ``program/first_call`` span and line a step program and batch
    shape, with the block's traces, the layers that gather for themselves,
    those of them that gather a second time in their backward, the rings
    laid into them (one a sharded leaf of a KIND of block, gpt2's sixteen,
    whatever the depth) and whether look-ups and head are the plan's too."""
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
    assert lines[0].endswith(f"grad_reduce={form} bucket_layers={layers} bucket_rings={rings} bucket_regather={again} "
                             f"bucket_head={head}")
    span = [s for s in tracer.spans() if s["name"] == "program/first_call" and s["attrs"].get("family") == "train"][-1]
    assert [span["attrs"][k] for k in ("grad_reduce", "bucket_layers", "bucket_rings", "bucket_regather", "bucket_head",
                                       "block_traces")] == [form, layers, rings, again, head, traces]


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
