"""A transformer block is traced once a KIND of block and program, not once a
layer: ``program_regions_traced_total{region="block", site}`` is counted by the
region the Python body of the block opens (``models/transformer.py`` ``block_fn``, the v2 runner's
``_stack_body``), so it counts traces and not calls. And the numbers hold: the
cached block gives what a plain Python loop over ``Block.apply`` gives.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.inference.v2 import InferenceEngineV2, RaggedBatchConfig, RaggedInferenceEngineConfig
from deepspeed_tpu.inference.v2.model_runner import fused_forward, ragged_forward, spec_verify_forward
from deepspeed_tpu.models import CausalLM, TransformerConfig
from deepspeed_tpu.models.transformer import Block, make_norm
from deepspeed_tpu.parallel.mesh import reset_mesh
from deepspeed_tpu.telemetry.registry import get_registry
from deepspeed_tpu.utils.compile_cache import block_traces

LAYERS = 6
# what makes two layers different kinds -> how many kinds the 6-layer model then has
KINDS = {
    "dense": ({}, 1),
    "windows": ({"sliding_window": 8, "window_layers": (1, 3, 5)}, 2),
    "moe": ({"moe_num_experts": 4, "moe_layer_freq": 2}, 2),
}


def _model(dtype=jnp.float32, **kw):
    cfg = TransformerConfig(vocab_size=128, n_layers=LAYERS, n_heads=4, n_kv_heads=2, d_model=32, max_seq_len=128,
                            norm="rmsnorm", activation="swiglu", pos_emb="rope", tie_embeddings=False, dtype=dtype,
                            **kw)
    model = CausalLM(cfg)
    return model, model.init(jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 8), np.int32)})


def _traces(site):
    return int(get_registry().peek("program_regions_traced_total", region="block", site=site) or 0)


@pytest.mark.parametrize("kind", KINDS)
def test_the_trainers_first_step_traces_a_block_once_a_kind_not_once_a_layer(kind):
    """forward / backward / step of the engine with the MFU gauge on: every
    kind of block is traced once, for the step program, and the gauge counts
    its FLOPs off that same trace (the parent ran the body 2 x 6 times: six
    layers, and a walk of its own for the gauge). ``init`` keeps its plain
    loop and is not counted: it makes the tree, with flax's own submodules."""
    extra, n_kinds = KINDS[kind]
    reset_mesh()
    before = _traces("train")
    model, params = _model(**extra)
    assert _traces("train") == before  # init runs Block's body through flax's submodules, not through block_fn
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params, config={
        "train_micro_batch_size_per_gpu": 1, "optimizer": {"type": "adam", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 1}, "steps_per_print": 10**9})
    batch = {"input_ids": np.arange(8 * 16, dtype=np.int32).reshape(8, 16) % 128}
    loss = engine.forward(batch)
    engine.backward(loss)
    engine.step()
    assert np.isfinite(float(loss))
    assert engine._step_flops > 0  # the gauge did walk the step
    traced = _traces("train") - before
    assert traced == n_kinds, f"{traced} block traces for {n_kinds} kind(s) of {LAYERS} layers"
    loss = engine.forward(batch)  # the same shapes again: nothing is traced
    engine.backward(loss)
    engine.step()
    assert _traces("train") - before == traced


@pytest.fixture(scope="module", params=[1, 2], ids=["tp1", "tp2"])
def served(request):
    """A v2 engine for its placed parameters, pools and tensor-parallel
    context; the forwards are then traced directly, on operands of our own."""
    reset_mesh()
    model, params = _model()
    eng = InferenceEngineV2(model, params, RaggedInferenceEngineConfig(
        state_manager=RaggedBatchConfig(kv_block_size=8, max_context=64, num_kv_blocks=32),
        dtype="float32", tensor_parallel=request.param))
    assert (eng._tp_ctx is not None) == (request.param == 2)
    yield eng
    reset_mesh()


def _ints(*shape):
    return jnp.zeros(shape, jnp.int32)


def _layer_calls(jaxpr):
    """id() of the jaxpr behind every call of the layer, the ``shard_map`` region's included."""
    calls = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "jit" and eqn.params["name"] == "layer":
            calls.append(id(eqn.params["jaxpr"]))
        elif eqn.primitive.name == "shard_map":
            calls += _layer_calls(eqn.params["jaxpr"])
    return calls


@pytest.mark.parametrize("forward", ["ragged_prefill", "ragged_decode", "fused", "spec_verify"])
def test_a_serving_forward_traces_a_block_once_not_once_a_layer(served, forward):
    """All three forwards share ``_stack_body``: one trace of the layer a
    forward, inside and outside the ``shard_map`` region (the parent: six)."""
    eng = served
    kw = {"interpret": eng._interpret, "mesh": eng._run_mesh, "tp": eng._tp, "tp_ctx": eng._tp_ctx}
    pools, pages = (eng.k_pages, eng.v_pages), 8
    if forward == "ragged_prefill":  # (B, S) = (2, 16)
        fn = functools.partial(ragged_forward, eng._run_cfg, decode=False, **kw)
        args = (_ints(2, 16), _ints(2, 16), *pools, _ints(2, pages), _ints(2) + 16, _ints(32), _ints(2))
    elif forward == "ragged_decode":  # (B, 1) = (4, 1)
        fn = functools.partial(ragged_forward, eng._run_cfg, decode=True, **kw)
        args = (_ints(4, 1), _ints(4, 1), *pools, _ints(4, pages), _ints(4) + 1, _ints(4), _ints(4))
    elif forward == "fused":  # 4 decode rows and 2 prefill rows of 8: T = 20, N = 6
        fn = functools.partial(fused_forward, eng._run_cfg, n_dec=4, chunk=8, **kw)
        args = (_ints(20), _ints(20), *pools, _ints(6, pages), _ints(6) + 8, _ints(20), _ints(6))
    else:  # 2 rows of K + 1 = 4 tokens
        fn = functools.partial(spec_verify_forward, eng._run_cfg, chunk=4, **kw)
        args = (_ints(8), _ints(8), *pools, _ints(2, pages), _ints(2) + 4, _ints(8))
    before = _traces("serve")
    jaxpr = jax.make_jaxpr(fn)(eng.params, *args)
    assert _traces("serve") - before == 1
    calls = _layer_calls(jaxpr.jaxpr)  # the layer is ONE jaxpr of the program, called by one equation a layer
    assert len(calls) == LAYERS and len(set(calls)) == 1


def test_first_call_span_and_line_carry_the_block_traces_of_the_call(served):
    """``program/first_call`` tells it: ``block_traces`` next to the phases, in
    the span and in the one log line, and far under one a layer."""
    import logging

    from deepspeed_tpu.telemetry import get_tracer

    lines = []
    handler = logging.Handler()
    handler.emit = lambda record: lines.append(record.getMessage())
    logger = logging.getLogger("deepspeed_tpu")
    logger.addHandler(handler)
    tracer = get_tracer()
    tracer.clear()
    try:
        before = block_traces()
        served.generate([[3, 17, 42, 9], [5, 6, 7]], max_new_tokens=4)
    finally:
        logger.removeHandler(handler)
    firsts = [s["attrs"] for s in tracer.spans() if s["name"] == "program/first_call"]
    assert firsts and sum(a["block_traces"] for a in firsts) == block_traces() - before
    # a fused program holds two forwards (the mixed pass, and the decode steps under its scan)
    assert all(1 <= a["block_traces"] <= 2 for a in firsts if a["family"] == "fused")
    said = [l for l in lines if l.startswith("program first call:")]
    assert len(said) == len(firsts) and all(f" block_traces={a['block_traces']}" in l for l, a in zip(said, firsts))


def _plain_loss(cfg, params, ids, train=True):
    """The model as a plain Python loop over ``Block.apply``, one block object
    a layer built from the layer's index, as the parent's loop did: the
    reference the cached block is held to."""
    B, S = ids.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    x = params["wte"][ids].astype(cfg.dtype)
    aux = 0.0
    for i in range(cfg.n_layers):
        block = Block(cfg, cfg.kinds[i], is_training=train)
        x, sown = block.apply({"params": params[f"layer_{i}"]}, x, positions, mutable=["losses", "intermediates"])
        aux = aux + sum(jnp.sum(l) for l in jax.tree_util.tree_leaves(sown.get("losses", {})))
    norm_key = next(k for k in params if k.startswith("RMSNorm"))
    x = make_norm(cfg).apply({"params": params[norm_key]}, x)
    logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"]["kernel"].astype(cfg.dtype)).astype(jnp.float32)
    labels = ids[:, 1:]
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    ce = -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))
    return ce + cfg.moe_aux_loss_coef * aux


@pytest.mark.parametrize("kind", KINDS)
def test_loss_and_gradients_equal_a_plain_loop_over_block_apply(kind):
    """float32 on the CPU differs only in the order XLA fuses: the loss to 1e-6,
    a gradient to 1e-5 of its largest entry (a sum over every token reaches
    1.1e-6); a dropped layer, a wrong window, a reused parameter or a lost
    auxiliary loss is orders off."""
    model, params = _model(**KINDS[kind][0])
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 128, (2, 24)), jnp.int32)
    got_loss, got = jax.jit(jax.value_and_grad(lambda p: model.loss_fn(p, {"input_ids": ids})))(params)
    want_loss, want = jax.jit(jax.value_and_grad(lambda p: _plain_loss(model.cfg, p, ids)))(params)
    np.testing.assert_allclose(got_loss, want_loss, rtol=1e-6)
    flat_got, flat_want = jax.tree_util.tree_leaves_with_path(got), jax.tree_util.tree_leaves_with_path(want)
    assert [p for p, _ in flat_got] == [p for p, _ in flat_want]
    for (path, g), (_, w) in zip(flat_got, flat_want):
        scale = float(jnp.max(jnp.abs(w))) or 1.0
        np.testing.assert_allclose(np.asarray(g) / scale, np.asarray(w) / scale, rtol=0, atol=1e-5,
                                   err_msg=jax.tree_util.keystr(path))
    # every layer's parameters got a gradient of their own: none dropped, none shared
    norms = [float(jnp.linalg.norm(got[f"layer_{i}"]["attn"]["q_proj"]["kernel"])) for i in range(LAYERS)]
    assert all(n > 0 for n in norms) and len(set(norms)) == LAYERS


@pytest.mark.parametrize("case", ["remat", "kv_cache", "eager", "moe_eval"])
def test_every_way_into_the_loop_calls_the_same_cached_block(case):
    """Activation checkpointing, v1 decode's ``kv_caches`` branch, an eager
    ``apply`` and a forward that collects nothing all go through ``block_fn``."""
    model, params = _model(**({"remat": True} if case == "remat" else KINDS["moe"][0] if case == "moe_eval" else {}))
    ids = jnp.asarray(np.random.default_rng(1).integers(0, 128, (2, 12)), jnp.int32)
    before = _traces("train")
    if case == "remat":
        plain, _ = _model()
        got = jax.jit(jax.grad(lambda p: model.loss_fn(p, {"input_ids": ids})))(params)
        want = jax.jit(jax.grad(lambda p: plain.loss_fn(p, {"input_ids": ids})))(params)
        jax.tree_util.tree_map(lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7), got, want)
        assert _traces("train") - before == 2  # one a program
    elif case == "kv_cache":
        full = model.apply(params, ids, train=False)
        caches = model.init_kv_caches(2, 16)
        step = jax.jit(lambda p, t, pos, c: model.apply(p, t, positions=pos, kv_caches=c))
        logits, caches = step(params, ids[:, :8], jnp.broadcast_to(jnp.arange(8), (2, 8)), caches)
        np.testing.assert_allclose(logits, full[:, :8], rtol=1e-5, atol=1e-5)
        for t in range(8, 12):  # four decode steps, one program
            logits, caches = step(params, ids[:, t:t + 1], jnp.full((2, 1), t), caches)
            np.testing.assert_allclose(logits[:, 0], full[:, t], rtol=1e-5, atol=1e-5)
        assert _traces("train") - before == 3  # the full forward, the prefill program, the decode program
    elif case == "eager":
        want = jax.jit(lambda p: model.apply(p, ids))(params)
        np.testing.assert_allclose(model.apply(params, ids), want, rtol=1e-5, atol=1e-5)
        assert _traces("train") - before == 2
    else:  # a MoE forward with no mutable collection: the auxiliary loss has nowhere to go and nothing fails
        logits = jax.jit(lambda p: model.apply(p, ids, train=False))(params)
        assert logits.shape == (2, 12, 128) and bool(jnp.all(jnp.isfinite(logits)))
        hidden, mods = model.module.apply({"params": params}, ids, return_hidden=True, mutable=["losses"])
        assert sorted(mods["losses"]) == ["layer_1", "layer_3", "layer_5"]  # where the parent's sow put them
        assert _traces("train") - before == 2 * 2


@pytest.mark.parametrize("site", ["train", "serve"])
def test_flops_of_jaxpr_counts_every_layer_of_the_cached_block(site):
    """The MFU gauge and ``program/cost_card`` walk the jaxpr. In serving it
    holds one ``jit`` equation a layer on ONE shared jaxpr, which must count
    once a call, not once; in training the cached equations are replayed a
    layer. Either way the matmul FLOPs of a 4-layer model less those of a
    2-layer one are two layers' analytic count."""
    from deepspeed_tpu.profiling.flops_profiler.profiler import flops_of_fn

    B, S, d, H, KVH, f = 2, 16, 32, 4, 2, 128
    D = d // H
    ids = np.zeros((B, S), np.int32)

    def macs(n_layers):
        model = CausalLM(dataclasses.replace(_model()[0].cfg, n_layers=n_layers, d_ff=f))
        params = model.init(jax.random.PRNGKey(0), {"input_ids": ids})
        if site == "train":
            return flops_of_fn(lambda p: model.apply(p, ids), params)[1]
        eng = InferenceEngineV2(model, params, RaggedInferenceEngineConfig(
            state_manager=RaggedBatchConfig(kv_block_size=8, max_context=64, num_kv_blocks=32), dtype="float32"))
        fn = functools.partial(ragged_forward, eng._run_cfg, decode=False, interpret=eng._interpret)
        return flops_of_fn(fn, eng.params, _ints(B, S), _ints(B, S), eng.k_pages, eng.v_pages, _ints(B, 8),
                           _ints(B) + S, _ints(B * S), _ints(B))[1]

    proj = B * S * d * (H * D + 2 * KVH * D + H * D)  # q, k, v, o
    mlp = 3 * B * S * d * f  # gate, up, down
    keys = S if site == "train" else 8 * 8  # attention_xla forms all S x S scores; paged prefill gathers 8 pages of 8
    attn = 2 * B * H * S * keys * D  # q k^T and p v
    rope = B * S * (H + KVH) * D * D  # the rotation's partners: every head of q and k times a (D, D) matrix of zeros and ones
    assert macs(4) - macs(2) == 2 * (proj + mlp + attn + rope)
