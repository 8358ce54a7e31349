"""The four kinds of a decoder-hybrid-decoder stack (``models/mixers.py``: a Mamba-1 scan, differential attention with
and without a window, a gated memory unit, differential cross-attention) and the values that travel between blocks
(``LayerKind.gives`` / ``takes``): the six-kind model against the configuration's plain reference at a small width on
the CPU, in logits, loss and every leaf's gradient, with ``remat`` on and off; the gradient's way back THROUGH the
carried values; what the controls break; the refusals of the stacked forms; ZeRO-3's block hook; and that a model whose
kinds give and take nothing traces the equations it traced before the loop carried anything.

The reference is the benchmark configuration's own file (``benchmarks/configs/phi4-mini-flash-l6.reference.py``),
loaded by its path: it imports nothing of the program or of the benchmark."""

import dataclasses
import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
from deepspeed_tpu.models import CausalLM, TransformerConfig, transformer as table
from deepspeed_tpu.models.transformer import _SOWN, Block, block_fn
from deepspeed_tpu.parallel.mesh import initialize_mesh, reset_mesh
from deepspeed_tpu.runtime.config import MeshConfig

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NUMBERS = (0, 1, 16, 17, 18, 19)  # the published indices of the six layers, of 32
KINDS = (("ssm", "dense"), ("diff_window", "dense"), ("ssm", "dense"), ("diff", "dense"), ("gmu", "dense"), ("diff_cross", "dense"))
PUBLISHED = {"layer_norm_eps": 1e-5, "layers_here": list(NUMBERS), "published_layers": 32, "sliding_window": 8}


def tiny(**over):
    base = dict(vocab_size=97, n_layers=6, n_heads=8, n_kv_heads=4, head_dims=16, d_model=64, d_ff=96, max_seq_len=64, norm="layernorm",
                activation="swiglu", pos_emb="none", dense_bias=False, tie_embeddings=True, sliding_window=8, ssm_inner=128, ssm_state=16,
                ssm_conv=4, ssm_dt_rank=4, layer_numbers=NUMBERS, layer_kinds=KINDS)
    return TransformerConfig(**dict(base, **over))


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location("sambay_reference", os.path.join(ROOT, "benchmarks", "configs", "phi4-mini-flash-l6.reference.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def seeded():
    """(ids, parameters): ``init``'s, every leaf stirred (the norms' scales start at one, the biases at zero, ``D`` at one)."""
    ids = np.random.default_rng(0).integers(0, 97, (2, 48)).astype(np.int32)
    params = CausalLM(tiny()).init(jax.random.PRNGKey(0), {"input_ids": ids})
    leaves, tree = jax.tree_util.tree_flatten(params)
    stirred = [x + 0.05 * jax.random.normal(jax.random.PRNGKey(7 + i), x.shape) for i, x in enumerate(leaves)]
    return ids, jax.tree_util.tree_unflatten(tree, stirred)


def ref_loss(ref, params, ids, dtype=jnp.float32, **control):
    logits = ref.logits(params, ids, PUBLISHED, control, dtype)
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, jnp.asarray(ids)[:, 1:, None], axis=-1))


def close(a, b, tol, what=""):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert np.max(np.abs(a - b)) <= tol * (1.0 + np.max(np.abs(b))), (what, np.max(np.abs(a - b)), np.max(np.abs(b)))


def test_the_tree_is_the_kinds_own_and_the_layers_are_what_the_reference_derives(ref):
    params = jax.eval_shape(lambda: CausalLM(tiny()).init(jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 8), np.int32)}))
    assert [ref.kind_of(n, 32) for n in NUMBERS] == [mixer for mixer, _ in KINDS]
    assert [ref.kind_of(n, 32) for n in range(32)].count("ssm") == 9 and [ref.kind_of(n, 32) for n in range(32)].count("diff") == 1
    assert set(params["layer_0"]["ssm"]) == {"in_proj", "conv_kernel", "conv_bias", "x_proj", "dt_proj", "dt_bias", "A_log", "D", "out_proj"}
    assert set(params["layer_3"]["diff"]) == set(params["layer_1"]["diff_window"]) == \
        {"q_proj", "k_proj", "v_proj", "o_proj", "lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2", "subln"}
    assert set(params["layer_4"]["gmu"]) == {"in_proj", "out_proj"}
    assert set(params["layer_5"]["diff_cross"]) == set(params["layer_3"]["diff"]) - {"k_proj", "v_proj"}
    assert params["layer_3"]["diff"]["v_proj"]["kernel"].shape == (64, 2, 32) and params["layer_3"]["diff"]["o_proj"]["kernel"].shape == (4, 32, 64)
    assert tiny().shares == ("layer", "scan_out", "shared_k", "shared_v")


# float32 at the highest matmul precision on both sides: what is left is the order of float32 sums (a softmax row of up
# to 48 keys, a scan 48 tokens deep, the fused cross-entropy): 2e-5 of the largest entry for the logits, 5e-5 for a
# gradient (sums over 96 tokens). A bf16 scan state reads 4e-3 and lambda = 0 reads 0.1 and more in the logits (the
# controls' test below), two and four orders over these
@pytest.mark.parametrize("remat", [False, True])
def test_logits_loss_and_every_leafs_gradient_agree_with_the_float32_reference(ref, seeded, remat):
    ids, params = seeded
    model = CausalLM(tiny(remat=remat))
    with jax.default_matmul_precision("highest"):
        close(model.apply(params, ids), ref.logits(params, ids, PUBLISHED, {}, jnp.float32), 2e-5, "logits")
        ours, g_ours = jax.value_and_grad(lambda p: model.loss_fn(p, {"input_ids": ids}))(params)
        theirs, g_theirs = jax.value_and_grad(lambda p: ref_loss(ref, p, ids))(params)
    close(ours, theirs, 1e-6, "loss")
    theirs_by_path = dict(jax.tree_util.tree_leaves_with_path(g_theirs))
    leaves = jax.tree_util.tree_leaves_with_path(g_ours)
    assert len(leaves) == len(theirs_by_path) == 90
    for path, leaf in leaves:
        close(leaf, theirs_by_path[path], 5e-5, jax.tree_util.keystr(path))
    assert all(float(jnp.max(jnp.abs(leaf))) > 0 for _, leaf in leaves)  # every leaf takes a gradient


@pytest.mark.parametrize("control,least", [("no_window", 1e-2), ("no_lambda", 0.1), ("gated_memory", 0.1), ("own_keys", 0.1)])
def test_the_references_controls_move_the_logits(ref, seeded, control, least):
    ids, params = seeded
    with jax.default_matmul_precision("highest"):
        sound = ref.logits(params, ids, PUBLISHED, {}, jnp.float32)
        broken = ref.logits(params, ids, PUBLISHED, {control: True}, jnp.float32)
    assert float(jnp.linalg.norm(broken - sound) / jnp.linalg.norm(sound)) > least


def test_a_bf16_scan_state_is_the_bf16_reference_with_a_lower_state_and_over_the_float32_tolerance(ref, seeded):
    ids, params = seeded
    truth = ref.logits(params, ids, PUBLISHED, {}, jnp.float32)
    plain, low = (ref.logits(params, ids, PUBLISHED, {"low_state": flag}, jnp.bfloat16) for flag in (False, True))
    assert 0 < float(jnp.max(jnp.abs(low - plain)))
    assert float(jnp.max(jnp.abs(ref.logits(params, ids, PUBLISHED, {"low_state": True}, jnp.float32) - truth))) == 0.0  # float32 has no lower state
    # the state alone in bf16, everything else float32: what the program's kernel must not do
    from deepspeed_tpu.ops import ssm

    u, delta, A, B, C, D = (jnp.asarray(x) for x in _scan_operands())
    exact = ssm.ssm_recurrence(u, delta, A, B, C, D)
    rounded = ssm.ssm_recurrence(u, delta, A, B, C, D, state_dtype=jnp.bfloat16)
    assert float(jnp.max(jnp.abs(rounded - exact)) / jnp.max(jnp.abs(exact))) > 2e-5 * 50


def _scan_operands():
    rng = np.random.default_rng(3)
    return (rng.standard_normal((1, 48, 128)).astype(np.float32), np.log1p(np.exp(rng.standard_normal((1, 48, 128)) - 2)).astype(np.float32),
            -np.tile(np.arange(1.0, 17.0, dtype=np.float32), (128, 1)), rng.standard_normal((1, 48, 16)).astype(np.float32),
            rng.standard_normal((1, 48, 16)).astype(np.float32), np.ones(128, np.float32))


def test_the_gradient_reaches_the_givers_leaves_through_the_carried_values(seeded):
    """Layer 2 (published 16) hands on its scan's output, layer 3 (17) its keys and values. With the second half's two
    takers' output projections at zero nothing reads the carried values: the leaves that only they feed (``k_proj`` and
    ``v_proj`` of layer 3 feed its own attention too, so they change; the scan's leaves feed layer 2's own output too)
    change their gradients, every one; and a carried value's cotangent is not zero."""
    ids, params = seeded
    model = CausalLM(tiny())
    grad = jax.grad(lambda p: model.loss_fn(p, {"input_ids": ids}))
    whole = grad(params)
    cut = jax.tree_util.tree_map(lambda x: x, params)
    cut["layer_4"]["gmu"]["out_proj"]["kernel"] = jnp.zeros_like(params["layer_4"]["gmu"]["out_proj"]["kernel"])
    cut["layer_5"]["diff_cross"]["o_proj"]["kernel"] = jnp.zeros_like(params["layer_5"]["diff_cross"]["o_proj"]["kernel"])
    alone = grad(cut)
    moved = lambda layer, part, name: float(jnp.max(jnp.abs(jnp.asarray(jax.tree_util.tree_leaves(whole[layer][part][name])[0])
                                                            - jnp.asarray(jax.tree_util.tree_leaves(alone[layer][part][name])[0]))))
    for name in ("in_proj", "x_proj", "dt_proj", "A_log", "D", "conv_kernel"):
        assert moved("layer_2", "ssm", name) > 1e-7, name
    for name in ("k_proj", "v_proj"):
        assert moved("layer_3", "diff", name) > 1e-7, name
    # and directly: the block that takes them returns a cotangent for each
    cfg = tiny()
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 48, 64))
    positions = jnp.broadcast_to(jnp.arange(48, dtype=jnp.int32), (2, 48))
    taken = {"shared_k": jax.random.normal(jax.random.PRNGKey(2), (2, 48, 4, 16)), "shared_v": jax.random.normal(jax.random.PRNGKey(3), (2, 48, 2, 32)),
             "layer": jnp.asarray(19, jnp.int32)}
    run = lambda k, v: jnp.sum(block_fn(cfg, KINDS[5], True, True)(params["layer_5"], x, positions, None, None, dict(taken, shared_k=k, shared_v=v))[0][0])
    dk, dv = jax.grad(run, argnums=(0, 1))(taken["shared_k"], taken["shared_v"])
    assert float(jnp.max(jnp.abs(dk))) > 0 and float(jnp.max(jnp.abs(dv))) > 0


def test_a_giver_of_a_name_replaces_an_earlier_one_and_a_taker_without_a_giver_is_refused():
    ids = np.zeros((1, 16), np.int32)
    with pytest.raises(ValueError, match="layer 0 .gmu. takes scan_out, which no earlier layer gives"):
        CausalLM(tiny(n_layers=1, layer_kinds=(("gmu", "dense"),), layer_numbers=None)).init(jax.random.PRNGKey(0), {"input_ids": ids})
    # layer numbers default to the layer's own place
    cfg = tiny(n_layers=2, layer_kinds=(("diff", "dense"), ("diff_cross", "dense")), layer_numbers=None)
    model = CausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0), {"input_ids": ids})
    assert np.isfinite(np.asarray(model.apply(params, ids))).all()


def test_the_stacked_forms_refuse_carried_values_in_words(monkeypatch):
    cfg = tiny()
    model = CausalLM(cfg)
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 8), np.int32)}))
    with pytest.raises(ValueError, match="give or take values between blocks .scan_out. need the unrolled loop"):
        CausalLM(tiny(scan_layers=True, n_layers=2, layer_kinds=(("ssm", "dense"),) * 2, layer_numbers=None)).init(
            jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 8), np.int32)})
    # by ``stackable`` first, and by the carried values where every kind says it stacks
    for refused in (lambda: model.to_pipeline(1, params=shapes), lambda: InferenceEngineV2(model, shapes)):
        with pytest.raises(NotImplementedError, match="ssm"):
            refused()
    for name in ("ssm", "diff", "diff_window", "gmu", "diff_cross"):
        monkeypatch.setattr(table.MIXERS[name], "stackable", True)
    with pytest.raises(NotImplementedError, match="give or take layer, scan_out, shared_k, shared_v between blocks"):
        model.to_pipeline(1, params=shapes)
    with pytest.raises(NotImplementedError, match="give or take .'layer', 'scan_out', 'shared_k', 'shared_v'. between blocks"):
        InferenceEngineV2(model, shapes)


def test_the_model_trains_under_the_engine_with_remat_and_says_its_paths():
    from deepspeed_tpu.telemetry import get_tracer

    ids = np.random.default_rng(0).integers(0, 97, (2, 48)).astype(np.int32)
    model = CausalLM(tiny(remat=True))
    params = model.init(jax.random.PRNGKey(0), {"input_ids": ids})
    reset_mesh()
    topo = initialize_mesh(MeshConfig.from_dict({"data": 1}), devices=jax.devices()[:1], force=True)
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params, mesh=topo, config={
        "train_micro_batch_size_per_gpu": 2, "optimizer": {"type": "adam", "params": {"lr": 1e-2}}, "zero_optimization": {"stage": 0},
        "mesh": {"data": 1}, "steps_per_print": 10**9})
    losses = []
    for _ in range(3):
        loss = engine.forward({"input_ids": ids})
        engine.backward(loss)
        engine.step()
        losses.append(float(loss))
    assert np.isfinite(losses).all() and losses[2] < losses[0]
    said = [s["attrs"] for s in get_tracer().spans() if s["name"] == "program/first_call" and s["attrs"].get("family") == "train"][-1]
    assert said["layer_kinds"] == "diff+dense:1,diff_cross+dense:1,diff_window+dense:1,gmu+dense:1,ssm+dense:2"
    assert said["ssm_path"] == "xla" and said["diff_path"] == "xla" and said["remat_keeps"] == "flash_attention+projection+ssm_scan"
    assert said["block_traces"] == 5  # the two scan layers share one trace: a layer's number is a value, not a part of the kind
    reset_mesh()


def test_zero3s_block_hook_passes_the_carried_values_through():
    """``gathered_block`` (the wrap ZeRO-3's plan gives ``block_fn``) on a mesh of four: a block that takes keys, values
    and its number and one that gives them, each equal to the plain block on whole parameters, cotangents included."""
    from jax.sharding import Mesh, PartitionSpec as P

    from deepspeed_tpu.runtime.zero import overlap

    cfg = tiny()
    mesh = Mesh(np.array(jax.devices()[:4]), ("fsdp",))
    plan = type("Plan", (), {"axis": "fsdp", "size": 4, "mesh": mesh})()
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 64))
    positions = jnp.broadcast_to(jnp.arange(16, dtype=jnp.int32), (4, 16))
    for kind, layer, taken in ((KINDS[3], "layer_3", {"layer": jnp.asarray(17, jnp.int32)}),
                               (KINDS[5], "layer_5", {"layer": jnp.asarray(19, jnp.int32), "shared_k": jax.random.normal(jax.random.PRNGKey(2), (4, 16, 4, 16)),
                                                      "shared_v": jax.random.normal(jax.random.PRNGKey(3), (4, 16, 2, 32))})):
        params = Block(cfg, kind).init(jax.random.PRNGKey(0), x, positions, None, None, taken)["params"]
        specs = jax.tree_util.tree_map(lambda w: P("fsdp") if w.ndim >= 2 and w.shape[0] % 4 == 0 else P(), params)
        sharded = jax.tree_util.tree_map(lambda w, s: jax.device_put(w, jax.sharding.NamedSharding(mesh, s)), params, specs)
        for keep in (True, False):
            wrap = lambda apply: overlap.gathered_block(plan, specs, keep, apply)

            def total(fn, p, x, taken):
                (y, _), _, given = fn(p, x, positions, None, None, taken)
                return jnp.sum(y) + sum(jnp.sum(v) for v in given.values())

            floats = {k: v for k, v in taken.items() if k != "layer"}
            run = lambda fn, p: jax.value_and_grad(lambda p, x, f: total(fn, p, x, dict(taken, **f)), argnums=(0, 1, 2))(p, x, floats)
            want, g_want = run(block_fn(cfg, kind, True, False), params)
            got, g_got = jax.jit(lambda p: run(block_fn(cfg, kind, True, False, wrap=wrap), p))(sharded)
            close(got, want, 1e-5)
            for a, b in zip(jax.tree_util.tree_leaves(g_got), jax.tree_util.tree_leaves(g_want)):
                close(a, b, 2e-5)


# ------------------------------------------------------------------ a model that carries nothing traces what it traced
def _parents_block_fn(cfg, kind, train, remat):
    """``block_fn`` as it was before a block took or gave values (PR 45), word for word but for the names."""
    block = Block(cfg, kind, is_training=train)

    def apply(params, x, positions, kv_cache, segment_ids):
        with table.region("block", site="train"):
            out, sown = block.apply({"params": params}, x, positions, kv_cache, segment_ids, mutable=_SOWN)
        return (out if kv_cache is not None else (out, None)), sown

    fn = apply
    if remat:
        keeps = table.remat_keeps(kind)
        fn = jax.checkpoint(fn, policy=jax.checkpoint_policies.save_only_these_names(*keeps)) if keeps else jax.checkpoint(fn)
    return jax.jit(fn, inline=True)


@pytest.mark.parametrize("kind,remat", [(("full", "dense"), False), (("full", "dense"), True), (("window", "moe"), True), (("gdn", "routed"), True),
                                        (("mla", "routed"), True), (("kda", "dense"), True), (("sparse", "routed"), True)])
def test_a_block_that_carries_nothing_is_equation_for_equation_the_parents(kind, remat):
    """The gradient program of a block of every older kind: through ``block_fn`` with its empty dicts, and through the
    parent's form that knew of none. The texts are equal but for addresses."""
    from test_layer_kinds import tiny as tiny_kind

    cfg = dataclasses.replace(tiny_kind(*kind), remat=remat)
    x, positions = jnp.zeros((2, 64, 32)), jnp.broadcast_to(jnp.arange(64, dtype=jnp.int32), (2, 64))
    params = jax.eval_shape(lambda: Block(cfg, kind).init(jax.random.PRNGKey(0), x, positions))["params"]
    text = lambda loss: re.sub(r"0x[0-9a-f]+", "0x", str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(params, x)))
    ours = text(lambda p, x: jnp.sum(block_fn(cfg, kind, True, remat)(p, x, positions, None, None, {})[0][0].astype(jnp.float32)))
    parents = text(lambda p, x: jnp.sum(_parents_block_fn(cfg, kind, True, remat)(p, x, positions, None, None)[0][0].astype(jnp.float32)))
    assert ours == parents and not table.MIXERS[kind[0]].gives and not table.MIXERS[kind[0]].takes
