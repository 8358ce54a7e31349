"""A stack whose layers mix tokens by a gated short convolution (the kind ``conv``) three times in four and by grouped-query
attention with q/k norms once, over a dense SwiGLU and a routed FFN chosen by a biased sigmoid router: the model against
the configuration's plain reference at a small width on the CPU, in logits, loss and every leaf's gradient, a block of
each pair and the five-layer stack, ``remat`` on and off; what the reference's controls break; the selection bias (it
changes the choice and takes no gradient); the four shares of a routed layer add up to the uncut layer; the kernels of
``ops/pallas/short_conv.py`` (interpreted) against the definition; the kind's record; the trainer's path and the
first-call line; and the older cells' steps, equation for equation what the parent commit traced.

The reference is the benchmark configuration's own file (``benchmarks/configs/lfm2-8b-a1b-l5e8.reference.py``), loaded by
its path: it imports nothing of the program or of the benchmark."""

import hashlib
import importlib.util
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import CausalLM, TransformerConfig, transformer as table
from deepspeed_tpu.models.mixers import ShortConvMixer, causal_conv, gated_conv
from deepspeed_tpu.moe.sharded_moe import sigmoid_topk
from deepspeed_tpu.ops.pallas import short_conv
from deepspeed_tpu.telemetry import get_registry, get_tracer
from deepspeed_tpu.telemetry.tracing import regions_traced

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
VOCAB, S = 211, 80
TYPES = ["conv", "conv", "full_attention", "conv", "conv", "conv", "full_attention", "conv"]
PUBLISHED = {"norm_eps": 1e-5, "rope_theta": 1e6, "num_hidden_layers": 5, "num_experts": 4, "num_experts_per_tok": 4, "routed_over": 16,
             "layer_types": TYPES, "num_dense_layers": 2, "layers_here": [1, 2, 3, 4, 5]}
REF = {"held_first": 4}
KINDS = (("conv", "dense"), ("full", "routed"), ("conv", "routed"), ("conv", "routed"), ("conv", "routed"))


def tiny(**over):
    base = dict(vocab_size=VOCAB, n_layers=5, n_heads=4, n_kv_heads=2, head_dims=16, d_model=64, d_ff=96, max_seq_len=S, norm="rmsnorm",
                activation="swiglu", pos_emb="rope", rope_theta=1e6, tie_embeddings=True, norm_eps=1e-5, qk_norm=True, conv_kernel=3,
                layer_kinds=KINDS, moe_num_experts=16, moe_top_k=4, moe_d_ff=32, moe_held=(4, 4), moe_scoring="sigmoid", moe_renorm_eps=1e-6,
                moe_aux_loss_coef=0.0)
    return TransformerConfig(**dict(base, **over))


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location("lfm2_reference", os.path.join(ROOT, "benchmarks", "configs", "lfm2-8b-a1b-l5e8.reference.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


IDS = np.random.default_rng(3).integers(0, VOCAB, (2, S)).astype(np.int32)


def stirred(params, by=0.05):
    """Every leaf moved off its start; the selection bias (zeros at init: a choice by the scores alone would pass) by
    six times as much, a third of the scores' own spread, so that it moves many tokens' choice."""
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    far_off = lambda path: 6.0 if "select_bias" in jax.tree_util.keystr(path) else 1.0
    return jax.tree_util.tree_unflatten(tree, [x + by * far_off(path) * jax.random.normal(jax.random.PRNGKey(7 + i), x.shape)
                                               for i, (path, x) in enumerate(leaves)])


def seeded(cfg):
    return stirred(CausalLM(cfg).init(jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 16), np.int32)}))


def close(a, b, tol, what=""):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert np.max(np.abs(a - b)) <= tol * (1.0 + np.max(np.abs(b))), (what, np.max(np.abs(a - b)), np.max(np.abs(b)))


def far(a, b, tol):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.max(np.abs(a - b)) > tol * (1.0 + np.max(np.abs(b)))


def test_the_kinds_record_and_its_tree():
    params = jax.eval_shape(lambda: CausalLM(tiny()).init(jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 16), np.int32)}))
    assert set(params["layer_0"]) == {"RMSNorm_0", "RMSNorm_1", "conv", "mlp"} and set(params["layer_1"]) == {"RMSNorm_0", "RMSNorm_1", "attn", "routed"}
    conv = params["layer_2"]["conv"]
    assert (conv["in_proj"]["kernel"].shape, conv["conv_kernel"].shape, conv["out_proj"]["kernel"].shape) == ((64, 192), (3, 64), (64, 64))
    assert set(params["layer_1"]["attn"]) == {"q_proj", "k_proj", "v_proj", "o_proj", "q_norm", "k_norm"}
    assert set(params["layer_1"]["routed"]) == {"gate", "select_bias", "experts_wg", "experts_wi", "experts_wo"}
    assert "lm_head" not in params and len(jax.tree_util.tree_leaves(params)) == 2 + 8 + 13 + 3 * 10  # the head is the embedding
    record = table.MIXERS["conv"]
    assert record is ShortConvMixer and record.hybrid and not record.stackable and record.gives == record.takes == () and not record.sows
    assert record.keeps == ("short_conv", "projection") and record.paths == {"conv_path": ("mixer/conv", {"op": "short_conv", "pass": "fwd"})}
    assert table.remat_keeps(("conv", "dense")) == ("short_conv", "projection")
    assert table.remat_keeps(("conv", "routed")) == ("short_conv", "projection", "routed_ffn")
    assert tiny().unstackable == ("conv", "routed") and tiny().shares == () and TransformerConfig().conv_kernel == 3
    assert TransformerConfig().moe_renorm_eps == 1e-20  # the other sigmoid-routed families' own


# float32 at the highest matmul precision on both sides: what is left is the order of float32 sums (the filter's three
# products, a softmax row whole against XLA's own reduction, the fused cross-entropy against a log-softmax, the grouped
# products against a loop over experts): 2e-5 of the largest entry for the logits, 5e-5 for a gradient. A filter with an
# activation, the chunks in another order, a choice by the scores alone or no q/k norm read 1e-2 and more (below)
@pytest.mark.parametrize("kinds,remat", [((("conv", "dense"),), False), ((("conv", "routed"),), False), ((("full", "routed"),), False),
                                         (KINDS, False), (KINDS, True)], ids=["conv+dense", "conv+routed", "full+routed", "stack", "stack-remat"])
def test_the_model_is_the_plain_reference_in_logits_loss_and_every_gradient(ref, kinds, remat):
    """A block of each pair alone and the five-layer stack, on rows of 80 tokens."""
    here = {(("conv", "dense"),): [1], (("conv", "routed"),): [3], (("full", "routed"),): [2]}.get(kinds, [1, 2, 3, 4, 5])
    pub = dict(PUBLISHED, layers_here=here, num_hidden_layers=len(here))
    cfg = tiny(n_layers=len(kinds), layer_kinds=kinds, remat=remat)
    model, params = CausalLM(cfg), seeded(cfg)
    with jax.default_matmul_precision("highest"):
        close(model.apply(params, IDS), ref.logits(params, IDS, pub, REF, jnp.float32), 2e-5, "logits")
        ours, g_ours = jax.value_and_grad(lambda p: model.loss_fn(p, {"input_ids": IDS}))(params)
        (theirs, _), g_theirs = ref.loss_and_grads(params, IDS, pub, REF, jnp.float32)
    close(ours, theirs, 1e-6, "loss")
    theirs_by_path = dict(jax.tree_util.tree_leaves_with_path(g_theirs))
    mine = jax.tree_util.tree_leaves_with_path(g_ours)
    assert len(mine) == len(theirs_by_path)
    for path, leaf in mine:
        name = jax.tree_util.keystr(path)
        close(leaf, theirs_by_path[path], 5e-5, name)
        assert (float(jnp.max(jnp.abs(leaf))) > 0) == ("select_bias" not in name), name  # the bias chooses and takes no gradient


def test_remat_on_and_off_give_the_same_gradients():
    grads = []
    for remat in (False, True):
        cfg = tiny(remat=remat)
        model, params = CausalLM(cfg), seeded(cfg)
        with jax.default_matmul_precision("highest"):
            grads.append(jax.grad(lambda p: model.loss_fn(p, {"input_ids": IDS}))(params))
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(grads[0]), jax.tree_util.tree_leaves(grads[1])):
        close(a, b, 1e-6, jax.tree_util.keystr(path))


@pytest.mark.parametrize("control", [{"filter_act": "silu"}, {"chunks": "cbu"}, {"choice": "scores"}, {"qk_norm": "none"}, {"renorm": "none"},
                                     {"layers": 4}, {"no_final_norm": True}])
def test_a_reference_with_one_thing_wrong_is_far_from_the_model(ref, control):
    """An activation after the filter (as the scan layers' convolutions have), W_in's first two chunks the other way round (B and u may change places: their product commutes), the top 4
    of the scores alone (the bias ignored), no q/k norm, the chosen scores not rescaled, a layer short, no final norm."""
    cfg = tiny()
    model, params = CausalLM(cfg), seeded(cfg)
    with jax.default_matmul_precision("highest"):
        assert far(model.apply(params, IDS), ref.logits(params, IDS, PUBLISHED, dict(REF, **control), jnp.float32), 1e-2), control


def test_the_selection_bias_changes_the_choice_and_not_the_weights(ref):
    """``sigmoid_topk``: the top 4 of score + bias, weighed by the SCORES over their sum plus ``eps``; against the
    reference's published form, and by hand for a bias that lifts one expert over the rest."""
    logits = jax.random.normal(jax.random.PRNGKey(1), (256, 32), jnp.float32)
    bias = 0.3 * jax.random.normal(jax.random.PRNGKey(2), (32,), jnp.float32)
    idx, weights = sigmoid_topk(logits, bias, 4, 1.0, 1e-6)
    idx_ref, weights_ref = ref.routing(logits, bias, 4)
    order = lambda i, w: (jnp.take_along_axis(i, jnp.argsort(i, axis=-1), -1), jnp.take_along_axis(w, jnp.argsort(i, axis=-1), -1))
    (i_a, w_a), (i_b, w_b) = order(idx, weights), order(idx_ref, weights_ref)
    assert (i_a == i_b).all()
    np.testing.assert_allclose(w_a, w_b, rtol=1e-6)
    plain, _ = sigmoid_topk(logits, jnp.zeros((32,)), 4, 1.0, 1e-6)
    assert (jnp.sort(plain, -1) != jnp.sort(idx, -1)).any()  # the bias moved some token's choice
    s = jax.nn.sigmoid(logits)
    chosen = jnp.take_along_axis(s, idx, -1)
    np.testing.assert_allclose(weights, chosen / (chosen.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)  # scores, not score + bias
    assert float(jnp.max(jnp.sum(weights, -1))) < 1.0  # the 1e-6: the four weights sum to a little under one
    lifted, _ = sigmoid_topk(logits, jnp.zeros((32,)).at[7].set(10.0), 4, 1.0, 1e-6)
    assert (lifted == 7).any(axis=-1).all()
    grad = jax.grad(lambda b: jnp.sum(sigmoid_topk(logits, b, 4, 1.0, 1e-6)[1] ** 2))(bias)
    assert float(jnp.max(jnp.abs(grad))) == 0.0
    # the default is what the other sigmoid-routed cells trace: 1e-20
    np.testing.assert_array_equal(sigmoid_topk(logits, bias, 4, 2.0)[1], sigmoid_topk(logits, bias, 4, 2.0, 1e-20)[1])


def test_the_four_shares_of_a_layer_add_up_to_the_uncut_layer(ref):
    """The share ties to the model: 32 experts, 4 a token, over 4 chips of 8. What each share's PROGRAM block adds to the
    mixer's output, summed over the four, is what the plain reference gives for the whole layer with all 32 experts (the
    mixer, computed alike on every chip, counted once), for a convolution layer and for the attention layer."""
    E, held, k, d = 32, 8, 4, 64
    pub = dict(PUBLISHED, num_experts=held, num_experts_per_tok=k, routed_over=E)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, S, d), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (2, S))
    experts = ("experts_wg", "experts_wi", "experts_wo")
    for n, kind in ((1, ("full", "routed")), (2, ("conv", "routed"))):
        whole_cfg = tiny(n_layers=1, layer_kinds=(kind,), moe_num_experts=E, moe_top_k=k, moe_held=(0, E))
        whole = stirred(table.Block(whole_cfg, kind).init(jax.random.PRNGKey(n), x, positions)["params"], 0.1)
        with jax.default_matmul_precision("highest"):
            uncut = ref.layer_part(whole, x, pub, REF, jnp.float32, n, 0, E)
            no_expert = dict(whole, routed={**whole["routed"], **{w: whole["routed"][w][:0] for w in experts}})
            h = ref.layer_part(no_expert, x, pub, REF, jnp.float32, n, 0, 0)  # the mixer's output added to the input: no expert's part
            total = h
            for share in range(E // held):
                cfg = tiny(n_layers=1, layer_kinds=(kind,), moe_num_experts=E, moe_top_k=k, moe_held=(share * held, held))
                mine = dict(whole, routed={**whole["routed"], **{w: whole["routed"][w][share * held:(share + 1) * held] for w in experts}})
                y, _ = table.Block(cfg, kind).apply({"params": mine}, x, positions, mutable=["intermediates"])
                total = total + (y - h)
        close(total, uncut, 2e-5, kind)
        assert far(h, uncut, 1e-3)  # the experts' part is no rounding


def test_the_operator_is_its_equations_by_hand():
    """``out = (C * conv(B * u)) W_out`` with ``[B, C, u] = x W_in`` in THAT order, three taps reaching two tokens back,
    no activation: written out with a loop over tokens."""
    cfg = tiny(n_layers=1, layer_kinds=(("conv", "dense"),))
    mixer = ShortConvMixer(cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 12, 64), jnp.float32)
    p = stirred(mixer.init(jax.random.PRNGKey(0), x)["params"], 0.1)
    bcu = np.asarray(x[0] @ p["in_proj"]["kernel"], np.float64)
    B, C, u = bcu[:, :64], bcu[:, 64:128], bcu[:, 128:]
    g, w = B * u, np.asarray(p["conv_kernel"], np.float64)
    c = np.stack([sum(w[j] * g[t - 2 + j] for j in range(3) if t - 2 + j >= 0) for t in range(12)])
    with jax.default_matmul_precision("highest"):
        close(mixer.apply({"params": p}, x)[0], (C * c) @ np.asarray(p["out_proj"]["kernel"], np.float64), 1e-5)
    np.testing.assert_allclose(gated_conv(jnp.asarray(bcu, jnp.float32)[None], p["conv_kernel"])[0], C * c, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(causal_conv(jnp.asarray(g, jnp.float32)[None], p["conv_kernel"])[0], c, rtol=1e-5, atol=1e-6)


def test_no_cache_and_no_packed_segments():
    mixer = ShortConvMixer.from_config(tiny(), "conv")
    x = jnp.zeros((1, 8, 64))
    params = mixer.init(jax.random.PRNGKey(0), x)
    for kw in ({"kv_cache": (x, x, jnp.asarray(0))}, {"segment_ids": jnp.zeros((1, 8), jnp.int32)}):
        with pytest.raises(NotImplementedError, match="conv layer takes no KV cache and no packed segments"):
            mixer.apply(params, x, None, **kw)
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2

    with pytest.raises(NotImplementedError, match="conv"):
        InferenceEngineV2(CausalLM(tiny(n_layers=2, layer_kinds=(("conv", "dense"),) * 2)), params=None)


@pytest.mark.parametrize("Bt,rows,D,K,dtype", [(2, 64, 128, 3, jnp.float32), (1, 96, 256, 3, jnp.bfloat16), (1, 512, 128, 4, jnp.float32), (1, 48, 640, 2, jnp.float32)])
def test_the_kernels_are_the_definition_forward_and_backward(Bt, rows, D, K, dtype):
    """One tile, three tiles of 32 (a halo either side of the middle one), two of 256, lanes of 128 at five chunks:
    interpreted, against ``gated_conv`` and what JAX derives from it. bf16: the same float32 arithmetic rounded once, so
    equal to the last bit in the output; a cotangent sums three products in another order."""
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(keys[0], (Bt, rows, 3 * D), jnp.float32).astype(dtype)
    w, dy = jax.random.normal(keys[1], (K, D)), jax.random.normal(keys[2], (Bt, rows, D)).astype(dtype)
    want, vjp = jax.vjp(gated_conv, x, w)
    got, vjp_kernel = jax.vjp(lambda x, w: short_conv.short_conv(x, w, True), x, w)
    tol = 1e-5 if dtype == jnp.float32 else 1e-2
    close(got, want, 1e-6 if dtype == jnp.float32 else 0.0, "out")
    for name, a, b in zip(("dx", "dw"), vjp_kernel(dy), vjp(dy)):
        close(a, b, tol if name == "dx" else 1e-6, name)


def test_the_kernels_take_whole_tiles_and_the_chooser_says_xla_off_the_tpu():
    assert [short_conv.rows_a_tile(n) for n in (16384, 96, 80, 1008, 100)] == [256, 32, 16, 16, 0]
    assert short_conv.fits(16384, 2048, 3) and not short_conv.fits(100, 2048, 3) and not short_conv.fits(96, 64, 3)
    assert not short_conv.fits(96, 128, 9) and short_conv.fits(96, 128, 8) and not short_conv.fits(96, 128, 1)
    assert short_conv.path_for(16384, 2048, 3) == "xla"  # no TPU here


@pytest.mark.parametrize("tokens,k,experts,d,f,want", [
    (16384, 4, 32, 2048, 1792, ((512, 512, 896), (512, 896, 1024))),  # this cell: 2,048 rows an expert, 1,792 = 2 x 896
    (16384, 6, 64, 2560, 768, ((256, 512, 768), (256, 768, 512))),    # SmallThinker's, the fullest of the older cells (1,536): as it was
    (8192, 6, 64, 2048, 1408, ((256, 512, 1408), (256, 1408, 1024))),  # Kimi-VL's: as it was
    (16384, 8, 128, 2048, 768, ((256, 512, 768), (256, 768, 1024))),  # SDAR's, the same buffer of 32,768 rows at 1,024 rows an expert: as it was
])
def test_the_grouped_products_tiles_follow_the_width_and_the_load(monkeypatch, tokens, k, experts, d, f, want):
    """``routed_part`` hands the first rung a row tile of 512 where a uniform router gives an expert 2,048 rows or more;
    ``_grouped`` lists 896 for a width of 1,792. What ``gmm`` is called with, up product and down product."""
    from jax.experimental.pallas.ops.tpu import megablox

    from deepspeed_tpu.moe import sharded_moe
    from deepspeed_tpu.ops.pallas import moe_sum_rows

    n, seen = 8 if experts < 128 else 16, []

    def gmm(xs, w, sizes, preferred_element_type=None, tiling=None, interpret=False):
        seen.append(tiling)
        return jnp.zeros((xs.shape[0], w.shape[2]), xs.dtype)

    monkeypatch.setattr(megablox, "gmm", gmm)
    monkeypatch.setattr(moe_sum_rows, "fits", lambda *a: False)  # the gathers: this test reads the products' tiles alone
    idx = jnp.zeros((tokens, k), jnp.int32)
    shapes = [jax.ShapeDtypeStruct(s, jnp.bfloat16) for s in ((tokens, d), (n, d, f), (n, d, f), (n, f, d))]
    jax.eval_shape(lambda t, wg, wi, wo: sharded_moe.routed_part(t, idx, jnp.ones((tokens, k), jnp.float32), wg, wi, wo, 0, experts, True)[0], *shapes)
    first_rung = seen[:3]  # gate, up, down of the first rung: traced first
    assert (first_rung[0], first_rung[2]) == want and first_rung[1] == want[0]
    assert all(t[0] == 256 for t in seen[3:])  # the rungs above keep rows of 256


@pytest.mark.parametrize("stage,mesh,n", [(0, {"data": 1}, 1), (3, {"fsdp": 4}, 4)])
def test_the_stack_trains_through_initialize_and_the_first_call_line_names_its_kinds(stage, mesh, n):
    """Stage 0 on one device and ZeRO-3 on four virtual devices: the same first loss and the same loss after 3 steps
    within 2e-3; the first-call span names the kinds, how the operator ran and what a checkpointed block keeps."""
    import deepspeed_tpu
    from deepspeed_tpu.parallel.mesh import initialize_mesh, reset_mesh
    from deepspeed_tpu.runtime.config import MeshConfig

    model = CausalLM(tiny(remat=True))
    ids = np.random.default_rng(0).integers(0, VOCAB, (4, S)).astype(np.int32)
    params = model.init(jax.random.PRNGKey(0), {"input_ids": ids[:1]})
    reg = get_registry()
    rows = [reg.peek(name) or 0.0 for name in ("moe_rows_routed_here_total", "moe_rows_dropped_total")]
    traced = regions_traced("mixer/conv", op="short_conv", path="xla")
    reset_mesh()
    try:
        topo = initialize_mesh(MeshConfig.from_dict(mesh), devices=jax.devices()[:n], force=True)
        engine, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params, mesh=topo, config={
            "train_micro_batch_size_per_gpu": 4 // n, "gradient_accumulation_steps": 1, "steps_per_print": 10**9,
            "optimizer": {"type": "adam", "params": {"lr": 1e-3}}, "zero_optimization": {"stage": stage}})
        bias = np.asarray(jax.tree_util.tree_leaves(engine.params["layer_1"]["routed"]["select_bias"])[0]).copy()
        losses = []
        for _ in range(4):
            loss = engine.forward({"input_ids": ids})
            engine.backward(loss)
            engine.step()
            losses.append(float(loss))
    finally:
        reset_mesh()
    counted = reg.peek("moe_rows_routed_here_total") - rows[0]
    assert 3 * 4 * 4 * S * 0.5 <= counted <= 4 * 4 * 4 * S * 4  # 4 routed layers, 4 rows of S tokens, up to 4 choices each, three or four steps counted
    assert reg.peek("moe_rows_dropped_total") == rows[1] and regions_traced("mixer/conv", op="short_conv", path="xla") > traced
    np.testing.assert_array_equal(np.asarray(engine.params["layer_1"]["routed"]["select_bias"]), bias)  # a buffer: Adam's step of a zero gradient is zero
    said = [s["attrs"] for s in get_tracer().spans() if s["name"] == "program/first_call" and s["attrs"].get("family") == "train"][-1]
    assert said["layer_kinds"] == "conv+dense:1,conv+routed:3,full+routed:1" and said["block_traces"] == 3
    assert (said["conv_path"], said["full_path"], said["moe_path"], said["moe_combine"], said["rope"]) == ("xla",) * 5
    assert said["moe_router"] == "sigmoid+compare_sum" and said["remat_keeps"] == "flash_attention+projection+routed_ffn+short_conv"
    _TRAINED.setdefault("losses", losses)
    assert np.isfinite(losses).all() and losses[3] < losses[0]
    np.testing.assert_allclose([losses[0], losses[3]], [_TRAINED["losses"][0], _TRAINED["losses"][3]], atol=2e-3)


_TRAINED = {}


# (lines, sha256 of ``str(jaxpr)``) of the gradient of a rehearsal's loss, made from the PARENT commit (PR 54) by the same
# lines under this suite's ``conftest.py``: the kinds that do not ask for ``conv`` trace what they traced, equation for
# equation: the new field of the sigmoid router (Kimi-VL, Kimi-Linear), ``causal_conv``'s users (Kimi-Linear, Phi-4), the
# flash kernels' count of VMEM (every cell), the table's new line (all)
PARENTS_STEPS = {"kimi-vl-a3b-l6e8": (5438, "21efcb2b344c9faf"), "kimi-linear-48b-l5e8": (8381, "d6f2415731622e43"),
                 "phi4-mini-flash-l6": (7475, "f6c36c3d89b17795"), "smallthinker-21b-l4e8": (4356, "409a4a6701b53837"),
                 "olmo-1b": (1625, "9574049980e89c1b")}


@pytest.mark.parametrize("name", sorted(PARENTS_STEPS))
def test_an_older_cells_step_is_the_program_the_parent_traced(name):
    with open(os.path.join(ROOT, "benchmarks", "configs", f"{name}.json")) as f:
        cfg = json.load(f)
    program = dict(cfg["program"], **cfg["rehearse"].get("program", {}))
    dtype = jnp.bfloat16 if program.pop("dtype", None) == "bfloat16" else jnp.float32
    hashable = lambda v: tuple(hashable(x) for x in v) if isinstance(v, list) else v
    model = CausalLM(TransformerConfig(**{k: hashable(v) for k, v in program.items()}, dtype=dtype))
    ids = np.zeros((1, 96), np.int32)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), {"input_ids": ids}))
    text = re.sub(r"0x[0-9a-f]+", "0x", str(jax.make_jaxpr(jax.grad(lambda p, i: model.loss_fn(p, {"input_ids": i})))(params, ids)))
    assert (len(text.splitlines()), hashlib.sha256(text.encode()).hexdigest()[:16]) == PARENTS_STEPS[name]
