"""A LOOPED stack: ``n_layers`` blocks run ``loop_steps`` times on the same parameters, every sublayer between two norms
(``norm_scheme="sandwich"``), the final norm inside the loop, ONE head and ONE exit gate after every pass, and the
expected-loss objective whose weights (the exit distribution) carry a gradient. The model against the configuration's
plain reference at a small width on the CPU, in every pass's logits, the gates, the exit distribution, the loss and every
leaf's gradient, float32 and bf16, ``remat`` on and off; a shared leaf's
gradient as the sum of ``T`` untied copies'; what the reference's controls break; ``fused_cross_entropy_tokens`` against
``jax.grad`` of the unfused form; the older callers' programs, equation for equation what the parent commit traced; what
refuses ``loop_steps > 1``, by name; the trainer's path, its first-call line, its regions and its device counts.

The reference is the benchmark configuration's own file (``benchmarks/configs/ouro-2.6b-l8.reference.py``), loaded by
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
from deepspeed_tpu.ops import fused_ce
from deepspeed_tpu.telemetry import get_registry, get_tracer

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
VOCAB, S, T, L, BETA = 211, 80, 4, 2, 0.05
PUBLISHED = {"rms_norm_eps": 1e-6, "rope_theta": 1e6, "total_ut_steps": T, "num_hidden_layers": L}
REF = {"beta": BETA}
IDS = np.random.default_rng(3).integers(0, VOCAB, (2, S)).astype(np.int32)


def tiny(**over):
    base = dict(vocab_size=VOCAB, n_layers=L, n_heads=4, n_kv_heads=4, head_dims=16, d_model=64, d_ff=96, max_seq_len=S, norm="rmsnorm",
                norm_eps=1e-6, norm_scheme="sandwich", activation="swiglu", pos_emb="rope", rope_theta=1e6, tie_embeddings=False,
                loop_steps=T, exit_gate=True, exit_entropy_coef=BETA)
    return TransformerConfig(**dict(base, **over))


@pytest.fixture(scope="module")
def ref():
    path = os.path.join(ROOT, "benchmarks", "configs", "ouro-2.6b-l8.reference.py")
    spec = importlib.util.spec_from_file_location("ouro_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def seeded(cfg, by=0.05):
    """Every leaf moved off its start: the norms' weights off one, and the gate off ZERO (at its start lambda is 1/2 whatever
    the state, and the state takes no gradient through it), by six times as much."""
    params = CausalLM(cfg).init(jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 16), np.int32)})
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    far_off = lambda path: 6.0 if "exit_gate" in jax.tree_util.keystr(path) else 1.0
    return jax.tree_util.tree_unflatten(tree, [x + by * far_off(path) * jax.random.normal(jax.random.PRNGKey(7 + i), x.shape)
                                               for i, (path, x) in enumerate(leaves)])


def close(a, b, tol, what=""):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert np.max(np.abs(a - b)) <= tol * (1.0 + np.max(np.abs(b))), (what, np.max(np.abs(a - b)), np.max(np.abs(b)))


def of_the_model(cfg, params):
    """(every pass's logits, lambda_t of the passes that have a gate, p, the loss, its gradient) by the program."""
    model = CausalLM(cfg)
    hidden = model.apply(params, IDS, return_hidden=True)
    logits = jnp.einsum("tbsd,dv->tbsv", hidden.astype(jnp.float32), params["lm_head"]["kernel"])
    gate = jnp.einsum("tbsd,d->tbs", hidden[:-1].astype(jnp.float32), params["exit_gate"]["kernel"][:, 0]) + params["exit_gate"]["bias"]
    loss, grads = jax.value_and_grad(lambda p: model.loss_fn(p, {"input_ids": IDS}))(params)
    return logits, jax.nn.sigmoid(gate), table.exit_distribution(gate)[1], loss, grads


def test_the_tree_holds_four_norms_a_layer_one_gate_and_nothing_a_pass():
    params = jax.eval_shape(lambda: CausalLM(tiny()).init(jax.random.PRNGKey(0), {"input_ids": IDS[:1]}))
    assert sorted(params) == ["RMSNorm_0", "exit_gate", "layer_0", "layer_1", "lm_head", "wte"]
    assert sorted(params["layer_0"]) == ["RMSNorm_0", "RMSNorm_1", "RMSNorm_2", "RMSNorm_3", "attn", "mlp"]
    assert params["exit_gate"]["kernel"].shape == (64, 1) and params["exit_gate"]["bias"].shape == (1,)
    once = jax.eval_shape(lambda: CausalLM(tiny(loop_steps=1, exit_gate=False, exit_entropy_coef=0.0)).init(jax.random.PRNGKey(0), {"input_ids": IDS[:1]}))
    count = lambda tree: sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))
    assert count(params) == count(once) + 64 + 1  # the gate, and NOTHING a pass


# float32: both sides are float32 and differ in the order of their sums (the rotation as a product with a matrix of ones,
# the fused cross-entropy's chunks): 1e-5 of the largest value is a hundred roundings. bf16: each side rounds every
# product's result to 8 bits and the two round at different places (the program keeps a checkpointed block's input in bf16
# and rotates in one pass); over 8 block applications a logit near 1 drifts by a few 1e-2 and a gradient of 1e-2 by a few
# 1e-4: 0.08 of the largest value is what the plain bf16 path itself lies from the float32 one
@pytest.mark.parametrize("dtype,remat,tol", [(jnp.float32, False, 1e-5), (jnp.float32, True, 1e-5), (jnp.bfloat16, False, 0.08), (jnp.bfloat16, True, 0.08)])
def test_the_model_is_the_plain_reference_in_every_pass_and_every_gradient(ref, dtype, remat, tol):
    cfg = tiny(dtype=dtype, remat=remat)
    params = seeded(cfg)
    logits, lam, p, loss, grads = of_the_model(cfg, params)
    (want_loss, want), want_grads = ref.loss_and_grads(params, IDS, PUBLISHED, REF, dtype)
    want_logits = ref.pass_logits(params, IDS, PUBLISHED, REF, dtype, np.arange(S))
    assert logits.shape == want_logits.shape == (T, 2, S, VOCAB)
    close(logits, want_logits, tol, "logits")
    close(lam[..., :-1], want["lam"][:-1], tol, "lambda")
    close(p[..., :-1], want["p"], tol, "p")
    close(loss, want_loss, tol if dtype == jnp.float32 else 2e-3, "loss")
    flat, want_flat = (dict(jax.tree_util.tree_flatten_with_path(g)[0]) for g in (grads, want_grads))
    assert flat.keys() == want_flat.keys() and len(flat) == 2 * (4 + 4 + 3) + 5
    for leaf in flat:
        close(flat[leaf], want_flat[leaf], tol * (1 if dtype == jnp.float32 else 2), jax.tree_util.keystr(leaf))


def test_the_exit_distribution_sums_to_one_and_the_last_pass_has_no_gate():
    logits = jax.random.normal(jax.random.PRNGKey(1), (T - 1, 2, S)) * 3
    log_p, p = table.exit_distribution(logits)
    lam = jax.nn.sigmoid(logits)
    np.testing.assert_allclose(jnp.sum(p, axis=0), 1.0, atol=1e-6)
    np.testing.assert_allclose(p[0], lam[0], atol=1e-6)
    np.testing.assert_allclose(p[2], lam[2] * (1 - lam[0]) * (1 - lam[1]), atol=1e-6)
    np.testing.assert_allclose(p[3], (1 - lam[0]) * (1 - lam[1]) * (1 - lam[2]), atol=1e-6)
    np.testing.assert_allclose(jnp.exp(log_p), p, atol=1e-6)
    # with a head that says nothing (every nll is ln V, constant), what reaches a state is the gate's alone: the last
    # pass's state gets NO gradient (lambda_T is unused: the last pass takes what is left), the earlier ones do
    cfg = tiny()
    params = seeded(cfg)
    params["lm_head"]["kernel"] = jnp.zeros_like(params["lm_head"]["kernel"])
    model = CausalLM(cfg)
    hidden = model.apply(params, IDS, return_hidden=True)
    leaves = (params["lm_head"]["kernel"],)
    dh = jax.grad(lambda h: model._loop_loss(params, h, leaves, {"input_ids": IDS}))(hidden)
    assert float(jnp.max(jnp.abs(dh[-1]))) == 0.0 and all(float(jnp.max(jnp.abs(dh[t]))) > 1e-6 for t in range(T - 1))


def test_a_shared_leafs_gradient_is_the_sum_of_its_uses_in_untied_copies():
    """The loop by hand over ``T`` COPIES of the stack and of the final norm (the program's own blocks, one set of
    parameters a pass), the program's objective on the states: summed over the copies, every layer's and the norm's
    gradient is the looped model's."""
    cfg = tiny()
    params = seeded(cfg)
    model = CausalLM(cfg)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), IDS.shape)
    shared = {k: v for k, v in params.items() if k.startswith("layer_") or k == "RMSNorm_0"}

    def untied(copies):
        x, states = params["wte"][IDS], []
        for copy in copies:
            for i in range(L):
                x = table.Block(cfg, ("full", "dense")).apply({"params": copy[f"layer_{i}"]}, x, positions)
            x = table.make_norm(cfg).apply({"params": copy["RMSNorm_0"]}, x)
            states.append(x)
        return model._loop_loss(params, jnp.stack(states), (params["lm_head"]["kernel"],), {"input_ids": IDS})

    loss, by_copy = jax.value_and_grad(untied)([shared] * T)
    want_loss, want = jax.value_and_grad(lambda p: model.loss_fn(p, {"input_ids": IDS}))(params)
    close(loss, want_loss, 1e-6, "loss")
    summed = jax.tree_util.tree_map(lambda *uses: sum(uses), *by_copy)
    for leaf, got in jax.tree_util.tree_flatten_with_path(summed)[0]:
        one_use = by_copy[0]
        for key in leaf:
            one_use, want_leaf = one_use[key.key], (want if key is leaf[0] else want_leaf)[key.key]
        close(got, want_leaf, 1e-5, jax.tree_util.keystr(leaf))
        assert np.max(np.abs(np.asarray(one_use) - np.asarray(got))) > 1e-3 * np.max(np.abs(np.asarray(got)))  # one use is not the sum


def test_without_a_gate_the_loss_is_the_last_passs(ref):
    cfg = tiny(exit_gate=False, exit_entropy_coef=0.0)
    params = seeded(tiny())
    params.pop("exit_gate")
    loss = CausalLM(cfg).loss_fn(params, {"input_ids": IDS})
    last = ref.pass_logits(dict(params), IDS, PUBLISHED, REF, jnp.float32, np.arange(S))[-1]
    logp = jax.nn.log_softmax(last[:, :-1], axis=-1)
    close(loss, -jnp.mean(jnp.take_along_axis(logp, IDS[:, 1:, None], axis=-1)), 1e-5)
    logits = CausalLM(cfg).apply(params, IDS)  # a caller that asks for logits gets the last pass's
    close(logits, last, 1e-5)


CONTROLS = ["steps_short", "norm_outside_loop", "no_sandwich", "uniform_exit", "no_entropy", "layers_short"]


@pytest.mark.parametrize("control", CONTROLS)
def test_a_reference_with_one_thing_wrong_is_far_from_the_model(ref, control):
    """Each control of the configuration's ``ref_cfg`` moves the loss by more than fifty times the float32 agreement, or
    (a missing pass) changes the shape of what is compared."""
    cfg = tiny()
    params = seeded(cfg)
    loss = CausalLM(cfg).loss_fn(params, {"input_ids": IDS})
    out = ref.logits(params, IDS, PUBLISHED, dict(REF, **{control: True}), jnp.float32)
    assert out["nll"].shape[0] == (T - 1 if control == "steps_short" else T)
    assert abs(float(ref.loss(out, IDS)) - float(loss)) > 5e-4, control


def test_the_precision_below_the_stated_one_is_seen_in_the_gates_and_the_loss(ref):
    """``low_state`` (the norms', the softmax's, the gate's and the cross-entropy's statistics in bf16): further from the
    float32 reference than the plain bf16 path, in lambda and in the loss."""
    cfg = tiny()
    params = seeded(cfg)
    truth, plain, low = (ref.logits(params, IDS, PUBLISHED, dict(REF, **extra), dtype)
                         for extra, dtype in (({}, jnp.float32), ({}, jnp.bfloat16), ({"low_state": True}, jnp.bfloat16)))
    err = lambda a, key: float(jnp.max(jnp.abs(a[key] - truth[key])))
    assert err(low, "lam") > 1.5 * err(plain, "lam") and err(low, "nll") > 1.5 * err(plain, "nll")


# ---------------------------------------------------------------- ops/fused_ce.py
def _unfused_tokens(x, w, labels, bias=None, vd_layout=False):
    logits = jnp.einsum("bsd,vd->bsv" if vd_layout else "bsd,dv->bsv", x, w).astype(jnp.float32) + (0.0 if bias is None else bias)
    valid = labels != -100
    logp = jax.nn.log_softmax(logits, axis=-1)
    return jnp.where(valid, -jnp.take_along_axis(logp, jnp.where(valid, labels, 0)[..., None], axis=-1)[..., 0], 0.0)


@pytest.mark.parametrize("vd_layout,with_bias,chunk", [(False, False, 16), (False, True, 32), (True, False, 64), (True, True, 16)])
def test_a_tokens_loss_and_its_cotangent_are_jax_grad_of_the_unfused_form(vd_layout, with_bias, chunk):
    """``fused_cross_entropy_tokens`` under an objective that weighs a token by something it differentiates,
    ``sum_i softmax(a)_i nll_i``: the value, and the gradients in the states, the head, the bias AND the weights' own
    parameter, against plain autodiff of the whole logits."""
    B, S_, D, V = 3, 64, 32, 97
    k = jax.random.split(jax.random.PRNGKey(0), 5)
    x, w = jax.random.normal(k[0], (B, S_, D)), jax.random.normal(k[1], (V, D) if vd_layout else (D, V)) * 0.2
    bias = jax.random.normal(k[2], (V,)) if with_bias else None
    labels = jax.random.randint(k[3], (B, S_), 0, V).at[:, -3:].set(-100)
    a = jax.random.normal(k[4], (B, S_))
    weigh = lambda a: jax.nn.softmax(a.reshape(-1)).reshape(a.shape)
    fused = lambda x, w, bias, a: jnp.sum(weigh(a) * fused_ce.fused_cross_entropy_tokens(x, w, labels, vd_layout=vd_layout, chunk=chunk, bias=bias))
    plain = lambda x, w, bias, a: jnp.sum(weigh(a) * _unfused_tokens(x, w, labels, bias, vd_layout))
    argnums = (0, 1, 2, 3) if with_bias else (0, 1, 3)
    got, want = (jax.value_and_grad(f, argnums)(x, w, bias, a) for f in (fused, plain))
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for g, wnt in zip(got[1], want[1]):
        np.testing.assert_allclose(g, wnt, rtol=2e-4, atol=2e-6)
    tokens = fused_ce.fused_cross_entropy_tokens(x, w, labels, vd_layout=vd_layout, chunk=chunk, bias=bias)
    assert tokens.shape == (B, S_) and float(jnp.max(jnp.abs(tokens[:, -3:]))) == 0.0
    total, count = fused_ce.fused_cross_entropy_sums(x, w, labels, vd_layout=vd_layout, chunk=chunk, bias=bias)
    np.testing.assert_allclose(jnp.sum(tokens), total, rtol=1e-6)
    assert int(count) == B * (S_ - 3)


def _pin(jaxpr):
    text = re.sub(r"0x[0-9a-f]+", "0x", str(jaxpr))
    return len(text.splitlines()), hashlib.sha256(text.encode()).hexdigest()[:16]


def _ce_programs():
    x, w, labels = jnp.zeros((2, 64, 32), jnp.bfloat16), jnp.zeros((32, 97), jnp.bfloat16), jnp.zeros((2, 64), jnp.int32)
    weights = jnp.ones((2, 64), jnp.float32)
    mean = lambda x, w: fused_ce.fused_cross_entropy(x, w, labels, chunk=16)
    weighed = lambda x, w, weights: fused_ce.fused_cross_entropy_sums(x, w, labels, chunk=16, weights=weights)[0]
    return {"mean": _pin(jax.make_jaxpr(jax.value_and_grad(mean, (0, 1)))(x, w)),
            "weighed": _pin(jax.make_jaxpr(jax.value_and_grad(weighed, (0, 1)))(x, w, weights))}


# (lines, sha256 of ``str(jaxpr)``) made from the PARENT commit (PR 62) by the same lines under this suite's ``conftest.py``
PARENTS_CE = {"mean": (237, "e32faeca65e21eeb"), "weighed": (210, "adbfac504553a462")}


@pytest.mark.parametrize("which", ["mean", "weighed"])
def test_the_sums_callers_programs_are_the_ones_the_parent_traced(which):
    """``fused_cross_entropy`` and block diffusion's weighted sum (constant weights), value and gradient: equation for
    equation what they were before the scans learned to hand out a loss a token, so bit for bit the same result."""
    assert _ce_programs()[which] == PARENTS_CE[which]


def test_a_constant_weight_takes_no_gradient_and_the_weighted_sum_is_the_unfused_one():
    x, w = jax.random.normal(jax.random.PRNGKey(0), (2, 64, 32)), jax.random.normal(jax.random.PRNGKey(1), (32, 97)) * 0.2
    labels = jax.random.randint(jax.random.PRNGKey(2), (2, 64), 0, 97)
    weights = jax.random.uniform(jax.random.PRNGKey(3), (2, 64))
    total = lambda weights: fused_ce.fused_cross_entropy_sums(x, w, labels, chunk=16, weights=weights)[0]
    np.testing.assert_allclose(total(weights), jnp.sum(weights * _unfused_tokens(x, w, labels)), rtol=1e-5)
    assert float(jnp.max(jnp.abs(jax.grad(total)(weights)))) == 0.0


PARENTS_PROGRAMS = {
    "olmo-1b": {"forward": (453, "7a1f612927c7c7bb"), "gradient": (1625, "266e6637d13d21be")},
    "nemotron3-nano-30b-l9e8": {"forward": (1459, "3f8cacc043a022a8"), "gradient": (5864, "522ada3994588d28")},
    "phi4-mini-flash-l6": {"forward": (1932, "003123d10985db1b"), "gradient": (7920, "17919f53fda1bfd8")},
}


def program_of(name):
    with open(os.path.join(ROOT, "benchmarks", "configs", f"{name}.json")) as f:
        cfg = json.load(f)
    program = dict(cfg["program"], **cfg["rehearse"].get("program", {}))
    dtype = jnp.bfloat16 if program.pop("dtype", None) == "bfloat16" else jnp.float32
    hashable = lambda v: tuple(hashable(x) for x in v) if isinstance(v, list) else v
    model = CausalLM(TransformerConfig(**{k: hashable(v) for k, v in program.items()}, dtype=dtype))
    ids = np.zeros((1, 64), np.int32)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), {"input_ids": ids}))
    return {"forward": _pin(jax.make_jaxpr(lambda p, i: model.apply(p, i))(params, ids)),
            "gradient": _pin(jax.make_jaxpr(jax.grad(lambda p, i: model.loss_fn(p, {"input_ids": i})))(params, ids))}


@pytest.mark.parametrize("name", sorted(PARENTS_PROGRAMS))
@pytest.mark.parametrize("which", ["forward", "gradient"])
def test_with_one_pass_pre_norms_and_no_gate_a_cells_program_is_the_one_the_parent_traced(name, which):
    """``loop_steps = 1``, ``norm_scheme = "pre"``, no gate: three cells that ``test_mamba2_layers.py`` does not pin (the dense
    one, the newest and the one whose layers share values) trace what the parent commit traced, forward and gradient."""
    assert program_of(name)[which] == PARENTS_PROGRAMS[name][which]


# ---------------------------------------------------------------- what refuses a loop
def _scan_layers():
    CausalLM(tiny(scan_layers=True)).init(jax.random.PRNGKey(0), {"input_ids": IDS[:1]})


def _kv_caches():
    CausalLM(tiny()).init_kv_caches(1, 16)


def _decode():
    cfg = tiny()
    zeros = lambda: jnp.zeros((1, 16, 4, 16))
    CausalLM(cfg).apply(seeded(cfg), IDS[:1, :4], kv_caches=[(zeros(), zeros(), jnp.asarray(0, jnp.int32))] * L)


def _pipeline():
    cfg = tiny()
    CausalLM(cfg).to_pipeline(2, params=seeded(cfg))


def _serving():
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2

    InferenceEngineV2(CausalLM(tiny()), seeded(tiny()))


def _zero3_hook():
    cfg = tiny()
    with table.block_hook(object()):
        CausalLM(cfg).loss_fn(seeded(cfg), {"input_ids": IDS})


def _layer_drop():
    cfg = tiny()
    CausalLM(cfg).loss_fn(seeded(cfg), {"input_ids": IDS, "pld_theta": 0.5}, rng=jax.random.PRNGKey(0))


@pytest.mark.parametrize("run,says", [(_scan_layers, "scan_layers"), (_kv_caches, "kv_caches"), (_decode, "kv_caches"), (_pipeline, "to_pipeline"),
                                      (_serving, "loop_steps=4"), (_zero3_hook, "block_hook"), (_layer_drop, "pld_theta")])
def test_what_cannot_run_a_loop_refuses_it_by_name(run, says):
    with pytest.raises(NotImplementedError, match=f"loop_steps=4.*{says}|{says}.*loop_steps" if says != "loop_steps=4" else says):
        run()


@pytest.mark.parametrize("over,says", [({"loop_steps": 0}, "loop_steps=0"), ({"loop_steps": 1}, "exit_gate=True with loop_steps=1"),
                                       ({"exit_gate": False}, "exit_entropy_coef=0.05 without exit_gate"),
                                       ({"block_type": "parallel"}, "norm_scheme='sandwich'.*block_type"),
                                       ({"norm_scheme": "both"}, "norm_scheme='both'")])
def test_a_configuration_that_contradicts_itself_is_refused_with_the_fields_name(over, says):
    with pytest.raises(ValueError, match=says):
        tiny(**over)


def test_the_serving_side_refuses_sandwich_norms_too():
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2

    cfg = tiny(loop_steps=1, exit_gate=False, exit_entropy_coef=0.0)
    with pytest.raises(NotImplementedError, match="norm_scheme='sandwich'"):
        InferenceEngineV2(CausalLM(cfg), seeded(cfg))


# ---------------------------------------------------------------- the trainer's path, its regions and its counts
def test_the_passes_lie_under_the_loops_region_beside_the_head_and_the_gate():
    cfg = tiny(remat=True)
    params = jax.eval_shape(lambda: seeded(cfg))
    text = jax.jit(jax.grad(lambda p: CausalLM(cfg).loss_fn(p, {"input_ids": IDS}))).lower(params).as_text(debug_info=True)
    for inside in ("loop_step/checkpoint/block/", "loop_step/norm/", "jvp(exit_gate)/", "jvp(head)/"):
        assert inside in text, inside
    assert text.count("stablehlo.while") >= 2  # ONE body a direction, run ``loop_steps`` times: the stack's equations are in the program once


def test_the_loop_trains_through_initialize_and_says_and_counts_what_it_ran():
    import deepspeed_tpu
    from deepspeed_tpu.parallel.mesh import initialize_mesh, reset_mesh
    from deepspeed_tpu.runtime.config import MeshConfig

    model = CausalLM(tiny(remat=True, dtype=jnp.bfloat16))
    params = model.init(jax.random.PRNGKey(0), {"input_ids": IDS[:1]})
    reg = get_registry()
    applied = reg.peek("train_loop_block_applications_total") or 0.0
    reset_mesh()
    try:
        topo = initialize_mesh(MeshConfig.from_dict({"data": 1}), devices=jax.devices()[:1], force=True)
        engine, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params, mesh=topo, config={
            "train_micro_batch_size_per_gpu": 2, "gradient_accumulation_steps": 1, "steps_per_print": 10**9, "bf16": {"enabled": True},
            "optimizer": {"type": "adam", "params": {"lr": 1e-3}}, "zero_optimization": {"stage": 0}})
        losses = []
        for _ in range(6):
            loss = engine.forward({"input_ids": IDS})
            engine.backward(loss)
            engine.step()
            losses.append(float(loss))
    finally:
        reset_mesh()
    assert np.isfinite(losses).all() and losses[-1] < losses[0]
    rose = reg.peek("train_loop_block_applications_total") - applied
    assert rose % (L * T) == 0 and 4 <= rose / (L * T) <= 6  # a step's count reaches the registry a dispatch or two late
    masses = [reg.peek("train_loop_exit_mass", step=str(t)) for t in range(1, T + 1)]
    np.testing.assert_allclose(sum(masses), 1.0, atol=1e-3)
    np.testing.assert_allclose(masses, [0.5, 0.25, 0.125, 0.125], atol=0.03)  # the gate starts at zero: lambda = 1/2
    expected_steps = reg.peek("train_loop_expected_steps")
    np.testing.assert_allclose(expected_steps, sum(t * m for t, m in zip(range(1, T + 1), masses)), atol=1e-3)
    assert 0.9 < reg.peek("train_loop_exit_entropy") < np.log(T) + 1e-3
    assert all(np.log(VOCAB) - 3 < reg.peek("train_loop_step_loss", step=str(t)) < np.log(VOCAB) + 1 for t in range(1, T + 1))
    said = [s["attrs"] for s in get_tracer().spans() if s["name"] == "program/first_call" and s["attrs"].get("family") == "train"][-1]
    assert (said["loop_steps"], said["block_traces"], said["remat_keeps"]) == (T, 1, "flash_attention")
