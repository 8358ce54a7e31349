"""The fourth configuration's layers (``models/mixers.py::GDNMixer``, ``Attention`` with an output gate, ``RoutedMoE``
with softmax scoring and a gated shared expert, the scan kernel's per-head form, ``moe_sum_rows`` at 32 held experts,
the flash backward a head at a time) against plain references, and the share of sixteen: tiny widths, float32, CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import hybrid_reference as ref
from deepspeed_tpu.models import CausalLM, TransformerConfig
from deepspeed_tpu.telemetry import get_registry

KINDS = (("gdn", "routed"),) * 3 + (("full", "routed"),)
THETA, ROTARY = 1e7, 0.25


def tiny(**over):
    """4 query heads on 1 key-value head of 32 with a quarter rotated; 2 key heads and 4 value heads of 16; 4 of 16
    experts a token by softmax, experts 4..7 held, a gated shared expert."""
    base = dict(vocab_size=211, n_layers=4, n_heads=4, n_kv_heads=1, head_dims=32, d_model=48, max_seq_len=64, norm="rmsnorm",
                rms_offset=True, norm_eps=1e-6, activation="swiglu", pos_emb="rope", rope_theta=THETA, rotary_pct=ROTARY, qk_norm=True,
                attn_output_gate=True, tie_embeddings=False, layer_kinds=KINDS, gdn_key_heads=2, gdn_value_heads=4, gdn_head_dim=16,
                moe_num_experts=16, moe_top_k=4, moe_d_ff=32, moe_shared_d_ff=32, moe_shared_gate=True, moe_scoring="softmax",
                moe_held=(4, 4), moe_aux_loss_coef=0.0)
    return TransformerConfig(**dict(base, **over))


@pytest.fixture(scope="module", autouse=True)
def _routed_rows_counters_left_as_found():
    """As ``test_hybrid_layers.py`` leaves them: a benchmark reader that is handed no counter reads the process's totals."""
    names = ("moe_rows_routed_here_total", "moe_rows_dropped_total", "moe_fallback_layers_total")
    found = {name: get_registry().peek(name) or 0.0 for name in names}
    yield
    for name in names:
        get_registry().counter(name).value = found[name]


@pytest.fixture(scope="module")
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def _close(a, b, tol=2e-5):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert np.max(np.abs(a - b)) <= tol * (1.0 + np.max(np.abs(b))), (np.max(np.abs(a - b)), np.max(np.abs(b)))


def _stirred(params, seed=11):
    """Norm weights that start at zero or one, ``A_log`` and the rest moved off their start, so that every one matters."""
    leaves, tree = jax.tree_util.tree_flatten(params)
    return jax.tree_util.tree_unflatten(tree, [x + 0.1 * jax.random.normal(jax.random.PRNGKey(seed + i), x.shape) for i, x in enumerate(leaves)])


def _routed_layer(held, cfg, shared=32, gate=True):
    from deepspeed_tpu.moe.layer import RoutedMoE

    return RoutedMoE(cfg.d_model, cfg.moe_num_experts, cfg.moe_top_k, cfg.moe_d_ff, held, shared, 1.0, "softmax", gate)


def _module(kind, cfg):
    from deepspeed_tpu.models.mixers import GDNMixer
    from deepspeed_tpu.models.transformer import Attention

    if kind == "gdn":
        return GDNMixer(cfg), (lambda m, p, h: m.apply({"params": p}, h)), lambda p, h: ref.gdn(p, h)
    if kind == "gated_attention":
        positions = lambda h: jnp.broadcast_to(jnp.arange(h.shape[1], dtype=jnp.int32), h.shape[:2])
        return (Attention(cfg), (lambda m, p, h: m.apply({"params": p}, h, positions(h))),
                lambda p, h: ref.gated_attention(p, h, THETA, ROTARY))
    return (_routed_layer(cfg.moe_held, cfg), (lambda m, p, h: m.apply({"params": p}, h)),
            lambda p, h: ref.routed_softmax(p, h, cfg.moe_held[0], cfg.moe_top_k))


@pytest.mark.parametrize("kind", ["gdn", "gated_attention", "routed_softmax"])
def test_a_layer_matches_its_plain_reference_forward_and_gradients(kind, highest):
    cfg = tiny()
    module, run, plain = _module(kind, cfg)
    h = jax.random.normal(jax.random.PRNGKey(1), (2, 40, cfg.d_model))
    init = module.init(jax.random.PRNGKey(2), h, jnp.zeros(h.shape[:2], jnp.int32)) if kind == "gated_attention" else module.init(jax.random.PRNGKey(2), h)
    params = _stirred(init["params"])
    w = jax.random.normal(jax.random.PRNGKey(3), h.shape)
    (lo, go) = jax.value_and_grad(lambda p, h: jnp.sum(run(module, p, h) * w), argnums=(0, 1))(params, h)
    (lt, gt) = jax.value_and_grad(lambda p, h: jnp.sum(plain(p, h) * w), argnums=(0, 1))(params, h)
    _close(run(module, params, h), plain(params, h))
    _close(lo, lt)
    theirs = dict(jax.tree_util.tree_leaves_with_path(gt))
    for path, leaf in jax.tree_util.tree_leaves_with_path(go):
        _close(leaf, theirs[path], 5e-5)
    if kind == "gated_attention":  # a head's 64 columns of q_proj: its query and its gate; one key-value head
        assert params["q_proj"]["kernel"].shape == (48, 4, 64) and params["k_proj"]["kernel"].shape == (48, 1, 32)
    if kind == "routed_softmax":
        assert "select_bias" not in params and params["shared_expert_gate"]["kernel"].shape == (48, 1)


def test_the_output_gate_and_the_decay_are_not_decorations(highest):
    """The references' controls move the result: without the gate, and without the decay."""
    cfg = tiny()
    h = jax.random.normal(jax.random.PRNGKey(1), (1, 40, cfg.d_model))
    for kind, wrong in (("gated_attention", lambda p: ref.gated_attention(p, h, THETA, ROTARY, gated=False)), ("gdn", lambda p: ref.gdn(p, h, decay=False))):
        module, run, plain = _module(kind, cfg)
        init = module.init(jax.random.PRNGKey(2), h, jnp.zeros(h.shape[:2], jnp.int32)) if kind == "gated_attention" else module.init(jax.random.PRNGKey(2), h)
        params = _stirred(init["params"])
        assert float(jnp.max(jnp.abs(wrong(params) - plain(params, h)))) > 1e-2 * float(jnp.max(jnp.abs(plain(params, h))))


def test_the_four_layer_model_matches_its_plain_reference_loss_and_gradients(highest):
    """Three DeltaNet layers and one gated attention layer, every FFN routed, through ``CausalLM`` with the blocks
    checkpointed as the trainer has them: the loss and every leaf's gradient."""
    cfg = tiny(remat=True)
    model = CausalLM(cfg)
    ids = jnp.asarray(np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 48), np.int32))
    params = _stirred(model.init(jax.random.PRNGKey(5), {"input_ids": ids}))
    lo, go = jax.jit(jax.value_and_grad(lambda p: model.loss_fn(p, {"input_ids": ids})))(params)
    lt, gt = jax.value_and_grad(lambda p: ref.deltanet_model_loss(p, ids, KINDS, cfg.moe_held[0], cfg.moe_top_k, THETA, ROTARY))(params)
    _close(lo, lt)
    theirs = dict(jax.tree_util.tree_leaves_with_path(gt))
    for path, leaf in jax.tree_util.tree_leaves_with_path(go):
        _close(leaf, theirs[path], 1e-4)
    assert {k for k in params["layer_0"]} == {"RMSNorm_0", "RMSNorm_1", "gdn", "routed"} and "attn" in params["layer_3"]


def _scan_inputs(S, decay, seed=0, B=1, Hk=2, Hv=4, d=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = ref.l2(jax.random.normal(ks[0], (B, Hk, S, d))) * d ** -0.5
    k = ref.l2(jax.random.normal(ks[1], (B, Hk, S, d)))
    v = jax.random.normal(ks[2], (B, Hv, S, d))
    alpha = jnp.clip(decay + 0.01 * jax.random.uniform(ks[3], (B, Hv, S), minval=-1.0), 1e-12, 1.0)
    return q, k, v, jnp.log(alpha), jax.nn.sigmoid(jax.random.normal(ks[4], (B, Hv, S)))


@pytest.mark.parametrize("S,decay,Hk,Hv,heads_a_step", [
    (256, 0.9, 2, 4, 4), (100, 0.9, 2, 4, 4), (128, 0.999999, 2, 4, 4), (70, 1e-9, 2, 4, 4), (260, 0.02, 2, 4, 4),
    (1000, 0.9, 2, 4, 4), (256, 0.9, 3, 6, 2), (100, 0.9, 4, 4, 4), (260, 0.02, 6, 6, 2), (100, 0.9, 3, 3, 1), (256, 0.9, 1, 4, 4)])
def test_the_per_head_kernel_matches_the_token_recurrence(S, decay, Hk, Hv, heads_a_step, highest):
    """Interpret mode, one, two or four value heads a key head: lengths that are and are not whole chunks, decays near 1
    and near 0 (no exponent in the kernel is positive), forward and the gradients of all five operands (dq and dk added
    over the value heads that share a key head); 4, 6 and 3 value heads, which the rule walks 4, 2 and 1 a grid step."""
    from deepspeed_tpu.ops.kda import gdn_chunked, gdn_recurrence

    args = _scan_inputs(S, decay, Hk=Hk, Hv=Hv)
    w = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
    run = lambda fn: jax.value_and_grad(lambda *a: jnp.sum(fn(*a) * w), argnums=(0, 1, 2, 3, 4))(*args)
    (lo, go), (lt, gt) = run(lambda *a: gdn_chunked(*a, interpret=True)), run(gdn_recurrence)
    _close(gdn_chunked(*args, interpret=True), gdn_recurrence(*args))
    sw = lambda x: jnp.swapaxes(x, 1, 2)  # the oracle's oracle keeps the sequence before the heads
    q, k, v, g, beta = args
    rep = lambda x: jnp.repeat(x, Hv // Hk, axis=1)
    plain = sw(ref.delta_rule(sw(rep(q)), sw(rep(k)), sw(v), jnp.broadcast_to(jnp.exp(sw(g))[..., None], sw(v).shape), sw(beta)))
    _close(gdn_recurrence(*args), plain)
    assert np.isfinite(float(lo))
    for a, b in zip(go, gt):
        _close(a, b, 1e-4)
    ref.kernel_of_one_head_a_step(args, heads_a_step)


@pytest.mark.parametrize("heads,rep,dim,forward,backward,key_heads", [
    (32, 1, 128, 4, 4, 4), (32, 2, 128, 4, 4, 2), (8, 4, 128, 4, 4, 1), (6, 2, 128, 2, 2, 1), (2, 2, 128, 2, 2, 1), (6, 3, 128, 1, 1, 1),
    (15, 1, 128, 1, 1, 1), (16, 2, 512, 4, 2, 2), (16, 2, 1024, 2, 1, 1)])
def test_the_heads_of_a_grid_step_are_whole_key_heads_and_fit_in_vmem(heads, rep, dim, forward, backward, key_heads):
    """``heads_a_step`` from the operands alone, both forms: the most of 4, 2, 1 that divides the value heads, is a key
    head's repetition or whole repetitions, and holds its blocks and temporaries in the kernel's share of VMEM (heads of
    512 and 1,024 channels take fewer in the backward); the step's q and k block is ONE key head's where the repetition
    allows, fetched once, and the grid is (heads / H, chunks)."""
    from deepspeed_tpu.ops.pallas import kda as K

    S = jax.ShapeDtypeStruct
    q, vb = S((heads // rep, 2 * K.CHUNK, dim), jnp.bfloat16), S((heads, 2 * K.CHUNK, dim), jnp.bfloat16)
    for g in (S((heads * 2, 1, K.CHUNK), jnp.float32),) + ((S(vb.shape, jnp.float32),) if rep == 1 else ()):
        assert [K.heads_a_step(q, vb, g, False), K.heads_a_step(q, vb, g, True)] == [forward, backward]
        H, block = forward, K._form(q, vb, g, lambda c: c, forward, False)[2](dim)
        assert block.block_shape == (key_heads, K.CHUNK, dim)
        # grid step b works on value heads b H .. b H + H - 1, whose key heads are (b H) // rep .. : the block that holds them
        assert [block.index_map(b, 1)[0] * key_heads for b in range(heads // H)] == [b * H // rep for b in range(heads // H)]
    if dim == 128:
        assert f"grid=({heads // forward}, 2)" in str(jax.make_jaxpr(lambda *a: K.scan_fwd(*a, True))(q, q, vb, vb, g))


@pytest.mark.parametrize("Hk,Hv,heads_a_step", [(2, 4, 4), (3, 6, 2), (3, 3, 1)])
def test_the_per_head_kernel_under_bf16_operands_is_bf16_close(Hk, Hv, heads_a_step, highest):
    """... and several heads a grid step give one head's bits under bf16 operands too."""
    from deepspeed_tpu.ops.kda import gdn_chunked, gdn_recurrence

    q, k, v, g, beta = _scan_inputs(256, 0.9, Hk=Hk, Hv=Hv)
    low = tuple(x.astype(jnp.bfloat16) for x in (q, k, v))
    ref.kernel_of_one_head_a_step((*low, g, beta.astype(jnp.bfloat16)), heads_a_step)
    w = jax.random.normal(jax.random.PRNGKey(9), v.shape)
    run = lambda fn, *qkv: jax.grad(lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * w), argnums=(0, 1, 2, 3, 4))(*qkv, g, beta)
    got, want = run(lambda *a: gdn_chunked(*a, interpret=True), *low), run(gdn_recurrence, *(x.astype(jnp.float32) for x in low))
    for a, b in zip(got, want):
        assert float(jnp.linalg.norm(a.astype(jnp.float32) - b) / jnp.linalg.norm(b)) < 2e-2


def test_the_per_head_form_is_the_per_channel_form_with_one_decay_for_all_channels(highest):
    """``kda_chunked`` fed the decay broadcast over the channels and q, k repeated gives the same numbers: the form the
    issue rules out on cost (a (B, H, S, d_k) float32 decay), kept as a second oracle."""
    from deepspeed_tpu.ops.kda import gdn_chunked, kda_chunked

    q, k, v, g, beta = _scan_inputs(200, 0.9)
    rep = lambda x: jnp.repeat(x, 2, axis=1)
    _close(gdn_chunked(q, k, v, g, beta, interpret=True),
           kda_chunked(rep(q), rep(k), v, jnp.broadcast_to(g[..., None], v.shape), beta, interpret=True), 1e-5)


def test_the_two_forms_are_told_apart_by_the_decays_shape_and_named_apart():
    """The calls' names are what the benchmark's readers match: ``kda_scan_roofline``'s pattern must not see ``gdn_scan``."""
    import re

    from deepspeed_tpu.ops.pallas import kda as K

    x, xv = jnp.zeros((2, K.CHUNK, 128), jnp.bfloat16), jnp.zeros((4, K.CHUNK, 128), jnp.bfloat16)
    per_head = str(jax.make_jaxpr(lambda *a: K.scan_fwd(*a, True))(x, x, xv, xv, jnp.zeros((4, 1, K.CHUNK), jnp.float32)))
    per_channel = str(jax.make_jaxpr(lambda *a: K.scan_fwd(*a, True))(x, x, x, x, jnp.zeros((2, K.CHUNK, 128), jnp.float32)))
    assert "gdn_scan_fwd" in per_head and "kda_scan" not in per_head
    assert "kda_scan_fwd" in per_channel and "gdn_scan" not in per_channel
    assert not re.search(r"kda_scan_(fwd|bwd)", "gdn_scan_fwd gdn_scan_bwd")


def _share_of(whole, first, count):
    return {k: (v[first:first + count] if k.startswith("experts_") else v) for k, v in whole.items()}


def test_sixteen_shares_add_up_to_the_whole_layer(highest):
    """THE SHARE TEST: 64 experts, 10 a token by softmax, sixteen shares of 4: the parts that all sixteen give, the
    gated shared expert counted once, add up to the uncut layer's output, which is the plain reference's."""
    h = jax.random.normal(jax.random.PRNGKey(4), (2, 24, 48))
    cfg = tiny(moe_num_experts=64, moe_top_k=10)
    whole_layer = _routed_layer(None, cfg)
    whole = _stirred(whole_layer.init(jax.random.PRNGKey(5), h)["params"])
    want = whole_layer.apply({"params": whole}, h)
    shared_once = _routed_layer((0, 4), cfg).apply({"params": _share_of(whole, 0, 4)}, h)
    no_shared = {k: v for k, v in whole.items() if not k.startswith("shared_")}
    rest = sum(_routed_layer((f, 4), cfg, 0).apply({"params": _share_of(no_shared, f, 4)}, h) for f in range(4, 64, 4))
    _close(shared_once + rest, want)
    _close(want, ref.routed_softmax(whole, h, 0, 10))


def test_the_row_sum_takes_32_held_experts_at_the_cells_shape():
    """``fits`` at (8,192 tokens, the usual buffer and every pair, hidden 2,048, 32 experts) inside ``vmem_budget()``,
    where windows of 128 rows did not; at 8 experts the window, and so every shape of the Kimi cells, is what it was."""
    from deepspeed_tpu.ops.pallas import moe_sum_rows as M
    from deepspeed_tpu.ops.pallas._utils import vmem_budget

    assert M.fits(8192, 20480, 2048, 32, jnp.bfloat16) and M.fits(8192, 81920, 2048, 32, jnp.bfloat16)
    assert [M.window_rows(n) for n in (1, 8, 16, 32, 64)] == [128, 128, 64, 32, 32]
    assert M._vmem_bytes(32, 2048, 2) == M._vmem_bytes(8, 2048, 2) <= vmem_budget()
    assert 2 * 32 * 128 * 2048 * 2 + 2 * 256 * 2048 * 2 + 256 * 2048 * 4 + 4 * 256 * 32 * 128 * 4 > vmem_budget()  # the old count


@pytest.mark.parametrize("routing", ["uniform", "one_held_expert"])
def test_the_row_sum_at_32_held_experts_is_the_gathered_sum(routing):
    """The kernel (interpreted) with windows of 32 rows against the gathers, through ``held_experts``: value and every
    gradient, at a near-uniform routing and with every token on ONE held expert (a tile's span of 256 rows passes its
    window eight times over)."""
    from deepspeed_tpu.moe.sharded_moe import held_experts
    from deepspeed_tpu.ops.pallas import moe_sum_rows as M

    N, D, F, E, held, first, k = 512, 128, 64, 64, 32, 8, 10
    key = jax.random.PRNGKey(0)
    tokens, weights = jax.random.normal(key, (N, D)), jax.random.uniform(jax.random.fold_in(key, 1), (N, k))
    wg, wi, wo = (0.1 * jax.random.normal(jax.random.fold_in(key, 2 + i), s) for i, s in enumerate(((held, D, F), (held, D, F), (held, F, D))))
    cot = jax.random.normal(jax.random.fold_in(key, 9), (N, D))
    idx = jax.lax.top_k(jax.random.uniform(jax.random.PRNGKey(3), (N, E)), k)[1] if routing == "uniform" else \
        jnp.concatenate([jnp.full((N, 1), 10), 40 + jnp.broadcast_to(jnp.arange(k - 1), (N, k - 1))], axis=1)
    idx = idx.astype(jnp.int32)
    assert M.fits(N, N * k, D, held, tokens.dtype)

    def run(kernel):
        call = lambda *a: held_experts(a[0], idx, *a[1:], first, N * k, kernel)
        out, routed, dropped, *_ = call(tokens, weights, wg, wi, wo)
        return (out,) + jax.grad(lambda *a: jnp.sum(call(*a)[0] * cot), argnums=(0, 1, 2, 3, 4))(tokens, weights, wg, wi, wo), int(routed), int(dropped)

    got, routed, dropped = run(True)
    want, routed_xla, _ = run(False)
    assert (routed, dropped) == (routed_xla, 0) and routed >= N
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5 * float(jnp.max(jnp.abs(b)) + 1e-6))


def test_the_flash_backward_runs_a_head_at_a_time_where_a_groups_gradients_do_not_fit(monkeypatch):
    """16 query heads on 2 key-value heads of 256 at 8,192 positions: the fused backward's GQA form needs 74 MiB of a
    budget of 48, a head alone 43. Here the budget is steered between a tiny shape's two needs: the gradients are those
    of the grouped form, dk and dv added over the group outside the kernel."""
    from deepspeed_tpu.ops.pallas import flash_attention as F

    B, S, H, KVH, D = 1, 256, 4, 1, 32
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v, do = (jax.random.normal(ks[i], (B, S, h, D)) for i, h in enumerate((H, KVH, KVH, H)))
    grads = lambda: jax.grad(lambda q, k, v: jnp.sum(F.flash_attention(q, k, v, causal=True, interpret=True) * do), argnums=(0, 1, 2))(q, k, v)
    want = grads()
    bq, bk = F._blk(S, F.DEFAULT_BQ), F._blk(S, F.DEFAULT_BK)
    grouped, alone = F._fused_bwd_vmem(S, S, D, 4, bq, bk, H // KVH, D), F._fused_bwd_vmem(S, S, D, 4, bq, bk, 1, D)
    assert alone < grouped
    monkeypatch.setattr(F, "vmem_budget", lambda: (grouped + alone) // 2)
    got = grads()
    for a, b in zip(got, want):
        assert a.shape == b.shape
        _close(a, b, 1e-5)
    # the published shape, by the kernel's own count: refused as a group, taken a head at a time
    full = lambda n_rep: F._fused_bwd_vmem(8192, 8192, 256, 2, 512, 512, n_rep, 256)
    assert full(8) > 48 << 20 >= full(1)


def test_serving_refuses_the_delta_rule_layers_and_the_output_gate_by_name():
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2

    shapes = lambda model: jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 8), np.int32)}))
    model = CausalLM(tiny())
    with pytest.raises(NotImplementedError, match="gdn"):
        InferenceEngineV2(model, shapes(model))
    with pytest.raises(NotImplementedError, match="pipeline"):
        model.to_pipeline(1, params=shapes(model))
    gated = CausalLM(tiny(layer_kinds=None, n_layers=2))  # softmax attention and dense FFNs, with the gate
    with pytest.raises(NotImplementedError, match="attn_output_gate"):
        InferenceEngineV2(gated, shapes(gated))
