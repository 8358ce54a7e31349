"""A stack whose every layer is ONE part (the kind ``none`` in the other half): a Mamba-2 mixer (the kind ``ssd``), a routed
FFN of UNGATED relu^2 experts behind a biased sigmoid router with a shared expert, or grouped-query attention without
positions. The model against the configuration's plain reference at a small width on the CPU, in logits, loss and every
leaf's gradient, a block of each kind and the nine-layer stack, ``remat`` on and off; what the reference's controls break;
the sixteen shares of a routed layer plus the shared expert ONCE add up to the uncut layer; the kernels of
``ops/pallas/ssd.py`` (interpreted) against the token-by-token recurrence, output and every gradient; a block of one
part's tree; the trainer's path and the first-call line; and the gated routed cells' steps, equation for equation what
the parent commit traced.

The reference is the benchmark configuration's own file (``benchmarks/configs/nemotron3-nano-30b-l9e8.reference.py``),
loaded by its path: it imports nothing of the program or of the benchmark."""

import functools
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
from deepspeed_tpu.models.mixers import SSDMixer
from deepspeed_tpu.moe import sharded_moe
from deepspeed_tpu.ops import placement, ssd as ssd_ops
from deepspeed_tpu.ops.pallas import ssd as ssd_kernels
from deepspeed_tpu.telemetry import get_registry, get_tracer
from deepspeed_tpu.telemetry.tracing import regions_traced

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
VOCAB, S = 211, 80
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
PUBLISHED = {"layer_norm_epsilon": 1e-5, "hybrid_override_pattern": PATTERN, "layers_here": list(range(9)), "num_hidden_layers": 9,
             "mamba_num_heads": 4, "mamba_head_dim": 8, "n_groups": 2, "ssm_state_size": 16, "n_routed_experts": 4, "routed_over": 16,
             "num_experts_per_tok": 3, "routed_scaling_factor": 2.5}
REF = {"held_first": 4}
KIND_OF = {"M": ("ssd", "none"), "E": ("none", "routed"), "*": ("nope", "none")}
KINDS = tuple(KIND_OF[c] for c in PATTERN[:9])


def tiny(**over):
    base = dict(vocab_size=VOCAB, n_layers=9, n_heads=4, n_kv_heads=2, head_dims=16, d_model=64, max_seq_len=S, norm="rmsnorm",
                activation="relu2", pos_emb="none", tie_embeddings=False, norm_eps=1e-5, layer_kinds=KINDS, ssd_heads=4, ssd_head_dim=8,
                ssd_state=16, ssd_groups=2, ssd_conv=4, moe_num_experts=16, moe_top_k=3, moe_d_ff=32, moe_shared_d_ff=48, moe_held=(4, 4),
                moe_scoring="sigmoid", moe_route_scale=2.5, moe_aux_loss_coef=0.0)
    return TransformerConfig(**dict(base, **over))


@pytest.fixture(scope="module")
def ref():
    path = os.path.join(ROOT, "benchmarks", "configs", "nemotron3-nano-30b-l9e8.reference.py")
    spec = importlib.util.spec_from_file_location("nemotron_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


IDS = np.random.default_rng(3).integers(0, VOCAB, (2, S)).astype(np.int32)


def stirred(params, by=0.05):
    """Every leaf moved off its start (``D`` off one, the convolution's bias and the norms' weights off their constants);
    the selection bias (zeros at init: a choice by the scores alone would pass) by six times as much."""
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


def test_a_block_of_one_part_holds_one_norm_and_nothing_for_the_absent_half():
    params = jax.eval_shape(lambda: CausalLM(tiny()).init(jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 16), np.int32)}))
    assert [sorted(params[f"layer_{i}"]) for i in range(9)] == [["RMSNorm_0", {"M": "ssd", "E": "routed", "*": "attn"}[c]] for c in PATTERN[:9]]
    mixer = params["layer_0"]["ssd"]
    assert set(mixer) == {"in_proj", "conv_kernel", "conv_bias", "dt_bias", "A_log", "D", "norm_scale", "out_proj"}
    assert mixer["in_proj"]["kernel"].shape == (64, 32 + (32 + 2 * 2 * 16) + 4) and mixer["out_proj"]["kernel"].shape == (32, 64)  # [z, xBC, dt]
    assert (mixer["conv_kernel"].shape, mixer["conv_bias"].shape, mixer["A_log"].shape, mixer["norm_scale"].shape) == ((4, 96), (96,), (4,), (32,))
    # an expert of TWO matrices, the shared one too: no gate's weights anywhere
    assert set(params["layer_1"]["routed"]) == {"gate", "select_bias", "experts_wi", "experts_wo", "shared_up_proj", "shared_down_proj"}
    assert params["layer_1"]["routed"]["experts_wi"].shape == (4, 64, 32) and params["layer_1"]["routed"]["shared_up_proj"]["kernel"].shape == (64, 48)
    assert set(params["layer_5"]["attn"]) == {"q_proj", "k_proj", "v_proj", "o_proj"} and "wpe" not in params and "lm_head" in params
    record = table.MIXERS["ssd"]
    assert record is SSDMixer and record.hybrid and not record.stackable and record.gives == record.takes == () and not record.sows
    assert record.keeps == ("ssd_scan", "projection") and record.paths == {"ssd_path": ("mixer/kernel", {"op": "ssd", "pass": "fwd"}),
                                                                      "conv_silu_path": ("mixer/conv", {"op": "conv_silu", "pass": "fwd"})}
    absent = table.MIXERS["none"]
    assert absent is table.FFNS["none"] and absent.keeps == () and absent.hybrid and not absent.stackable and not absent.sows and not absent.paths
    assert table.remat_keeps(("ssd", "none")) == ("ssd_scan", "projection") and table.remat_keeps(("none", "routed")) == ("routed_ffn", "projection")
    assert table.remat_keeps(("nope", "none")) == ("flash_attention", "projection")  # a block of one part keeps by name, whatever the part
    assert tiny().unstackable == ("none", "nope", "routed", "ssd") and tiny().shares == () and not tiny().moe_for(0) and tiny().moe_for(1)
    assert len({kind for kind in tiny().kinds}) == 3  # three traced blocks for nine layers
    defaults = TransformerConfig()
    assert (defaults.ssd_heads, defaults.ssd_head_dim, defaults.ssd_state, defaults.ssd_groups, defaults.ssd_conv) == (0, 64, 128, 1, 4)


def test_a_layer_with_neither_part_and_a_one_part_block_in_another_wiring_are_refused_in_words():
    with pytest.raises(ValueError, match="neither a mixer nor an FFN"):
        tiny(n_layers=2, layer_kinds=(("ssd", "none"), ("none", "none"))).kinds
    x, positions = jnp.zeros((1, 8, 64)), jnp.broadcast_to(jnp.arange(8, dtype=jnp.int32), (1, 8))
    for over in ({"block_type": "parallel"}, {"norm_scheme": "post"}):
        cfg = tiny(n_layers=1, layer_kinds=(("nope", "none"),), **over)
        with pytest.raises(NotImplementedError, match="a block of one part"):
            table.Block(cfg, ("nope", "none")).init(jax.random.PRNGKey(0), x, positions)
    cfg = tiny(n_layers=1, layer_kinds=(("nope", "none"),))
    block = table.Block(cfg, ("nope", "none"))
    params = block.init(jax.random.PRNGKey(0), x, positions)
    cache = (jnp.zeros((1, 8, 2, 16)), jnp.zeros((1, 8, 2, 16)), jnp.asarray(0, jnp.int32))
    with pytest.raises(NotImplementedError, match="takes no KV"):
        block.apply(params, x, positions, cache)


# float32 at the highest matmul precision on both sides: what is left is the order of float32 sums (the convolution's four
# products, the grouped products against a loop over experts, a softmax row whole against XLA's own reduction, the fused
# cross-entropy against a log-softmax; the recurrence is the same ``lax.scan`` on both sides off the TPU): 2e-5 of the largest
# entry for the logits, 5e-5 for a gradient. A state rounded to bf16 after every token reads 5e-4 and a missing ``D x`` 0.8
# (below), every other control 1e-2 and more
@pytest.mark.parametrize("kinds,remat", [((("ssd", "none"),), False), ((("none", "routed"),), False), ((("nope", "none"),), False),
                                         (KINDS, False), (KINDS, True)], ids=["ssd", "routed", "nope", "stack", "stack-remat"])
def test_the_model_is_the_plain_reference_in_logits_loss_and_every_gradient(ref, kinds, remat):
    """A block of each kind alone and the nine-layer stack, on rows of 80 tokens."""
    here = {(("ssd", "none"),): [0], (("none", "routed"),): [1], (("nope", "none"),): [5]}.get(kinds, list(range(9)))
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


@pytest.mark.parametrize("control", [{"expert_act": "silu_gated"}, {"expert_act": "relu"}, {"decay": "none"}, {"norm": "before_gate"}, {"skip": "none"},
                                     {"choice": "scores"}, {"layers": 8}, {"no_final_norm": True}], ids=lambda c: "-".join(map(str, c.values())) + next(iter(c)))
def test_a_reference_with_one_thing_wrong_is_far_from_the_model(ref, control):
    """Experts that are a gated SiLU on the one product there is, or a relu without its square; the decay dropped; the
    group norm before the gate; no ``D x``; the top 3 of the scores alone; a layer short; no final norm."""
    cfg = tiny()
    model, params = CausalLM(cfg), seeded(cfg)
    with jax.default_matmul_precision("highest"):
        assert far(model.apply(params, IDS), ref.logits(params, IDS, PUBLISHED, dict(REF, **control), jnp.float32), 1e-2), control


def operands(Bt, rows, H, P, G, N, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(ks[0], (Bt, rows, H, P), jnp.float32).astype(dtype)
    delta = jax.nn.softplus(jax.random.normal(ks[1], (Bt, rows, H)) - 2.0)
    A = -jnp.exp(jax.random.uniform(ks[2], (H,), minval=0.0, maxval=2.7))  # -1 .. -15: log U(1, 16)'s range
    B, C = ((0.3 * jax.random.normal(k, (Bt, rows, G, N))).astype(dtype) for k in ks[3:5])
    return (x, delta, A, B, C, jax.random.normal(ks[5], (H,))), jax.random.normal(ks[6], (Bt, rows, H, P)).astype(dtype)


def test_the_tolerance_sees_a_state_in_bf16_and_a_missing_skip():
    """The recurrence with its state rounded to bf16 after every token, and without ``D x``, against itself: both far over
    the 2e-5 the model is held to."""
    args, _ = operands(1, S, 4, 8, 2, 16, jnp.float32)
    truth = ssd_ops.ssd_recurrence(*args)
    assert far(ssd_ops.ssd_recurrence(*args, state_dtype=jnp.bfloat16), truth, 2e-4)  # ten times the model's tolerance
    assert far(ssd_ops.ssd_recurrence(*args[:5], jnp.zeros_like(args[5])), truth, 1e-1)


@pytest.mark.parametrize("Bt,rows,H,G,P,N,dtype", [(1, 200, 2, 1, 64, 128, jnp.float32), (1, 130, 8, 1, 64, 128, jnp.float32), (2, 256, 4, 2, 64, 128, jnp.float32),
                                                   (1, 200, 16, 2, 64, 128, jnp.bfloat16), (1, 128, 2, 2, 128, 128, jnp.float32)],
                         ids=["2-heads-a-group-padded", "8-heads-a-group-padded", "2-groups-whole-chunks", "bf16-8-heads-a-group", "heads-of-128"])
def test_the_kernels_are_the_recurrence_forward_and_backward(Bt, rows, H, G, P, N, dtype):
    """Interpreted, against ``ssd_recurrence`` and what JAX derives from it: y and the gradient to EVERY operand (x, delta,
    A, B, C, D), at sequences that are no whole number of chunks (padded: a padded token's step is zero), with 2 and 8 heads
    a group (one tile and four), two groups, a head that fills a tile. float32: products at the highest precision, so what
    is left is the order of sums over a chunk; bf16: the operands of every product rounded (the state stays float32)."""
    args, dy = operands(Bt, rows, H, P, G, N, dtype)
    want, vjp = jax.vjp(ssd_ops.ssd_recurrence, *args)
    got, vjp_kernel = jax.vjp(lambda *a: ssd_ops.ssd_chunked(*a, interpret=True), *args)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    close(got, want, tol, "y")
    for name, a, b in zip(("dx", "ddelta", "dA", "dB", "dC", "dD"), vjp_kernel(dy), vjp(dy)):
        close(a, b, 5 * tol, name)


def test_the_kernels_take_whole_tiles_and_the_chooser_says_xla_off_the_tpu(monkeypatch):
    assert ssd_kernels.fits(64, 64, 8, 128) and ssd_kernels.fits(2, 64, 1, 128) and ssd_kernels.fits(4, 128, 2, 256)
    assert not ssd_kernels.fits(4, 8, 2, 16) and not ssd_kernels.fits(1, 64, 1, 128) and not ssd_kernels.fits(64, 64, 8, 64) and not ssd_kernels.fits(6, 64, 4, 128)
    args, dy = operands(1, 128, 2, 64, 1, 128, jnp.float32)
    count = lambda path, pass_: regions_traced("mixer/kernel", op="ssd", path=path, **{"pass": pass_})
    before = [count("xla", "fwd"), count("kernel", "fwd"), count("kernel", "bwd")]
    want = ssd_ops.ssd(*args)  # no TPU here: the recurrence, counted so
    assert [count("xla", "fwd"), count("kernel", "fwd")] == [before[0] + 1, before[1]]
    # the kernels' own count: forward where the call's rule is traced, backward where its transpose is
    got, vjp = jax.vjp(lambda *a: ssd_ops.ssd_chunked(*a, interpret=True), *args)
    vjp(dy)
    assert [count("kernel", "fwd"), count("kernel", "bwd")] == [before[1] + 1, before[2] + 1]
    close(got, want, 2e-5)
    # said to be a TPU: sizes the kernel's tiles do not take are the recurrence's all the same
    monkeypatch.setattr(placement, "pallas_available", lambda: True)
    small, _ = operands(1, 16, 4, 8, 2, 16, jnp.float32)
    ssd_ops.ssd(*small)
    assert [count("xla", "fwd"), count("kernel", "fwd")] == [before[0] + 2, before[1] + 1]


def test_the_mixer_is_its_equations_by_hand():
    """``[z, xBC, dt] = h W_in`` in THAT order; the convolution over all of xBC with its bias and a SiLU; head ``h`` reads
    group ``h // 2``; the recurrence written out with a loop over tokens; the gate FIRST and then the norm a group."""
    cfg = tiny(n_layers=1, layer_kinds=(("ssd", "none"),))
    mixer = SSDMixer(cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 12, 64), jnp.float32)
    p = stirred(mixer.init(jax.random.PRNGKey(0), x)["params"], 0.1)
    H, P, G, N, inner = 4, 8, 2, 16, 32
    f = lambda leaf: np.asarray(leaf, np.float64)
    zxbcdt = f(x[0]) @ f(p["in_proj"]["kernel"])
    z, xbc, dt = zxbcdt[:, :inner], zxbcdt[:, inner:inner + 96], zxbcdt[:, inner + 96:]
    w, b = f(p["conv_kernel"]), f(p["conv_bias"])
    conv = np.stack([sum(w[j] * xbc[t - 3 + j] for j in range(4) if t - 3 + j >= 0) for t in range(12)]) + b
    xbc = conv / (1 + np.exp(-conv))
    xs, B, C = xbc[:, :inner].reshape(12, H, P), xbc[:, inner:inner + G * N].reshape(12, G, N), xbc[:, inner + G * N:].reshape(12, G, N)
    delta = np.log1p(np.exp(dt + f(p["dt_bias"])))
    A, D = -np.exp(f(p["A_log"])), f(p["D"])
    state, y = np.zeros((H, P, N)), np.zeros((12, H, P))
    for t in range(12):
        for h in range(H):
            state[h] = np.exp(delta[t, h] * A[h]) * state[h] + delta[t, h] * np.outer(xs[t, h], B[t, h // 2])
            y[t, h] = state[h] @ C[t, h // 2] + D[h] * xs[t, h]
    gated = (y.reshape(12, inner) * (z / (1 + np.exp(-z)))).reshape(12, G, inner // G)
    normed = (gated / np.sqrt((gated ** 2).mean(-1, keepdims=True) + 1e-5)).reshape(12, inner) * f(p["norm_scale"])
    with jax.default_matmul_precision("highest"):
        close(mixer.apply({"params": p}, x)[0], normed @ f(p["out_proj"]["kernel"]), 2e-5)


def test_no_cache_and_no_packed_segments():
    mixer = SSDMixer.from_config(tiny(), "ssd")
    x = jnp.zeros((1, 8, 64))
    params = mixer.init(jax.random.PRNGKey(0), x)
    for kw in ({"kv_cache": (x, x, jnp.asarray(0))}, {"segment_ids": jnp.zeros((1, 8), jnp.int32)}):
        with pytest.raises(NotImplementedError, match="ssd layer takes no KV cache and no packed segments"):
            mixer.apply(params, x, None, **kw)
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2

    with pytest.raises(NotImplementedError, match="ssd"):
        InferenceEngineV2(CausalLM(tiny(n_layers=2, layer_kinds=(("ssd", "none"),) * 2)), params=None)


def test_the_sixteen_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer(ref):
    """The share ties to the model for an expert of TWO matrices: 128 experts, 6 a token, over 16 chips of 8. What each
    share's PROGRAM block adds for its routed experts, summed over the sixteen, plus the shared expert ONCE (it is computed
    alike on every chip: a sum of sixteen blocks' outputs would count it sixteen times), is what the plain reference gives
    for the whole ``E`` layer with all 128 experts."""
    E, held, k, d = 128, 8, 6, 64
    pub = dict(PUBLISHED, n_routed_experts=held, num_experts_per_tok=k, routed_over=E)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, S, d), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (2, S))
    experts, kind = ("experts_wi", "experts_wo"), ("none", "routed")
    whole_cfg = tiny(n_layers=1, layer_kinds=(kind,), moe_num_experts=E, moe_top_k=k, moe_held=(0, E))
    whole = stirred(table.Block(whole_cfg, kind).init(jax.random.PRNGKey(1), x, positions)["params"], 0.1)
    assert "experts_wg" not in whole["routed"]
    with jax.default_matmul_precision("highest"):
        uncut = ref.layer_part(whole, x, pub, REF, jnp.float32, 1, 0, E)
        no_expert = dict(whole, routed={**whole["routed"], **{w: whole["routed"][w][:0] for w in experts}})
        shared_alone = ref.layer_part(no_expert, x, pub, REF, jnp.float32, 1, 0, 0)  # x + the shared expert: no routed expert's part
        total = shared_alone
        for share in range(E // held):
            cfg = tiny(n_layers=1, layer_kinds=(kind,), moe_num_experts=E, moe_top_k=k, moe_held=(share * held, held))
            mine = dict(whole, routed={**whole["routed"], **{w: whole["routed"][w][share * held:(share + 1) * held] for w in experts}})
            y, _ = table.Block(cfg, kind).apply({"params": mine}, x, positions, mutable=["intermediates"])
            total = total + (y - shared_alone)  # the share's routed part alone
    close(total, uncut, 2e-5)
    assert far(shared_alone, uncut, 1e-3)  # the routed experts' part is no rounding
    with jax.default_matmul_precision("highest"):  # ... and neither is the shared expert's
        assert far(ref.layer_part(no_expert, x, pub, REF, jnp.float32, 1, 0, 0, shared=False), shared_alone, 1e-3)


def test_an_expert_without_a_gate_runs_the_same_ladder_and_the_fallback_differentiates(monkeypatch):
    """``wg`` None through ``routed_part``: the first rung and, with every pair routed to the held experts, the rung above it
    (the every-pair fallback and its hand-written backward), against the loop over experts."""
    N, k, d, f, n, E = 64, 2, 16, 24, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    tokens, wi, wo = jax.random.normal(ks[0], (N, d)), 0.3 * jax.random.normal(ks[1], (n, d, f)), 0.3 * jax.random.normal(ks[2], (n, f, d))
    weights = jax.nn.softmax(jax.random.normal(ks[3], (N, k)))
    monkeypatch.setattr(sharded_moe, "buffer_rungs", lambda every, n, num_experts: (32, 64, every))  # a first rung of 32 rows under 128 pairs

    def loop(tokens, weights, wi, wo, idx):
        out = jnp.zeros_like(tokens)
        for e in range(n):
            w_e = jnp.sum(jnp.where(idx == e, weights, 0.0), axis=-1, keepdims=True)
            out = out + w_e * (jnp.square(jax.nn.relu(tokens @ wi[e])) @ wo[e])
        return out

    for idx, rung in ((jnp.stack([jnp.arange(N) % E, (jnp.arange(N) + 3) % E], axis=1), 0), (jnp.tile(jnp.asarray([[0, 1]]), (N, 1)), 2)):
        part = lambda t, w, i, o: sharded_moe.routed_part(t, idx, w, None, i, o, 0, E, False, "relu2")
        with jax.default_matmul_precision("highest"):
            out, routed, dropped, *_, took = part(tokens, weights, wi, wo)
            assert (int(took), int(dropped)) == (rung, 0)
            close(out, loop(tokens, weights, wi, wo, idx), 1e-5)
            ours = jax.grad(lambda *a: jnp.sum(part(*a)[0] ** 2), argnums=(0, 1, 2, 3))(tokens, weights, wi, wo)
            theirs = jax.grad(lambda *a: jnp.sum(loop(*a, idx) ** 2), argnums=(0, 1, 2, 3))(tokens, weights, wi, wo)
        for a, b in zip(ours, theirs):
            close(a, b, 1e-5)


@pytest.mark.parametrize("axis", [2, 4])
def test_an_expert_axis_gives_the_one_device_layers_output_for_experts_without_a_gate(axis):
    """With an ``expert`` mesh axis of 2 and of 4 virtual devices (``_over_expert_axis``: the held experts split over it, no
    operand and no spec for the ``wg`` there is not) the layer's output and every gradient equal the one-device layer's."""
    from deepspeed_tpu.moe.layer import RoutedMoE
    from deepspeed_tpu.parallel.mesh import initialize_mesh, reset_mesh
    from deepspeed_tpu.runtime.config import MeshConfig

    h = jax.random.normal(jax.random.PRNGKey(6), (2, 24, 64))
    layer = RoutedMoE.from_config(tiny(moe_held=None), "routed")
    params = layer.init(jax.random.PRNGKey(7), h)["params"]
    assert "experts_wg" not in params and params["experts_wi"].shape[0] == 16
    reset_mesh()
    with jax.default_matmul_precision("highest"):
        want, grads_want = jax.value_and_grad(lambda p: jnp.sum(layer.apply({"params": p}, h) ** 2))(params)
        try:
            topo = initialize_mesh(MeshConfig.from_dict({"expert": axis}), devices=jax.devices()[:axis], force=True)
            with topo.mesh:
                got, grads = jax.jit(jax.value_and_grad(lambda p: jnp.sum(layer.apply({"params": p}, h) ** 2)))(params)
        finally:
            reset_mesh()
    close(got, want, 1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(grads_want)):
        close(a, b, 1e-4)


TILES_1856 = ((256, 896, 1024), (256, 1024, 896))  # two tiles of 1,024, the second part empty: the fastest of five tilings on the chip (PERF.md, PR 59)


@pytest.mark.parametrize("tokens,k,experts,d,f,want", [
    (8192, 6, 128, 2688, 1856, "TILES_1856"),  # this cell: 1,856 = 29 x 64, which no listed tile divides
    (16384, 4, 32, 2048, 1792, ((512, 512, 896), (512, 896, 1024))),  # LFM2's: as it was
    (8192, 6, 64, 2048, 1408, ((256, 512, 1408), (256, 1408, 1024))),  # Kimi-VL's: as it was
])
def test_the_grouped_products_tiles_at_a_width_128_does_not_divide(monkeypatch, tokens, k, experts, d, f, want):
    """Two products a call for an ungated expert, three for a gated one; the tiles ``gmm`` is called with."""
    from jax.experimental.pallas.ops.tpu import megablox

    from deepspeed_tpu.ops.pallas import moe_sum_rows

    want = TILES_1856 if want == "TILES_1856" else want
    n, seen = 8, []

    def gmm(xs, w, sizes, preferred_element_type=None, tiling=None, interpret=False):
        seen.append(tiling)
        return jnp.zeros((xs.shape[0], w.shape[2]), xs.dtype)

    monkeypatch.setattr(megablox, "gmm", gmm)
    monkeypatch.setattr(moe_sum_rows, "fits", lambda *a: False)
    idx = jnp.zeros((tokens, k), jnp.int32)
    gated = f != 1856
    shapes = [jax.ShapeDtypeStruct(s, jnp.bfloat16) for s in ((tokens, d), (n, d, f), (n, d, f), (n, f, d))]
    jax.eval_shape(lambda t, wg, wi, wo: sharded_moe.routed_part(t, idx, jnp.ones((tokens, k), jnp.float32), wg if gated else None, wi, wo, 0, experts, True,
                                                                 "silu" if gated else "relu2")[0], *shapes)
    first_rung = seen[:3 if gated else 2]
    assert (first_rung[0], first_rung[-1]) == want


_TRAINED = {}


@pytest.mark.parametrize("stage,mesh,n", [(0, {"data": 1}, 1), (3, {"fsdp": 4}, 4)])
def test_the_stack_trains_through_initialize_and_the_first_call_line_names_its_kinds(stage, mesh, n):
    """Stage 0 on one device and ZeRO-3 on four virtual devices (``gathered_block`` reads the records of one-part blocks as
    it reads the others'): the same first loss and the same loss after 3 steps within 2e-3; the first-call span names the
    kinds, how the scan ran and what a checkpointed block keeps."""
    import deepspeed_tpu
    from deepspeed_tpu.parallel.mesh import initialize_mesh, reset_mesh
    from deepspeed_tpu.runtime.config import MeshConfig

    model = CausalLM(tiny(remat=True))
    ids = np.random.default_rng(0).integers(0, VOCAB, (4, S)).astype(np.int32)
    params = model.init(jax.random.PRNGKey(0), {"input_ids": ids[:1]})
    reg = get_registry()
    rows = [reg.peek(name) or 0.0 for name in ("moe_rows_routed_here_total", "moe_rows_dropped_total")]
    traced = [regions_traced("mixer/kernel", op="ssd", path="xla"), regions_traced("ffn/experts", act="relu2")]
    reset_mesh()
    try:
        topo = initialize_mesh(MeshConfig.from_dict(mesh), devices=jax.devices()[:n], force=True)
        engine, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params, mesh=topo, config={
            "train_micro_batch_size_per_gpu": 4 // n, "gradient_accumulation_steps": 1, "steps_per_print": 10**9,
            "optimizer": {"type": "adam", "params": {"lr": 1e-3}}, "zero_optimization": {"stage": stage}})
        losses = []
        for _ in range(4):
            loss = engine.forward({"input_ids": ids})
            engine.backward(loss)
            engine.step()
            losses.append(float(loss))
    finally:
        reset_mesh()
    counted = reg.peek("moe_rows_routed_here_total") - rows[0]
    assert 3 * 4 * 4 * S * 0.2 <= counted <= 4 * 4 * 4 * S * 3  # 4 routed layers, 4 rows of S tokens, up to 3 choices each, three or four steps counted
    assert reg.peek("moe_rows_dropped_total") == rows[1]
    assert regions_traced("mixer/kernel", op="ssd", path="xla") > traced[0] and regions_traced("ffn/experts", act="relu2") > traced[1]
    said = [s["attrs"] for s in get_tracer().spans() if s["name"] == "program/first_call" and s["attrs"].get("family") == "train"][-1]
    assert said["layer_kinds"] == "none+routed:4,nope+none:1,ssd+none:4" and said["block_traces"] == 3
    assert (said["ssd_path"], said["nope_path"], said["moe_path"], said["moe_combine"]) == ("xla",) * 4
    assert said["moe_router"] == "sigmoid+compare_sum" and said["moe_activation"] == "relu2"
    assert said["remat_keeps"] == "flash_attention+projection+routed_ffn+ssd_scan"
    _TRAINED.setdefault("losses", losses)
    assert np.isfinite(losses).all() and losses[3] < losses[0]
    np.testing.assert_allclose([losses[0], losses[3]], [_TRAINED["losses"][0], _TRAINED["losses"][3]], atol=2e-3)


# (lines, sha256 of ``str(jaxpr)``) of a rehearsal's forward (the logits) and of the gradient of its loss, made from the PARENT
# commit (PR 58) by the same lines under this suite's ``conftest.py``: the SEVEN cells whose routed experts are gated trace
# what they traced, equation for equation, though ``held_experts``, ``routed_part``, the fallback and ``_over_expert_axis`` now
# carry a ``wg`` that may be None, ``Block`` a branch for one part and the table two lines more (``test_short_conv_layers.py``
# pins five gradients from PR 54's parent: Kimi-VL, Kimi-Linear, Phi-4, SmallThinker, OLMo)
PARENTS_PROGRAMS = {
    "kimi-linear-48b-l5e8": {"forward": (2314, "95a7da359f6f3b02"), "gradient": (8381, "d6f2415731622e43")},
    "kimi-vl-a3b-l6e8": {"forward": (1640, "33a67f936cc841b3"), "gradient": (5438, "21efcb2b344c9faf")},
    "qwen3-next-80b-l4e32": {"forward": (2005, "5a9d6d1f1c4f3e46"), "gradient": (7159, "2a9d9d02aff49901")},
    "keye-vl2-30b-l4e16": {"forward": (1745, "b7c104971ab9a615"), "gradient": (6279, "ab205d2d4c5a03f4")},
    "sdar-30b-a3b-l4e16": {"forward": (1244, "82653d8c8edc0c8f"), "gradient": (4157, "d28e6a8cd0a8a70c")},
    "smallthinker-21b-l4e8": {"forward": (1459, "7fb12366800e5f8d"), "gradient": (4356, "409a4a6701b53837")},
    "lfm2-8b-a1b-l5e8": {"forward": (1534, "9d5afa266a1de5d8"), "gradient": (4755, "a1aa706bffa0b806")},
}


@functools.lru_cache(maxsize=None)
def program_of(name):
    with open(os.path.join(ROOT, "benchmarks", "configs", f"{name}.json")) as f:
        cfg = json.load(f)
    program = dict(cfg["program"], **cfg["rehearse"].get("program", {}))
    dtype = jnp.bfloat16 if program.pop("dtype", None) == "bfloat16" else jnp.float32
    hashable = lambda v: tuple(hashable(x) for x in v) if isinstance(v, list) else v
    model = CausalLM(TransformerConfig(**{k: hashable(v) for k, v in program.items()}, dtype=dtype))
    ids = np.zeros((1, 96), np.int32)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), {"input_ids": ids}))
    pin = lambda jaxpr: (lambda text: (len(text.splitlines()), hashlib.sha256(text.encode()).hexdigest()[:16]))(re.sub(r"0x[0-9a-f]+", "0x", str(jaxpr)))
    return {"forward": pin(jax.make_jaxpr(lambda p, i: model.apply(p, i))(params, ids)),
            "gradient": pin(jax.make_jaxpr(jax.grad(lambda p, i: model.loss_fn(p, {"input_ids": i})))(params, ids))}


@pytest.mark.parametrize("name", ["kimi-linear-48b-l5e8", "kimi-vl-a3b-l6e8", "qwen3-next-80b-l4e32", "keye-vl2-30b-l4e16", "sdar-30b-a3b-l4e16",
                                  "smallthinker-21b-l4e8", "lfm2-8b-a1b-l5e8"])
@pytest.mark.parametrize("which", ["forward", "gradient"])
def test_a_gated_cells_program_is_the_one_the_parent_traced(name, which):
    assert program_of(name)[which] == PARENTS_PROGRAMS[name][which]
