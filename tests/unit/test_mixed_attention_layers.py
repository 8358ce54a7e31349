"""A stack that mixes attention kinds which differ in BOTH mask and rotation (``nope``: every earlier key, no
positions; ``window``: the last ``sliding_window`` keys, rotated) over a routed ReGLU FFN whose router reads the block's
FIRST norm's output (``routed_early``): the model against the configuration's plain reference at a small width on the
CPU, in logits, loss and every leaf's gradient, with a sequence longer than the window and both kinds present, ``remat``
on and off; what the reference's controls break; the records of the two new kinds and what their hosts read of them; the
routed layer's gate by the configuration's ``activation``; the trainer's path, ZeRO-3's gathered block and the
first-call line.

The reference is the benchmark configuration's own file (``benchmarks/configs/smallthinker-21b-l4e8.reference.py``),
loaded by its path: it imports nothing of the program or of the benchmark."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import CausalLM, TransformerConfig, transformer as table
from deepspeed_tpu.models.layers import Attention, UnrotatedAttention
from deepspeed_tpu.moe.layer import EarlyRoutedMoE, RoutedMoE
from deepspeed_tpu.moe.sharded_moe import GATES, held_experts, softmax_topk
from deepspeed_tpu.telemetry import get_registry, get_tracer
from deepspeed_tpu.telemetry.tracing import regions_traced, regions_traced_by

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
VOCAB, S, WINDOW = 211, 80, 16
LAYOUT = [0, 1, 1, 1] * 2
PUBLISHED = {"rms_norm_eps": 1e-6, "rope_theta": 1.5e6, "num_hidden_layers": 4, "moe_num_primary_experts": 4, "moe_num_active_primary_experts": 4,
             "routed_over": 16, "sliding_window_size": WINDOW, "rope_layout": LAYOUT, "sliding_window_layout": LAYOUT, "layers_here": [0, 1, 2, 3]}
REF = {"held_first": 4}
KINDS = (("nope", "routed_early"),) + (("window", "routed_early"),) * 3


def tiny(**over):
    base = dict(vocab_size=VOCAB, n_layers=4, n_heads=4, n_kv_heads=2, head_dims=16, d_model=64, max_seq_len=S, norm="rmsnorm",
                activation="reglu", pos_emb="rope", rope_theta=1.5e6, tie_embeddings=False, norm_eps=1e-6, sliding_window=WINDOW,
                layer_kinds=KINDS, moe_num_experts=16, moe_top_k=4, moe_d_ff=32, moe_held=(4, 4), moe_scoring="softmax", moe_aux_loss_coef=0.0)
    return TransformerConfig(**dict(base, **over))


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location("smallthinker_reference", os.path.join(ROOT, "benchmarks", "configs", "smallthinker-21b-l4e8.reference.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


IDS = np.random.default_rng(3).integers(0, VOCAB, (2, S)).astype(np.int32)


def stirred(params, by=0.05):
    leaves, tree = jax.tree_util.tree_flatten(params)
    return jax.tree_util.tree_unflatten(tree, [x + by * jax.random.normal(jax.random.PRNGKey(7 + i), x.shape) for i, x in enumerate(leaves)])


@pytest.fixture(scope="module")
def seeded():
    """Parameters: ``init``'s, every leaf stirred (the norms' scales start at one)."""
    return stirred(CausalLM(tiny()).init(jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 16), np.int32)}))


def close(a, b, tol, what=""):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert np.max(np.abs(a - b)) <= tol * (1.0 + np.max(np.abs(b))), (what, np.max(np.abs(a - b)), np.max(np.abs(b)))


def far(a, b, tol):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.max(np.abs(a - b)) > tol * (1.0 + np.max(np.abs(b)))


def test_the_tree_is_one_whatever_the_kind_and_the_records_say_what_their_hosts_read():
    params = jax.eval_shape(lambda: CausalLM(tiny()).init(jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 16), np.int32)}))
    for i in range(4):  # a full layer without positions and a rotated window layer: the same names and shapes
        assert set(params[f"layer_{i}"]) == {"RMSNorm_0", "RMSNorm_1", "attn", "routed"}
        assert set(params[f"layer_{i}"]["attn"]) == {"q_proj", "k_proj", "v_proj", "o_proj"}
        assert set(params[f"layer_{i}"]["routed"]) == {"gate", "experts_wg", "experts_wi", "experts_wo"}
    assert jax.tree_util.tree_map(lambda x: x.shape, params["layer_0"]) == jax.tree_util.tree_map(lambda x: x.shape, params["layer_1"])
    assert len(jax.tree_util.tree_leaves(params)) == 3 + 4 * 10
    nope, window, early = table.MIXERS["nope"], table.MIXERS["window"], table.FFNS["routed_early"]
    assert nope is UnrotatedAttention and issubclass(nope, Attention) and window is Attention is table.MIXERS["full"]
    assert early is EarlyRoutedMoE and issubclass(early, RoutedMoE) and table.FFNS["routed"] is RoutedMoE
    assert early.takes == ("mixer_input",) and RoutedMoE.takes == () == table.FFNS["dense"].takes == table.FFNS["moe"].takes
    assert nope.gives == nope.takes == window.takes == () and tiny().shares == ()  # an FFN's value lies inside its own block
    assert tiny().unstackable == ("nope", "routed_early") and tiny(layer_kinds=(("window", "dense"),) * 4).unstackable == ()
    assert table.remat_keeps(("nope", "routed_early")) == table.remat_keeps(("window", "routed")) == ("flash_attention", "projection", "routed_ffn")
    # the module's fields follow the kind
    built = lambda kind: table.MIXERS[kind].from_config(tiny(), kind)
    assert [(m.rotates, m.window, m.op, m.name) for m in map(built, ("nope", "window", "full"))] == \
        [(False, None, "nope", "attn"), (True, WINDOW, "window", "attn"), (True, None, "full", "attn")]


# float32 at the highest matmul precision on both sides: what is left is the order of float32 sums (a softmax row whole
# against XLA's own reduction, the fused cross-entropy against a log-softmax, the grouped products against a loop over
# experts): 2e-5 of the largest entry for the logits, 5e-5 for a gradient (sums over 160 positions). A wrong mask, a
# rotated full layer, a late router or silu for relu read 1e-2 and more (the test below): three orders over these
@pytest.mark.parametrize("remat", [False, True])
def test_the_model_is_the_plain_reference_in_logits_loss_and_every_gradient(ref, seeded, remat):
    """Rows of 80 tokens under a window of 16: a row crosses the window five times; one full layer, three window layers."""
    model = CausalLM(tiny(remat=remat))
    with jax.default_matmul_precision("highest"):
        close(model.apply(seeded, IDS), ref.logits(seeded, IDS, PUBLISHED, REF, jnp.float32), 2e-5, "logits")
        ours, g_ours = jax.value_and_grad(lambda p: model.loss_fn(p, {"input_ids": IDS}))(seeded)
        (theirs, _), g_theirs = ref.loss_and_grads(seeded, IDS, PUBLISHED, REF, jnp.float32)
    close(ours, theirs, 1e-6, "loss")
    theirs_by_path = dict(jax.tree_util.tree_leaves_with_path(g_theirs))
    mine = jax.tree_util.tree_leaves_with_path(g_ours)
    assert len(mine) == len(theirs_by_path) == 43
    for path, leaf in mine:
        close(leaf, theirs_by_path[path], 5e-5, jax.tree_util.keystr(path))
        assert float(jnp.max(jnp.abs(leaf))) > 0, jax.tree_util.keystr(path)


@pytest.mark.parametrize("control", [{"windows": "none"}, {"rotation": "all"}, {"rotation": "none"}, {"router": "late"}, {"gate": "silu"}, {"layers": 3}])
def test_a_reference_with_one_thing_wrong_is_far_from_the_model(ref, seeded, control):
    """Each control of the configuration's ``correct_why``: window layers that attend every key, the full layer rotated
    (or no layer), the router on ``RMSNorm_2(h)`` (the experts' own input), ``silu`` for ``relu``, a layer short: the
    logits lie 500 and more of the agreement's limit away."""
    model = CausalLM(tiny())
    with jax.default_matmul_precision("highest"):
        ours = model.apply(seeded, IDS)
        assert far(ours, ref.logits(seeded, IDS, PUBLISHED, dict(REF, **control), jnp.float32), 1e-2), control


def test_the_published_routing_is_softmax_topk():
    """Top 6 of the logits, a softmax over the six (the reference, the published way) equals a softmax over all 64 with
    its top 6 rescaled to sum to one (``moe/sharded_moe.py::softmax_topk``, which the program runs)."""
    logits = jax.random.normal(jax.random.PRNGKey(1), (512, 64), jnp.float32) * 3.0
    chosen, idx_theirs = jax.lax.top_k(logits, 6)
    weights_theirs = jax.nn.softmax(chosen, axis=-1)
    idx, weights = softmax_topk(logits, 6, 1.0)
    order = lambda i, w: (jnp.take_along_axis(i, jnp.argsort(i, axis=-1), -1), jnp.take_along_axis(w, jnp.argsort(i, axis=-1), -1))
    (i_a, w_a), (i_b, w_b) = order(idx, weights), order(idx_theirs, weights_theirs)
    assert (i_a == i_b).all()
    np.testing.assert_allclose(w_a, w_b, rtol=2e-6, atol=1e-7)
    np.testing.assert_allclose(jnp.sum(weights, -1), 1.0, rtol=1e-6)


def test_the_nope_kind_rotates_nothing_and_the_window_kind_rotates_as_pos_emb_says(seeded):
    """A ``nope`` layer is a ``full`` layer of a model without positions, on the same parameters; under ``rope`` a
    ``full`` layer differs from it. ``mixer/rope`` rises for the rotated kinds alone."""
    one = lambda kind, **over: CausalLM(tiny(n_layers=1, layer_kinds=((kind, "routed_early"),), **over))
    params = {k: v for k, v in seeded.items() if not k.startswith("layer_") or k == "layer_0"}
    rope = lambda: regions_traced("mixer/rope")
    before = rope()
    nope = one("nope").apply(params, IDS)
    assert rope() == before  # no rotating site was traced
    np.testing.assert_array_equal(nope, one("full", pos_emb="none").apply(params, IDS))
    rotated = one("full").apply(params, IDS)
    assert rope() == before + 1 and far(nope, rotated, 1e-3)
    np.testing.assert_array_equal(one("window", sliding_window=S).apply(params, IDS), rotated)  # a window of the whole row


def test_the_router_scores_the_first_norms_output_and_the_experts_read_the_second(seeded):
    """By the module itself: ``routed_early`` handed the block's first norm's output routes by it, and a ``routed``
    layer on the same parameters (which scores its own input) chooses other experts; the experts' input is the same."""
    cfg = tiny()
    x, u = (jax.random.normal(jax.random.PRNGKey(k), (2, 24, 64), jnp.float32) for k in (1, 2))
    p = {"params": seeded["layer_1"]["routed"]}
    early, late = (table.FFNS[kind].from_config(cfg, kind) for kind in ("routed_early", "routed"))
    (out_early, _), (out_late, _) = early.apply(p, x, True, mixer_input=u, mutable=["intermediates"]), late.apply(p, x, True, mutable=["intermediates"])
    assert far(out_early, out_late, 1e-2)
    np.testing.assert_array_equal(early.apply(p, x, True, mixer_input=x, mutable=["intermediates"])[0], out_late)
    # by hand: idx and weights from u, the held experts' ReGLU on x
    idx, weights = softmax_topk(u.reshape(-1, 64) @ p["params"]["gate"]["kernel"], 4, 1.0)
    want = jnp.zeros((48, 64))
    for e in range(4):
        wg, wi, wo = (p["params"][f"experts_{n}"][e] for n in ("wg", "wi", "wo"))
        w_e = jnp.sum(jnp.where(idx == 4 + e, weights, 0.0), -1, keepdims=True)
        want = want + w_e * ((jax.nn.relu(x.reshape(-1, 64) @ wg) * (x.reshape(-1, 64) @ wi)) @ wo)
    close(out_early.reshape(-1, 64), want, 1e-5)


def test_a_block_that_makes_no_first_norm_for_the_ffn_refuses_in_words():
    for over in ({"block_type": "parallel"}, {"block_type": "parallel_shared"}, {"norm_scheme": "post"}):
        with pytest.raises(NotImplementedError, match="routed_early FFN takes mixer_input of a sequential pre-norm block"):
            CausalLM(tiny(**over)).init(jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 16), np.int32)})


@pytest.mark.parametrize("act", ["silu", "relu"])
def test_the_experts_gate_is_the_configurations(act):
    """``held_experts(..., act=)``: ``wo (act(x wg) * x wi)``; ``silu`` is the default and what every value of
    ``activation`` but ``"reglu"`` gives a routed layer; ``relu`` is counted on ``ffn/experts``."""
    N, d, f, n, k = 32, 16, 8, 3, 2
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    tokens, wg, wi, wo = (jax.random.normal(kk, s) * 0.3 for kk, s in zip(keys, ((N, d), (n, d, f), (n, d, f), (n, f, d))))
    idx, weights = softmax_topk(jax.random.normal(keys[4], (N, 6)), k, 1.0)
    series = lambda: regions_traced("ffn/experts", act="relu")
    before = series()
    out = held_experts(tokens, idx, weights, wg, wi, wo, 1, N * k, False, act=act)[0]
    assert series() - before == (3.0 if act == "relu" else 0.0)  # three grouped products, each counted with its path
    want = jnp.zeros((N, d))
    for e in range(n):
        w_e = jnp.sum(jnp.where(idx == 1 + e, weights, 0.0), -1, keepdims=True)
        want = want + w_e * ((GATES[act](tokens @ wg[e]) * (tokens @ wi[e])) @ wo[e])
    close(out, want, 1e-5)
    if act == "silu":
        np.testing.assert_array_equal(out, held_experts(tokens, idx, weights, wg, wi, wo, 1, N * k, False)[0])
    picked = {a: RoutedMoE.from_config(tiny(activation=a), "routed").act for a in ("reglu", "swiglu", "gelu", "geglu")}
    assert picked == {"reglu": "relu", "swiglu": "silu", "gelu": "silu", "geglu": "silu"}


def test_a_dense_ffn_takes_reglu_too(seeded):
    cfg = tiny(n_layers=1, layer_kinds=(("nope", "dense"),), d_ff=48)
    params = CausalLM(cfg).init(jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 16), np.int32)})
    mlp, x = params["layer_0"]["mlp"], jax.random.normal(jax.random.PRNGKey(1), (2, 8, 64))
    out = table.FFNS["dense"].from_config(cfg, "dense").apply({"params": mlp}, x)
    close(out, (jax.nn.relu(x @ mlp["gate_proj"]["kernel"]) * (x @ mlp["up_proj"]["kernel"])) @ mlp["down_proj"]["kernel"], 1e-5)
    assert cfg.ffn_dim == 48 and tiny(d_model=256).ffn_dim == tiny(d_model=256, activation="swiglu").ffn_dim  # a gated FFN's sizing


def test_the_eight_shares_of_a_layer_add_up_to_the_uncut_layer(ref):
    """The share ties to the model: 64 experts, 6 a token, over 8 chips of 8. What each share's PROGRAM block adds to the
    mixer's output, summed over the eight, is what the plain reference gives for the whole layer with all 64 experts
    (attention, computed alike on every chip, counted once), for a window layer and for the full one."""
    E, held, k, d, f = 64, 8, 6, 64, 32
    pub = dict(PUBLISHED, moe_num_primary_experts=held, moe_num_active_primary_experts=k, routed_over=E)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, S, d), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (2, S))
    for n, kind in ((0, ("nope", "routed_early")), (1, ("window", "routed_early"))):
        whole_cfg = tiny(n_layers=1, layer_kinds=(kind,), moe_num_experts=E, moe_top_k=k, moe_held=(0, E))
        whole = stirred(table.Block(whole_cfg, kind).init(jax.random.PRNGKey(n), x, positions)["params"], 0.1)
        with jax.default_matmul_precision("highest"):
            uncut = ref.layer_part(whole, x, pub, REF, jnp.float32, n, 0, E)
            no_expert = dict(whole, routed={**whole["routed"], **{w: whole["routed"][w][:0] for w in ("experts_wg", "experts_wi", "experts_wo")}})
            h = ref.layer_part(no_expert, x, pub, REF, jnp.float32, n, 0, 0)  # the mixer's output added to the input: no expert's part
            total = h
            for share in range(E // held):
                cfg = tiny(n_layers=1, layer_kinds=(kind,), moe_num_experts=E, moe_top_k=k, moe_held=(share * held, held))
                mine = dict(whole, routed={**whole["routed"], **{w: whole["routed"][w][share * held:(share + 1) * held]
                                                                 for w in ("experts_wg", "experts_wi", "experts_wo")}})
                y, _ = table.Block(cfg, kind).apply({"params": mine}, x, positions, mutable=["intermediates"])
                total = total + (y - h)
        close(total, uncut, 2e-5, kind)
        assert far(h, uncut, 1e-3)  # the experts' part is no rounding


def test_a_window_calls_walk_says_its_window_and_its_tiles():
    """``program_regions_traced_total{region="mixer/kernel", window_tiles, window_tile}``: the flash forward under a window
    counts the tiles its walk visits of the square's (a band: at 16,384 rows under 4,096 keys 252 of 1,024 where the causal
    mask visits 528) and, as the backward does, the walk's tile (PR 69: under a band narrower than a block the strips', 128
    x 128 where the blocks are 512); a causal call says none. The mixer's own count says the window."""
    from deepspeed_tpu.ops import masks
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    assert masks.tiles_visited(masks.Causal(4096), bq=512, bk=512, seq_q=16384, seq_k=16384) == 252 == sum(min(i + 1, 9) for i in range(32))
    assert masks.tiles_visited(masks.Causal(), bq=512, bk=512, seq_q=16384, seq_k=16384) == 528
    # a record says what its walk is counted under (``masks.py::walk_labels``): nothing, the band's label, the block mask's two
    assert masks.Causal().walk_labels("512x512", "528/1024") == masks.Full().walk_labels("512x512", "1024/1024") == {}
    assert masks.Causal(4096).walk_labels("512x512", "252/1024") == {"window_tile": "512x512", "window_tiles": "252/1024"}
    assert masks.Causal(4096).walk_labels("512x512") == {"window_tile": "512x512"}  # a backward call: the tile alone
    assert masks.BlockDiffusion(4, 64).walk_labels("64x64", "3/4") == {"tiles": "3/4", "pairs": str(64 * 64 + 64 * 4)} and masks.BlockDiffusion(4, 64).walk_labels("64x64") == {}
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 2048, 1, 16), jnp.float32)
    before = dict(regions_traced_by("mixer/kernel", "window_tiles"))
    flash_attention(q, q, q, causal=True, interpret=True)
    assert regions_traced_by("mixer/kernel", "window_tiles") == before
    flash_attention(q, q, q, causal=True, window=64, interpret=True)
    rose = {k: v - before.get(k, 0) for k, v in regions_traced_by("mixer/kernel", "window_tiles").items() if v != before.get(k, 0)}
    assert len(rose) == 1 and list(rose.values()) == [1.0]
    # (PR 69) a band of 64 keys under blocks of 512 is walked in strips of 128: sixteen q tiles, two kv tiles each, where four
    # q blocks of 512 visited 1 + 2 + 2 + 2 = 7 of 16 (eight times the pairs a visit); the causal mask visits 10 of 16
    assert next(iter(rose)) == "32/256" and regions_traced_by("mixer/kernel", "window_tile").get("128x128", 0) >= 1
    assert Attention.joined["window_tiles"] == ("mixer/kernel", None, "window_tiles") and Attention.joined["window_keys"] == ("mixer/kernel", None, "window")
    assert Attention.joined["window_tile"] == ("mixer/kernel", None, "window_tile")
    assert UnrotatedAttention.paths == {"nope_path": ("mixer/kernel", {"op": "nope", "pass": "fwd"})}
    assert EarlyRoutedMoE.joined["moe_router_input"] == ("ffn/router", ("mixer_input",), "input") and RoutedMoE.joined["moe_activation"] == ("ffn/experts", ("relu", "relu2"), "act")  # (PR 59) and the ungated experts' word
    assert "moe_router_input" not in RoutedMoE.joined


@pytest.mark.parametrize("stage,mesh,n", [(0, {"data": 1}, 1), (3, {"fsdp": 4}, 4)])
def test_the_stack_trains_through_initialize_and_the_first_call_line_names_its_kinds(stage, mesh, n):
    """Stage 0 on one device and ZeRO-3 on four virtual devices (whose gathered block carries the first norm's output
    to the FFN as any value inside a block): the same first loss and the same loss after 3 steps within 2e-3; the
    first-call span names both attention kinds, the router's input and the experts' activation."""
    import deepspeed_tpu
    from deepspeed_tpu.parallel.mesh import initialize_mesh, reset_mesh
    from deepspeed_tpu.runtime.config import MeshConfig

    model = CausalLM(tiny(remat=True))
    ids = np.random.default_rng(0).integers(0, VOCAB, (4, S)).astype(np.int32)
    params = model.init(jax.random.PRNGKey(0), {"input_ids": ids[:1]})
    reg = get_registry()
    rows = [reg.peek(name) or 0.0 for name in ("moe_rows_routed_here_total", "moe_rows_dropped_total", "moe_fallback_layers_total")]
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
    assert 3 * 4 * 4 * S <= counted <= 4 * 4 * 4 * S * 4  # 4 layers, 4 rows of S tokens, up to 4 choices each, three or four steps counted
    assert reg.peek("moe_rows_dropped_total") == rows[1] and reg.peek("moe_fallback_layers_total") >= rows[2]
    said = [s["attrs"] for s in get_tracer().spans() if s["name"] == "program/first_call" and s["attrs"].get("family") == "train"][-1]
    assert said["layer_kinds"] == "nope+routed_early:1,window+routed_early:3" and said["block_traces"] == 2
    assert (said["nope_path"], said["window_path"], said["moe_path"], said["moe_combine"], said["rope"]) == ("xla",) * 5 and "full_path" not in said
    assert (said["window_keys"], said["moe_router"], said["moe_router_input"], said["moe_activation"]) == ("16", "softmax+compare_sum", "mixer_input", "relu")
    assert said["remat_keeps"] == "flash_attention+projection+routed_ffn"
    _TRAINED.setdefault("losses", losses)
    assert np.isfinite(losses).all() and losses[3] < losses[0]
    np.testing.assert_allclose([losses[0], losses[3]], [_TRAINED["losses"][0], _TRAINED["losses"][3]], atol=2e-3)


_TRAINED = {}


def test_serving_refuses_the_new_kinds_by_name():
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2

    for kinds in (KINDS, (("nope", "dense"),) * 4):  # the routed FFN aside: a layer without positions is refused of itself
        model = CausalLM(tiny(layer_kinds=kinds))
        with pytest.raises(NotImplementedError, match="nope"):
            InferenceEngineV2(model, params=None)
    with pytest.raises(NotImplementedError, match="nope"):
        CausalLM(tiny(layer_kinds=(("nope", "dense"),) * 4, scan_layers=True)).init(jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 16), np.int32)})
