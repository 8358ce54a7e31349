"""Block-diffusion training of a routed decoder (``models/mixers.py::BlockDiffMixer``, the objective it states, the
weighted fused cross-entropy): the model against the configuration's plain reference at a small width on the CPU, in
the noised half's logits, the loss and every leaf's gradient, for blocks of 4 and 16, with ``remat`` on and off; what
the reference's controls and a lower precision break; the weighted cross-entropy against the plain one; the routed
layer's eight shares; the device counts; the refusals' words; the trainer's path and its first-call line.

The reference is the benchmark configuration's own file (``benchmarks/configs/sdar-30b-a3b-l4e16.reference.py``),
loaded by its path: it imports nothing of the program or of the benchmark."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
from deepspeed_tpu.models import CausalLM, TransformerConfig, transformer as table
from deepspeed_tpu.ops.fused_ce import fused_cross_entropy, fused_cross_entropy_sums
from deepspeed_tpu.parallel.mesh import initialize_mesh, reset_mesh
from deepspeed_tpu.runtime.config import MeshConfig
from deepspeed_tpu.telemetry import get_registry

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
VOCAB, MASK, L = 211, 210, 48
PUBLISHED = {"rms_norm_eps": 1e-6, "rope_theta": 1e6, "num_hidden_layers": 2, "num_experts": 4, "num_experts_per_tok": 4, "routed_over": 16}
REF = {"held_first": 4, "mask_token_id": MASK}


def tiny(block=4, **over):
    base = dict(vocab_size=VOCAB, n_layers=2, n_heads=4, n_kv_heads=2, head_dims=16, d_model=64, max_seq_len=L, norm="rmsnorm",
                activation="swiglu", pos_emb="rope", rope_theta=1e6, qk_norm=True, tie_embeddings=False, norm_eps=1e-6,
                layer_kinds=(("blockdiff", "routed"),) * 2, block_length=block, mask_token_id=MASK, moe_num_experts=16, moe_top_k=4,
                moe_d_ff=32, moe_held=(4, 4), moe_scoring="softmax", moe_aux_loss_coef=0.0)
    return TransformerConfig(**dict(base, **over))


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location("blockdiff_reference", os.path.join(ROOT, "benchmarks", "configs", "sdar-30b-a3b-l4e16.reference.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def rows(block, seed=0, batch=2):
    """[xt ; x0] by the benchmark's own generator."""
    spec = importlib.util.spec_from_file_location("blockdiff_batches", os.path.join(ROOT, "benchmarks", "generators", "block_diffusion_batches.py"))
    gen = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gen)
    return gen.generate({"seq_len": L, "block_len": block, "n_batches": 1}, seed, 0.0, {"vocab_size": VOCAB, "global_batch": batch})["batches"][0]["input_ids"]


@pytest.fixture(scope="module")
def seeded():
    """Parameters: ``init``'s, every leaf stirred (the norms' scales start at one)."""
    params = CausalLM(tiny()).init(jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 16), np.int32)})
    leaves, tree = jax.tree_util.tree_flatten(params)
    return jax.tree_util.tree_unflatten(tree, [x + 0.05 * jax.random.normal(jax.random.PRNGKey(7 + i), x.shape) for i, x in enumerate(leaves)])


def close(a, b, tol, what=""):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert np.max(np.abs(a - b)) <= tol * (1.0 + np.max(np.abs(b))), (what, np.max(np.abs(a - b)), np.max(np.abs(b)))


def test_the_tree_is_the_kinds_own_and_the_record_states_the_objective():
    params = jax.eval_shape(lambda: CausalLM(tiny()).init(jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 16), np.int32)}))
    assert set(params["layer_0"]["blockdiff"]) == {"q_proj", "k_proj", "v_proj", "o_proj", "q_norm", "k_norm"}
    assert len(jax.tree_util.tree_leaves(params)) == 3 + 2 * 12
    assert tiny().objective is table.MIXERS["blockdiff"] and table.TransformerConfig().objective is None and tiny().unstackable == ("blockdiff", "routed")


# float32 at the highest matmul precision on both sides: what is left is the order of float32 sums (a softmax row of up
# to 52 keys whole against by XLA's own reduction, the fused cross-entropy against a log-softmax): 2e-5 of the largest
# entry for the logits, 5e-5 for a gradient (sums over 192 positions). bf16 for float32 reads 1e-2, a wrong mask 0.5 and
# more (the tests below): three and four orders over these
@pytest.mark.parametrize("block,remat", [(4, False), (4, True), (16, False), (16, True)])
def test_logits_loss_and_every_leafs_gradient_agree_with_the_float32_reference(ref, seeded, block, remat):
    ids, params = rows(block), seeded
    model = CausalLM(tiny(block, remat=remat))
    rc = dict(REF, block_length=block)
    with jax.default_matmul_precision("highest"):
        close(model.apply(params, ids)[:, :L], ref.logits(params, ids, PUBLISHED, rc, jnp.float32), 2e-5, "logits")
        ours, g_ours = jax.value_and_grad(lambda p: model.loss_fn(p, {"input_ids": ids}))(params)
        (theirs, _), g_theirs = ref.loss_and_grads(params, ids, PUBLISHED, rc, jnp.float32)
    close(ours, theirs, 1e-6, "loss")
    theirs_by_path = dict(jax.tree_util.tree_leaves_with_path(g_theirs))
    leaves = jax.tree_util.tree_leaves_with_path(g_ours)
    assert len(leaves) == len(theirs_by_path) == 27
    for path, leaf in leaves:
        close(leaf, theirs_by_path[path], 5e-5, jax.tree_util.keystr(path))
    assert all(float(jnp.max(jnp.abs(leaf))) > 0 for _, leaf in leaves)  # every leaf takes a gradient


@pytest.mark.parametrize("control", ["causal", "blind"])
def test_a_wrong_mask_moves_the_logits(ref, seeded, control):
    ids = rows(4)
    with jax.default_matmul_precision("highest"):
        sound = ref.logits(seeded, ids, PUBLISHED, dict(REF, block_length=4), jnp.float32)
        broken = ref.logits(seeded, ids, PUBLISHED, dict(REF, block_length=4, mask=control), jnp.float32)
        ours = CausalLM(tiny()).apply(seeded, ids)[:, :L]
    assert float(jnp.linalg.norm(broken - sound) / jnp.linalg.norm(sound)) > 0.3
    with pytest.raises(AssertionError):
        close(ours, broken, 2e-5)


@pytest.mark.parametrize("control", ["uniform_weights", "shift"])
def test_another_loss_moves_the_loss_and_the_gradient(ref, seeded, control):
    ids = rows(4)
    with jax.default_matmul_precision("highest"):
        (sound, _), g_sound = ref.loss_and_grads(seeded, ids, PUBLISHED, dict(REF, block_length=4), jnp.float32)
        (broken, _), g_broken = ref.loss_and_grads(seeded, ids, PUBLISHED, dict(REF, block_length=4, **{control: True}), jnp.float32)
    assert abs(float(broken - sound)) > 1e-3  # random targets: a shifted one scores nearly alike; three orders over the 1e-6 above
    far = lambda a, b: float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
    assert far(g_broken["lm_head"]["kernel"], g_sound["lm_head"]["kernel"]) > 0.2 and far(g_broken["wte"], g_sound["wte"]) > 0.2


def test_bf16_for_float32_is_over_the_float32_tolerance(ref, seeded):
    ids = rows(4)
    rc = dict(REF, block_length=4)
    truth = ref.logits(seeded, ids, PUBLISHED, rc, jnp.float32)
    plain, low = (ref.logits(seeded, ids, PUBLISHED, dict(rc, low_state=flag), jnp.bfloat16) for flag in (False, True))
    assert float(jnp.max(jnp.abs(plain - truth)) / jnp.max(jnp.abs(truth))) > 2e-5 * 20
    assert 0 < float(jnp.max(jnp.abs(low - plain)))  # the statistics in bf16 too: the precision below the stated one
    assert float(jnp.max(jnp.abs(ref.logits(seeded, ids, PUBLISHED, dict(rc, low_state=True), jnp.float32) - truth))) == 0.0


def test_the_references_loss_is_the_stated_sum_and_reads_the_files_block_length(ref):
    ids = rows(4, seed=3)
    logits = jax.random.normal(jax.random.PRNGKey(1), (2, L, VOCAB))
    xt, x0 = np.asarray(ids[:, :L]), np.asarray(ids[:, L:])
    logp = np.asarray(jax.nn.log_softmax(logits, axis=-1))
    want = 0.0
    for r in range(2):
        for b in range(L // 4):
            block = range(4 * b, 4 * b + 4)
            m = sum(xt[r, i] == MASK for i in block)
            want += sum(4.0 / m * -logp[r, i, x0[r, i]] for i in block if xt[r, i] == MASK)
    close(ref.masked_loss(logits, ids, 4, MASK), want / (2 * L), 1e-6)
    close(ref.loss(logits, ids), ref.masked_loss(logits, ids, 4, VOCAB - 1), 0.0)  # the file's block length, the last row held


@pytest.mark.parametrize("vd_layout", [False, True])
def test_the_weighted_fused_cross_entropy_is_the_plain_weighted_sum(vd_layout):
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    x, w = jax.random.normal(ks[0], (2, 64, 32)), jax.random.normal(ks[1], (97, 32) if vd_layout else (32, 97)) * 0.2
    labels = jax.random.randint(ks[2], (2, 64), 0, 97)
    weights = jnp.where(jax.random.uniform(ks[3], (2, 64)) < 0.4, 0.0, jax.random.uniform(ks[3], (2, 64)) * 4)

    def plain(x, w):
        logits = x @ (w.T if vd_layout else w)
        nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        return jnp.sum(weights * nll)

    fused = lambda x, w: fused_cross_entropy_sums(x, w, labels, vd_layout=vd_layout, chunk=16, weights=weights)[0]
    with jax.default_matmul_precision("highest"):
        close(fused(x, w), plain(x, w), 1e-6)
        for got, want in zip(jax.grad(fused, (0, 1))(x, w), jax.grad(plain, (0, 1))(x, w)):
            close(got, want, 1e-5)
        # ``ignore_index`` is the weight-0 case: ones and zeros for weights give the older sum and the older count
        kept = weights > 0
        ignored = jnp.where(kept, labels, -100)
        total, count = fused_cross_entropy_sums(x, w, ignored, vd_layout=vd_layout, chunk=16)
        as_weights, n = fused_cross_entropy_sums(x, w, labels, vd_layout=vd_layout, chunk=16, weights=kept.astype(jnp.float32))
        close(as_weights, total, 1e-6)
        assert int(count) == int(n) == int(kept.sum())
        close(fused_cross_entropy(x, w, ignored, vd_layout=vd_layout, chunk=16), total / count, 1e-6)
    # the weights take no gradient and need none
    assert float(jnp.max(jnp.abs(jax.grad(lambda ws: fused_cross_entropy_sums(x, w, labels, vd_layout=vd_layout, weights=ws)[0])(weights)))) == 0.0


def test_eight_shares_add_up_to_the_uncut_references_layer(ref):
    """THE SHARE TEST: 16 experts, 4 a position by softmax, renormalised, no shared expert: eight chips each hold 2 and
    give their experts' part; the parts add up to the plain reference's layer over all 16. What every chip of the group
    computes alike (attention under the block mask, the norms, the router) is one program on the same weights."""
    from deepspeed_tpu.moe.layer import RoutedMoE

    layer = lambda held: RoutedMoE(hidden_size=48, num_experts=16, k=4, d_ff=24, held=held, shared_ff=0, scale=1.0, scoring="softmax", dtype=jnp.float32)
    h = jax.random.normal(jax.random.PRNGKey(4), (2, 24, 48))
    whole = layer(None).init(jax.random.PRNGKey(5), h)["params"]
    whole = {k: v + 0.05 * jax.random.normal(jax.random.PRNGKey(i), jnp.shape(v)) if not isinstance(v, dict) else v for i, (k, v) in enumerate(whole.items())}
    share = lambda first: {k: (v[first:first + 2] if k.startswith("experts_") else v) for k, v in whole.items()}
    with jax.default_matmul_precision("highest"):
        parts = sum(layer((first, 2)).apply({"params": share(first)}, h) for first in range(0, 16, 2))
        close(parts, ref._routed(whole, h, jnp.float32, 0, 16, 4, jnp.float32), 1e-5)


def _engine(cfg, batch, lr=1e-2):
    model = CausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 16), np.int32)})
    reset_mesh()
    topo = initialize_mesh(MeshConfig.from_dict({"data": 1}), devices=jax.devices()[:1], force=True)
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params, mesh=topo, config={
        "train_micro_batch_size_per_gpu": batch, "optimizer": {"type": "adam", "params": {"lr": lr}}, "zero_optimization": {"stage": 0},
        "mesh": {"data": 1}, "steps_per_print": 10**9})
    return engine


def test_the_model_trains_under_the_engine_counts_its_noise_and_says_its_path():
    from deepspeed_tpu.telemetry import get_tracer

    ids = rows(4, seed=5)
    engine = _engine(tiny(remat=True), 2)
    reg = get_registry()
    before = [reg.total(name) for name in ("diffusion_masked_positions_total", "diffusion_positions_total")]
    losses = []
    for _ in range(4):
        loss = engine.forward({"input_ids": ids})
        engine.backward(loss)
        engine.step()
        losses.append(float(loss))
    jax.block_until_ready(engine.params if hasattr(engine, "params") else loss)
    loss = engine.forward({"input_ids": ids})  # one more dispatch takes the ended steps' counts
    assert np.isfinite(losses).all() and losses[3] < losses[0]
    masked, positions = (reg.total(name) - was for name, was in zip(("diffusion_masked_positions_total", "diffusion_positions_total"), before))
    n_masked = int((np.asarray(ids[:, :L]) == MASK).sum())
    assert positions > 0 and positions % (2 * L) == 0 and masked / positions == n_masked / (2 * L)  # the device read the noise as it was drawn
    assert abs(reg.peek("diffusion_weight_sum") - L) < 1e-4 * L  # every block masks a position and its weights sum to the block (4/3 in float32)
    said = [s["attrs"] for s in get_tracer().spans() if s["name"] == "program/first_call" and s["attrs"].get("family") == "train"][-1]
    assert said["layer_kinds"] == "blockdiff+routed:2" and said["blockdiff_path"] == "xla"
    assert said["remat_keeps"] == "flash_attention+projection+routed_ffn" and said["block_traces"] == 1
    assert "blockdiff_tiles" not in said  # the kernels' walk: XLA's form visits the square
    reset_mesh()


def test_the_masked_share_follows_the_generators_law():
    """Blocks of B mask m of B with m uniform on 1 .. B: (B + 1) / (2 B) of the positions, 0.625 at B = 4."""
    for block, want in ((4, 0.625), (16, 17 / 32)):
        spec = importlib.util.spec_from_file_location("blockdiff_batches", os.path.join(ROOT, "benchmarks", "generators", "block_diffusion_batches.py"))
        gen = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(gen)
        ids = gen.generate({"seq_len": 8192, "block_len": block, "n_batches": 2}, 7, 0.0, {"vocab_size": VOCAB, "global_batch": 1})["batches"][0]["input_ids"]
        _, _, weights, divisor = table.MIXERS["blockdiff"].targets(tiny(block), jnp.asarray(ids))
        assert abs(float(jnp.mean(weights > 0)) - want) < 0.02 and abs(float(jnp.sum(weights)) - 8192.0) < 0.5 and divisor == 8192


def test_the_refusals_say_in_words_what_they_refuse():
    model = CausalLM(tiny())
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 16), np.int32)}))
    params = model.init(jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 16), np.int32)})
    for bad in (15, 18, 8 + 8 + 4):  # odd; halves of 9; halves of 10: neither whole blocks of 4
        with pytest.raises(ValueError, match="a block-diffusion row is .noised ; clean.: an even count of ids, each half a whole number"):
            model.apply(params, np.zeros((1, bad), np.int32))
    with pytest.raises(NotImplementedError, match="takes no KV cache.*generation denoises a block in several steps"):
        model.apply(params, np.zeros((1, 16), np.int32), kv_caches=model.init_kv_caches(1, 16))
    with pytest.raises(NotImplementedError, match="no\\s+packed segments"):
        model.apply(params, np.zeros((1, 16), np.int32), segment_ids=jnp.zeros((1, 16), jnp.int32))
    with pytest.raises(ValueError, match="makes its targets from input_ids: give no labels"):
        model.loss_fn(params, {"input_ids": np.zeros((1, 16), np.int32), "labels": np.zeros((1, 16), np.int32)})
    with pytest.raises(NotImplementedError, match="blockdiff.*not pipeline-partitionable"):
        model.to_pipeline(1, params=shapes)
    with pytest.raises(NotImplementedError, match="inference/v2 serves softmax attention over one head size.*blockdiff"):
        InferenceEngineV2(model, shapes)
    with pytest.raises(NotImplementedError, match="the scan over layers stacks.*blockdiff.*set scan_layers=False"):
        CausalLM(tiny(scan_layers=True)).init(jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 16), np.int32)})


def test_zero3s_sliced_head_is_refused_for_weighted_targets():
    model = CausalLM(tiny())
    params = model.init(jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 16), np.int32)})

    class Hook:  # offers a sliced head, as ``zero/overlap.py`` would for a model that sows nothing
        def look_up(self, *a):
            return None

        def head(self, *a):
            return lambda hidden, labels: (jnp.zeros((1,)), jnp.ones((1,)))

        def __call__(self, paths, layers, sows, i, x):
            return None, x

    with table.block_hook(Hook()), pytest.raises(NotImplementedError, match="sliced loss head .* sums unweighted targets"):
        model.loss_fn(params, {"input_ids": rows(4, batch=1)})
