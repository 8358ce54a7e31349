"""The configuration ``kimi-vl-a3b-l6e8`` (latent attention with a rotated
shared key part in every layer, a routed FFN of 6 of 64 with two shared
experts, as one chip's share of an 8-chip group): its files pass the
manifest's checks and hold the catalog row's widths, the program agrees with
its plain float32 reference in logits, loss and the gradient of every leaf,
the reference's controls move the result, its FLOP module's total is a sum a
reader can check by hand, its reader reads a synthetic trace's kernels and
nothing else, and its rehearsal ends ``correct`` false with a reference a
layer short (true with the sound one: ``test_benchmark_rehearse.py`` picks the
cell up from ``workloads``)."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmarks.lib import flops, manifest as mf

MANIFEST = mf.load_manifest()
NAME, CELL = "kimi-vl-a3b-l6e8", "kimi-vl-a3b-l6e8.pretrain-8k"
CONFIG = mf.load_json(os.path.join(mf.BENCH, "configs", f"{NAME}.json"))
PUBLISHED = mf.published(CONFIG)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_the_manifest_and_the_configuration_have_no_problems():
    assert mf.problems(MANIFEST) == []
    entry = next(c for c in MANIFEST["configs"] if c["name"] == NAME)
    assert mf.config_problems(CONFIG, entry) == []
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert (cell["chips"], cell["traffic"], cell["config"]) == (1, "pretrain-8k", NAME)
    assert (entry["file"], entry["source"]) == (f"benchmarks/configs/{NAME}.json", CONFIG["source"])  # found by name: later entries follow
    assert CONFIG["trainer"]["train_micro_batch_size_per_gpu"] == 1 and CONFIG["trainer"]["zero_optimization"]["stage"] == 0
    reported = {m["name"] for g in ("end_to_end", "per_layer") for m in mf.metrics_of(MANIFEST, CELL, g)}
    assert {"train_tokens_per_s", "setup_s", "mfu.train", "latent_attention_roofline", "moe_expert_matmul_roofline"} <= reported
    metric = next(m for m in MANIFEST["per_layer"] if m["name"] == "latent_attention_roofline")
    assert CELL in metric["workloads"] and (metric["unit"], metric["source"], metric["moves"]) == ("%", "device_trace", "train_tokens_per_s")


@pytest.mark.skipif(not os.path.isfile(CATALOG), reason="the catalog of published configurations is not on this machine")
def test_every_width_is_the_sources():
    source = next(json.loads(line) for line in open(CATALOG) if '"Kimi-VL-A3B-Instruct"' in line)
    assert CONFIG["source"] == source["source_url"]
    source = source["config"]
    differs = {k for k, v in source.items() if CONFIG.get(k, "missing") != v}
    assert differs == set(CONFIG["reduced"]) == {"num_hidden_layers", "n_routed_experts", "vocab_size"}
    held = CONFIG["share"]["held"]
    assert CONFIG["share"]["chips_per_layer"] == 8 and CONFIG["routed_over"] == source["n_routed_experts"] == 64
    assert held["n_routed_experts"] == {"published": 64, "here": 8} and held["vocab_size"] == {"published": 163840, "here": 20480}
    assert 64 // 8 == 8 and 163840 // 8 == 20480  # the floors: 8 routed experts, an eighth of the vocabulary
    assert CONFIG["num_hidden_layers"] - source["first_k_dense_replace"] >= 4  # and four layers after the leading dense one
    p = CONFIG["program"]
    assert p["layer_kinds"] == [["mla", "dense"]] + [["mla", "routed"]] * 5 and p["n_layers"] == CONFIG["num_hidden_layers"] == 6
    assert (p["d_model"], p["d_ff"], p["n_heads"], p["moe_d_ff"], p["moe_top_k"], p["moe_num_experts"], p["moe_route_scale"]) == \
        (source["hidden_size"], source["intermediate_size"], source["num_attention_heads"], source["moe_intermediate_size"],
         source["num_experts_per_tok"], source["n_routed_experts"], source["routed_scaling_factor"])
    assert (p["mla_kv_rank"], p["mla_qk_nope_dim"], p["mla_qk_rope_dim"], p["mla_v_dim"]) == \
        (source["kv_lora_rank"], source["qk_nope_head_dim"], source["qk_rope_head_dim"], source["v_head_dim"])
    assert p["moe_shared_d_ff"] == source["n_shared_experts"] * source["moe_intermediate_size"] == 2816  # two of 1,408 as one
    assert (p["pos_emb"], p["rope_theta"], p["rope_style"], p["norm_eps"]) == ("rope", source["rope_theta"], "gptj", source["rms_norm_eps"])
    assert p["moe_held"] == [0, CONFIG["n_routed_experts"]] and p["vocab_size"] == CONFIG["vocab_size"] and not p["tie_embeddings"]
    for key in ("rotation", "select_bias", "auxiliary_loss", "optimizer", "weights", "held", "shared_experts", "vision_tower"):
        assert key in CONFIG["assumed"]


def test_the_flop_count_is_the_hand_written_sum():
    mod = flops.for_config(CONFIG)
    m = dict(PUBLISHED, hidden_size=64, intermediate_size=96, moe_intermediate_size=32, vocab_size=509, n_routed_experts=4,
             routed_over=16, num_experts_per_tok=3, num_attention_heads=4, kv_lora_rank=32, qk_nope_head_dim=24,
             qk_rope_head_dim=8, v_head_dim=16, num_hidden_layers=3)
    S, d = 96, 64
    mla = 2 * (d * 4 * 32 + d * (32 + 8) + 32 * 4 * (24 + 16) + 4 * 16 * d) + 3 * 5 * 8 + S * 4 * (32 + 16)
    expert = 3 * d * 32
    routed = 2 * (d * 16 + 2 * expert + (3 * 4 / 16) * expert)
    forward = 3 * mla + 2 * 3 * d * 96 + 2 * routed + 2 * d * 509
    assert mod.train_flops_per_token(m, S) == pytest.approx(3 * forward) and mod.mla_layers(m) == 3
    # the published widths: 2.63 GFLOP a token; attention's quadratic part about 29% of it, more than a layer's projections
    total, square = mod.train_flops_per_token(PUBLISHED, 8192), 3 * 6 * 8192 * 16 * (192 + 128)
    assert 2.62e9 < total < 2.65e9 and 0.28 < square / total < 0.30 and mod.mla_layers(PUBLISHED) == 6
    cost = mod.mla_attention_cost(PUBLISHED, 1, 8192, backward=False)
    assert cost["flops"] == 8192 * 8192 * 16 * 320 and mod.mla_attention_cost(PUBLISHED, 1, 8192, backward=True)["flops"] == 2 * cost["flops"]
    # a held expert's three products at the 6,144 rows a uniform router sends here: 12 x rows x d x f forward and as much twice back
    rows = 8192 * 6 * 8 / 64
    assert mod.expert_matmul_cost(PUBLISHED, rows, backward=False)["flops"] == 6.0 * 2048 * 1408 * rows


def _tiny(layers=3):
    """The rehearsal's width, ``layers`` deep (the dense layer and then routed ones), float32."""
    from benchmarks.lib import weights

    r = CONFIG["rehearse"]
    cfg = dict(CONFIG, **r["published"], num_hidden_layers=layers, reference=r["reference"])
    cfg["program"] = dict(CONFIG["program"], **r["program"], dtype="float32", n_layers=layers,
                          layer_kinds=CONFIG["program"]["layer_kinds"][:layers])
    return cfg, weights.build_model(cfg)


@pytest.fixture(scope="module")
def tiny_model():
    import jax
    import numpy as np

    cfg, model = _tiny()
    ids = np.random.default_rng(0).integers(0, 509, (2, 40)).astype(np.int32)
    params = model.init(jax.random.PRNGKey(0), {"input_ids": ids})
    return cfg, model, params, ids


def _close(a, b, tol):
    import numpy as np

    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert np.max(np.abs(a - b)) <= tol * (1.0 + np.max(np.abs(b))), (np.max(np.abs(a - b)), np.max(np.abs(b)))


def test_the_program_agrees_with_the_plain_reference_in_logits_loss_and_every_gradient(tiny_model):
    """One dense and two routed layers, seeded random weights, float32: the program (one shared SwiGLU of twice the
    width, the rotation as ``apply_rope`` does it, the sorted grouped products) against the reference (two shared
    experts added, the rotation written out by pairs, a loop over the held experts)."""
    import jax
    import jax.numpy as jnp

    from benchmarks.lib import reference

    cfg, model, params, ids = tiny_model
    logits, loss = reference.for_config(cfg)
    pub = mf.published(cfg)
    plain_loss = lambda p: loss(logits(p, ids, pub, cfg["reference"], jnp.float32), ids)
    with jax.default_matmul_precision("highest"):
        _close(model.apply(params, ids), logits(params, ids, pub, cfg["reference"], jnp.float32), 2e-5)
        (ours, g_ours), (theirs, g_theirs) = (jax.value_and_grad(f)(params) for f in (lambda p: model.loss_fn(p, {"input_ids": ids}), plain_loss))
    _close(ours, theirs, 1e-6)
    theirs_by_path = dict(jax.tree_util.tree_leaves_with_path(g_theirs))
    leaves = jax.tree_util.tree_leaves_with_path(g_ours)
    assert len(leaves) == len(theirs_by_path) == len(jax.tree_util.tree_leaves(params))
    for path, leaf in leaves:
        _close(leaf, theirs_by_path[path], 5e-5)
    touched = {jax.tree_util.keystr(p) for p, l in leaves if float(jnp.max(jnp.abs(l))) > 0}
    assert len(leaves) - len(touched) == 2  # every leaf but the two routed layers' selection bias, which only chooses


@pytest.mark.parametrize("control,least", [({"no_rope": True}, 1e-3), ({"layers_short": 1}, 1e-2)])
def test_the_references_controls_move_the_logits(tiny_model, control, least):
    """Without the rotation, and a layer short, the reference is another function: the comparisons that use them as
    controls (``chip_smoke.py --only latent``, the cell's ``correct``) can tell."""
    import jax
    import jax.numpy as jnp

    from benchmarks.lib import reference

    cfg, _, params, ids = tiny_model
    logits, _ = reference.for_config(cfg)
    pub = mf.published(cfg)
    with jax.default_matmul_precision("highest"):
        sound = logits(params, ids, pub, cfg["reference"], jnp.float32)
        broken = logits(params, ids, pub, dict(cfg["reference"], **control), jnp.float32)
    assert float(jnp.linalg.norm(broken - sound) / jnp.linalg.norm(sound)) > least


def test_the_low_state_control_is_the_bf16_reference_with_lower_statistics(tiny_model):
    import jax.numpy as jnp

    from benchmarks.lib import reference

    cfg, _, params, ids = tiny_model
    logits, _ = reference.for_config(cfg)
    pub = mf.published(cfg)
    plain = logits(params, ids, pub, cfg["reference"], jnp.bfloat16)
    low = logits(params, ids, pub, dict(cfg["reference"], low_state=True), jnp.bfloat16)
    same = logits(params, ids, pub, dict(cfg["reference"], low_state=True), jnp.float32)  # float32 has no lower state
    assert float(jnp.max(jnp.abs(low - plain))) > 0 and float(jnp.max(jnp.abs(low - plain))) < 0.5
    assert float(jnp.max(jnp.abs(same - logits(params, ids, pub, cfg["reference"], jnp.float32)))) == 0.0


def _record(ops, counters=None, steps=4, config=CONFIG):
    """A traced training record with one device, ``steps`` executions of the step program and these operations."""
    dev = {"ops": ops, "op_counts": {k: 1 for k in ops}, "modules": [("jit_fused_step(123)", 0.1 * i, 0.1 * i + 0.09) for i in range(steps)]}
    return {"reduced": {"devices": {"0": dev}, "window_s": 1.0}, "published": mf.published(config), "config": config,
            "train": {"micro_batch": 1, "seq_len": 8192, "steps": 100}, "device": {"kind": "TPU v5 lite", "count": 1},
            "counters": counters or {}, "end_to_end": {"train_tokens_per_s": 1.0}}


MLA_OPS = {'mla.3 custom-call (bf16[16,8192,128]{2,1,0}, f32[16,16,1,512]{3,2,1,0}) custom_call_target="tpu_custom_call"': 0.06,
           'mla.18 custom-call (bf16[16,8192,192]{2,1,0}, bf16[16,8192,192]{2,1,0}, bf16[16,8192,128]) custom_call_target="tpu_custom_call"': 0.15}
MOE_OPS = {'gmm custom-call bf16[24576,1408]{1,0} custom_call_target="tpu_custom_call"': 0.04,
           'tgmm custom-call bf16[8,2048,1408]{2,1,0} custom_call_target="tpu_custom_call"': 0.04}
OTHER = {"fusion.1 fusion bf16[8192,2048]{1,0}": 0.5,
         'mla.1 custom-call (bf16[32,8192,192]{2,1,0}, bf16[32,8192,192]{2,1,0}) custom_call_target="tpu_custom_call"': 0.3}  # 32 heads: not this model's


@pytest.mark.parametrize("metric,ops,counters", [
    ("latent_attention_roofline", MLA_OPS, {}),
    ("moe_expert_matmul_roofline", MOE_OPS, {"moe_rows_routed_here_total": 100 * 5 * 6144.0}),
])
def test_a_reader_reads_its_kernels_and_nothing_else(metric, ops, counters):
    read = mf.metric_module(metric).read
    share = read(_record(dict(ops, **OTHER), counters))
    assert 0 < share < 100
    assert read(_record(dict({k: 2 * v for k, v in ops.items()}, **OTHER), counters)) == pytest.approx(share / 2)
    assert read(_record(OTHER, counters)) is None                      # a program without the kernel
    assert read(dict(_record(dict(ops, **OTHER), counters), reduced=None)) is None  # an untraced run
    assert read(dict(_record(dict(ops, **OTHER), counters), config={})) is None      # a configuration with no such cost
    if counters:
        assert read(_record(dict(ops, **OTHER))) in (None, pytest.approx(share))  # no counter handed: none, or the process's own


def test_the_latent_reader_counts_six_layers_and_the_other_configurations_module_gives_it_nothing():
    from benchmarks.lib.peaks import peaks_for

    mod, peaks = flops.for_config(CONFIG), peaks_for("TPU v5 lite")
    need = sum(flops.roofline_seconds(mod.mla_attention_cost(PUBLISHED, 1, 8192, backward=b), peaks)["seconds"] for b in (False, True))
    read = mf.metric_module("latent_attention_roofline").read
    assert read(_record(MLA_OPS)) == pytest.approx(100 * 4 * 6 * need / 0.21)
    # the benchmark's other latent-attention configuration names no ``mla_layers``: the reader finds nothing and does not raise
    other = mf.load_json(os.path.join(mf.BENCH, "configs", "kimi-linear-48b-l5e8.json"))
    assert read(_record(MLA_OPS, config=other)) is None


@pytest.fixture(scope="module")
def short_copy(tmp_path_factory):
    """A copy of the benchmark whose new configuration's reference leaves out the last layer."""
    root = str(tmp_path_factory.mktemp("latent"))
    shutil.copytree(mf.BENCH, os.path.join(root, "benchmarks"), ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(mf.ROOT, "deepspeed_tpu"), os.path.join(root, "deepspeed_tpu"))
    cfg = json.loads(json.dumps(CONFIG))
    cfg["reference"]["layers_short"] = cfg["rehearse"]["reference"]["layers_short"] = 1
    json.dump(cfg, open(os.path.join(root, "benchmarks", "configs", f"{NAME}.json"), "w"))
    json.dump(MANIFEST, open(os.path.join(root, "BENCHMARK.json"), "w"))
    out = subprocess.run([sys.executable, os.path.join(root, "benchmarks", "run.py"), "--workload", CELL, "--rehearse",
                          "--seed", str(2**31 + 23), "--seconds", "1"], capture_output=True, text=True, timeout=900,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    return json.loads(lines[-1]), json.loads(lines[-2])["extras"], out.stdout, out.stderr


def test_a_reference_a_layer_short_is_not_correct(short_copy):
    last, extras, _, err = short_copy
    assert last["correct"] is False and err.strip().splitlines()[-1] == "correct: False"
    rule = extras["first_loss_f32_rule"]
    assert rule["ours_vs_f32"] > 2.5 * max(rule["plain_bf16_vs_f32"], 3e-4)


def test_the_rehearsal_says_what_was_traced_and_drops_no_row(short_copy):
    _, extras, said, _ = short_copy
    counters = extras["counters"]
    assert counters["moe_rows_routed_here_total"] > 0 and counters["moe_rows_dropped_total"] == 0
    line = next(l for l in said.splitlines() if "program first call: family=train" in l)
    for word in ("block_traces=2", "layer_kinds=mla+dense:1,mla+routed:5", "mla_path=xla", "mla_rope=xla", "moe_path=xla"):
        assert word in line
