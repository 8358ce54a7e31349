"""The configuration ``qwen3-next-80b-l4e32`` (Gated DeltaNet layers three to one with output-gated grouped-query
attention at a head size of 256, a softmax-routed FFN of 10 of 512 with a gated shared expert, as one chip's share of
a 16-chip group): its files pass the manifest's checks and hold the catalog row's widths, the program agrees with its
plain float32 reference in logits, loss and the gradient of every leaf, the reference's controls move the result, its
FLOP module's total is a sum a reader can check by hand, its two readers read a synthetic trace's kernels and nothing
else, and its rehearsal says what was traced (``test_benchmark_rehearse.py`` picks the cell up from ``workloads``).
Nothing here pins an entry's place in ``BENCHMARK.json``: a later cell is appended after this one."""

import json
import os

import pytest

from benchmarks.lib import flops, manifest as mf

MANIFEST = mf.load_manifest()
NAME, CELL = "qwen3-next-80b-l4e32", "qwen3-next-80b-l4e32.pretrain-8k"
CONFIG = mf.load_json(os.path.join(mf.BENCH, "configs", f"{NAME}.json"))
PUBLISHED = mf.published(CONFIG)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_the_configuration_and_its_cell_have_no_problems():
    entry = next(c for c in MANIFEST["configs"] if c["name"] == NAME)
    assert mf.config_problems(CONFIG, entry) == []
    mine = [p for p in mf.problems(MANIFEST) if NAME in p or "gdn_scan" in p or "gated_attention" in p]
    assert mine == []
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert (cell["chips"], cell["traffic"], cell["config"]) == (1, "pretrain-8k", NAME) and "1/16" in cell["why"]
    assert CONFIG["trainer"]["train_micro_batch_size_per_gpu"] == 1 and CONFIG["trainer"]["zero_optimization"]["stage"] == 0
    reported = {m["name"] for g in ("end_to_end", "per_layer") for m in mf.metrics_of(MANIFEST, CELL, g)}
    assert {"train_tokens_per_s", "setup_s", "mfu.train", "gdn_scan_roofline", "gated_attention_roofline"} <= reported
    for name in ("gdn_scan_roofline", "gated_attention_roofline"):
        metric = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
        assert CELL in metric["workloads"] and (metric["unit"], metric["better"], metric["source"], metric["moves"]) == \
            ("%", "higher", "device_trace", "train_tokens_per_s")


@pytest.mark.skipif(not os.path.isfile(CATALOG), reason="the catalog of published configurations is not on this machine")
def test_every_width_is_the_sources():
    source = next(json.loads(line) for line in open(CATALOG) if '"name": "Qwen3-Next-80B-A3B-Instruct"' in line)
    assert CONFIG["source"] == source["source_url"]
    source = source["config"]
    differs = {k for k, v in source.items() if CONFIG.get(k, "missing") != v}
    assert differs == set(CONFIG["reduced"]) == {"num_hidden_layers", "num_experts", "vocab_size"}
    held = CONFIG["share"]["held"]
    assert CONFIG["share"]["chips_per_layer"] == 16 and CONFIG["routed_over"] == source["num_experts"] == 512
    assert held["num_experts"] == {"published": 512, "here": 32} and held["vocab_size"] == {"published": 151936, "here": 18992}
    # the floors: four layers and one whole period, at least 8 routed experts, an eighth of the vocabulary
    assert CONFIG["num_hidden_layers"] == 4 == source["full_attention_interval"] and 512 // 16 == 32 >= 8 and 151936 // 8 == 18992
    p = CONFIG["program"]
    assert p["layer_kinds"] == [["gdn", "routed"]] * 3 + [["full", "routed"]] and p["n_layers"] == 4
    assert (p["d_model"], p["n_heads"], p["n_kv_heads"], p["head_dims"], p["rotary_pct"], p["rope_theta"], p["norm_eps"]) == \
        (source["hidden_size"], source["num_attention_heads"], source["num_key_value_heads"], source["head_dim"],
         source["partial_rotary_factor"], source["rope_theta"], source["rms_norm_eps"]) == (2048, 16, 2, 256, 0.25, 1e7, 1e-6)
    assert (p["gdn_key_heads"], p["gdn_value_heads"], p["gdn_head_dim"], p["gdn_conv_size"]) == \
        (source["linear_num_key_heads"], source["linear_num_value_heads"], source["linear_key_head_dim"], source["linear_conv_kernel_dim"])
    assert source["linear_key_head_dim"] == source["linear_value_head_dim"] == 128
    assert (p["moe_num_experts"], p["moe_top_k"], p["moe_d_ff"], p["moe_shared_d_ff"]) == \
        (source["num_experts"], source["num_experts_per_tok"], source["moe_intermediate_size"], source["shared_expert_intermediate_size"])
    assert (p["moe_scoring"], p["moe_shared_gate"], p["attn_output_gate"], p["qk_norm"], p["rms_offset"], p["pos_emb"]) == \
        ("softmax", True, True, True, True, "rope") and source["norm_topk_prob"] is True
    assert p["moe_held"] == [0, CONFIG["num_experts"]] and p["vocab_size"] == CONFIG["vocab_size"] and not p["tie_embeddings"]
    assert "d_ff" not in p and source["mlp_only_layers"] == [] and source["decoder_sparse_step"] == 1  # no layer uses intermediate_size
    assert CONFIG["first_k_dense_replace"] == 0 and "first_k_dense_replace" not in source  # the accepted reader's key, explained beside it
    for key in ("layer_order", "A_log", "auxiliary_loss", "multi_token_prediction", "optimizer", "held", "weights"):
        assert key in CONFIG["assumed"]
    for word in ("16", "expert parallel", "8 ways", "pipeline", "absent"):
        assert word in CONFIG["deployment"]


def test_the_parameter_count_is_the_issues_sum():
    """625.7 M parameters, by the shapes of the program's own tree: 7.51 GB of float32 master and two moments."""
    import jax
    import numpy as np

    from benchmarks.lib import weights

    shapes = weights.param_shapes(weights.build_model(CONFIG))
    count = lambda tree: sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))
    assert count(shapes["layer_0"]["gdn"]) == 2048 * 12288 + 2048 * 64 + 4 * 8192 + 4096 * 2048 + 32 + 32 + 128
    assert count(shapes["layer_3"]["attn"]) == 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048 + 2 * 256
    routed = shapes["layer_0"]["routed"]
    assert count({k: v for k, v in routed.items() if k.startswith("experts_")}) == 32 * 3 * 2048 * 512
    assert count({k: v for k, v in routed.items() if not k.startswith("experts_")}) == 2048 * 512 + 3 * 2048 * 512 + 2048
    assert 625.6e6 < count(shapes) < 625.9e6 and 7.50e9 < 12 * count(shapes) < 7.52e9


def test_the_flop_count_is_the_hand_written_sum():
    mod = flops.for_config(CONFIG)
    m = dict(PUBLISHED, hidden_size=64, moe_intermediate_size=32, shared_expert_intermediate_size=48, vocab_size=509, num_experts=4, routed_over=16,
             num_experts_per_tok=3, num_attention_heads=4, num_key_value_heads=1, head_dim=32, linear_num_key_heads=2,
             linear_num_value_heads=4, linear_key_head_dim=16, linear_value_head_dim=16, num_hidden_layers=8)
    S, d = 96, 64
    gdn = 2 * (d * (2 * 32 + 2 * 64) + d * 8 + 64 * d) + 2 * 4 * (2 * 32 + 64) + 7 * 4 * 16 * 16
    attn = 2 * (d * 2 * 128 + 2 * d * 32 + 128 * d) + 3 * 5 * 32 * 0.25 + S * 4 * 64
    expert = 3 * d * 32
    routed = 2 * (d * 16 + 3 * d * 48 + d + (3 * 4 / 16) * expert)
    forward = 6 * gdn + 2 * attn + 8 * routed + 2 * d * 509
    assert mod.train_flops_per_token(m, S) == pytest.approx(3 * forward) and (mod.gdn_layers(m), mod.full_layers(m)) == (6, 2)
    # the published widths: 1.39 GFLOP a token (the issue's "about 1.4"); the one attention layer's square 14% of it
    total, square = mod.train_flops_per_token(PUBLISHED, 8192), 3 * 8192 * 16 * 512
    assert 1.38e9 < total < 1.40e9 and 0.14 < square / total < 0.15 and (mod.gdn_layers(PUBLISHED), mod.full_layers(PUBLISHED)) == (3, 1)
    cost = mod.gdn_cost(PUBLISHED, 8192, backward=False)
    assert cost["flops"] == 7 * 32 * 128 * 128 * 8192 and cost["bytes"] == 8192 * ((2 * 2048 + 2 * 4096) * 2 + 2 * 32 * 4)
    assert mod.gdn_cost(PUBLISHED, 8192, backward=True)["flops"] == 2 * cost["flops"]
    rows = 8192 * 10 * 32 / 512  # what a uniform router sends here: 5,120 pairs, 160 an expert
    assert rows == 5120 and mod.expert_matmul_cost(PUBLISHED, rows, backward=False)["flops"] == 6.0 * 2048 * 512 * rows
    assert flops.flash_attention_cost(1, 8192, 16, 2, 256, backward=False)["flops"] == 2.0 * 16 * 8192 * 8192 * 256


def _tiny():
    """The rehearsal's width, all four layers, float32."""
    from benchmarks.lib import weights

    r = CONFIG["rehearse"]
    cfg = dict(CONFIG, **r["published"], reference=r["reference"])
    cfg["program"] = dict(CONFIG["program"], **r["program"], dtype="float32")
    return cfg, weights.build_model(cfg)


@pytest.fixture(scope="module")
def tiny_model():
    import jax
    import numpy as np

    cfg, model = _tiny()
    ids = np.random.default_rng(0).integers(0, 509, (2, 40)).astype(np.int32)
    params = model.init(jax.random.PRNGKey(0), {"input_ids": ids})
    # the norms' weights start at zero and the DeltaNet output's at one: moved, so that each matters to the comparison
    leaves, tree = jax.tree_util.tree_flatten(params)
    stirred = [x + 0.05 * jax.random.normal(jax.random.PRNGKey(7 + i), x.shape) for i, x in enumerate(leaves)]
    return cfg, model, jax.tree_util.tree_unflatten(tree, stirred), ids


def _close(a, b, tol):
    import numpy as np

    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert np.max(np.abs(a - b)) <= tol * (1.0 + np.max(np.abs(b))), (np.max(np.abs(a - b)), np.max(np.abs(b)))


def test_the_program_agrees_with_the_plain_reference_in_logits_loss_and_every_gradient(tiny_model):
    """Three DeltaNet layers and one gated attention layer, all routed, seeded weights, float32: the program (the scan
    as the recurrence over value heads, ``Attention`` with its gate, the sorted grouped products) against the reference
    (the state token by token with q and k repeated, the rotation of the leading quarter written out, a loop over the
    held experts)."""
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
    assert all(float(jnp.max(jnp.abs(l))) > 0 for _, l in leaves)  # every leaf takes a gradient: softmax routing has no bias that only chooses


@pytest.mark.parametrize("control,least", [({"no_decay_layer": 2}, 1e-3), ({"no_output_gate": True}, 1e-3), ({"layers_short": 1}, 1e-2)])
def test_the_references_controls_move_the_logits(tiny_model, control, least):
    """Without one DeltaNet layer's decay, without the attention's output gate, and a layer short, the reference is
    another function: the comparisons that use them as controls (``chip_smoke.py --only deltanet``, the cell's
    ``first_loss_tol``) can tell."""
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
    assert 0 < float(jnp.max(jnp.abs(low - plain))) < 1.0
    assert float(jnp.max(jnp.abs(same - logits(params, ids, pub, cfg["reference"], jnp.float32)))) == 0.0


def _record(ops, counters=None, steps=4, config=CONFIG):
    """A traced training record with one device, ``steps`` executions of the step program and these operations."""
    dev = {"ops": ops, "op_counts": {k: 1 for k in ops}, "modules": [("jit_fused_step(123)", 0.1 * i, 0.1 * i + 0.09) for i in range(steps)]}
    return {"reduced": {"devices": {"0": dev}, "window_s": 1.0}, "published": mf.published(config), "config": config,
            "train": {"micro_batch": 1, "seq_len": 8192, "steps": 100}, "device": {"kind": "TPU v5 lite", "count": 1},
            "counters": counters or {}, "end_to_end": {"train_tokens_per_s": 1.0}}


# labels as ``lib/trace.py::op_label`` makes them from a v5e trace of this cell's step (my chip run, PR 39)
GDN_OPS = {'gdn_scan_fwd custom-call (bf16[32,8192,128]{2,1,0:T(8,128)(2,1)}, f32[32,64,128,128]{3,2,1,0:T(8,128)} custom_call_target="tpu_custom_call"': 0.05,
           'gdn_scan_bwd custom-call (bf16[32,8192,128]{2,1,0:T(8,128)(2,1)}, bf16[32,8192,128]{2,1,0:T(8,12 custom_call_target="tpu_custom_call"': 0.07}
FLASH_OPS = {'flash_fwd custom-call (bf16[16,8192,256]{2,1,0:T(8,128)(2,1)}, f32[16,16,1,512]{3,2,1,0:T(1,128)}) custom_call_target="tpu_custom_call"': 0.02,
             'flash_bwd custom-call (bf16[16,8192,256]{2,1,0:T(8,128)(2,1)}, bf16[16,8192,256]{2,1,0:T(8,12 custom_call_target="tpu_custom_call"': 0.04}
OTHER = {"fusion.1 fusion bf16[8192,2048]{1,0}": 0.5,
         'kda_scan_fwd custom-call (bf16[32,8192,128]{2,1,0}, f32[32,64,128,128]{3,2,1,0}) custom_call_target="tpu_custom_call"': 0.3,  # the per-channel form: not this model's
         'flash_fwd custom-call (bf16[16,8192,128]{2,1,0}, f32[16,16,1,512]{3,2,1,0}) custom_call_target="tpu_custom_call"': 0.3,      # heads of 128: not this model's
         'moe_sum_rows custom-call bf16[8192,2048]{1,0} custom_call_target="tpu_custom_call"': 0.1}


@pytest.mark.parametrize("metric,ops", [("gdn_scan_roofline", GDN_OPS), ("gated_attention_roofline", FLASH_OPS)])
def test_a_reader_reads_its_kernels_and_nothing_else(metric, ops):
    mod = mf.metric_module(metric)
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == metric)
    assert (mod.UNIT, mod.BETTER, mod.SOURCE, mod.LAYER, mod.MOVES) == tuple(entry[k] for k in ("unit", "better", "source", "layer", "moves"))
    share = mod.read(_record(dict(ops, **OTHER)))
    assert 0 < share < 100
    assert mod.read(_record(dict({k: 2 * v for k, v in ops.items()}, **OTHER))) == pytest.approx(share / 2)
    assert mod.read(_record(OTHER)) is None                                  # a program without the kernel: the parent commit's
    assert mod.read(dict(_record(dict(ops, **OTHER)), reduced=None)) is None  # an untraced run
    assert mod.read(dict(_record(dict(ops, **OTHER)), config={})) is None      # a configuration with no such layers
    other = mf.load_json(os.path.join(mf.BENCH, "configs", "kimi-linear-48b-l5e8.json"))
    assert mod.read(_record(dict(ops, **OTHER), config=other)) is None         # another configuration's FLOP module: nothing, and no raise


def test_the_readers_count_three_scans_and_one_attention_layer():
    from benchmarks.lib.peaks import peaks_for

    mod, peaks = flops.for_config(CONFIG), peaks_for("TPU v5 lite")
    scan = sum(flops.roofline_seconds(mod.gdn_cost(PUBLISHED, 8192, backward=b), peaks)["seconds"] for b in (False, True))
    assert mf.metric_module("gdn_scan_roofline").read(_record(GDN_OPS)) == pytest.approx(100 * 4 * 3 * scan / 0.12)
    attn = sum(flops.roofline_seconds(flops.flash_attention_cost(1, 8192, 16, 2, 256, backward=b), peaks)["seconds"] for b in (False, True))
    assert mf.metric_module("gated_attention_roofline").read(_record(FLASH_OPS)) == pytest.approx(100 * 4 * 1 * attn / 0.06)
    assert flops.roofline_seconds(mod.gdn_cost(PUBLISHED, 8192, backward=False), peaks)["bound"] == "memory"
    assert flops.roofline_seconds(flops.flash_attention_cost(1, 8192, 16, 2, 256, backward=True), peaks)["bound"] == "compute"


def test_the_rehearsal_says_what_was_traced_and_drops_no_row():
    """A process of its own, as the driver starts one: the package's log line goes to that process's stdout."""
    import subprocess
    import sys

    out = subprocess.run([sys.executable, os.path.join(mf.ROOT, "benchmarks", "run.py"), "--workload", CELL, "--rehearse",
                          "--seed", str(2**31 + 11), "--seconds", "1"], capture_output=True, text=True, timeout=900,
                         cwd=mf.ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    last, counters = json.loads(lines[-1]), json.loads(lines[-2])["extras"]["counters"]
    assert last["correct"] is True
    assert counters["moe_rows_routed_here_total"] > 0 and counters["moe_rows_dropped_total"] == 0
    line = next(l for l in lines if "program first call: family=train" in l)
    for word in ("block_traces=2", "layer_kinds=full+routed:1,gdn+routed:3", "gdn_path=xla", "moe_path=xla", "moe_router=softmax"):
        assert word in line
