"""The configuration ``smallthinker-21b-l4e8`` (SmallThinker-21BA3B-Instruct's layers 0-3 as one chip's share of 8: one
full layer without positions to three rotated window layers of 4,096, GQA 28/4, a routed ReGLU FFN whose router reads the
attention's input) and its cell ``smallthinker-21b-l4e8.pretrain-16k``: the files pass the manifest's checks and hold the
catalog row's widths with both layouts whole, ``reduced`` and ``share`` agree, the program's tree has the parameters the
issue counted, the FLOP module's total is a sum a reader can check by hand, the program agrees with its plain float32
reference at the rehearsal's width, the reader reads its kernels and nothing else on a recorded trace's labels, and the
rehearsal ends ``correct`` true, and false under a control. Nothing here pins an entry's place in ``BENCHMARK.json`` or
counts its cells: a later cell is appended after this one."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmarks.lib import flops, manifest as mf

MANIFEST = mf.load_manifest()
NAME, CELL = "smallthinker-21b-l4e8", "smallthinker-21b-l4e8.pretrain-16k"
CONFIG = mf.load_json(os.path.join(mf.BENCH, "configs", f"{NAME}.json"))
TRAFFIC = mf.load_json(os.path.join(mf.BENCH, "traffic", "pretrain-16k.json"))
PUBLISHED = mf.published(CONFIG)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
READER = "mixed_attention_roofline"
REDUCED = ["num_hidden_layers", "moe_num_primary_experts", "vocab_size"]


def test_the_configuration_and_its_cell_have_no_problems():
    entry = next(c for c in MANIFEST["configs"] if c["name"] == NAME)
    assert mf.config_problems(CONFIG, entry) == [] and entry["reduced"] == REDUCED == CONFIG["reduced"]
    assert [p for p in mf.problems(MANIFEST) if NAME in p or READER in p or "pretrain-16k" in p] == []  # ``manifest.problems`` has nothing new
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert (cell["chips"], cell["traffic"], cell["config"]) == (1, "pretrain-16k", NAME) and len(cell["why"]) <= 200
    for word in ("16384", "3,584", "8,192", "47%", "1,536 rows an expert", "1/8", "attention weighs more"):  # the shapes, and what weighs more
        assert word in cell["why"]
    assert CONFIG["trainer"]["train_micro_batch_size_per_gpu"] == 1 and CONFIG["trainer"]["zero_optimization"]["stage"] == 0
    assert CONFIG["trainer"]["mesh"] == {"data": 1} and CONFIG["trainer"]["bf16"] == {"enabled": True}
    assert CONFIG["trainer"]["optimizer"]["type"] == "adam" and CONFIG["program"]["remat"] is True and CONFIG["env"] == {}
    reported = {m["name"] for g in ("end_to_end", "per_layer") for m in mf.metrics_of(MANIFEST, CELL, g)}
    assert {"train_tokens_per_s", "setup_s", "mfu.train", "moe_expert_matmul_roofline", READER} <= reported  # at least what its PR brought: a later reader may list the cell
    assert TRAFFIC["generator"] == "fixed_batches" and TRAFFIC["params"] == {"seq_len": 16384, "n_batches": 8}
    assert TRAFFIC["rehearse"] == {"params": {"seq_len": 96, "n_batches": 2}} and CONFIG["rehearse"]["program"]["sliding_window"] == 16
    assert "first_loss_tol" in CONFIG["correct_why"] and 0 < CONFIG["correct"]["first_loss_tol"] <= 0.05


def test_the_new_metric_is_this_cells_alone():
    metric = next(m for m in MANIFEST["per_layer"] if m["name"] == READER)
    assert CELL in metric["workloads"] and (metric["unit"], metric["better"], metric["source"], metric["moves"]) == \
        ("%", "higher", "device_trace", "train_tokens_per_s")
    mod = mf.metric_module(READER)
    assert (mod.UNIT, mod.BETTER, mod.SOURCE, mod.LAYER, mod.MOVES) == tuple(metric[k] for k in ("unit", "better", "source", "layer", "moves"))
    assert metric["layer"] in {m["layer"] for m in MANIFEST["per_layer"] if m["name"] != READER}  # a layer BENCHMARK.json already names
    for shared in ("train_tokens_per_s", "mfu.train", "moe_expert_matmul_roofline"):  # appended to, nothing else changed
        listed = next(m for g in ("end_to_end", "per_layer") for m in MANIFEST[g] if m["name"] == shared)["workloads"]
        assert CELL in listed and "sdar-30b-a3b-l4e16.blockdiff-8k" in listed and listed.index(CELL) > listed.index("sdar-30b-a3b-l4e16.blockdiff-8k")


@pytest.mark.parametrize("case,needle", [
    ("as_it_is", None),
    ("a_width_reduced", "reduced names a width"),
    ("the_window_reduced", "reduced names a width"),
    ("a_layout_reduced", "reduced names a width"),
    ("a_held_count_not_reduced", "which reduced does not list"),
    ("the_share_disagrees", "are held here, the file says"),
    ("the_entry_disagrees", "reduced differs between BENCHMARK.json and its file"),
])
def test_reduced_and_share_agree_and_the_checks_find_what_does_not(case, needle):
    cfg = json.loads(json.dumps(CONFIG))
    entry = dict(next(c for c in MANIFEST["configs"] if c["name"] == NAME))
    if case == "a_width_reduced":
        cfg["reduced"] = entry["reduced"] = CONFIG["reduced"] + ["moe_ffn_hidden_size"]
    elif case == "the_window_reduced":
        cfg["reduced"] = entry["reduced"] = CONFIG["reduced"] + ["sliding_window_size"]
    elif case == "a_layout_reduced":  # a layout is no count: ``lib/manifest.py::WIDTH`` refuses any key with ``window`` in its name
        cfg["reduced"] = entry["reduced"] = CONFIG["reduced"] + ["sliding_window_layout"]
    elif case == "a_held_count_not_reduced":
        cfg["reduced"] = entry["reduced"] = [k for k in CONFIG["reduced"] if k != "moe_num_primary_experts"]
    elif case == "the_share_disagrees":
        cfg["share"]["held"]["moe_num_primary_experts"]["here"] = 16
    elif case == "the_entry_disagrees":
        entry["reduced"] = CONFIG["reduced"][:-1]
    found = mf.config_problems(cfg, entry)
    assert (found == []) == (needle is None) and (needle is None or any(needle in p for p in found))


@pytest.mark.skipif(not os.path.isfile(CATALOG), reason="the catalog of published configurations is not on this machine")
def test_every_width_is_the_sources_and_both_layouts_are_whole():
    row = next(json.loads(line) for line in open(CATALOG) if '"name": "SmallThinker-21BA3B-Instruct"' in line)
    assert CONFIG["source"] == row["source_url"] and row["not_given"] == ["dense feed-forward width"]
    source = row["config"]
    differs = {k for k, v in source.items() if CONFIG.get(k, "missing") != v}
    assert differs == set(CONFIG["reduced"]) == set(REDUCED)
    assert CONFIG["rope_layout"] == CONFIG["sliding_window_layout"] == source["rope_layout"] == [0, 1, 1, 1] * 13  # 52 entries each, as published
    assert CONFIG["published_layers"] == source["num_hidden_layers"] == 52 and CONFIG["layers_here"] == [0, 1, 2, 3] and CONFIG["num_hidden_layers"] == 4
    assert CONFIG["share"] == {"chips_per_layer": 8, "held": {"moe_num_primary_experts": {"published": source["moe_num_primary_experts"], "here": 8},
                                                             "vocab_size": {"published": source["vocab_size"], "here": 18992}}}
    assert source["moe_num_primary_experts"] // 8 == 8 == CONFIG["moe_num_primary_experts"] and source["vocab_size"] // 8 == 18992 == CONFIG["vocab_size"]
    assert CONFIG["routed_over"] == source["moe_num_primary_experts"] == 64 and CONFIG["first_k_dense_replace"] == 0
    p = CONFIG["program"]
    assert (p["d_model"], p["n_heads"], p["n_kv_heads"], p["head_dims"], p["moe_d_ff"], p["moe_top_k"], p["moe_num_experts"], p["norm_eps"], p["rope_theta"],
            p["sliding_window"], p["max_seq_len"]) == \
        (source["hidden_size"], source["num_attention_heads"], source["num_key_value_heads"], source["head_dim"], source["moe_ffn_hidden_size"],
         source["moe_num_active_primary_experts"], source["moe_num_primary_experts"], source["rms_norm_eps"], source["rope_theta"],
         source["sliding_window_size"], source["max_position_embeddings"]) == (2560, 28, 4, 128, 768, 6, 64, 1e-6, 1.5e6, 4096, 16384)
    assert p["tie_embeddings"] is source["tie_word_embeddings"] is False and source["norm_topk_prob"] is True and source["moe_primary_router_apply_softmax"] is True
    assert p["moe_scoring"] == "softmax" and p["moe_route_scale"] == 1.0 and p["moe_shared_d_ff"] == 0 and p["moe_held"] == [0, 8] and p["moe_aux_loss_coef"] == 0.0
    assert p["activation"] == "reglu" and p["pos_emb"] == "rope" and p["norm"] == "rmsnorm" and p["vocab_size"] == CONFIG["vocab_size"]
    # a layer's kind by the two layouts at its published index: the program's, the FLOP module's and the reference's readings agree
    assert p["layer_kinds"] == [["window" if source["sliding_window_layout"][i] else "nope", "routed_early"] for i in CONFIG["layers_here"]]
    assert flops.for_config(CONFIG).kinds(PUBLISHED) == [mixer for mixer, _ in p["layer_kinds"]] == ["nope", "window", "window", "window"]
    assert p["max_seq_len"] == TRAFFIC["params"]["seq_len"]
    for key in ("router_input", "secondary_experts", "experts", "attention", "rotation", "layouts", "auxiliary_loss", "norms", "optimizer", "weights", "start", "held"):
        assert key in CONFIG["assumed"], key
    for word in ("13 pipeline stages", "8-chip", "104", "8 ways", "absent", "1,536 rows", "370,547,200"):
        assert word in CONFIG["deployment"]


def test_the_parameter_count_is_the_issues_sum():
    """370,547,200 parameters by the shapes of the program's own tree: 4.45 GB of float32 master and two moments."""
    import jax

    from benchmarks.lib import weights

    shapes = weights.param_shapes(weights.build_model(CONFIG))
    count = lambda tree: sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))
    d = 2560
    attention = d * 3584 + 2 * d * 512 + 3584 * d  # q and o are 2,560 x 3,584: the heads' width is not the model's
    assert count(shapes["layer_0"]["attn"]) == attention == 20_971_520
    assert count(shapes["layer_0"]["routed"]) == d * 64 + 8 * 3 * d * 768 == 163_840 + 47_185_920
    assert all(count(shapes[f"layer_{i}"]) == 21_140_480 + 47_185_920 == 68_326_400 for i in range(4))
    assert count(shapes["wte"]) == count(shapes["lm_head"]) == 18992 * d == 48_619_520
    assert count(shapes) == 4 * 68_326_400 + 2 * 48_619_520 + d == 370_547_200 and 4.44e9 < 12 * count(shapes) < 4.45e9


def test_the_flop_count_is_the_hand_written_sum():
    mod = flops.for_config(CONFIG)
    S, d, W = 16384, 2560, 4096
    assert mod.visible_pairs(S) == S * (S + 1) / 2 and mod.visible_pairs(S, W) == W * (W + 1) / 2 + (S - W) * W
    assert mod.visible_pairs(S) / S == 8192.5 and mod.visible_pairs(S, W) / S == pytest.approx(3584.1, abs=0.05)
    proj = 2 * (d * 3584 + 2 * d * 512 + 3584 * d)
    router, experts = 2 * d * 64, 0.75 * 2 * 3 * d * 768  # 6 x 8 / 64 expert evaluations a token, here
    full, window = 4 * 28 * 128 * 8192.5, 4 * 28 * 128 * mod.visible_pairs(S, W) / S
    assert (proj, router, experts) == (41_943_040, 327_680, 8_847_360) and full == pytest.approx(117.45e6, rel=1e-4) and window == pytest.approx(51.38e6, rel=1e-4)
    forward = 4 * (proj + router + experts) + full + 3 * window + 2 * d * 18992
    assert proj + router + experts + full == pytest.approx(168.57e6, rel=1e-4) and proj + router + experts + window == pytest.approx(102.50e6, rel=1e-4)
    assert mod.forward_flops_per_token(PUBLISHED, S) == pytest.approx(forward) and forward == pytest.approx(573.3e6, rel=1e-4)
    assert mod.train_flops_per_token(PUBLISHED, S) == pytest.approx(3 * forward) and 3 * forward * S == pytest.approx(28.2e12, rel=2e-3)
    assert (full + 3 * window) / forward == pytest.approx(0.47, abs=0.005)  # the kept pairs: 47% of the required work
    at_8k = 4 * 28 * 128 * (mod.visible_pairs(8192) + 3 * mod.visible_pairs(8192, W)) / 8192
    assert mod.visible_pairs(8192, W) / mod.visible_pairs(8192) == pytest.approx(0.75, abs=0.001)  # at 8k the window removes a quarter
    assert at_8k / (4 * (proj + router + experts) + at_8k + 2 * d * 18992) == pytest.approx(0.385, abs=0.01)
    for kind, pairs in (("nope", mod.visible_pairs(S)), ("window", mod.visible_pairs(S, W))):
        fwd, bwd = (mod.mixed_attention_cost(PUBLISHED, 1, S, kind, backward=b) for b in (False, True))
        assert fwd["flops"] == 4.0 * 28 * 128 * pairs and bwd["flops"] == 2 * fwd["flops"]
        moved = 2.0 * S * (2 * 3584 + 2 * 512) + 4.0 * S * 28  # q, k, v, o in bf16 and a float32 a head and query
        assert fwd["bytes"] == moved and bwd["bytes"] == 2 * moved + 2.0 * S * 3584
    assert mod.expert_matmul_cost(PUBLISHED, 12288.0, backward=False)["flops"] == 2.0 * 3 * d * 768 * 12288
    assert mod.expert_matmul_cost(PUBLISHED, 12288.0, backward=True)["bytes"] == 2 * 2.0 * (8 * 3 * d * 768 + 12288 * (2 * d + 3 * 768))


def _tiny():
    """The rehearsal's width, all four layers, float32."""
    from benchmarks.lib import weights

    r = CONFIG["rehearse"]
    cfg = dict(CONFIG, **r["published"], reference=r["reference"])
    cfg["program"] = dict(CONFIG["program"], **r["program"], dtype="float32")
    return cfg, weights.build_model(cfg)


def _rows(seed, batch=2, vocab=509):
    gen = mf.load_module(os.path.join(mf.BENCH, "generators", "fixed_batches.py"))
    return gen.generate(TRAFFIC["rehearse"]["params"], seed, 40.0, {"vocab_size": vocab, "global_batch": batch})["batches"][0]["input_ids"]


@pytest.mark.parametrize("control", [None, "router", "windows", "rotation", "gate"])
def test_the_program_agrees_with_the_plain_reference_in_logits_loss_and_every_gradient(control):
    """Four layers at the rehearsal's width on the rehearsal's traffic (rows of 96 under a window of 16), seeded weights
    with every leaf stirred, float32 at the highest matmul precision on both sides: 2e-5 of the largest logit and 5e-5 of
    a leaf's largest gradient entry (the order of float32 sums). Through the harness's own pair, ``reference.for_config``.
    Under a control (the router on the experts' own input, window layers that see every key, every layer rotated, silu for
    relu) the same comparison FAILS: the logits lie 1e-2 and more away."""
    import jax
    import jax.numpy as jnp

    from benchmarks.lib import reference

    cfg, model = _tiny()
    ids = _rows(5)
    params = model.init(jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 16), np.int32)})
    leaves, tree = jax.tree_util.tree_flatten(params)
    params = jax.tree_util.tree_unflatten(tree, [x + 0.05 * jax.random.normal(jax.random.PRNGKey(7 + i), x.shape) for i, x in enumerate(leaves)])
    ref_logits, ref_loss = reference.for_config(cfg)
    pub = mf.published(cfg)
    wrong = {"router": {"router": "late"}, "windows": {"windows": "none"}, "rotation": {"rotation": "all"}, "gate": {"gate": "silu"}}.get(control, {})
    ref_cfg = dict(cfg["reference"], **wrong)
    gap = lambda a, b: np.max(np.abs(np.asarray(a, np.float64) - np.asarray(b, np.float64))) / (1.0 + np.max(np.abs(np.asarray(b, np.float64))))
    with jax.default_matmul_precision("highest"):
        theirs_logits = ref_logits(params, ids, pub, ref_cfg, jnp.float32)
        assert theirs_logits.shape == (2, 96, 509)
        ours_logits = model.apply(params, ids)
        if control is not None:
            assert gap(ours_logits, theirs_logits) > 1e-2
            return
        assert gap(ours_logits, theirs_logits) < 2e-5
        ours, g_ours = jax.value_and_grad(lambda p: model.loss_fn(p, {"input_ids": ids}))(params)
        theirs, g_theirs = jax.value_and_grad(lambda p: ref_loss(ref_logits(p, ids, pub, ref_cfg, jnp.float32), ids))(params)
    assert gap(ours, theirs) < 1e-6
    theirs_by_path = dict(jax.tree_util.tree_leaves_with_path(g_theirs))
    mine = jax.tree_util.tree_leaves_with_path(g_ours)
    assert len(mine) == len(theirs_by_path) == 3 + 4 * 10
    for path, leaf in mine:
        assert gap(leaf, theirs_by_path[path]) < 5e-5, jax.tree_util.keystr(path)
        assert float(jnp.max(jnp.abs(leaf))) > 0


def _record(ops, steps=4, config=CONFIG):
    """A traced training record with one device, ``steps`` executions of the step program and these operations."""
    dev = {"ops": ops, "op_counts": {k: 1 for k in ops}, "modules": [("jit_fused_step(123)", 0.1 * i, 0.1 * i + 0.09) for i in range(steps)]}
    return {"reduced": {"devices": {"0": dev}, "window_s": 1.0}, "published": mf.published(config), "config": config,
            "train": {"micro_batch": 1, "seq_len": 16384, "steps": 100}, "device": {"kind": "TPU v5 lite", "count": 1},
            "counters": {}, "end_to_end": {"train_tokens_per_s": 1.0}}


# labels as ``lib/trace.py::op_label`` makes them from a v5e trace of this cell's step, and their seconds over four steps
# (my chip run, PR 53)
ATTENTION_OPS = {'flash_bwd custom-call (bf16[28,16384,128]{2,1,0:T(8,128)(2,1)}, bf16[28,16384,128]{2,1,0:T(8 custom_call_target="tpu_cus': 0.300640395,
                 'flash_fwd custom-call (bf16[28,16384,128]{2,1,0:T(8,128)(2,1)}, f32[28,32,1,512]{3,2,1,0:T(1 custom_call_target="tpu_cus': 0.156491192}
OTHER = {'gmm custom-call bf16[49152,2560]{1,0:T(8,128)(2,1)} custom_call_target="tpu_custom_call"': 0.030762149,
         'tgmm custom-call bf16[8,2560,768]{2,1,0:T(8,128)(2,1)} custom_call_target="tpu_custom_call"': 0.03,
         'blockdiff_fwd custom-call (bf16[32,16384,128]{2,1,0:T(8,128)(2,1)}, f32[32,32,1,512]{3,2,1,0:T(1 custom_call_target="tpu': 0.2,  # another mask's
         'sparse_fwd custom-call bf16[32,8192,128]{2,1,0} custom_call_target="tpu_custom_call"': 0.1,
         "flash_copy_fusion fusion bf16[28,16384,128]{2,1,0:T(8,128)(2,1)}": 0.05}  # XLA's copies of the KV heads: no custom call


def test_the_reader_reads_its_kernels_and_nothing_else():
    from benchmarks.lib.peaks import peaks_for

    mod = mf.metric_module(READER)
    share = mod.read(_record(dict(ATTENTION_OPS, **OTHER)))
    assert share == pytest.approx(59.29, abs=0.01)  # the chip run's own reading of these seconds (59.294)
    assert mod.read(_record(dict({k: 2 * v for k, v in ATTENTION_OPS.items()}, **OTHER))) == pytest.approx(share / 2)
    assert mod.read(_record(OTHER)) is None                                            # a program without the kernels
    assert mod.read(dict(_record(dict(ATTENTION_OPS, **OTHER)), reduced=None)) is None  # an untraced run
    assert mod.read(dict(_record(dict(ATTENTION_OPS, **OTHER)), config={})) is None      # a configuration with no such layers
    for other in ("keye-vl2-30b-l4e16", "phi4-mini-flash-l6", "olmo-1b"):  # another configuration's FLOP module: nothing, and no raise
        assert mod.read(_record(dict(ATTENTION_OPS, **OTHER), config=mf.load_json(os.path.join(mf.BENCH, "configs", f"{other}.json")))) is None
    counts, peaks = flops.for_config(CONFIG), peaks_for("TPU v5 lite")
    need = [flops.roofline_seconds(counts.mixed_attention_cost(PUBLISHED, 1, 16384, kind, backward=b), peaks)
            for kind in ("nope", "window", "window", "window") for b in (False, True)]
    assert {n["bound"] for n in need} == {"compute"}
    assert share == pytest.approx(100 * 4 * sum(n["seconds"] for n in need) / sum(ATTENTION_OPS.values()))
    # Phi-4's reader matches the same kernels' names and finds no cost of its own in this configuration's FLOP module
    assert mf.metric_module("diff_attention_roofline").read(_record(dict(ATTENTION_OPS, **OTHER))) is None
    assert mf.metric_module("blockdiff_attention_roofline").read(_record(ATTENTION_OPS)) is None


def _rehearse(root, seed):
    out = subprocess.run([sys.executable, os.path.join(root, "benchmarks", "run.py"), "--workload", CELL, "--rehearse", "--seed", str(seed),
                          "--seconds", "1"], capture_output=True, text=True, timeout=900, cwd=root, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-3000:]
    return out


def test_the_rehearsal_ends_correct_and_says_what_was_traced():
    """A process of its own, as the driver starts one: the package's log line goes to that process's stdout."""
    out = _rehearse(mf.ROOT, 2**31 + 42)
    lines = out.stdout.strip().splitlines()
    last, counters = json.loads(lines[-1]), json.loads(lines[-2])["extras"]["counters"]
    assert last["correct"] is True and "first_loss_vs_f32" in out.stderr  # the f32 rule is the rehearsal's
    for series in ('{op="nope",pass="fwd",path="xla",region="mixer/kernel"}', '{op="window",pass="fwd",path="xla",region="mixer/kernel",window="16"}',
                   '{input="mixer_input",path="softmax",region="ffn/router"}', '{act="relu",path="xla",region="ffn/experts"}',
                   '{op="qk",path="xla",region="mixer/rope"}'):
        assert "program_regions_traced_total" + series in counters, series
    assert counters["moe_rows_dropped_total"] == 0 and counters["moe_fallback_layers_total"] == 0
    steps = counters["train_steps_total"]
    assert steps > 0 and 0.5 < counters["moe_rows_routed_here_total"] / (steps * 4 * 96) < 2.0  # 96 x 4 x 4 / 16 = 96 uniform pairs a layer
    line = next(l for l in lines if "program first call: family=train" in l)
    for word in ("block_traces=2", "layer_kinds=nope+routed_early:1,window+routed_early:3", "nope_path=xla", "window_path=xla", "window_keys=16",
                 "moe_router=softmax+compare_sum", "moe_router_input=mixer_input", "moe_activation=relu", "rope=xla",
                 "remat_keeps=flash_attention+projection+routed_ffn"):
        assert word in line, word


def test_the_rehearsal_ends_false_under_a_control(tmp_path):
    """The same run against a reference with one thing wrong (no final norm: the cell's own control): ``correct`` false
    by ``first_loss_diff``. (A late router or a wrong mask moves a mean loss over 95 random targets by its sampling noise:
    the comparison above, in logits and gradients, is where those fail.) The reference's control is switched on through a
    copy of the checkout's benchmark files, so no file of the benchmark is touched."""
    import shutil

    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(mf.ROOT, "benchmarks"), root / "benchmarks", ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(mf.ROOT, "BENCHMARK.json"), root / "BENCHMARK.json")
    os.symlink(os.path.join(mf.ROOT, "deepspeed_tpu"), root / "deepspeed_tpu")
    path = root / "benchmarks" / "configs" / f"{NAME}.json"
    cfg = json.loads(path.read_text())
    cfg["rehearse"]["reference"]["no_final_norm"] = True
    path.write_text(json.dumps(cfg))
    out = _rehearse(str(root), 2**31 + 42)
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["correct"] is False and "first_loss_diff" in out.stderr
    diff = float(next(l for l in out.stderr.splitlines() if l.startswith("correct: first_loss_diff")).split()[2])
    assert diff > 0.05
