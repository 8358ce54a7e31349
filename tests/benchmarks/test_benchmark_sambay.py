"""The configuration ``phi4-mini-flash-l6`` (Phi-4-mini-flash-reasoning's decoder-hybrid-decoder stack as six layers of
one chip: Mamba-1 scans beside differential attention under a window and full, a gated memory unit and differential
cross-attention that read what the first half made): its files pass the manifest's checks and hold the catalog row's
widths, the program's tree has the parameters the issue counted, the program agrees with its plain float32 reference at
the rehearsal's width, the FLOP module's total is a sum a reader can check by hand, its two readers read a made-up
trace's kernels and nothing else, and its rehearsal says what was traced. Nothing here pins an entry's place in
``BENCHMARK.json``: a later cell is appended after this one."""

import json
import os

import pytest

from benchmarks.lib import flops, manifest as mf

MANIFEST = mf.load_manifest()
NAME, CELL = "phi4-mini-flash-l6", "phi4-mini-flash-l6.pretrain-8k"
CONFIG = mf.load_json(os.path.join(mf.BENCH, "configs", f"{NAME}.json"))
PUBLISHED = mf.published(CONFIG)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
READERS = ("ssm_scan_roofline", "diff_attention_roofline")


def test_the_configuration_and_its_cell_have_no_problems():
    entry = next(c for c in MANIFEST["configs"] if c["name"] == NAME)
    assert mf.config_problems(CONFIG, entry) == []
    assert [p for p in mf.problems(MANIFEST) if NAME in p or "ssm_scan" in p or "diff_attention" in p] == []
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert (cell["chips"], cell["traffic"], cell["config"]) == (1, "pretrain-8k", NAME) and len(cell["why"]) <= 200
    for word in ("2 of 6", "8 of 32", "9 of 32", "32k", "segment ids"):  # what weighs more here, and what the cell cannot show
        assert word in cell["why"]
    assert CONFIG["trainer"]["train_micro_batch_size_per_gpu"] == 1 and CONFIG["trainer"]["zero_optimization"]["stage"] == 0
    assert CONFIG["trainer"]["optimizer"] == {"type": "adam", "params": {"lr": 1e-4}} and CONFIG["program"]["remat"] is True
    reported = {m["name"] for g in ("end_to_end", "per_layer") for m in mf.metrics_of(MANIFEST, CELL, g)}
    assert {"train_tokens_per_s", "setup_s", "mfu.train", *READERS} <= reported  # at least what its PR brought: a later reader may list the cell


@pytest.mark.parametrize("name", READERS)
def test_a_new_metric_is_this_cells_alone(name):
    metric = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
    assert CELL in metric["workloads"] and (metric["unit"], metric["better"], metric["source"], metric["moves"]) == \
        ("%", "higher", "device_trace", "train_tokens_per_s")
    mod = mf.metric_module(name)
    assert (mod.UNIT, mod.BETTER, mod.SOURCE, mod.LAYER, mod.MOVES) == tuple(metric[k] for k in ("unit", "better", "source", "layer", "moves"))


def _one_cell_base():
    """The first cell alone, as ``test_benchmark_hybrid.py::_one_cell_base`` builds it: cases on it count no cells of
    ``BENCHMARK.json`` as it stands."""
    m = json.loads(json.dumps(MANIFEST))
    first = m["workloads"][0]["name"]
    m["workloads"], m["configs"] = m["workloads"][:1], m["configs"][:1]
    for group in ("end_to_end", "per_layer"):
        m[group] = [dict(x, workloads=[first]) if "workloads" in x else x for x in m[group] if x.get("workloads", [first])[0] == first]
    return m


@pytest.mark.parametrize("case,needle", [
    ("as_it_is", None),
    ("with_this_cell", None),
    ("a_width_reduced", "reduced names a width"),
    ("the_window_reduced", "reduced names a width"),
    ("a_held_count_not_reduced", "which reduced does not list"),
    ("the_entry_disagrees", "reduced differs between BENCHMARK.json and its file"),
])
def test_the_manifests_checks_on_a_one_cell_base(case, needle):
    m = _one_cell_base()
    if case != "as_it_is":
        cfg = json.loads(json.dumps(CONFIG))
        entry = dict(next(c for c in MANIFEST["configs"] if c["name"] == NAME))
        if case == "a_width_reduced":
            cfg["reduced"] = entry["reduced"] = CONFIG["reduced"] + ["intermediate_size"]
        elif case == "the_window_reduced":
            cfg["reduced"] = entry["reduced"] = CONFIG["reduced"] + ["sliding_window"]
        elif case == "a_held_count_not_reduced":
            cfg["reduced"] = entry["reduced"] = [k for k in CONFIG["reduced"] if k != "vocab_size"]
        elif case == "the_entry_disagrees":
            entry["reduced"] = CONFIG["reduced"][:-1]
        assert (mf.config_problems(cfg, entry) == []) == (needle is None)
        assert needle is None or any(needle in p for p in mf.config_problems(cfg, entry))
        m["configs"].append(entry)
        m["workloads"].append(next(w for w in MANIFEST["workloads"] if w["name"] == CELL))
        for group in ("end_to_end", "per_layer"):
            m[group] += [dict(x, workloads=[CELL]) for x in MANIFEST[group] if CELL in x.get("workloads", []) and x["name"] not in {y["name"] for y in m[group]}]
            for x in m[group]:
                if "workloads" in x and x["name"] in ("train_tokens_per_s", "mfu.train") and CELL not in x["workloads"]:
                    x["workloads"].append(CELL)
    found = mf.problems(m)  # against the files on disk, which are sound: an entry that was changed above differs from its file, no more
    assert [p for p in found if "reduced differs" not in p] == []
    assert bool(found) == (case in ("a_width_reduced", "the_window_reduced", "a_held_count_not_reduced", "the_entry_disagrees"))


@pytest.mark.skipif(not os.path.isfile(CATALOG), reason="the catalog of published configurations is not on this machine")
def test_every_width_is_the_sources():
    source = next(json.loads(line) for line in open(CATALOG) if '"name": "Phi-4-mini-flash-reasoning"' in line)
    assert CONFIG["source"] == source["source_url"]
    source = source["config"]
    differs = {k for k, v in source.items() if CONFIG.get(k, "missing") != v}
    assert differs == set(CONFIG["reduced"]) == {"num_hidden_layers", "vocab_size"}
    assert CONFIG["share"] == {"chips_per_layer": 8, "held": {"vocab_size": {"published": source["vocab_size"], "here": 25008}}}
    assert source["vocab_size"] // 8 == 25008 == CONFIG["vocab_size"] and CONFIG["num_hidden_layers"] == 6  # the floors: an eighth, a period of each half and the two between
    assert CONFIG["published_layers"] == source["num_hidden_layers"] == 32 and CONFIG["layers_here"] == [0, 1, 16, 17, 18, 19]
    p = CONFIG["program"]
    assert (p["d_model"], p["n_heads"], p["n_kv_heads"], p["head_dims"], p["d_ff"], p["sliding_window"], p["norm_eps"]) == \
        (source["hidden_size"], source["num_attention_heads"], source["num_key_value_heads"], source["hidden_size"] // source["num_attention_heads"],
         source["intermediate_size"], source["sliding_window"], source["layer_norm_eps"]) == (2560, 40, 20, 64, 10240, 512, 1e-5)
    assert p["tie_embeddings"] is source["tie_word_embeddings"] is True and p["dense_bias"] is source["mlp_bias"] is False and not source["lm_head_bias"]
    assert p["norm"] == "layernorm" and p["activation"] == "swiglu" and source["hidden_act"] == "silu" and p["pos_emb"] == "none"
    assert p["vocab_size"] == CONFIG["vocab_size"] and p["n_layers"] == 6 and p["layer_numbers"] == CONFIG["layers_here"]
    mamba = CONFIG["mamba"]
    assert (p["ssm_inner"], p["ssm_state"], p["ssm_conv"], p["ssm_dt_rank"]) == (mamba["d_inner"], mamba["d_state"], mamba["d_conv"], mamba["dt_rank"]) == \
        (2 * source["hidden_size"], 16, 4, source["hidden_size"] // 16) and source["mb_per_layer"] == 2
    mod = flops.for_config(CONFIG)
    assert [[kind, "dense"] for kind in mod.kinds(PUBLISHED)] == p["layer_kinds"]  # the layers' kinds follow from their published indices
    whole = [mod.kind_of(i, 32) for i in range(32)]
    assert (whole.count("ssm"), whole.count("diff_window"), whole.count("diff"), whole.count("gmu"), whole.count("diff_cross")) == (9, 8, 1, 7, 7)
    for key in ("mamba", "delta", "memory", "head_pairing", "biases", "lambdas", "softmax_scale", "norms", "dropout", "optimizer", "weights", "held"):
        assert key in CONFIG["assumed"]
    for word in ("8 v5e chips", "8 ways", "pipeline", "absent", "0, 1, 16, 17, 18, 19"):
        assert word in CONFIG["deployment"]


def test_the_parameter_count_is_the_issues_sum():
    """697.07 M parameters by the shapes of the program's own tree (the issue's 696.9 M summed its parts rounded down):
    8.36 GB of float32 master and two moments."""
    import jax
    import numpy as np

    from benchmarks.lib import weights

    shapes = weights.param_shapes(weights.build_model(CONFIG))
    count = lambda tree: sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))
    d, inner, ff = 2560, 5120, 10240
    scan = d * 2 * inner + inner * (160 + 32) + 160 * inner + inner * d + inner * (16 + 1 + 1 + 4 + 1)  # A_log, D, dt_bias, the convolution and its bias
    attention = d * (2560 + 1280 + 1280) + 2560 * d + 4 * 64 + 128
    assert count(shapes["layer_0"]["ssm"]) == count(shapes["layer_2"]["ssm"]) == scan and 41.2e6 < scan < 41.3e6
    assert count(shapes["layer_1"]["diff_window"]) == count(shapes["layer_3"]["diff"]) == attention and 19.6e6 < attention < 19.7e6
    assert count(shapes["layer_4"]["gmu"]) == 2 * d * inner and count(shapes["layer_5"]["diff_cross"]) == 2 * d * d + 4 * 64 + 128
    assert all(count(shapes[f"layer_{i}"]["mlp"]) == 3 * d * ff for i in range(6)) and count(shapes["wte"]) == 25008 * d
    layers = sum(count(shapes[f"layer_{i}"]) for i in range(6))
    assert 633.0e6 < layers < 633.1e6 and 64.0e6 < count(shapes["wte"]) < 64.1e6
    assert 697.0e6 < count(shapes) < 697.1e6 and 8.36e9 < 12 * count(shapes) < 8.37e9
    assert "lm_head" not in shapes  # tied


def test_the_flop_count_is_the_hand_written_sum():
    mod = flops.for_config(CONFIG)
    S, W = 8192, 512
    assert mod.visible_pairs(S) == S * (S + 1) / 2 and mod.visible_pairs(S, W) == sum(min(t + 1, W) for t in range(S)) == W * (W + 1) / 2 + (S - W) * W
    assert mod.visible_pairs(256, W) == mod.visible_pairs(256)  # a sequence no longer than the window: plain causal attention
    d, inner, ff = 2560, 5120, 10240
    ffn = 2 * 3 * d * ff
    scan = 2 * (d * 2 * inner + inner * 192 + 160 * inner + inner * d) + 2 * 4 * inner + 6 * inner * 16
    pair = 2 * 40 * 3 * 64  # q.k over 64 and p v over 128, every one of the 40 query heads
    full, window = pair * (S + 1) / 2, pair * mod.visible_pairs(S, W) / S
    self_attention, cross, memory = 2 * (d * 5120 + 2560 * d), 2 * (d * 2560 + 2560 * d), 2 * 2 * d * inner
    forward = 6 * ffn + 2 * scan + (self_attention + window) + (self_attention + full) + memory + (cross + full) + 2 * d * 25008
    assert mod.forward_flops_per_token(PUBLISHED, S) == pytest.approx(forward) and 1.52e9 < forward < 1.54e9
    assert 6 * ffn == pytest.approx(0.944e9, rel=1e-3) and 2 * full == pytest.approx(0.126e9, rel=1e-2) and window == pytest.approx(7.6e6, rel=1e-2)
    assert mod.train_flops_per_token(PUBLISHED, S) == pytest.approx(3 * forward)
    assert mod.ssm_layers(PUBLISHED) == 2 and mod.diff_layers(PUBLISHED) == 3
    fwd, bwd = mod.ssm_cost(PUBLISHED, S, backward=False), mod.ssm_cost(PUBLISHED, S, backward=True)
    assert fwd["flops"] == 6.0 * inner * 16 * S and bwd["flops"] == 2 * fwd["flops"]
    assert fwd["bytes"] == S * (inner * (2 + 4) + 2 * 16 * 2 + inner * 2) and bwd["bytes"] == fwd["bytes"] + S * (inner * 6 + 64)
    attn = mod.diff_attention_cost(PUBLISHED, 1, S, "diff", backward=False)
    assert attn["flops"] == pair * mod.visible_pairs(S) and mod.diff_attention_cost(PUBLISHED, 1, S, "diff_cross", backward=True)["flops"] == 2 * attn["flops"]
    assert mod.diff_attention_cost(PUBLISHED, 1, S, "diff_window", backward=False)["flops"] == pair * mod.visible_pairs(S, W)
    assert attn["bytes"] == 2.0 * S * (2560 + 1280 + 2 * 1280 + 2 * 2560)


def _tiny():
    """The rehearsal's width, all six layers, float32."""
    from benchmarks.lib import weights

    r = CONFIG["rehearse"]
    cfg = dict(CONFIG, **r["published"], reference=r["reference"])
    cfg["program"] = dict(CONFIG["program"], **r["program"], dtype="float32")
    return cfg, weights.build_model(cfg)


def test_the_program_agrees_with_the_plain_reference_in_logits_loss_and_every_gradient():
    """Six layers at the rehearsal's width, seeded weights with every leaf stirred, float32 at the highest matmul
    precision on both sides: 2e-5 of the largest logit and 5e-5 of a leaf's largest gradient entry (the order of float32
    sums; ``tests/unit/test_sambay_layers.py`` has the controls that read two to four orders over these)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.lib import reference

    cfg, model = _tiny()
    ids = np.random.default_rng(0).integers(0, 509, (2, 80)).astype(np.int32)
    params = model.init(jax.random.PRNGKey(0), {"input_ids": ids})
    leaves, tree = jax.tree_util.tree_flatten(params)
    params = jax.tree_util.tree_unflatten(tree, [x + 0.05 * jax.random.normal(jax.random.PRNGKey(7 + i), x.shape) for i, x in enumerate(leaves)])
    ref_logits, ref_loss = reference.for_config(cfg)
    pub = mf.published(cfg)
    close = lambda a, b, tol: np.testing.assert_array_less(np.max(np.abs(np.asarray(a, np.float64) - np.asarray(b, np.float64))),
                                                            tol * (1.0 + np.max(np.abs(np.asarray(b, np.float64)))))
    with jax.default_matmul_precision("highest"):
        close(model.apply(params, ids), ref_logits(params, ids, pub, cfg["reference"], jnp.float32), 2e-5)
        ours, g_ours = jax.value_and_grad(lambda p: model.loss_fn(p, {"input_ids": ids}))(params)
        theirs, g_theirs = jax.value_and_grad(lambda p: ref_loss(ref_logits(p, ids, pub, cfg["reference"], jnp.float32), ids))(params)
    close(ours, theirs, 1e-6)
    theirs_by_path = dict(jax.tree_util.tree_leaves_with_path(g_theirs))
    mine = jax.tree_util.tree_leaves_with_path(g_ours)
    assert len(mine) == len(theirs_by_path) == 90
    for path, leaf in mine:
        close(leaf, theirs_by_path[path], 5e-5)
        assert float(jnp.max(jnp.abs(leaf))) > 0


def _record(ops, steps=4, config=CONFIG):
    """A traced training record with one device, ``steps`` executions of the step program and these operations."""
    dev = {"ops": ops, "op_counts": {k: 1 for k in ops}, "modules": [("jit_fused_step(123)", 0.1 * i, 0.1 * i + 0.09) for i in range(steps)]}
    return {"reduced": {"devices": {"0": dev}, "window_s": 1.0}, "published": mf.published(config), "config": config,
            "train": {"micro_batch": 1, "seq_len": 8192, "steps": 100}, "device": {"kind": "TPU v5 lite", "count": 1},
            "counters": {}, "end_to_end": {"train_tokens_per_s": 1.0}}


# labels as ``lib/trace.py::op_label`` makes them from a v5e trace of this cell's step (my chip run, PR 46)
SCAN_OPS = {'ssm_scan_fwd custom-call (bf16[1,8192,5120]{2,1,0:T(8,128)(2,1)}, f32[1,64,16,5120]{3,2,1,0:T(8,128)}) custom_call_target="tpu_custom_call"': 0.016,
            'ssm_scan_bwd custom-call (bf16[1,8192,5120]{2,1,0:T(8,128)(2,1)}, f32[1,8192,5120]{2,1,0:T(8,128)}, f32[1 custom_call_target="tpu_custom_call"': 0.045}
ATTENTION_OPS = {'flash_bwd custom-call (bf16[20,8192,64]{2,1,0:T(8,128)(2,1)}, bf16[10,8192,64]{2,1,0:T(8,128 custom_call_target="tpu_custom_call"': 0.105,
                 'flash_fwd custom-call (bf16[20,8192,128]{2,1,0:T(8,128)(2,1)S(1)}, f32[20,16,1,512]{3,2,1,0: custom_call_target="tpu_custom_call"': 0.060}
OTHER = {"fusion.1 fusion bf16[8192,10240]{1,0}": 0.5,
         'gdn_scan_fwd custom-call (bf16[32,8192,128]{2,1,0}, f32[32,64,128,128]{3,2,1,0}) custom_call_target="tpu_custom_call"': 0.2,  # another scan: not this one
         'moe_sum_rows custom-call bf16[8192,2048]{1,0} custom_call_target="tpu_custom_call"': 0.1}


@pytest.mark.parametrize("metric,ops", [("ssm_scan_roofline", SCAN_OPS), ("diff_attention_roofline", ATTENTION_OPS)])
def test_a_reader_reads_its_kernels_and_nothing_else(metric, ops):
    mod = mf.metric_module(metric)
    share = mod.read(_record(dict(ops, **OTHER)))
    assert 0 < share < 100
    assert mod.read(_record(dict({k: 2 * v for k, v in ops.items()}, **OTHER))) == pytest.approx(share / 2)
    assert mod.read(_record(OTHER)) is None                                  # a program without the kernels: the parent commit's
    assert mod.read(dict(_record(dict(ops, **OTHER)), reduced=None)) is None  # an untraced run
    assert mod.read(dict(_record(dict(ops, **OTHER)), config={})) is None      # a configuration with no such layers
    other = mf.load_json(os.path.join(mf.BENCH, "configs", "qwen3-next-80b-l4e32.json"))
    assert mod.read(_record(dict(ops, **OTHER), config=other)) is None         # another configuration's FLOP module: nothing, and no raise


def test_the_readers_count_two_scan_layers_and_three_attention_layers_by_their_visible_pairs():
    from benchmarks.lib.peaks import peaks_for

    mod, peaks = flops.for_config(CONFIG), peaks_for("TPU v5 lite")
    need = lambda cost: flops.roofline_seconds(cost, peaks)
    scan = sum(need(mod.ssm_cost(PUBLISHED, 8192, backward=b))["seconds"] for b in (False, True))
    assert mf.metric_module("ssm_scan_roofline").read(_record(SCAN_OPS)) == pytest.approx(100 * 4 * 2 * scan / 0.061)
    assert need(mod.ssm_cost(PUBLISHED, 8192, backward=True))["bound"] == "memory"  # no vector peak is listed: the scan's bound is its bytes
    attention = sum(need(mod.diff_attention_cost(PUBLISHED, 1, 8192, kind, backward=b))["seconds"]
                    for kind in ("diff_window", "diff", "diff_cross") for b in (False, True))
    assert mf.metric_module("diff_attention_roofline").read(_record(ATTENTION_OPS)) == pytest.approx(100 * 4 * attention / 0.165)
    assert need(mod.diff_attention_cost(PUBLISHED, 1, 8192, "diff", backward=False))["bound"] == "compute"
    # the window layer needs 6% of a full layer's time: a kernel that walked the whole square under a window would read low, not high
    window = need(mod.diff_attention_cost(PUBLISHED, 1, 8192, "diff_window", backward=False))["seconds"]
    assert window / need(mod.diff_attention_cost(PUBLISHED, 1, 8192, "diff", backward=False))["seconds"] == pytest.approx(0.121, abs=0.005)


def test_the_rehearsal_says_what_was_traced():
    """A process of its own, as the driver starts one: the package's log line goes to that process's stdout."""
    import subprocess
    import sys

    out = subprocess.run([sys.executable, os.path.join(mf.ROOT, "benchmarks", "run.py"), "--workload", CELL, "--rehearse",
                          "--seed", str(2**31 + 11), "--seconds", "1"], capture_output=True, text=True, timeout=900,
                         cwd=mf.ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    last, counters = json.loads(lines[-1]), json.loads(lines[-2])["extras"]["counters"]
    assert last["correct"] is True and "first_loss_vs_f32" in out.stderr  # the f32 rule is the rehearsal's
    assert 'program_regions_traced_total{op="ssm",pass="fwd",path="xla",region="mixer/kernel"}' in counters
    assert 'program_regions_traced_total{op="diff",pass="fwd",path="xla",region="mixer/kernel"}' in counters
    line = next(l for l in lines if "program first call: family=train" in l)
    for word in ("block_traces=5", "layer_kinds=diff+dense:1,diff_cross+dense:1,diff_window+dense:1,gmu+dense:1,ssm+dense:2", "ssm_path=xla",
                 "diff_path=xla", "remat_keeps=flash_attention+projection+ssm_scan"):
        assert word in line
