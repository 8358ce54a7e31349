"""The configuration ``lfm2-8b-a1b-l5e8`` (LFM2-8B-A1B's published layers 1-5 as one chip's share of 4: gated short
convolutions three layers in four beside GQA 32/8 of 64 with q/k norms, a dense SwiGLU in the leading layer and a
bias-chosen sigmoid router of 4 in 32 over experts of 1,792 in the others, a tied head) and its cell
``lfm2-8b-a1b-l5e8.pretrain-16k``: the files pass the manifest's checks and hold the catalog row's widths with the layer
pattern whole, ``reduced`` and ``share`` agree, the program's tree has the parameters the issue counted, the FLOP module's
total is a sum a reader can check by hand, the program agrees with its plain float32 reference at the rehearsal's width,
the reader reads its kernels and nothing else on a recorded trace's labels, and the rehearsal ends ``correct`` true, and
false under a control. Nothing here pins an entry's place in ``BENCHMARK.json`` or counts its cells: a later cell is
appended after this one."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmarks.lib import flops, manifest as mf

MANIFEST = mf.load_manifest()
NAME, CELL = "lfm2-8b-a1b-l5e8", "lfm2-8b-a1b-l5e8.pretrain-16k"
CONFIG = mf.load_json(os.path.join(mf.BENCH, "configs", f"{NAME}.json"))
TRAFFIC = mf.load_json(os.path.join(mf.BENCH, "traffic", "pretrain-16k.json"))
PUBLISHED = mf.published(CONFIG)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
READER = "short_conv_roofline"
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]
KINDS = [["conv", "dense"], ["full", "routed"], ["conv", "routed"], ["conv", "routed"], ["conv", "routed"]]


def test_the_configuration_and_its_cell_have_no_problems():
    entry = next(c for c in MANIFEST["configs"] if c["name"] == NAME)
    assert mf.config_problems(CONFIG, entry) == [] and entry["reduced"] == REDUCED == CONFIG["reduced"]
    assert [p for p in mf.problems(MANIFEST) if NAME in p or READER in p] == []  # ``manifest.problems`` has nothing new
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert (cell["chips"], cell["traffic"], cell["config"]) == (1, "pretrain-16k", NAME) and len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    for word in ("16384", "4 conv layers", "bandwidth-bound", "14%", "the least of any cell", "2,048 rows an expert", "1/4"):  # the shapes, and what weighs less
        assert word in cell["why"]
    assert CONFIG["trainer"]["train_micro_batch_size_per_gpu"] == 1 and CONFIG["trainer"]["zero_optimization"]["stage"] == 0
    assert CONFIG["trainer"]["mesh"] == {"data": 1} and CONFIG["trainer"]["bf16"] == {"enabled": True}
    assert CONFIG["trainer"]["optimizer"]["type"] == "adam" and CONFIG["program"]["remat"] is True and CONFIG["env"] == {}
    reported = {m["name"] for g in ("end_to_end", "per_layer") for m in mf.metrics_of(MANIFEST, CELL, g)}
    assert {"train_tokens_per_s", "setup_s", "mfu.train", "moe_expert_matmul_roofline", READER} <= reported  # at least what its PR brought: a later reader may list the cell
    assert TRAFFIC["generator"] == "fixed_batches" and TRAFFIC["params"] == {"seq_len": 16384, "n_batches": 8}  # the file the benchmark has
    assert "first_loss_tol" in CONFIG["correct_why"] and 0 < CONFIG["correct"]["first_loss_tol"] <= 0.05


def test_the_new_metric_is_this_cells_alone():
    metric = next(m for m in MANIFEST["per_layer"] if m["name"] == READER)
    assert CELL in metric["workloads"] and (metric["unit"], metric["better"], metric["source"], metric["moves"]) == \
        ("%", "higher", "device_trace", "train_tokens_per_s")
    mod = mf.metric_module(READER)
    assert (mod.UNIT, mod.BETTER, mod.SOURCE, mod.LAYER, mod.MOVES) == tuple(metric[k] for k in ("unit", "better", "source", "layer", "moves"))
    assert metric["layer"] == "kernels (ops/pallas/short_conv.py)" and os.path.isfile(os.path.join(mf.ROOT, "deepspeed_tpu", "ops", "pallas", "short_conv.py"))
    for shared in ("train_tokens_per_s", "mfu.train", "moe_expert_matmul_roofline"):  # appended to, nothing else changed
        listed = next(m for g in ("end_to_end", "per_layer") for m in MANIFEST[g] if m["name"] == shared)["workloads"]
        assert CELL in listed and listed.index(CELL) > listed.index("smallthinker-21b-l4e8.pretrain-16k")


@pytest.mark.parametrize("case,needle", [
    ("as_it_is", None),
    ("a_width_reduced", "reduced names a width"),
    ("the_experts_a_token_reduced", "reduced names a width"),
    ("a_held_count_not_reduced", "which reduced does not list"),
    ("the_share_disagrees", "are held here, the file says"),
    ("the_entry_disagrees", "reduced differs between BENCHMARK.json and its file"),
])
def test_reduced_and_share_agree_and_the_checks_find_what_does_not(case, needle):
    cfg = json.loads(json.dumps(CONFIG))
    entry = dict(next(c for c in MANIFEST["configs"] if c["name"] == NAME))
    if case == "a_width_reduced":
        cfg["reduced"] = entry["reduced"] = CONFIG["reduced"] + ["moe_intermediate_size"]
    elif case == "the_experts_a_token_reduced":
        cfg["reduced"] = entry["reduced"] = CONFIG["reduced"] + ["num_experts_per_tok"]
    elif case == "a_held_count_not_reduced":
        cfg["reduced"] = entry["reduced"] = [k for k in CONFIG["reduced"] if k != "num_experts"]
    elif case == "the_share_disagrees":
        cfg["share"]["held"]["num_experts"]["here"] = 16
    elif case == "the_entry_disagrees":
        entry["reduced"] = CONFIG["reduced"][:-1]
    found = mf.config_problems(cfg, entry)
    assert (found == []) == (needle is None) and (needle is None or any(needle in p for p in found))


@pytest.mark.skipif(not os.path.isfile(CATALOG), reason="the catalog of published configurations is not on this machine")
def test_every_width_is_the_sources_and_the_layer_pattern_is_whole():
    row = next(json.loads(line) for line in open(CATALOG) if '"name": "LFM2-8B-A1B"' in line)
    assert CONFIG["source"] == row["source_url"] and row["not_given"] == []
    source = row["config"]
    differs = {k for k, v in source.items() if CONFIG.get(k, "missing") != v}
    assert differs == set(CONFIG["reduced"]) == set(REDUCED)
    assert CONFIG["layer_types"] == source["layer_types"] and len(source["layer_types"]) == 24 and CONFIG["num_dense_layers"] == source["num_dense_layers"] == 2
    assert [i for i, t in enumerate(source["layer_types"]) if t == "full_attention"] == [2, 6, 10, 14, 18, 21]
    assert CONFIG["published_layers"] == source["num_hidden_layers"] == 24 and CONFIG["layers_here"] == [1, 2, 3, 4, 5] and CONFIG["num_hidden_layers"] == 5
    assert [source["layer_types"][i] for i in CONFIG["layers_here"]] == ["conv", "full_attention", "conv", "conv", "conv"]  # layer 1, then one whole period
    assert CONFIG["share"] == {"chips_per_layer": 4, "held": {"num_experts": {"published": source["num_experts"], "here": 8},
                                                             "vocab_size": {"published": source["vocab_size"], "here": 16384}}}
    assert source["num_experts"] // 4 == 8 == CONFIG["num_experts"] and source["vocab_size"] // 4 == 16384 == CONFIG["vocab_size"]
    assert CONFIG["routed_over"] == source["num_experts"] == 32 and CONFIG["first_k_dense_replace"] == 1  # of the five held, one is dense
    p = CONFIG["program"]
    assert (p["d_model"], p["n_heads"], p["n_kv_heads"], p["head_dims"], p["d_ff"], p["moe_d_ff"], p["moe_top_k"], p["moe_num_experts"], p["norm_eps"],
            p["rope_theta"], p["conv_kernel"]) == \
        (source["hidden_size"], source["num_attention_heads"], source["num_key_value_heads"], source["hidden_size"] // source["num_attention_heads"],
         source["intermediate_size"], source["moe_intermediate_size"], source["num_experts_per_tok"], source["num_experts"], source["norm_eps"],
         source["rope_theta"], source["conv_L_cache"]) == (2048, 32, 8, 64, 7168, 1792, 4, 32, 1e-5, 1e6, 3)
    assert source["conv_bias"] is False and source["use_expert_bias"] is True and source["norm_topk_prob"] is True and source["routed_scaling_factor"] == 1
    assert p["moe_scoring"] == "sigmoid" and p["moe_route_scale"] == 1.0 and p["moe_renorm_eps"] == 1e-6 and p["moe_shared_d_ff"] == 0
    assert p["moe_held"] == [0, 8] and p["moe_aux_loss_coef"] == 0.0 and p["qk_norm"] is True and p["tie_embeddings"] is True
    assert p["activation"] == "swiglu" and p["pos_emb"] == "rope" and p["norm"] == "rmsnorm" and p["vocab_size"] == CONFIG["vocab_size"]
    # a layer's kind by the pattern at its published index: the program's, the FLOP module's and the reference's readings agree
    assert p["layer_kinds"] == KINDS == [list(kind) for kind in flops.for_config(CONFIG).kinds(PUBLISHED)]
    ref = mf.load_module(os.path.join(mf.ROOT, CONFIG["reference"]["module"]))
    assert ref.kinds(PUBLISHED) == tuple((mixer == "conv", ffn == "dense") for mixer, ffn in KINDS)
    assert p["max_seq_len"] == TRAFFIC["params"]["seq_len"] <= source["max_position_embeddings"]
    for key in ("tied_embedding", "conv_operator", "attention", "rotation", "router", "expert_bias", "dense_ffn", "auxiliary_loss", "norms", "optimizer",
                "weights", "start", "held"):
        assert key in CONFIG["assumed"], key
    for word in ("4-chip", "expert parallel 4", "8 of 32", "16,384 rows a chip", "absent", "2,048 rows", "507,820,288", "6.09 GB", "layers 1-5"):
        assert word in CONFIG["deployment"], word


def test_the_parameter_count_is_the_issues_sum():
    """507,820,288 parameters by the shapes of the program's own tree (issue 55 counted "about 508 M"): 6.09 GB of float32
    master and two moments."""
    import jax

    from benchmarks.lib import weights

    shapes = weights.param_shapes(weights.build_model(CONFIG))
    count = lambda tree: sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))
    d = 2048
    conv, attention = d * 3 * d + 3 * d + d * d, 2 * d * 2048 + 2 * d * 512 + 2 * 64  # W_in, the filter, W_out; q, o, k, v and the two norms of 64
    routed, dense = d * 32 + 32 + 8 * 3 * d * 1792, 3 * d * 7168
    assert (conv, attention, routed, dense) == (16_783_360, 10_485_888, 88_145_952, 44_040_192)
    assert count(shapes["layer_0"]) == conv + dense + 2 * d == 60_827_648 and count(shapes["layer_1"]) == attention + routed + 2 * d == 98_635_936
    assert all(count(shapes[f"layer_{i}"]) == conv + routed + 2 * d == 104_933_408 for i in (2, 3, 4))
    assert count(shapes["wte"]) == 16384 * d == 33_554_432 and "lm_head" not in shapes
    assert count(shapes) == 507_820_288 and 6.09e9 < 12 * count(shapes) < 6.10e9


def test_the_flop_count_is_the_hand_written_sum():
    mod = flops.for_config(CONFIG)
    S, d = 16384, 2048
    conv = 2 * (d * 3 * d + d * d)
    proj, pairs = 2 * (d * 2048 + 2 * d * 512 + 2048 * d), 4 * 32 * 64 * (S + 1) / 2
    router, experts, dense, head = 2 * d * 32, 1.0 * 2 * 3 * d * 1792, 2 * 3 * d * 7168, 2 * d * 16384  # 4 x 8 / 32 = ONE expert evaluation a token, here
    assert (conv, proj, router, experts, dense, head) == (33_554_432, 20_971_520, 131_072, 22_020_096, 88_080_384, 67_108_864)
    assert pairs == pytest.approx(67.11e6, rel=1e-4)
    forward = 4 * conv + proj + pairs + 4 * (router + experts) + dense + head
    assert mod.forward_flops_per_token(PUBLISHED, S) == pytest.approx(forward) and forward == pytest.approx(466.1e6, rel=1e-4)
    assert mod.train_flops_per_token(PUBLISHED, S) == pytest.approx(3 * forward) and 3 * forward * S == pytest.approx(22.9e12, rel=2e-3)
    assert pairs / forward == pytest.approx(0.144, abs=0.001) and 4 * conv / forward == pytest.approx(0.288, abs=0.001)  # the cell's why
    # issue 55 counted four expert evaluations a token (88.1 MFLOP a routed layer: the deployment's four chips together) and so 0.73 GFLOP
    assert forward + 4 * 3 * experts == pytest.approx(730.3e6, rel=1e-3)
    fwd, bwd = (mod.short_conv_cost(PUBLISHED, 1, S, backward=b) for b in (False, True))
    cells = S * d
    assert fwd == {"flops": 7.0 * cells, "bytes": 8.0 * cells + 4 * 3 * d} and bwd == {"flops": 14.0 * cells, "bytes": 14.0 * cells + 4 * 3 * d}
    assert mod.expert_matmul_cost(PUBLISHED, 16384.0, backward=False)["flops"] == 2.0 * 3 * d * 1792 * 16384
    assert mod.expert_matmul_cost(PUBLISHED, 16384.0, backward=True)["bytes"] == 2 * 2.0 * (8 * 3 * d * 1792 + 16384 * (2 * d + 3 * 1792))


def _tiny():
    """The rehearsal's width, all five layers, float32."""
    from benchmarks.lib import weights

    r = CONFIG["rehearse"]
    cfg = dict(CONFIG, **r["published"], reference=r["reference"])
    cfg["program"] = dict(CONFIG["program"], **r["program"], dtype="float32")
    return cfg, weights.build_model(cfg)


def _rows(seed, batch=2, vocab=509):
    gen = mf.load_module(os.path.join(mf.BENCH, "generators", "fixed_batches.py"))
    return gen.generate(TRAFFIC["rehearse"]["params"], seed, 40.0, {"vocab_size": vocab, "global_batch": batch})["batches"][0]["input_ids"]


@pytest.mark.parametrize("control", [None, "filter_act", "chunks", "choice", "qk_norm"])
def test_the_program_agrees_with_the_plain_reference_in_logits_loss_and_every_gradient(control):
    """Five layers at the rehearsal's width on the rehearsal's traffic (rows of 96), seeded weights with every leaf
    stirred (the selection bias too, by more), float32 at the highest matmul precision on both sides: 2e-5 of the largest
    logit and 5e-5 of a leaf's largest gradient entry (the order of float32 sums). Through the harness's own pair,
    ``reference.for_config``. Under a control (an activation after the filter, W_in's first two chunks the other way
    round, the choice by the scores alone, no q/k norm) the same comparison FAILS: the logits lie 1e-2 and more away."""
    import jax
    import jax.numpy as jnp

    from benchmarks.lib import reference

    cfg, model = _tiny()
    ids = _rows(5)
    params = model.init(jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 16), np.int32)})
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    by = lambda path: 0.3 if "select_bias" in jax.tree_util.keystr(path) else 0.05
    params = jax.tree_util.tree_unflatten(tree, [x + by(path) * jax.random.normal(jax.random.PRNGKey(7 + i), x.shape) for i, (path, x) in enumerate(leaves)])
    ref_logits, ref_loss = reference.for_config(cfg)
    pub = mf.published(cfg)
    wrong = {"filter_act": {"filter_act": "silu"}, "chunks": {"chunks": "cbu"}, "choice": {"choice": "scores"}, "qk_norm": {"qk_norm": "none"}}.get(control, {})
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
    assert len(mine) == len(theirs_by_path) == 2 + 8 + 13 + 3 * 10
    for path, leaf in mine:
        assert gap(leaf, theirs_by_path[path]) < 5e-5, jax.tree_util.keystr(path)
        assert (float(jnp.max(jnp.abs(leaf))) > 0) == ("select_bias" not in jax.tree_util.keystr(path))  # the bias is a buffer


def _record(ops, steps=4, config=CONFIG):
    """A traced training record with one device, ``steps`` executions of the step program and these operations."""
    dev = {"ops": ops, "op_counts": {k: 1 for k in ops}, "modules": [("jit_fused_step(123)", 0.1 * i, 0.1 * i + 0.09) for i in range(steps)]}
    return {"reduced": {"devices": {"0": dev}, "window_s": 1.0}, "published": mf.published(config), "config": config,
            "train": {"micro_batch": 1, "seq_len": 16384, "steps": 100}, "device": {"kind": "TPU v5 lite", "count": 1},
            "counters": {}, "end_to_end": {"train_tokens_per_s": 1.0}}


# labels as ``lib/trace.py::op_label`` makes them from a v5e trace of this cell's step, and their seconds over four steps
# (my chip run, PR 55)
CONV_OPS = {'short_conv_fwd custom-call bf16[1,16384,2048]{2,1,0:T(8,128)(2,1)} custom_call_target="tpu_custom_call"': 0.006483519,
            'short_conv_bwd custom-call (bf16[1,16384,6144]{2,1,0:T(8,128)(2,1)}, f32[1,8,2048]{2,1,0:T(8,128) custom_call_target="tpu_custom_call"': 0.011962774}
OTHER = {'gmm custom-call bf16[32768,2048]{1,0:T(8,128)(2,1)} custom_call_target="tpu_custom_call"': 0.063821743,
         'tgmm custom-call bf16[8,2048,1792]{2,1,0:T(8,128)(2,1)} custom_call_target="tpu_custom_call"': 0.031816581,
         'flash_fwd custom-call (bf16[32,16384,64]{2,1,0:T(8,128)(2,1)}, f32[32,32,1,512]{3,2,1,0:T(1, custom_call_target="tpu_custom_call"': 0.054470516,
         'ssm_scan_fwd custom-call bf16[1,8192,5120]{2,1,0} custom_call_target="tpu_custom_call"': 0.1,  # another cell's filter rides in its scan's region
         "fusion fusion bf16[1,16384,6144]{2,1,0:T(8,128)(2,1)}": 0.034759719}  # XLA's own passes at the operator's shapes: no custom call


def test_the_reader_reads_its_kernels_and_nothing_else():
    from benchmarks.lib.peaks import peaks_for

    mod = mf.metric_module(READER)
    share = mod.read(_record(dict(CONV_OPS, **OTHER)))
    assert share == pytest.approx(78.19, abs=0.01)  # the chip run's own reading of these seconds (78.186)
    assert mod.read(_record(dict({k: 2 * v for k, v in CONV_OPS.items()}, **OTHER))) == pytest.approx(share / 2)
    assert mod.read(_record(OTHER)) is None                                         # a program that runs the operator as XLA's fusions, or the parent's
    assert mod.read(dict(_record(dict(CONV_OPS, **OTHER)), reduced=None)) is None  # an untraced run
    assert mod.read(dict(_record(dict(CONV_OPS, **OTHER)), config={})) is None      # a configuration with no such layers
    for other in ("smallthinker-21b-l4e8", "phi4-mini-flash-l6", "kimi-linear-48b-l5e8", "olmo-1b"):  # another configuration's FLOP module: nothing, and no raise
        assert mod.read(_record(dict(CONV_OPS, **OTHER), config=mf.load_json(os.path.join(mf.BENCH, "configs", f"{other}.json")))) is None
    counts, peaks = flops.for_config(CONFIG), peaks_for("TPU v5 lite")
    need = [flops.roofline_seconds(counts.short_conv_cost(PUBLISHED, 1, 16384, backward=b), peaks) for b in (False, True)]
    assert {n["bound"] for n in need} == {"memory"}  # a share of the chip's bandwidth
    assert share == pytest.approx(100 * 4 * 4 * sum(n["seconds"] for n in need) / sum(CONV_OPS.values()))  # four steps, four conv layers
    # the older attention readers that name a cost of their own find none in this configuration's FLOP module
    for older in ("diff_attention_roofline", "blockdiff_attention_roofline", "loop_attention_roofline"):
        assert mf.metric_module(older).read(_record(dict(CONV_OPS, **OTHER))) is None


def test_the_mixed_attention_reader_reads_this_cells_one_attention_layer():
    """Since PR 65 the FLOP module names ``mixed_attention_cost``, so ``mixed_attention_roofline`` (the benchmark's, not
    edited) reads this cell's flash calls: one ``full`` layer of the five held, nothing for a ``conv`` layer."""
    from benchmarks.lib.peaks import peaks_for

    counts, peaks, S = flops.for_config(CONFIG), peaks_for("TPU v5 lite"), 16384
    assert [mixer for mixer, _ in counts.kinds(PUBLISHED)] == ["conv", "full", "conv", "conv", "conv"]
    for b in (False, True):
        assert counts.mixed_attention_cost(PUBLISHED, 1, S, ("conv", "routed"), backward=b) == {"flops": 0.0, "bytes": 0.0}
    fwd, bwd = (counts.mixed_attention_cost(PUBLISHED, 1, S, ("full", "routed"), backward=b) for b in (False, True))
    assert fwd["flops"] == 4.0 * 32 * 64 * S * (S + 1) / 2 and bwd["flops"] == 2 * fwd["flops"]  # GQA 32/8 of 64: every QUERY head's pairs
    moved = 2.0 * S * (2 * 2048 + 2 * 512) + 4.0 * S * 32  # q, k, v, o in bf16 and a float32 a head and query
    assert fwd["bytes"] == moved and bwd["bytes"] == 2 * moved + 2.0 * S * 2048
    assert 4.0 * 32 * 64 * (S + 1) / 2 == pytest.approx(0.14 * counts.forward_flops_per_token(PUBLISHED, S), rel=0.03)  # the cell's ``why``: 14% of the FLOPs
    need = [flops.roofline_seconds(cost, peaks) for cost in (fwd, bwd)]
    assert {n["bound"] for n in need} == {"compute"} and sum(n["seconds"] for n in need) == pytest.approx(16.745e-3, rel=1e-4)
    # the two calls' seconds over four steps on the ledger's PR 64 line of this cell (``breakdown.device_ops``)
    flash = {k: v for k, v in OTHER.items() if k.startswith("flash_fwd")}
    flash['flash_bwd custom-call (bf16[32,16384,64]{2,1,0:T(8,128)(2,1)}, bf16[8,16384,64]{2,1,0:T(8,12 custom_call_target="tpu_custom_call"'] = 0.13639
    share = mf.metric_module("mixed_attention_roofline").read(_record({**CONV_OPS, **OTHER, **flash}))
    assert share == pytest.approx(100 * 4 * sum(n["seconds"] for n in need) / sum(flash.values())) and 30 < share < 40
    assert mf.metric_module("mixed_attention_roofline").read(_record(CONV_OPS)) is None  # a trace without a flash call: nothing, and never 0


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
    for series in ('{op="short_conv",pass="fwd",path="xla",region="mixer/conv"}', '{op="full",pass="fwd",path="xla",region="mixer/kernel"}',
                   '{path="sigmoid",region="ffn/router"}', '{path="xla",region="ffn/experts"}', '{op="qk",path="xla",region="mixer/rope"}'):
        assert "program_regions_traced_total" + series in counters, series
    assert counters["moe_rows_dropped_total"] == 0 and counters["moe_fallback_layers_total"] == 0
    steps = counters["train_steps_total"]
    assert steps > 0 and 0.5 < counters["moe_rows_routed_here_total"] / (steps * 4 * 96) < 2.0  # 96 x 4 x 4 / 16 = 96 uniform pairs a layer, four routed layers
    assert counters['moe_buffer_rung_layers_total{rung="first"}'] >= steps * 4 - 8  # the rung the buffer took (counted a step late)
    line = next(l for l in lines if "program first call: family=train" in l)
    for word in ("block_traces=3", "layer_kinds=conv+dense:1,conv+routed:3,full+routed:1", "conv_path=xla", "full_path=xla",
                 "moe_router=sigmoid+compare_sum", "rope=xla", "remat_keeps=flash_attention+projection+routed_ffn+short_conv"):
        assert word in line, word


def test_the_rehearsal_ends_false_under_a_control(tmp_path):
    """The same run against a reference with one thing wrong (no final norm: the cell's own control): ``correct`` false
    by ``first_loss_diff``. (A wrong filter or choice moves a mean loss over 95 random targets by its sampling noise: the
    comparison above, in logits and gradients, is where those fail.) The reference's control is switched on through a
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
    # a hundred times the sound rehearsal's 1e-4, and under the rehearsal's 0.05: the tied head's entries start at 0.02, so
    # at 64 wide the logits are small with the norm or without it and the loss moves by a hundredth; it is the f32 rule
    # (ours against the float32 truth, no worse than 2.5 times the plain path's own error) that fails the run
    assert diff > 0.01 and "first_loss_vs_f32" in out.stderr
