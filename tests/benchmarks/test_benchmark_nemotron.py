"""The configuration ``nemotron3-nano-30b-l9e8`` (NVIDIA-Nemotron-3-Nano-30B-A3B's published layers 0-8 as one chip's share
of 16: Mamba-2 mixers, routed FFNs of ungated relu^2 experts 6 of 128 with a shared expert, and GQA 32/2 without positions,
each layer ONE part, an untied head) and its cell ``nemotron3-nano-30b-l9e8.pretrain-8k``: the files pass the manifest's
checks and hold the catalog row's widths with the layer pattern whole, ``reduced`` and ``share`` agree, the program's tree
has the parameters the issue counted, the FLOP module's total is a sum a reader can check by hand, the program agrees with
its plain float32 reference at the rehearsal's width, the new reader reads its kernels and nothing else, the expert
reader counts TWO products an expert, and the rehearsal ends ``correct`` true, and false under a control. Nothing here pins
an entry's place in ``BENCHMARK.json`` or counts its cells: a later cell is appended after this one."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmarks.lib import flops, manifest as mf

MANIFEST = mf.load_manifest()
NAME, CELL = "nemotron3-nano-30b-l9e8", "nemotron3-nano-30b-l9e8.pretrain-8k"
CONFIG = mf.load_json(os.path.join(mf.BENCH, "configs", f"{NAME}.json"))
TRAFFIC = mf.load_json(os.path.join(mf.BENCH, "traffic", "pretrain-8k.json"))
PUBLISHED = mf.published(CONFIG)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
READER = "ssd_scan_roofline"
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size"]
KINDS = [{"M": ["ssd", "none"], "E": ["none", "routed"], "*": ["nope", "none"]}[c] for c in "MEMEM*EME"]


def test_the_configuration_and_its_cell_have_no_problems():
    entry = next(c for c in MANIFEST["configs"] if c["name"] == NAME)
    assert mf.config_problems(CONFIG, entry) == [] and entry["reduced"] == REDUCED == CONFIG["reduced"]
    assert [p for p in mf.problems(MANIFEST) if NAME in p or READER in p] == []  # ``manifest.problems`` has nothing new
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert (cell["chips"], cell["traffic"], cell["config"]) == (1, "pretrain-8k", NAME) and len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    for word in ("8192", "4 of 9 layers Mamba-2 scans", "two-matrix experts", "384 rows", "1/16", "no exchange"):  # the rows, which layers are scans, the load
        assert word in cell["why"]
    assert CONFIG["trainer"]["train_micro_batch_size_per_gpu"] == 1 and CONFIG["trainer"]["zero_optimization"]["stage"] == 0
    assert CONFIG["trainer"]["mesh"] == {"data": 1} and CONFIG["trainer"]["bf16"] == {"enabled": True}
    assert CONFIG["trainer"]["optimizer"]["type"] == "adam" and CONFIG["program"]["remat"] is True and CONFIG["env"] == {}
    assert (CONFIG["warmup_steps"], CONFIG["trace_steps"]) == (3, 4)
    reported = {m["name"] for g in ("end_to_end", "per_layer") for m in mf.metrics_of(MANIFEST, CELL, g)}
    assert {"train_tokens_per_s", "setup_s", "mfu.train", "moe_expert_matmul_roofline", READER} <= reported  # at least what its PR brought: a later reader may list the cell
    assert TRAFFIC["generator"] == "fixed_batches" and TRAFFIC["params"] == {"seq_len": 8192, "n_batches": 8}  # the file the benchmark has
    assert "first_loss_tol" in CONFIG["correct_why"] and 0 < CONFIG["correct"]["first_loss_tol"] <= 0.05


def test_the_new_metric_is_this_cells_alone():
    metric = next(m for m in MANIFEST["per_layer"] if m["name"] == READER)
    assert CELL in metric["workloads"] and (metric["unit"], metric["better"], metric["source"], metric["moves"]) == \
        ("%", "higher", "device_trace", "train_tokens_per_s")
    mod = mf.metric_module(READER)
    assert (mod.UNIT, mod.BETTER, mod.SOURCE, mod.LAYER, mod.MOVES) == tuple(metric[k] for k in ("unit", "better", "source", "layer", "moves"))
    assert metric["layer"] == "kernels (ops/pallas/ssd.py)" and os.path.isfile(os.path.join(mf.ROOT, "deepspeed_tpu", "ops", "pallas", "ssd.py"))
    for shared in ("train_tokens_per_s", "mfu.train", "moe_expert_matmul_roofline"):  # appended to, nothing else changed
        listed = next(m for g in ("end_to_end", "per_layer") for m in MANIFEST[g] if m["name"] == shared)["workloads"]
        assert CELL in listed and listed.index(CELL) > listed.index("lfm2-8b-a1b-l5e8.pretrain-16k")


@pytest.mark.parametrize("case,needle", [
    ("as_it_is", None),
    ("a_width_reduced", "reduced names a width"),
    ("a_state_size_reduced", "reduced names a width"),
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
    elif case == "a_state_size_reduced":
        cfg["reduced"] = entry["reduced"] = CONFIG["reduced"] + ["ssm_state_size"]
    elif case == "the_experts_a_token_reduced":
        cfg["reduced"] = entry["reduced"] = CONFIG["reduced"] + ["num_experts_per_tok"]
    elif case == "a_held_count_not_reduced":
        cfg["reduced"] = entry["reduced"] = [k for k in CONFIG["reduced"] if k != "n_routed_experts"]
    elif case == "the_share_disagrees":
        cfg["share"]["held"]["n_routed_experts"]["here"] = 16
    elif case == "the_entry_disagrees":
        entry["reduced"] = CONFIG["reduced"][:-1]
    found = mf.config_problems(cfg, entry)
    assert (found == []) == (needle is None) and (needle is None or any(needle in p for p in found))


@pytest.mark.skipif(not os.path.isfile(CATALOG), reason="the catalog of published configurations is not on this machine")
def test_every_width_is_the_sources_and_the_layer_pattern_is_whole():
    row = next(json.loads(line) for line in open(CATALOG) if '"name": "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"' in line)
    assert CONFIG["source"] == row["source_url"] and row["not_given"] == []
    source = row["config"]
    differs = {k for k, v in source.items() if CONFIG.get(k, "missing") != v}
    assert differs == set(CONFIG["reduced"]) == set(REDUCED)
    pattern = source["hybrid_override_pattern"]
    assert CONFIG["hybrid_override_pattern"] == pattern and len(pattern) == 52 == source["num_hidden_layers"] == CONFIG["published_layers"]
    assert (pattern.count("M"), pattern.count("E"), pattern.count("*")) == (23, 23, 6) and [i for i, c in enumerate(pattern) if c == "*"] == [5, 12, 19, 26, 33, 42]
    assert CONFIG["layers_here"] == list(range(9)) and CONFIG["num_hidden_layers"] == 9 and pattern[:9] == "MEMEM*EME"  # 4 M, 4 E, 1 *
    assert CONFIG["share"] == {"chips_per_layer": 16, "held": {"n_routed_experts": {"published": source["n_routed_experts"], "here": 8},
                                                              "vocab_size": {"published": source["vocab_size"], "here": 16384}}}
    assert source["n_routed_experts"] // 16 == 8 == CONFIG["n_routed_experts"] and source["vocab_size"] // 8 == 16384 == CONFIG["vocab_size"]
    assert CONFIG["routed_over"] == source["n_routed_experts"] == 128 and CONFIG["first_k_dense_replace"] == 5  # of the nine held, five are no routed FFN
    p = CONFIG["program"]
    assert (p["d_model"], p["n_heads"], p["n_kv_heads"], p["head_dims"], p["moe_d_ff"], p["moe_shared_d_ff"], p["moe_top_k"], p["moe_num_experts"], p["norm_eps"],
            p["moe_route_scale"]) == \
        (source["hidden_size"], source["num_attention_heads"], source["num_key_value_heads"], source["head_dim"], source["moe_intermediate_size"],
         source["n_shared_experts"] * source["moe_shared_expert_intermediate_size"], source["num_experts_per_tok"], source["n_routed_experts"],
         source["layer_norm_epsilon"], source["routed_scaling_factor"]) == (2688, 32, 2, 128, 1856, 3712, 6, 128, 1e-5, 2.5)
    assert (p["ssd_heads"], p["ssd_head_dim"], p["ssd_state"], p["ssd_groups"], p["ssd_conv"]) == \
        (source["mamba_num_heads"], source["mamba_head_dim"], source["ssm_state_size"], source["n_groups"], source["conv_kernel"]) == (64, 64, 128, 8, 4)
    assert p["ssd_heads"] * p["ssd_head_dim"] == 4096 != source["expand"] * source["hidden_size"]  # d_inner is heads x head_dim, not expand x hidden
    assert source["mlp_hidden_act"] == "relu2" == p["activation"] and source["use_conv_bias"] is True and source["mamba_proj_bias"] is False
    assert source["norm_topk_prob"] is True and source["n_group"] == source["topk_group"] == 1 and source["tie_word_embeddings"] is False
    assert p["moe_scoring"] == "sigmoid" and p["moe_held"] == [0, 8] and p["moe_aux_loss_coef"] == 0.0 and p["tie_embeddings"] is False
    assert p["pos_emb"] == "none" and p["norm"] == "rmsnorm" and p["vocab_size"] == CONFIG["vocab_size"] and "moe_renorm_eps" not in p  # the family's 1e-20 is the default
    # a layer's kind by the pattern at its published index: the program's, the FLOP module's and the reference's readings agree
    assert p["layer_kinds"] == KINDS == [list(kind) for kind in flops.for_config(CONFIG).kinds(PUBLISHED)] and p["n_layers"] == 9
    ref = mf.load_module(os.path.join(mf.ROOT, CONFIG["reference"]["module"]))
    assert ref.kinds(PUBLISHED) == tuple("MEMEM*EME")
    assert p["max_seq_len"] == TRAFFIC["params"]["seq_len"] <= source["max_position_embeddings"]
    for key in ("one_part_blocks", "rotation", "attention", "mamba2", "mamba2_start", "router", "correction_bias", "experts", "auxiliary_loss", "optimizer",
                "weights", "held"):
        assert key in CONFIG["assumed"], key
    for word in ("16-chip", "expert parallel 16", "8 of 128", "16,384 rows", "absent", "384 rows", "6,144", "1/16", "layers 0-8"):
        assert word in CONFIG["deployment"], word


def test_the_parameter_count_is_the_issues_sum():
    """666,963,456 parameters by the shapes of the program's own tree (issue 59 counted 667 M): 8.0 GB of float32 master
    and two moments."""
    import jax

    from benchmarks.lib import weights

    shapes = weights.param_shapes(weights.build_model(CONFIG))
    count = lambda tree: sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))
    d = 2688
    mamba = d * 10304 + 4 * 6144 + 6144 + 3 * 64 + 4096 + 4096 * d  # W_in, the filter and its bias, dt_bias, A_log and D, the norm's weight, W_out
    attention = 2 * d * 4096 + 2 * d * 256
    routed = d * 128 + 128 + 8 * 2 * d * 1856 + 2 * d * 3712  # the router and its bias, TWO matrices an expert, the shared expert's two
    assert (mamba, attention, routed) == (38_742_208, 23_396_352, 100_122_752)
    for i, c in enumerate("MEMEM*EME"):
        assert count(shapes[f"layer_{i}"]) == {"M": mamba, "E": routed, "*": attention}[c] + d, i  # and ONE norm
    assert count(shapes["wte"]) == count(shapes["lm_head"]) == 16384 * d == 44_040_192
    assert count(shapes) == 666_963_456 and 8.00e9 < 12 * count(shapes) < 8.01e9


def test_the_flop_count_is_the_hand_written_sum():
    mod = flops.for_config(CONFIG)
    S, d = 8192, 2688
    products, conv = 2 * (d * 10304 + 4096 * d), 2 * 4 * 6144
    scan = 2 * (8 * 64.5 * 128 + 64 * 64.5 * 64 + 2 * 64 * 64 * 128)  # C B^T a group and its product with X a head over half a chunk's square; the states out and in
    assert scan == mod.ssd_scan_flops_per_token(PUBLISHED) == pytest.approx(2.758e6, rel=1e-3)
    proj, pairs = 2 * (d * 4096 + 2 * d * 256 + 4096 * d), 4 * 32 * 128 * (S + 1) / 2
    router, experts, shared, head = 2 * d * 128, 0.375 * 2 * 2 * d * 1856, 2 * 2 * d * 3712, 2 * d * 16384  # 6 x 8 / 128 expert evaluations a token, TWO products each
    assert (products, proj, router, experts, shared, head) == (77_414_400, 46_792_704, 688_128, 7_483_392.0, 39_911_424, 88_080_384)
    forward = 4 * (products + conv + scan) + proj + pairs + 4 * (router + experts + shared) + head
    assert mod.forward_flops_per_token(PUBLISHED, S) == pytest.approx(forward) and forward == pytest.approx(715.4e6, rel=1e-3)  # issue 59: 717
    assert mod.train_flops_per_token(PUBLISHED, S) == pytest.approx(3 * forward) and 3 * forward * S == pytest.approx(17.58e12, rel=2e-3)
    assert 4 * (products + conv + scan) / forward == pytest.approx(0.449, abs=0.002)  # the four Mamba-2 layers: 45% of the operations
    assert mod.ssd_layers(PUBLISHED) == 4
    fwd, bwd = (mod.ssd_cost(PUBLISHED, 1, S, backward=b) for b in (False, True))
    operands, y, states = S * (2 * 4096 + 2 * 2 * 1024 + 4 * 64), S * 2 * 4096, S // 128 * 64 * 64 * 128 * 4
    assert fwd == {"flops": scan * S, "bytes": float(operands + y + states)} and bwd == {"flops": 2 * scan * S, "bytes": float(2 * operands + y + states)}
    assert states == 134_217_728  # 134 MB of chunk-boundary states a layer
    assert mod.expert_matmul_cost(PUBLISHED, 3072.0, backward=False) == {"flops": 2.0 * 2 * d * 1856 * 3072, "bytes": 2.0 * (8 * 2 * d * 1856 + 3072 * (2 * d + 2 * 1856))}
    assert mod.expert_matmul_cost(PUBLISHED, 3072.0, backward=True)["flops"] == 2 * mod.expert_matmul_cost(PUBLISHED, 3072.0, backward=False)["flops"]


def _tiny():
    """The rehearsal's width, all nine layers, float32."""
    from benchmarks.lib import weights

    r = CONFIG["rehearse"]
    cfg = dict(CONFIG, **r["published"], reference=r["reference"])
    cfg["program"] = dict(CONFIG["program"], **r["program"], dtype="float32")
    return cfg, weights.build_model(cfg)


def _rows(seed, batch=2, vocab=509):
    gen = mf.load_module(os.path.join(mf.BENCH, "generators", "fixed_batches.py"))
    return gen.generate(TRAFFIC["rehearse"]["params"], seed, 40.0, {"vocab_size": vocab, "global_batch": batch})["batches"][0]["input_ids"]


@pytest.mark.parametrize("control", [None, "expert_act", "decay", "norm", "skip", "choice"])
def test_the_program_agrees_with_the_plain_reference_in_logits_loss_and_every_gradient(control):
    """Nine layers at the rehearsal's width on the rehearsal's traffic (rows of 96), seeded weights with every leaf stirred
    (the selection bias too, by more), float32 at the highest matmul precision on both sides: 2e-5 of the largest logit
    and 5e-5 of a leaf's largest gradient entry (the order of float32 sums). Through the harness's own pair,
    ``reference.for_config``. Under a control (experts that are a gated SiLU, the decay dropped, the norm before the gate,
    no ``D x``, the choice by the scores alone) the same comparison FAILS: the logits lie 1e-2 and more away."""
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
    wrong = {"expert_act": {"expert_act": "silu_gated"}, "decay": {"decay": "none"}, "norm": {"norm": "before_gate"}, "skip": {"skip": "none"},
             "choice": {"choice": "scores"}}.get(control, {})
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
    assert len(mine) == len(theirs_by_path) == 3 + 4 * 9 + 4 * 7 + 5  # the tables and the final norm; a layer's one norm and its part's leaves
    for path, leaf in mine:
        assert gap(leaf, theirs_by_path[path]) < 5e-5, jax.tree_util.keystr(path)
        assert (float(jnp.max(jnp.abs(leaf))) > 0) == ("select_bias" not in jax.tree_util.keystr(path))  # the bias is a buffer


def _record(ops, steps=4, config=CONFIG, counters=None):
    """A traced training record with one device, ``steps`` executions of the step program and these operations."""
    dev = {"ops": ops, "op_counts": {k: 1 for k in ops}, "modules": [("jit_fused_step(123)", 0.1 * i, 0.1 * i + 0.09) for i in range(steps)]}
    return {"reduced": {"devices": {"0": dev}, "window_s": 1.0}, "published": mf.published(config), "config": config,
            "train": {"micro_batch": 1, "seq_len": 8192, "steps": 100}, "device": {"kind": "TPU v5 lite", "count": 1},
            "counters": counters or {}, "end_to_end": {"train_tokens_per_s": 1.0}}


# labels as ``lib/trace.py::op_label`` makes them from a v5e trace of this cell's step, and their seconds over four steps
# (my chip run, PR 59, seed 7)
SSD_OPS = {'ssd_scan_fwd custom-call (bf16[1,8192,4096]{2,1,0:T(8,128)(2,1)}, f32[1,8,64,512,128]{4,3,2,1,0:T(8,128 custom_call_target="tpu_custom_call"': 0.011288,
           'ssd_scan_bwd custom-call (bf16[1,8192,4096]{2,1,0:T(8,128)(2,1)}, bf16[1,8192,1024]{2,1,0:T(8,1 custom_call_target="tpu_custom_call"': 0.037730311}
OTHER = {'gmm custom-call bf16[6144,1856]{1,0:T(8,128)(2,1)S(1)} custom_call_target="tpu_custom_call"': 0.032853,
         'tgmm custom-call bf16[8,1856,2688]{2,1,0:T(8,128)(2,1)} custom_call_target="tpu_custom_call"': 0.01418,
         'flash_bwd custom-call (bf16[32,8192,128]{2,1,0:T(8,128)(2,1)}, bf16[2,8192,128]{2,1,0:T(8,12 custom_call_target="tpu_custom_call"': 0.036355966,
         'moe_sum_rows custom-call bf16[8192,2688]{1,0:T(8,128)(2,1)} custom_call_target="tpu_custom_call"': 0.010025,
         'ssm_scan_fwd custom-call bf16[1,8192,5120]{2,1,0} custom_call_target="tpu_custom_call"': 0.1,  # another cell's scan: a Mamba-1 kernel is not this reader's
         'gdn_scan_fwd custom-call bf16[32,8192,128]{2,1,0} custom_call_target="tpu_custom_call"': 0.1,  # ... nor a delta-rule scan
         "fusion fusion bf16[1,8192,10304]{2,1,0:T(8,128)(2,1)}": 0.042506692}  # XLA's own passes at the projection's shape: no custom call


def test_the_reader_reads_its_kernels_and_nothing_else():
    from benchmarks.lib.peaks import peaks_for

    mod = mf.metric_module(READER)
    share = mod.read(_record(dict(SSD_OPS, **OTHER)))
    counts, peaks = flops.for_config(CONFIG), peaks_for("TPU v5 lite")
    need = [flops.roofline_seconds(counts.ssd_cost(PUBLISHED, 1, 8192, backward=b), peaks) for b in (False, True)]
    assert {n["bound"] for n in need} == {"memory"}  # a share of the chip's bandwidth: the states and the operands bind before the MXU
    assert share == pytest.approx(100 * 4 * 4 * sum(n["seconds"] for n in need) / sum(SSD_OPS.values()))  # four steps, four Mamba-2 layers
    assert share == pytest.approx(28.34, abs=0.01)  # the chip run's own reading of these seconds: 28.334
    assert mod.read(_record(dict({k: 2 * v for k, v in SSD_OPS.items()}, **OTHER))) == pytest.approx(share / 2)
    assert mod.read(_record(OTHER)) is None                                        # a program whose scan is XLA's recurrence, or the parent's
    assert mod.read(dict(_record(dict(SSD_OPS, **OTHER)), reduced=None)) is None  # an untraced run
    assert mod.read(dict(_record(dict(SSD_OPS, **OTHER)), config={})) is None      # a configuration with no such layers
    for other in ("phi4-mini-flash-l6", "qwen3-next-80b-l4e32", "lfm2-8b-a1b-l5e8", "olmo-1b"):  # another configuration's FLOP module: nothing, and no raise
        assert mod.read(_record(dict(SSD_OPS, **OTHER), config=mf.load_json(os.path.join(mf.BENCH, "configs", f"{other}.json")))) is None
    # the older scan readers find no cost of their own in this configuration's FLOP module
    for older in ("ssm_scan_roofline", "gdn_scan_roofline", "kda_scan_roofline", "short_conv_roofline"):
        assert mf.metric_module(older).read(_record(dict(SSD_OPS, **OTHER))) is None


def test_the_mixed_attention_reader_reads_this_cells_one_attention_layer():
    """Since PR 65 the FLOP module names ``mixed_attention_cost``, so ``mixed_attention_roofline`` (the benchmark's, not
    edited) reads this cell's flash calls: one ``nope`` layer of the nine held, sixteen query heads a key head; nothing
    for a Mamba-2 layer or a routed FFN's."""
    from benchmarks.lib.peaks import peaks_for

    counts, peaks, S = flops.for_config(CONFIG), peaks_for("TPU v5 lite"), 8192
    assert [mixer for mixer, _ in counts.kinds(PUBLISHED)].count("nope") == 1
    for kind in (("ssd", "none"), ("none", "routed")):
        for b in (False, True):
            assert counts.mixed_attention_cost(PUBLISHED, 1, S, kind, backward=b) == {"flops": 0.0, "bytes": 0.0}
    fwd, bwd = (counts.mixed_attention_cost(PUBLISHED, 1, S, ("nope", "none"), backward=b) for b in (False, True))
    assert fwd["flops"] == 4.0 * 32 * 128 * S * (S + 1) / 2 and bwd["flops"] == 2 * fwd["flops"]  # GQA 32/2 of 128: every QUERY head's pairs
    moved = 2.0 * S * (2 * 4096 + 2 * 256) + 4.0 * S * 32  # q, k, v, o in bf16 and a float32 a head and query
    assert fwd["bytes"] == moved and bwd["bytes"] == 2 * moved + 2.0 * S * 4096
    need = [flops.roofline_seconds(cost, peaks) for cost in (fwd, bwd)]
    assert {n["bound"] for n in need} == {"compute"} and sum(n["seconds"] for n in need) == pytest.approx(8.373e-3, rel=1e-4)
    # ``OTHER`` holds the backward call alone (its seconds over four steps, my chip run, PR 59): whatever calls the trace holds are read
    flash = {k: v for k, v in OTHER.items() if k.startswith("flash_")}
    share = mf.metric_module("mixed_attention_roofline").read(_record(dict(SSD_OPS, **OTHER)))
    assert share == pytest.approx(100 * 4 * sum(n["seconds"] for n in need) / sum(flash.values())) and 90 < share < 95
    assert mf.metric_module("mixed_attention_roofline").read(_record(SSD_OPS)) is None  # a trace without a flash call: nothing, and never 0


def test_the_expert_reader_counts_two_products_at_the_counters_rows():
    """``moe_expert_matmul_roofline`` (the benchmark's, not edited) on this configuration: four routed layers by
    ``num_hidden_layers - first_k_dense_replace``, the rows from the program's counter, TWO grouped products a row."""
    from benchmarks.lib.peaks import peaks_for

    mod = mf.metric_module("moe_expert_matmul_roofline")
    record = _record(dict(SSD_OPS, **OTHER), counters={"moe_rows_routed_here_total": 100 * 4 * 3072.0})  # the uniform load: 8,192 x 6 x 8 / 128 pairs a layer
    share = mod.read(record)
    cost, peaks = flops.for_config(CONFIG).expert_matmul_cost, peaks_for("TPU v5 lite")
    need = sum(flops.roofline_seconds(cost(PUBLISHED, 3072.0, backward=b), peaks)["seconds"] for b in (False, True))
    assert share == pytest.approx(100 * 4 * 4 * need / (0.032853 + 0.01418)) and 30 < share < 33  # the gmm and tgmm calls' seconds above; the chip run read 31.19 at its own rows
    assert cost(PUBLISHED, 3072.0, backward=False)["flops"] * 1.5 == 2.0 * 3 * 2688 * 1856 * 3072  # two thirds of a gated expert's


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
    for series in ('{op="ssd",pass="fwd",path="xla",region="mixer/kernel"}', '{op="nope",pass="fwd",path="xla",region="mixer/kernel"}',
                   '{path="sigmoid",region="ffn/router"}', '{act="relu2",path="xla",region="ffn/experts"}'):
        assert "program_regions_traced_total" + series in counters, series
    assert counters["moe_rows_dropped_total"] == 0 and counters["moe_fallback_layers_total"] == 0
    steps = counters["train_steps_total"]
    assert steps > 0 and 0.5 < counters["moe_rows_routed_here_total"] / (steps * 4 * 72) < 2.0  # 96 x 3 x 4 / 16 = 72 uniform pairs a layer, four routed layers
    assert counters['moe_buffer_rung_layers_total{rung="first"}'] >= steps * 4 - 8  # the rung the buffer took (counted a step late)
    line = next(l for l in lines if "program first call: family=train" in l)
    for word in ("block_traces=3", "layer_kinds=none+routed:4,nope+none:1,ssd+none:4", "ssd_path=xla", "nope_path=xla", "moe_router=sigmoid+compare_sum",
                 "moe_activation=relu2", "remat_keeps=flash_attention+projection+routed_ffn+ssd_scan"):
        assert word in line, word


def test_the_rehearsal_ends_false_under_a_control(tmp_path):
    """The same run against a reference with one thing wrong (no ``D x``: a Mamba-2 layer's skip dropped): ``correct`` false.
    The reference's control is switched on through a copy of the checkout's benchmark files, so no file of the benchmark
    is touched."""
    import shutil

    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(mf.ROOT, "benchmarks"), root / "benchmarks", ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(mf.ROOT, "BENCHMARK.json"), root / "BENCHMARK.json")
    os.symlink(os.path.join(mf.ROOT, "deepspeed_tpu"), root / "deepspeed_tpu")
    path = root / "benchmarks" / "configs" / f"{NAME}.json"
    cfg = json.loads(path.read_text())
    cfg["rehearse"]["reference"]["skip"] = "none"
    path.write_text(json.dumps(cfg))
    out = _rehearse(str(root), 2**31 + 42)
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["correct"] is False and "first_loss_vs_f32" in out.stderr
