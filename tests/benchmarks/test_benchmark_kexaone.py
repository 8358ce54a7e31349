"""The configuration ``k-exaone-236b-l5e8`` (K-EXAONE-236B-A23B's published layers 0-4 as ONE FOUR-CHIP HOST's share of a
64-chip group: window 128 / full GQA 64/8 with q/k norms, the full layer without positions, a norm on each sublayer's
OUTPUT, a dense SwiGLU ahead of a sigmoid router of 8 in 128 with a shared expert) and its cell
``k-exaone-236b-l5e8.pretrain-8k-ep4``: the files pass the manifest's checks and hold the catalog row's keys with the
three patterns whole, the program's tree has the parameters the issue counted, the FLOP module's total is a sum a reader
can check by hand, the program agrees with its plain float32 reference at the rehearsal's width on one device and on four
(the rows exchanged), sixteen hosts' shares of a routed layer add up to the uncut one, the new reader divides the host's
counter by its chips, and the rehearsal on four virtual devices ends ``correct`` true and says what was traced. Nothing
here pins an entry's place in ``BENCHMARK.json`` or counts its cells: a later cell is appended after this one."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmarks.lib import flops, manifest as mf

MANIFEST = mf.load_manifest()
NAME, CELL = "k-exaone-236b-l5e8", "k-exaone-236b-l5e8.pretrain-8k-ep4"
CONFIG = mf.load_json(os.path.join(mf.BENCH, "configs", f"{NAME}.json"))
TRAFFIC = mf.load_json(os.path.join(mf.BENCH, "traffic", "pretrain-8k-ep4.json"))
PUBLISHED = mf.published(CONFIG)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
READER = "moe_exchange_roofline"
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]
KINDS = [["window", "dense"], ["window", "routed"], ["window", "routed"], ["nope", "routed"], ["window", "routed"]]
LISTED = ("train_tokens_per_s", "mfu.train", "stall_share.train", "host_dispatch_ms.train", "collective_exposed_share.train", "mixed_attention_roofline")


def test_the_configuration_and_its_cell_have_no_problems():
    entry = next(c for c in MANIFEST["configs"] if c["name"] == NAME)
    assert mf.config_problems(CONFIG, entry) == [] and entry["reduced"] == REDUCED == CONFIG["reduced"]
    assert mf.problems(MANIFEST) == []
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert (cell["chips"], cell["traffic"], cell["config"]) == (4, "pretrain-8k-ep4", NAME) and len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    for word in ("4 x 8192", "2 a chip", "exchanged", "ZeRO-3", "2,048 rows", "1/16"):
        assert word in cell["why"], word
    trainer = CONFIG["trainer"]
    assert trainer["train_micro_batch_size_per_gpu"] == 1 and trainer["zero_optimization"] == {"stage": 3} and trainer["mesh"] == {"fsdp": 4}
    assert trainer["bf16"] == {"enabled": True} and trainer["optimizer"]["type"] == "adam" and CONFIG["program"]["remat"] is True and CONFIG["env"] == {}
    reported = {m["name"] for g in ("end_to_end", "per_layer") for m in mf.metrics_of(MANIFEST, CELL, g)}
    assert {"setup_s", READER, *LISTED} <= reported and "moe_expert_matmul_roofline" not in reported  # that reader sums the host's rows against a chip's time
    assert TRAFFIC["generator"] == "fixed_batches" and TRAFFIC["params"] == {"seq_len": 8192, "n_batches": 8}
    assert "first_loss_tol" in CONFIG["correct_why"] and 0 < CONFIG["correct"]["first_loss_tol"] <= 0.05
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) <= max(1, len(MANIFEST["workloads"]) // 4)


def test_the_new_metric_is_this_cells_and_the_older_lists_only_gained_it():
    metric = next(m for m in MANIFEST["per_layer"] if m["name"] == READER)
    assert CELL in metric["workloads"] and (metric["unit"], metric["better"], metric["source"], metric["moves"], metric["layer"]) == \
        ("%", "higher", "device_trace", "train_tokens_per_s", "expert layer (moe/)")
    mod = mf.metric_module(READER)
    assert (mod.UNIT, mod.BETTER, mod.SOURCE, mod.LAYER, mod.MOVES) == tuple(metric[k] for k in ("unit", "better", "source", "layer", "moves"))
    order = [w["name"] for w in MANIFEST["workloads"]]
    for shared in LISTED:  # appended to, nothing else changed: the cell is listed, and a list keeps the order of the manifest's cells, whoever joins it later
        listed = next(m for g in ("end_to_end", "per_layer") for m in MANIFEST[g] if m["name"] == shared)["workloads"]
        assert CELL in listed and listed == [cell for cell in order if cell in listed]


@pytest.mark.skipif(not os.path.isfile(CATALOG), reason="the catalog of published configurations is not on this machine")
def test_every_key_is_the_sources_and_the_three_patterns_are_whole():
    row = next(json.loads(line) for line in open(CATALOG) if '"name": "K-EXAONE-236B-A23B"' in line)
    assert CONFIG["source"] == row["source_url"] and row["not_given"] == []
    source = row["config"]
    assert {k for k, v in source.items() if CONFIG.get(k, "missing") != v} == set(REDUCED)
    for pattern in ("layer_types", "mlp_layer_types", "sliding_windows"):
        assert CONFIG[pattern] == source[pattern] and len(source[pattern]) == 48
    assert CONFIG["published_layers"] == source["num_hidden_layers"] == 48 and CONFIG["layers_here"] == [0, 1, 2, 3, 4] and CONFIG["num_hidden_layers"] == 5
    assert [source["layer_types"][i][0] for i in CONFIG["layers_here"]] == list("sssfs") and source["sliding_window_pattern"] == "LLLG"
    assert [source["mlp_layer_types"][i] for i in CONFIG["layers_here"]] == ["dense"] + ["sparse"] * 4 and source["first_k_dense_replace"] == 1
    assert CONFIG["share"]["held"] == {"num_experts": {"published": source["num_experts"], "here": 8}, "vocab_size": {"published": source["vocab_size"], "here": 19200}}
    assert (CONFIG["share"]["chips_per_layer"], CONFIG["share"]["chips_here"], CONFIG["share"]["experts_a_chip"]) == (64, 4, 2)
    assert source["num_experts"] // 64 == 2 and source["vocab_size"] // 8 == 19200 and CONFIG["routed_over"] == source["num_experts"] == 128
    p = CONFIG["program"]
    assert (p["d_model"], p["n_heads"], p["n_kv_heads"], p["head_dims"], p["d_ff"], p["moe_d_ff"], p["moe_shared_d_ff"], p["moe_top_k"], p["moe_num_experts"],
            p["norm_eps"], p["rope_theta"], p["sliding_window"], p["moe_route_scale"]) == \
        (source["hidden_size"], source["num_attention_heads"], source["num_key_value_heads"], source["head_dim"], source["intermediate_size"],
         source["moe_intermediate_size"], source["num_shared_experts"] * source["moe_intermediate_size"], source["num_experts_per_tok"], source["num_experts"],
         source["rms_norm_eps"], source["rope_parameters"]["rope_theta"], source["sliding_window"], source["routed_scaling_factor"]) == \
        (6144, 64, 8, 128, 18432, 2048, 2048, 8, 128, 1e-5, 1e6, 128, 2.5)
    assert p["moe_scoring"] == source["scoring_func"] == "sigmoid" and source["n_group"] == source["topk_group"] == 1 and source["norm_topk_prob"] is True
    assert p["moe_held"] == [0, 8] and p["moe_aux_loss_coef"] == 0.0 and p["qk_norm"] is True and p["tie_embeddings"] is source["tie_word_embeddings"] is False
    assert p["norm_scheme"] == "output" and p["activation"] == "swiglu" and p["pos_emb"] == "rope" and p["norm"] == "rmsnorm" and p["vocab_size"] == CONFIG["vocab_size"]
    # a layer's kind by the patterns at its published index: the program's, the FLOP module's and the reference's readings agree
    counts = flops.for_config(CONFIG)
    assert p["layer_kinds"] == KINDS == [list(kind) for kind in counts.kinds(PUBLISHED)] and counts.windows(PUBLISHED) == [128, 128, 128, 0, 128]
    ref = mf.load_module(os.path.join(mf.ROOT, CONFIG["reference"]["module"]))
    assert ref.kinds(PUBLISHED) == tuple((mixer == "window", 128 if mixer == "window" else 0, ffn == "routed") for mixer, ffn in KINDS)
    assert p["max_seq_len"] == TRAFFIC["params"]["seq_len"] <= source["max_position_embeddings"]
    for key in ("norms", "attention", "rotation", "window", "router", "experts", "auxiliary_loss", "mtp", "optimizer", "weights", "held"):
        assert key in CONFIG["assumed"], key
    for word in ("64 v5e chips", "expert parallel 64", "2 of 128 experts a chip", "ONE HOST", "fifteen hosts are absent", "2,048 rows", "1/16", "2,504,068,864", "10.0 GB"):
        assert word in CONFIG["deployment"], word


def test_the_parameter_count_is_the_issues_sum():
    """2,504,068,864 parameters by the shapes of the program's own tree (issue 66 counted 2,504 M): at the engine's 16 bytes
    (float32 master and two moments, the carried bf16 copy, a bf16 gradient) 40.1 GB, 10.0 GB a chip of four."""
    import jax

    from benchmarks.lib import weights

    shapes = weights.param_shapes(weights.build_model(CONFIG))
    count = lambda tree: sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))
    d = 6144
    attention, dense = 2 * d * 8192 + 2 * d * 1024 + 2 * 128, 3 * d * 18432
    routed = d * 128 + 128 + 8 * 3 * d * 2048 + 3 * d * 2048
    assert (attention, dense, routed) == (113_246_464, 339_738_624, 340_525_184)
    assert count(shapes["layer_0"]) == attention + dense + 2 * d and all(count(shapes[f"layer_{i}"]) == attention + routed + 2 * d for i in (1, 2, 3, 4))
    assert count(shapes["wte"]) == count(shapes["lm_head"]) == 19200 * d
    assert count(shapes) == 2_504_068_864 and 10.0e9 < 16 * count(shapes) / 4 < 10.1e9 and 16 * count(shapes) > 2 * 15.75e9


def test_the_flop_count_is_the_hand_written_sum():
    mod = flops.for_config(CONFIG)
    S, d = 8192, 6144
    proj = 2 * (2 * d * 8192 + 2 * d * 1024)
    band, square = 4 * 64 * 128 * (128 * S - 128 * 127 / 2) / S, 4 * 64 * 128 * (S + 1) / 2
    router, experts, shared, dense, head = 2 * d * 128, 0.5 * 2 * 3 * d * 2048, 2 * 3 * d * 2048, 2 * 3 * d * 18432, 2 * d * 19200  # 8 x 8 / 128: HALF an expert evaluation a token, here
    forward = 5 * proj + 4 * band + square + 4 * (router + experts + shared) + dense + head
    assert mod.forward_flops_per_token(PUBLISHED, S) == pytest.approx(forward) and mod.train_flops_per_token(PUBLISHED, S) == pytest.approx(3 * forward)
    assert mod.kept_pairs(S, 128) == sum(min(t + 1, 128) for t in range(S)) and mod.kept_pairs(S, 0) == S * (S + 1) / 2
    assert 3 * forward * S == pytest.approx(65.3e12, rel=5e-3)  # a chip and step; the issue's "about 87 TFLOP" counted the forward made again under remat
    assert mod.exchange_cost(PUBLISHED, 1000.0) == {"bytes_sent": 4.0 * 1000 * d * 2}
    fwd, bwd = (mod.mixed_attention_cost(PUBLISHED, 1, S, ("window", "routed"), backward=b) for b in (False, True))
    assert fwd["flops"] == 4.0 * 64 * 128 * mod.kept_pairs(S, 128) and bwd["flops"] == 2 * fwd["flops"]
    assert mod.mixed_attention_cost(PUBLISHED, 1, S, ("nope", "routed"), backward=False)["flops"] == 4.0 * 64 * 128 * S * (S + 1) / 2


def _tiny():
    """The rehearsal's width, all five layers, float32."""
    from benchmarks.lib import weights

    r = CONFIG["rehearse"]
    cfg = dict(CONFIG, **r["published"], reference=r["reference"])
    cfg["program"] = dict(CONFIG["program"], **r["program"], dtype="float32")
    return cfg, weights.build_model(cfg)


def _rows(seed, batch=4, vocab=512):
    gen = mf.load_module(os.path.join(mf.BENCH, "generators", "fixed_batches.py"))
    return gen.generate(TRAFFIC["rehearse"]["params"], seed, 40.0, {"vocab_size": vocab, "global_batch": batch})["batches"][0]["input_ids"]


def _stirred(model):
    import jax

    params = model.init(jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 16), np.int32)})
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    by = lambda path: 0.3 if "select_bias" in jax.tree_util.keystr(path) else 0.05
    return jax.tree_util.tree_unflatten(tree, [x + by(path) * jax.random.normal(jax.random.PRNGKey(7 + i), x.shape) for i, (path, x) in enumerate(leaves)])


gap = lambda a, b: np.max(np.abs(np.asarray(a, np.float64) - np.asarray(b, np.float64))) / (1.0 + np.max(np.abs(np.asarray(b, np.float64))))


@pytest.mark.parametrize("case", ["one_device", "four_devices", "norms_pre", "windows_none", "rotation_all", "no_qk_norm", "no_final_norm"])
def test_the_program_agrees_with_the_plain_reference_in_logits_loss_and_every_gradient(case):
    """Five layers at the rehearsal's width on the rehearsal's traffic (four rows of 96), seeded weights with every leaf
    stirred (the selection bias too, by more), float32 at the highest matmul precision on both sides: 2e-5 of the largest
    logit and 5e-5 of a leaf's largest gradient entry (the order of float32 sums), on one device and on four virtual ones
    under the cell's mesh (``fsdp=4``: every chip its own row, experts 4-7 one a chip, the rows exchanged). Under a control
    of the reference (norms on the sublayers' inputs, window layers that see every key, the full layer rotated, no q/k
    norm, no final norm) the same comparison FAILS: the logits lie 1e-2 and more away."""
    import jax
    import jax.numpy as jnp

    from benchmarks.lib import reference
    from deepspeed_tpu.parallel.mesh import initialize_mesh, reset_mesh
    from deepspeed_tpu.runtime.config import MeshConfig

    cfg, model = _tiny()
    ids, params = _rows(5), _stirred(model)
    ref_logits, ref_loss = reference.for_config(cfg)
    pub = mf.published(cfg)
    wrong = {"norms_pre": {"norms": "pre"}, "windows_none": {"windows": "none"}, "rotation_all": {"rotation": "all"}, "no_qk_norm": {"no_qk_norm": True},
             "no_final_norm": {"no_final_norm": True}}.get(case, {})
    ref_cfg = dict(cfg["reference"], **wrong)
    reset_mesh()
    try:
        with jax.default_matmul_precision("highest"):
            theirs_logits = ref_logits(params, ids, pub, ref_cfg, jnp.float32)
            assert theirs_logits.shape == (4, 96, 512)
            if case == "four_devices":
                topo = initialize_mesh(MeshConfig.from_dict({"fsdp": 4}), devices=jax.devices()[:4], force=True)
                with topo.mesh:
                    ours_logits = jax.jit(model.apply)(params, ids)
                    ours, g_ours = jax.jit(jax.value_and_grad(lambda p: model.loss_fn(p, {"input_ids": ids})))(params)
            else:
                ours_logits = model.apply(params, ids)
                if wrong:
                    assert gap(ours_logits, theirs_logits) > 1e-2
                    return
                ours, g_ours = jax.value_and_grad(lambda p: model.loss_fn(p, {"input_ids": ids}))(params)
            assert gap(ours_logits, theirs_logits) < 2e-5
            theirs, g_theirs = jax.value_and_grad(lambda p: ref_loss(ref_logits(p, ids, pub, ref_cfg, jnp.float32), ids))(params)
    finally:
        reset_mesh()
    assert gap(ours, theirs) < 1e-6
    theirs_by_path = dict(jax.tree_util.tree_leaves_with_path(g_theirs))
    mine = jax.tree_util.tree_leaves_with_path(g_ours)
    assert len(mine) == len(theirs_by_path) == 3 + 11 + 4 * 16
    for path, leaf in mine:
        assert gap(leaf, theirs_by_path[path]) < 5e-5, jax.tree_util.keystr(path)
        assert (float(jnp.max(jnp.abs(leaf))) > 0) == ("select_bias" not in jax.tree_util.keystr(path))  # the bias is a buffer


def test_sixteen_hosts_shares_of_a_routed_layer_add_up_to_the_uncut_layer():
    """The reference's routed FFN for each of sixteen hosts' experts (one a host at this width: host ``j`` holds expert
    ``j`` of 16), the shared expert counted ONCE, summed, against the uncut layer over all 16 with the shared expert; and
    the PROGRAM's layer as host 5's share (``moe_held = (5, 1)``) against the reference's part for that host."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.moe.layer import RoutedMoE

    cfg, _ = _tiny()
    pub, ref = mf.published(cfg), mf.load_module(os.path.join(mf.ROOT, CONFIG["reference"]["module"]))
    whole = RoutedMoE(hidden_size=64, num_experts=16, k=4, d_ff=32, held=None, shared_ff=32, scale=2.5, scoring="sigmoid", dtype=jnp.float32, name="routed")
    h = jax.random.normal(jax.random.PRNGKey(3), (2, 96, 64))
    p = whole.init(jax.random.PRNGKey(4), h)["params"]
    p = dict(p, select_bias=0.3 * jax.random.normal(jax.random.PRNGKey(5), (16,)))
    share = lambda j: {"routed": dict(p, **{name: p[name][j:j + 1] for name in ("experts_wg", "experts_wi", "experts_wo")})}
    uncut = ref.layer_part({"routed": p}, h, pub, cfg["reference"], jnp.float32, first=0, held=16, shared=True)
    parts = [ref.layer_part(share(j), h, pub, cfg["reference"], jnp.float32, first=j, held=1, shared=j == 0) for j in range(16)]
    assert gap(sum(parts), uncut) < 1e-6 and gap(parts[5], uncut) > 1e-2
    with jax.default_matmul_precision("highest"):
        ours_whole = whole.apply({"params": p}, h)
        host5 = RoutedMoE(hidden_size=64, num_experts=16, k=4, d_ff=32, held=(5, 1), shared_ff=32, scale=2.5, scoring="sigmoid", dtype=jnp.float32)
        ours_5 = host5.apply({"params": share(5)["routed"]}, h)
    assert gap(ours_whole, uncut) < 1e-5
    assert gap(ours_5, ref.layer_part(share(5), h, pub, cfg["reference"], jnp.float32, first=5, held=1, shared=True)) < 1e-5


def _record(ops, counters, steps=4, chips=4, config=CONFIG):
    """A traced training record of ``chips`` devices, each with ``steps`` executions of the step program and these operations."""
    dev = {"ops": ops, "op_counts": {k: 1 for k in ops}, "modules": [("jit_fused_step(123)", 0.1 * i, 0.1 * i + 0.09) for i in range(steps)]}
    return {"reduced": {"devices": {str(i): dev for i in range(chips)}, "window_s": 1.0}, "published": mf.published(config), "config": config,
            "train": {"micro_batch": 1, "seq_len": 8192, "steps": 40}, "device": {"kind": "TPU v5 lite", "count": chips},
            "counters": counters, "end_to_end": {"train_tokens_per_s": 1.0}}


EXCHANGE = {"all-to-all all-to-all bf16[4,2048,6144]{2,1,0:T(8,128)(2,1)}": 0.040, "all-to-all all-to-all s32[4,1,2048]{2,1,0}": 0.0004}
OTHER = {'gmm custom-call bf16[8192,2048]{1,0:T(8,128)(2,1)} custom_call_target="tpu_custom_call"': 0.06, "all-gather all-gather bf16[6144,8,128]{2,1,0}": 0.05,
         "fusion fusion bf16[1,8192,6144]{2,1,0:T(8,128)(2,1)}": 0.03}


def test_the_reader_divides_the_hosts_counter_by_its_chips_and_reads_nothing_on_the_parent():
    """``moe_rows_sent_total`` is summed over the host (a layer, a step): 40 steps x 4 layers x 4 chips x 3,072 rows (three
    quarters of a chip's 4,096 pairs leave it under a uniform router). A chip's least time for its own 4 x 3,072 rows, four
    crossings each, over a chip's seconds: the same share whether the trace holds one chip's seconds or four chips'."""
    from benchmarks.lib.peaks import peaks_for

    mod = mf.metric_module(READER)
    sent = 40 * 4 * 4 * 3072.0
    share = mod.read(_record(dict(EXCHANGE, **OTHER), {"moe_rows_sent_total": sent}))
    need = 4 * 3072 * 4 * 6144 * 2 * 8 / peaks_for("TPU v5 lite")["ici_bits_per_s"]  # a chip and step
    assert share == pytest.approx(100 * 4 * need / sum(EXCHANGE.values())) and 0 < share < 100
    assert mod.read(_record(dict(EXCHANGE, **OTHER), {"moe_rows_sent_total": sent / 4}, chips=1)) == pytest.approx(share)
    assert mod.read(dict(_record(dict(EXCHANGE, **OTHER), {}), program={"counters": {}})) is None  # the parent: a program with no such counter
    assert mod.read(_record(dict(EXCHANGE, **OTHER), {"moe_rows_sent_total": 0.0})) is None  # no row travelled (one chip, or the parts summed)
    assert mod.read(_record(OTHER, {"moe_rows_sent_total": sent})) is None              # a trace without an all-to-all
    assert mod.read(dict(_record(dict(EXCHANGE, **OTHER), {"moe_rows_sent_total": sent}), reduced=None)) is None  # an untraced run
    for other in ("smallthinker-21b-l4e8", "olmo-1b"):  # another configuration's FLOP module names no such cost: nothing, and no raise
        cfg = mf.load_json(os.path.join(mf.BENCH, "configs", f"{other}.json"))
        assert mod.read(_record(dict(EXCHANGE, **OTHER), {"moe_rows_sent_total": sent}, config=cfg)) is None
    # the accepted reader that now lists the cell reads its flash calls by the configuration's own cost, a chip's
    flash = {'flash_fwd custom-call (bf16[64,8192,128]{2,1,0:T(8,128)(2,1)}, f32[64,16,1,512]{3,2,1,0:T(1, custom_call_target="tpu_custom_call"': 0.1}
    assert 0 < mf.metric_module("mixed_attention_roofline").read(_record(dict(flash, **OTHER), {})) < 100


def test_the_rehearsal_on_four_virtual_devices_ends_correct_and_says_what_was_traced():
    """A process of its own, as the driver starts one: four virtual devices, ZeRO-3 over ``fsdp=4``, the rows exchanged."""
    out = subprocess.run([sys.executable, os.path.join(mf.ROOT, "benchmarks", "run.py"), "--workload", CELL, "--rehearse", "--seed", str(2**31 + 42),
                          "--seconds", "1"], capture_output=True, text=True, timeout=900, cwd=mf.ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    last, counters = json.loads(lines[-1]), json.loads(lines[-2])["extras"]["counters"]
    assert last["correct"] is True and last["device"]["count"] == 4 and "first_loss_vs_f32" in out.stderr  # the f32 rule is the rehearsal's
    for series in ('{form="rows",path="dispatch",region="ffn/exchange"}', '{form="rows",path="return",region="ffn/exchange"}',
                   '{op="window",pass="fwd",path="xla",region="mixer/kernel",window="16"}', '{op="nope",pass="fwd",path="xla",region="mixer/kernel"}',
                   '{path="sigmoid",region="ffn/router"}'):
        assert "program_regions_traced_total" + series in counters, series
    steps = counters["train_steps_total"]
    assert counters["moe_rows_dropped_total"] == 0 and steps > 0
    assert 0 < counters["moe_rows_sent_total"] < counters["moe_rows_routed_here_total"]
    assert 0.5 < counters["moe_rows_routed_here_total"] / (steps * 4 * 4 * 96) < 2.0  # 4 x 96 x 4 x 4 / 16 = 384 uniform pairs a layer on the host, four routed layers
    line = next(l for l in lines if "program first call: family=train" in l)
    for word in ("layer_kinds=nope+routed:1,window+dense:1,window+routed:3", "window_path=xla", "nope_path=xla", "window_keys=16", "moe_exchange=rows",
                 "moe_router=sigmoid+compare_sum", "compute_copy=carried", "remat_keeps=flash_attention+projection+routed_ffn"):
        assert word in line, word
