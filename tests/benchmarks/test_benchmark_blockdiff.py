"""The configuration ``sdar-30b-a3b-l4e16`` (SDAR-30B-A3B-Chat in block-diffusion TRAINING as four layers of one chip:
GQA under the block mask over a doubled row, a masked-token loss weighted a block, softmax-routed 8 of 128) and its cell
``sdar-30b-a3b-l4e16.blockdiff-8k``: the files pass the manifest's checks and hold the catalog row's widths, ``reduced``
and ``share`` agree, the program's tree has the parameters the issue counted, the FLOP module's total is a sum a reader
can check by hand, the generator's noise is the stated law and repeats by seed, the program agrees with its plain float32
reference at the rehearsal's width, the reader reads its kernels and nothing else on a recorded trace's labels, and the
rehearsal ends ``correct`` true, and false under a control. Nothing here pins an entry's place in ``BENCHMARK.json``: a
later cell is appended after this one."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmarks.lib import flops, manifest as mf

MANIFEST = mf.load_manifest()
NAME, CELL = "sdar-30b-a3b-l4e16", "sdar-30b-a3b-l4e16.blockdiff-8k"
CONFIG = mf.load_json(os.path.join(mf.BENCH, "configs", f"{NAME}.json"))
TRAFFIC = mf.load_json(os.path.join(mf.BENCH, "traffic", "blockdiff-8k.json"))
PUBLISHED = mf.published(CONFIG)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
READER = "blockdiff_attention_roofline"


def test_the_configuration_and_its_cell_have_no_problems():
    entry = next(c for c in MANIFEST["configs"] if c["name"] == NAME)
    assert mf.config_problems(CONFIG, entry) == []
    assert [p for p in mf.problems(MANIFEST) if NAME in p or "blockdiff" in p] == []  # ``manifest.problems`` has nothing new
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert (cell["chips"], cell["traffic"], cell["config"]) == (1, "blockdiff-8k", NAME) and len(cell["why"]) <= 200
    for word in ("16,384", "2 a trained token", "288 of 1,024", "1,024 rows an expert", "1/8"):  # what a token is here, and what weighs more
        assert word in cell["why"]
    assert CONFIG["trainer"]["train_micro_batch_size_per_gpu"] == 1 and CONFIG["trainer"]["zero_optimization"]["stage"] == 0
    assert CONFIG["trainer"]["optimizer"] == {"type": "adam", "params": {"lr": 3e-7}} and CONFIG["program"]["remat"] is True
    reported = {m["name"] for g in ("end_to_end", "per_layer") for m in mf.metrics_of(MANIFEST, CELL, g)}
    assert {"train_tokens_per_s", "setup_s", "mfu.train", "moe_expert_matmul_roofline", READER} <= reported  # at least what its PR brought: a later reader may list the cell
    assert TRAFFIC["generator"] == "block_diffusion_batches" and TRAFFIC["params"] == {"seq_len": 8192, "block_len": 4, "n_batches": 8}
    assert TRAFFIC["params"]["block_len"] == CONFIG["block_length"] == CONFIG["program"]["block_length"]
    assert "first_loss_tol" in CONFIG["correct_why"] and 0 < CONFIG["correct"]["first_loss_tol"] <= 0.05


def test_the_new_metric_is_this_cells_alone():
    metric = next(m for m in MANIFEST["per_layer"] if m["name"] == READER)
    assert CELL in metric["workloads"] and (metric["unit"], metric["better"], metric["source"], metric["moves"]) == \
        ("%", "higher", "device_trace", "train_tokens_per_s")
    mod = mf.metric_module(READER)
    assert (mod.UNIT, mod.BETTER, mod.SOURCE, mod.LAYER, mod.MOVES) == tuple(metric[k] for k in ("unit", "better", "source", "layer", "moves"))
    assert metric["layer"] in {m["layer"] for m in MANIFEST["per_layer"] if m["name"] != READER}  # a layer BENCHMARK.json already names


@pytest.mark.parametrize("case,needle", [
    ("as_it_is", None),
    ("a_width_reduced", "reduced names a width"),
    ("experts_per_token_reduced", "reduced names a width"),
    ("a_held_count_not_reduced", "which reduced does not list"),
    ("the_share_disagrees", "are held here, the file says"),
    ("the_entry_disagrees", "reduced differs between BENCHMARK.json and its file"),
])
def test_reduced_and_share_agree_and_the_checks_find_what_does_not(case, needle):
    cfg = json.loads(json.dumps(CONFIG))
    entry = dict(next(c for c in MANIFEST["configs"] if c["name"] == NAME))
    if case == "a_width_reduced":
        cfg["reduced"] = entry["reduced"] = CONFIG["reduced"] + ["moe_intermediate_size"]
    elif case == "experts_per_token_reduced":
        cfg["reduced"] = entry["reduced"] = CONFIG["reduced"] + ["num_experts_per_tok"]
    elif case == "a_held_count_not_reduced":
        cfg["reduced"] = entry["reduced"] = [k for k in CONFIG["reduced"] if k != "num_experts"]
    elif case == "the_share_disagrees":
        cfg["share"]["held"]["num_experts"]["here"] = 32
    elif case == "the_entry_disagrees":
        entry["reduced"] = CONFIG["reduced"][:-1]
    found = mf.config_problems(cfg, entry)
    assert (found == []) == (needle is None) and (needle is None or any(needle in p for p in found))


@pytest.mark.skipif(not os.path.isfile(CATALOG), reason="the catalog of published configurations is not on this machine")
def test_every_width_is_the_sources():
    row = next(json.loads(line) for line in open(CATALOG) if '"name": "SDAR-30B-A3B-Chat"' in line)
    assert CONFIG["source"] == row["source_url"] and row["not_given"] == ["block length", "noise schedule"]
    source = row["config"]
    differs = {k for k, v in source.items() if CONFIG.get(k, "missing") != v}
    assert differs == set(CONFIG["reduced"]) == {"num_hidden_layers", "num_experts", "vocab_size"}
    assert CONFIG["share"] == {"chips_per_layer": 8, "held": {"num_experts": {"published": source["num_experts"], "here": 16},
                                                             "vocab_size": {"published": source["vocab_size"], "here": 18992}}}
    assert source["num_experts"] // 8 == 16 == CONFIG["num_experts"] and source["vocab_size"] // 8 == 18992 == CONFIG["vocab_size"]
    assert CONFIG["num_hidden_layers"] == 4 and CONFIG["routed_over"] == source["num_experts"] == 128 and CONFIG["first_k_dense_replace"] == 0
    p = CONFIG["program"]
    assert (p["d_model"], p["n_heads"], p["n_kv_heads"], p["head_dims"], p["moe_d_ff"], p["moe_top_k"], p["moe_num_experts"], p["norm_eps"], p["rope_theta"]) == \
        (source["hidden_size"], source["num_attention_heads"], source["num_key_value_heads"], source["head_dim"], source["moe_intermediate_size"],
         source["num_experts_per_tok"], source["num_experts"], source["rms_norm_eps"], source["rope_theta"]) == (2048, 32, 4, 128, 768, 8, 128, 1e-6, 1e6)
    assert p["tie_embeddings"] is source["tie_word_embeddings"] is False and source["attention_bias"] is False and source["norm_topk_prob"] is True
    assert p["moe_scoring"] == "softmax" and p["moe_route_scale"] == 1.0 and p["moe_shared_d_ff"] == 0 and p["moe_held"] == [0, 16]
    assert p["layer_kinds"] == [["blockdiff", "routed"]] * 4 and p["vocab_size"] == CONFIG["vocab_size"] and p["mask_token_id"] == p["vocab_size"] - 1
    assert p["max_seq_len"] == TRAFFIC["params"]["seq_len"] <= source["max_position_embeddings"] and p["blockdiff_qk_init_scale"] == 3.0
    for key in ("training_form", "block_length", "objective", "noise", "no_shift", "mask_token", "qk_norm", "rotation", "optimizer", "weights", "start", "held"):
        assert key in CONFIG["assumed"]
    for word in ("8 v5e chips", "8 ways", "pipeline", "absent", "1,024 rows"):
        assert word in CONFIG["deployment"]


def test_the_parameter_count_is_the_issues_sum():
    """456.3 M parameters by the shapes of the program's own tree: 5.48 GB of float32 master and two moments."""
    import jax

    from benchmarks.lib import weights

    shapes = weights.param_shapes(weights.build_model(CONFIG))  # initialised on 16 positions: two halves of two blocks
    count = lambda tree: sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))
    d = 2048
    attention = d * 4096 + 2 * d * 512 + 4096 * d + 2 * 128
    assert count(shapes["layer_0"]["blockdiff"]) == attention == 18_874_624
    assert count(shapes["layer_0"]["routed"]) == d * 128 + 16 * 3 * d * 768 == 262_144 + 75_497_472
    assert all(count(shapes[f"layer_{i}"]) == 94_638_336 for i in range(4))
    assert count(shapes["wte"]) == count(shapes["lm_head"]) == 18992 * d == 38_895_616
    assert count(shapes) == 4 * 94_638_336 + 2 * 38_895_616 + d == 456_346_624 and 5.47e9 < 12 * count(shapes) < 5.48e9


def test_the_flop_count_is_the_hand_written_sum():
    mod = flops.for_config(CONFIG)
    S, L, B, d = 16384, 8192, 4, 2048
    assert mod.kept_pairs(PUBLISHED, S) == L * B + L * (L - B) // 2 + L * (L + B) // 2 == L * L + L * B == 67_141_632
    proj = 2 * (d * 4096 + 2 * d * 512 + 4096 * d)
    attention = 4 * 32 * 128 * 67_141_632 / S
    routed = 2 * (d * 128 + 1 * 3 * d * 768)  # 8 x 16 / 128 = one expert evaluation a position, here
    layer = proj + attention + routed
    assert layer == pytest.approx(114.85e6, rel=1e-3) and attention / layer == pytest.approx(0.585, abs=0.005)  # 58% at 8k
    forward = 4 * layer + d * 18992  # the head over half of the positions
    assert mod.forward_flops_per_token(PUBLISHED, S) == pytest.approx(forward) and forward * S == pytest.approx(8.16e12, rel=1e-2)
    assert mod.train_flops_per_token(PUBLISHED, S) == pytest.approx(3 * forward) and mod.blockdiff_layers(PUBLISHED) == 4
    at_4k = 4 * 32 * 128 * mod.kept_pairs(PUBLISHED, 8192) / 8192
    assert at_4k / (proj + at_4k + routed) == pytest.approx(0.41, abs=0.01)
    fwd, bwd = (mod.blockdiff_attention_cost(PUBLISHED, 1, S, backward=b) for b in (False, True))
    assert fwd["flops"] == 4.0 * 32 * 128 * 67_141_632 and bwd["flops"] == 2 * fwd["flops"]
    assert fwd["bytes"] == 2.0 * S * (2 * 4096 + 2 * 512) and bwd["bytes"] == 2.0 * S * (5 * 4096 + 4 * 512)
    assert mod.expert_matmul_cost(PUBLISHED, 16384.0, backward=False)["flops"] == 2.0 * 3 * d * 768 * 16384


def _generate(params, seed, batch=1, vocab=18992):
    gen = mf.load_module(os.path.join(mf.BENCH, "generators", "block_diffusion_batches.py"))
    return gen.generate(params, seed, 40.0, {"vocab_size": vocab, "global_batch": batch})["batches"]


@pytest.mark.parametrize("block", [4, 16])
def test_the_generators_noise_is_the_stated_law_and_repeats_by_seed(block):
    params = {"seq_len": 8192, "block_len": block, "n_batches": 8}
    batches = _generate(params, 2**31 + 77)
    again, other = _generate(params, 2**31 + 77), _generate(params, 2**31 + 78)
    assert len(batches) == 8 and all(b["input_ids"].shape == (1, 16384) and b["input_ids"].dtype == np.int32 for b in batches)
    assert all((a["input_ids"] == b["input_ids"]).all() for a, b in zip(batches, again))  # the same seed: the same rows AND the same noise
    assert not (batches[0]["input_ids"] == other[0]["input_ids"]).all() and not (batches[0]["input_ids"] == batches[1]["input_ids"]).all()
    ids = np.stack([b["input_ids"][0] for b in batches])
    xt, x0 = ids[:, :8192], ids[:, 8192:]
    masked = xt == 18991
    assert x0.max() <= 18990 and x0.min() >= 0 and (xt[~masked] == x0[~masked]).all()  # the mask's row is never a clean token
    m = masked.reshape(8, 8192 // block, block).sum(-1)
    assert m.min() >= 1 and m.max() == block  # every block masks at least one position, so a row's weights sum to L
    counts = np.bincount(m.ravel(), minlength=block + 1)[1:] / m.size
    assert np.abs(counts - 1.0 / block).max() < 0.02  # m uniform on 1 .. B
    assert abs(masked.mean() - (block + 1) / (2 * block)) < 0.01  # 0.625 at B = 4
    by_place = masked.reshape(-1, block).mean(0)
    assert np.abs(by_place - by_place.mean()).max() < 0.02  # a uniformly chosen subset: no place in a block is favoured
    with pytest.raises(ValueError, match="not a whole number of blocks"):
        _generate({"seq_len": 100, "block_len": 16, "n_batches": 1}, 0)


def _tiny():
    """The rehearsal's width, all four layers, float32."""
    from benchmarks.lib import weights

    r = CONFIG["rehearse"]
    cfg = dict(CONFIG, **r["published"], reference=r["reference"])
    cfg["program"] = dict(CONFIG["program"], **r["program"], dtype="float32")
    return cfg, weights.build_model(cfg)


def test_the_program_agrees_with_the_plain_reference_in_logits_loss_and_every_gradient():
    """Four layers at the rehearsal's width on the rehearsal's traffic, seeded weights with every leaf stirred, float32 at
    the highest matmul precision on both sides: 2e-5 of the largest logit and 5e-5 of a leaf's largest gradient entry
    (the order of float32 sums; ``tests/unit/test_blockdiff_layers.py`` has the controls that read orders over these).
    The loss is the harness's own pair, ``reference.for_config``'s, which reads the file's block length."""
    import jax
    import jax.numpy as jnp

    from benchmarks.lib import reference

    cfg, model = _tiny()
    ids = _generate(TRAFFIC["rehearse"]["params"] | {"block_len": 4}, 5, batch=2, vocab=509)[0]["input_ids"]
    params = model.init(jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 16), np.int32)})
    leaves, tree = jax.tree_util.tree_flatten(params)
    params = jax.tree_util.tree_unflatten(tree, [x + 0.05 * jax.random.normal(jax.random.PRNGKey(7 + i), x.shape) for i, x in enumerate(leaves)])
    ref_logits, ref_loss = reference.for_config(cfg)
    pub = mf.published(cfg)
    close = lambda a, b, tol: np.testing.assert_array_less(np.max(np.abs(np.asarray(a, np.float64) - np.asarray(b, np.float64))),
                                                            tol * (1.0 + np.max(np.abs(np.asarray(b, np.float64)))))
    with jax.default_matmul_precision("highest"):
        theirs_logits = ref_logits(params, ids, pub, cfg["reference"], jnp.float32)
        assert theirs_logits.shape == (2, 96, 509)  # the noised half's
        close(model.apply(params, ids)[:, :96], theirs_logits, 2e-5)
        ours, g_ours = jax.value_and_grad(lambda p: model.loss_fn(p, {"input_ids": ids}))(params)
        theirs, g_theirs = jax.value_and_grad(lambda p: ref_loss(ref_logits(p, ids, pub, cfg["reference"], jnp.float32), ids))(params)
    close(ours, theirs, 1e-6)
    theirs_by_path = dict(jax.tree_util.tree_leaves_with_path(g_theirs))
    mine = jax.tree_util.tree_leaves_with_path(g_ours)
    assert len(mine) == len(theirs_by_path) == 3 + 4 * 12
    for path, leaf in mine:
        close(leaf, theirs_by_path[path], 5e-5)
        assert float(jnp.max(jnp.abs(leaf))) > 0


def _record(ops, steps=4, config=CONFIG):
    """A traced training record with one device, ``steps`` executions of the step program and these operations."""
    dev = {"ops": ops, "op_counts": {k: 1 for k in ops}, "modules": [("jit_fused_step(123)", 0.1 * i, 0.1 * i + 0.09) for i in range(steps)]}
    return {"reduced": {"devices": {"0": dev}, "window_s": 1.0}, "published": mf.published(config), "config": config,
            "train": {"micro_batch": 1, "seq_len": 16384, "steps": 100}, "device": {"kind": "TPU v5 lite", "count": 1},
            "counters": {}, "end_to_end": {"train_tokens_per_s": 1.0}}


# labels as ``lib/trace.py::op_label`` makes them from a v5e trace of this cell's step, and their seconds over four steps
# (my chip run, PR 49)
ATTENTION_OPS = {'blockdiff_bwd custom-call (bf16[32,16384,128]{2,1,0:T(8,128)(2,1)}, bf16[32,16384,128]{2,1,0:T(8 custom_call_target="tpu': 0.33162669,
                 'blockdiff_fwd custom-call (bf16[32,16384,128]{2,1,0:T(8,128)(2,1)}, f32[32,32,1,512]{3,2,1,0:T(1 custom_call_target="tpu': 0.196547007}
OTHER = {"subtract_convert_fusion fusion (bf16[1,16384,32,64]{3,1,2,0:T(8,128)(2,1)}, bf16[1,16384,32,64]{3,1,2": 0.036802546,
         'gmm custom-call bf16[65536,2048]{1,0:T(8,128)(2,1)} custom_call_target="tpu_custom_call"': 0.030762149,
         'flash_fwd custom-call (bf16[16,8192,128]{2,1,0}, f32[16,16,1,512]{3,2,1,0}) custom_call_target="tpu_custom_call"': 0.2,  # a causal call: not this mask's
         'sparse_fwd custom-call bf16[32,8192,128]{2,1,0} custom_call_target="tpu_custom_call"': 0.1}


def test_the_reader_reads_its_kernels_and_nothing_else():
    from benchmarks.lib.peaks import peaks_for

    mod = mf.metric_module(READER)
    share = mod.read(_record(dict(ATTENTION_OPS, **OTHER)))
    assert share == pytest.approx(50.75, abs=0.01)  # the chip run's own reading of these seconds
    assert mod.read(_record(dict({k: 2 * v for k, v in ATTENTION_OPS.items()}, **OTHER))) == pytest.approx(share / 2)
    assert mod.read(_record(OTHER)) is None                                            # a program without the kernels: the parent commit's
    assert mod.read(dict(_record(dict(ATTENTION_OPS, **OTHER)), reduced=None)) is None  # an untraced run
    assert mod.read(dict(_record(dict(ATTENTION_OPS, **OTHER)), config={})) is None      # a configuration with no such layers
    other = mf.load_json(os.path.join(mf.BENCH, "configs", "keye-vl2-30b-l4e16.json"))
    assert mod.read(_record(dict(ATTENTION_OPS, **OTHER), config=other)) is None         # another configuration's FLOP module: nothing, and no raise
    counts, peaks = flops.for_config(CONFIG), peaks_for("TPU v5 lite")
    need = [flops.roofline_seconds(counts.blockdiff_attention_cost(PUBLISHED, 1, 16384, backward=b), peaks) for b in (False, True)]
    assert [n["bound"] for n in need] == ["compute", "compute"]
    assert share == pytest.approx(100 * 4 * 4 * sum(n["seconds"] for n in need) / sum(ATTENTION_OPS.values()))
    # the older attention readers find nothing of theirs in this cell's trace
    for older in ("flash_attention_roofline", "sparse_attention_roofline", "diff_attention_roofline"):
        assert mf.metric_module(older).read(_record(dict(ATTENTION_OPS, **{k: v for k, v in OTHER.items() if "flash" not in k and "sparse" not in k}))) is None


def _rehearse(seed, **env):
    out = subprocess.run([sys.executable, os.path.join(mf.ROOT, "benchmarks", "run.py"), "--workload", CELL, "--rehearse",
                          "--seed", str(seed), "--seconds", "1"], capture_output=True, text=True, timeout=900,
                         cwd=mf.ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu", **env))
    assert out.returncode == 0, out.stderr[-3000:]
    return out


def test_the_rehearsal_ends_correct_and_says_what_was_traced():
    """A process of its own, as the driver starts one: the package's log line goes to that process's stdout."""
    out = _rehearse(2**31 + 42)
    lines = out.stdout.strip().splitlines()
    last, counters = json.loads(lines[-1]), json.loads(lines[-2])["extras"]["counters"]
    assert last["correct"] is True and "first_loss_vs_f32" in out.stderr  # the f32 rule is the rehearsal's
    assert 'program_regions_traced_total{op="blockdiff",pass="fwd",path="xla",region="mixer/kernel"}' in counters
    assert counters["moe_rows_dropped_total"] == 0 and counters["moe_fallback_layers_total"] == 0
    steps = counters["train_steps_total"]
    assert counters["diffusion_positions_total"] > 0 and counters["diffusion_positions_total"] % 96 == 0 and steps > 0
    assert 0.5 < counters["diffusion_masked_positions_total"] / counters["diffusion_positions_total"] < 0.75  # 0.625 over 48 blocks of a few batches
    line = next(l for l in lines if "program first call: family=train" in l)
    for word in ("block_traces=1", "layer_kinds=blockdiff+routed:4", "blockdiff_path=xla", "moe_router=softmax+compare_sum",
                 "remat_keeps=flash_attention+projection+routed_ffn"):
        assert word in line


def test_the_rehearsal_ends_false_under_a_control(tmp_path):
    """The same run against a reference with one thing wrong (no final norm: the cell's own control): ``correct`` false
    by ``first_loss_diff``. The reference's control is switched on through a copy of the checkout's benchmark files, so
    no file of the benchmark is touched."""
    import shutil

    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(mf.ROOT, "benchmarks"), root / "benchmarks", ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(mf.ROOT, "BENCHMARK.json"), root / "BENCHMARK.json")
    os.symlink(os.path.join(mf.ROOT, "deepspeed_tpu"), root / "deepspeed_tpu")
    path = root / "benchmarks" / "configs" / f"{NAME}.json"
    cfg = json.loads(path.read_text())
    cfg["rehearse"]["reference"]["no_final_norm"] = True
    path.write_text(json.dumps(cfg))
    out = subprocess.run([sys.executable, str(root / "benchmarks" / "run.py"), "--workload", CELL, "--rehearse", "--seed", str(2**31 + 42),
                          "--seconds", "1"], capture_output=True, text=True, timeout=900, cwd=str(root), env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-3000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["correct"] is False and "first_loss_diff" in out.stderr
    diff = float(next(l for l in out.stderr.splitlines() if l.startswith("correct: first_loss_diff")).split()[2])
    assert diff > 0.05
