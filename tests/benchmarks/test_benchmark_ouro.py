"""The configuration ``ouro-2.6b-l8`` (Ouro-2.6B's published layers 0-7 of 48, stage 0 of six: ONE stack run
``total_ut_steps`` = 4 times on the same weights, sandwich norms, a head and an exit gate after every pass, the expected
loss over the four exits) and its cell ``ouro-2.6b-l8.pretrain-8k``: the files pass the manifest's checks and hold the
catalog row's every key but the depth, the program's tree has the parameters the issue counted, the FLOP module's total is
a sum a reader can check by hand (applications, not parameters), the program agrees with its plain float32 reference at
the rehearsal's width through the harness's own pair, each control of the reference moves the first loss past the
rehearsal's rule or is stated not to, the new reader counts the applications it reads (32 backward calls a step: a
reading; 24: None) and the rehearsal ends ``correct`` true, and false under a control. Nothing here pins an entry's place
in ``BENCHMARK.json`` or counts its cells: a later cell is appended after this one."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmarks.lib import flops, manifest as mf

MANIFEST = mf.load_manifest()
NAME, CELL = "ouro-2.6b-l8", "ouro-2.6b-l8.pretrain-8k"
CONFIG = mf.load_json(os.path.join(mf.BENCH, "configs", f"{NAME}.json"))
TRAFFIC = mf.load_json(os.path.join(mf.BENCH, "traffic", "pretrain-8k.json"))
PUBLISHED = mf.published(CONFIG)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
READER = "loop_attention_roofline"


def test_the_configuration_and_its_cell_have_no_problems():
    entry = next(c for c in MANIFEST["configs"] if c["name"] == NAME)
    assert mf.config_problems(CONFIG, entry) == [] and entry["reduced"] == ["num_hidden_layers"] == CONFIG["reduced"]
    assert [p for p in mf.problems(MANIFEST) if NAME in p or READER in p] == []  # ``manifest.problems`` has nothing new
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert (cell["chips"], cell["traffic"], cell["config"]) == (1, "pretrain-8k", NAME) and len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    for word in ("8192", "32 block applications", "4 heads", "49,152", "a token counts once", "15.6%", "3.0%"):
        assert word in cell["why"], word
    assert "share" not in CONFIG  # no layer is shared between chips: the cut is in depth alone
    assert CONFIG["trainer"]["train_micro_batch_size_per_gpu"] == 1 and CONFIG["trainer"]["zero_optimization"]["stage"] == 0
    assert CONFIG["trainer"]["mesh"] == {"data": 1} and CONFIG["trainer"]["bf16"] == {"enabled": True}
    assert CONFIG["trainer"]["optimizer"]["type"] == "adam" and CONFIG["program"]["remat"] is True
    assert (CONFIG["warmup_steps"], CONFIG["trace_steps"]) == (3, 4)
    reported = {m["name"] for g in ("end_to_end", "per_layer") for m in mf.metrics_of(MANIFEST, CELL, g)}
    assert {"train_tokens_per_s", "setup_s", "mfu.train", READER} <= reported  # at least what its PR brought: a later reader may list the cell
    assert TRAFFIC["generator"] == "fixed_batches" and TRAFFIC["params"] == {"seq_len": 8192, "n_batches": 8}  # the file the benchmark has
    assert "first_loss_tol" in CONFIG["correct_why"] and 0 < CONFIG["correct"]["first_loss_tol"] <= 0.05


def test_the_new_metric_is_this_cells_alone():
    metric = next(m for m in MANIFEST["per_layer"] if m["name"] == READER)
    assert CELL in metric["workloads"] and (metric["unit"], metric["better"], metric["source"], metric["moves"]) == \
        ("%", "higher", "device_trace", "train_tokens_per_s")
    mod = mf.metric_module(READER)
    assert (mod.UNIT, mod.BETTER, mod.SOURCE, mod.LAYER, mod.MOVES) == tuple(metric[k] for k in ("unit", "better", "source", "layer", "moves"))
    assert metric["layer"] == "kernels (ops/pallas/flash_attention.py)"
    for shared in ("train_tokens_per_s", "mfu.train"):  # appended to, nothing else changed
        listed = next(m for g in ("end_to_end", "per_layer") for m in MANIFEST[g] if m["name"] == shared)["workloads"]
        assert CELL in listed and listed.index(CELL) > listed.index("nemotron3-nano-30b-l9e8.pretrain-8k")


@pytest.mark.parametrize("case,needle", [("as_it_is", None), ("a_width_reduced", "reduced names a width"), ("the_head_size_reduced", "reduced names a width"),
                                         ("the_entry_disagrees", "reduced differs between BENCHMARK.json and its file")])
def test_the_checks_find_a_width_among_the_reduced_keys(case, needle):
    cfg = json.loads(json.dumps(CONFIG))
    entry = dict(next(c for c in MANIFEST["configs"] if c["name"] == NAME))
    if case == "a_width_reduced":
        cfg["reduced"] = entry["reduced"] = CONFIG["reduced"] + ["intermediate_size"]
    elif case == "the_head_size_reduced":
        cfg["reduced"] = entry["reduced"] = CONFIG["reduced"] + ["head_dim"]
    elif case == "the_entry_disagrees":
        entry["reduced"] = []
    found = mf.config_problems(cfg, entry)
    assert (found == []) == (needle is None) and (needle is None or any(needle in p for p in found))


@pytest.mark.skipif(not os.path.isfile(CATALOG), reason="the catalog of published configurations is not on this machine")
def test_every_key_but_the_depth_is_the_sources_and_the_vocabulary_is_whole():
    row = next(json.loads(line) for line in open(CATALOG) if '"name": "Ouro-2.6B"' in line)
    assert CONFIG["source"] == row["source_url"] and row["not_given"] == [] and row["mechanisms"] == ["layers run several times"]
    source = row["config"]
    assert {k for k, v in source.items() if CONFIG.get(k, "missing") != v} == {"num_hidden_layers"} == set(CONFIG["reduced"])
    assert CONFIG["layer_types"] == ["full_attention"] * 48 and CONFIG["published_layers"] == 48 == source["num_hidden_layers"]
    assert CONFIG["layers_here"] == list(range(8)) and CONFIG["num_hidden_layers"] == 8
    p = CONFIG["program"]
    assert (p["d_model"], p["n_heads"], p["n_kv_heads"], p["head_dims"], p["d_ff"], p["vocab_size"], p["loop_steps"], p["norm_eps"], p["rope_theta"]) == \
        (source["hidden_size"], source["num_attention_heads"], source["num_key_value_heads"], source["head_dim"], source["intermediate_size"],
         source["vocab_size"], source["total_ut_steps"], source["rms_norm_eps"], source["rope_theta"]) == (2048, 16, 16, 128, 5632, 49152, 4, 1e-6, 1e6)
    assert (p["n_layers"], p["norm_scheme"], p["exit_gate"], p["exit_entropy_coef"], p["norm"], p["activation"], p["pos_emb"], p["tie_embeddings"]) == \
        (8, "sandwich", True, 0.05, "rmsnorm", "swiglu", "rope", False)
    assert source["tie_word_embeddings"] is False and source["hidden_act"] == "silu" and source["rope_scaling"] is None and source["sliding_window"] is None
    assert p["exit_entropy_coef"] == CONFIG["reference"]["beta"] and "loop_path" not in p
    assert p["max_seq_len"] == TRAFFIC["params"]["seq_len"] <= source["max_position_embeddings"]
    for key in ("sandwich_norm", "final_norm_in_loop", "loop", "attention", "ffn", "head", "exit_gate", "objective", "start", "optimizer"):
        assert key in CONFIG["assumed"], key
    for key, whose in (("sandwich_norm", "paper's"), ("final_norm_in_loop", "modelling code"), ("exit_gate", "paper's"), ("objective", "paper's")):
        assert whose in CONFIG["assumed"][key], key  # every assumption says whose it is
    for word in ("SIX pipeline stages of eight layers", "one v5e chip a stage", "stage 0", "head and the gate", "absent", "15.6%", "3.0%"):
        assert word in CONFIG["deployment"], word


def test_the_parameter_count_is_the_issues_sum():
    """612,438,017 parameters by the shapes of the program's own tree (issue 63 counted 612.4 M): 8.57 GB at 14 bytes."""
    import jax

    from benchmarks.lib import weights

    shapes = weights.param_shapes(weights.build_model(CONFIG))
    count = lambda tree: sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))
    d = 2048
    layer = 4 * d * d + 3 * d * 5632 + 4 * d  # q, k, v, o; gate, up, down; FOUR norm weights
    assert layer == 51_388_416 and all(count(shapes[f"layer_{i}"]) == layer for i in range(8))
    assert count(shapes["wte"]) == count(shapes["lm_head"]) == 49152 * d == 100_663_296
    assert count(shapes["exit_gate"]) == d + 1 and count(shapes["RMSNorm_0"]) == d
    assert count(shapes) == 8 * layer + 2 * 49152 * d + 2 * d + 1 == 612_438_017 and 8.57e9 < 14 * count(shapes) < 8.58e9


def test_the_flop_count_is_the_hand_written_sum_over_applications():
    mod = flops.for_config(CONFIG)
    S, d = 8192, 2048
    products, pairs = 2 * (4 * d * d + 3 * d * 5632), 4 * 16 * 128 * (S + 1) / 2
    assert (products, pairs) == (102_760_448, 33_558_528.0) and mod.block_flops_per_token(PUBLISHED, S) == products + pairs
    head = 2 * d * (49152 + 1)  # one pass's head and gate
    assert mod.head_flops_per_token(PUBLISHED) == head == 201_330_688 and mod.applications(PUBLISHED) == 32
    forward = 4 * (8 * (products + pairs) + head)
    assert mod.forward_flops_per_token(PUBLISHED, S) == forward == pytest.approx(5.168e9, rel=1e-3)
    assert mod.train_flops_per_token(PUBLISHED, S) == 3 * forward and 3 * forward * S == pytest.approx(127.0e12, rel=2e-3)  # issue 63: 127 TFLOP a step
    assert pairs / (products + pairs) == pytest.approx(0.246, abs=0.001)  # the causal pairs: 24.6% of a block application
    assert mod.head_share(PUBLISHED, S) == pytest.approx(0.156, abs=0.001) and mod.head_share(PUBLISHED, S, layers=48) == pytest.approx(0.030, abs=0.001)
    fwd, bwd = (mod.attention_cost(PUBLISHED, 1, S, backward=b) for b in (False, True))
    assert fwd == flops.flash_attention_cost(1, S, 16, 16, 128, backward=False) and bwd == flops.flash_attention_cost(1, S, 16, 16, 128, backward=True)
    assert flops.for_config(CONFIG) is not flops and not hasattr(flops.for_config(mf.load_json(os.path.join(mf.BENCH, "configs", "olmo-1b.json"))), "applications")


def _tiny():
    """The rehearsal's width, two layers four times, float32."""
    from benchmarks.lib import weights

    r = CONFIG["rehearse"]
    cfg = dict(CONFIG, **r["published"])
    cfg["program"] = dict(CONFIG["program"], **r["program"], dtype="float32")
    return cfg, weights.build_model(cfg)


def _rows(seed, batch=2, vocab=311):
    gen = mf.load_module(os.path.join(mf.BENCH, "generators", "fixed_batches.py"))
    return gen.generate(TRAFFIC["rehearse"]["params"], seed, 40.0, {"vocab_size": vocab, "global_batch": batch})["batches"][0]["input_ids"]


def _stirred(model):
    import jax

    params = model.init(jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 16), np.int32)})
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    by = lambda path: 0.3 if "exit_gate" in jax.tree_util.keystr(path) else 0.05  # the gate starts at zero: moved well off it
    return jax.tree_util.tree_unflatten(tree, [x + by(path) * jax.random.normal(jax.random.PRNGKey(7 + i), x.shape) for i, (path, x) in enumerate(leaves)])


# what each control does to the FIRST LOSS at the rehearsal's width, stirred weights, float32 (the harness compares that one
# number): every one moves it by more than 1e-3, four times the rehearsal's rule (2.5 x the 3e-4 floor)
CONTROLS = ["steps_short", "norm_outside_loop", "no_sandwich", "uniform_exit", "no_entropy", "layers_short"]


@pytest.mark.parametrize("control", [None] + CONTROLS)
def test_the_program_agrees_with_the_plain_reference_and_not_with_a_control(control):
    """Through the harness's own pair, ``reference.for_config``: what ``logits`` returns goes to ``loss`` untouched. Loss and
    every leaf's gradient within 1e-5 of the largest entry (the order of float32 sums); under a control the loss is 1e-3
    and more away."""
    import jax
    import jax.numpy as jnp

    from benchmarks.lib import reference

    cfg, model = _tiny()
    ids = _rows(5)
    params = _stirred(model)
    ref_logits, ref_loss = reference.for_config(cfg)
    pub = mf.published(cfg)
    ref_cfg = dict(cfg["reference"], **({control: True} if control else {}))
    gap = lambda a, b: np.max(np.abs(np.asarray(a, np.float64) - np.asarray(b, np.float64))) / (1.0 + np.max(np.abs(np.asarray(b, np.float64))))
    with jax.default_matmul_precision("highest"):
        ours, g_ours = jax.value_and_grad(lambda p: model.loss_fn(p, {"input_ids": ids}))(params)
        if control is not None:
            assert abs(float(ours) - float(ref_loss(ref_logits(params, ids, pub, ref_cfg, jnp.float32), ids))) > 1e-3
            return
        theirs, g_theirs = jax.value_and_grad(lambda p: ref_loss(ref_logits(p, ids, pub, ref_cfg, jnp.float32), ids))(params)
        out = ref_logits(params, ids, pub, ref_cfg, jnp.float32)
        assert gap(model.apply(params, ids), out["last"]) < 1e-5 and out["nll"].shape == out["p"].shape == (4, 2, 95)
    assert gap(ours, theirs) < 1e-6
    theirs_by_path = dict(jax.tree_util.tree_leaves_with_path(g_theirs))
    mine = jax.tree_util.tree_leaves_with_path(g_ours)
    assert len(mine) == len(theirs_by_path) == 5 + 2 * 11  # the tables, the final norm, the gate's two; a layer's four norms and seven products
    for path, leaf in mine:
        assert gap(leaf, theirs_by_path[path]) < 1e-5, jax.tree_util.keystr(path)
        assert float(jnp.max(jnp.abs(leaf))) > 0, jax.tree_util.keystr(path)  # every leaf is reached, the gate's too


def _record(ops, counts, steps=4, config=CONFIG, counters=None, window_steps=30):
    """A traced training record with one device, ``steps`` executions of the step program and these operations."""
    dev = {"ops": ops, "op_counts": counts, "modules": [("jit_fused_step(123)", 0.2 * i, 0.2 * i + 0.19) for i in range(steps)]}
    return {"reduced": {"devices": {"0": dev}, "window_s": 1.0}, "published": mf.published(config), "config": config,
            "train": {"micro_batch": 1, "seq_len": 8192, "steps": window_steps}, "device": {"kind": "TPU v5 lite", "count": 1},
            "counters": {"train_loop_block_applications_total": 32.0 * window_steps} if counters is None else counters,
            "end_to_end": {"train_tokens_per_s": 1.0}}


FWD = 'flash_fwd custom-call (bf16[16,8192,128]{2,1,0:T(8,128)(2,1)}, f32[16,16,1,512]{3,2,1,0:T(1,128)}) custom_call_target="tpu_custom_call"'
BWD = 'flash_bwd custom-call (bf16[16,8192,128]{2,1,0:T(8,128)(2,1)}, bf16[16,8192,128]{2,1,0:T(8,12 custom_call_target="tpu_custom_call"'
OTHER = {"fusion fusion bf16[8192,5632]{1,0:T(8,128)(2,1)}": 0.3, 'ssd_scan_bwd custom-call bf16[1,8192,4096]{2,1,0} custom_call_target="tpu_custom_call"': 0.1}
SECONDS = {FWD: 0.40, BWD: 0.56}  # four traced steps: 256 forward calls (a checkpointed block makes its forward again) and 128 backward calls


def test_the_reader_counts_the_applications_it_reads():
    from benchmarks.lib.peaks import peaks_for

    mod = mf.metric_module(READER)
    calls = lambda bwd_a_step, fwd_a_step=64: dict({FWD: 4 * fwd_a_step, BWD: 4 * bwd_a_step}, **{k: 1 for k in OTHER})
    share = mod.read(_record(dict(SECONDS, **OTHER), calls(32)))
    counts, peaks = flops.for_config(CONFIG), peaks_for("TPU v5 lite")
    need = [flops.roofline_seconds(counts.attention_cost(PUBLISHED, 1, 8192, backward=b), peaks) for b in (False, True)]
    assert {n["bound"] for n in need} == {"compute"}
    assert share == pytest.approx(100 * 4 * 32 * sum(n["seconds"] for n in need) / 0.96) and 0 < share < 100
    assert mod.read(_record(dict({k: 2 * v for k, v in SECONDS.items()}, **OTHER), calls(32))) == pytest.approx(share / 2)
    assert mod.read(_record(dict(SECONDS, **OTHER), calls(32, fwd_a_step=32))) == pytest.approx(share)  # without remat: the forward's calls are not what is counted
    assert mod.read(_record(dict(SECONDS, **OTHER), calls(24))) is None   # a pass that did not run: three passes' backward calls
    assert mod.read(_record(dict(SECONDS, **OTHER), calls(28))) is None   # a layer that did not
    assert mod.read(_record(dict(SECONDS, **OTHER), calls(32), counters={"train_loop_block_applications_total": 24.0 * 30})) is None  # ... by the program's count
    assert mod.read(_record(dict(SECONDS, **OTHER), calls(32), counters={"train_loop_block_applications_total": 32.0 * 28})) == pytest.approx(share)  # two steps' counts still on their way
    assert mod.read(_record(dict(SECONDS, **OTHER), calls(32), counters={"train_loop_block_applications_total": 32.0 * 20})) is None
    assert mod.read(_record(dict(SECONDS, **OTHER), calls(32), counters={})) is None  # the parent of the PR that added the counter: nothing, and no raise
    assert mod.read(_record(OTHER, {k: 1 for k in OTHER})) is None             # a trace without the kernel
    assert mod.read(dict(_record(dict(SECONDS, **OTHER), calls(32)), reduced=None)) is None  # an untraced run
    for other in ("olmo-1b", "nemotron3-nano-30b-l9e8", "smallthinker-21b-l4e8"):  # another configuration's FLOP module: nothing, and no raise
        assert mod.read(_record(dict(SECONDS, **OTHER), calls(32), config=mf.load_json(os.path.join(mf.BENCH, "configs", f"{other}.json")))) is None
    # the older flash readers that name a cost of their own find none in this configuration's FLOP module
    for older in ("mixed_attention_roofline", "gated_attention_roofline", "blockdiff_attention_roofline", "ssd_scan_roofline"):
        assert mf.metric_module(older).read(_record(dict(SECONDS, **OTHER), calls(32))) is None


def _rehearse(root, seed):
    out = subprocess.run([sys.executable, os.path.join(root, "benchmarks", "run.py"), "--workload", CELL, "--rehearse", "--seed", str(seed),
                          "--seconds", "1"], capture_output=True, text=True, timeout=900, cwd=root, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-3000:]
    return out


def test_the_rehearsal_ends_correct_and_says_and_counts_its_passes():
    """A process of its own, as the driver starts one: the package's log line goes to that process's stdout."""
    out = _rehearse(mf.ROOT, 2**31 + 42)
    lines = out.stdout.strip().splitlines()
    last, counters = json.loads(lines[-1]), json.loads(lines[-2])["extras"]["counters"]
    assert last["correct"] is True and "first_loss_vs_f32" in out.stderr  # the f32 rule is the rehearsal's
    steps = counters["train_steps_total"]
    applied = counters["train_loop_block_applications_total"]
    assert steps > 0 and applied % 8 == 0 and abs(applied / 8 - steps) <= 3  # 2 layers x 4 passes a step, counted a step or two late
    line = next(l for l in lines if "program first call: family=train" in l)
    # ``remat_keeps`` is ``models/transformer.py::remat_keeps`` over the stack's kinds, as ``runtime/engine.py`` joins it: since
    # PR 64 a plain block keeps its flash call's outputs under that one name (``inputs`` before, and where no kind keeps any)
    for word in ("block_traces=1", "loop_steps=4", "remat_keeps=flash_attention", "rope=xla"):
        assert word in line, word


def test_the_rehearsal_ends_false_under_a_control(tmp_path):
    """The same run against a reference with one thing wrong (uniform exits: p_t = 1/4): ``correct`` false. The control is
    switched on through a copy of the checkout's benchmark files, so no file of the benchmark is touched."""
    import shutil

    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(mf.ROOT, "benchmarks"), root / "benchmarks", ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(mf.ROOT, "BENCHMARK.json"), root / "BENCHMARK.json")
    os.symlink(os.path.join(mf.ROOT, "deepspeed_tpu"), root / "deepspeed_tpu")
    path = root / "benchmarks" / "configs" / f"{NAME}.json"
    cfg = json.loads(path.read_text())
    cfg["reference"]["uniform_exit"] = True
    path.write_text(json.dumps(cfg))
    out = _rehearse(str(root), 2**31 + 42)
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["correct"] is False and "first_loss_vs_f32" in out.stderr
