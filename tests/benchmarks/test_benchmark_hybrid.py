"""The hybrid configuration ``kimi-linear-48b-l5e8``: its files pass the
manifest's checks, its rehearsal ends ``correct`` false with a reference a
layer short (true with the sound one: ``test_benchmark_rehearse.py`` picks the
cell up from ``workloads``), its three readers read a synthetic trace and
return None where nothing matches, and its FLOP module's total is a sum a
reader can check by hand."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmarks.lib import flops, manifest as mf

MANIFEST = mf.load_manifest()
NAME, CELL = "kimi-linear-48b-l5e8", "kimi-linear-48b-l5e8.pretrain-8k"
CONFIG = mf.load_json(os.path.join(mf.BENCH, "configs", f"{NAME}.json"))
PUBLISHED = mf.published(CONFIG)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_the_manifest_and_the_configuration_have_no_problems():
    assert mf.problems(MANIFEST) == []
    entry = next(c for c in MANIFEST["configs"] if c["name"] == NAME)
    assert mf.config_problems(CONFIG, entry) == []
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert (cell["chips"], cell["traffic"]) == (1, "pretrain-8k")
    traffic = mf.load_json(os.path.join(mf.BENCH, "traffic", "pretrain-8k.json"))
    assert traffic["generator"] == "fixed_batches" and traffic["params"] == {"seq_len": 8192, "n_batches": 8}
    assert CONFIG["trainer"]["train_micro_batch_size_per_gpu"] == 1 and CONFIG["trainer"]["zero_optimization"]["stage"] == 0
    reported = {m["name"] for g in ("end_to_end", "per_layer") for m in mf.metrics_of(MANIFEST, CELL, g)}
    assert {"train_tokens_per_s", "setup_s", "mfu.train", "kda_scan_roofline", "mla_attention_roofline",
            "moe_expert_matmul_roofline"} <= reported  # at least what its PR brought: a later reader may list the cell


@pytest.mark.skipif(not os.path.isfile(CATALOG), reason="the catalog of published configurations is not on this machine")
def test_every_width_is_the_sources():
    source = next(json.loads(line) for line in open(CATALOG) if "Kimi-Linear-48B-A3B" in line)["config"]
    differs = {k for k, v in source.items() if CONFIG.get(k, "missing") != v}
    assert differs == set(CONFIG["reduced"]) == {"num_hidden_layers", "num_experts", "vocab_size", "linear_attn_config"}
    lin, src = CONFIG["linear_attn_config"], source["linear_attn_config"]
    assert {k for k in src if lin[k] != src[k]} == {"kda_layers", "full_attn_layers"}  # the lists end at layer 5; no width moved
    assert lin["kda_layers"] == [l for l in src["kda_layers"] if l <= 5] and lin["full_attn_layers"] == [4]
    assert CONFIG["share"]["chips_per_layer"] == 32 and CONFIG["routed_over"] == source["num_experts"]
    program = CONFIG["program"]
    assert [k[0] for k in program["layer_kinds"]] == ["kda", "kda", "kda", "mla", "kda"]
    assert (program["d_model"], program["d_ff"], program["moe_d_ff"], program["moe_top_k"], program["moe_num_experts"]) == \
        (source["hidden_size"], source["intermediate_size"], source["moe_intermediate_size"], source["num_experts_per_token"], 256)


def test_the_flop_count_is_the_hand_written_sum_for_the_tiny_preset():
    m = dict(PUBLISHED, hidden_size=64, intermediate_size=96, moe_intermediate_size=32, vocab_size=509, num_experts=4,
             routed_over=16, num_experts_per_token=4, num_attention_heads=4, kv_lora_rank=32, qk_nope_head_dim=24,
             qk_rope_head_dim=8, v_head_dim=16,
             linear_attn_config=dict(PUBLISHED["linear_attn_config"], num_heads=4, head_dim=16))
    mod = flops.for_config(CONFIG)
    S, d, hd, rank = 96, 64, 4 * 16, 128
    kda = 2 * (4 * d * hd + 2 * (d * rank + rank * hd) + d * 4) + 2 * 3 * 4 * hd + 7 * 4 * 16 * 16
    mla = 2 * (d * 4 * 32 + d * (32 + 8) + 32 * 4 * (24 + 16) + 4 * 16 * d) + S * 4 * (32 + 16)
    expert = 3 * d * 32
    routed = 2 * (d * 16 + expert + (4 * 4 / 16) * expert)
    dense = 2 * 3 * d * 96
    forward = 4 * kda + mla + dense + 4 * routed + 2 * d * 509
    assert mod.train_flops_per_token(m, S) == pytest.approx(3 * forward)
    # the published widths: 2.3 GFLOP a token, of which the head and layer 1's dense FFN (code OLMo also runs) under 30%
    total = mod.train_flops_per_token(PUBLISHED, 8192)
    assert 2.2e9 < total < 2.4e9 and 6 * (2304 * 20480 + 3 * 2304 * 9216) / total < 0.3


def _record(ops, counters=None, steps=4):
    """A traced training record with one device, ``steps`` executions of the step program and these operations."""
    dev = {"ops": ops, "op_counts": {k: 1 for k in ops}, "modules": [("jit_fused_step(123)", 0.1 * i, 0.1 * i + 0.09) for i in range(steps)]}
    return {"reduced": {"devices": {"0": dev}, "window_s": 1.0}, "published": PUBLISHED, "config": CONFIG,
            "train": {"micro_batch": 1, "seq_len": 8192, "steps": 100}, "device": {"kind": "TPU v5 lite", "count": 1},
            "counters": counters or {}, "end_to_end": {"train_tokens_per_s": 1.0}}


KDA_OPS = {'kda_scan_fwd custom-call (bf16[32,8192,128]{2,1,0}, f32[32,128,128,128]{3,2,1,0}) custom_call_target="tpu_custom_call"': 0.04,
           'kda_scan_bwd custom-call (bf16[32,8192,128]{2,1,0}, bf16[32,8192,128]{2,1,0}) custom_call_target="tpu_custom_call"': 0.12}
MLA_OPS = {'custom-call custom-call (bf16[32,8192,128]{2,1,0}, f32[32,16,1,512]{3,2,1,0}) custom_call_target="tpu_custom_call"': 0.02,
           'custom-call custom-call (bf16[32,8192,192]{2,1,0}, bf16[32,8192,192]{2,1,0}, bf16[32,8192,128]) custom_call_target="tpu_custom_call"': 0.05}
MOE_OPS = {'gmm custom-call bf16[8192,1024]{1,0} custom_call_target="tpu_custom_call"': 0.04,
           'tgmm custom-call bf16[8,2304,1024]{2,1,0} custom_call_target="tpu_custom_call"': 0.04}
OTHER = {"fusion.1 fusion bf16[8192,2304]{1,0}": 0.5}


@pytest.mark.parametrize("metric,ops,counters", [
    ("kda_scan_roofline", KDA_OPS, {}),
    ("mla_attention_roofline", MLA_OPS, {}),
    ("moe_expert_matmul_roofline", MOE_OPS, {"moe_rows_routed_here_total": 100 * 4 * 2048.0}),
])
def test_a_reader_reads_its_kernels_and_nothing_else(metric, ops, counters):
    read = mf.metric_module(metric).read
    share = read(_record(dict(ops, **OTHER), counters))
    assert 0 < share < 100
    assert read(_record(dict({k: 2 * v for k, v in ops.items()}, **OTHER), counters)) == pytest.approx(share / 2)
    assert read(_record(OTHER, counters)) is None                      # a program without the kernel: the parent
    assert read(dict(_record(dict(ops, **OTHER), counters), reduced=None)) is None  # an untraced run
    assert read(dict(_record(dict(ops, **OTHER), counters), config={})) is None      # a configuration with no such cost
    if counters:
        assert read(_record(dict(ops, **OTHER))) is None               # a program without the counter


def test_the_kda_reader_counts_what_its_docstring_says():
    from benchmarks.lib.peaks import peaks_for

    mod, peaks = flops.for_config(CONFIG), peaks_for("TPU v5 lite")
    need = sum(flops.roofline_seconds(mod.kda_cost(PUBLISHED, 8192, backward=b), peaks)["seconds"] for b in (False, True))
    assert mf.metric_module("kda_scan_roofline").read(_record(KDA_OPS)) == pytest.approx(100 * 4 * 4 * need / 0.16)


@pytest.fixture(scope="module")
def short_copy(tmp_path_factory):
    """A copy of the benchmark whose hybrid configuration's reference leaves out the last layer."""
    root = str(tmp_path_factory.mktemp("hybrid"))
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
    return json.loads(lines[-1]), json.loads(lines[-2])["extras"], out.stderr


def test_a_reference_a_layer_short_is_not_correct(short_copy):
    last, extras, err = short_copy
    assert last["correct"] is False and err.strip().splitlines()[-1] == "correct: False"
    rule = extras["first_loss_f32_rule"]
    assert rule["ours_vs_f32"] > 2.5 * max(rule["plain_bf16_vs_f32"], 3e-4)


def test_the_rehearsal_counts_rows_and_drops_none(short_copy):
    _, extras, err = short_copy
    counters = extras["counters"]
    assert counters["moe_rows_routed_here_total"] > 0 and counters["moe_rows_dropped_total"] == 0


def _one_cell_base(n, four=0):
    """The first cell alone, as ``BENCHMARK.json`` was when ``test_benchmark_architecture.py``'s cases of the quarter rule
    were written, plus ``n`` copies of it, the last ``four`` on four chips."""
    m = json.loads(json.dumps(MANIFEST))
    first = m["workloads"][0]["name"]
    m["workloads"], m["configs"] = m["workloads"][:1], m["configs"][:1]
    for group in ("end_to_end", "per_layer"):
        m[group] = [dict(x, workloads=[first]) if "workloads" in x else x for x in m[group] if x.get("workloads", [first])[0] == first]
    for i in range(n):
        cell = dict(m["workloads"][0], name=f"more.{i}", chips=4 if i >= n - four else 1)
        m["workloads"].append(cell)
        for group in ("end_to_end", "per_layer"):
            for metric in m[group]:
                metric.get("workloads", []).append(cell["name"])
    return m


@pytest.mark.parametrize("n,four,needle", [
    (1, 1, "more than 1 of 2 cells ask for four chips"),
    (6, 1, "more than 1 of 7 cells ask for four chips"),
    (7, 1, None),  # a second four-chip cell has room among eight
    (2, 2, "more than 1 of 3 cells ask for four chips"),
])
def test_the_quarter_rule_on_a_one_cell_base(n, four, needle):
    found = mf.problems(_one_cell_base(n, four))
    assert found == [] if needle is None else any(needle in p for p in found)


def test_the_manifest_as_it_stands_leaves_room_for_no_second_four_chip_cell():
    """The title was true of three cells. The rule it states is: a four-chip cell more has no problem while a quarter of
    the cells, rounded down, allows it (``test_benchmark_manifest.py`` shows that for every cell there is), and a
    one-cell base with two more cells still refuses the second."""
    assert any("more than 1 of 3 cells ask for four chips" in p for p in mf.problems(_one_cell_base(2, 2)))
    m = json.loads(json.dumps(MANIFEST))
    m["workloads"].append(dict(m["workloads"][0], name="more.0", chips=4))
    for group in ("end_to_end", "per_layer"):
        for metric in m[group]:
            if m["workloads"][0]["name"] in metric.get("workloads", []):
                metric["workloads"].append("more.0")
    n, four, room = len(m["workloads"]), sum(w["chips"] == 4 for w in m["workloads"]), max(1, len(m["workloads"]) // 4)
    assert mf.problems(m) == ([f"workloads: more than {room} of {n} cells ask for four chips"] if four > room else [])
