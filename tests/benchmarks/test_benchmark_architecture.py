"""An architecture the harness has never seen arrives as files: a throw-away
one-chip training configuration with its own plain reference and FLOP count, a
nested source key and a list-valued ``program`` field, in a temporary copy in
which no file that was there is changed. It rehearses to ``correct`` true, and
to ``correct`` false with a reference that drops a layer."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmarks.lib import flops, manifest as mf, program, reference
from tests.benchmarks.test_benchmark_hybrid import _one_cell_base  # the first cell alone and n more: a case counts what it builds

MANIFEST = mf.load_manifest()

# two layers, the second with a window of 8 positions: the pattern is a nested key of the "source", as a
# layer-type list is in a hybrid model's config.json, and the program takes it as a list in `program`
ARCH = {
    "kind": "train", "source": "none", "hidden_size": 64, "intermediate_size": 96, "num_attention_heads": 4,
    "num_key_value_heads": 4, "num_hidden_layers": 2, "vocab_size": 509, "rope_theta": 10000.0,
    "tie_word_embeddings": True, "layer_pattern": {"window_layers": [1], "sliding_window": 8},
    "reduced": [], "assumed": {}, "env": {}, "pattern_why": "a key of the harness's, by its ending",
    "program": {"vocab_size": 509, "n_layers": 2, "n_heads": 4, "n_kv_heads": 4, "d_model": 64, "d_ff": 96,
                "max_seq_len": 64, "norm": "layernorm_np", "activation": "swiglu", "pos_emb": "rope",
                "tie_embeddings": True, "dtype": "bfloat16", "sliding_window": 8, "window_layers": [1]},
    "reference": {"norm": "layernorm_np", "module": "benchmarks/configs/arch.reference.py"},
    "flops": {"module": "benchmarks/configs/arch.flops.py"},
    "trainer": {"train_micro_batch_size_per_gpu": 2, "gradient_accumulation_steps": 1, "bf16": {"enabled": True},
                "optimizer": {"type": "adam", "params": {"lr": 0.001}}, "zero_optimization": {"stage": 0},
                "steps_per_print": 1000000000, "mesh": {"data": 1}},
    "warmup_steps": 2, "correct": {"first_loss_tol": 0.05, "first_loss_rule": "f32"}, "rehearse": {},
}
REFERENCE = '''
from benchmarks.lib import reference


def logits(params, ids, published, ref_cfg, dtype):
    pattern = published["layer_pattern"]  # nested: lib/manifest.py::published keeps it
    layers = int(published["num_hidden_layers"]) - DROPPED
    windows = [pattern["sliding_window"] if i in pattern["window_layers"] else None for i in range(layers)]
    return reference.decoder_logits(params, ids, dict(published, num_hidden_layers=layers), ref_cfg["norm"], dtype, windows)
'''
FLOPS = '''
from benchmarks.lib import flops


def train_flops_per_token(published, seq_len):  # the dense count, less the far half of the windowed layers' attention
    windowed = len(published["layer_pattern"]["window_layers"])
    attn = 3 * 2 * seq_len * published["num_attention_heads"] * flops.head_dim(published)
    return flops.train_flops_per_token(published, seq_len) - windowed * attn / 2
'''


def _files(root):
    return {os.path.join(dp, f): open(os.path.join(dp, f)).read()
            for dp, _, fs in os.walk(os.path.join(root, "benchmarks")) if "__pycache__" not in dp for f in fs}


def _rehearse(root, workload):
    out = subprocess.run([sys.executable, os.path.join(root, "benchmarks", "run.py"), "--workload", workload, "--rehearse",
                          "--seed", str(2**31 + 17), "--seconds", "1"], capture_output=True, text=True, timeout=600,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    return json.loads(lines[-1]), json.loads(lines[-2])["extras"], out.stderr


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """The temporary copy with the architecture added by new files and appended
    entries, and its two rehearsals: the sound reference and the one a layer short."""
    root = str(tmp_path_factory.mktemp("arch"))
    shutil.copytree(mf.BENCH, os.path.join(root, "benchmarks"), ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(mf.ROOT, "deepspeed_tpu"), os.path.join(root, "deepspeed_tpu"))
    before = _files(root)
    b = os.path.join(root, "benchmarks")
    m = json.loads(json.dumps(MANIFEST))
    for name, dropped in (("arch", 0), ("arch-short", 1)):
        cfg = json.loads(json.dumps(ARCH).replace("configs/arch.", f"configs/{name}."))
        json.dump(cfg, open(f"{b}/configs/{name}.json", "w"))
        open(f"{b}/configs/{name}.reference.py", "w").write(f"DROPPED = {dropped}\n" + REFERENCE)
        open(f"{b}/configs/{name}.flops.py", "w").write(FLOPS)
        m["configs"].append({"name": name, "source": "none", "file": f"benchmarks/configs/{name}.json", "reduced": [], "why": "w"})
        m["workloads"].append({"name": f"{name}.pretrain-1chip", "config": name, "traffic": "pretrain-1chip", "chips": 1, "why": "w"})
        for group in ("end_to_end", "per_layer"):
            for metric in m[group]:
                if "workloads" in metric:
                    metric["workloads"].append(f"{name}.pretrain-1chip")  # the one thing a later PR extends in place
    json.dump({"generator": "fixed_batches", "who": "w", "why": "w", "params": {"seq_len": 32, "n_batches": 2}},
              open(f"{b}/traffic/pretrain-1chip.json", "w"))
    json.dump(m, open(os.path.join(root, "BENCHMARK.json"), "w"))
    runs = {name: _rehearse(root, f"{name}.pretrain-1chip") for name in ("arch", "arch-short")}
    return {"root": root, "manifest": m, "before": before, "after": _files(root), "runs": runs}


def test_the_architecture_is_added_with_no_file_that_was_there_changed(copy):
    assert mf.problems(copy["manifest"], copy["root"]) == []
    assert all(copy["after"][path] == text for path, text in copy["before"].items())
    assert len(copy["after"]) == len(copy["before"]) + 7


def test_it_rehearses_to_correct_with_its_own_reference(copy):
    last, extras, err = copy["runs"]["arch"]
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] > 0
    rule = extras["first_loss_f32_rule"]
    assert rule["ours_vs_f32"] <= reference.f32_limit(rule["plain_bf16_vs_f32"], reference.LOSS_FLOOR)
    assert {"first_loss", "plain_first_loss"} <= set(extras) and rule["f32_first_loss"] > 0  # all three losses
    assert err.strip().splitlines()[-1] == "correct: True" and "correct: first_loss_vs_f32" in err


def test_a_reference_that_drops_a_layer_is_not_correct(copy):
    last, extras, err = copy["runs"]["arch-short"]
    rule = extras["first_loss_f32_rule"]
    assert last["correct"] is False
    assert rule["ours_vs_f32"] > reference.f32_limit(rule["plain_bf16_vs_f32"], reference.LOSS_FLOOR)
    assert err.strip().splitlines()[-1] == "correct: False"


def test_the_train_records_counters_are_the_windows_deltas(copy):
    _, extras, _ = copy["runs"]["arch"]
    assert extras["counters"]["train_steps_total"] == extras["steps"] > 0     # not the warm-up's two steps as well
    assert extras["counters"]["train_tokens_total"] == extras["steps"] * 2 * 32
    assert extras["counters"]["program_first_calls_total"] == 0               # nothing was first called in the window


def test_published_keeps_the_sources_nested_keys_and_none_of_the_harnesss():
    pub = mf.published(ARCH)
    assert pub["layer_pattern"] == {"window_layers": [1], "sliding_window": 8} and pub["hidden_size"] == 64
    assert not set(pub) & (mf.HARNESS_KEYS | {"pattern_why"})
    olmo = mf.load_json(os.path.join(mf.BENCH, "configs", "olmo-1b.json"))
    assert mf.published(olmo)["model_type"] == "olmo" and "trainer_why" not in mf.published(olmo)


def test_mfu_counts_with_the_configurations_own_flops(copy):
    cfg = mf.load_json(os.path.join(copy["root"], "benchmarks", "configs", "arch.json"))
    record = {"end_to_end": {"train_tokens_per_s": 1e6}, "train": {"seq_len": 2048}, "published": mf.published(cfg),
              "device": {"kind": "TPU v5 lite", "count": 1}, "config": dict(cfg, flops={"module": os.path.join(copy["root"], cfg["flops"]["module"])})}
    named = mf.metric_module("mfu.train").read(record)
    dense = mf.metric_module("mfu.train").read(dict(record, config={}))
    assert dense == pytest.approx(100 * flops.train_flops_per_token(record["published"], 2048) * 1e6 / 197e12)
    assert named == pytest.approx(dense - 100 * (3 * 2 * 2048 * 64 / 2) * 1e6 / 197e12) and named < dense
    assert flops.for_config({}) is flops and flops.for_config(None) is flops


def test_a_default_configuration_gets_the_decoder_familys_reference():
    logits, loss = reference.for_config({"reference": {"norm": "rmsnorm"}})
    assert loss is reference.causal_lm_loss and logits.__module__ == reference.__name__


def test_the_programs_lists_become_tuples():
    from benchmarks.lib import weights

    cfg = weights.build_model(ARCH).cfg
    assert cfg.window_layers == (1,) and cfg.window_for(1) == 8 and cfg.window_for(0) is None
    hash(cfg)  # a frozen dataclass that keys jax.jit's caches


def _with(**over):
    return dict(ARCH, **over)


@pytest.mark.parametrize("cfg,needle", [
    (_with(reduced=["hidden_size"]), "names a width"),
    (_with(reduced=["num_hidden_layers", "kv_lora_rank"]), "names a width"),
    (_with(reduced=["num_experts_per_tok"]), "names a width"),
    (_with(reduced=["num_hidden_layers"], num_experts=8,
           share={"chips_per_layer": 32, "held": {"num_experts": {"published": 256, "here": 8}}}), "reduced does not list"),
    (_with(reduced=["num_experts"], num_experts=16,
           share={"chips_per_layer": 32, "held": {"num_experts": {"published": 256, "here": 8}}}), "the file says 16"),
])
def test_a_configurations_problems_are_found(cfg, needle):
    assert any(needle in p for p in mf.config_problems(cfg))


def test_a_chips_share_that_is_listed_is_no_problem():
    cfg = _with(reduced=["num_hidden_layers", "num_experts", "vocab_size"], num_experts=8, vocab_size=20480,
                share={"chips_per_layer": 32, "held": {"num_experts": {"published": 256, "here": 8},
                                                       "vocab_size": {"published": 163840, "here": 20480}}})
    assert mf.config_problems(cfg) == []


@pytest.mark.parametrize("name", sorted(f[:-5] for f in os.listdir(os.path.join(mf.BENCH, "configs")) if f.endswith(".json")))
def test_every_configuration_file_here_has_no_problems(name):
    entry = next((c for c in MANIFEST["configs"] if c["name"] == name), None)  # mistral-7b-l16 is not listed
    assert mf.config_problems(mf.load_json(os.path.join(mf.BENCH, "configs", f"{name}.json")), entry) == []


@pytest.mark.parametrize("manifest,needle", [
    (_one_cell_base(24), "more than 24"),
    (_one_cell_base(1, four=1), "more than 1 of 2 cells ask for four chips"),
    (_one_cell_base(6, four=1), "more than 1 of 7 cells ask for four chips"),
])
def test_too_many_cells_and_a_second_four_chip_cell_are_found(manifest, needle):
    assert any(needle in p for p in mf.problems(manifest))


def test_a_second_four_chip_cell_has_room_among_eight():
    assert mf.problems(_one_cell_base(7, four=1)) == []
    entry = dict(MANIFEST["configs"][0], reduced=["num_hidden_layers"])
    assert any("differs between" in p for p in mf.problems(dict(MANIFEST, configs=[entry])))


def test_a_reader_of_a_new_counter_edits_nothing():
    record = {"counters": {"moe_tokens_routed_total": 7.0}, "program": {"counters": {"moe_tokens_dropped_total": 3.0}}}
    assert program.counter(record, "moe_tokens_routed_total") == 7.0          # the window's delta
    assert program.counter(record, "moe_tokens_dropped_total") == 3.0         # else the process's total
    assert program.counter(record, "no_such_counter_total") is None
    from deepspeed_tpu.telemetry import get_registry

    get_registry().counter("bench_test_probe_total", site="here").inc(5)
    assert program.counter({}, 'bench_test_probe_total{site="here"}') == 5.0  # read in process, as a measuring run does
