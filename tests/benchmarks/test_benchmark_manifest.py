"""BENCHMARK.json and the files it names: the rules a later PR breaks most
easily, and that each kind of piece can be added as files of its own."""

import glob
import inspect
import itertools
import json
import os
import shutil
import subprocess
import sys
import traceback

import pytest

from benchmarks.lib import flops, manifest as mf, peaks

MANIFEST = mf.load_manifest()

# the sources' own numbers, copied here so that a changed width fails a test
PUBLISHED = {
    "mistral-7b-l16": {"hidden_size": 4096, "intermediate_size": 14336, "num_attention_heads": 32,
                       "num_key_value_heads": 8, "vocab_size": 32000, "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
                       "sliding_window": 4096, "max_position_embeddings": 32768, "tie_word_embeddings": False,
                       "num_hidden_layers": 32},
    "olmo-1b": {"hidden_size": 2048, "intermediate_size": 8192, "num_attention_heads": 16, "num_key_value_heads": 16,
                "vocab_size": 50304, "rope_theta": 10000.0, "max_position_embeddings": 2048,
                "tie_word_embeddings": True, "num_hidden_layers": 16},
}
PROGRAM_KEYS = {"hidden_size": "d_model", "intermediate_size": "d_ff", "num_attention_heads": "n_heads",
                "num_key_value_heads": "n_kv_heads", "vocab_size": "vocab_size", "num_hidden_layers": "n_layers",
                "rope_theta": "rope_theta", "tie_word_embeddings": "tie_embeddings",
                "max_position_embeddings": "max_seq_len", "sliding_window": "sliding_window"}


def test_manifest_has_no_problems():
    assert mf.problems(MANIFEST) == []


def test_manifest_keys_are_the_contracts():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert MANIFEST["command"] == ["python3", "benchmarks/run.py"]
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) <= max(1, len(MANIFEST["workloads"]) // 4)


def _with_four_chip_copies(of, k):
    """The manifest as it stands with ``k`` more cells on four chips, copies of the cell ``of``, each listed in every metric
    that lists the FIRST cell (metrics any cell of its kind can report: what ``manifest.problems`` checks is the bookkeeping)."""
    m = json.loads(json.dumps(MANIFEST))
    first, base = m["workloads"][0]["name"], next(w for w in m["workloads"] if w["name"] == of)
    for i in range(k):
        m["workloads"].append(dict(base, name=f"more.{i}", chips=4))
        for group in ("end_to_end", "per_layer"):
            for metric in m[group]:
                if first in metric.get("workloads", []):
                    metric["workloads"].append(f"more.{i}")
    return m


def _named_in_one_more_reader(m, cell):
    """``cell`` in the list of the first per-layer metric that does not name it yet and moves an end-to-end metric the cell
    reports; None where every reader already names it."""
    reports = {e["name"] for e in mf.metrics_of(m, cell, "end_to_end")}
    metric = next((x for x in m["per_layer"] if cell not in x.get("workloads", [cell]) and x["moves"] in reports), None)
    if metric is not None:
        metric["workloads"].append(cell)
    return metric


@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_the_manifest_as_it_stands_has_room_by_the_rule_and_by_no_count(cell):
    """What a later PR may do without editing a test: add a cell, on four chips while a quarter of the cells (rounded down,
    one always) allows it, and list a cell in a reader that is there. No other test counts the cells, places an entry or
    pins a cell's set of metrics; this one states the rule over whatever the manifest holds, a case a cell."""
    n, four = len(MANIFEST["workloads"]), sum(w["chips"] == 4 for w in MANIFEST["workloads"])
    for k in range(1, mf.MAX_CELLS - n + 1):  # (a) four-chip copies of this cell, one more at a time, until the rule refuses one
        room = max(1, (n + k) // 4)
        refused = [f"workloads: more than {room} of {n + k} cells ask for four chips"] if four + k > room else []
        assert mf.problems(_with_four_chip_copies(cell, k)) == refused, k
        if refused:
            break
    m = json.loads(json.dumps(MANIFEST))  # (b) this cell in one more reader's list
    metric = _named_in_one_more_reader(m, cell)
    assert mf.problems(m) == [] and (metric is None or metric in mf.metrics_of(m, cell, "per_layer"))
    if four + 1 <= max(1, (n + 1) // 4):  # and the four-chip cell of (a) in a reader that the first cell is not in
        m = _with_four_chip_copies(cell, 1)
        metric = _named_in_one_more_reader(m, "more.0")
        assert mf.problems(m) == [] and (metric is None or metric in mf.metrics_of(m, "more.0", "per_layer"))


def _a_checkout_grown_by_a_cell(root, of, config):
    """A checkout at ``root`` as a PR that adds a cell leaves one: ``benchmarks/`` as it stands and ``BENCHMARK.json`` with one
    cell more, the cell ``of`` under the configuration name ``config``. What such a PR appends and nothing else: the
    configuration's entry and its three files under the new name, the cell LAST in ``workloads`` and last in the list of every
    metric that lists ``of`` (so of every metric that lists all cells), and one per-layer metric more, LAST, that lists the new
    cell alone (a copy of the newest reader that lists ``of``, so that its file states what its entry says). The copy asks for
    the chips of ``of`` where the quarter rule admits them. The new cell's name."""
    shutil.copytree(mf.BENCH, os.path.join(root, "benchmarks"), ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(mf.ROOT, "deepspeed_tpu"), os.path.join(root, "deepspeed_tpu"))  # a reader's layer names a module of the program: a test looks it up
    m = json.loads(json.dumps(MANIFEST))
    original = next(w for w in m["workloads"] if w["name"] == of)
    entry = next(c for c in m["configs"] if c["name"] == original["config"])
    cell = dict(original, name=f"{config}.{original['traffic']}", config=config)
    if sum(w["chips"] == 4 for w in m["workloads"]) + 1 > max(1, (len(m["workloads"]) + 1) // 4):
        cell["chips"] = 1  # the quarter rule has no room for a four-chip cell more: the copy keeps the original's lists and asks for one chip
    for file in glob.glob(os.path.join(mf.BENCH, "configs", entry["name"] + ".*")):  # the configuration, its plain reference, its FLOP module
        with open(file) as f, open(os.path.join(root, "benchmarks", "configs", config + os.path.basename(file)[len(entry["name"]):]), "w") as g:
            g.write(f.read().replace(f"configs/{entry['name']}.", f"configs/{config}."))
    m["configs"].append(dict(entry, name=config, file=f"benchmarks/configs/{config}.json"))
    m["workloads"].append(cell)
    for metric in m["end_to_end"] + m["per_layer"]:
        if of in metric.get("workloads", []):
            metric["workloads"].append(cell["name"])
    reader = [x for x in m["per_layer"] if of in x.get("workloads", []) and x["moves"] != "setup_s"][-1]
    m["per_layer"].append(dict(reader, name="a_later_prs_" + reader["name"], workloads=[cell["name"]]))
    shutil.copy(os.path.join(mf.BENCH, "metrics", reader["name"] + ".py"), os.path.join(root, "benchmarks", "metrics", "a_later_prs_" + reader["name"] + ".py"))
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(m, f)
    return cell["name"]


def _calls_pytest_would_make(fn):
    """``fn``'s arguments, a dict a case (the product of its ``parametrize`` marks); None where it takes anything its marks do
    not give it: a fixture (a temporary directory, a clean environment: what a test that writes files or rehearses asks for)."""
    names, values = [], []
    for mark in getattr(fn, "pytestmark", []):
        if mark.name == "skip" or (mark.name == "skipif" and mark.args and mark.args[0] is True):
            return []
        if mark.name == "parametrize":
            args = [a.strip() for a in mark.args[0].split(",")] if isinstance(mark.args[0], str) else list(mark.args[0])
            rows = [getattr(v, "values", v if len(args) > 1 else (v,)) for v in mark.args[1]]  # ``pytest.param(...)`` keeps a tuple there
            names += args
            values.append([dict(zip(args, row)) for row in rows])
    if set(inspect.signature(fn).parameters) != set(names):
        return None
    return [{k: v for part in case for k, v in part.items()} for case in itertools.product(*values)]


@pytest.mark.parametrize("of,config", [
    ("k-exaone-236b-l5e8.pretrain-8k-ep4", "thirteenth-l5e8"),  # (a) one more cell of four chips, while a quarter of the cells has room for it (3 of 13 today)
    ("smallthinker-21b-l4e8.pretrain-16k", "thirteenth-l4e8"),  # (b) one of one chip, in a reader that four cells share
])
def test_a_checkout_with_one_cell_more_keeps_every_rule_a_test_here_states_of_the_manifest(of, config, tmp_path, monkeypatch):
    """THE RULE: a test under ``tests/benchmarks/`` states a rule of the manifest, never its count or an entry's place. A PR that
    adds a cell may append entries and add files and may edit no file here, so an assertion that holds only of the cells
    there are today stops that PR and nobody else. This test finds such an assertion in the tier-1 run of the PR that writes
    it: in a checkout grown by a cell, every test function of this directory that names the manifest and takes no fixture
    (no rehearsal, no file written) is called as pytest would call it, and none may fail. The checkout is handed over where
    every module takes it from: ``benchmarks.lib.manifest``'s ``ROOT``, ``BENCH`` and the default roots of its functions."""
    root = str(tmp_path)
    new = _a_checkout_grown_by_a_cell(root, of, config)
    for fn in [f for f in vars(mf).values() if inspect.isfunction(f) and mf.ROOT in (f.__defaults__ or ())]:
        monkeypatch.setattr(fn, "__defaults__", tuple(root if d == mf.ROOT else d for d in fn.__defaults__))
    monkeypatch.setattr(mf, "ROOT", root)
    monkeypatch.setattr(mf, "BENCH", os.path.join(root, "benchmarks"))
    grown = mf.load_manifest()
    assert len(grown["workloads"]) == len(MANIFEST["workloads"]) + 1 and grown["workloads"][-1]["name"] == new and mf.problems(grown) == []
    assert grown["per_layer"][-1]["workloads"] == [new] and len(mf.metrics_of(grown, new, "per_layer")) == len(mf.metrics_of(grown, of, "per_layer")) + 1

    before, held, broken = set(sys.modules), [], []
    try:
        for path in sorted(glob.glob(os.path.join(os.path.dirname(os.path.abspath(__file__)), "test_*.py"))):
            with open(path) as f:
                if "load_manifest" not in f.read():
                    continue
            module = mf.load_module(path)  # a copy of its own: its ``MANIFEST`` is the grown one's, and so is every parametrisation made from it
            for name, fn in sorted(vars(module).items()):
                if not (name.startswith("test_") and inspect.isfunction(fn) and fn.__module__ == module.__name__):
                    continue
                source = inspect.getsource(fn)
                calls = _calls_pytest_would_make(fn) if "MANIFEST" in source or "load_manifest" in source else None
                for kwargs in calls or []:
                    case = f"{os.path.basename(path)}::{name}{sorted(kwargs.items()) if kwargs else ''}"[:300]
                    try:
                        fn(**kwargs)
                        held.append(case)
                    except pytest.skip.Exception:
                        pass
                    except Exception as e:  # every broken rule is reported, not the first: the list is what the PR's writer needs
                        at = traceback.extract_tb(e.__traceback__)[-1]  # these modules' asserts are not rewritten: the line says what the message cannot
                        broken.append(f"{case}: {type(e).__name__}: {str(e)[:300]} (line {at.lineno}: {at.line})")
    finally:
        for name in set(sys.modules) - before:
            if name.startswith("tests.benchmarks."):  # a helper module first imported here took the grown manifest: the next importer gets its own
                del sys.modules[name]
    assert broken == [], "\n".join(broken)
    assert any(case.startswith(os.path.basename(__file__) + "::test_manifest_has_no_problems") for case in held)  # it reached this file's own, at the least
    assert any(new in case for case in held)  # and a case that pytest would make of the new cell


@pytest.mark.parametrize("config,key", [(c, k) for c, keys in PUBLISHED.items() for k in keys])
def test_every_size_is_the_sources_or_listed_as_reduced(config, key):
    cfg = mf.load_json(os.path.join(mf.BENCH, "configs", f"{config}.json"))
    entry = next((e for e in MANIFEST["configs"] if e["name"] == config), None)
    assert mf.config_problems(cfg, entry) == []  # no width reduced; a listed entry says what its file says
    if key in cfg["reduced"]:
        assert cfg[key] != PUBLISHED[config][key]
    else:
        assert cfg[key] == PUBLISHED[config][key]
    if key in PROGRAM_KEYS:  # what the program is built with is what the file publishes
        assert cfg["program"][PROGRAM_KEYS[key]] == cfg[key]


def _a_cells_files_exist_and_name_their_pieces(manifest, cell, root):
    c = mf.cell(manifest, cell, root)
    assert os.path.isfile(os.path.join(root, "benchmarks", "drivers", c["config"]["kind"] + ".py"))  # a driver of any kind
    assert os.path.isfile(os.path.join(root, "benchmarks", "generators", c["traffic"]["generator"] + ".py"))
    assert c["traffic"]["why"] and c["traffic"]["who"] and "rehearse" in c["config"]


@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_a_cells_files_exist_and_name_their_pieces(cell):
    _a_cells_files_exist_and_name_their_pieces(MANIFEST, cell, mf.ROOT)


@pytest.mark.parametrize("metric", [m["name"] for m in MANIFEST["per_layer"]])
def test_a_per_layer_metric_moves_a_metric_its_cells_report(metric):
    m = next(x for x in MANIFEST["per_layer"] if x["name"] == metric)
    cells = m.get("workloads") or [w["name"] for w in MANIFEST["workloads"]]
    for cell in cells:
        assert m["moves"] in {e["name"] for e in mf.metrics_of(MANIFEST, cell, "end_to_end")}
    reader = mf.metric_module(metric)
    assert reader.read({"end_to_end": {}, "summary": {"tokens_total": 0}}) is None  # nothing to read: no number


@pytest.mark.parametrize("breakage,needle", [
    (lambda m: m["per_layer"][0].update(moves="ttft_p95_ms"), "does not report"),
    (lambda m: m["workloads"][0].update(traffic="no-such-mix"), "no traffic file"),
    (lambda m: m["end_to_end"][0].update(unit="tokens per second"), "bad unit"),
    (lambda m: m["end_to_end"][:] and m["end_to_end"].pop(), "setup_s"),
    (lambda m: m["configs"][0].update(source="https://elsewhere"), "source differs"),
])
def test_problems_are_found(breakage, needle):
    broken = json.loads(json.dumps(MANIFEST))
    breakage(broken)
    assert any(needle in p for p in mf.problems(broken))


def test_unknown_device_is_an_error_not_a_default():
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")


def test_parameter_and_flop_counts_from_shapes():
    olmo = mf.load_json(os.path.join(mf.BENCH, "configs", "olmo-1b.json"))
    assert abs(flops.total_params(olmo) / 1e9 - 1.177) < 0.001
    mistral = mf.load_json(os.path.join(mf.BENCH, "configs", "mistral-7b-l16.json"))
    assert abs(flops.total_params(mistral) / 1e9 - 3.752) < 0.001
    per_token = flops.train_flops_per_token(olmo, 2048)
    assert 6 * flops.matmul_params(olmo) < per_token < 6.5 * flops.matmul_params(olmo)
    fwd = flops.flash_attention_cost(2, 2048, 16, 16, 128, backward=False)
    assert fwd["flops"] == 2 * 2 * 16 * 2048 * 2048 * 128
    assert flops.roofline_seconds(fwd, peaks.peaks_for("TPU v5 lite"))["bound"] == "compute"


def test_each_kind_of_piece_is_added_by_new_files_and_appended_entries(tmp_path):
    """A throw-away configuration, traffic mix, generator, per-layer metric,
    driver and cell, in a temporary copy: nothing that was there is edited."""
    root = str(tmp_path)
    shutil.copytree(mf.BENCH, os.path.join(root, "benchmarks"), ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(mf.ROOT, "deepspeed_tpu"), os.path.join(root, "deepspeed_tpu"))
    before = {p: open(os.path.join(dp, p)).read() for dp, _, fs in os.walk(os.path.join(root, "benchmarks")) for p in fs}
    b = os.path.join(root, "benchmarks")
    json.dump({"kind": "toy", "source": "none", "hidden_size": 8, "reduced": [], "env": {}, "rehearse": {}},
              open(f"{b}/configs/toy.json", "w"))
    json.dump({"generator": "toy_gen", "why": "w", "who": "w", "params": {"n": 3}}, open(f"{b}/traffic/toy-mix.json", "w"))
    open(f"{b}/generators/toy_gen.py", "w").write("def generate(params, seed, seconds, ctx):\n    return {'items': list(range(params['n']))}\n")
    open(f"{b}/drivers/toy.py", "w").write(
        "from benchmarks.lib.manifest import BENCH, load_module\n"
        "def run(cell, opts):\n"
        "    gen = load_module(f\"{BENCH}/generators/{cell['traffic']['generator']}.py\")\n"
        "    n = len(gen.generate(cell['traffic']['params'], opts['seed'], opts['seconds'], {})['items'])\n"
        "    return {'correct': True, 'attempted': n, 'failed': 0, 'end_to_end': {'toy_rate': 1.0, 'setup_s': 0.1}, 'extras': {}}\n")
    open(f"{b}/metrics/toy_count.py", "w").write(
        "UNIT, BETTER, SOURCE, LAYER, MOVES = 'count', 'higher', 'program_counter', 'toy layer', 'toy_rate'\n"
        "def read(record):\n    return record['attempted']\n")
    m = json.loads(json.dumps(MANIFEST))
    m["configs"].append({"name": "toy", "source": "none", "file": "benchmarks/configs/toy.json", "reduced": [], "why": "w"})
    m["workloads"].append({"name": "toy.toy-mix", "config": "toy", "traffic": "toy-mix", "chips": 1, "why": "w"})
    m["end_to_end"].append({"name": "toy_rate", "unit": "1/s", "better": "higher", "bound": 0.01, "source": "host_clock",
                            "workloads": ["toy.toy-mix"]})
    m["per_layer"].append({"name": "toy_count", "unit": "count", "better": "higher", "source": "program_counter",
                           "layer": "toy layer", "moves": "toy_rate", "workloads": ["toy.toy-mix"]})
    json.dump(m, open(os.path.join(root, "BENCHMARK.json"), "w"))
    assert mf.problems(m, root) == []
    _a_cells_files_exist_and_name_their_pieces(m, "toy.toy-mix", root)  # a driver of a kind of its own is listed
    out = subprocess.run([sys.executable, f"{b}/run.py", "--workload", "toy.toy-mix", "--rehearse", "--seconds", "1"],
                         capture_output=True, text=True, timeout=300, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-2000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["attempted"] == 3 and last["correct"] is True
    after = {p: open(os.path.join(dp, p)).read() for dp, _, fs in os.walk(b) for p in fs if "__pycache__" not in dp}
    assert all(after[p] == text for p, text in before.items())  # nothing that was there changed
