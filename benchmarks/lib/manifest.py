"""``BENCHMARK.json`` and the files it names: loading, the checks this
benchmark holds itself to, and finding a cell's pieces by name."""

import importlib.util
import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_manifest(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def load_module(path: str):
    """A module from a file whose name may hold dots (``mfu.train.py``)."""
    name = "bench_" + re.sub(r"\W", "_", os.path.relpath(path, ROOT))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell(manifest: dict, workload: str, root: str = ROOT) -> dict:
    """Everything one cell names: its entry, configuration and traffic files."""
    by_name = {w["name"]: w for w in manifest["workloads"]}
    if workload not in by_name:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json (have: {sorted(by_name)})")
    w = by_name[workload]
    return compose(manifest, w["config"], w["traffic"], w["chips"], root, name=workload)


def compose(manifest: dict, config: str, traffic: str, chips: int, root: str = ROOT, name: str = None) -> dict:
    files = {c["name"]: c["file"] for c in manifest["configs"]}
    cfg_file = os.path.join(root, files.get(config, f"benchmarks/configs/{config}.json"))
    return {"name": name or f"{config}.{traffic}", "chips": int(chips),
            "config": load_json(cfg_file), "config_name": config,
            "traffic": load_json(os.path.join(root, "benchmarks", "traffic", f"{traffic}.json")), "traffic_name": traffic}


def published(config: dict) -> dict:
    """The configuration file's own numbers (the source's keys): what the plain
    reference and the FLOP counts are given."""
    return {k: v for k, v in config.items() if isinstance(v, (int, float, bool)) or v is None}


def metrics_of(manifest: dict, workload: str, group: str) -> list:
    """The metrics of ``group`` (``end_to_end`` or ``per_layer``) this cell reports."""
    return [m for m in manifest[group] if "workloads" not in m or workload in m["workloads"]]


def metric_module(name: str, root: str = ROOT):
    return load_module(os.path.join(root, "benchmarks", "metrics", f"{name}.py"))


def problems(manifest: dict, root: str = ROOT) -> list:
    """What is wrong with the manifest and its files, as a list of sentences
    (empty: nothing). The driver's own contract is wider; this is the part a
    later PR breaks most easily: names, units, files, and the rule that a
    per-layer metric is reported only where the metric it moves is."""
    out = []
    names = lambda group: [m["name"] for m in manifest[group]]
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for n in names(group):
            if not NAME.match(n):
                out.append(f"{group}: bad name {n!r}")
        if len(set(names(group))) != len(names(group)):
            out.append(f"{group}: a name appears twice")
    if "setup_s" not in names("end_to_end"):
        out.append("end_to_end lacks setup_s")
    cells = {w["name"]: w for w in manifest["workloads"]}
    cfgs = {c["name"]: c for c in manifest["configs"]}
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if not UNIT.match(m["unit"]):
            out.append(f"{m['name']}: bad unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher") or m["source"] not in SOURCES:
            out.append(f"{m['name']}: bad better/source")
        for w in m.get("workloads", []):
            if w not in cells:
                out.append(f"{m['name']}: lists unknown workload {w!r}")
    for m in manifest["end_to_end"]:
        if m["source"] not in ("host_clock", "device_trace"):
            out.append(f"{m['name']}: an end-to-end metric is taken by the benchmark itself")
        if not 0 < m["bound"] <= 0.1:
            out.append(f"{m['name']}: bound {m['bound']} outside (0, 0.1]")
    for name, w in cells.items():
        if w["config"] not in cfgs:
            out.append(f"{name}: unknown config {w['config']!r}")
            continue
        if not os.path.isfile(os.path.join(root, cfgs[w["config"]]["file"])):
            out.append(f"{name}: no file {cfgs[w['config']]['file']}")
        traffic = os.path.join(root, "benchmarks", "traffic", f"{w['traffic']}.json")
        if not os.path.isfile(traffic):
            out.append(f"{name}: no traffic file for {w['traffic']!r}")
        else:
            gen = load_json(traffic).get("generator")
            if not os.path.isfile(os.path.join(root, "benchmarks", "generators", f"{gen}.py")):
                out.append(f"{name}: no generator {gen!r}")
        e2e = {m["name"] for m in metrics_of(manifest, name, "end_to_end")}
        if len(e2e - {"setup_s"}) < 1 or "setup_s" not in e2e:
            out.append(f"{name}: needs setup_s and one more end-to-end metric")
        layer = metrics_of(manifest, name, "per_layer")
        if not layer:
            out.append(f"{name}: reports no per-layer metric")
        for m in layer:
            if m["moves"] not in e2e:
                out.append(f"{name}: {m['name']} moves {m['moves']}, which this cell does not report")
    for c in cfgs.values():
        if not any(w["config"] == c["name"] for w in cells.values()):
            out.append(f"config {c['name']} is used by no cell")
    for m in manifest["per_layer"]:
        path = os.path.join(root, "benchmarks", "metrics", f"{m['name']}.py")
        if not os.path.isfile(path):
            out.append(f"{m['name']}: no reader {os.path.relpath(path, root)}")
            continue
        mod = load_module(path)
        for key in ("unit", "layer", "moves", "source", "better"):
            if getattr(mod, key.upper(), None) != m[key]:
                out.append(f"{m['name']}: {key} differs between BENCHMARK.json and its reader")
    return out
