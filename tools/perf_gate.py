#!/usr/bin/env python
"""Continuous perf-regression sentinel (docs/OBSERVABILITY.md
"Closing the loop").

    python tools/perf_gate.py                                  # candidate BENCH_PERF.json vs frozen baseline
    python tools/perf_gate.py --candidate out/BENCH_PERF.json
    python tools/perf_gate.py --update-baseline                # promote the candidate

Compares a candidate ``BENCH_PERF.json`` (the bench harness artifact)
against the committed frozen baseline ``tools/perf_baseline.json``
using ``perf_report.py``'s per-rung headline diff, with per-rung /
per-metric regression budgets from ``tools/perf_thresholds.json``.
Every run appends one JSON line to the trend ledger
(``tools/perf_trend.jsonl``, git-ignored) so a slow drift is visible
even while each step stays inside its budget. Exits nonzero naming
every regressing (rung, metric) pair; exits 0 on the committed
baseline vs itself.
"""

import argparse
import importlib.util
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))  # repo root

_TOOLS_DIR = os.path.dirname(os.path.abspath(__file__))
_REPO_ROOT = os.path.dirname(_TOOLS_DIR)

DEF_BASELINE = os.path.join(_TOOLS_DIR, "perf_baseline.json")
DEF_THRESHOLDS = os.path.join(_TOOLS_DIR, "perf_thresholds.json")
DEF_CANDIDATE = os.path.join(_REPO_ROOT, "BENCH_PERF.json")
DEF_LEDGER = os.path.join(_TOOLS_DIR, "perf_trend.jsonl")


def _perf_report():
    spec = importlib.util.spec_from_file_location(
        "perf_report_cli", os.path.join(_TOOLS_DIR, "perf_report.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def _load(path, what):
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        raise SystemExit(f"perf_gate: cannot read {what} {path}: {e}")


def gate(baseline, candidate, thresholds, rung=None, fallback=0.05):
    """Pure comparison: returns (regressions, rows_by_rung) where each
    regression is {rung, metric, pct, budget, baseline, candidate}."""
    pr = _perf_report()
    snaps_a = baseline.get("snapshots") or {}
    snaps_b = candidate.get("snapshots") or {}
    rungs = sorted(set(snaps_a) & set(snaps_b))
    if rung is not None:
        if rung not in rungs:
            raise SystemExit(f"perf_gate: rung {rung!r} not in both artifacts "
                             f"(common: {rungs})")
        rungs = [rung]
    regressions, by_rung = [], {}
    for r in rungs:
        budget = pr.threshold_resolver(thresholds, r, fallback)
        rows = pr.diff_rows(pr.snapshot_headline(snaps_a[r]),
                            pr.snapshot_headline(snaps_b[r]), budget)
        by_rung[r] = rows
        for row in rows:
            if row["regressed"]:
                regressions.append({
                    "rung": r, "metric": row["metric"], "pct": row["pct"],
                    "budget": row["budget"], "baseline": row["a"],
                    "candidate": row["b"]})
    return regressions, by_rung


def append_ledger(path, entry) -> None:
    with open(path, "a") as f:
        f.write(json.dumps(entry, sort_keys=True, default=str) + "\n")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--baseline", default=DEF_BASELINE,
                    help="frozen baseline artifact (default tools/perf_baseline.json)")
    ap.add_argument("--candidate", default=DEF_CANDIDATE,
                    help="candidate BENCH_PERF.json (default repo BENCH_PERF.json)")
    ap.add_argument("--thresholds", default=DEF_THRESHOLDS,
                    help="per-rung/per-metric budget file")
    ap.add_argument("--threshold", type=float, default=0.05,
                    help="fallback budget for entries the file does not list")
    ap.add_argument("--rung", default=None, help="gate one rung only")
    ap.add_argument("--ledger", default=DEF_LEDGER,
                    help="trend ledger to append (JSONL)")
    ap.add_argument("--no-ledger", action="store_true")
    ap.add_argument("--update-baseline", action="store_true",
                    help="promote the candidate to the frozen baseline and exit")
    ap.add_argument("--json", action="store_true",
                    help="emit the verdict as JSON on stdout")
    args = ap.parse_args(argv)

    candidate = _load(args.candidate, "candidate")
    if args.update_baseline:
        with open(args.baseline, "w") as f:
            json.dump(candidate, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"perf_gate: baseline <- {args.candidate}")
        return 0

    baseline = _load(args.baseline, "baseline")
    thresholds = _load(args.thresholds, "thresholds") if args.thresholds else None
    regressions, by_rung = gate(baseline, candidate, thresholds,
                                rung=args.rung, fallback=args.threshold)

    pr = _perf_report()
    if not args.json:
        for r, rows in by_rung.items():
            print(f"== {r} ==  (baseline -> candidate)")
            print(pr.render_compare(rows, label_a="baseline", label_b="candidate"))
            print()
    if not by_rung:
        print("perf_gate: no common rungs between baseline and candidate",
              file=sys.stderr)
        return 2

    entry = {
        "ts_unix": time.time(),
        "baseline": os.path.abspath(args.baseline),
        "candidate": os.path.abspath(args.candidate),
        "rungs": {r: {row["metric"]: {"baseline": row["a"],
                                      "candidate": row["b"],
                                      "pct": row["pct"],
                                      "budget": row["budget"],
                                      "regressed": row["regressed"]}
                      for row in rows}
                  for r, rows in by_rung.items()},
        "regressed": bool(regressions),
    }
    if not args.no_ledger:
        try:
            append_ledger(args.ledger, entry)
        except OSError as e:
            print(f"perf_gate: ledger append failed: {e}", file=sys.stderr)

    if args.json:
        print(json.dumps({"regressions": regressions, "entry": entry},
                         indent=2, sort_keys=True, default=str))
    if regressions:
        for reg in regressions:
            print(f"perf_gate: REGRESSION {reg['rung']}.{reg['metric']} "
                  f"{100.0 * reg['pct']:+.1f}% (budget {100.0 * reg['budget']:.1f}%): "
                  f"{reg['baseline']:.6g} -> {reg['candidate']:.6g}",
                  file=sys.stderr)
        return 1
    print("perf_gate: PASS (no headline metric beyond budget)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
