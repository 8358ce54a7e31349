#!/usr/bin/env python
"""Render performance-accounting snapshots (``PerfAccountant.snapshot()``,
what the ops plane's ``GET /perf`` returns) as per-program attribution +
roofline tables.

Usage:
    python tools/perf_report.py PERF.json [--rung serve] [--json]

The file holds ``{"snapshots": {<name>: <snapshot>, ...}}``. Stdlib-only on
purpose: the artifact is produced on the TPU host, the report is usually
read elsewhere. Each snapshot renders as:

- headline: accounting mode, peak FLOP/s + bandwidth and the machine
  balance point, window totals, MFU, goodput fraction;
- the roofline table: one row per (program, bucket signature) cost card,
  sorted by attributed time — calls, FLOPs/call, HBM bytes/call, wall
  time, achieved TF/s and GB/s with %-of-peak, arithmetic intensity, and
  the compute/memory-bound classification;
- the goodput ledger: useful vs padded slot tokens, speculative tokens
  rejected by verification (and their priced FLOPs), prefill FLOPs saved
  by the prefix cache, COW copy bytes;
- HBM pools: weights / paged KV / prefix-held / compiled temp peak, and
  the pressure fraction against the device limit.

See docs/OBSERVABILITY.md "Performance accounting" for definitions.
"""

import argparse
import json
import os
import sys


def _num(x, unit="", precision=2):
    """Humanize a number: 1.23e12 -> '1.23T'."""
    x = float(x)
    for scale, suffix in ((1e12, "T"), (1e9, "G"), (1e6, "M"), (1e3, "K")):
        if abs(x) >= scale:
            return f"{x / scale:.{precision}f}{suffix}{unit}"
    return f"{x:.{precision}f}{unit}"


def _table(headers, rows):
    widths = [max(len(h), max((len(r[i]) for r in rows), default=0)) for i, h in enumerate(headers)]
    def fmt(cells):
        return "  ".join(c.ljust(w) if i == 0 else c.rjust(w)
                         for i, (c, w) in enumerate(zip(cells, widths)))
    lines = [fmt(headers), fmt(["-" * w for w in widths])]
    lines += [fmt(r) for r in rows]
    return "\n".join(lines)


def _card_label(card):
    meta = card.get("meta") or {}
    kind = meta.get("kind")
    label = card.get("program", "?")
    if kind and kind not in label:
        label = f"{label}[{kind}]"
    dims = ",".join(f"{k}={v}" for k, v in sorted(meta.items())
                    if k not in ("kind", "sampled") and isinstance(v, (int, float)))
    return f"{label}({dims})" if dims else label


def render_snapshot(rung, snap):
    out = []
    peaks = snap.get("peaks") or {}
    pk_f = float(peaks.get("flops_per_s") or 0.0)
    pk_b = float(peaks.get("bytes_per_s") or 0.0)
    totals = snap.get("totals") or {}
    out.append(f"== {rung} ==  (accounting mode {snap.get('mode', '?')})")
    if pk_f > 0 and pk_b > 0:
        out.append(f"peak: {_num(pk_f, 'F/s')}  {_num(pk_b, 'B/s')}  "
                   f"machine balance {peaks.get('machine_balance_flops_per_byte', 0.0):.1f} F/B")
    else:
        out.append("peak: unknown (set DS_TPU_PEAK_TFLOPS / DS_TPU_PEAK_GBPS; "
                   "MFU and %peak columns are dark)")
    mfu = snap.get("mfu")
    ledger = snap.get("ledger") or {}
    out.append(f"window: {_num(totals.get('flops', 0))}F over "
               f"{float(totals.get('time_s', 0.0)):.3f}s attributed"
               + (f", MFU {100.0 * mfu:.1f}%" if mfu is not None else "")
               + f", goodput {100.0 * float(ledger.get('goodput_fraction', 0.0)):.1f}%")

    cards = snap.get("cards") or []
    if cards:
        rows = []
        for c in cards:
            pctf = c.get("pct_peak_flops")
            pctb = c.get("pct_peak_bw")
            rows.append([
                _card_label(c),
                str(c.get("calls", 0)),
                _num(c.get("flops", 0)),
                _num(c.get("bytes_accessed", 0)),
                f"{float(c.get('time_s', 0.0)):.3f}",
                f"{float(c.get('achieved_tflops', 0.0)):.2f}",
                f"{pctf:.1f}" if pctf is not None else "-",
                f"{float(c.get('achieved_gbps', 0.0)):.1f}",
                f"{pctb:.1f}" if pctb is not None else "-",
                f"{float(c.get('intensity_flops_per_byte', 0.0)):.1f}",
                c.get("bound", "unknown"),
                c.get("source", "?"),
            ])
        out.append("")
        out.append(_table(["program", "calls", "flops/call", "bytes/call", "time_s",
                           "TF/s", "%pk", "GB/s", "%pk", "F/B", "bound", "src"], rows))

    out.append("")
    out.append("goodput ledger:")
    out.append(f"  useful/slot tokens: {int(ledger.get('useful_tokens', 0))}"
               f"/{int(ledger.get('slot_tokens', 0))}"
               f" (padding fill {100.0 * (1.0 - float(ledger.get('goodput_fraction', 0.0))):.1f}%)")
    if ledger.get("spec_proposed_tokens"):
        out.append(f"  spec: {int(ledger.get('spec_accepted_tokens', 0))}"
                   f"/{int(ledger.get('spec_proposed_tokens', 0))} accepted, "
                   f"{int(ledger.get('spec_rejected_tokens', 0))} rejected "
                   f"(~{_num(ledger.get('spec_rejected_flops', 0))}F wasted)")
    if ledger.get("prefix_hit_tokens"):
        out.append(f"  prefix cache: {int(ledger.get('prefix_hit_tokens', 0))} tokens reused "
                   f"(~{_num(ledger.get('prefix_saved_prefill_flops', 0))}F prefill saved)")
    if ledger.get("readmit_tokens"):
        out.append(f"  kv readmit: {int(ledger.get('readmit_tokens', 0))} tokens over h2d "
                   f"(~{_num(ledger.get('readmit_saved_prefill_flops', 0))}F prefill saved)")
    if ledger.get("cow_copy_bytes"):
        out.append(f"  cow copies: {_num(ledger.get('cow_copy_bytes', 0), 'B')}")

    hbm = snap.get("hbm") or {}
    out.append("hbm pools:")
    for k in ("weights", "kv_pages", "prefix", "temp_peak", "host_spill"):
        out.append(f"  {k:<10} {_num(hbm.get(k, 0), 'B')}")
    if hbm.get("limit"):
        out.append(f"  pressure   {100.0 * float(hbm.get('pressure', 0.0)):.1f}% "
                   f"of {_num(hbm['limit'], 'B')} limit")
    else:
        out.append("  pressure   n/a (no device memory limit reported)")
    return "\n".join(out)


def render(doc, rung=None):
    snaps = doc.get("snapshots") or {}
    if rung is not None:
        if rung not in snaps:
            raise KeyError(f"rung {rung!r} not in artifact (have {sorted(snaps)})")
        snaps = {rung: snaps[rung]}
    return "\n\n".join(render_snapshot(r, s) for r, s in sorted(snaps.items()))


# --------------------------------------------------------------- diff mode

# headline metric -> direction: +1 = higher is better, -1 = lower is better
HEADLINE_METRICS = (("tokens_per_sec", +1), ("mfu", +1),
                    ("goodput_fraction", +1), ("dispatches", -1))


def snapshot_headline(snap):
    """The comparable scalars of one snapshot."""
    totals = snap.get("totals") or {}
    ledger = snap.get("ledger") or {}
    time_s = float(totals.get("time_s") or 0.0)
    useful = float(totals.get("useful_tokens") or 0.0)
    return {
        "tokens_per_sec": useful / time_s if time_s > 0 else 0.0,
        "mfu": snap.get("mfu"),
        "goodput_fraction": float(ledger.get("goodput_fraction") or 0.0),
        "dispatches": float(sum(int(c.get("calls", 0)) for c in snap.get("cards") or [])),
    }


def diff_rows(head_a, head_b, threshold):
    """Per-metric comparison rows; each carries a ``regressed`` verdict
    (a relative drop beyond ``threshold`` in the metric's good direction)."""
    rows = []
    for metric, sign in HEADLINE_METRICS:
        a, b = head_a.get(metric), head_b.get(metric)
        row = {"metric": metric, "a": a, "b": b, "delta": None,
               "pct": None, "regressed": False}
        if isinstance(a, (int, float)) and isinstance(b, (int, float)):
            row["delta"] = b - a
            if a:
                row["pct"] = (b - a) / abs(a)
                row["regressed"] = sign * row["pct"] < -threshold
        rows.append(row)
    return rows


def render_compare(rows, label_a="A", label_b="B"):
    """Render comparison rows (also reused by the replay what-if CLI:
    any rows shaped {metric, a, b, delta[, pct, regressed]})."""
    def cell(v):
        if v is None:
            return "-"
        if isinstance(v, float):
            return f"{v:.4g}"
        return str(v)
    table_rows = []
    for r in rows:
        pct = r.get("pct")
        table_rows.append([
            str(r["metric"]), cell(r.get("a")), cell(r.get("b")),
            cell(r.get("delta")),
            f"{100.0 * pct:+.1f}%" if isinstance(pct, (int, float)) else "-",
            "REGRESSED" if r.get("regressed") else "",
        ])
    return _table(["metric", label_a, label_b, "delta", "pct", ""], table_rows)


def render_diff(doc_a, doc_b, label_a, label_b, rung=None, threshold=0.05):
    """Compare two snapshot files, name by name. Returns (report text,
    regressed flag)."""
    snaps_a = doc_a.get("snapshots") or {}
    snaps_b = doc_b.get("snapshots") or {}
    rungs = sorted(set(snaps_a) & set(snaps_b))
    if rung is not None:
        if rung not in rungs:
            raise KeyError(f"rung {rung!r} not in both artifacts (common: {rungs})")
        rungs = [rung]
    out, regressed = [], False
    for r in rungs:
        rows = diff_rows(snapshot_headline(snaps_a[r]), snapshot_headline(snaps_b[r]),
                         threshold)
        regressed = regressed or any(row["regressed"] for row in rows)
        out.append(f"== {r} ==  ({label_a} -> {label_b}, threshold {100.0 * threshold:.0f}%)")
        out.append(render_compare(rows, label_a=label_a, label_b=label_b))
    only_a = sorted(set(snaps_a) - set(snaps_b))
    only_b = sorted(set(snaps_b) - set(snaps_a))
    if only_a:
        out.append(f"(rungs only in {label_a}: {', '.join(only_a)})")
    if only_b:
        out.append(f"(rungs only in {label_b}: {', '.join(only_b)})")
    if not rungs:
        out.append("no common rungs to compare")
    return "\n\n".join(out), regressed


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("path", nargs="?", default=None, help="snapshot file (not with --diff)")
    ap.add_argument("--rung", default=None, help="render the snapshot of this name only")
    ap.add_argument("--json", action="store_true", help="echo the (selected) raw JSON instead")
    ap.add_argument("--diff", nargs=2, metavar=("A.json", "B.json"), default=None,
                    help="compare two snapshot files name by name "
                         "(tokens/s, MFU, goodput, dispatches); exits 1 on "
                         "a regression beyond --threshold")
    ap.add_argument("--threshold", type=float, default=0.05,
                    help="relative regression threshold for --diff (default 0.05)")
    args = ap.parse_args(argv)
    if args.diff is not None:
        path_a, path_b = args.diff
        try:
            with open(path_a) as f:
                doc_a = json.load(f)
            with open(path_b) as f:
                doc_b = json.load(f)
        except OSError as e:
            print(f"perf_report: cannot read diff input: {e}", file=sys.stderr)
            return 1
        try:
            text, regressed = render_diff(doc_a, doc_b,
                                          os.path.basename(path_a), os.path.basename(path_b),
                                          rung=args.rung, threshold=args.threshold)
        except KeyError as e:
            print(f"perf_report: {e.args[0]}", file=sys.stderr)
            return 1
        print(text)
        return 1 if regressed else 0
    if args.path is None:
        ap.error("a snapshot file, or --diff A.json B.json")
    try:
        with open(args.path) as f:
            doc = json.load(f)
    except OSError as e:
        print(f"perf_report: cannot read {args.path}: {e}", file=sys.stderr)
        return 1
    try:
        if args.json:
            snaps = doc.get("snapshots") or {}
            sel = snaps if args.rung is None else {args.rung: snaps[args.rung]}
            print(json.dumps(sel, indent=1, sort_keys=True))
        else:
            print(render(doc, rung=args.rung))
    except KeyError as e:
        print(f"perf_report: {e.args[0]}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
