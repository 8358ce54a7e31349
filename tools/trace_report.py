#!/usr/bin/env python
"""Render a device-timeline capture as a per-quantum waterfall.

Usage:
    python tools/trace_report.py <target> [--json] [--top N]
    python tools/trace_report.py smoke [--dir DIR]

``<target>`` is any of: a capture directory holding ``summary.json``
(what ``telemetry/profiler.py`` writes next to the raw trace), a
``DS_TPU_PROFILE_DIR`` holding ``capture-*`` subdirectories (the newest
summarised capture is picked), a raw profiler output directory (e.g. a
flight capture's ``profile/`` — parsed on the fly as one window), a
``summary.json`` file, or a raw ``.xplane.pb`` file.

Output: the waterfall table (per-quantum device compute / collective
split exposed-vs-overlapped / transfer / host gap), the top-N device
operations, the exposed-collective summary cross-checked against the
``tp_all_reduce`` ledger and, for a capture of a training run
(``DS_TPU_PROFILE=1``: docs/OBSERVABILITY.md, "Regions"), the compiled
step's device time by region and phase, and for a capture that a hunt kept
(``DS_TPU_PROFILE=stall``) the stalled step, for a capture of set-up
(``DS_TPU_PROFILE=setup``) its programs' first executions. ``--json`` dumps the summary
document instead.

``smoke`` captures an 8-request fused serving run end-to-end (arm →
trace → parse) and asserts nonzero device time and a well-formed
waterfall — run by ``tools/lint_all.py --profile-smoke``.
"""

import argparse
import glob
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _load_summary(target):
    """Resolve any accepted target shape to a summary document."""
    from deepspeed_tpu.telemetry import profiler as prof

    if os.path.isfile(target):
        if target.endswith(".xplane.pb"):
            summary = prof.build_waterfall(prof.parse_trace_events(prof.load_xplane(target)), markers=[])
            summary["trace"] = "ok"
            return summary
        with open(target) as f:
            doc = json.load(f)
        return doc.get("summary", doc)  # profile-rank<k>.json wraps it
    if os.path.isdir(target):
        direct = os.path.join(target, "summary.json")
        if os.path.isfile(direct):
            with open(direct) as f:
                return json.load(f)
        captures = sorted(glob.glob(os.path.join(target, "capture-*")))
        for cap in reversed(captures):
            path = os.path.join(cap, "summary.json")
            if os.path.isfile(path):
                with open(path) as f:
                    return json.load(f)
        # raw profiler output (flight capture profile/): parse on the fly
        return prof.summarize_trace_dir(target)
    raise SystemExit(f"trace_report: no capture at {target!r}")


def _ms(v):
    return f"{float(v) * 1e3:9.3f}"


def render(summary, top=8):
    lines = []
    totals = summary.get("totals") or {}
    fr = summary.get("fractions") or {}
    lines.append(f"device-timeline capture: trace={summary.get('trace', '?')} "
                 f"window={totals.get('wall_s', summary.get('window_s', 0.0))}s "
                 f"quanta={summary.get('n_quanta', 0)} "
                 f"events={summary.get('n_events', 0)}")
    lines.append("")
    lines.append("per-quantum waterfall (ms):")
    lines.append(f"  {'idx':>3} {'program':<14} {'start':>9} {'dur':>9} "
                 f"{'compute':>9} {'coll':>9} {'exposed':>9} {'xfer':>9} "
                 f"{'hostgap':>9}")
    for q in summary.get("quanta") or []:
        lines.append(f"  {q['index']:>3} {q['program']:<14.14}"
                     f" {_ms(q['start_s'])} {_ms(q['dur_s'])}"
                     f" {_ms(q['compute_s'])} {_ms(q['collective_s'])}"
                     f" {_ms(q['collective_exposed_s'])} {_ms(q['transfer_s'])}"
                     f" {_ms(q['host_gap_s'])}")
    if summary.get("quanta_truncated"):
        lines.append(f"  ... {summary['quanta_truncated']} more quanta truncated")
    lines.append("")
    lines.append(f"totals: compute {_ms(totals.get('compute_s', 0)).strip()}ms"
                 f"  collective {_ms(totals.get('collective_s', 0)).strip()}ms"
                 f"  transfer {_ms(totals.get('transfer_s', 0)).strip()}ms"
                 f"  host gap {_ms(totals.get('host_gap_s', 0)).strip()}ms")
    lines.append(f"fractions: device busy {fr.get('device_busy', 0.0):.3f}"
                 f"  host gap {fr.get('host_gap', 0.0):.3f}"
                 f"  collective exposed {fr.get('collective_exposed', 0.0):.3f}")
    progs = (summary.get("programs") or [])[:top]
    if progs:
        lines.append("")
        lines.append(f"top {len(progs)} device programs:")
        for name, sec in progs:
            lines.append(f"  {_ms(sec)}ms  {name}")
    coll = summary.get("collectives") or {}
    lines.append("")
    lines.append("exposed-collective summary:")
    lines.append(f"  trace ops {coll.get('trace_ops', 0)}"
                 f"  time {_ms(coll.get('trace_s', 0)).strip()}ms"
                 f"  exposed {_ms(coll.get('exposed_s', 0)).strip()}ms"
                 f"  overlapped {_ms(coll.get('overlapped_s', 0)).strip()}ms"
                 f"  exposed fraction {coll.get('exposed_fraction', 0.0):.3f}")
    ledger = coll.get("ledger") or {}
    if ledger:
        lines.append(f"  tp_all_reduce ledger: {json.dumps(ledger, sort_keys=True)}")
    if "error" in summary:
        lines.append(f"  note: {summary['error']}")
    if summary.get("idle_by_span"):
        lines.append("")
        lines.append("idle time of the first device, by the host span it lay under (ms): "
                     + ", ".join(f"{k} {float(v) * 1e3:.3f}" for k, v in summary["idle_by_span"].items()))
    if summary.get("capture_cost_s"):
        lines.append("the capture cost (s): " + ", ".join(f"{k} {v}" for k, v in summary["capture_cost_s"].items()))
    if summary.get("hunted"):
        lines.append(f"the hunt: {sum(not h['kept'] for h in summary['hunted'])} captures dropped before this one; each capture's cost (s): "
                     + "; ".join(", ".join(f"{k} {v}" for k, v in h.items()) for h in summary["hunted"][-top:]))
    if (summary.get("setup") or {}).get("programs"):  # a capture of set-up (``DS_TPU_PROFILE=setup``)
        lines += ["", "set-up's programs on the first device, in the order they first ran (name, executions, first began at s, first took s, later median s):"]
        lines += ["  " + "  ".join(str(v) for v in row) for row in summary["setup"]["programs"]]
    if summary.get("stall"):
        lines += ["", render_stall(summary["stall"])]
    if summary.get("regions"):
        lines += ["", render_regions(summary["regions"], top=top)]
    return "\n".join(lines)


def render_stall(stall):
    """The stalled quantum of a capture the hunt kept (``DS_TPU_PROFILE=stall``)."""
    ms = lambda s: f"{float(s) * 1e3:.3f}"
    lines = [f"the stalled step: quantum {stall['quantum']} took {ms(stall['period_s'])} ms against a median of {ms(stall['median_s'])}; "
             f"the first device busy {ms(stall['device_busy_s'])}, idle {ms(stall['device_idle_s'])}"]
    for gap in stall.get("idle", []):
        lines.append(f"  idle for {ms(gap['dur_s'])} ms from {ms(gap['start_s'])} ms into it ({ms(gap.get('quiet_s', 0.0))} of them with no host thread "
                     "in an event of the runtime's), under " + ", ".join(f"{k} {ms(v)}" for k, v in gap["under"].items()))
        lines += [f"    {ms(sec):>10} ms  {line}: {name}" for line, name, sec in gap["host"]]
    if stall.get("long_ops"):
        lines.append("  operations over 1.5 x their own median of the other quanta (ms here | usual): "
                     + ", ".join(f"{name} {ms(here)} | {ms(usual)}" for name, here, usual in stall["long_ops"]))
    return "\n".join(lines)


PHASES = ("forward", "recomputed", "backward", "update")


def render_regions(regions, top=8):
    """The compiled step's device time by region and phase, ms a step."""
    if "table" not in regions:
        return f"regions: {regions.get('error', 'none')}"
    ms = lambda s: f"{float(s) * 1e3:10.3f}"
    table = regions["table"]
    lines = [f"regions of {regions['module']}: {regions['steps']} steps on {regions['devices']} device(s), ms a step; "
             f"device self time {float(regions['step_self_s']) * 1e3:.3f}, the program's events {float(regions['step_module_s']) * 1e3:.3f}, "
             f"start to start {float(regions.get('step_period_s', 0.0)) * 1e3:.3f}",
             f"  {'region':<20}" + "".join(f"{p:>11}" for p in PHASES) + f"{'all':>11}{'share':>8}"]
    total = float(regions["step_self_s"]) or 1.0
    for region, row in sorted(table.items(), key=lambda kv: -sum(kv[1].values())):
        lines.append(f"  {region:<20}" + "".join(ms(row.get(p, 0.0)) + " " for p in PHASES) + ms(sum(row.values()))
                     + f" {100 * sum(row.values()) / total:6.2f}%")
    by_phase = [sum(row.get(p, 0.0) for row in table.values()) for p in PHASES]
    lines.append(f"  {'all':<20}" + "".join(ms(v) + " " for v in by_phase) + ms(sum(by_phase)) + f" {100 * sum(by_phase) / total:6.2f}%")
    lines.append(f"  unattributed {100 * regions['unattributed_share']:.2f}% of the device self time; in fusions that span "
                 f"several regions {100 * regions['mixed_share']:.2f}% ({float(regions['mixed_s']) * 1e3:.3f} ms), charged to "
                 f"the region of their product, else of their root")
    for row in regions.get("mixed", [])[:top]:
        lines.append(f"    {ms(row['s'])} ms  {row['fusion']} -> {row['region']}/{row['phase']}  holds "
                     + ", ".join(f"{r} x{n}" for r, n in sorted(row["members"].items())))
    if regions.get("unattributed"):
        lines.append("  the longest with no region: " + ", ".join(f"{k} {float(v) * 1e3:.3f}" for k, v in sorted(regions["unattributed"].items(), key=lambda kv: -kv[1])[:top]))
    if regions.get("compiler_made"):
        lines.append("  what the compiler made itself, by opcode and the region it was given (ms a step): "
                     + ", ".join(f"{k} {float(v) * 1e3:.3f}" for k, v in sorted(regions["compiler_made"].items(), key=lambda kv: -kv[1])[:2 * top]))
    if regions.get("within"):
        lines.append("  by what encloses them (ms a step):")
        for outer, row in regions["within"].items():
            if outer != "block":
                lines.append(f"    {outer}: " + ", ".join(f"{r} {float(v) * 1e3:.3f}" for r, v in sorted(row.items(), key=lambda kv: -kv[1])))
    if regions.get("kernels"):
        lines.append("  Pallas calls (ms a step): " + ", ".join(f"{k} {float(v) * 1e3:.3f}" for k, v in sorted(regions["kernels"].items(), key=lambda kv: -kv[1])))
    lines.append("  how the card found the region (ms a step): " + ", ".join(f"{k} {float(v) * 1e3:.3f}" for k, v in regions.get("found", {}).items()))
    if regions.get("note"):
        lines.append(f"  NOTE: {regions['note']}")
    return "\n".join(lines)


def check_waterfall(summary, require_device_time=True):
    """Well-formedness assertions shared by smoke and tests; returns a
    list of failure strings (empty = healthy)."""
    bad = []
    if not isinstance(summary, dict):
        return ["summary is not a dict"]
    for key in ("totals", "fractions", "quanta", "collectives"):
        if key not in summary:
            bad.append(f"missing section {key!r}")
    for q in summary.get("quanta") or []:
        for k in ("program", "start_s", "dur_s", "compute_s", "collective_s",
                  "collective_exposed_s", "transfer_s", "host_gap_s"):
            if k not in q:
                bad.append(f"quantum {q.get('index')} missing {k!r}")
                break
    fr = summary.get("fractions") or {}
    for k in ("device_busy", "host_gap", "collective_exposed"):
        v = fr.get(k)
        if not isinstance(v, (int, float)) or not 0.0 <= v <= 1.0:
            bad.append(f"fraction {k!r} out of [0,1]: {v!r}")
    if require_device_time and not (summary.get("totals") or {}).get("compute_s"):
        bad.append("no device compute time in capture")
    return bad


def cmd_smoke(args) -> int:
    """Capture an 8-request fused serving run and assert the waterfall."""
    import jax
    import numpy as np

    from deepspeed_tpu.models import CausalLM
    from deepspeed_tpu.models.transformer import TransformerConfig
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2, RaggedBatchConfig,
                                            RaggedInferenceEngineConfig)
    from deepspeed_tpu.telemetry import profiler as prof_mod

    outdir = args.dir or tempfile.mkdtemp(prefix="profile-smoke-")
    cfg = TransformerConfig(vocab_size=128, n_layers=2, n_heads=4, n_kv_heads=2,
                            d_model=32, max_seq_len=128, norm="rmsnorm",
                            activation="swiglu", pos_emb="rope",
                            tie_embeddings=False)
    model = CausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        {"input_ids": np.zeros((1, 8), np.int32)})
    eng = InferenceEngineV2(model, params, RaggedInferenceEngineConfig(
        state_manager=RaggedBatchConfig(kv_block_size=8, max_context=128,
                                        num_kv_blocks=64),
        dtype="float32"))
    rng = np.random.RandomState(7)
    prompts = [rng.randint(1, cfg.vocab_size, size=int(l)).tolist()
               for l in rng.randint(4, 9, size=8)]
    eng.generate(prompts, max_new_tokens=8)  # compile outside the capture
    prof, armed = prof_mod.request_capture(quanta=6)
    prof.out_dir = outdir
    if not armed:
        print("smoke: FAIL — profiler already tracing", file=sys.stderr)
        return 1
    eng.generate(prompts, max_new_tokens=8)
    summary = prof.finish()
    if summary is None:
        print("smoke: FAIL — no capture landed (no quanta dispatched?)",
              file=sys.stderr)
        return 1
    print(render(summary))
    # device lanes are a TPU's planes: on the CPU the capture is checked for its form alone
    failures = check_waterfall(summary, require_device_time=jax.default_backend() == "tpu")
    for msg in failures:
        print(f"smoke: FAIL — {msg}", file=sys.stderr)
    if not failures:
        print(f"smoke: PASS (capture under {outdir})")
    return 1 if failures else 0


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if argv and argv[0] == "smoke":
        ap = argparse.ArgumentParser(prog="trace_report.py smoke")
        ap.add_argument("--dir", default=None,
                        help="capture output dir (default: temp dir)")
        return cmd_smoke(ap.parse_args(argv[1:]))
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("target",
                    help="capture dir, DS_TPU_PROFILE_DIR, raw profiler dir, "
                         "summary.json, or .xplane.pb — or 'smoke'")
    ap.add_argument("--json", action="store_true",
                    help="dump the summary document instead of tables")
    ap.add_argument("--top", type=int, default=8,
                    help="device programs to list (default 8)")
    args = ap.parse_args(argv)
    summary = _load_summary(args.target)
    if args.json:
        print(json.dumps(summary, indent=2, sort_keys=True))
    else:
        print(render(summary, top=args.top))
    return 0


if __name__ == "__main__":
    sys.exit(main())
