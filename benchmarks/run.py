#!/usr/bin/env python3
"""One cell of the benchmark, once, in a new process.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

builds seeded weights on the device, warms up, measures for ``--seconds`` and
prints one JSON object as the last line of its output: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``), ``device`` and, traced,
``breakdown``. Without a TPU, or with fewer chips than the cell asks for, it
exits non-zero and prints no result: the measuring path has no CPU branch.

``--rehearse`` runs the same control flow on the CPU at the tiny width of the
configuration's ``rehearse`` block, with interpreted kernels, and prints counts
but never a metric. ``--config/--traffic/--chips`` compose a cell that
BENCHMARK.json does not list (the capacity run). See benchmarks/README.md.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)



def _merge(base, over):
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(out[k], v) if isinstance(v, dict) and isinstance(out.get(k), dict) else v
    return out


def _rehearsal(cell):
    cfg, traffic = cell["config"], cell["traffic"]
    r = dict(cfg.get("rehearse", {}))
    cfg = _merge(_merge(cfg, r.pop("published", {})), r)
    return dict(cell, config=cfg, traffic=_merge(traffic, traffic.get("rehearse", {})))


def _digest(trace, path):
    import re

    from benchmarks.lib import trace as trace_lib

    """What a reader needs to see of a trace before writing a reduction
    against it: every plane and line, and the commonest event names with one
    event's stats each."""
    out = []
    for plane in trace["planes"]:
        for line in plane["lines"]:
            names = {}
            for name, _, dur, stats in line["events"]:
                rec = names.setdefault(name, [0, 0.0, stats])
                rec[0] += 1
                rec[1] += dur
            top = sorted(names.items(), key=lambda kv: -kv[1][1])[:25]
            kinds = {}  # every opcode, and every custom call by its own name: kernels are short and many
            for n, (c, d, _) in names.items():
                own, opcode, _typ = trace_lib.parse_op(n)
                key = re.sub(r"[.][0-9]+$", "", own) if opcode == "custom-call" else opcode or "(not an HLO line)"
                rec = kinds.setdefault(("custom-call " if opcode == "custom-call" else "") + key, [0, 0.0, n[:400]])
                rec[0] += c
                rec[1] += d / 1e9
            out.append({"plane": plane["name"], "line": line["name"], "events": len(line["events"]),
                        "top": [[n[:300], c, d / 1e9, st] for n, (c, d, st) in top],
                        "by_opcode": sorted(([k] + v for k, v in kinds.items()), key=lambda r: -r[2])[:60]})
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(path, "w") as f:
        json.dump(out, f, indent=1, default=str)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--config")
    ap.add_argument("--traffic")
    ap.add_argument("--chips", type=int, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--digest", help="with --trace 1: write a digest of the whole trace to this file")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "deepspeed_tpu")):
        print("benchmarks/run.py: the program (deepspeed_tpu/) is not in this checkout", file=sys.stderr)
        return 4

    from benchmarks.lib import manifest as mf

    manifest = mf.load_manifest(ROOT)
    if args.workload:
        cell = mf.cell(manifest, args.workload, ROOT)
    elif args.config and args.traffic and args.chips:
        cell = mf.compose(manifest, args.config, args.traffic, args.chips, ROOT)
    else:
        ap.error("give --workload, or --config, --traffic and --chips")
    seconds = float(args.seconds if args.seconds is not None else manifest["run_seconds"])
    if args.rehearse:
        cell = _rehearsal(cell)
        os.environ["JAX_PLATFORMS"] = "cpu"
        if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={max(cell['chips'], 1)} "
                                       + os.environ.get("XLA_FLAGS", ""))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    for knob, value in cell["config"].get("env", {}).items():
        os.environ[knob] = str(value)  # before the engine is built: it reads its knobs then

    import jax

    devices = jax.devices()
    if not args.rehearse:
        if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
            print(f"benchmarks/run.py: {cell['name']} needs {cell['chips']} TPU chip(s); JAX found "
                  f"{len(devices)} x {devices[0].platform}. There is no CPU branch: see --rehearse.", file=sys.stderr)
            return 3
        from deepspeed_tpu.utils.compile_cache import enable_compilation_cache

        enable_compilation_cache(jax)  # $JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache_tpu

    from benchmarks.lib import runtime, trace as trace_lib

    say = runtime.progress(T0)
    say(f"{len(devices)} x {devices[0].device_kind}; compile cache {jax.config.jax_compilation_cache_dir}")
    trace_dir = os.path.join(ROOT, ".bench_out", f"trace-{cell['name']}")
    shutil.rmtree(trace_dir, ignore_errors=True)
    tracer = runtime.Tracer(bool(args.trace) and not args.rehearse, trace_dir)
    opts = {"seed": args.seed, "seconds": seconds, "rehearse": args.rehearse, "t0": T0, "tracer": tracer,
            "compiles": runtime.CompileCounter(), "say": say, "cache_counts": runtime.cache_counts}
    driver = mf.load_module(os.path.join(ROOT, "benchmarks", "drivers", f"{cell['config']['kind']}.py"))
    record = driver.run(cell, opts)

    record["device"] = runtime.device_info(cell["chips"])
    record["published"] = mf.published(cell["config"])
    result = {"correct": record["correct"], "attempted": record["attempted"], "failed": record["failed"],
              "metrics": {}, "device": record["device"]}
    tracer.stop()
    if tracer.traced:
        opts["say"]("trace stopped")
        xplane = trace_lib.find_xplane(trace_dir)
        if xplane is None:
            raise RuntimeError(f"the profiler left no .xplane.pb under {trace_dir}")
        if args.digest:
            _digest(trace_lib.load_xplane(xplane), args.digest)
        reduced = trace_lib.reduce_trace(trace_lib.load_xplane(xplane, trace_lib.keep_for_metrics))
        shutil.rmtree(trace_dir, ignore_errors=True)
        record["reduced"] = reduced
        result["device"] = dict(record["device"], busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        result["breakdown"] = trace_lib.breakdown(reduced)
        record["extras"]["idle_share"] = reduced.get("idle_share")
        opts["say"]("trace reduced")
    if not args.rehearse:
        if args.trace:
            for m in mf.metrics_of(manifest, cell["name"], "per_layer"):
                value = mf.metric_module(m["name"], ROOT).read(record)
                if value is not None:
                    result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
        else:
            units = {m["name"]: m["unit"] for m in manifest["end_to_end"]}
            wanted = [m["name"] for m in mf.metrics_of(manifest, cell["name"], "end_to_end")] if args.workload \
                else list(record["end_to_end"])
            for name in wanted:
                if record["end_to_end"].get(name) is not None:
                    result["metrics"][name] = {"value": record["end_to_end"][name], "unit": units.get(name, "")}
    extras = dict(record.get("extras", {}), workload=cell["name"], seed=args.seed, seconds=seconds,
                  compiles_in_window=record.get("compiles_in_window"), rehearsal=args.rehearse)
    if args.rehearse or not args.workload:
        extras["end_to_end_unreported"] = None if args.rehearse else record["end_to_end"]
        extras["counters"] = record.get("counters")
    sys.stdout.flush()
    print(json.dumps({"extras": extras}, default=str), flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
