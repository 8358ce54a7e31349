"""Device-busy time of one decode step: for every pure-decode quantum in the
traced stretch, the device's busy time inside the driver's ``bench/run_fused``
span over the quantum's steps; the median over quanta."""

from statistics import median

from benchmarks.lib.trace import busy_inside

UNIT, BETTER, SOURCE = "ms", "lower", "device_trace"
LAYER = "model step (inference/v2/model_runner.py fused program)"
MOVES = "tpot_p95_ms"


def read(record):
    reduced = record.get("reduced")
    if not reduced:
        return None
    per_step = []
    for name, lo, hi, stats in reduced["spans"]:
        what = str(stats.get("what", ""))
        if name == "bench/run_fused" and what.startswith("decode "):
            steps = int(what.rsplit("steps", 1)[1])
            per_step.append(busy_inside(reduced, lo, hi) / steps * 1e3)
    return median(per_step) if per_step else None
