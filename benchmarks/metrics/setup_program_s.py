"""Seconds of set-up that JAX spent on programs' first calls: tracing, lowering
to MLIR and backend compile (which includes fetching from the persistent cache)
of every program called before the window's start, from the program's own
process-wide counters (``deepspeed_tpu/utils/compile_cache.py``). The split by
phase goes to ``extras["setup_program_split_s"]``.

For writers of ``program_span`` and ``program_counter`` readers: the profiler's
xplane is gone by the time ``read(record)`` runs, but the process is the one
that ran the cell, so ``benchmarks/lib/program.py::of(record)`` reaches the
program's span ring (``deepspeed_tpu.telemetry.get_tracer().spans()``: name,
start_s, dur_s, id, parent, attrs), its request event log
(``get_event_log().events()``) and its counters (``get_registry().peek(name)``)
in process. The counters are totals since the process started: what fell inside
the window is taken off by the ``program/first_call`` spans that began in it, and
where the driver counted more compilations in the window than those spans
account for, there is no number.
"""

from benchmarks.lib import program

UNIT, BETTER, SOURCE = "s", "lower", "program_counter"
LAYER = "program caches and jax.jit (first calls)"
MOVES = "setup_s"


def read(record):
    if (record.get("end_to_end") or {}).get("setup_s") is None:
        return None
    prog = program.of(record)
    totals = [(prog or {}).get("counters", {}).get(c) for c in program.PHASE_COUNTERS]
    if any(v is None for v in totals):
        return None
    calls = program.first_calls_in_window(record, prog) or []
    if (record.get("compiles_in_window") or 0) > sum(a.get("programs", 0) for a in calls):
        return None  # something compiled inside the window that no span of the program accounts for
    split = {p: t - sum(a.get(p + "_s", 0.0) for a in calls) for p, t in zip(program.PHASES, totals)}
    record.setdefault("extras", {})["setup_program_split_s"] = split
    return split["trace"] + split["lower"] + split["compile"]  # compile includes cache_fetch
