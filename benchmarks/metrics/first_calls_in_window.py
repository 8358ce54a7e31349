"""Programs that had their first call inside the window, by the program's own
``program/first_call`` spans (each counts what reached the backend inside it):
0 unless timing brought a shape that the warm-up missed. Each costs arrivals
seconds. ``extras["first_calls_in_window"]`` lists each with its family, bucket
and seconds by phase, and how many more the driver's own listener counted
outside the program's caches (helper programs no cache wraps)."""

from benchmarks.lib import program

UNIT, BETTER, SOURCE = "count", "lower", "program_span"
LAYER = "program cache (inference/v2/engine_v2.py _fused_for)"
MOVES = "ttft_p95_ms"


def read(record):
    calls = program.first_calls_in_window(record, program.of(record))
    if calls is None:
        return None
    n = sum(a.get("programs", 0) for a in calls)
    record.setdefault("extras", {})["first_calls_in_window"] = {
        "calls": calls, "seconds": sum(a.get("total_s", 0.0) for a in calls),
        "outside_program_caches": (record.get("compiles_in_window") or 0) - n}
    return n
