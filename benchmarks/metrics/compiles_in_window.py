"""Programs JAX compiled, or fetched from its persistent cache, between the
window's start and the end of the run: 0 unless timing brought a shape that
the warm-up replay missed. Each costs seconds of tracing that arrivals wait out."""

UNIT, BETTER, SOURCE = "count", "lower", "program_counter"
LAYER = "program cache (inference/v2/engine_v2.py _fused_for)"
MOVES = "ttft_p95_ms"


def read(record):
    return record.get("compiles_in_window")
