"""Output tokens committed per dispatched program: all tokens of the run over
the delta of the registry's ``infer_dispatches_total``."""

UNIT, BETTER, SOURCE = "tokens", "higher", "program_counter"
LAYER = "engine step (inference/v2/engine_v2.py _run_fused)"
MOVES = "serve_tokens_per_s"


def read(record):
    n = (record.get("counters") or {}).get("infer_dispatches_total")
    return record["summary"]["tokens_total"] / n if n else None
