"""What the program recorded about itself, for readers of ``program_span`` and
``program_counter`` metrics: its span ring, its event log and its counters, read
in process after the run (``snapshot``), or handed over in ``record["program"]``
in the same form (a test's hand-made spans, a rehearsal's record).

A program that lacks a span's ``id`` or a counter (the parent of the PR that
added them) gives None there, and every reader then returns None.
"""

from typing import Dict, List, Optional

PHASES = ("trace", "lower", "compile", "cache_fetch")
PHASE_COUNTERS = tuple(f"program_{phase}_seconds_total" for phase in PHASES)
COUNTERS = PHASE_COUNTERS + ("program_first_calls_total", "telemetry_spans_dropped_total")


def snapshot() -> Optional[Dict]:
    try:
        from deepspeed_tpu.telemetry import get_event_log, get_registry, get_tracer
    except ImportError:
        return None
    spans = get_tracer().spans()
    if spans and "id" not in spans[0]:
        spans = None  # no parent ids: nobody's self time can be told
    reg = get_registry()
    return {"spans": spans, "events": get_event_log().events(), "counters": {c: reg.peek(c) for c in COUNTERS}}


def of(record: Dict) -> Optional[Dict]:
    return record.get("program") or snapshot()


def self_times(spans: List[Dict]) -> Dict[int, float]:
    """Span id -> its duration less its direct children's."""
    out = {s["id"]: s["dur_s"] for s in spans}
    for s in spans:
        if s["parent"] in out:
            out[s["parent"]] -= s["dur_s"]
    return out


def last(prog: Optional[Dict], name: str, n: int) -> Optional[List[Dict]]:
    """The last ``n`` spans called ``name``: the window's, since the window is
    the last thing a run does. None, never fewer, where the ring no longer
    holds them all or may have dropped other spans of that stretch (the ring
    drops its oldest first, so a stretch is whole while something older than
    it is still there, or nothing was ever dropped)."""
    spans = (prog or {}).get("spans")
    if not spans or n <= 0:
        return None
    found = [s for s in spans if s["name"] == name][-n:]
    dropped = prog["counters"].get("telemetry_spans_dropped_total") or 0
    if len(found) < n or (dropped and spans[0]["start_s"] >= found[0]["start_s"]):
        return None
    return found


def window_start(record: Dict, prog: Optional[Dict]) -> Optional[float]:
    """The start of the window on the ring's clock: of its first training step,
    or of its first serving quantum."""
    if record.get("train"):
        steps = last(prog, "train/forward", int(record["train"]["steps"]))
    else:
        steps = last(prog, "infer/fused_step", len(record.get("quanta") or ()))
    return steps[0]["start_s"] if steps else None


def first_calls_in_window(record: Dict, prog: Optional[Dict]) -> Optional[List[Dict]]:
    """The attributes of the ``program/first_call`` spans that began inside the
    window (family, bucket, ``programs``, seconds by phase), or None where the
    window cannot be told on the ring."""
    start = window_start(record, prog)
    if start is None:
        return None
    return [s["attrs"] for s in prog["spans"] if s["name"] == "program/first_call" and s["start_s"] >= start]
