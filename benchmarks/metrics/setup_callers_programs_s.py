"""Seconds of set-up in the backend for programs that lie in NO first-call span
of the program's: ``program_trace|lower|compile_seconds_total`` of the whole
process at the window's start, less the same three phases of every
``program_first_call_seconds_total{family, phase}``. What is left is what the
CALLER sent to the backend before the window: ``model.init`` (the weights, one
jitted call from the seed: ``lib/weights.py``) and the plain reference
(``lib/reference.py``, which a training cell runs through its first steps in
set-up). It is the part of ``setup_s`` that moved by 9 s between two sides of
one tree while the three readers beside it moved by 2 (ledger, PR 69, Kimi-Linear).
With four readers on a line, ``extras["setup_timeline_s"]["harness"]`` is
``setup_s`` less their sum and the import. The rule (a counter at the window's start
is its total less ``record["counters"]``'s rise): ``lib/setup_timeline.py``.
None where the program has no such counters."""

from benchmarks.lib import setup_timeline

UNIT, BETTER, SOURCE = "s", "lower", "program_counter"
LAYER = "the caller's programs (model.init, the plain reference: benchmarks/lib/weights.py, lib/reference.py)"
MOVES = "setup_s"


def read(record):
    parts = setup_timeline.timeline(record)
    return None if parts is None else parts["callers_programs"]
