"""Seconds of set-up on the Python and MLIR side of the step's first calls:
family ``train``'s ``program_first_call_seconds_total`` at the window's start
over every phase but ``compile`` (``trace``, ``lower``, ``other`` and
``flops_count``, the one Python trace of the model, which ``jax.jit`` keeps for
the call). It costs the same with the step in the persistent cache or not. The
phases apart go to ``extras["setup_timeline_s"]["step_first_calls"]``. The rule
(a counter at the window's start is its total less ``record["counters"]``'s
rise): ``lib/setup_timeline.py``. None where the program has no such counters."""

from benchmarks.lib import setup_timeline

UNIT, BETTER, SOURCE = "s", "lower", "program_counter"
LAYER = "program caches and jax.jit (first calls)"
MOVES = "setup_s"


def read(record):
    parts = setup_timeline.timeline(record)
    return None if parts is None else parts["step_trace"]
