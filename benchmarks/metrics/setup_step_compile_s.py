"""Seconds of set-up in the backend for the step's first calls: family
``train``'s ``compile`` phase of ``program_first_call_seconds_total`` at the
window's start. JAX's compile event spans the persistent cache's fetch, so a
warm run reads its fetches here and a cold run its compiles; the fetch alone is
``cache_fetch`` in ``extras["setup_timeline_s"]["step_first_calls"]``. The rule
(a counter at the window's start is its total less ``record["counters"]``'s
rise): ``lib/setup_timeline.py``. None where the program has no such counters."""

from benchmarks.lib import setup_timeline

UNIT, BETTER, SOURCE = "s", "lower", "program_counter"
LAYER = "program caches and jax.jit (first calls)"
MOVES = "setup_s"


def read(record):
    parts = setup_timeline.timeline(record)
    return None if parts is None else parts["step_compile"]
