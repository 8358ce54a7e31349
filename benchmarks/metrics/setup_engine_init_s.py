"""Seconds of set-up inside the trainer's construction: the sum of
``engine_init_seconds_total`` over its parts at the window's start (``mesh``,
``shard_state``, ``optimizer`` and ``rest`` are the ``init/engine`` span's
wall seconds; ``after`` the first calls of family ``init`` that lie outside
it: the first compute copy's cast, the overflow count's two programs). The
parts, and every other part of ``setup_s``, go to ``extras["setup_timeline_s"]``. How a reader of set-up
reaches the program, and the rule it goes by (a counter at the window's start
is its total less ``record["counters"]``'s rise): ``lib/setup_timeline.py``.
None where the program has no such counters."""

from benchmarks.lib import setup_timeline

UNIT, BETTER, SOURCE = "s", "lower", "program_counter"
LAYER = "trainer construction (runtime/engine.py)"
MOVES = "setup_s"


def read(record):
    parts = setup_timeline.timeline(record)
    return None if parts is None else parts["engine_init"]
