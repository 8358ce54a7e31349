"""What the host takes to hand a training step to the device: the seconds of
``train/forward``'s phases ``put_batch`` (the batch's transfer), ``dispatch``
(the call of the jitted step) and ``device_counts`` (the look at earlier steps'
counts), summed a step, the median over the window's steps, in milliseconds.
The three medians apart go to ``extras["host_dispatch_split_ms"]``. None where
the program's spans carry no ``phase_s``."""

from statistics import median

from benchmarks.lib import program

UNIT, BETTER, SOURCE = "ms", "lower", "program_span"
LAYER = "trainer step loop (runtime/engine.py)"
MOVES = "train_tokens_per_s"
PHASES = ("put_batch", "dispatch", "device_counts")


def read(record):
    n = int((record.get("train") or {}).get("steps") or 0)
    forwards = program.last(program.of(record) if n else None, "train/forward", n)
    phases = [(f.get("attrs") or {}).get("phase_s") for f in forwards or ()]
    if not phases or not all(phases):
        return None
    record.setdefault("extras", {})["host_dispatch_split_ms"] = {p: 1e3 * median(by.get(p, 0.0) for by in phases) for p in PHASES}
    return 1e3 * median(sum(by.get(p, 0.0) for p in PHASES) for by in phases)
