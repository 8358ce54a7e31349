"""The share of the window that stalled steps took beyond their usual period:
100 x the window's rise of the trainer's ``train_step_stall_seconds_total``
(a step's period on the host less the median of the 32 before it, for every
step over 1.5 medians: ``deepspeed_tpu/telemetry/health.py::StepStallDetector``)
over the window's seconds. 0.0 in a clean window and 2-7 in one that held the
8k cells' stall: the number that says of a ledger line "this side held a
stall". ``extras["stalls"]`` is the window's rise of ``train_step_stalls_total``.
None where the program has no such counter."""

from benchmarks.lib import program

UNIT, BETTER, SOURCE = "%", "lower", "program_counter"
LAYER = "trainer step loop (runtime/engine.py)"
MOVES = "train_tokens_per_s"


def read(record):
    seconds = program.counter(record, "train_step_stall_seconds_total")
    if seconds is None or not record.get("elapsed_s"):
        return None
    record.setdefault("extras", {})["stalls"] = program.counter(record, "train_step_stalls_total")
    return 100.0 * seconds / record["elapsed_s"]
