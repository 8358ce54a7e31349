"""What the kernel rooflines of a training cell share: how many executions of
the step program the traced stretch holds, and the device seconds of the
operations whose label matches a pattern (``lib/trace.py::op_label``)."""

import re

STEP_PROGRAM = re.compile(r"^jit_fused_step\(")


def steps_and_seconds(reduced, pattern: str):
    """(executions of the step program inside the window, device seconds of
    the matching operations), summed over the devices that have both; (0, 0)
    where nothing matches."""
    rx = re.compile(pattern)
    steps = took = 0.0
    for dev in (reduced or {}).get("devices", {}).values():
        secs = sum(s for label, s in dev["ops"].items() if rx.search(label))
        n = sum((min(e, reduced["window_s"]) - max(s, 0.0)) / (e - s)
                for name, s, e in dev["modules"] if STEP_PROGRAM.match(name) and e > s)
        if secs and n:
            steps, took = steps + n, took + secs
    return steps, took
