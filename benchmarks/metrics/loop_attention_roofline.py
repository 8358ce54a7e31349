"""The flash-attention kernels' share of their roofline in a LOOPED stack, where a layer's attention runs once a pass on
the same weights: the least time for ``applications(published)`` causal attention calls a step, forward and backward
(``attention_cost`` of the configuration's own FLOP module by ``lib/flops.py::roofline_seconds``), of the steps in the
traced stretch, over the device time of every flash call of the step (``flash_fwd``, ``flash_bwd``, ``flash_dq``,
``flash_dkv``: whatever form the backward takes, and a checkpointed block's second forward, which is the program's time
and not required work).

The applications are counted, not assumed: a traced step's BACKWARD calls (``flash_bwd``, else ``flash_dkv``: one an
application, and a checkpointed block does not repeat them as it does the forward's) must be ``applications(published)``,
and the program's own counter ``train_loop_block_applications_total`` must have risen by that many a step of the window
(a whole multiple of it, within the three steps whose counts may still be on their way from the device), so a pass that
did not run cannot read as a faster kernel. None where either disagrees, where the configuration names no such cost
(every older one), where the program has no such counter (the parent of the PR that added it) or the trace no flash kernel."""

from benchmarks.lib import flops, kernel_time, program, trace
from benchmarks.lib.peaks import peaks_for

UNIT, BETTER, SOURCE = "%", "higher", "device_trace"
LAYER = "kernels (ops/pallas/flash_attention.py)"
MOVES = "train_tokens_per_s"
KERNELS = r"^(?=.*custom-call)(?=.*flash_(fwd|bwd|dq|dkv))"
BACKWARDS = (r"^(?=.*custom-call)(?=.*flash_bwd)", r"^(?=.*custom-call)(?=.*flash_dkv)")
LAG_STEPS = 3  # a step's device counts reach the registry a dispatch or two after it ended (runtime/engine.py::_take_reported)


def read(record):
    counts = flops.for_config(record.get("config"))
    cost, applications = getattr(counts, "attention_cost", None), getattr(counts, "applications", None)
    reduced = record.get("reduced")
    steps, took = kernel_time.steps_and_seconds(reduced, KERNELS)
    counted = program.counter(record, "train_loop_block_applications_total")
    if cost is None or applications is None or not took or not counted or not record["train"].get("steps"):
        return None
    m, t = record["published"], record["train"]
    expected = applications(m)
    calls = next((n for n in (trace.ops_matching(reduced, rx)[1] for rx in BACKWARDS) if n), 0)
    if abs(calls / steps - expected) > 0.5:  # the traced steps ran another number of applications a step
        return None
    if counted % expected or abs(counted / expected - t["steps"]) > LAG_STEPS:  # the window's steps did, by the program's count
        return None
    peaks = peaks_for(record["device"]["kind"])
    need = sum(flops.roofline_seconds(cost(m, t["micro_batch"], t["seq_len"], backward=b), peaks)["seconds"] for b in (False, True))
    return 100.0 * steps * expected * need / took
