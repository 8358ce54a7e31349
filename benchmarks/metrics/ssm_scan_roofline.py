"""The selective-scan kernels' share of their roofline: the least time the chip could take for the Mamba-1 recurrences
of the steps in the traced stretch (``ssm_cost`` of the configuration's own FLOP module by ``lib/flops.py::
roofline_seconds``, forward and backward, times its ``ssm_layers(published)``) over the device time of the kernels
``ops/pallas/ssm.py`` names ``ssm_scan_fwd`` and ``ssm_scan_bwd``. ``lib/peaks.py`` lists no rate for the vector unit,
which is what the scan's multiply-adds run on, so the FLOP side of the roofline is the MXU's peak and never binds: the
bound is the operands' bytes over HBM's peak, loose for this kernel (PERF.md section 3 gives the vector count beside it)
and never over 100%. None where the trace holds no such kernel (a program without the layer, or one that lacks the
kernel) or the configuration names no ``ssm_cost``."""

from benchmarks.lib import flops, kernel_time
from benchmarks.lib.peaks import peaks_for

UNIT, BETTER, SOURCE = "%", "higher", "device_trace"
LAYER = "kernels (ops/pallas/ssm.py)"
MOVES = "train_tokens_per_s"
KERNELS = r"^(?=.*custom-call)(?=.*ssm_scan_(fwd|bwd))"


def read(record):
    counts = flops.for_config(record.get("config"))
    cost, layers = getattr(counts, "ssm_cost", None), getattr(counts, "ssm_layers", None)
    steps, took = kernel_time.steps_and_seconds(record.get("reduced"), KERNELS)
    if cost is None or layers is None or not took:
        return None
    m, t = record["published"], record["train"]
    peaks = peaks_for(record["device"]["kind"])
    need = sum(flops.roofline_seconds(cost(m, t["micro_batch"] * t["seq_len"], backward=b), peaks)["seconds"] for b in (False, True))
    return 100.0 * steps * layers(m) * need / took
