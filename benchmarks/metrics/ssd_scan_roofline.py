"""The Mamba-2 (SSD) scan kernels' share of their roofline: the least time the chip could take for the chunked scans of
the steps in the traced stretch (``ssd_cost`` of the configuration's own FLOP module by ``lib/flops.py::
roofline_seconds``, forward and backward, times its ``ssd_layers(published)``) over the device time of the kernels
``ops/pallas/ssd.py`` names ``ssd_scan_fwd`` and ``ssd_scan_bwd``. The scan's products run on the MXU and are counted;
what it moves (x, B, C and y once in bf16, every chunk's float32 state once) binds first at the published sizes, so the
share is of the chip's bandwidth. None where the trace holds no such kernel (a program without the layer, or one that lacks
the kernel) or the configuration names no ``ssd_cost``."""

from benchmarks.lib import flops, kernel_time
from benchmarks.lib.peaks import peaks_for

UNIT, BETTER, SOURCE = "%", "higher", "device_trace"
LAYER = "kernels (ops/pallas/ssd.py)"
MOVES = "train_tokens_per_s"
KERNELS = r"^(?=.*custom-call)(?=.*ssd_scan_(fwd|bwd))"


def read(record):
    counts = flops.for_config(record.get("config"))
    cost, layers = getattr(counts, "ssd_cost", None), getattr(counts, "ssd_layers", None)
    steps, took = kernel_time.steps_and_seconds(record.get("reduced"), KERNELS)
    if cost is None or layers is None or not took:
        return None
    m, t = record["published"], record["train"]
    peaks = peaks_for(record["device"]["kind"])
    need = sum(flops.roofline_seconds(cost(m, t["micro_batch"], t["seq_len"], backward=b), peaks)["seconds"] for b in (False, True))
    return 100.0 * steps * layers(m) * need / took
