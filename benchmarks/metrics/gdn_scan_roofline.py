"""The Gated DeltaNet scan kernels' share of their roofline: the least time the
chip could take for the delta-rule recurrences, one decay a head, of the steps
in the traced stretch (``gdn_cost`` of the configuration's own FLOP module at
the chip's peaks: the larger of FLOPs over peak and bytes over peak, forward
and backward, times its ``gdn_layers(published)``) over the device time of the
kernels ``ops/pallas/kda.py`` names ``gdn_scan_fwd`` and ``gdn_scan_bwd``.
None where the trace holds no such kernel (a program without the layer, or
one that lacks the per-head form) or the configuration names no ``gdn_cost``."""

from benchmarks.lib import flops, kernel_time
from benchmarks.lib.peaks import peaks_for

UNIT, BETTER, SOURCE = "%", "higher", "device_trace"
LAYER = "kernels (ops/pallas/kda.py)"
MOVES = "train_tokens_per_s"
KERNELS = r"^(?=.*custom-call)(?=.*gdn_scan_(fwd|bwd))"


def read(record):
    counts = flops.for_config(record.get("config"))
    cost, layers = getattr(counts, "gdn_cost", None), getattr(counts, "gdn_layers", None)
    steps, took = kernel_time.steps_and_seconds(record.get("reduced"), KERNELS)
    if cost is None or layers is None or not took:
        return None
    m, t = record["published"], record["train"]
    peaks = peaks_for(record["device"]["kind"])
    need = sum(flops.roofline_seconds(cost(m, t["micro_batch"] * t["seq_len"], backward=b), peaks)["seconds"] for b in (False, True))
    return 100.0 * steps * layers(m) * need / took
