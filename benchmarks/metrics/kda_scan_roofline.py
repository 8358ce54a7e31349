"""The KDA scan kernels' share of their roofline: the least time the chip could
take for the gated delta-rule recurrences of the steps in the traced stretch
(``kda_cost`` of the configuration's own FLOP module at the chip's peaks: the
larger of FLOPs over peak and bytes over peak, forward and backward of every
KDA layer) over the device time of the kernels ``ops/pallas/kda.py`` names
``kda_scan_fwd`` and ``kda_scan_bwd``. None where the trace holds no such
kernel (a program without the layer) or the configuration names no ``kda_cost``."""

from benchmarks.lib import flops, kernel_time
from benchmarks.lib.peaks import peaks_for

UNIT, BETTER, SOURCE = "%", "higher", "device_trace"
LAYER = "kernels (ops/pallas/kda.py)"
MOVES = "train_tokens_per_s"
KERNELS = r"^(?=.*custom-call)(?=.*kda_scan_(fwd|bwd))"


def read(record):
    cost = getattr(flops.for_config(record.get("config")), "kda_cost", None)
    steps, took = kernel_time.steps_and_seconds(record.get("reduced"), KERNELS)
    if cost is None or not took:
        return None
    m, t = record["published"], record["train"]
    peaks = peaks_for(record["device"]["kind"])
    layers = len(m["linear_attn_config"]["kda_layers"])
    need = sum(flops.roofline_seconds(cost(m, t["micro_batch"] * t["seq_len"], backward=b), peaks)["seconds"] for b in (False, True))
    return 100.0 * steps * layers * need / took
