"""The gated short convolution's share of its roofline: the least time for what a convolution layer moves BETWEEN its two
products, forward and backward (``short_conv_cost`` of the configuration's own FLOP module: ``[B, C, u]`` read and the
gated result written once, then ``[B, C, u]`` and a cotangent read and one written; a layer of each ``conv`` its
``kinds(published)`` lists), of the steps in the traced stretch, over the device time of the calls that do it
(``short_conv_fwd``, ``short_conv_bwd``: ``ops/pallas/short_conv.py``, one call each way a layer). Bound by memory: the
share is of the chip's bandwidth. A program that runs the operator as XLA's fusions has no such call and reads nothing
here. None where the configuration names no such cost (every older one), or the trace holds no such kernel."""

from benchmarks.lib import flops, kernel_time
from benchmarks.lib.peaks import peaks_for

UNIT, BETTER, SOURCE = "%", "higher", "device_trace"
LAYER = "kernels (ops/pallas/short_conv.py)"
MOVES = "train_tokens_per_s"
KERNELS = r"^(?=.*custom-call)(?=.*short_conv_(fwd|bwd))"


def read(record):
    counts = flops.for_config(record.get("config"))
    cost, kinds = getattr(counts, "short_conv_cost", None), getattr(counts, "kinds", None)
    steps, took = kernel_time.steps_and_seconds(record.get("reduced"), KERNELS)
    if cost is None or kinds is None or not took:
        return None
    m, t = record["published"], record["train"]
    peaks = peaks_for(record["device"]["kind"])
    layers = sum(mixer == "conv" for mixer, _ in kinds(m))
    need = sum(flops.roofline_seconds(cost(m, t["micro_batch"], t["seq_len"], backward=b), peaks)["seconds"] for b in (False, True))
    return 100.0 * steps * layers * need / took
