"""The attention kernels' share of their roofline in a stack that mixes full and window layers: the least time for the
pairs the two masks KEEP, forward and backward (``mixed_attention_cost`` of the configuration's own FLOP module, a layer
of each kind its ``kinds(published)`` lists: a window layer by its band, a full one by half the square), of the steps in
the traced stretch, over the device time of every attention call of the step (``flash_fwd``, ``flash_bwd``,
``flash_dq``, ``flash_dkv``: whatever form the backward takes, the same work is read). A walk that visits tiles outside
a band reads lower by the same count; copies of the KV heads made around a backward that runs a head at a time are
XLA's work and not in this time. None where the configuration names no such cost (every older one), or the trace
holds no flash kernel."""

from benchmarks.lib import flops, kernel_time
from benchmarks.lib.peaks import peaks_for

UNIT, BETTER, SOURCE = "%", "higher", "device_trace"
LAYER = "kernels (ops/pallas/flash_attention.py)"
MOVES = "train_tokens_per_s"
KERNELS = r"^(?=.*custom-call)(?=.*flash_(fwd|bwd|dq|dkv))"


def read(record):
    counts = flops.for_config(record.get("config"))
    cost, kinds = getattr(counts, "mixed_attention_cost", None), getattr(counts, "kinds", None)
    steps, took = kernel_time.steps_and_seconds(record.get("reduced"), KERNELS)
    if cost is None or kinds is None or not took:
        return None
    m, t = record["published"], record["train"]
    peaks = peaks_for(record["device"]["kind"])
    need = sum(flops.roofline_seconds(cost(m, t["micro_batch"], t["seq_len"], kind, backward=b), peaks)["seconds"]
               for kind in kinds(m) for b in (False, True))
    return 100.0 * steps * need / took
