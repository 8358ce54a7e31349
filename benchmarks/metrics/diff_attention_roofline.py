"""Differential attention's kernels' share of their roofline: the least time for the two causal maps of every such
layer (``diff_attention_cost`` of the configuration's own FLOP module, forward and backward, a layer of each kind its
``kinds(published)`` lists: a window layer by the band its mask keeps, a full or cross layer by half the square), of the
steps in the traced stretch, over the device time of the flash kernels (``flash_fwd``, ``flash_bwd``, ``flash_dq``,
``flash_dkv``: in a model with such layers every attention call is one of theirs, keys of ``head_dim`` beside values
twice as wide). The difference, its sub-norm and the lambdas are XLA's work after the calls and not in this time. None
where the configuration names no such cost, or the trace holds no flash kernel."""

from benchmarks.lib import flops, kernel_time
from benchmarks.lib.peaks import peaks_for

UNIT, BETTER, SOURCE = "%", "higher", "device_trace"
LAYER = "kernels (ops/pallas/flash_attention.py)"
MOVES = "train_tokens_per_s"
KERNELS = r"^(?=.*custom-call)(?=.*flash_(fwd|bwd|dq|dkv))"


def read(record):
    counts = flops.for_config(record.get("config"))
    cost, kinds = getattr(counts, "diff_attention_cost", None), getattr(counts, "kinds", None)
    steps, took = kernel_time.steps_and_seconds(record.get("reduced"), KERNELS)
    if cost is None or kinds is None or not took:
        return None
    m, t = record["published"], record["train"]
    peaks = peaks_for(record["device"]["kind"])
    need = sum(flops.roofline_seconds(cost(m, t["micro_batch"], t["seq_len"], kind, backward=b), peaks)["seconds"]
               for kind in kinds(m) if kind.startswith("diff") for b in (False, True))
    return 100.0 * steps * need / took
