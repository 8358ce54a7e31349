"""Block-diffusion attention's kernels' share of their roofline: the least time for softmax attention over the pairs
the block mask KEEPS alone, forward and backward (``blockdiff_attention_cost`` of the configuration's own FLOP module,
times its ``blockdiff_layers(published)``, of the steps in the traced stretch), over the device time of the attention
calls (``blockdiff_fwd`` / ``blockdiff_bwd``, the Pallas calls ``ops/pallas/flash_attention.py`` makes under that mask
on a TPU). A walk that visits tiles outside the mask, or masks more tiles than cross an edge, reads lower by the same
count; copies of the KV heads made around a backward that runs a head at a time are XLA's work and not in this time.
None where the configuration names no such cost (every older one), or the trace holds no such kernel (a program
without the mask: the parent's)."""

from benchmarks.lib import flops, kernel_time
from benchmarks.lib.peaks import peaks_for

UNIT, BETTER, SOURCE = "%", "higher", "device_trace"
LAYER = "kernels (ops/pallas/flash_attention.py)"
MOVES = "train_tokens_per_s"
KERNELS = r"^(?=.*custom-call)(?=.*\bblockdiff_(fwd|bwd)\b)"


def read(record):
    counts = flops.for_config(record.get("config"))
    cost, layers = getattr(counts, "blockdiff_attention_cost", None), getattr(counts, "blockdiff_layers", None)
    steps, took = kernel_time.steps_and_seconds(record.get("reduced"), KERNELS)
    if cost is None or layers is None or not took:
        return None
    m, t = record["published"], record["train"]
    peaks = peaks_for(record["device"]["kind"])
    need = sum(flops.roofline_seconds(cost(m, t["micro_batch"], t["seq_len"], backward=b), peaks)["seconds"] for b in (False, True))
    return 100.0 * steps * layers(m) * need / took
