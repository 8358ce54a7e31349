"""Attention over the keys an indexer chose: its kernels' share of their
roofline. The least time for softmax attention over the CHOSEN (query, key)
pairs alone, forward and backward (``sparse_attention_cost`` of the
configuration's own FLOP module, times its ``sparse_layers(published)``, of
the steps in the traced stretch), over the device time of the attention calls
(``sparse_fwd`` / ``sparse_bwd``, the Pallas calls ``ops/indexed_attention.py``
makes on a TPU). The calls visit every causal pair and mask, so at the flash
kernels' efficiency this reads chosen / visible of theirs; a gathered kernel
would be read by the same count. The indexer's loss's own walk over the
pairs (``index_loss``, one call since PR 48; the ``sparse_probs`` pass it
replaced is gone) is in neither this nor ``index_select_roofline``. None where
the configuration names no such cost, or nothing matches."""

from benchmarks.lib import flops, kernel_time
from benchmarks.lib.peaks import peaks_for

UNIT, BETTER, SOURCE = "%", "higher", "device_trace"
LAYER = "kernels (ops/pallas/indexed_attention.py)"
MOVES = "train_tokens_per_s"
KERNELS = r"^(?=.*custom-call)(?=.*\bsparse_(fwd|bwd)\b)"


def read(record):
    counts = flops.for_config(record.get("config"))
    cost, layers = getattr(counts, "sparse_attention_cost", None), getattr(counts, "sparse_layers", None)
    steps, took = kernel_time.steps_and_seconds(record.get("reduced"), KERNELS)
    if cost is None or layers is None or not took:
        return None
    m, t = record["published"], record["train"]
    peaks = peaks_for(record["device"]["kind"])
    need = sum(flops.roofline_seconds(cost(m, t["micro_batch"], t["seq_len"], backward=b), peaks)["seconds"] for b in (False, True))
    return 100.0 * steps * layers(m) * need / took
