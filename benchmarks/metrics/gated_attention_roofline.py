"""Grouped-query attention's kernels' share of their roofline where the model
has such layers among others: the least time for causal attention at
``num_attention_heads`` query heads on ``num_key_value_heads`` key-value heads
of ``head_dim`` (``lib/flops.py::flash_attention_cost``, forward and backward,
times the configuration's ``full_layers(published)``, of the steps in the
traced stretch) over the device time of the Mosaic calls with a ``[heads, S,
head_dim]`` result, which no other kernel of such a step has: the forward's
output and the backward's dq (matched by shape, as
``latent_attention_roofline.py`` matches its own; the flash calls carry no
layer's name). The output gate, the q/k norms and the rotation are XLA's work
around the calls and not in this time. None where the configuration names no
such layer count, or nothing matches."""

from benchmarks.lib import flops, kernel_time
from benchmarks.lib.peaks import peaks_for

UNIT, BETTER, SOURCE = "%", "higher", "device_trace"
LAYER = "kernels (ops/pallas/flash_attention.py)"
MOVES = "train_tokens_per_s"


def read(record):
    layers = getattr(flops.for_config(record.get("config")), "full_layers", None)
    if layers is None or not record.get("reduced"):
        return None
    m, t = record["published"], record["train"]
    heads, kv, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    result = rf"bf16\[{t['micro_batch'] * heads},{t['seq_len']},{hd}\]"  # o; dq
    steps, took = kernel_time.steps_and_seconds(record["reduced"], rf"custom-call .*{result}.*tpu_custom_call")
    if not took:
        return None
    peaks = peaks_for(record["device"]["kind"])
    need = sum(flops.roofline_seconds(flops.flash_attention_cost(t["micro_batch"], t["seq_len"], heads, kv, hd, backward=b), peaks)["seconds"]
               for b in (False, True))
    return 100.0 * steps * layers(m) * need / took
