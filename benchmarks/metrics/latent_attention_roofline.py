"""Latent attention's kernels' share of their roofline, for any number of
such layers: the least time for causal attention with keys of
``qk_nope_head_dim + qk_rope_head_dim`` and values of ``v_head_dim``
(``mla_attention_cost`` of the configuration's own FLOP module, forward and
backward, times its ``mla_layers(published)``, of the steps in the traced
stretch) over the device time of the Mosaic calls that carry an operand or a
result of the key head size, which no other kernel of the step has: the
forward's result is ``[heads, S, v]`` beside its ``[heads, S / block, 1,
block]`` row statistics, the backward's are dq and dk of ``[heads, S, qk]``
(matched as ``mla_attention_roofline.py`` matches them; that reader counts
its layers from a key only its own configuration has). Rotating the shared
key part is XLA's work ahead of the call and not in this time. None where the
configuration names no such cost or layer count, or nothing matches."""

from benchmarks.lib import flops, kernel_time
from benchmarks.lib.peaks import peaks_for

UNIT, BETTER, SOURCE = "%", "higher", "device_trace"
LAYER = "kernels (ops/pallas/flash_attention.py)"
MOVES = "train_tokens_per_s"


def read(record):
    counts = flops.for_config(record.get("config"))
    cost, layers = getattr(counts, "mla_attention_cost", None), getattr(counts, "mla_layers", None)
    if cost is None or layers is None or not record.get("reduced"):
        return None
    m, t = record["published"], record["train"]
    rows, seq = t["micro_batch"] * m["num_attention_heads"], t["seq_len"]
    qk, v = m["qk_nope_head_dim"] + m["qk_rope_head_dim"], m["v_head_dim"]
    forward = rf"\(bf16\[{rows},{seq},{v}\][^ ]*, f32\[{rows},[0-9]+,1,[0-9]+\]"  # o and the row statistics
    backward = rf"bf16\[{rows},{seq},{qk}\]"                                       # dq, dk
    steps, took = kernel_time.steps_and_seconds(record["reduced"], rf"custom-call .*({forward}|{backward}).*tpu_custom_call")
    if not took:
        return None
    peaks = peaks_for(record["device"]["kind"])
    need = sum(flops.roofline_seconds(cost(m, t["micro_batch"], seq, backward=b), peaks)["seconds"] for b in (False, True))
    return 100.0 * steps * layers(m) * need / took
