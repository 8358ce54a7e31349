"""The flash-attention kernels' share of their roofline: the least time the
chip could take for the attention of the steps in the traced stretch (from
shapes: the larger of FLOPs over peak and bytes over peak, causal; compute
bounds it at S=2048, D=128) over the device time those kernels took.

The kernels are found as the trace names them on a v5e (my chip run, PR 24):
Mosaic custom calls (``custom_call_target="tpu_custom_call"``) whose result is
shaped ``[micro-batch x heads, sequence, head size]``; their HLO names are
``shard_map.N`` (the wrapper the kernel sits in), not the kernel functions', and
one layer's forward and backward are three calls (forward, dq, dk+dv). So the
work is counted from what ran, not from the calls: executions of the step
program in the stretch, times layers, times one forward and one backward.
"""

import re

from benchmarks.lib.flops import flash_attention_cost, roofline_seconds
from benchmarks.lib.peaks import peaks_for

UNIT, BETTER, SOURCE = "%", "higher", "device_trace"
LAYER = "kernels (ops/pallas/flash_attention.py)"
MOVES = "train_tokens_per_s"
STEP_PROGRAM = re.compile(r"^jit_fused_step\(")


def read(record):
    reduced = record.get("reduced")
    if not reduced or not reduced.get("devices"):
        return None
    m, t = record["published"], record["train"]
    peaks = peaks_for(record["device"]["kind"])
    heads = m["num_attention_heads"]
    head_dim = m["hidden_size"] // heads
    shape = (t["micro_batch"], t["seq_len"], heads, m.get("num_key_value_heads") or heads, head_dim)
    per_layer_step = sum(roofline_seconds(flash_attention_cost(*shape, backward=b), peaks)["seconds"] for b in (False, True))
    operand = f"[{t['micro_batch'] * heads},{t['seq_len']},{head_dim}]"
    took = need = 0.0
    for dev in reduced["devices"].values():
        secs = sum(s for label, s in dev["ops"].items()
                   if " custom-call " in label and "tpu_custom_call" in label and operand in label)
        steps = sum((min(e, reduced["window_s"]) - max(s, 0.0)) / (e - s)
                    for name, s, e in dev["modules"] if STEP_PROGRAM.match(name) and e > s)
        if secs and steps:
            took += secs
            need += steps * m["num_hidden_layers"] * per_layer_step
    return 100.0 * need / took if took else None
