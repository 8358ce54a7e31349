"""Model FLOP/s utilization: required forward+backward FLOPs per token (from
the published shapes; recomputation not counted) times the run's tokens per
second, over chips times the chip's bf16 peak."""

from benchmarks.lib.flops import train_flops_per_token
from benchmarks.lib.peaks import peaks_for

UNIT, BETTER, SOURCE = "%", "higher", "host_clock"
LAYER = "model step (models/transformer.py under runtime/engine.py)"
MOVES = "train_tokens_per_s"


def read(record):
    rate = record["end_to_end"].get("train_tokens_per_s")
    if not rate:
        return None
    peak = peaks_for(record["device"]["kind"])["bf16_flops"] * record["device"]["count"]
    return 100.0 * train_flops_per_token(record["published"], record["train"]["seq_len"]) * rate / peak
