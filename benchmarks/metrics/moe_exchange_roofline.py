"""The routed rows' exchange's share of its roofline: the least time for the bytes a chip must SEND OFF THE CHIP in the
routed layers' exchanges of the traced steps (``exchange_cost`` of the configuration's own FLOP module: each row that
leaves its chip crosses four times, out and back in the forward and in the backward; a row that stays is no traffic) at
the chip's interchip rate (``lib/peaks.py``: ``ici_bits_per_s``), over the device time of the step's ``all-to-all``
operations (under ZeRO's partitioner the exchange's are the only ones). The rows are the program's counter
``moe_rows_sent_total``, which the layer sums over the HOST, a layer and a step: divided by the window's steps and by the
chips before it meets a chip's time (``kernel_time.steps_and_seconds`` sums steps and seconds chip by chip). None where
the program has no such counter (the parent of the PR that added it), the configuration names no such cost, no row
travelled, or the trace holds no all-to-all."""

from benchmarks.lib import flops, kernel_time, program
from benchmarks.lib.peaks import peaks_for

UNIT, BETTER, SOURCE = "%", "higher", "device_trace"
LAYER = "expert layer (moe/)"
MOVES = "train_tokens_per_s"
OPS = r"\ball-to-all\b"


def read(record):
    cost = getattr(flops.for_config(record.get("config")), "exchange_cost", None)
    sent = program.counter(record, "moe_rows_sent_total")
    steps, took = kernel_time.steps_and_seconds(record.get("reduced"), OPS)
    window_steps = (record.get("train") or {}).get("steps")
    if cost is None or not sent or not took or not window_steps:
        return None
    rows = sent / (window_steps * record["device"]["count"])  # rows that leave ONE chip in a step, all routed layers together
    need = cost(record["published"], rows)["bytes_sent"] * 8.0 / peaks_for(record["device"]["kind"])["ici_bits_per_s"]
    return 100.0 * steps * need / took
