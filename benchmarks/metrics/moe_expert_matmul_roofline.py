"""The routed FFN's grouped products' share of their roofline: the least time
for three grouped matrix products, forward and backward, over the (token,
expert) pairs that were routed to the experts held here
(``expert_matmul_cost`` of the configuration's own FLOP module at the rows the
program's counter ``moe_rows_routed_here_total`` gives a layer and a step)
over the device time of the grouped-matmul kernels (``gmm`` / ``tgmm``, the
Pallas grouped matmul ``moe/sharded_moe.py`` calls on a TPU). None where the
program has no such counter or the trace no such kernel."""

from benchmarks.lib import flops, kernel_time, program
from benchmarks.lib.peaks import peaks_for

UNIT, BETTER, SOURCE = "%", "higher", "device_trace"
LAYER = "expert layer (moe/)"
MOVES = "train_tokens_per_s"
KERNELS = r"^(?=.*custom-call)(?=.*\bt?gmm\b)"


def read(record):
    cost = getattr(flops.for_config(record.get("config")), "expert_matmul_cost", None)
    routed = program.counter(record, "moe_rows_routed_here_total")
    steps, took = kernel_time.steps_and_seconds(record.get("reduced"), KERNELS)
    if cost is None or not routed or not took or not record["train"].get("steps"):
        return None
    m = record["published"]
    layers = m["num_hidden_layers"] - m["first_k_dense_replace"]
    rows = routed / (record["train"]["steps"] * layers)  # pairs a routed layer, a step of the measured window
    peaks = peaks_for(record["device"]["kind"])
    need = sum(flops.roofline_seconds(cost(m, rows, backward=b), peaks)["seconds"] for b in (False, True))
    return 100.0 * steps * layers * need / took
