"""Share of the device's busy time that the paged-attention kernels take
(decode, chunked prefill and mixed: every Pallas kernel of
``ops/pallas/paged_attention.py``, found in the trace by its name)."""

from benchmarks.lib.trace import ops_matching

UNIT, BETTER, SOURCE = "%", "lower", "device_trace"
LAYER = "kernels (ops/pallas/paged_attention.py)"
MOVES = "serve_tokens_per_s"
KERNELS = r"_decode_kernel|_prefill_kernel|paged_attention|paged_decode|paged_prefill|paged_mixed"


def read(record):
    reduced = record.get("reduced")
    if not reduced or not reduced["busy_s"]:
        return None
    secs, calls = ops_matching(reduced, KERNELS)
    if not calls:
        return None
    return 100.0 * secs / (reduced["busy_s"] * len(reduced["devices"]))
