"""How late the load generator ran: 95th percentile of (admitted - due) over
the window's requests. A starved generator must not be read as a fast server."""

UNIT, BETTER, SOURCE = "ms", "lower", "host_clock"
LAYER = "load generator (benchmarks/drivers/serve.py)"
MOVES = "ttft_p95_ms"


def read(record):
    return (record.get("summary") or {}).get("gen_late_p95_ms")
