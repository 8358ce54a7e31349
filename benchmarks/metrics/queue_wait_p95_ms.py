"""How long a request waited for the scheduler: its first ``prefill_chunk``
event less its ``enqueue`` event (stamped with the arrival that was due), from
the program's request event log, 95th percentile over the window's requests.
The window's requests are each uid's last timeline (``benchmarks/lib/program.py``)."""

from benchmarks.lib import program
from benchmarks.lib.stats import percentile

UNIT, BETTER, SOURCE = "ms", "lower", "program_span"
LAYER = "scheduler (inference/v2/scheduler.py)"
MOVES = "ttft_p95_ms"


def read(record):
    n = len(record.get("requests") or ())
    events = (program.of(record) or {}).get("events") if n else None
    if not events:
        return None
    enqueued, waited = {}, {}
    for e in events:
        uid = e.get("uid", -1)
        if e["kind"] == "enqueue":
            enqueued[uid] = e["ts"]
            waited.pop(uid, None)  # a uid is used again by every pass: the last one is the window's
        elif e["kind"] == "prefill_chunk" and uid in enqueued and uid not in waited:
            waited[uid] = e["ts"] - enqueued[uid]
    waits = [waited[uid] * 1e3 for uid in range(n) if uid in waited]
    return percentile(waits, 95) if len(waits) == n else None
