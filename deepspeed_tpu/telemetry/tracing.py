"""Wall-time span tracer with a fixed-size ring buffer.

``span("train/forward", **attrs)`` records a span at *dispatch*
granularity: entry/exit stamp ``time.perf_counter()`` and never touch a
device, so a span around jitted work measures how long the Python side
took to *enqueue* it — exactly the trace-safe semantics the async TPU
dispatch model wants.

Spans form a tree: each carries an ``id`` and its ``parent``'s id (0 at
the root of a thread), so a layer's self time is its duration less its
children's (``self_times``). ``q`` (the scheduler's quantum id) and
``uid`` attributes are the identifiers a span shares with the event log.

Every span is also a ``jax.profiler.TraceAnnotation`` of the same name
and entry attributes: in any profiler session the program's spans lie on
``/host:CPU`` on the profiler's clock, nested inside whatever annotations
the caller holds. With no session open the annotation costs about half a
microsecond (PERF.md, PR 25).

``dump_trace(path)`` exports the ring as Chrome trace-event JSON
(load in Perfetto / ``chrome://tracing``) or, for ``*.jsonl`` paths,
one span per line.
"""

import itertools
import json
import os
import threading
import time
from collections import deque
from typing import Dict, List, Optional

from jax.profiler import TraceAnnotation

from ..analysis import knobs

_TLS = threading.local()
_IDS = itertools.count(1)  # next() is one C call: atomic under the GIL


class _NullSpan:
    """Singleton no-op context manager — the disabled path allocates nothing."""
    __slots__ = ()
    attrs = None

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc_val, exc_tb):
        return False

    def set(self, **attrs):
        pass


_NULL_SPAN = _NullSpan()


class _ActiveSpan:
    __slots__ = ("_tracer", "name", "attrs", "t0", "depth", "id", "parent", "_up", "_ann")

    def __init__(self, tracer, name, attrs):
        self._tracer = tracer
        self.name = name
        self.attrs = attrs

    def set(self, **attrs):
        """Attributes known only once the work is under way (hit or miss,
        seconds by phase). They reach the ring, not the profiler's copy."""
        if self.attrs is None:
            self.attrs = attrs
        else:
            self.attrs.update(attrs)

    def __enter__(self):
        up = self._up = getattr(_TLS, "top", None)
        if up is None:
            self.depth, self.parent = 0, 0
        else:
            self.depth, self.parent = up.depth + 1, up.id
        self.id = next(_IDS)
        _TLS.top = self
        ann = self._ann = TraceAnnotation(self.name, **self.attrs) if self.attrs else TraceAnnotation(self.name)
        ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc_val, exc_tb):
        t1 = time.perf_counter()
        self._ann.__exit__(exc_type, exc_val, exc_tb)
        _TLS.top = self._up
        tr = self._tracer
        ring = tr._ring
        if len(ring) == ring.maxlen:
            tr._m_dropped.inc()  # oldest span about to fall off the ring
        ring.append((self.name, self.t0, t1 - self.t0, threading.get_ident(),
                     self.depth, self.attrs, self.id, self.parent))
        return False


def _record(rec) -> Dict:
    name, t0, dur, tid, depth, attrs, sid, parent = rec
    return {"name": name, "start_s": t0, "dur_s": dur, "tid": tid, "depth": depth,
            "attrs": attrs or {}, "id": sid, "parent": parent}


class SpanTracer:
    """Ring-buffered span recorder. One process-wide instance via
    ``get_tracer()``; direct construction is for tests."""

    def __init__(self, capacity: int = 4096, enabled: bool = True, registry=None):
        self.enabled = enabled
        self._ring = deque(maxlen=max(1, int(capacity)))
        if registry is None:
            from .registry import get_registry
            registry = get_registry()
        self._m_dropped = registry.counter("telemetry_spans_dropped_total")

    def span(self, name: str, **attrs):
        if not self.enabled:
            return _NULL_SPAN
        return _ActiveSpan(self, name, attrs or None)

    # ---------------------------------------------------------- reading
    def spans(self) -> List[Dict]:
        """Completed spans, oldest first, as dicts."""
        return [_record(rec) for rec in self._ring]

    def clear(self) -> None:
        self._ring.clear()

    def dump_trace(self, path) -> str:
        """Write the ring to ``path``: Chrome trace-event JSON by default,
        one-record-per-line JSONL when the path ends in ``.jsonl``."""
        path = str(path)
        records = self.spans()
        if path.endswith(".jsonl"):
            with open(path, "w") as f:
                for rec in records:
                    f.write(json.dumps(rec) + "\n")
            return path
        pid = os.getpid()
        events = [
            {"name": r["name"], "ph": "X", "ts": r["start_s"] * 1e6, "dur": r["dur_s"] * 1e6,
             "pid": pid, "tid": r["tid"],
             "cat": r["name"].split("/", 1)[0] if "/" in r["name"] else "span",
             "args": dict(r["attrs"], id=r["id"], parent=r["parent"])}
            for r in records
        ]
        with open(path, "w") as f:
            json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, f)
        return path


_TRACER: Optional[SpanTracer] = None


def get_tracer() -> SpanTracer:
    """The process-wide tracer. Env knobs: ``DS_TPU_TELEMETRY=0`` disables,
    ``DS_TPU_TRACE_RING`` sizes the ring."""
    global _TRACER
    if _TRACER is None:
        _TRACER = SpanTracer(
            capacity=knobs.get_int("DS_TPU_TRACE_RING"),
            enabled=knobs.get_bool("DS_TPU_TELEMETRY"),
        )
    return _TRACER


def span(name: str, **attrs):
    """Module-level convenience over ``get_tracer().span(...)``."""
    tracer = _TRACER
    if tracer is None:
        tracer = get_tracer()
    if not tracer.enabled:
        return _NULL_SPAN
    return _ActiveSpan(tracer, name, attrs or None)


def dump_trace(path) -> str:
    return get_tracer().dump_trace(path)


def current_span():
    """The innermost span open on this thread, or None: how a seam deep in a
    call (a program's first call) learns the ``q`` of the quantum it is in."""
    return getattr(_TLS, "top", None)


def self_times(spans: List[Dict]) -> Dict[int, float]:
    """Span id -> its duration less its direct children's, for records as
    ``spans()`` gives them. A child whose parent is not among ``spans``
    (it fell off the ring, or is still open) is charged to nobody."""
    out = {s["id"]: s["dur_s"] for s in spans}
    for s in spans:
        if s["parent"] in out:
            out[s["parent"]] -= s["dur_s"]
    return out
