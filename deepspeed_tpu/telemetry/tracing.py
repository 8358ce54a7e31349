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
``span.phase(name)`` times a named PART of an open span without a record of
its own (``phase_s`` in the span's attributes; ``_Phase``).

Every span is also a ``jax.profiler.TraceAnnotation`` of the same name
and entry attributes: in any profiler session the program's spans lie on
``/host:CPU`` on the profiler's clock, nested inside whatever annotations
the caller holds. With no session open the annotation costs about half a
microsecond (PERF.md, PR 25).

``dump_trace(path)`` exports the ring as Chrome trace-event JSON
(load in Perfetto / ``chrome://tracing``) or, for ``*.jsonl`` paths,
one span per line.

``region("ffn/experts", path="kernel")`` is the other instrument, for code
that runs while a program is being TRACED, where a span is for code that runs
each step on the host. It is ``jax.named_scope``: the name reaches the
``op_name`` of every HLO instruction traced under it, through ``jax.jit``'s
replay of cached equations, ``jax.checkpoint``, ``custom_vjp``, ``shard_map``
and ``lax.cond``, and so onto the device's clock at no run-time cost
(``telemetry/profiler.py`` reads it back: ``region_card``, ``region_times``).
The names are a closed list in docs/OBSERVABILITY.md, "Regions".
"""

import itertools
import json
import os
import threading
import time
from collections import deque
from typing import Dict, List, Optional

import jax
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

    def phase(self, name):
        return self


_NULL_SPAN = _NullSpan()


class _Phase:
    """A named part of an open span: a bare annotation ``<span>/<name>`` (a
    device idle gap under it is named by the part) whose seconds are added to
    the span's late attribute ``phase_s[name]``. No ring record, no span id:
    the span's self time and its readers stay as they were."""
    __slots__ = ("_span", "_name", "_ann", "_t0")

    def __init__(self, span, name):
        self._span, self._name = span, name

    def __enter__(self):
        self._ann = TraceAnnotation(f"{self._span.name}/{self._name}")
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc_val, exc_tb):
        took = time.perf_counter() - self._t0
        self._ann.__exit__(exc_type, exc_val, exc_tb)
        span = self._span
        if span.attrs is None:
            span.attrs = {}
        by = span.attrs.setdefault("phase_s", {})
        by[self._name] = by.get(self._name, 0.0) + took
        return False


class _ActiveSpan:
    __slots__ = ("_tracer", "name", "attrs", "t0", "id", "parent", "_up", "_ann")

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

    def phase(self, name):
        return _Phase(self, name)

    def __enter__(self):
        up = self._up = getattr(_TLS, "top", None)
        self.parent = 0 if up is None else up.id
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
                     self.attrs, self.id, self.parent))
        return False


def _record(rec) -> Dict:
    name, t0, dur, tid, attrs, sid, parent = rec
    return {"name": name, "start_s": t0, "dur_s": dur, "tid": tid,
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


PHASES = ("forward", "recomputed", "backward", "update")


def phase_of(op_name: str) -> str:
    """The phase JAX's transforms wrote into a name stack (an HLO
    instruction's ``op_name``, or an equation's stack under its enclosing
    equations'). ``rematted_computation``: the forward made a second time
    under ``jax.checkpoint`` (``recomputed``); else ``transpose(``: the
    ``backward``; else ``jvp(``: the ``forward``; what has none of them (the
    optimizer, and whatever is not differentiated) is the ``update``. The
    phase is no region: nobody names it."""
    return ("recomputed" if "rematted_computation" in op_name else "backward" if "transpose(" in op_name
            else "forward" if "jvp(" in op_name else "update")


_REGIONS_SEEN = set()  # every name ``region`` was given in this process: what ``region_card`` looks for in an ``op_name``


def regions_seen() -> frozenset:
    return frozenset(_REGIONS_SEEN)


class _TimedRegion:
    """A region traced inside a program's first call: the scope, and the
    body's Python seconds less the regions nested in it, summed by name into
    the first-call span's ``region_trace_s``."""
    __slots__ = ("name", "_into", "_scope", "_t0", "_nested", "_up")

    def __init__(self, name, into):
        self.name, self._into = name, into

    def __enter__(self):
        self._up, _TLS.region, self._nested = getattr(_TLS, "region", None), self, 0.0
        self._scope = jax.named_scope(self.name)
        self._scope.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc_val, exc_tb):
        took = time.perf_counter() - self._t0
        self._scope.__exit__(exc_type, exc_val, exc_tb)
        _TLS.region = self._up
        if self._up is not None:
            self._up._nested += took
        self._into[self.name] = self._into.get(self.name, 0.0) + took - self._nested
        return False


def region(name: str, **choice):
    """``jax.named_scope(name)`` around code that is being traced into a
    program, and nothing else at run time (the body does not run then).
    While tracing it also counts ``program_regions_traced_total{region,
    **choice}`` where the site made a ``choice`` (``path="kernel"`` /
    ``"xla"``, ``pass`` and ``op`` where a site has them: which form of the
    part was traced, counted where it is chosen), and, inside a program's
    first call, adds the body's Python self time to that
    ``program/first_call`` span's ``region_trace_s`` (a dict by region; a
    late attribute, so the ring's copy alone). With ``DS_TPU_TELEMETRY=0`` it
    is the scope alone."""
    tracer = _TRACER
    if tracer is None:
        tracer = get_tracer()
    _REGIONS_SEEN.add(name)
    if not tracer.enabled:
        return jax.named_scope(name)
    if choice:
        from .registry import get_registry

        get_registry().counter("program_regions_traced_total", region=name, **choice).inc()
    up = open_span("program/first_call")
    if up is _NULL_SPAN:
        return jax.named_scope(name)
    if up.attrs is None or "region_trace_s" not in up.attrs:
        up.set(region_trace_s={})
    return _TimedRegion(name, up.attrs["region_trace_s"])


def regions_traced(region: str, **labels) -> float:
    """The sum of ``program_regions_traced_total`` over every series of
    ``region`` whose labels include ``labels``."""
    from .registry import get_registry

    return get_registry().total("program_regions_traced_total", region=region, **labels)


def regions_traced_by(region: str, label: str) -> Dict[str, float]:
    """``regions_traced`` of ``region`` by the values of ``label``, for a site whose label is a number it worked out
    (the tiles a walk visits) and not one of a closed list of words."""
    from .registry import get_registry

    return get_registry().by_label("program_regions_traced_total", label, region=region)


def current_span():
    """The innermost span open on this thread, or None: how a seam deep in a
    call (a program's first call) learns the ``q`` of the quantum it is in."""
    return getattr(_TLS, "top", None)


def open_span(name: str):
    """The nearest open span called ``name`` on this thread, else the null
    span: how code under a span (the trainer's dispatch, inside a first
    call's own span) adds a phase to it."""
    up = getattr(_TLS, "top", None)
    while up is not None and up.name != name:
        up = up._up
    return up if up is not None else _NULL_SPAN


def self_times(spans: List[Dict]) -> Dict[int, float]:
    """Span id -> its duration less its direct children's, for records as
    ``spans()`` gives them. A child whose parent is not among ``spans``
    (it fell off the ring, or is still open) is charged to nobody."""
    out = {s["id"]: s["dur_s"] for s in spans}
    for s in spans:
        if s["parent"] in out:
            out[s["parent"]] -= s["dur_s"]
    return out
