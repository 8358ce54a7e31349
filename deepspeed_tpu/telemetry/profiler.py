"""Device-timeline profiler: per-quantum waterfall with collective exposure.

The perf accountant (PR 8) prices each dispatch as one opaque wall window;
nothing in the stack can say how much of a serving quantum was device
compute, how much was TP allreduce time actually *exposed* (not hidden
under compute), how much was d2h/h2d transfer, and how much was host gap
(scheduling, commit closures, readbacks). This module closes that hole
with bounded structured capture windows:

- ``DS_TPU_PROFILE=1`` arms a one-shot capture at engine construction
  (or ``POST /profile/capture`` re-arms at runtime). The first quantum
  dispatched after arming starts a ``jax.profiler`` trace under
  ``DS_TPU_PROFILE_DIR``; each subsequent quantum records a synchronized
  host-side marker at its readback boundary (the same boundary the perf
  accountant's ``attribute()`` closes); after ``DS_TPU_PROFILE_QUANTA``
  markers the trace stops and is parsed in-process.
- The emitted Chrome-trace events are classified into device compute /
  collective / transfer lanes (host lanes and executor bookkeeping are
  excluded) and cut against the quantum markers into a per-quantum
  waterfall: compute, collective split exposed-vs-overlapped (interval
  subtraction against the compute union), transfer, and host gap.
- Collective trace time is cross-checked against the ``tp_all_reduce``
  ledger from ``comm/collectives.py`` (comm-audit entries when
  ``DS_TPU_COMM_AUDIT`` is on, plus the ``infer_tp_allreduce_bytes_total``
  counter delta) so a trace that dropped collective events is visible.

Derived registry metrics: ``profile_collective_exposed_fraction``,
``profile_host_gap_fraction``, ``profile_device_busy_fraction``, and the
``profile_captures_total`` counter. Consumers: ``tools/trace_report.py``
(waterfall rendering), the ops plane (``GET /profile``) and the flight
recorder (post-anomaly window summarised into the manifest).

Lane classification note: real accelerator traces put XLA ops on
``/device:*`` pids; the CPU backend puts them on host-pid threads named
``tf_XLATfrtCpuClient/...`` — both count as device lanes so the CPU
smoke path measures real (nonzero) device time.

Everything is best-effort and bounded: a failed ``start_trace`` (e.g.
the flight recorder already holds the profiler) degrades to a span-only
summary, parse failures record an error string, and the stored summary
caps quantum rows and program lists so an ops-plane scrape stays small.
"""

import gzip
import json
import os
import re
import threading
import time
from typing import Dict, List, Optional, Tuple

from ..analysis import knobs

SUMMARY_SCHEMA = 1
MAX_QUANTA_ROWS = 256     # summary rows kept per capture (ops-plane bound)
TOP_PROGRAMS = 8          # top-N device programs reported per quantum/total

_COLLECTIVE_TOKENS = ("all-reduce", "allreduce", "all_reduce", "psum",
                      "reduce-scatter", "reduce_scatter", "all-gather",
                      "all_gather", "allgather", "all-to-all", "alltoall",
                      "collective-permute", "collective_permute",
                      "collective-broadcast", "ragged-all-to-all")
_TRANSFER_TOKENS = ("d2h", "h2d", "memcpy", "copy-start", "copy-done",
                    "copy.", "copystart", "copydone", "infeed", "outfeed",
                    "transferto", "transferfrom", "buffer_from", "to_host",
                    "from_host", "device_to_host", "host_to_device")
_INFRA_TOKENS = ("threadpoollistener", "thunkexecutor", "taskdispatcher")
# CPU backend: XLA executes on these host threads; TPU: /device:* pids
_DEVICE_THREAD_RE = re.compile(
    r"XLATfrtCpuClient|XLA.*Launch|StreamExecutor|TensorFlow Ops", re.I)

_DTYPE_BYTES = {"float32": 4, "f32": 4, "float64": 8, "f64": 8,
                "bfloat16": 2, "bf16": 2, "float16": 2, "f16": 2,
                "int8": 1, "uint8": 1, "int16": 2, "uint16": 2,
                "int32": 4, "uint32": 4, "int64": 8, "uint64": 8,
                "bool": 1}


# --------------------------------------------------------------- trace IO
def find_trace_files(root: str) -> List[str]:
    """Chrome-trace files under a profiler output dir — jax lands them at
    ``<root>/plugins/profile/<timestamp>/<host>.trace.json.gz``."""
    out: List[str] = []
    for dirpath, _dirs, files in os.walk(root):
        for fn in files:
            if fn.endswith(".trace.json.gz") or fn.endswith(".trace.json"):
                out.append(os.path.join(dirpath, fn))
    return sorted(out)


def load_trace(path: str) -> Dict:
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return json.loads(f.read().decode())
    with open(path) as f:
        return json.load(f)


def dir_bytes(path: str) -> int:
    """Total on-disk bytes below ``path`` (size-bound enforcement)."""
    total = 0
    for dirpath, _dirs, files in os.walk(path):
        for fn in files:
            try:
                total += os.path.getsize(os.path.join(dirpath, fn))
            except OSError:
                pass
    return total


# ---------------------------------------------------------------- parsing
def _classify(name: str) -> str:
    low = name.lower()
    if any(t in low for t in _INFRA_TOKENS):
        return "infra"
    if any(t in low for t in _COLLECTIVE_TOKENS):
        return "collective"
    if any(t in low for t in _TRANSFER_TOKENS):
        return "transfer"
    return "compute"


def parse_trace_events(doc: Dict) -> Dict:
    """Normalise a Chrome-trace document (``{"traceEvents": [...]}``) into
    categorised events with window-relative times in seconds.

    Device lanes are ``/device:*`` pids (real accelerators) plus host-pid
    threads matching ``_DEVICE_THREAD_RE`` (the CPU backend's XLA
    execution threads); everything else is ``host``. Device events are
    split compute / collective / transfer by op-name tokens, with
    executor bookkeeping (``ThreadpoolListener`` etc.) set aside as
    ``infra`` so it never counts as device busy time."""
    evs = doc.get("traceEvents") or []
    pid_names: Dict = {}
    tid_names: Dict = {}
    for e in evs:
        if e.get("ph") == "M":
            args = e.get("args") or {}
            if e.get("name") == "process_name":
                pid_names[e.get("pid")] = str(args.get("name", ""))
            elif e.get("name") == "thread_name":
                tid_names[(e.get("pid"), e.get("tid"))] = str(args.get("name", ""))
    xs = [e for e in evs
          if e.get("ph") == "X" and isinstance(e.get("ts"), (int, float))]
    if not xs:
        return {"t0_us": 0.0, "span_s": 0.0, "events": []}
    t0 = min(float(e["ts"]) for e in xs)
    out: List[Dict] = []
    span = 0.0
    for e in xs:
        pname = pid_names.get(e.get("pid"), "")
        tname = tid_names.get((e.get("pid"), e.get("tid")), "")
        device = pname.startswith("/device:") or bool(_DEVICE_THREAD_RE.search(tname))
        name = str(e.get("name", ""))
        cat = _classify(name) if device else "host"
        start = (float(e["ts"]) - t0) / 1e6
        dur = max(0.0, float(e.get("dur") or 0.0) / 1e6)
        span = max(span, start + dur)
        out.append({"name": name, "cat": cat, "start_s": start,
                    "dur_s": dur, "lane": tname or pname})
    return {"t0_us": t0, "span_s": span, "events": out}


# ------------------------------------------------------- interval algebra
def _merge(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Sorted union of [lo, hi) intervals."""
    merged: List[Tuple[float, float]] = []
    for lo, hi in sorted(i for i in intervals if i[1] > i[0]):
        if merged and lo <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], hi))
        else:
            merged.append((lo, hi))
    return merged


def _total(merged: List[Tuple[float, float]]) -> float:
    return sum(hi - lo for lo, hi in merged)


def _clip(merged: List[Tuple[float, float]], lo: float,
          hi: float) -> List[Tuple[float, float]]:
    return [(max(a, lo), min(b, hi)) for a, b in merged
            if b > lo and a < hi]


def _subtract(a: List[Tuple[float, float]],
              b: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged intervals of ``a`` minus the union ``b`` (exposed time)."""
    out: List[Tuple[float, float]] = []
    for lo, hi in a:
        cur = lo
        for blo, bhi in b:
            if bhi <= cur or blo >= hi:
                continue
            if blo > cur:
                out.append((cur, blo))
            cur = max(cur, bhi)
            if cur >= hi:
                break
        if cur < hi:
            out.append((cur, hi))
    return out


def _frac(num: float, den: float) -> float:
    if den <= 0:
        return 0.0
    return max(0.0, min(1.0, num / den))


# -------------------------------------------------------------- waterfall
def build_waterfall(parsed: Optional[Dict], markers: List[Dict],
                    window_s: Optional[float] = None,
                    ledger: Optional[Dict] = None,
                    top_n: int = TOP_PROGRAMS) -> Dict:
    """Cut categorised trace events against quantum markers into the
    per-quantum waterfall model.

    ``markers`` are readback-boundary host stamps (``rel_s`` relative to
    trace start): quantum *k* covers ``(markers[k-1].rel_s,
    markers[k].rel_s]`` — the interval between consecutive completions,
    so host gap between dispatches lands in the quantum that paid it.
    With no markers the whole window is one synthetic quantum (raw
    flight-recorder profiles)."""
    parsed = parsed or {"span_s": 0.0, "events": []}
    events = parsed.get("events") or []
    span = max(float(window_s or 0.0), float(parsed.get("span_s") or 0.0))

    by_cat: Dict[str, List[Tuple[float, float]]] = {
        "compute": [], "collective": [], "transfer": []}
    prog_time: Dict[str, float] = {}
    for e in events:
        cat = e["cat"]
        if cat in by_cat:
            by_cat[cat].append((e["start_s"], e["start_s"] + e["dur_s"]))
        if cat == "compute":
            prog_time[e["name"]] = prog_time.get(e["name"], 0.0) + e["dur_s"]
    comp_u = _merge(by_cat["compute"])
    coll_u = _merge(by_cat["collective"])
    tran_u = _merge(by_cat["transfer"])
    busy_u = _merge(comp_u + coll_u + tran_u)
    exposed_u = _subtract(coll_u, comp_u)

    marks = sorted((dict(m) for m in markers or []), key=lambda m: m["rel_s"])
    if marks:
        bounds = [0.0] + [float(m["rel_s"]) for m in marks]
    else:
        bounds = [0.0, span]
        marks = [{"program": "window", "attrs": {}}]
    quanta: List[Dict] = []
    for i, mark in enumerate(marks):
        lo, hi = bounds[i], bounds[i + 1] if i + 1 < len(bounds) else span
        hi = max(hi, lo)
        c = _clip(comp_u, lo, hi)
        k = _clip(coll_u, lo, hi)
        t = _clip(tran_u, lo, hi)
        b = _clip(busy_u, lo, hi)
        x = _clip(exposed_u, lo, hi)
        dur = hi - lo
        quanta.append({
            "index": i, "program": mark.get("program", "?"),
            "start_s": round(lo, 6), "dur_s": round(dur, 6),
            "compute_s": round(_total(c), 6),
            "collective_s": round(_total(k), 6),
            "collective_exposed_s": round(_total(x), 6),
            "transfer_s": round(_total(t), 6),
            "device_busy_s": round(_total(b), 6),
            "host_gap_s": round(max(0.0, dur - _total(b)), 6),
            "attrs": mark.get("attrs", {}),
        })

    busy_s = _total(busy_u)
    coll_s = _total(coll_u)
    exposed_s = _total(exposed_u)
    totals = {
        "wall_s": round(span, 6),
        "compute_s": round(_total(comp_u), 6),
        "collective_s": round(coll_s, 6),
        "collective_exposed_s": round(exposed_s, 6),
        "collective_overlapped_s": round(max(0.0, coll_s - exposed_s), 6),
        "transfer_s": round(_total(tran_u), 6),
        "device_busy_s": round(busy_s, 6),
        "host_gap_s": round(max(0.0, span - busy_s), 6),
    }
    fractions = {
        "device_busy": round(_frac(busy_s, span), 6),
        "host_gap": round(_frac(max(0.0, span - busy_s), span), 6),
        "collective_exposed": round(_frac(exposed_s, coll_s), 6),
    }
    programs = sorted(prog_time.items(), key=lambda kv: -kv[1])[:top_n]
    n_coll_events = sum(1 for e in events if e["cat"] == "collective")
    collectives = {
        "trace_ops": n_coll_events,
        "trace_s": totals["collective_s"],
        "exposed_s": totals["collective_exposed_s"],
        "overlapped_s": totals["collective_overlapped_s"],
        "exposed_fraction": fractions["collective_exposed"],
        "ledger": dict(ledger or {}),
    }
    return {
        "schema": SUMMARY_SCHEMA,
        "window_s": round(span, 6),
        "n_events": len(events),
        "n_quanta": len(quanta),
        "quanta": quanta[:MAX_QUANTA_ROWS],
        "quanta_truncated": max(0, len(quanta) - MAX_QUANTA_ROWS),
        "totals": totals,
        "fractions": fractions,
        "programs": [[name, round(sec, 6)] for name, sec in programs],
        "collectives": collectives,
    }


def summarize_trace_dir(trace_dir: str,
                        window_s: Optional[float] = None) -> Dict:
    """Parse a raw profiler output directory (e.g. a flight capture's
    ``profile/``) into a single-window waterfall summary."""
    files = find_trace_files(trace_dir)
    if not files:
        return {"schema": SUMMARY_SCHEMA, "trace": "unavailable",
                "error": f"no trace files under {trace_dir}"}
    try:
        summary = build_waterfall(parse_trace_events(load_trace(files[-1])),
                                  markers=[], window_s=window_s)
        summary["trace"] = "ok"
        summary["trace_file"] = os.path.basename(files[-1])
        return summary
    except Exception as e:  # a corrupt trace must not kill the caller
        return {"schema": SUMMARY_SCHEMA, "trace": "unavailable",
                "error": f"{type(e).__name__}: {e}"}


# ----------------------------------------------------------- the profiler
class DeviceProfiler:
    """One-shot bounded capture window over serving quanta.

    States: ``idle`` → ``arm()`` → ``armed`` → first ``note_quantum``
    starts the trace (``tracing``) → after ``quanta_target`` markers the
    trace stops, parses, lands gauges, and the profiler returns to
    ``idle``. ``note_quantum`` in ``idle`` is one attribute compare."""

    def __init__(self, out_dir: Optional[str] = None,
                 quanta: Optional[int] = None):
        self.out_dir = str(out_dir
                           or knobs.get_str("DS_TPU_PROFILE_DIR", "")
                           or "profile_captures")
        self.quanta_target = max(1, int(
            quanta if quanta is not None
            else knobs.get_int("DS_TPU_PROFILE_QUANTA")))
        self.state = "idle"
        self.captures = 0
        self._lock = threading.Lock()
        self._markers: List[Dict] = []
        self._host_t0 = 0.0
        self._trace_dir: Optional[str] = None
        self._trace_ok = False
        self._audit_mark = 0
        self._bytes_mark = 0.0
        self._summary: Optional[Dict] = None

    # -------------------------------------------------------- jax seams
    # overridable so unit tests can drop a fixture trace instead of
    # depending on a live jax profiler (which is process-global)
    def _start_trace(self, trace_dir: str) -> None:
        import jax
        jax.profiler.start_trace(trace_dir)

    def _stop_trace(self) -> None:
        import jax
        jax.profiler.stop_trace()

    # ------------------------------------------------------------ control
    def arm(self, quanta: Optional[int] = None) -> bool:
        """Request one capture window; no-op (False) while tracing."""
        with self._lock:
            if self.state == "tracing":
                return False
            if quanta is not None:
                self.quanta_target = max(1, int(quanta))
            self._markers = []
            self.state = "armed"
        return True

    def note_quantum(self, program: str, **attrs) -> None:
        """Dispatch-site hook, called at each quantum's readback boundary
        (right after the perf accountant's ``attribute()``)."""
        if self.state not in ("armed", "tracing"):
            return
        finalize = False
        with self._lock:
            if self.state == "armed":
                self._begin_locked()
                return  # this quantum ran before the trace started
            if self.state != "tracing":
                return
            self._markers.append({
                "index": len(self._markers), "program": str(program),
                "rel_s": time.perf_counter() - self._host_t0,
                "attrs": {k: v for k, v in attrs.items()
                          if isinstance(v, (int, float, str, bool))},
            })
            if len(self._markers) >= self.quanta_target:
                self.state = "stopping"
                finalize = True
        if finalize:
            self._finalize()

    def finish(self) -> Optional[Dict]:
        """Close an in-flight capture with however many quanta arrived
        (``tools/trace_report.py smoke`` calls this so that a short run
        still lands a summary)."""
        with self._lock:
            if self.state == "armed":
                self.state = "idle"
                return None
            if self.state != "tracing":
                return self._summary
            self.state = "stopping"
        self._finalize()
        return self._summary

    def _begin_locked(self) -> None:
        trace_dir = os.path.join(
            self.out_dir, f"capture-{self.captures:03d}-{os.getpid()}")
        try:
            os.makedirs(trace_dir, exist_ok=True)
        except OSError:
            trace_dir = None
        self._trace_dir = trace_dir
        self._trace_ok = False
        if trace_dir is not None:
            try:
                self._start_trace(trace_dir)
                self._trace_ok = True
            except Exception:
                # another trace (flight recorder) may hold the profiler:
                # degrade to a marker-only window
                self._trace_ok = False
        from .registry import get_registry
        self._bytes_mark = get_registry().peek(
            "infer_tp_allreduce_bytes_total") or 0.0
        try:
            from ..analysis.comm_audit import get_auditor
            auditor = get_auditor()
            self._audit_mark = len(auditor.entries()) if auditor else 0
        except Exception:
            self._audit_mark = 0
        self._host_t0 = time.perf_counter()
        self.state = "tracing"

    def _finalize(self) -> None:
        window_s = time.perf_counter() - self._host_t0
        trace_state = "ok" if self._trace_ok else "unavailable"
        if self._trace_ok:
            try:
                self._stop_trace()
            except Exception:
                trace_state = "unavailable"
        parsed = None
        if trace_state == "ok" and self._trace_dir:
            files = find_trace_files(self._trace_dir)
            if files:
                try:
                    parsed = parse_trace_events(load_trace(files[-1]))
                except Exception:
                    trace_state = "unavailable"
            else:
                trace_state = "unavailable"
        summary = build_waterfall(parsed, self._markers,
                                  window_s=window_s,
                                  ledger=self._ledger_delta())
        summary["trace"] = trace_state
        summary["trace_dir"] = self._trace_dir
        summary["quanta_target"] = self.quanta_target
        self._land_metrics(summary)
        if self._trace_dir:
            try:
                with open(os.path.join(self._trace_dir, "summary.json"),
                          "w") as f:
                    json.dump(summary, f, indent=2, sort_keys=True)
            except OSError:
                pass
        with self._lock:
            self._summary = summary
            self.captures += 1
            self.state = "idle"

    def _ledger_delta(self) -> Dict:
        """``tp_all_reduce`` traffic recorded during the window: comm-audit
        entries (op/dtype/shape → bytes) when the auditor is on, plus the
        allreduce-bytes counter delta either way."""
        from .registry import get_registry
        out: Dict = {"source": "counter"}
        now = get_registry().peek("infer_tp_allreduce_bytes_total") or 0.0
        out["counter_bytes"] = int(now - self._bytes_mark)
        try:
            from ..analysis.comm_audit import get_auditor
            auditor = get_auditor()
        except Exception:
            auditor = None
        if auditor is not None:
            ops = 0
            nbytes = 0
            for op in auditor.entries()[self._audit_mark:]:
                if op.op != "tp_all_reduce":
                    continue
                ops += 1
                elems = 1
                for d in op.shape:
                    elems *= int(d)
                nbytes += elems * _DTYPE_BYTES.get(str(op.dtype), 4)
            out.update(source="comm_audit", ops=ops, bytes=nbytes)
        return out

    def _land_metrics(self, summary: Dict) -> None:
        try:
            from .registry import get_registry
            reg = get_registry()
            fr = summary.get("fractions") or {}
            reg.gauge("profile_collective_exposed_fraction").set(
                float(fr.get("collective_exposed") or 0.0))
            reg.gauge("profile_host_gap_fraction").set(
                float(fr.get("host_gap") or 0.0))
            reg.gauge("profile_device_busy_fraction").set(
                float(fr.get("device_busy") or 0.0))
            reg.counter("profile_captures_total").inc()
        except Exception:
            pass

    # ------------------------------------------------------------ reading
    def summary(self) -> Optional[Dict]:
        return self._summary

    def status(self) -> Dict:
        return {"state": self.state, "captures": self.captures,
                "quanta_target": self.quanta_target,
                "out_dir": self.out_dir,
                "n_markers": len(self._markers)}

    def write_rank_summary(self, out_dir: str) -> Optional[str]:
        """Drop this rank's last summary as ``profile-rank<k>.json`` for
        ``tools/telemetry_merge.py`` (parallel to the metric snapshots'
        ``telemetry-rank<k>.json``)."""
        if self._summary is None:
            return None
        from .agg import rank_stamp
        stamp = rank_stamp()
        os.makedirs(out_dir, exist_ok=True)
        path = os.path.join(out_dir,
                            f"profile-rank{stamp['process_index']}.json")
        with open(path, "w") as f:
            json.dump({"rank": stamp, "summary": self._summary}, f,
                      indent=2, sort_keys=True)
        return path


# ----------------------------------------------------------- module state
_PROFILER: Optional[DeviceProfiler] = None
_PROFILER_LOCK = threading.Lock()


def get_device_profiler() -> Optional[DeviceProfiler]:
    return _PROFILER


def maybe_arm_profiler() -> Optional[DeviceProfiler]:
    """Engine-constructor hook: with ``DS_TPU_PROFILE`` unset this is one
    bool read; set, it creates the singleton and arms the one-shot
    capture (only if it has never fired — a finished capture is not
    re-armed by the next engine build; ``request_capture`` re-arms)."""
    global _PROFILER
    if not knobs.get_bool("DS_TPU_PROFILE"):
        return _PROFILER
    with _PROFILER_LOCK:
        if _PROFILER is None:
            _PROFILER = DeviceProfiler()
    if _PROFILER.captures == 0 and _PROFILER.state == "idle":
        _PROFILER.arm()
    return _PROFILER


def request_capture(quanta: Optional[int] = None) -> Tuple[DeviceProfiler, bool]:
    """Arm a capture on demand (the ops plane): creates the singleton
    if needed; returns (profiler, armed) — armed is False while a
    capture is already tracing."""
    global _PROFILER
    with _PROFILER_LOCK:
        if _PROFILER is None:
            _PROFILER = DeviceProfiler(quanta=quanta)
    return _PROFILER, _PROFILER.arm(quanta)


def note_quantum(program: str, **attrs) -> None:
    """Module-level dispatch hook: one global read + None check when no
    profiler exists (the common case)."""
    p = _PROFILER
    if p is not None:
        p.note_quantum(program, **attrs)


def _reset_for_tests() -> None:
    global _PROFILER
    _PROFILER = None
