"""Device-timeline profiler: per-quantum waterfall with collective exposure,
and the compiled training step's device time by named region and phase.

The perf accountant (PR 8) prices each dispatch as one opaque wall window;
nothing in the stack can say how much of a serving quantum was device
compute, how much was TP allreduce time actually *exposed* (not hidden
under compute), how much was d2h/h2d transfer, and how much was host gap
(scheduling, commit closures, readbacks). This module closes that hole
with bounded structured capture windows:

- ``DS_TPU_PROFILE=1`` arms a one-shot capture at engine construction, the
  serving engine's and the trainer's (or ``POST /profile/capture`` re-arms at
  runtime). The first quantum dispatched after arming starts a
  ``jax.profiler`` trace under ``DS_TPU_PROFILE_DIR``; each subsequent quantum
  records a host-side marker at its boundary (a serving quantum's readback, a
  trainer's ``step()``; the trainer passes by steps that make a first call);
  after ``DS_TPU_PROFILE_QUANTA`` markers the trace stops and is reduced
  in-process.
- ``DS_TPU_PROFILE=stall`` hunts: captures back to back, each DROPPED unread
  (``profile_captures_dropped_total``) unless one of its quanta took more
  than ``health.STALL_X`` medians by the host's stamps; the first that holds
  one is kept, the hunt ends, and its summary gains ``stall``
  (``stall_section``: the stalled quantum's name, as far as a trace has one).
- ``DS_TPU_PROFILE=setup`` captures SET-UP: the one-shot capture starts at the
  trainer's construction, ahead of ``init/engine``, and stops at the end of
  the first ``step()`` that made no first call, as ONE quantum. Construction's
  spans and phases and every ``program/first_call`` are annotations, so
  ``idle_by_span`` puts the first device's idle seconds of set-up down to them
  or to ``between spans`` (the caller), and the summary gains ``setup``
  (``module_executions``: each program's first execution beside its later
  ones). It reads no regions and leaves the compile cache's key alone.
- The trace is read from the ``.xplane.pb`` through
  ``jax.profiler.ProfileData`` (four chips make 240,000 device events a
  second and the Chrome JSON is an export of it that may be cut), with the
  lanes a TPU v5e trace has (``PERF.md``, "Trace facts"): one plane a chip,
  ``/device:TPU:<n>``, on it the lines ``XLA Modules`` (whole programs),
  ``XLA Ops`` (single operations, named by their whole HLO line; a ``while``
  and a ``conditional`` enclose their bodies' operations) and ``Async XLA
  Ops`` (start-to-done spans of asynchronous copies and collectives); the
  host's threads on ``/host:CPU``, where the program's spans lie as
  ``TraceAnnotation``s on the profiler's clock. Operations are compute /
  collective / transfer by opcode and are cut against the quantum markers
  into a per-quantum waterfall: compute, collective split
  exposed-vs-overlapped (interval subtraction against the compute union),
  transfer, and host gap, of the FIRST device.
- Collective trace time is cross-checked against the ``tp_all_reduce``
  ledger from ``comm/collectives.py`` (comm-audit entries when
  ``DS_TPU_COMM_AUDIT`` is on, plus the ``infer_tp_allreduce_bytes_total``
  counter delta) so a trace that dropped collective events is visible.
- **Regions.** A program that ``describe``s itself (the trainer's step:
  the text of the executable that runs) gets a *region card*
  (``region_card``): every instruction's region (``telemetry/tracing.py::
  region``: the innermost on its ``op_name``), the enclosing ones and its
  phase (forward, recomputed forward, backward, update), fused instructions
  too; and ``region_times``: device self seconds a step by (region, phase)
  over the events inside that program's ``XLA Modules`` intervals, mean over
  devices, with what has no region, what lies in fusions that span several,
  and the Pallas kernels by name. The card is built when a capture is
  reduced, never at set-up.

Derived registry metrics: ``profile_collective_exposed_fraction``,
``profile_host_gap_fraction``, ``profile_device_busy_fraction``, and the
``profile_captures_total`` counter. Consumers: ``tools/trace_report.py``
(waterfall and region rendering), the ops plane (``GET /profile``) and the
flight recorder (post-anomaly window summarised into the manifest).

Everything is best-effort and bounded: a failed ``start_trace`` (e.g.
the flight recorder already holds the profiler) degrades to a span-only
summary, parse failures record an error string, and the stored summary
caps quantum rows and program lists so an ops-plane scrape stays small.
"""

import json
import os
import re
import shutil
import statistics
import threading
import time
from typing import Callable, Dict, Iterable, List, Optional, Tuple

from jax.profiler import TraceAnnotation

from ..analysis import knobs
from ..utils.logging import logger
from .health import STALL_X
from .tracing import phase_of as _phase

SUMMARY_SCHEMA = 2
MAX_QUANTA_ROWS = 256     # summary rows kept per capture (ops-plane bound)
TOP_PROGRAMS = 8          # top-N device operations reported per quantum/total
TOP_MIXED = 12            # mixed fusions listed with their members

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, ASYNC_LINE, MODULES_LINE = "XLA Ops", "Async XLA Ops", "XLA Modules"
# an operation's event is named by its whole HLO line: "%fusion.7 = bf16[8,128]{1,0:T(8,128)} fusion(bf16[...] %p), ..."
HLO = re.compile(r"^\s*(?:ROOT )?%?(?P<own>[^\s=]+) = (?P<type>\(.*?\)|\S+) (?P<opcode>[a-z][a-z0-9\-]*)\(")
COLLECTIVE = re.compile(r"all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all|collective-broadcast")
CALLS_COLLECTIVE = re.compile(r"calls=%?(?:" + COLLECTIVE.pattern + ")")  # a collective the compiler runs as a fusion
TRANSFER = re.compile(r"^(?:copy-start|copy-done|infeed|outfeed|send|send-done|recv|recv-done)$")
HOST_SPAN = re.compile(r"^[a-z][a-z0-9_]*(/[a-z0-9_]+)+$")  # the program's spans (docs/OBSERVABILITY.md, "Span convention")
QUANTUM_MARK = "profile/quantum"
LONG_IDLE_S = 0.010       # an idle stretch of the first device this long is worth a name: the host's events over it are kept
TOP_HOST_EVENTS = 5

_DTYPE_BYTES = {"float32": 4, "f32": 4, "float64": 8, "f64": 8,
                "bfloat16": 2, "bf16": 2, "float16": 2, "f16": 2,
                "int8": 1, "uint8": 1, "int16": 2, "uint16": 2,
                "int32": 4, "uint32": 4, "int64": 8, "uint64": 8,
                "bool": 1}


# --------------------------------------------------------------- trace IO
def find_xplane(root: str) -> Optional[str]:
    """The newest ``.xplane.pb`` under a profiler output dir: jax lands it at
    ``<root>/plugins/profile/<timestamp>/<host>.xplane.pb``."""
    found: List[str] = []
    for dirpath, _dirs, files in os.walk(root):
        found += [os.path.join(dirpath, fn) for fn in files if fn.endswith(".xplane.pb")]
    return sorted(found)[-1] if found else None


def _device_planes(trace: Dict) -> List[Dict]:
    """The device planes of a trace in the plain form, by chip number."""
    return [p for _, p in sorted((int(DEVICE_PLANE.match(p["name"]).group(1)), p) for p in trace.get("planes", [])
                                 if DEVICE_PLANE.match(p["name"]))]


def _first_device_ops(trace: Dict) -> List[list]:
    return [ev for plane in _device_planes(trace)[:1] for line in plane["lines"] if line["name"] == OPS_LINE for ev in line["events"]]


def idle_intervals(trace: Dict, at_least_ns: float = 0.0) -> List[Tuple[float, float]]:
    """The first device's idle stretches between its first and its last
    operation, in the trace's nanoseconds."""
    ops = _first_device_ops(trace)
    if not ops:
        return []
    busy = _merge([(s, s + d) for _, s, d, *_ in ops])
    return [(lo, hi) for lo, hi in _subtract([(busy[0][0], busy[-1][1])], busy) if hi - lo >= at_least_ns]


def load_xplane(path: str, runtime_over_idle: bool = True) -> Dict:
    """An ``.xplane.pb`` as plain data, ``{"planes": [{"name", "lines":
    [{"name", "events": [[name, start_ns, dur_ns, {stat: value}], ...]}]}]}``:
    every line of a device plane, and of the host's threads the events named
    as the program's spans are, and every other event (the runtime's own
    threads) that lies over an idle stretch of ``LONG_IDLE_S`` of the first
    device: what a stall's name is read from, and nothing in a clean trace
    (``runtime_over_idle=False``: the spans alone; set-up is idle stretches
    from end to end and the compiler's threads are not what it is read for)."""
    from jax.profiler import ProfileData

    planes, hosts = [], []
    for plane in ProfileData.from_file(path).planes:
        if DEVICE_PLANE.match(plane.name):  # no statistic is read
            lines = [{"name": line.name, "events": [[ev.name, float(ev.start_ns), float(ev.duration_ns), {}] for ev in line.events]}
                     for line in plane.lines]
            planes.append({"name": plane.name, "lines": [line for line in lines if line["events"]]})
        elif plane.name.startswith("/host:"):
            hosts.append(plane)
    idle = idle_intervals({"planes": planes}, LONG_IDLE_S * 1e9) if runtime_over_idle else []
    for plane in hosts:
        lines = []
        for line in plane.lines:
            events = [[ev.name, float(ev.start_ns), float(ev.duration_ns), {}] for ev in line.events
                      if HOST_SPAN.match(ev.name) or any(ev.start_ns < hi and ev.start_ns + ev.duration_ns > lo for lo, hi in idle)]
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


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
def parse_op(name: str) -> Tuple[str, str]:
    """(own name, opcode) of an operation's event name; a name that is not
    an HLO line is its own name, with no opcode."""
    m = HLO.match(name)
    return (m.group("own"), m.group("opcode")) if m else (name.lstrip("%"), "")


def _classify(name: str) -> str:
    own, opcode = parse_op(name)
    if COLLECTIVE.search(opcode or own) or (opcode == "fusion" and CALLS_COLLECTIVE.search(name)):
        return "collective"
    if TRANSFER.match(opcode or re.sub(r"[.][0-9]+$", "", own)):
        return "transfer"
    return "compute"


def self_times(events: List[list]) -> List[Tuple[str, float, float, float]]:
    """(name, start, end, self) per event of one line, where an event's self
    time is its duration less what the events nested in it cover (a ``while``
    and a ``conditional`` enclose their bodies' operations on the same line)."""
    out, stack = [], []  # stack: indices into out of the events still open
    for name, start, dur, *_ in sorted(events, key=lambda e: (e[1], -e[2])):
        end = start + dur
        while stack and out[stack[-1]][2] <= start:
            stack.pop()
        if stack:
            parent = out[stack[-1]]
            parent[3] -= min(end, parent[2]) - start
        out.append([name, start, end, dur])
        stack.append(len(out) - 1)
    return [(n, s, e, max(own, 0.0)) for n, s, e, own in out]


def parse_trace_events(trace: Dict) -> Dict:
    """Normalise a trace in the plain form (``load_xplane``) into categorised
    events with window-relative times in seconds, for the waterfall.

    The device lane is the FIRST ``/device:TPU:<n>`` plane (every chip of a
    mesh runs the same program; ``region_times`` is the mean over all). Its
    ``XLA Ops`` are compute / collective / transfer by opcode, or
    ``enclosing`` where others are nested in them (a ``while``, a
    ``conditional``: busy time, and no category's, since their bodies'
    operations are events of their own); its ``Async XLA Ops`` are the start-to-done
    spans of collectives and copies (``lane`` ``async``: they count as
    collective or transfer time, never as busy); whole programs (``XLA
    Modules``) are ``module`` and the plane's other lines ``other``.
    Everything on ``/host:*`` is ``host``."""
    devices = _device_planes(trace)
    found: List[Tuple[str, str, str, float, float]] = []  # (name, cat, lane, start_ns, dur_ns)
    for plane in trace.get("planes", []):
        if plane["name"].startswith("/host:"):
            found += [(n, "host", line["name"], s, d) for line in plane["lines"] for n, s, d, *_ in line["events"]]
    for line in (devices[0]["lines"] if devices else []):
        if line["name"] == OPS_LINE:
            found += [(parse_op(n)[0], _classify(n) if own >= 0.999 * (e - s) else "enclosing", "ops", s, e - s)
                      for n, s, e, own in self_times(line["events"])]
        elif line["name"] == ASYNC_LINE:
            found += [(parse_op(n)[0], _classify(n), "async", s, d) for n, s, d, *_ in line["events"] if _classify(n) != "compute"]
        else:
            found += [(n, "module" if line["name"] == MODULES_LINE else "other", line["name"], s, d)
                      for n, s, d, *_ in line["events"]]
    if not found:
        return {"t0_ns": 0.0, "span_s": 0.0, "events": []}
    t0 = min(s for *_, s, _ in found)
    events = [{"name": n, "cat": cat, "lane": lane, "start_s": (s - t0) / 1e9, "dur_s": max(0.0, d / 1e9)}
              for n, cat, lane, s, d in found]
    return {"t0_ns": t0, "span_s": max(e["start_s"] + e["dur_s"] for e in events), "events": events}


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
    held = []  # what holds the operation lane: every operation, the enclosing ones too, and no start-to-done span
    for e in events:
        cat = e["cat"]
        if cat in by_cat:
            by_cat[cat].append((e["start_s"], e["start_s"] + e["dur_s"]))
        if e.get("lane") == "ops":
            held.append((e["start_s"], e["start_s"] + e["dur_s"]))
        if cat == "compute":
            prog_time[e["name"]] = prog_time.get(e["name"], 0.0) + e["dur_s"]
    comp_u = _merge(by_cat["compute"])
    coll_u = _merge(by_cat["collective"])
    tran_u = _merge(by_cat["transfer"])
    busy_u = _merge(held)
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
    # a collective is one operation: its start-to-done span, or itself where it is synchronous; not its ``-done`` too
    n_coll_events = sum(1 for e in events if e["cat"] == "collective" and "-done" not in e["name"])
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


# ----------------------------------------------------------- region card
_SKIP = frozenset(("parameter", "constant", "tuple", "get-tuple-element", "bitcast"))  # hold no time and no name of their own
_OPAQUE = frozenset(("parameter", "constant", "tuple"))  # say nothing of where a value came from: several values, or none
_CALLED = re.compile(r"(?:calls|to_apply|body|condition|true_computation|false_computation)=%?([\w.\-]+)")
_CALLED_LIST = re.compile(r"(?:branch_computations|called_computations)=\{([^}]*)\}")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_TRANSFORM = re.compile(r"[A-Za-z_0-9.]+\(|\)")  # jvp( transpose( jit( ... and their closing brackets


def regions_of(op_name: str, regions: Iterable[str]) -> Tuple[List[str], str]:
    """(the regions on an ``op_name``'s name stack, outermost first, and the
    phase the transforms in it say: ``tracing.phase_of``), as in
    ``jit(step)/transpose(jvp(block/ffn))/checkpoint/rematted_computation/
    norm/mul``: ``block``, ``norm``; ``recomputed``. ``regions``: the names to
    look for, or ``_finder`` of them."""
    find = regions if callable(regions) else _finder(regions)
    return find("/" + _TRANSFORM.sub("", op_name) + "/"), _phase(op_name)


def _finder(regions: Iterable[str]):
    names = sorted(set(regions), key=lambda r: (-len(r), r))  # the longest first: ``ffn/cond`` before a ``ffn``
    if not names:
        return lambda path: []
    pattern = re.compile("/(" + "|".join(re.escape(r) for r in names) + ")(?=/)")
    return lambda path: pattern.findall(path)


def region_card(hlo_text: str, regions: Optional[Iterable[str]] = None) -> Dict:
    """``{"module": name, "instructions": {own name: entry}}`` from the text of
    a compiled executable (``jitted.lower(*args).compile().as_text()``: the
    metadata of instructions inside fused computations too). An entry:
    ``opcode``, ``region`` (None: nothing says), ``phase``, ``within`` (the enclosing
    regions, outermost first), ``how`` the region was found, and for an
    instruction that calls a computation (a fusion, an asynchronous wrapper)
    ``members``: {region: instructions of it inside}; ``mixed`` where those
    span several. How: an instruction's own ``op_name`` (``own``: the
    INNERMOST region on it); a fusion's product where it holds one, else its
    root (``members``: the compiler names a fusion after one of the
    instructions in it, so its own ``op_name`` decides nothing); and what the
    compiler made itself and left without a name (layout copies and their
    waits, converts, the zeros a conditional's branch writes for the other's
    residuals) takes the producer's of its first operand that has one
    (``operand``), else that of the ``while`` or ``conditional`` whose body it
    lies in (``caller``), else its first user's (``user``); never through a
    tuple or a parameter, which gather unrelated values. ``regions``: the names
    to look for (default: every name ``region()`` was given in this process)."""
    if regions is None:
        from .tracing import regions_seen

        regions = regions_seen()
    regions = sorted(set(regions))
    find, seen = _finder(regions), {}
    module = re.search(r"^HloModule\s+([^\s,]+)", hlo_text, re.M)
    computations: Dict[str, List[str]] = {}
    insts: Dict[str, Dict] = {}
    current = None
    for line in hlo_text.splitlines():
        if line and not line[0].isspace():
            head = re.match(r"(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$", line)
            current = head.group(1) if head else None
            if current is not None:
                computations[current] = []
            continue
        m = HLO.match(line)
        if m is None or current is None:
            continue
        own, opcode = m.group("own"), m.group("opcode")
        body = line[m.end():]
        called = _CALLED.findall(body) + [c.strip().lstrip("%") for lst in _CALLED_LIST.findall(body) for c in lst.split(",")]
        op_name = _OP_NAME.search(body)
        insts[own] = {"opcode": opcode, "op_name": op_name.group(1) if op_name else "", "root": line.lstrip().startswith("ROOT "),
                      "in": current, "bodies": called if opcode in ("while", "conditional", "call") else [],
                      "operands": [o for o in re.findall(r"%([\w.\-]+)", body.split(", metadata=")[0]) if o not in called],
                      "calls": called if opcode not in ("while", "conditional") else []}
        computations[current].append(own)
    users: Dict[str, List[str]] = {}
    callers: Dict[str, str] = {}  # a ``while``'s or ``conditional``'s computation -> the instruction that runs it
    for own, inst in insts.items():
        for operand in inst["operands"]:
            users.setdefault(operand, []).append(own)
        for body in inst["bodies"]:
            callers[body] = own

    def named(own):
        op_name = insts[own]["op_name"]
        if op_name not in seen:  # a few thousand distinct names among a hundred thousand instructions
            names, phase = regions_of(op_name, find)
            seen[op_name] = (names[-1], phase, names[:-1]) if names else None
        return seen[op_name]

    card: Dict[str, Dict] = {}

    def resolve(own, via=()):
        if own in card:
            return card[own]
        inst = insts[own]
        entry = {"opcode": inst["opcode"], "region": None, "phase": _phase(inst["op_name"]), "within": [], "how": None}
        inside = [i for comp in inst["calls"] for i in computations.get(comp, []) if insts[i]["opcode"] not in _SKIP]
        if inside:
            found = [(i, named(i)) for i in inside]
            members: Dict[str, int] = {}
            for _, hit in found:
                if hit:
                    members[hit[0]] = members.get(hit[0], 0) + 1
            lead = ([hit for i, hit in found if hit and insts[i]["opcode"] in ("dot", "convolution")]
                    or [hit for i, hit in found if hit and insts[i]["root"]] or [hit for _, hit in found if hit])
            entry.update(members=members, mixed=len(members) > 1)
            if lead:
                entry.update(region=lead[0][0], phase=lead[0][1], within=lead[0][2], how="members")
        if entry["region"] is None and named(own):
            entry.update(zip(("region", "phase", "within"), named(own)), how="own")
        card[own] = entry
        if entry["region"] is None and len(via) < 64:  # what the compiler made itself
            caller = callers.get(inst["in"])
            for how, around in (("operand", inst["operands"]), ("caller", [caller] if caller else []), ("user", users.get(own, []))):
                for other in around:
                    if other in insts and other not in via and insts[other]["opcode"] not in _OPAQUE:
                        hit = resolve(other, via + (own,))
                        if hit["region"] is not None:
                            entry.update(region=hit["region"], phase=hit["phase"], within=hit["within"], how=how)
                            return entry
        return entry

    for own in insts:
        if insts[own]["opcode"] not in _SKIP:
            resolve(own)
    return {"module": module.group(1) if module else "", "regions": regions,
            "instructions": {own: entry for own, entry in card.items() if insts[own]["opcode"] not in _SKIP}}


UNATTRIBUTED = "unattributed"
CACHE_NOTE = ("nearly nothing in this executable carries a region: it was fetched from a persistent compile cache written "
              "before the names existed (JAX leaves metadata out of the cache's key). Capture again from an empty cache "
              "(another JAX_COMPILATION_CACHE_DIR).")


def region_times(trace: Dict, card: Dict) -> Dict:
    """Device self seconds A STEP by (region, phase), from a trace in the
    plain form and the ``region_card`` of the program it ran.

    Only the operations inside the intervals of that program's ``XLA
    Modules`` events count (instruction names repeat across programs); self
    time by nesting; a step is one execution of the module; mean over
    devices. ``step_module_s`` is the program's own event, ``step_period_s``
    the time from a step's start to the next one's (the step's period while
    the capture runs). ``table`` {region: {phase: seconds}} sums to ``step_self_s``;
    what the card gives no region is ``unattributed``. A fusion that spans
    several regions is in the table under its leading one AND in ``mixed``:
    ``mixed_s`` in all and the longest with their members. ``unattributed``:
    the longest of what has no region, by own name and opcode.
    ``compiler_made``: the longest of what the compiler made itself and left
    unnamed (layout copies and their waits, a conditional's zeros), by opcode
    and the region the card gave them. ``kernels``: the
    Pallas calls by name. ``found``: seconds by how the card found the region
    (``own``, ``members``, ``operand``, ``user``)."""
    module, insts = card.get("module", ""), card.get("instructions", {})
    table: Dict[str, Dict[str, float]] = {}
    within: Dict[str, Dict[str, float]] = {}
    found: Dict[str, float] = {}
    kernels: Dict[str, float] = {}
    nameless: Dict[str, float] = {}
    inherited: Dict[str, float] = {}
    mixed: Dict[str, list] = {}
    per_device = []  # (executions, self seconds, module seconds)
    for plane in trace.get("planes", []):
        if not DEVICE_PLANE.match(plane["name"]):
            continue
        lines = {line["name"]: line["events"] for line in plane["lines"]}
        runs = sorted((s, s + d) for n, s, d, *_ in lines.get(MODULES_LINE, []) if n.split("(")[0] == module)
        if not runs:
            continue
        total, k = 0.0, 0
        for name, s, e, own_ns in self_times(lines.get(OPS_LINE, [])):
            while k < len(runs) and runs[k][1] <= s:
                k += 1
            if k == len(runs) or s < runs[k][0]:
                continue
            own, opcode = parse_op(name)
            entry = insts.get(own) or {}
            region, phase, sec = entry.get("region") or UNATTRIBUTED, entry.get("phase") or "update", own_ns / 1e9
            total += sec
            row = table.setdefault(region, {})
            row[phase] = row.get(phase, 0.0) + sec
            found[entry.get("how") or "none"] = found.get(entry.get("how") or "none", 0.0) + sec
            for outer in dict.fromkeys(entry.get("within") or ()):
                if outer != region:
                    inner = within.setdefault(outer, {})
                    inner[region] = inner.get(region, 0.0) + sec
            label = re.sub(r"[.][0-9]+$", "", own)
            if region == UNATTRIBUTED:
                nameless[f"{label} {opcode}"] = nameless.get(f"{label} {opcode}", 0.0) + sec
            elif entry.get("how") in ("operand", "caller", "user"):
                inherited[f"{opcode} -> {region}/{phase}"] = inherited.get(f"{opcode} -> {region}/{phase}", 0.0) + sec
            if entry.get("mixed"):
                key = f"{label} {region}/{phase} " + ",".join(f"{r}:{n}" for r, n in sorted(entry["members"].items()))
                slot = mixed.setdefault(key, [0.0, label, region, phase, entry["members"]])
                slot[0] += sec
            if opcode == "custom-call" and "tpu_custom_call" in name:
                kernels[label] = kernels.get(label, 0.0) + sec
        per_device.append((len(runs), total, sum(e - s for s, e in runs) / 1e9,
                           (runs[-1][0] - runs[0][0]) / 1e9 / (len(runs) - 1) if len(runs) > 1 else 0.0))
    if not per_device:
        return {"module": module, "devices": 0, "steps": 0, "error": f"no execution of {module!r} in the trace"}
    steps = per_device[0][0]
    scale = 1.0 / sum(n for n, *_ in per_device)  # mean over devices of seconds a step
    step_self_s = sum(t for _, t, *_ in per_device) * scale
    per_step = lambda d: {k: round(v * scale, 9) for k, v in sorted(d.items())}
    unattributed_s = sum(table.get(UNATTRIBUTED, {}).values()) * scale
    mixed_rows = sorted(mixed.values(), key=lambda row: -row[0])
    out = {
        "module": module, "devices": len(per_device), "steps": steps,
        "step_self_s": round(step_self_s, 9), "step_module_s": round(sum(m for _, _, m, _ in per_device) * scale, 9),
        "step_period_s": round(sum(p for *_, p in per_device) / len(per_device), 9),  # from a step's start to the next one's
        "table": {region: per_step(row) for region, row in sorted(table.items())},
        "within": {outer: per_step(row) for outer, row in sorted(within.items())},
        "unattributed_s": round(unattributed_s, 9),
        "unattributed_share": round(unattributed_s / step_self_s, 6) if step_self_s else 0.0,
        "unattributed": per_step(dict(sorted(nameless.items(), key=lambda kv: -kv[1])[:TOP_MIXED])),
        "compiler_made": per_step(dict(sorted(inherited.items(), key=lambda kv: -kv[1])[:2 * TOP_MIXED])),
        "mixed_s": round(sum(row[0] for row in mixed_rows) * scale, 9),
        "mixed_share": round(sum(row[0] for row in mixed_rows) * scale / step_self_s, 6) if step_self_s else 0.0,
        "mixed": [{"fusion": label, "region": region, "phase": phase, "s": round(sec * scale, 9), "members": members}
                  for sec, label, region, phase, members in mixed_rows[:TOP_MIXED]],
        "kernels": per_step(kernels), "found": per_step(found),
    }
    if step_self_s and unattributed_s > 0.5 * step_self_s:
        out["note"] = CACHE_NOTE
    return out


def idle_by_span(trace: Dict, within: Optional[Tuple[float, float]] = None) -> Dict[str, float]:
    """The first device's idle seconds between its first and its last
    operation (``within``: inside that stretch of the trace's nanoseconds
    alone), by the innermost of the program's host spans or phases they lie
    under (``train/forward/dispatch``: the host had not yet enqueued the
    step), else ``between spans``: the spans are ``TraceAnnotation``s on the
    profiler's clock, the device's own."""
    idle = idle_intervals(trace)
    if within is not None:
        idle = _clip(idle, *within)
    spans = sorted(((n, s, s + d) for p in trace["planes"] if p["name"].startswith("/host:") for line in p["lines"]
                    for n, s, d, *_ in line["events"] if n != QUANTUM_MARK and HOST_SPAN.match(n)),
                   key=lambda sp: sp[2] - sp[1])  # shortest first
    out: Dict[str, float] = {}
    covered: List[Tuple[float, float]] = []
    for name, s, e in spans:
        inside = _total(_subtract(_clip(idle, s, e), covered))  # what the spans nested in it leave of it
        covered = _merge(covered + [(s, e)])
        if inside > 0:
            out[name] = out.get(name, 0.0) + inside / 1e9
    rest = _total(_subtract(idle, covered))
    if rest > 0:
        out["between spans"] = rest / 1e9
    return {k: round(v, 9) for k, v in sorted(out.items(), key=lambda kv: -kv[1])}


def module_executions(trace: Dict) -> List[list]:
    """The first device's programs (``XLA Modules``) in the order they first
    ran: [name, executions, when the first began (seconds from the device's
    first program), the first execution's seconds, the median of the later
    ones' or None]. A step whose first execution is long beside its later ones
    paid something once on the device (a set-up capture's question)."""
    runs: Dict[str, List[Tuple[float, float]]] = {}
    for plane in _device_planes(trace)[:1]:
        for line in plane["lines"]:
            if line["name"] == MODULES_LINE:
                for name, start, dur, *_ in sorted(line["events"], key=lambda ev: ev[1]):
                    runs.setdefault(name, []).append((start, dur))
    t0 = min((r[0][0] for r in runs.values()), default=0.0)
    return [[name[:120], len(r), round((r[0][0] - t0) / 1e9, 6), round(r[0][1] / 1e9, 6),
             round(statistics.median(d for _, d in r[1:]) / 1e9, 6) if len(r) > 1 else None]
            for name, r in sorted(runs.items(), key=lambda kv: kv[1][0][0])]


def stalled_quantum(periods: List[float]) -> Optional[Tuple[int, float]]:
    """(index, median) of the longest of ``periods`` that exceeds ``STALL_X``
    medians, the first and the last apart (a capture's own waits for the
    device lengthen them), or None."""
    judged = periods[1:-1]
    if len(judged) < 3:
        return None
    median = statistics.median(judged)
    worst = max(range(1, len(periods) - 1), key=lambda i: periods[i])
    return (worst, median) if periods[worst] > STALL_X * median else None


def stall_section(trace: Dict, bounds_ns: List[float], quantum: int, median_s: float) -> Dict:
    """A stalled quantum's name, as far as the trace has one (``bounds_ns``:
    the quanta's edges on the trace's clock). Its period; the first device's
    busy and idle seconds in it; ``idle``: for each idle stretch of
    ``LONG_IDLE_S`` or more, when it began (seconds into the quantum), how
    long it was, ``under`` (``idle_by_span`` cut to it), ``quiet_s`` (the part
    of it in which NO host thread was in an event that is no span of the
    program's) and ``host``: the ``TOP_HOST_EVENTS`` such events, of any
    thread, with the most overlap, as [line, event, overlap seconds];
    ``long_ops``: [name, seconds, median] of the operations one execution of
    which took over ``STALL_X`` of the same instruction's median in the other
    quanta (a kernel or a copy that ran long), the largest excess first."""
    lo, hi = bounds_ns[quantum], bounds_ns[quantum + 1]
    idle = _clip(idle_intervals(trace), lo, hi)
    hosts = [(line["name"], ev) for p in trace["planes"] if p["name"].startswith("/host:") for line in p["lines"]
             for ev in line["events"] if not HOST_SPAN.match(ev[0])]
    stretches = []
    for a, b in idle:
        if b - a < LONG_IDLE_S * 1e9:
            continue
        over: Dict[Tuple[str, str], float] = {}
        ran = []
        for line, (name, s, d, *_) in hosts:
            if s < b and s + d > a:
                over[(line, name)] = over.get((line, name), 0.0) + min(b, s + d) - max(a, s)
                ran.append((max(a, s), min(b, s + d)))
        stretches.append({"start_s": round((a - lo) / 1e9, 6), "dur_s": round((b - a) / 1e9, 6), "under": idle_by_span(trace, (a, b)),
                          "quiet_s": round((b - a - _total(_merge(ran))) / 1e9, 6),  # no thread of the process was in any event of its own
                          "host": [[line, name[:120], round(ns / 1e9, 6)]
                                   for (line, name), ns in sorted(over.items(), key=lambda kv: -kv[1])[:TOP_HOST_EVENTS]]})
    here: Dict[str, List[float]] = {}
    usual: Dict[str, List[float]] = {}
    for name, s, _e, own in self_times(_first_device_ops(trace)):  # an execution is judged against the same instruction's in the other quanta
        (here if lo <= s < hi else usual).setdefault(parse_op(name)[0], []).append(own / 1e9)
    long_ops = [[label, round(max(secs), 6), round(median, 6)] for label, secs in here.items()
                for median in [statistics.median(usual.get(label) or secs)] if max(secs) > STALL_X * median and max(secs) - median >= 1e-3]
    idle_s = _total(idle) / 1e9
    return {"quantum": quantum, "period_s": round((hi - lo) / 1e9, 6), "median_s": round(median_s, 6),
            "device_busy_s": round((hi - lo) / 1e9 - idle_s, 6), "device_idle_s": round(idle_s, 6), "idle": stretches,
            "long_ops": sorted(long_ops, key=lambda row: row[2] - row[1])[:TOP_PROGRAMS]}


def summarize_trace_dir(trace_dir: str,
                        window_s: Optional[float] = None) -> Dict:
    """Parse a raw profiler output directory (e.g. a flight capture's
    ``profile/``) into a single-window waterfall summary."""
    path = find_xplane(trace_dir)
    if path is None:
        return {"schema": SUMMARY_SCHEMA, "trace": "unavailable",
                "error": f"no .xplane.pb under {trace_dir}"}
    try:
        summary = build_waterfall(parse_trace_events(load_xplane(path)),
                                  markers=[], window_s=window_s)
        summary["trace"] = "ok"
        summary["trace_file"] = os.path.basename(path)
        return summary
    except Exception as e:  # a corrupt trace must not kill the caller
        return {"schema": SUMMARY_SCHEMA, "trace": "unavailable",
                "error": f"{type(e).__name__}: {e}"}


# ----------------------------------------------------------- the profiler
class DeviceProfiler:
    """One-shot bounded capture window over serving quanta or training steps.

    States: ``idle`` → ``arm()`` → ``armed`` → first ``note_quantum``
    starts the trace (``tracing``) → after ``quanta_target`` markers the
    trace stops, parses, lands gauges, and the profiler returns to
    ``idle``. ``note_quantum`` in ``idle`` is one attribute compare.
    ``describe(text)`` hands it the program whose regions the capture is to
    be split by: a function that gives the text of the executable that runs,
    called when a capture is reduced and not before. With ``hunt`` a capture
    that holds no stalled quantum is dropped unread and the profiler arms
    itself again; the first that holds one is kept and ends the hunt
    (``hunted``: every capture's cost, kept or dropped). With ``setup`` the
    capture is of set-up: ``begin()`` starts the trace where it is armed (the
    trainer's construction) and the first quantum noted ends it."""

    def __init__(self, out_dir: Optional[str] = None,
                 quanta: Optional[int] = None, hunt: bool = False, setup: bool = False):
        self.out_dir = str(out_dir
                           or knobs.get_str("DS_TPU_PROFILE_DIR", "")
                           or "profile_captures")
        self.setup = setup
        self.quanta_target = 1 if setup else max(1, int(
            quanta if quanta is not None
            else knobs.get_int("DS_TPU_PROFILE_QUANTA")))
        self.state = "idle"
        self.captures = 0
        self.hunt = hunt
        self.hunted: List[Dict] = []
        self._lock = threading.Lock()
        self._markers: List[Dict] = []
        self._host_t0 = self._start_s = 0.0
        self._trace_dir: Optional[str] = None
        self._trace_ok = False
        self._audit_mark = 0
        self._bytes_mark = 0.0
        self._summary: Optional[Dict] = None
        self._program_text: Optional[Callable[[], str]] = None

    def describe(self, program_text: Callable[[], str]) -> None:
        self._program_text = program_text

    # -------------------------------------------------------- jax seams
    # overridable so unit tests can drop a fixture trace instead of
    # depending on a live jax profiler (which is process-global)
    def _start_trace(self, trace_dir: str) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()  # the runtime's own threads, and no Python frames: as the benchmark's tracer
        opts.python_tracer_level, opts.host_tracer_level = 0, 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)

    def _stop_trace(self) -> None:
        import jax
        jax.profiler.stop_trace()

    _now = staticmethod(time.perf_counter)  # the clock of the quanta's stamps

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

    def begin(self) -> None:
        """Start an armed capture now, ahead of any quantum (a capture of set-up)."""
        with self._lock:
            if self.state == "armed":
                self._begin_locked()

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
            with TraceAnnotation(QUANTUM_MARK, index=len(self._markers)):  # the same boundary on the profiler's clock
                rel_s = self._now() - self._host_t0
            self._markers.append({
                "index": len(self._markers), "program": str(program), "rel_s": rel_s,
                "attrs": {k: v for k, v in attrs.items()
                          if isinstance(v, (int, float, str, bool))},
            })
            if len(self._markers) >= self.quanta_target:
                self.state = "stopping"
                finalize = True
        if finalize:
            self._finalize()

    def closes_next(self) -> bool:
        """Whether the next ``note_quantum`` ends the capture: a caller that
        dispatches ahead of the device waits for it first."""
        return self.state == "tracing" and len(self._markers) + 1 >= self.quanta_target

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
        t_start = time.perf_counter()
        if trace_dir is not None:
            try:
                self._start_trace(trace_dir)
                self._trace_ok = True
            except Exception:
                # another trace (flight recorder) may hold the profiler:
                # degrade to a marker-only window
                self._trace_ok = False
        self._start_s = time.perf_counter() - t_start
        from .registry import get_registry
        self._bytes_mark = get_registry().peek(
            "infer_tp_allreduce_bytes_total") or 0.0
        try:
            from ..analysis.comm_audit import get_auditor
            auditor = get_auditor()
            self._audit_mark = len(auditor.entries()) if auditor else 0
        except Exception:
            self._audit_mark = 0
        self._host_t0 = self._now()
        self.state = "tracing"

    def _finalize(self) -> None:
        window_s = self._now() - self._host_t0
        t_stop = time.perf_counter()
        trace_state = "ok" if self._trace_ok else "unavailable"
        if self._trace_ok:
            try:
                self._stop_trace()
            except Exception:
                trace_state = "unavailable"
        t_reduce = time.perf_counter()
        rel = [0.0] + [m["rel_s"] for m in self._markers]
        stalled = stalled_quantum([b - a for a, b in zip(rel, rel[1:])]) if self.hunt else None
        if self.hunt:
            self.hunted.append({"capture": self.captures, "kept": stalled is not None, "start": round(self._start_s, 6),
                                "stop": round(t_reduce - t_stop, 6), "window_s": round(window_s, 6)})
            del self.hunted[:-256]
            if stalled is None:  # nothing in it: the raw trace goes unread, and the hunt goes on
                shutil.rmtree(self._trace_dir or "", ignore_errors=True)
                self.hunted[-1]["drop"] = round(time.perf_counter() - t_reduce, 6)
                logger.info(f"profile hunt: capture {self.captures} of {len(self._markers)} quanta held no stall and was dropped: {self.hunted[-1]}")
                from .registry import get_registry
                get_registry().counter("profile_captures_dropped_total").inc()
                with self._lock:
                    self._summary = {"schema": SUMMARY_SCHEMA, "trace": "dropped", "hunted": self.hunted}
                    self.captures += 1
                    self.state = "idle"
                self.arm()
                return
            self.hunt = False
        trace = parsed = None
        path = find_xplane(self._trace_dir) if trace_state == "ok" and self._trace_dir else None
        if path is not None:
            try:
                trace = load_xplane(path, runtime_over_idle=not self.setup)
                parsed = parse_trace_events(trace)
                self._markers_on_the_trace_clock(trace, parsed)
            except Exception:
                trace = parsed = None
        if parsed is None:
            trace_state = "unavailable"
        summary = build_waterfall(parsed, self._markers,
                                  window_s=window_s,
                                  ledger=self._ledger_delta())
        summary["trace"] = trace_state
        summary["trace_dir"] = self._trace_dir
        summary["quanta_target"] = self.quanta_target
        if trace is not None:
            summary["idle_by_span"] = idle_by_span(trace)
            if stalled is not None:
                bounds = [parsed["t0_ns"] + m["rel_s"] * 1e9 for m in [{"rel_s": 0.0}] + self._markers]
                summary["stall"] = stall_section(trace, bounds, *stalled)
            if self.setup:
                summary["setup"] = {"programs": module_executions(trace)}
            elif self._program_text is not None:
                try:  # the text of the executable that ran, asked for now and not at set-up; kept beside the raw trace
                    text = self._program_text()
                    with open(os.path.join(self._trace_dir, "program.hlo.txt"), "w") as f:
                        f.write(text)
                    summary["regions"] = region_times(trace, region_card(text))
                except Exception as e:
                    summary["regions"] = {"error": f"{type(e).__name__}: {e}"}
        # what the capture cost, beside the step's period inside it (the quanta's ``dur_s``): seconds to start the
        # trace, to stop it, and to read and reduce what it left
        summary["capture_cost_s"] = {"start": round(self._start_s, 6), "stop": round(t_reduce - t_stop, 6),
                                     "reduce": round(time.perf_counter() - t_reduce, 6)}
        if self.hunted:
            summary["hunted"] = self.hunted
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

    def _markers_on_the_trace_clock(self, trace: Dict, parsed: Dict) -> None:
        """The quanta's boundaries as the trace has them (``profile/quantum``
        annotations, on the clock the device's events are on), where it has
        them all; else the host's stamps stay."""
        marks = sorted(s for p in trace["planes"] if p["name"].startswith("/host:") for line in p["lines"]
                       for n, s, *_ in line["events"] if n == QUANTUM_MARK)
        if len(marks) == len(self._markers):
            for marker, ns in zip(self._markers, marks):
                marker["rel_s"] = max(0.0, (ns - parsed["t0_ns"]) / 1e9)

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
    read; set, it creates the singleton and arms the one-shot
    capture (only if it has never fired — a finished capture is not
    re-armed by the next engine build; ``request_capture`` re-arms).
    ``DS_TPU_PROFILE=stall``: the hunt; ``setup``: the capture starts here,
    at the trainer's construction (``DeviceProfiler``)."""
    global _PROFILER
    word = (knobs.get_str("DS_TPU_PROFILE") or "").strip().lower()
    hunt, setup = word == "stall", word == "setup"
    if not hunt and not setup and not knobs.get_bool("DS_TPU_PROFILE"):
        return _PROFILER
    with _PROFILER_LOCK:
        if _PROFILER is None:
            _PROFILER = DeviceProfiler(hunt=hunt, setup=setup)
            if not setup:  # a capture of set-up reads no region, and must find the cache the run would have found
                # JAX leaves metadata out of the persistent compile cache's key, so a cache written by another tree can hand
                # back an executable whose instructions carry other names or none; a process that is to read regions off its
                # executables keys the cache on them (one compile the first time, a fetch after)
                import jax

                jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    if _PROFILER.captures == 0 and _PROFILER.state == "idle":
        _PROFILER.arm()
        if _PROFILER.setup:
            _PROFILER.begin()
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
