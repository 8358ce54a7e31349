"""Reduction of a JAX profiler trace to the numbers the per-layer metrics read.

The interval arithmetic (``merge``, ``clip``, ``subtract``) is a copy of
``deepspeed_tpu/telemetry/profiler.py``'s; the lanes are found here, from what a
real TPU v5e trace holds (PERF.md, Findings): one plane per chip named
``/device:TPU:<n>``, on it a line of whole programs (``XLA Modules``) and a line
of single operations (``XLA Ops``), and the host's threads on ``/host:CPU``.

A trace is handled as plain data, so that a small recorded one can be kept with
the tests: ``{"planes": [{"name", "lines": [{"name", "events": [[name, start_ns,
dur_ns, {stat: value}], ...]}]}]}``.
"""

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

Interval = Tuple[float, float]

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"  # start-to-done spans of asynchronous copies and collectives
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(r"all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all|collective-broadcast")
# an operation's event is named by its whole HLO line: "%fusion.7 = bf16[8,128]{1,0:T(8,128)} fusion(bf16[...] %p), ..."
HLO = re.compile(r"^%?(?P<own>[^\s=]+) = (?P<type>\(.*?\)|\S+) (?P<opcode>[a-z][a-z0-9\-]*)\(")
# scheduling shells that enclose other operations: their own time is what their children leave
HOST_SPAN_PREFIX = "bench/"
WINDOW_SPAN = "bench/window"


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if min(e, hi) > max(s, lo)]


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Parts of merged ``a`` that merged ``b`` does not cover."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def total(intervals: Iterable[Interval]) -> float:
    return float(sum(e - s for s, e in intervals))


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def load_xplane(path: str, keep_line=None) -> Dict:
    """Read an ``.xplane.pb`` with nothing but JAX into the plain form."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            if keep_line is not None and not keep_line(plane.name, line.name):
                continue
            events = []
            host = plane.name.startswith("/host:")
            for ev in line.events:
                if keep_line is not None and host and not ev.name.startswith(HOST_SPAN_PREFIX):
                    continue
                stats = {}
                for k, v in ev.stats:
                    if isinstance(v, (int, float, str)):
                        stats[str(k)] = v
                events.append([ev.name, float(ev.start_ns), float(ev.duration_ns), stats])
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def keep_for_metrics(plane: str, line: str) -> bool:
    """The lines the reduction reads: device programs and operations, and
    every host thread (the driver's spans are found by name)."""
    if DEVICE_PLANE.match(plane):
        return line in (OPS_LINE, ASYNC_LINE, MODULES_LINE)
    return plane.startswith("/host:")


def parse_op(name: str) -> Tuple[str, str, str]:
    """(own name, opcode, result type) of an operation's event name; a name
    that is not an HLO line is its own name, with no opcode."""
    m = HLO.match(name)
    return (m.group("own"), m.group("opcode"), m.group("type")) if m else (name.lstrip("%"), "", "")


def is_collective(name: str) -> bool:
    own, opcode, _ = parse_op(name)
    return bool(COLLECTIVE.search(opcode) or COLLECTIVE.search(own))


def _leaves_and_self(events: List[list]) -> List[Tuple[str, float, float, float, dict]]:
    """(name, start, end, self seconds, stats) per event of one line, where an
    event's self time is its duration less what the events nested in it cover
    (a ``while`` encloses its body's operations on the same line)."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    out, stack = [], []  # stack of [index into out, end]
    for name, start, dur, stats in evs:
        end = start + dur
        while stack and stack[-1][1] <= start:
            stack.pop()
        if stack:
            parent = out[stack[-1][0]]
            parent[3] -= min(end, parent[2]) - start
        out.append([name, start, end, dur, stats])
        stack.append([len(out) - 1, end])
    return [(n, s, e, max(self_ns, 0.0), st) for n, s, e, self_ns, st in out]


def op_label(name: str, stats: dict) -> str:
    """An operation's label: its own name without the instance number, its
    opcode and its result type, so that the sixteen layers' instances of one
    operation fall together; a custom call keeps what its HLO line says of
    its target and kernel."""
    own, opcode, typ = parse_op(name)
    label = f"{re.sub(r'[.][0-9]+$', '', own)} {opcode} {typ[:70]}".strip()
    if opcode == "custom-call":
        label += " " + " ".join(re.findall(r'(?:custom_call_target|kernel_name|op_name)="[^"]*"', name))
    return label


def reduce_trace(trace: Dict, window_ns: Optional[Interval] = None) -> Dict:
    """Everything the metrics read, in seconds.

    ``window_ns`` cuts the trace (default: the driver's ``bench/window`` span,
    else from the first to the last device event). Per device: ``busy_s``; ``ops`` {label: self seconds} ; the leaf
    intervals of collectives and of all other operations; ``modules`` [(name,
    start, end)]. Over devices: ``busy_s`` (mean), ``window_s``, ``idle_share``,
    ``collective_exposed_share`` (mean over devices of the time a collective
    holds the operation lane while nothing else does, over the window). Host:
    ``spans`` [(name, start_s, end_s, stats)] for every event named ``bench/...``.
    Times are relative to the window's start.
    """
    devices, spans = {}, []
    first, last = None, None
    for plane in trace["planes"]:
        m = DEVICE_PLANE.match(plane["name"])
        if m:
            dev = devices.setdefault(int(m.group(1)), {"ops_events": [], "modules": [], "async": []})
            for line in plane["lines"]:
                if line["name"] == ASYNC_LINE:
                    dev["async"] = [(s, s + d) for n, s, d, _ in line["events"] if is_collective(n)]
                elif line["name"] == OPS_LINE:
                    dev["ops_events"] = line["events"]
                    for _, s, d, _ in line["events"]:
                        first = s if first is None else min(first, s)
                        last = s + d if last is None else max(last, s + d)
                elif line["name"] == MODULES_LINE:
                    dev["modules"] = [(n, s, s + d) for n, s, d, _ in line["events"]]
        elif plane["name"].startswith("/host:"):
            for line in plane["lines"]:
                for n, s, d, st in line["events"]:
                    if n.startswith(HOST_SPAN_PREFIX):
                        spans.append((n, s, s + d, st))
    if not devices or first is None:
        return {"devices": {}, "busy_s": 0.0, "window_s": 0.0, "spans": []}
    if window_ns is None:  # the driver's own mark of the window, else all device events
        marks = [(s, e) for n, s, e, _ in spans if n == WINDOW_SPAN]
        window_ns = marks[0] if marks else (first, last)
    lo, hi = window_ns
    sec = lambda ns: ns / 1e9
    out_devices = {}
    for idx, dev in sorted(devices.items()):
        ops: Dict[str, float] = {}
        counts: Dict[str, int] = {}
        coll, comp, every = list(dev["async"]), [], []
        nested = _leaves_and_self(dev["ops_events"])
        for name, s, e, self_ns, stats in nested:
            if e <= lo or s >= hi:
                continue
            every.append((s, e))
            share = (min(e, hi) - max(s, lo)) / (e - s) if e > s else 0.0
            label = op_label(name, stats)
            ops[label] = ops.get(label, 0.0) + sec(self_ns * share)
            counts[label] = counts.get(label, 0) + 1
            if self_ns >= 0.999 * (e - s):  # a leaf: nothing nested in it
                (coll if is_collective(name) else comp).append((s, e))
        busy = clip(merge(every), lo, hi)
        coll_m, comp_m = clip(merge(coll), lo, hi), clip(merge(comp), lo, hi)
        out_devices[idx] = {
            "busy": [(sec(s - lo), sec(e - lo)) for s, e in busy], "busy_s": sec(total(busy)),
            "ops": ops, "op_counts": counts,
            "collective_s": sec(total(coll_m)), "collective_exposed_s": sec(total(subtract(coll_m, comp_m))),
            "modules": [(n, sec(s - lo), sec(e - lo)) for n, s, e in dev["modules"] if e > lo and s < hi],
        }
    window_s = sec(hi - lo)
    n = len(out_devices)
    busy_s = sum(d["busy_s"] for d in out_devices.values()) / n
    return {
        "devices": out_devices, "busy_s": busy_s, "window_s": window_s,
        "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
        "collective_exposed_share": sum(d["collective_exposed_s"] for d in out_devices.values()) / n / window_s
        if window_s > 0 else None,
        "spans": sorted(((n_, sec(s - lo), sec(e - lo), st) for n_, s, e, st in spans
                         if e > lo and s < hi and n_ != WINDOW_SPAN), key=lambda sp: sp[:3]),
    }


def ops_matching(reduced: Dict, pattern: str) -> Tuple[float, int]:
    """(self seconds, calls) of the operations whose label matches, summed
    over devices."""
    rx = re.compile(pattern)
    secs = calls = 0
    for dev in reduced["devices"].values():
        for label, s in dev["ops"].items():
            if rx.search(label):
                secs += s
                calls += dev["op_counts"][label]
    return secs, calls


def busy_inside(reduced: Dict, lo_s: float, hi_s: float) -> float:
    """Device-busy seconds inside [lo_s, hi_s), mean over devices."""
    devs = reduced["devices"].values()
    return sum(total(clip(d["busy"], lo_s, hi_s)) for d in devs) / max(len(devs), 1)


def breakdown(reduced: Dict, top: int = 10) -> Dict:
    """The contract's ``breakdown``: the operations that took most device
    time, and the idle time of the first device by what the host was doing:
    under which driver span it lay (spans of one level: they do not nest), or
    between which two, or waiting for an arrival with no request in flight."""
    ops: Dict[str, float] = {}
    for dev in reduced["devices"].values():
        for label, s in dev["ops"].items():
            key = label[:120]
            ops[key] = ops.get(key, 0.0) + s / len(reduced["devices"])
    device_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
    gaps: Dict[str, float] = {}
    if reduced["devices"]:
        dev = reduced["devices"][min(reduced["devices"])]
        idle = subtract([(0.0, reduced["window_s"])], dev["busy"])
        spans = sorted(reduced["spans"], key=lambda sp: (sp[1], sp[2]))
        covered = merge((sp[1], sp[2]) for sp in spans)
        for sp in spans:  # idle time under a span is the host's time in that call
            inside = total(clip(idle, sp[1], sp[2]))
            if inside > 0:
                name = "in " + span_label(sp)
                gaps[name] = gaps.get(name, 0.0) + inside
        for s_, e_ in subtract(idle, covered):  # the rest lies between two calls
            before = [sp for sp in spans if sp[2] <= s_]
            after = [sp for sp in spans if sp[1] >= e_]
            prev = span_label(max(before, key=lambda sp: sp[2])) if before else "start"
            nxt = span_label(min(after, key=lambda sp: sp[1])) if after else "end"
            if after and str(after and min(after, key=lambda sp: sp[1])[3].get("live_before")) == "0":
                name = f"waiting for an arrival (no request in flight) before {nxt}"
            else:
                name = f"between {prev} and {nxt}"
            gaps[name] = gaps.get(name, 0.0) + (e_ - s_)
    idle_gaps = sorted(gaps.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in device_ops], "idle_gaps": [[k, v] for k, v in idle_gaps]}


def span_label(span) -> str:
    name, _, _, stats = span
    what = stats.get("group") or stats.get("what")  # a coarse label, where the driver gives one
    return f"{name}[{what}]" if what else name
