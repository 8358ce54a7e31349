"""Performance accounting for the serving engine: per-program cost cards,
device-time attribution, a goodput ledger, and roofline classification.

PRs 1 and 4 made serving legible in *time* (spans, TTFT/TPOT); this module
makes it legible in *work*. Every jitted serving program is wrapped (the
same sites ``analysis/jit_audit.py`` audits); the first sighting of an
argument signature builds a **cost card** holding the program's analytic
FLOPs (the jaxpr walker from ``profiling/flops_profiler``) and, at
``DS_TPU_PERF_ACCOUNT=2``, XLA's own cost/memory analysis via an AOT
``lower().compile()`` (the ``runtime/memory_audit.py`` idiom — one extra
compile per signature, paid at warmup only). At run time the engine
attributes each quantum's measured wall window to its card, yielding
achieved FLOP/s and bandwidth, MFU against a declared or auto-detected
peak (``DS_TPU_PEAK_TFLOPS`` / ``DS_TPU_PEAK_GBPS``), and a compute- vs
memory-bound classification per bucket.

Modes (``DS_TPU_PERF_ACCOUNT``):

- ``0`` — off; ``wrap`` returns the function unchanged.
- ``1`` — analytic cards only (default). Card construction is one extra
  *trace* (``jax.make_jaxpr``) per program signature — no XLA compile, so
  steady state stays compile-free even during warmup.
- ``2`` — additionally AOT-compile each new signature for XLA's
  ``cost_analysis()`` (HBM bytes accessed) and ``memory_analysis()``
  (peak temp bytes). Still compile-free after warmup: cards are keyed by
  the same signatures jit keys its trace cache on.

The goodput ledger separates useful work from overhead the bucketing
design knowingly pays: pow2-padding fill (useful vs slot tokens),
speculative tokens rejected by verification, prefill FLOPs saved by the
prefix cache, and COW page-copy traffic.
"""

from __future__ import annotations

import contextlib
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, Iterable, Optional, Tuple

from ..analysis import knobs
from ..utils.logging import logger
from .registry import get_registry
from .tracing import _NULL_SPAN, current_span, open_span, span

__all__ = [
    "CostCard",
    "PerfAccountant",
    "get_perf_accountant",
    "resolve_peaks",
]

# Peak dense-bf16 TFLOP/s and HBM GB/s per chip, by device-kind substring.
# Public spec-sheet numbers; first match wins (match on lowercased kind).
_PEAKS_BY_KIND: Tuple[Tuple[str, Tuple[float, float]], ...] = (
    ("v6e", (918.0, 1640.0)),
    ("v6", (918.0, 1640.0)),
    ("v5p", (459.0, 2765.0)),
    ("v5e", (197.0, 819.0)),
    ("v5 lite", (197.0, 819.0)),
    ("v5litepod", (197.0, 819.0)),
    ("v4", (275.0, 1228.0)),
)


def resolve_peaks() -> Tuple[float, float]:
    """(peak FLOP/s, peak bytes/s) — declared knobs win, else the device
    kind is matched against the spec table. Unknown kinds (CPU included)
    resolve to 0.0, and MFU/roofline readouts degrade to "unknown" rather
    than inventing a peak."""
    tflops = knobs.get_float("DS_TPU_PEAK_TFLOPS")
    gbps = knobs.get_float("DS_TPU_PEAK_GBPS")
    if tflops <= 0.0 or gbps <= 0.0:
        kind = ""
        try:
            import jax

            kind = jax.devices()[0].device_kind.lower()
        except Exception:
            pass
        for sub, (tf, gb) in _PEAKS_BY_KIND:
            if sub in kind:
                if tflops <= 0.0:
                    tflops = tf
                if gbps <= 0.0:
                    gbps = gb
                break
    return (max(0.0, tflops) * 1e12, max(0.0, gbps) * 1e9)


_UNSEEN = object()


@contextlib.contextmanager
def first_call(family: str, bucket: Any, notes: Optional[Dict[str, Any]] = None):
    """The first call of one (program, signature): a ``program/first_call``
    span with ``family``, ``bucket`` and the ``q`` and ``steps`` of the span
    it runs under, closed with the seconds JAX spent by phase inside it (the
    process-wide counters of ``utils/compile_cache.py``, read before and
    after) and one log line of the same. The line is what a run that is cut
    before ``dump_trace`` leaves behind, and what an operator greps for when
    a step recompiles in production. Yields a dict for phases of the
    caller's own (``cost_card``); a part that code under it times by name
    (``open_span("program/first_call").phase("flops_count")``, an annotation
    too) is one more. ``notes`` is the caller's too: what else
    it knows of the program by the time the call is over (the trainer: what
    the step does with gradients); both end up on the span and the line.
    ``programs`` counts what reached the
    backend inside the span, ``block_traces`` the times a transformer
    block's Python body ran (``program_regions_traced_total{region="block"}``:
    one a kind of block and traced program, not one a layer). The regions
    traced inside add ``region_trace_s`` (``telemetry/tracing.py::region``:
    Python seconds by region, on the span and in short on the line).

    As it closes it raises ``program_first_call_seconds_total{family, phase}``
    by the seconds it puts on the span (``trace``, ``lower``, ``compile``,
    ``cache_fetch``, ``other`` and the caller's own: ``flops_count``,
    ``cost_card``), so that over the phases but ``cache_fetch`` a family's
    series sum to its spans' ``total_s``, and the process-wide
    ``program_*_seconds_total`` less every family's are the seconds of programs
    that reached the backend in NO first-call span: the caller's. A first call
    inside another's is part of the outer one's seconds and raises nothing."""
    import jax

    from ..utils.compile_cache import PHASE_COUNTERS, PHASES, block_traces, register_cache_metrics

    register_cache_metrics(jax)
    reg = get_registry()
    up = current_span()
    nested = open_span("program/first_call") is not _NULL_SPAN
    inherited = {k: up.attrs[k] for k in ("q", "steps") if k in up.attrs} if up is not None and up.attrs else {}
    counters = PHASE_COUNTERS + ("program_first_calls_total",)
    before = [reg.peek(c) or 0.0 for c in counters]
    traces_before = block_traces()
    phases: Dict[str, float] = {}
    t0 = time.perf_counter()
    with span("program/first_call", family=family, bucket=bucket, **inherited) as sp:
        yield phases
        total = time.perf_counter() - t0
        *seconds, programs = [(reg.peek(c) or 0.0) - b for c, b in zip(counters, before)]
        phases.update(zip(PHASES, seconds))
        phases.update((sp.attrs or {}).pop("phase_s", {}))  # parts the code under it timed by name (``sp.phase``: the trainer's ``flops_count``)
        # JAX's compile event spans the persistent-cache fetch: "other" is what no phase covers
        phases["other"] = total - sum(v for k, v in phases.items() if k != "cache_fetch")
        # programs: how many reached the backend in here (this one, and helper programs it called first)
        traced = block_traces() - traces_before
        sp.set(programs=int(programs), block_traces=traced, total_s=total,
               **{k + "_s": v for k, v in phases.items()}, **(notes or {}))
        if not nested:
            for phase, took in phases.items():
                reg.counter("program_first_call_seconds_total", family=family, phase=phase).inc(took)
    if sp.attrs is not None:  # the tracer is on
        by_region = sorted(sp.attrs.get("region_trace_s", {}).items(), key=lambda kv: -kv[1])
        # the regions' Python seconds in all and the three dearest: a kernel that traces slowly at many sites shows here
        regions = [f"region_trace_s={sum(v for _, v in by_region):.3f}({','.join(f'{k}:{v:.3f}' for k, v in by_region[:3])})"]
        logger.info("program first call: family=%s bucket=%s %s", family, bucket, " ".join(
            [f"{k}={v}" for k, v in inherited.items()] + [f"total_s={total:.3f}"]
            + [f"{k}_s={v:.3f}" for k, v in phases.items()] + [f"block_traces={traced}"] + (regions if by_region else [])
            + [f"{k}={v}" for k, v in (notes or {}).items()]))


def _aval_bytes(avals: Iterable[Any]) -> int:
    total = 0
    for a in avals:
        size = getattr(a, "size", None)
        dtype = getattr(a, "dtype", None)
        if size is not None and dtype is not None:
            total += int(size) * int(getattr(dtype, "itemsize", 1))
    return total


@dataclass
class CostCard:
    """Static cost model + running attribution for one (program, argument
    signature) bucket — i.e. one XLA executable."""

    program: str
    signature: str
    # -- static, filled once at first sighting --------------------------
    flops: int = 0            # analytic model FLOPs per call (jaxpr walk)
    macs: int = 0
    xla_flops: int = 0        # XLA cost_analysis flops per call (mode 2)
    bytes_accessed: int = 0   # HBM traffic per call (XLA; else arg+out)
    arg_bytes: int = 0
    out_bytes: int = 0
    temp_bytes: int = 0       # XLA peak transient bytes (mode 2)
    source: str = "analytic"  # "analytic" | "xla" | "unavailable"
    meta: Dict[str, Any] = field(default_factory=dict)
    # -- running attribution ---------------------------------------------
    calls: int = 0            # every dispatch through the wrapper
    timed_calls: int = 0      # dispatches whose wall window was attributed
    time_s: float = 0.0       # summed attributed wall time
    useful_tokens: int = 0
    slot_tokens: int = 0

    # ------------------------------------------------------------- derived
    def achieved_flops_per_s(self) -> float:
        return self.flops * self.timed_calls / self.time_s if self.time_s > 0 else 0.0

    def achieved_bytes_per_s(self) -> float:
        return self.bytes_accessed * self.timed_calls / self.time_s if self.time_s > 0 else 0.0

    def intensity(self) -> float:
        """Arithmetic intensity (FLOPs per HBM byte) of the program."""
        return self.flops / self.bytes_accessed if self.bytes_accessed > 0 else 0.0

    def bound(self, peak_flops: float, peak_bw: float) -> str:
        """Roofline classification against the machine balance point."""
        if peak_flops <= 0 or peak_bw <= 0 or self.bytes_accessed <= 0 or self.flops <= 0:
            return "unknown"
        return "compute" if self.intensity() >= peak_flops / peak_bw else "memory"

    def as_dict(self, peak_flops: float = 0.0, peak_bw: float = 0.0) -> Dict[str, Any]:
        d = {
            "program": self.program,
            "signature": self.signature,
            "flops": self.flops,
            "macs": self.macs,
            "xla_flops": self.xla_flops,
            "bytes_accessed": self.bytes_accessed,
            "arg_bytes": self.arg_bytes,
            "out_bytes": self.out_bytes,
            "temp_bytes": self.temp_bytes,
            "source": self.source,
            "meta": dict(self.meta),
            "calls": self.calls,
            "timed_calls": self.timed_calls,
            "time_s": self.time_s,
            "useful_tokens": self.useful_tokens,
            "slot_tokens": self.slot_tokens,
            "achieved_tflops": self.achieved_flops_per_s() / 1e12,
            "achieved_gbps": self.achieved_bytes_per_s() / 1e9,
            "intensity_flops_per_byte": self.intensity(),
            "bound": self.bound(peak_flops, peak_bw),
        }
        if peak_flops > 0:
            d["pct_peak_flops"] = 100.0 * self.achieved_flops_per_s() / peak_flops
        if peak_bw > 0:
            d["pct_peak_bw"] = 100.0 * self.achieved_bytes_per_s() / peak_bw
        return d


class PerfAccountant:
    """Builds cost cards at compile time, attributes wall time at run time.

    Wiring mirrors ``JitAuditor``: the engine wraps the *raw* jitted
    program with ``wrap`` (the auditor, when on, wraps outside, so its
    recompile semantics are untouched). The wrapper derives the same
    abstract argument signature jit keys its trace cache on; a fresh
    signature builds a card, a warm one is a dict hit — steady-state cost
    is one dict lookup plus a ``perf_counter`` stamp.

    Attribution is explicit: the dispatch site calls ``attribute(useful,
    slots)`` after its host-visible boundary (the readback that already
    synchronizes), closing the window the wrapper opened. Programs wrapped
    with ``timed=False`` (the COW page copy, which dispatches *inside*
    another quantum's window) never open a window, so they cannot clobber
    the quantum's attribution.
    """

    def __init__(self, mode: Optional[int] = None, use_telemetry: bool = True):
        if mode is None:
            mode = knobs.get_int("DS_TPU_PERF_ACCOUNT")
        self.mode = int(mode)
        self.enabled = self.mode > 0
        self._lock = threading.Lock()
        self._cards: Dict[Tuple[str, Any], CostCard] = {}
        self._open: Optional[Tuple[CostCard, float]] = None
        self._hbm: Dict[str, Any] = {}
        self._hbm_limit = 0
        # goodput ledger (host-side accumulators)
        self.useful_tokens = 0
        self.slot_tokens = 0
        self.attributed_flops = 0
        self.attributed_time_s = 0.0
        self.spec_proposed = 0
        self.spec_accepted = 0
        self.prefix_hit_tokens = 0
        self.readmit_tokens = 0
        self.cow_bytes = 0
        self._peaks: Optional[Tuple[float, float]] = None
        self._m_flops = self._m_useful = self._m_slot = None
        self._m_goodput = self._m_mfu = None
        self._m_hbm = {}
        if use_telemetry and self.enabled:
            tele = get_registry()
            self._m_flops = tele.counter("infer_model_flops_total")
            self._m_useful = tele.counter("infer_useful_tokens_total")
            self._m_slot = tele.counter("infer_slot_tokens_total")
            self._m_goodput = tele.gauge("infer_goodput_fraction")
            self._m_mfu = tele.gauge("infer_mfu")
            self._m_hbm = {
                "weights": tele.gauge("infer_hbm_weights_bytes"),
                "temp_peak": tele.gauge("infer_hbm_temp_peak_bytes"),
                "kv_pages": tele.gauge("kv_hbm_pages_bytes"),
                "prefix": tele.gauge("kv_hbm_prefix_bytes"),
                "host_spill": tele.gauge("kv_host_spill_bytes"),
                "pressure": tele.gauge("infer_hbm_pressure"),
            }

    # ------------------------------------------------------------ peaks
    def peaks(self) -> Tuple[float, float]:
        if self._peaks is None:
            self._peaks = resolve_peaks()
        return self._peaks

    # ----------------------------------------------------------- wiring
    def wrap(self, name: str, fn, meta: Optional[Dict[str, Any]] = None, timed: bool = True,
             family: Optional[str] = None, bucket: Any = None):
        """Return ``fn`` with cost accounting; identity when disabled.

        ``family`` marks a program of one of the engine's caches: the first
        call of each argument signature through THIS wrapper is then a
        ``program/first_call`` span (``first_call`` below), accounting on or
        off. A program rebuilt after an eviction is a new wrapper and pays,
        and shows, its first calls again."""
        if not self.enabled and family is None:
            return fn
        static_meta = dict(meta or {})
        static_meta.update(getattr(fn, "_cost_meta", None) or {})
        from ..analysis.jit_audit import leaf_signature

        cards: Dict[Any, Optional[CostCard]] = {}  # this wrapper's signatures (None: accounting is off)

        def card_for(sig, args, kwargs, phases):
            if not self.enabled:
                return None
            key = (name, sig)
            card = self._cards.get(key)
            if card is None:
                t0 = time.perf_counter()
                with span("program/cost_card", program=name):
                    card = self._build_card(key, fn, args, kwargs, static_meta)
                phases["cost_card"] = time.perf_counter() - t0
            return card

        def dispatch(card, args, kwargs):
            if card is None:
                return fn(*args, **kwargs)
            t0 = time.perf_counter()
            out = fn(*args, **kwargs)
            with self._lock:
                card.calls += 1
                if timed:
                    # dispatch is async: the window stays open until the
                    # dispatch site's readback, closed by attribute()
                    self._open = (card, t0)
            return out

        def wrapped(*args, **kwargs):
            sig = leaf_signature(args) if not kwargs else (
                leaf_signature(args), leaf_signature(kwargs))
            card = cards.get(sig, _UNSEEN)  # one walk of the signature's hash a call
            if card is not _UNSEEN:
                return dispatch(card, args, kwargs)
            with (first_call(family, bucket) if family is not None else contextlib.nullcontext({})) as phases:
                card = cards[sig] = card_for(sig, args, kwargs, phases)
                return dispatch(card, args, kwargs)

        wrapped.__wrapped__ = fn  # type: ignore[attr-defined]
        wrapped._perf_account_name = name  # type: ignore[attr-defined]
        return wrapped

    def _build_card(self, key: Tuple[str, Any], fn, args, kwargs,
                    meta: Dict[str, Any]) -> CostCard:
        name, sig = key
        card = CostCard(program=name, signature=repr(sig), meta=meta)
        try:
            import jax

            from ..profiling.flops_profiler.profiler import flops_of_jaxpr

            # jax.jit itself sets __wrapped__ (the plain python fn) — only
            # unwrap while the candidate lacks the AOT .lower entry point
            raw = fn
            while not hasattr(raw, "lower") and hasattr(raw, "__wrapped__"):
                raw = raw.__wrapped__
            jaxpr = jax.make_jaxpr(raw)(*args, **kwargs)
            card.flops, card.macs = flops_of_jaxpr(jaxpr)
            card.arg_bytes = _aval_bytes(jaxpr.in_avals)
            card.out_bytes = _aval_bytes(jaxpr.out_avals)
            # analytic lower bound on HBM traffic: read args once, write
            # outputs once; XLA's estimate replaces it in mode 2
            card.bytes_accessed = card.arg_bytes + card.out_bytes
            if self.mode >= 2 and hasattr(raw, "lower"):
                compiled = raw.lower(*args, **kwargs).compile()
                ca = compiled.cost_analysis()
                if isinstance(ca, (list, tuple)):
                    ca = ca[0] if ca else {}
                card.xla_flops = int(ca.get("flops", 0.0) or 0)
                ba = int(ca.get("bytes accessed", 0.0) or 0)
                if ba > 0:
                    card.bytes_accessed = ba
                mem = compiled.memory_analysis()
                card.temp_bytes = int(getattr(mem, "temp_size_in_bytes", 0) or 0)
                card.arg_bytes = int(getattr(mem, "argument_size_in_bytes", card.arg_bytes) or 0)
                card.out_bytes = int(getattr(mem, "output_size_in_bytes", card.out_bytes) or 0)
                card.source = "xla"
        except Exception:
            card.source = "unavailable"
        with self._lock:
            return self._cards.setdefault(key, card)

    # ------------------------------------------------------ attribution
    def attribute(self, useful_tokens: int = 0, slot_tokens: int = 0) -> None:
        """Close the most recent open window: the wall time between the
        wrapped dispatch and this call (the dispatch site's host-visible
        boundary) is attributed to that program's card."""
        if not self.enabled:
            return
        now = time.perf_counter()
        with self._lock:
            opened = self._open
            self._open = None
            if opened is None:
                return
            card, t0 = opened
            dt = max(0.0, now - t0)
            card.timed_calls += 1
            card.time_s += dt
            card.useful_tokens += int(useful_tokens)
            card.slot_tokens += int(slot_tokens)
            self.useful_tokens += int(useful_tokens)
            self.slot_tokens += int(slot_tokens)
            self.attributed_flops += card.flops
            self.attributed_time_s += dt
            flops = card.flops
            goodput = self.useful_tokens / self.slot_tokens if self.slot_tokens else 0.0
        if self._m_flops is not None and flops:
            self._m_flops.inc(flops)
        if self._m_useful is not None and useful_tokens:
            self._m_useful.inc(int(useful_tokens))
        if self._m_slot is not None and slot_tokens:
            self._m_slot.inc(int(slot_tokens))
        if self._m_goodput is not None and self.slot_tokens:
            self._m_goodput.set(goodput)
        peak_flops, _ = self.peaks()
        if self._m_mfu is not None and peak_flops > 0 and dt > 0:
            self._m_mfu.set(flops / dt / peak_flops)

    # --------------------------------------------------- goodput ledger
    def note_spec(self, proposed: int, accepted: int) -> None:
        if not self.enabled:
            return
        with self._lock:
            self.spec_proposed += int(proposed)
            self.spec_accepted += int(accepted)

    def note_prefix_hit(self, tokens: int) -> None:
        if not self.enabled:
            return
        with self._lock:
            self.prefix_hit_tokens += int(tokens)

    def note_readmit(self, tokens: int) -> None:
        """Tokens whose KV returned from the host spill tier via h2d DMA
        instead of a prefill re-run (docs/SERVING.md "Tiered KV economy").
        Priced in the ledger at the prefill-class FLOP rate, like prefix
        hits — the DMA replaced exactly that work."""
        if not self.enabled:
            return
        with self._lock:
            self.readmit_tokens += int(tokens)

    def note_cow(self, n_bytes: int) -> None:
        if not self.enabled:
            return
        with self._lock:
            self.cow_bytes += int(n_bytes)

    # -------------------------------------------------------- HBM pools
    def set_hbm(self, limit: int = 0, **pools: int) -> float:
        """Record per-pool HBM bytes; returns the pressure fraction
        (resident + compiled temp peak over the device limit; 0.0 when no
        limit is known — CPU backends report none)."""
        if not self.enabled:
            return 0.0
        with self._lock:
            for k, v in pools.items():
                self._hbm[k] = int(v)
            if limit:
                self._hbm_limit = int(limit)
            temp = max((c.temp_bytes for c in self._cards.values()), default=0)
            self._hbm["temp_peak"] = temp
            # prefix-held blocks live inside the paged-KV pool: counted
            # once via kv_pages, reported separately as an informational
            # subset
            resident = self._hbm.get("weights", 0) + self._hbm.get("kv_pages", 0) + temp
            pressure = resident / self._hbm_limit if self._hbm_limit > 0 else 0.0
            self._hbm["resident"] = resident
            self._hbm["pressure"] = pressure
        for k, g in self._m_hbm.items():
            if k == "pressure":
                g.set(pressure)
            elif k in self._hbm:
                g.set(self._hbm[k])
        return pressure

    def hbm(self) -> Dict[str, int]:
        with self._lock:
            out = dict(self._hbm)
        out.setdefault("weights", 0)
        out.setdefault("kv_pages", 0)
        out.setdefault("prefix", 0)
        out.setdefault("host_spill", 0)
        out.setdefault("temp_peak", 0)
        out.setdefault("pressure", 0.0)
        if self._hbm_limit:
            out["limit"] = self._hbm_limit
        return out

    # --------------------------------------------------------- readouts
    def cards(self) -> Dict[Tuple[str, Any], CostCard]:
        with self._lock:
            return dict(self._cards)

    def totals(self) -> Dict[str, float]:
        """Cumulative attribution totals — cheap, for windowed deltas
        (a caller subtracts a copy taken before its window)."""
        with self._lock:
            return {
                "flops": float(self.attributed_flops),
                "time_s": self.attributed_time_s,
                "useful_tokens": float(self.useful_tokens),
                "slot_tokens": float(self.slot_tokens),
            }

    def mfu(self, flops: Optional[float] = None, time_s: Optional[float] = None) -> Optional[float]:
        """Model FLOP/s utilization; None when no peak is known."""
        peak_flops, _ = self.peaks()
        if peak_flops <= 0:
            return None
        f = self.attributed_flops if flops is None else flops
        t = self.attributed_time_s if time_s is None else time_s
        if t <= 0:
            return 0.0
        return f / t / peak_flops

    def ledger(self) -> Dict[str, Any]:
        with self._lock:
            cards = list(self._cards.values())
            useful, slot = self.useful_tokens, self.slot_tokens
            proposed, accepted = self.spec_proposed, self.spec_accepted
            prefix_tokens, cow = self.prefix_hit_tokens, self.cow_bytes
            readmit_tokens = self.readmit_tokens
        rejected = max(0, proposed - accepted)
        # wasted verify work: the spec programs' attributed FLOPs scale by
        # the rejected fraction of proposed tokens
        spec_flops = sum(c.flops * c.timed_calls for c in cards
                         if c.program.startswith("spec"))
        rejected_flops = int(spec_flops * rejected / proposed) if proposed else 0
        # saved prefill work: prefix-cache hit tokens never re-run prefill;
        # price them at the prefill-class per-slot-token FLOP rate
        pre_cards = [c for c in cards
                     if c.program.startswith(("prefill", "fused")) and c.slot_tokens > 0]
        pre_flops = sum(c.flops * c.timed_calls for c in pre_cards)
        pre_slots = sum(c.slot_tokens for c in pre_cards)
        saved_flops = int(prefix_tokens * pre_flops / pre_slots) if pre_slots else 0
        # re-admitted tokens are a subset of prefix hits whose KV came back
        # over h2d DMA — without the host tier they would have re-prefetched
        # nothing from the cache and re-run prefill
        readmit_saved = int(readmit_tokens * pre_flops / pre_slots) if pre_slots else 0
        return {
            "useful_tokens": useful,
            "slot_tokens": slot,
            "goodput_fraction": useful / slot if slot else 0.0,
            "spec_proposed_tokens": proposed,
            "spec_accepted_tokens": accepted,
            "spec_rejected_tokens": rejected,
            "spec_rejected_flops": rejected_flops,
            "prefix_hit_tokens": prefix_tokens,
            "prefix_saved_prefill_flops": saved_flops,
            "readmit_tokens": readmit_tokens,
            "readmit_saved_prefill_flops": readmit_saved,
            "cow_copy_bytes": cow,
        }

    def snapshot(self) -> Dict[str, Any]:
        """What ``GET /perf`` returns and ``tools/perf_report.py`` renders:
        peaks, per-card roofline rows, the goodput ledger, and the HBM pool
        gauges."""
        peak_flops, peak_bw = self.peaks()
        cards = sorted(self.cards().values(), key=lambda c: -c.time_s)
        return {
            "mode": self.mode,
            "peaks": {
                "flops_per_s": peak_flops,
                "bytes_per_s": peak_bw,
                "machine_balance_flops_per_byte":
                    peak_flops / peak_bw if peak_bw > 0 else 0.0,
            },
            "totals": self.totals(),
            "mfu": self.mfu(),
            "cards": [c.as_dict(peak_flops, peak_bw) for c in cards],
            "ledger": self.ledger(),
            "hbm": self.hbm(),
        }

    # ------------------------------------------------------------ resets
    def reset_counts(self) -> None:
        """Zero all running attribution (calls, time, tokens, ledger) but
        keep the built cards, so that a window after warmup is measured
        without re-tracing (and, in mode 2, without re-compiling) any
        program."""
        with self._lock:
            for c in self._cards.values():
                c.calls = c.timed_calls = 0
                c.time_s = 0.0
                c.useful_tokens = c.slot_tokens = 0
            self._open = None
            self.useful_tokens = self.slot_tokens = 0
            self.attributed_flops = 0
            self.attributed_time_s = 0.0
            self.spec_proposed = self.spec_accepted = 0
            self.prefix_hit_tokens = 0
            self.readmit_tokens = 0
            self.cow_bytes = 0

    def reset(self) -> None:
        """Full reset: drop cards, ledger, HBM pools, and re-read mode."""
        with self._lock:
            self._cards.clear()
            self._open = None
            self._hbm.clear()
            self._hbm_limit = 0
            self._peaks = None
        self.reset_counts()
        self.mode = knobs.get_int("DS_TPU_PERF_ACCOUNT")
        self.enabled = self.mode > 0


_ACCOUNTANT: Optional[PerfAccountant] = None
_ACCT_LOCK = threading.Lock()


def get_perf_accountant() -> PerfAccountant:
    """Process-wide accountant (mode read from ``DS_TPU_PERF_ACCOUNT`` at
    first use; ``reset()`` re-reads it)."""
    global _ACCOUNTANT
    with _ACCT_LOCK:
        if _ACCOUNTANT is None:
            _ACCOUNTANT = PerfAccountant()
        return _ACCOUNTANT
