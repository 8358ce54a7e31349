"""Central registry for ``DS_TPU_*`` environment knobs.

Every environment variable the package reads must be declared here with a
default and a docstring; ``tools/graft_lint.py`` flags any ``os.environ`` /
``os.getenv`` read of a ``DS_TPU_*`` name outside this module, and
``tests/unit/test_graft_lint.py`` enforces code <-> registry <-> docs drift
in both directions (mirroring the metric-catalog guard in test_telemetry).

This module must stay stdlib-only: ``utils/logging.py`` (imported by nearly
everything) resolves its level through it, so any package-internal import
here would create a cycle.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Dict, Optional, Tuple


@dataclass(frozen=True)
class Knob:
    """One declared environment knob."""

    name: str
    default: Optional[str]
    kind: str  # "str" | "int" | "float" | "bool"
    doc: str
    owner: str  # module that consumes (or sets) it
    # Knobs the launcher/agent *sets* for child processes rather than reads.
    set_only: bool = False


_REGISTRY: Dict[str, Knob] = {}

# Tuned-profile overlay (autotune/profile.py): knob values loaded from a
# profiles/<device_kind>.json file. Precedence per knob is
# explicit env > profile > call-site default > declared default, so an
# operator export always wins over the tuned operating point.
_PROFILE: Dict[str, str] = {}
_PROFILE_META: Dict[str, object] = {}

# Prefix knobs: dynamically-named families like DS_TPU_OP_<NAME> used by the
# op registries. Reads of names starting with one of these prefixes are
# sanctioned without per-name declarations.
_PREFIXES: Dict[str, Knob] = {}


def declare(
    name: str,
    default: Optional[str],
    kind: str,
    doc: str,
    owner: str,
    *,
    prefix: bool = False,
    set_only: bool = False,
) -> Knob:
    knob = Knob(name=name, default=default, kind=kind, doc=doc, owner=owner, set_only=set_only)
    if prefix:
        _PREFIXES[name] = knob
    else:
        _REGISTRY[name] = knob
    return knob


def all_knobs() -> Dict[str, Knob]:
    return dict(_REGISTRY)


def prefix_knobs() -> Dict[str, Knob]:
    return dict(_PREFIXES)


def is_declared(name: str) -> bool:
    if name in _REGISTRY:
        return True
    return any(name.startswith(p) for p in _PREFIXES)


def _lookup(name: str) -> Knob:
    try:
        return _REGISTRY[name]
    except KeyError:
        for p, knob in _PREFIXES.items():
            if name.startswith(p):
                return knob
        raise KeyError(
            f"environment knob {name!r} is not declared in deepspeed_tpu.analysis.knobs; "
            "add a declare(...) entry with a default and docstring"
        ) from None


def set_profile(overlay: Dict[str, str], meta: Optional[Dict[str, object]] = None) -> None:
    """Install a tuned-profile knob overlay (values as env-style strings).

    Every key must be a declared knob; the overlay sits between the
    environment and the declared defaults in every ``get_*`` resolution.
    """
    for name, value in overlay.items():
        _lookup(name)
        if not isinstance(value, str):
            raise TypeError(f"profile value for {name} must be a string (got {type(value).__name__})")
    _PROFILE.clear()
    _PROFILE.update(overlay)
    _PROFILE_META.clear()
    _PROFILE_META.update(meta or {})


def clear_profile() -> None:
    _PROFILE.clear()
    _PROFILE_META.clear()


def active_profile() -> Optional[Dict[str, object]]:
    """Metadata of the installed tuned profile (None when no profile)."""
    if not _PROFILE and not _PROFILE_META:
        return None
    meta = dict(_PROFILE_META)
    meta["knobs"] = dict(_PROFILE)
    meta["env_overridden"] = sorted(n for n in _PROFILE if n in os.environ)
    return meta


def provenance(name: str) -> str:
    """Where the current value of ``name`` comes from: 'env' | 'profile' | 'default'."""
    _lookup(name)
    if name in os.environ:
        return "env"
    if name in _PROFILE:
        return "profile"
    return "default"


def _raw(name: str) -> Optional[str]:
    """env > profile, else None."""
    raw = os.environ.get(name)
    if raw is None:
        raw = _PROFILE.get(name)
    return raw


def get_str(name: str, default: Optional[str] = None) -> Optional[str]:
    knob = _lookup(name)
    raw = _raw(name)
    if raw is not None:
        return raw
    return default if default is not None else knob.default


def get_int(name: str, default: Optional[int] = None) -> int:
    knob = _lookup(name)
    raw = _raw(name)
    if raw is None or raw == "":
        if default is not None:
            return default
        return int(knob.default or 0)
    return int(raw)


def get_float(name: str, default: Optional[float] = None) -> float:
    knob = _lookup(name)
    raw = _raw(name)
    if raw is None or raw == "":
        if default is not None:
            return default
        return float(knob.default or 0.0)
    return float(raw)


_TRUTHY: Tuple[str, ...] = ("1", "true", "yes", "on")


def get_bool(name: str, default: Optional[bool] = None) -> bool:
    knob = _lookup(name)
    raw = _raw(name)
    if raw is None:
        if default is not None:
            return default
        raw = knob.default or "0"
    return raw.strip().lower() in _TRUTHY


def is_set(name: str) -> bool:
    """True when the knob is explicitly set (environment or tuned profile)."""
    _lookup(name)
    return name in os.environ or name in _PROFILE


# ---------------------------------------------------------------------------
# Declarations — one entry per DS_TPU_* knob in the codebase.
# ---------------------------------------------------------------------------

# Serving engine (inference/v2/engine_v2.py)
declare("DS_TPU_SERVE_FUSED", "1", "bool",
        "Serve with the single-dispatch fused SplitFuse step (0 falls back to the unfused loop).",
        "inference/v2/engine_v2.py")
declare("DS_TPU_SPEC_DECODE", "0", "bool",
        "Enable speculative decoding (draft + single-dispatch K-token verify).",
        "inference/v2/engine_v2.py")
declare("DS_TPU_SPEC_K", "4", "int",
        "Speculation depth: draft tokens proposed per verify dispatch.",
        "inference/v2/engine_v2.py")
declare("DS_TPU_DECODE_BURST", "32", "int",
        "Max fused greedy-decode steps per dispatch (0 disables bursting).",
        "inference/v2/engine_v2.py")
declare("DS_TPU_MIN_DECODE_BUCKET", "8", "int",
        "Floor for the padded decode batch bucket (1 restores exact "
        "power-of-two bucketing; bigger trades padding for fewer compiles).",
        "inference/v2/engine_v2.py")
declare("DS_TPU_PREFILL_CHUNK", "512", "int",
        "SplitFuse prefill chunk size: long prompts enter the ragged batch "
        "in chunks of this many tokens.",
        "inference/v2/scheduler.py")
declare("DS_TPU_MAX_BATCH_TOKENS", "0", "int",
        "Scheduler quantum token budget override (0 keeps the state-manager "
        "config value, default 768).",
        "inference/v2/engine_v2.py")
declare("DS_TPU_PROGRAM_CACHE", "8", "int",
        "Max live compiled variants per serving program family (fused step, "
        "decode burst, spec verify) before LRU eviction.",
        "inference/v2/engine_v2.py")
declare("DS_TPU_TP", "0", "int",
        "Tensor-parallel degree for serving: shard attention heads, MLP "
        "hidden dims and the paged KV pool over a 'tensor' mesh axis of "
        "this many local devices (0/1 = off; explicit engine config wins).",
        "inference/v2/engine_v2.py")
declare("DS_TPU_TP_ALLREDUCE_BITS", "0", "int",
        "Quantized TP activation allreduce: 8 or 4 runs the two per-layer "
        "row-parallel reduces as an EQuARX-style shared-scale integer-code "
        "psum at that width (0 = exact full-precision reduce).",
        "comm/collectives.py")

# Closed-loop autotuning (autotune/, docs/OBSERVABILITY.md "Closing the loop")
declare("DS_TPU_TUNED_PROFILE", None, "str",
        "Path to a tuned-profile JSON (profiles/<device_kind>.json) whose "
        "knob vector overlays the defaults; 'auto' resolves profiles/ by "
        "device kind. Explicit env knobs always win over the profile.",
        "autotune/profile.py")

# Paged-KV state manager (inference/v2/ragged/manager.py)
declare("DS_TPU_PREFIX_CACHE", "1", "bool",
        "Enable the radix prefix cache: retiring prompts donate KV blocks for reuse.",
        "inference/v2/ragged/manager.py")

# Tiered KV economy (docs/SERVING.md "Tiered KV economy")
declare("DS_TPU_KV_QUANT", "0", "int",
        "KV-cache quantization bits: 8 stores K/V pages as int8 with per-block "
        "per-head scales (fused dequant in the paged-attention kernels); 0 keeps "
        "the engine dtype.",
        "inference/v2/engine_v2.py")
declare("DS_TPU_KV_SPILL", "0", "bool",
        "Spill prefix-cache evictions to a host-RAM pool (async d2h) and re-admit "
        "matched prefixes via h2d DMA instead of re-prefilling.",
        "inference/v2/engine_v2.py")
declare("DS_TPU_KV_HOST_POOL_MB", "256", "int",
        "Capacity of the host-RAM KV spill pool in MiB (block count derives from "
        "the per-block byte size of the device pools).",
        "inference/v2/ragged/host_tier.py")
declare("DS_TPU_KV_SPILL_WATERMARK", "0.1", "float",
        "Free-block fraction below which the serving loop pre-spills LRU cached "
        "blocks to the host tier between dispatches.",
        "inference/v2/ragged/prefix_cache.py")

# Runtime sanitizers (analysis/)
declare("DS_TPU_KV_SANITIZE", "0", "bool",
        "Shadow-refcount sanitizer for paged KV blocks: traps double-free, "
        "leak-at-flush, and writes to shared blocks that skipped COW.",
        "analysis/kv_sanitizer.py")
declare("DS_TPU_JIT_AUDIT", "0", "bool",
        "Wrap jitted serving programs in a JitAuditor that counts compilations "
        "per signature and alerts on steady-state recompiles.",
        "analysis/jit_audit.py")
declare("DS_TPU_TRANSFER_GUARD", "0", "bool",
        "Run fused/spec dispatch under jax.transfer_guard_device_to_host('disallow') "
        "so implicit host readbacks raise instead of silently syncing.",
        "analysis/transfer_guard.py")
declare("DS_TPU_COMM_AUDIT", "0", "bool",
        "Record every collective into a per-rank (op, dtype, shape, axis) ledger "
        "and cross-check ledgers at barrier points, raising a structured "
        "divergence report instead of hanging on a mismatched collective.",
        "analysis/comm_audit.py")

# Telemetry (telemetry/)
declare("DS_TPU_TELEMETRY", "1", "bool",
        "Master switch for the telemetry subsystem (metrics, traces, events).",
        "telemetry/registry.py")
declare("DS_TPU_TELEMETRY_FLUSH_STEPS", "1", "int",
        "The training engine's monitor bridge flushes telemetry every N steps.",
        "runtime/engine.py")
declare("DS_TPU_TRACE_RING", "4096", "int",
        "Capacity of the span tracer's ring buffer.",
        "telemetry/tracing.py")
declare("DS_TPU_EVENT_RING", "65536", "int",
        "Capacity of the request-lifecycle event ring buffer.",
        "telemetry/events.py")
declare("DS_TPU_EVENT_LOG", None, "str",
        "If set, append request-lifecycle events as JSONL to this path.",
        "telemetry/events.py")
declare("DS_TPU_HEALTH_LOG", None, "str",
        "If set, append health alerts as JSONL to this path.",
        "telemetry/health.py")
declare("DS_TPU_STALL_S", "30", "float",
        "Queue-stall detector threshold: alert when the oldest queued request "
        "waits longer than this many seconds.",
        "telemetry/health.py")
declare("DS_TPU_PERF_ACCOUNT", "1", "int",
        "Serving performance accounting: 0 off, 1 analytic cost cards "
        "(jaxpr FLOP walk, compile-free), 2 adds AOT XLA cost/memory "
        "analysis per program signature (one extra compile at warmup).",
        "telemetry/costs.py")
declare("DS_TPU_PEAK_TFLOPS", "0", "float",
        "Declared peak dense TFLOP/s per chip for MFU and roofline "
        "readouts (0 = auto-detect from the device kind; unknown kinds "
        "report no MFU).",
        "telemetry/costs.py")
declare("DS_TPU_PEAK_GBPS", "0", "float",
        "Declared peak HBM GB/s per chip for roofline classification "
        "(0 = auto-detect from the device kind).",
        "telemetry/costs.py")
declare("DS_TPU_OPS_PORT", "0", "int",
        "Introspection server port (/metrics, /healthz, /requests, /perf, "
        "/flight, /varz). 0 (the default) starts nothing: zero threads, "
        "zero sockets.",
        "telemetry/ops_plane.py")
declare("DS_TPU_FLIGHT_DIR", None, "str",
        "If set, attach the flight recorder: every health alert snapshots "
        "the black box (events, spans, metrics, perf, residency, knobs) "
        "into a bounded capture ring under this directory.",
        "telemetry/flight.py")
declare("DS_TPU_FLIGHT_MAX", "8", "int",
        "Flight-recorder ring size: oldest on-disk captures are evicted "
        "beyond this many.",
        "telemetry/flight.py")
declare("DS_TPU_FLIGHT_PROFILE_S", "0", "float",
        "If >0, each flight capture also records a jax.profiler trace of "
        "this many seconds following the anomaly (opt-in: tracing is not "
        "free).",
        "telemetry/flight.py")
declare("DS_TPU_FLIGHT_PROFILE_MAX_MB", "64", "float",
        "Size bound on a flight capture's post-anomaly profile directory: "
        "over this many MB the raw trace is dropped (drop-and-count in "
        "the manifest) and only the parsed waterfall summary survives.",
        "telemetry/flight.py")
declare("DS_TPU_PROFILE", "0", "str",
        "A word: 0 (off), 1, stall or setup. "
        "1 arms a one-shot device-timeline capture at engine construction: "
        "the next DS_TPU_PROFILE_QUANTA serving quanta, or training "
        "steps, are wrapped in a jax.profiler trace and parsed into a "
        "per-quantum waterfall (compute / exposed-vs-overlapped "
        "collective / transfer / host gap) and, for a training step, "
        "its device time by region and phase. stall hunts for a stalled "
        "step: captures back to back, each dropped unread unless one of "
        "its quanta took 1.5 medians; the first that holds one is kept, "
        "with a `stall` section in its summary, and the hunt ends. setup "
        "captures set-up as one quantum: from the trainer's construction "
        "to the end of the first step that made no first call.",
        "telemetry/profiler.py")
declare("DS_TPU_PROFILE_DIR", "profile_captures", "str",
        "Directory for device-timeline capture output (raw trace plus "
        "the parsed summary.json per capture).",
        "telemetry/profiler.py")
declare("DS_TPU_PROFILE_QUANTA", "32", "int",
        "Quanta per device-timeline capture window: the trace stops and "
        "parses after this many dispatch readback boundaries (training: "
        "optimizer steps).",
        "telemetry/profiler.py")
declare("DS_TPU_STRAGGLER_X", "4", "float",
        "Straggler detector threshold: flag a rank whose pooled "
        "collective-wait p50 exceeds this multiple of the cross-rank "
        "median p50.",
        "telemetry/health.py")
declare("DS_TPU_JOURNAL", "0", "bool",
        "Record serving sessions to a black-box journal (engine "
        "fingerprint, arrivals, quantum composition, committed-token "
        "digests) for deterministic replay via tools/replay.py.",
        "telemetry/journal.py")
declare("DS_TPU_JOURNAL_DIR", "journals", "str",
        "Directory for journal JSONL files (one per process) when "
        "DS_TPU_JOURNAL is on.",
        "telemetry/journal.py")

# Ops / kernels
declare("DS_TPU_OP_", None, "str",
        "Per-op implementation override for the training op registry, e.g. "
        "DS_TPU_OP_FLASH_ATTENTION=xla forces the XLA fallback for that op.",
        "ops/registry.py", prefix=True)
declare("DS_TPU_OP_V2_", None, "str",
        "Per-op implementation override for the inference-v2 module registry.",
        "inference/v2/modules.py", prefix=True)
declare("DS_TPU_FLASH_BQ", "512", "int",
        "Pallas flash-attention query-block size.",
        "ops/pallas/flash_attention.py")
declare("DS_TPU_FLASH_BK", "512", "int",
        "Pallas flash-attention key-block size.",
        "ops/pallas/flash_attention.py")
declare("DS_TPU_CE_CHUNK", "0", "int",
        "Fused cross-entropy vocab-chunk size (0 = derive from budget).",
        "ops/fused_ce.py")
declare("DS_TPU_CE_BUDGET_MB", "4096", "int",
        "Memory budget (MB) used to derive the fused cross-entropy chunk size.",
        "ops/fused_ce.py")
declare("DS_TPU_BUILD_DIR", None, "str",
        "Override the build/cache directory for natively-built op artifacts.",
        "ops/native/builder.py")

# Runtime / checkpoint
declare("DS_TPU_CKPT_ENGINE", None, "str",
        "Force a checkpoint engine backend (e.g. 'torch', 'tensorstore').",
        "runtime/checkpoint_engine.py")

# Utils
declare("DS_TPU_LOG_LEVEL", "INFO", "str",
        "Package log level (DEBUG/INFO/WARNING/ERROR).",
        "utils/logging.py")
declare("DS_TPU_MEMORY_DEBUG", "0", "bool",
        "Print live/peak device-memory stats from see_memory_usage().",
        "utils/memory.py")
declare("DS_TPU_WATCHDOG_TIMEOUT_S", "180", "float",
        "Default watchdog timeout for collective/step hangs (seconds).",
        "utils/watchdog.py")

# Distributed / launcher / elasticity
declare("DS_TPU_COORDINATOR", None, "str",
        "host:port for multi-host jax.distributed rendezvous.",
        "comm/comm.py")
declare("DS_TPU_NUM_PROCESSES", None, "int",
        "Process count for multi-host rendezvous (defaults to world size).",
        "comm/comm.py")
declare("DS_TPU_PROCESS_ID", None, "int",
        "This process's id for multi-host rendezvous (defaults to rank).",
        "comm/comm.py")
declare("DS_TPU_WORLD_CHIPS", None, "int",
        "Total chip count across the elastic world; set by the launcher, "
        "read by elasticity config validation.",
        "launcher/launch.py, elasticity/elasticity.py")
declare("DS_TPU_LOCAL_CHIPS", None, "str",
        "Comma-separated chip ids assigned to this node (set by the launcher).",
        "launcher/launch.py", set_only=True)
declare("DS_TPU_NODE_RANK", None, "int",
        "This node's rank in the launch topology (set by the launcher).",
        "launcher/launch.py", set_only=True)
declare("DS_TPU_ELASTIC_RESTART", None, "int",
        "Current elastic restart round (set by the elastic agent for children).",
        "elasticity/elastic_agent.py", set_only=True)
declare("DS_TPU_ELASTIC_MAX_RESTARTS", None, "int",
        "Maximum elastic restarts (set by the elastic agent for children).",
        "elasticity/elastic_agent.py", set_only=True)
