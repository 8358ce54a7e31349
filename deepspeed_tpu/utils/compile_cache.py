"""Persistent XLA compilation-cache policy (single definition).

Used by the test conftest (CPU suite), ``chip_smoke.py``, ``benchmarks/run.py``
and the autotuning trial runner. The directory is placed from outside: where
``JAX_COMPILATION_CACHE_DIR`` is set the program uses it and sets no other;
where it is not, the cache is the fixed ``<checkout>/.jax_cache_tpu`` (the
path is part of the cache key, so it must never move: no temporary name,
process id or time).
"""

import os

# 0.0: persist every program. The CPU tier compiles hundreds of sub-second
# toy-model programs per run (backend optimization is already off); at the
# default 1.0s floor none of them are ever cached and every rerun pays the
# full compile bill again. Hardware tools pass their own floor.
MIN_COMPILE_TIME_SECS = 0.0

CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))), ".jax_cache_tpu")

_METRICS_REGISTERED = []

def block_traces() -> int:
    """Traces of a transformer block's Python body so far in this process:
    ``program_regions_traced_total{region="block", site}``, counted by the
    ``region`` that body opens (``models/transformer.py`` ``block_fn``: site
    ``train``; ``inference/v2/model_runner.py`` ``_stack_body``: ``serve``). It
    runs when the block is traced, not when it is called: a program pays one a
    KIND of block, not one a layer. All sites together."""
    from ..telemetry.tracing import regions_traced

    return int(regions_traced("block"))


def enable_compilation_cache(jax, default_dir: str = CHECKOUT_CACHE_DIR, env_gate: str = "DS_BENCH_NO_CACHE",
                             min_compile_secs: float = MIN_COMPILE_TIME_SECS):
    """Point jax at a persistent compile cache unless ``env_gate`` =1.
    Returns the directory in use (None when gated off).

    ``JAX_COMPILATION_CACHE_DIR`` (when set) wins over ``default_dir``.
    """
    if os.environ.get(env_gate) == "1":
        return None
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or default_dir
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", min_compile_secs)
    register_cache_metrics(jax)
    return cache_dir


def write_entries_through_a_rename() -> bool:
    """JAX's file cache writes an entry IN PLACE (``LRUCache.put``: ``Path.write_bytes``) and, with no size bound set,
    under no lock: a second process that looks the same program up at that moment reads a torn file, and the executable
    it deserialises takes the process down (a tier-1 worker, once in two whole runs with six workers on one
    ``tests/.jax_cache``; ``PERF.md`` section 7, open after PR 65, (d)). Here such an entry is written under a name of the
    writing process's own beside it and renamed: a reader finds the whole entry or none. A cache WITH a size bound keeps
    JAX's own path, which reads and writes under the cache's lock. Idempotent; False where this JAX has no such class.
    It replaces a method of a PRIVATE JAX class, so nothing in the library calls it: ``tests/conftest.py`` does, for the
    one place several processes are known to share a cache directory with no size bound."""
    try:
        from jax._src import lru_cache
    except ImportError:
        return False
    plain = lru_cache.LRUCache.put
    if getattr(plain, "through_a_rename", False):
        return True

    def put(self, key: str, val: bytes) -> None:
        if not key or self.eviction_enabled:
            return plain(self, key, val)
        entry = self.path / f"{key}{lru_cache._CACHE_SUFFIX}"
        if entry.exists():
            return
        mine = self.path / f"{key}.{os.getpid()}.tmp"
        try:
            mine.write_bytes(val)
            os.replace(mine, entry)
        finally:
            if mine.exists():  # the write failed part of the way: no entry, and nothing left beside it
                mine.unlink()

    put.through_a_rename = True
    lru_cache.LRUCache.put = put
    return True


TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# seconds of every program's first call, by phase, in the order a call passes through them
PHASES = ("trace", "lower", "compile", "cache_fetch")
PHASE_COUNTERS = tuple(f"program_{phase}_seconds_total" for phase in PHASES)


def register_cache_metrics(jax) -> bool:
    """Feed jax's monitoring events into the telemetry registry.

    Idempotent; returns True once the listeners are installed. Counts:
    ``compile_cache_hits_total`` / ``compile_cache_misses_total`` (an
    executable read back from the persistent cache / a fresh one written).
    Seconds, process-wide, of every program's first call by phase: the four
    ``PHASE_COUNTERS``, and ``program_first_calls_total``, one per program that
    reached the backend (compiled, or fetched from the persistent cache: JAX's
    compile event spans both, so ``program_compile_seconds_total`` includes the
    fetch). A trace nested in another program's trace is part of the outer
    one's seconds and is not counted again.
    """
    if _METRICS_REGISTERED:
        return True
    from jax import monitoring

    from ..telemetry.registry import get_registry

    reg = get_registry()
    hits = reg.counter("compile_cache_hits_total")
    misses = reg.counter("compile_cache_misses_total")
    # JAX records each duration when the phase ENDS, on the thread that ran it
    seconds = {
        TRACE_EVENT: reg.counter("program_trace_seconds_total"),
        "/jax/core/compile/jaxpr_to_mlir_module_duration": reg.counter("program_lower_seconds_total"),
        COMPILE_EVENT: reg.counter("program_compile_seconds_total"),
        "/jax/compilation_cache/cache_retrieval_time_sec": reg.counter("program_cache_fetch_seconds_total"),
    }
    first_calls = reg.counter("program_first_calls_total")
    top_level = jax.core.trace_ctx.is_top_level

    def _listener(event, *args, **kwargs):
        if event == "/jax/compilation_cache/cache_hits":
            hits.inc()
        elif event == "/jax/compilation_cache/cache_misses":
            misses.inc()

    def _on_duration(event, duration, **kwargs):
        counter = seconds.get(event)
        if counter is None or (event == TRACE_EVENT and not top_level()):
            return
        counter.inc(duration)
        if event == COMPILE_EVENT:
            first_calls.inc()

    monitoring.register_event_listener(_listener)
    monitoring.register_event_duration_secs_listener(_on_duration)
    _METRICS_REGISTERED.append(_listener)
    return True
