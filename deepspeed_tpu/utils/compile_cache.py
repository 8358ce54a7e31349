"""Persistent XLA compilation-cache policy (single definition).

Used by the test conftest (CPU suite), ``chip_smoke.py``, ``bench.py`` and
the autotuning trial runner. The directory is placed from outside: where
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


def register_cache_metrics(jax) -> bool:
    """Feed jax's compilation-cache monitoring events into the telemetry
    registry (``compile_cache_hits_total`` / ``compile_cache_misses_total``).

    Idempotent; returns True once the listener is installed. jax records
    ``/jax/compilation_cache/cache_hits`` when an executable is read back
    and ``.../cache_misses`` when a freshly compiled one is written.
    """
    if _METRICS_REGISTERED:
        return True
    from jax import monitoring

    from ..telemetry.registry import get_registry

    reg = get_registry()
    hits = reg.counter("compile_cache_hits_total")
    misses = reg.counter("compile_cache_misses_total")

    def _listener(event, *args, **kwargs):
        if event == "/jax/compilation_cache/cache_hits":
            hits.inc()
        elif event == "/jax/compilation_cache/cache_misses":
            misses.inc()

    monitoring.register_event_listener(_listener)
    _METRICS_REGISTERED.append(_listener)
    return True
