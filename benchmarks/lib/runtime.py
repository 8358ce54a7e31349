"""What every driver needs of the process it runs in: the device it found, the
count of compilations, and a profiler trace over the end of the window."""

import os
import time


def device_info(chips: int) -> dict:
    import jax

    devs = jax.devices()[:chips]
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs), "memory_peak_bytes": peak}


class CompileCounter:
    """Counts what JAX compiles or fetches from its persistent cache: one
    ``/jax/core/compile/backend_compile_duration`` event per program that was
    not in the process's own cache (a persistent-cache hit included: it still
    cost a trace and a lowering, seconds for a sixteen-layer program)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        from jax import monitoring

        self.n = 0
        self.seconds = 0.0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self.EVENT:
            self.n += 1
            self.seconds += float(duration)

    def take(self):
        out = (self.n, self.seconds)
        self.n, self.seconds = 0, 0.0
        return out


class Tracer:
    """A JAX profiler trace of a stretch the driver chooses and marks with a
    ``bench/window`` span, which the reduction cuts to. Starting and stopping
    the TPU profiler each take seconds, and a traced second of four chips'
    training is 240,000 device events (PERF.md, Findings): so a driver starts
    it outside its measured window and traces a few steps, not the window.
    Python-level tracing is off: the host planes then hold the runtime's own
    threads and the driver's ``bench/...`` spans, and the trace stays small."""

    def __init__(self, enabled: bool, out_dir: str):
        self.enabled, self.out_dir = enabled, out_dir
        self.started = self.traced = False

    def start(self):
        if self.enabled and not self.started:
            import jax

            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            os.makedirs(self.out_dir, exist_ok=True)
            jax.profiler.start_trace(self.out_dir, profiler_options=opts)
            self.started = True

    def stop(self):
        if self.started:
            import jax

            jax.profiler.stop_trace()
            self.started, self.traced = False, True


def progress(t0: float):
    """A stamped line on stderr for each phase: what a chip call shows of a run
    that did not reach its end."""
    import sys

    def say(msg: str):
        print(f"[bench +{time.perf_counter() - t0:7.1f}s] {msg}", file=sys.stderr, flush=True)

    return say


def cache_counts() -> dict:
    """Persistent compile-cache hits and misses so far (the program's own
    listener, ``utils/compile_cache.register_cache_metrics``)."""
    from deepspeed_tpu.telemetry import get_registry

    reg = get_registry()
    return {"hits": int(reg.peek("compile_cache_hits_total") or 0), "misses": int(reg.peek("compile_cache_misses_total") or 0)}
