"""Wall-clock timers.

Parity: reference ``deepspeed/utils/timer.py`` (``SynchronizedWallClockTimer``
:43, ``ThroughputTimer`` :198). "Synchronized" on TPU means calling
``block_until_ready`` on the async dispatch stream before reading the clock.
"""

import time
from typing import Dict, List, Optional

from .logging import log_dist

FORWARD_MICRO_TIMER = "fwd_microstep"
FORWARD_GLOBAL_TIMER = "fwd"
BACKWARD_MICRO_TIMER = "bwd_microstep"
BACKWARD_GLOBAL_TIMER = "bwd"
BACKWARD_INNER_MICRO_TIMER = "bwd_inner_microstep"
BACKWARD_INNER_GLOBAL_TIMER = "bwd_inner"
BACKWARD_REDUCE_MICRO_TIMER = "bwd_allreduce_microstep"
BACKWARD_REDUCE_GLOBAL_TIMER = "bwd_allreduce"
STEP_MICRO_TIMER = "step_microstep"
STEP_GLOBAL_TIMER = "step"
TRAIN_BATCH_TIMER = "train_batch"


def _sync():
    import jax
    import jax.numpy as jnp

    (jnp.zeros(()) + 0).block_until_ready()  # graft-lint: readback (wall-clock timers sync by design)


class SynchronizedWallClockTimer:
    class Timer:
        def __init__(self, name: str):
            self.name_ = name
            self.started_ = False
            self.start_time = 0.0
            self.elapsed_ = 0.0
            self.count = 0

        def start(self, sync: bool = True):
            if self.started_:
                return
            if sync:
                _sync()
            self.start_time = time.perf_counter()
            self.started_ = True

        def stop(self, reset: bool = False, sync: bool = True):
            if not self.started_:
                return
            if sync:
                _sync()
            elapsed = time.perf_counter() - self.start_time
            if reset:
                self.elapsed_ = elapsed
            else:
                self.elapsed_ += elapsed
            self.count += 1
            self.started_ = False

        def elapsed(self, reset: bool = True) -> float:
            """Elapsed seconds (stops/restarts a running timer around the read)."""
            was_started = self.started_
            if was_started:
                self.stop()
            out = self.elapsed_
            if reset:
                self.elapsed_ = 0.0
            if was_started:
                self.start()
            return out

        def mean(self) -> float:
            return self.elapsed_ / max(self.count, 1)

        def reset(self):
            self.started_ = False
            self.elapsed_ = 0.0
            self.count = 0

    def __init__(self):
        self.timers: Dict[str, SynchronizedWallClockTimer.Timer] = {}

    def __call__(self, name: str) -> "SynchronizedWallClockTimer.Timer":
        if name not in self.timers:
            self.timers[name] = self.Timer(name)
        return self.timers[name]

    def get_timers(self):
        return self.timers

    @staticmethod
    def memory_usage() -> str:
        from ..accelerator import get_accelerator

        acc = get_accelerator()
        alloc = acc.memory_allocated() / (1024**3)
        peak = acc.max_memory_allocated() / (1024**3)
        return f"mem_allocated: {alloc:.4f} GB | peak: {peak:.4f} GB"

    def log(self, names: List[str], normalizer: float = 1.0, reset: bool = True, memory_breakdown: bool = False,
            ranks: Optional[List[int]] = None):
        assert normalizer > 0.0
        parts = []
        for name in names:
            if name in self.timers:
                ms = self.timers[name].elapsed(reset=reset) * 1000.0 / normalizer
                parts.append(f"{name}: {ms:.2f}")
        if parts:
            msg = "time (ms) | " + " | ".join(parts)
            if memory_breakdown:
                msg += " | " + self.memory_usage()
            log_dist(msg, ranks=ranks or [0])


class NoopTimer:
    class Timer:
        def start(self, **kw):
            ...

        def stop(self, **kw):
            ...

        def reset(self):
            ...

        def elapsed(self, **kw):
            return 0.0

        def mean(self):
            return 0.0

    def __call__(self, name):
        return self.Timer()

    def get_timers(self):
        return {}

    def log(self, *args, **kwargs):
        ...


class ThroughputTimer:
    """Samples/sec + tokens-style throughput. Reference ``timer.py:198``."""

    def __init__(self, config, batch_size: int, start_step: int = 2, steps_per_output: int = 50,
                 monitor_memory: bool = False, logging_fn=None):
        self.config = config
        self.batch_size = max(1, batch_size)
        self.start_step = start_step
        self.steps_per_output = steps_per_output
        self.monitor_memory = monitor_memory
        self.logging = logging_fn or log_dist
        self.initialized = False
        self.total_elapsed_time = 0.0
        self.step_elapsed_time = 0.0
        self.global_step_count = 0
        self.micro_step_count = 0
        self.start_time = 0.0
        self.started = False

    @property
    def enabled(self) -> bool:
        return getattr(self.config, "enabled", True)

    def start(self):
        if not self.enabled:
            return
        _sync()
        self.start_time = time.perf_counter()
        self.started = True

    def stop(self, global_step: bool, report_speed: bool = True):
        if not self.enabled or not self.started:
            return
        self.started = False
        self.micro_step_count += 1
        _sync()
        duration = time.perf_counter() - self.start_time
        if global_step:
            self.global_step_count += 1
            if self.global_step_count >= self.start_step:
                self.total_elapsed_time += duration
                self.step_elapsed_time += duration
                if report_speed and self.global_step_count % self.steps_per_output == 0:
                    self.logging(
                        f"epoch-step: {self.global_step_count} | "
                        f"throughput: {self.avg_samples_per_sec():.2f} samples/s | "
                        f"step time: {duration:.3f} s", ranks=[0])
                    self.step_elapsed_time = 0.0

    def avg_samples_per_sec(self) -> float:
        counted = self.global_step_count - self.start_step + 1
        if counted > 0 and self.total_elapsed_time > 0:
            return self.batch_size * counted / self.total_elapsed_time
        return 0.0
