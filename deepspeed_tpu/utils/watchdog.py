"""Watchdog for calls that can hang forever.

The first jax backend/device query blocks indefinitely, and cannot be
cancelled, when the chip is held by another process or a multi-host
init waits on a peer that never comes; a diagnostic that probes the backend
(``env_report``) goes through this spawn/join/timeout protocol instead.

Telemetry: every timeout increments ``watchdog_timeouts_total``; paired
with the engine's ``last_step_completed_unix`` heartbeat gauge this
makes a hung device call distinguishable from a merely slow step.
"""

import threading
import time
from typing import Any, Callable, Optional, Tuple

from ..analysis import knobs

DEFAULT_TIMEOUT_S = 180.0


def default_timeout() -> float:
    """The watchdog deadline when callers pass none: 180 s, overridable
    via ``DS_TPU_WATCHDOG_TIMEOUT_S``."""
    try:
        return knobs.get_float("DS_TPU_WATCHDOG_TIMEOUT_S", DEFAULT_TIMEOUT_S)
    except ValueError:
        return DEFAULT_TIMEOUT_S


def run_with_watchdog(fn: Callable[[], Any], timeout_s: Optional[float] = None) -> Tuple[str, Any]:
    """Run ``fn()`` on a daemon thread with a deadline (``default_timeout()``
    when ``timeout_s`` is None).

    Returns ``("ok", result)``, ``("error", exception)``, or
    ``("timeout", None)``. On timeout the thread is still stuck inside
    ``fn`` (likely holding the backend-init lock), so the caller must not
    make further backend calls in this process.
    """
    if timeout_s is None:
        timeout_s = default_timeout()
    box: dict = {}

    def run():
        try:
            box["value"] = fn()
        except BaseException as e:  # noqa: BLE001 - surfaced to the caller
            box["error"] = e

    from ..telemetry.health import get_health_monitor

    monitor = get_health_monitor()
    t = threading.Thread(target=run, daemon=True)
    t.start()
    # join in slices so clock-driven detectors (queue stall) can raise a
    # structured alert BEFORE the bare deadline fires — a scheduler that
    # admits nothing while requests wait trips DS_TPU_STALL_S first
    deadline = time.monotonic() + timeout_s
    while t.is_alive():
        remaining = deadline - time.monotonic()
        if remaining <= 0:
            break
        t.join(min(1.0, remaining))
        if t.is_alive():
            monitor.poll()
    if "error" in box:
        return "error", box["error"]
    if "value" in box:
        return "ok", box["value"]
    from ..telemetry.registry import get_registry

    get_registry().counter("watchdog_timeouts_total").inc()
    attrs = {"timeout_s": float(timeout_s)}
    stall = monitor.detector("queue_stall")
    if stall is not None and getattr(stall, "waiting", None):
        attrs["pending_requests"] = len(stall.waiting)
        attrs["stalled_s"] = round(stall.stalled_for(), 3)
    monitor.raise_alert("watchdog_timeout",
                        f"watchdog: call exceeded {timeout_s:.0f}s deadline",
                        **attrs)
    return "timeout", None
