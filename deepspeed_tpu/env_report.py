"""Environment report — the ``ds_report`` analogue.

Parity: reference ``deepspeed/env_report.py`` + ``bin/ds_report``: one
command that prints framework/runtime versions, visible devices, kernel
availability (Pallas + native host ops), and rendezvous-relevant env —
the first thing to ask for in a bug report.

Run as ``python -m deepspeed_tpu.env_report``.
"""

import os
import platform
import sys


def _try_version(mod_name: str) -> str:
    try:
        mod = __import__(mod_name)
        return getattr(mod, "__version__", "?")
    except Exception as e:  # noqa: BLE001 - report, don't crash
        return f"NOT AVAILABLE ({type(e).__name__})"


def _probe_devices(timeout_s: float = 180.0):
    """Backend facts under a watchdog: the first device query can hang
    forever (chip held by another process, a multi-host peer that never
    arrives), and a diagnostic tool must not hang on the very environment
    it exists to diagnose. 180s because real pod inits can take minutes.
    Returns ``(report_lines, backend_alive)``."""
    from .utils.watchdog import run_with_watchdog

    def probe():
        import jax

        backend = jax.default_backend()
        devs = jax.devices()
        return [f"backend .............. {backend}",
                f"devices .............. {len(devs)} x {devs[0].device_kind if devs else '-'}",
                f"process count ........ {jax.process_count()} (index {jax.process_index()})"]

    status, value = run_with_watchdog(probe, timeout_s)
    if status == "error":
        # clean failure: no thread is stuck, further jax calls return
        # promptly, so the registry section may still be attempted
        return [f"backend .............. FAILED: {type(value).__name__}: {value}"], True
    if status == "timeout":
        return [f"backend .............. UNREACHABLE (device probe did not return within {timeout_s:.0f}s — "
                "chip held by another process?)"], False
    return value, True


def report_string() -> str:
    from .version import __version__

    lines = ["=" * 70, "deepspeed_tpu environment report", "=" * 70]
    lines.append(f"deepspeed_tpu ......... {__version__}")
    for dep in ("jax", "jaxlib", "flax", "optax", "numpy"):
        lines.append(f"{dep:.<20} {_try_version(dep)}")
    lines.append(f"python ............... {sys.version.split()[0]} ({platform.platform()})")

    dev_lines, backend_responsive = _probe_devices()
    lines.extend(dev_lines)

    for var in ("JAX_PLATFORMS", "XLA_FLAGS", "TPU_NAME", "MASTER_ADDR", "WORLD_SIZE", "RANK"):
        if var in os.environ:
            lines.append(f"env {var} = {os.environ[var]}")

    lines.append("-" * 70)
    if backend_responsive:
        try:
            from .ops.registry import REGISTRY

            # importing the kernels registers their impls
            from .ops import pallas as _  # noqa: F401

            lines.append(REGISTRY.report())
        except Exception as e:  # noqa: BLE001
            lines.append(f"op registry .......... FAILED: {e}")
    else:
        # the stuck init thread (timeout case only) would block any
        # further jax call, op selection included
        lines.append("op registry .......... skipped (backend unreachable)")

    lines.append("-" * 70)
    try:
        from .ops.native.builder import native_available

        for lib in ("ds_cpu_optim", "ds_aio"):
            lines.append(f"native {lib:.<20} {'OK' if native_available(lib) else 'unavailable'}")
    except Exception as e:  # noqa: BLE001
        lines.append(f"native ops ........... FAILED: {e}")
    lines.append("=" * 70)
    return "\n".join(lines)


def main():
    print(report_string())
    return 0


if __name__ == "__main__":
    sys.exit(main())
