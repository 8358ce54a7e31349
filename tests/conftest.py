"""Test harness: simulate an 8-device TPU slice on CPU.

Mirrors the reference's distributed-without-a-cluster strategy
(``tests/unit/common.py``: fork N processes over loopback NCCL/gloo). The
TPU-native analogue is a faked 8-device host platform — real XLA
collectives, single process (SURVEY.md §4 "TPU translation").
MUST run before the first ``import jax`` anywhere in the test session.
"""

import os

if "--xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8 " + os.environ.get("XLA_FLAGS", "")
# the suite is compile-bound on the CPU backend; backend optimizations only
# burn time optimizing toy graphs (-37% wall measured; numerics/memory-audit
# suites verified green). DS_TEST_XLA_OPT=1 restores full optimization.
if ("--xla_backend_optimization_level" not in os.environ.get("XLA_FLAGS", "")
        and os.environ.get("DS_TEST_XLA_OPT") != "1"):
    os.environ["XLA_FLAGS"] = "--xla_backend_optimization_level=0 " + os.environ["XLA_FLAGS"]
os.environ["JAX_PLATFORMS"] = "cpu"  # the suite never takes the chip, even on a machine that has one
os.environ.setdefault("DS_ACCELERATOR", "tpu")

import jax  # noqa: E402

# persistent XLA compilation cache: the suite is compile-bound, and driver /
# CI reruns recompile identical toy HLO — warm runs cut test wall time ~2x
# (measured 24s -> 12s on the heaviest zeropp oracle). Keyed by HLO hash, so
# code changes re-compile exactly what changed. DS_TEST_NO_CACHE=1 disables.
from deepspeed_tpu.utils.compile_cache import enable_compilation_cache, write_entries_through_a_rename  # noqa: E402

# six xdist workers share the directory: an entry is written beside its name and renamed, so that no worker reads one
# another is half way through writing (the suite's own patch of a private JAX class; the library never applies it)
write_entries_through_a_rename()
enable_compilation_cache(jax, os.path.join(os.path.dirname(__file__), ".jax_cache"),
                         env_gate="DS_TEST_NO_CACHE")

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _reset_global_state():
    yield
    from deepspeed_tpu.parallel.mesh import reset_mesh

    reset_mesh()


@pytest.fixture(scope="module", autouse=True)
def _routed_rows_counters_left_as_found():
    """The routed layers' counters are the process's, and a benchmark reader that is handed no counter falls back to the
    process's totals (``benchmarks/lib/program.py::counter``): on a worker that ran a module with a routed layer first,
    ``tests/benchmarks/test_benchmark_hybrid.py`` and ``test_benchmark_latent.py`` read that module's rows where they
    expect none. Which modules share a worker follows their run times, so every module leaves them as it found them."""
    from deepspeed_tpu.telemetry.registry import get_registry

    names = ("moe_rows_routed_here_total", "moe_rows_dropped_total", "moe_fallback_layers_total")
    found = {name: get_registry().peek(name) for name in names}
    yield
    for name, value in found.items():
        if get_registry().peek(name) != value:
            get_registry().counter(name).value = value or 0.0


@pytest.fixture
def mesh8():
    """A pipe=1, data=8 default mesh over the 8 faked devices."""
    from deepspeed_tpu.parallel.mesh import initialize_mesh

    return initialize_mesh(force=True)


# make sibling test helpers (dist_utils) importable regardless of rootdir
import sys as _sys  # noqa: E402

_unit_dir = os.path.join(os.path.dirname(__file__), "unit")
if _unit_dir not in _sys.path:
    _sys.path.insert(0, _unit_dir)
