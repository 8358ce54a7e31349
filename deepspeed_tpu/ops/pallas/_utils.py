"""Shared Pallas kernel helpers (how a kernel is chosen and sits on a mesh: ``ops/placement.py``)."""

import jax
from jax.experimental.pallas import tpu as pltpu


def block_that_divides(n: int, want: int) -> int:
    """Largest power-of-two-reduced block <= ``want`` that divides ``n``."""
    b = min(n, want)
    while n % b:
        b //= 2
    return max(b, 1)


_VMEM_DEFAULT = 16 << 20  # what the compiler grants a kernel unless told otherwise (``vmem_limit_bytes``)


def vmem_budget() -> int:
    """What one kernel may plan to keep in VMEM: three eighths of the core's
    (48 MiB of a v5e's 128), so that a raised limit never asks for the whole
    core. Read from the attached TPU; with none attached (interpret mode, and
    the compiles for a described chip, which describe a v5e) it is a v5e's."""
    capacity = pltpu.get_tpu_info().vmem_capacity_bytes if jax.default_backend() == "tpu" else 128 << 20
    return capacity * 3 // 8


def compiler_params(*semantics, interpret, vmem_bytes: int = 0):
    """Mosaic dimension semantics: 'parallel' grid dims let the pipeline
    overlap the next program's DMA with current compute — valid whenever
    the dim carries no cross-program state. ``vmem_bytes``: the caller's
    count, from its shapes, of what the kernel holds in VMEM at once (kept
    under ``vmem_budget()`` by the caller); where that nears the default
    grant the limit is raised to cover it."""
    if interpret:
        return None
    limit = None
    if vmem_bytes > _VMEM_DEFAULT * 3 // 4:
        limit = vmem_bytes + _VMEM_DEFAULT
    return pltpu.CompilerParams(dimension_semantics=semantics, vmem_limit_bytes=limit)
