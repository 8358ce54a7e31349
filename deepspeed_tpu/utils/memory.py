"""Memory reporting. Reference: ``see_memory_usage`` in ``runtime/utils.py``."""

import gc

from ..analysis import knobs
from .logging import logger


def see_memory_usage(message: str, force: bool = False, ranks=(0,)):
    import jax

    if not force and not knobs.get_bool("DS_TPU_MEMORY_DEBUG"):
        return
    if jax.process_index() not in ranks:
        return
    from ..accelerator import get_accelerator

    acc = get_accelerator()
    ga = acc.memory_allocated() / (1024**3)
    peak = acc.max_memory_allocated() / (1024**3)
    limit = acc.total_memory() / (1024**3)
    try:
        import psutil

        vm = psutil.virtual_memory()
        host = f"host used: {vm.used / (1024**3):.2f} GB ({vm.percent}%)"
    except Exception:
        host = "host: n/a"
    logger.info(f"{message} | device allocated: {ga:.2f} GB | peak: {peak:.2f} GB | limit: {limit:.2f} GB | {host}")
