from .attention import attention
from .registry import REGISTRY, get_op, register_op

__all__ = ["attention", "REGISTRY", "get_op", "register_op"]

# Pallas kernels register themselves (interpretable on CPU, native on TPU)
from . import pallas  # noqa: F401,E402

__all__.append("pallas")
