"""deepspeed_tpu — a TPU-native training/inference framework with the
capabilities of DeepSpeed (reference 0.14.3), built on JAX/XLA/Pallas.

Top-level API parity (reference ``deepspeed/__init__.py``):
- ``initialize(...)`` -> ``(engine, optimizer, dataloader, lr_scheduler)``
- ``init_inference(...)`` -> inference engine
- ``deepspeed_tpu.comm`` as the distributed façade
- ``zero.Init`` for sharded model construction
"""

import time as _time

_T0 = _time.perf_counter()  # ``import_seconds``, at the bottom: this file top to bottom, whatever it is the first to import

from . import comm
from .accelerator import get_accelerator
from .comm import init_distributed  # reference deepspeed.init_distributed (deepspeed/__init__.py)
from .runtime.config import DeepSpeedConfig
from .utils import groups, logger
from .version import __version__


def initialize(args=None,
               model=None,
               optimizer=None,
               model_parameters=None,
               training_data=None,
               lr_scheduler=None,
               mesh=None,
               mpu=None,
               dist_init_required=None,
               collate_fn=None,
               config=None,
               config_params=None,
               **kwargs):
    """Build a training engine. Reference: ``deepspeed/__init__.py:70``.

    Returns ``(engine, optimizer, dataloader, lr_scheduler)``. At ZeRO stage 3
    on several chips the divided leaves of ``model_parameters`` are deleted
    once their shards stand (the tree is consumed: ``runtime/engine.py::initialize``).
    """
    from .runtime.engine import initialize as _initialize

    return _initialize(args=args, model=model, optimizer=optimizer, model_parameters=model_parameters,
                       training_data=training_data, lr_scheduler=lr_scheduler, mesh=mesh, mpu=mpu,
                       dist_init_required=dist_init_required, collate_fn=collate_fn,
                       config=config if config is not None else config_params, **kwargs)


def init_inference(model=None, config=None, **kwargs):
    """Build an inference engine. Reference: ``deepspeed/inference/engine.py:39``."""
    from .inference.engine import init_inference as _init_inference

    return _init_inference(model=model, config=config, **kwargs)


def default_inference_config():
    """Default v1 inference config dict (reference ``deepspeed/__init__.py:266``)."""
    from .inference.config import DeepSpeedInferenceConfig

    return DeepSpeedInferenceConfig().to_dict()


def add_config_arguments(parser):
    """Attach the reference's ``--deepspeed``/``--deepspeed_config`` CLI
    flags to an argparse parser (reference ``deepspeed/__init__.py:250``)."""
    group = parser.add_argument_group("DeepSpeed", "DeepSpeed configurations")
    group.add_argument("--deepspeed", default=False, action="store_true",
                       help="Enable DeepSpeed (helper flag for user code; the engine activates via config)")
    group.add_argument("--deepspeed_config", default=None, type=str,
                       help="Path to the DeepSpeed json configuration file")
    group.add_argument("--deepscale", default=False, action="store_true",
                       help="Deprecated alias of --deepspeed")
    group.add_argument("--deepscale_config", default=None, type=str,
                       help="Deprecated alias of --deepspeed_config")
    return parser


# reference top-level class/helper surface (deepspeed/__init__.py:25-50),
# resolved lazily so `import deepspeed_tpu` stays light
_LAZY_NAMES = {
    "DeepSpeedEngine": ("deepspeed_tpu.runtime.engine", "DeepSpeedEngine"),
    "DeepSpeedHybridEngine": ("deepspeed_tpu.runtime.hybrid_engine", "DeepSpeedHybridEngine"),
    "PipelineEngine": ("deepspeed_tpu.runtime.pipe.engine", "PipelineEngine"),
    "PipelineModule": ("deepspeed_tpu.runtime.pipe.module", "PipelineModule"),
    "InferenceEngine": ("deepspeed_tpu.inference.engine", "InferenceEngine"),
    "DeepSpeedInferenceConfig": ("deepspeed_tpu.inference.config", "DeepSpeedInferenceConfig"),
    "DeepSpeedTransformerLayer": ("deepspeed_tpu.ops.transformer.transformer_layer", "DeepSpeedTransformerLayer"),
    "DeepSpeedTransformerConfig": ("deepspeed_tpu.ops.transformer.transformer_layer", "DeepSpeedTransformerConfig"),
    "log_dist": ("deepspeed_tpu.utils.logging", "log_dist"),
    "OnDevice": ("deepspeed_tpu.utils.init_on_device", "OnDevice"),
    "ADAM_OPTIMIZER": ("deepspeed_tpu.runtime.optimizers", "ADAM_OPTIMIZER"),
    "checkpointing": ("deepspeed_tpu.runtime.activation_checkpointing", "checkpointing"),
    "LAMB_OPTIMIZER": ("deepspeed_tpu.runtime.optimizers", "LAMB_OPTIMIZER"),
}


def __getattr__(name):
    # Lazy submodule access: deepspeed_tpu.zero, .moe, .pipe, .ops, ...
    import importlib

    lazy = {"zero", "moe", "pipe", "sequence", "ops", "models", "inference", "checkpoint", "monitor", "profiling",
            "elasticity", "compression", "autotuning", "module_inject", "launcher", "runtime", "linear", "comm",
            "utils", "accelerator"}
    if name in lazy:
        return importlib.import_module(f".{name}", __name__)
    if name in _LAZY_NAMES:
        mod, attr = _LAZY_NAMES[name]
        return getattr(importlib.import_module(mod), attr)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


from .telemetry.registry import get_registry as _get_registry  # noqa: E402

_get_registry().gauge("import_seconds").set(_time.perf_counter() - _T0)
