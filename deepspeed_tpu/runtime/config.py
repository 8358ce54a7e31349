"""The DeepSpeed-TPU config tree.

One JSON/dict config is the spine of the framework, exactly as in the
reference (``runtime/config.py:705`` ``DeepSpeedConfig``): every feature is
toggled through it, and micro-batch/grad-accum/global-batch are triangulated
against the data-parallel world size (reference ``runtime/config.py:765``).

TPU-native departures:
- a ``mesh`` section declares named mesh-axis sizes (``data``, ``fsdp``,
  ``tensor``, ``pipe``, ``expert``, ``seq``) instead of the reference's
  implicit rank-grid from an external ``mpu`` object;
- precision defaults to bf16 (TPU-native dtype) rather than fp16.
"""

import json
import os
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Union

from .config_utils import DeepSpeedConfigModel, ds_field
from .constants import (GRADIENT_ACCUMULATION_STEPS, TRAIN_BATCH_SIZE, TRAIN_MICRO_BATCH_SIZE_PER_GPU)
from ..utils.logging import logger


@dataclass
class FP16Config(DeepSpeedConfigModel):
    """Reference: ``runtime/fp16/loss_scaler.py`` + fp16 section of ``runtime/config.py``."""
    enabled: bool = False
    auto_cast: bool = False
    loss_scale: float = ds_field(0.0, ge=0.0)  # 0 => dynamic
    initial_scale_power: int = ds_field(16, ge=0)
    loss_scale_window: int = ds_field(1000, gt=0)
    hysteresis: int = ds_field(2, ge=1)
    consecutive_hysteresis: bool = False
    min_loss_scale: float = ds_field(1.0, ge=0.0)
    fp16_master_weights_and_grads: bool = False

    @property
    def dynamic_loss_scale(self) -> bool:
        return self.loss_scale == 0


@dataclass
class BF16Config(DeepSpeedConfigModel):
    enabled: bool = False
    immediate_grad_update: bool = False


@dataclass
class ZeroOffloadParamConfig(DeepSpeedConfigModel):
    """Reference: ``runtime/zero/offload_config.py``."""
    device: str = "none"  # none | cpu | nvme
    nvme_path: Optional[str] = None
    buffer_count: int = ds_field(5, ge=1)
    buffer_size: int = ds_field(100_000_000, ge=1)
    max_in_cpu: int = ds_field(1_000_000_000, ge=0)
    pin_memory: bool = False


@dataclass
class ZeroOffloadOptimizerConfig(DeepSpeedConfigModel):
    device: str = "none"  # none | cpu | nvme
    nvme_path: Optional[str] = None
    buffer_count: int = ds_field(4, ge=1)
    pin_memory: bool = False
    pipeline_read: bool = False
    pipeline_write: bool = False
    fast_init: bool = False
    ratio: float = ds_field(1.0, ge=0.0, le=1.0)


@dataclass
class ZeroConfig(DeepSpeedConfigModel):
    """Reference: ``runtime/zero/config.py:82`` ``DeepSpeedZeroConfig``.

    On TPU the stages are realized as sharding specs over the mesh rather
    than tensor surgery (SURVEY.md §7): stage 1/2 shard optimizer state
    (and reduce-scatter grads) over the data axis; stage 3 additionally
    shards parameters over the ``fsdp`` axis with allgather-on-use.

    ``overlap_comm`` (default: true at stage 3, false below) is read at
    stage 3 on a TPU mesh whose one axis wider than a device is ZeRO's:
    true makes every transformer block gather its own parameters and reduce
    a layer's weight gradients by a ring under the next layer's backward, a
    layer being the bucket, and the token look-up and the loss head work on
    each device's own rows of the embedding with the same ring for what they
    sum over devices (``runtime/zero/overlap.py``); false, any other backend
    and any other mesh leave the reduction to XLA's partitioner.
    A block that gathered its 16-bit weights keeps them from its forward to
    its backward, so ``stage3_max_live_parameters`` is read there too, with
    the reference's meaning: the last blocks keep while the parameters they
    hold stay under it (the default 1e9: 2 GB a device in bf16), and the
    blocks before them release their weights after the forward and
    all-gather them a second time in the backward. No block leaves the plan
    for it; 0 keeps nothing, so every block gathers twice
    (``overlap_comm: false`` is the way to the partitioner's program).
    ``reduce_bucket_size`` is parsed and not read.
    """
    stage: int = ds_field(0, ge=0, le=3)
    contiguous_gradients: bool = True
    reduce_scatter: bool = True
    reduce_bucket_size: int = ds_field(500_000_000, ge=0)
    allgather_partitions: bool = True
    allgather_bucket_size: int = ds_field(500_000_000, ge=0)
    overlap_comm: Optional[bool] = None
    load_from_fp32_weights: bool = True
    elastic_checkpoint: bool = False
    offload_param: ZeroOffloadParamConfig = ds_field(default_factory=ZeroOffloadParamConfig)
    offload_optimizer: ZeroOffloadOptimizerConfig = ds_field(default_factory=ZeroOffloadOptimizerConfig)
    sub_group_size: int = ds_field(1_000_000_000, ge=0)
    cpu_offload: Optional[bool] = ds_field(None, deprecated=True, new_param="offload_optimizer")
    cpu_offload_params: Optional[bool] = ds_field(None, deprecated=True, new_param="offload_param")
    stage3_max_live_parameters: int = ds_field(1_000_000_000, ge=0)
    stage3_max_reuse_distance: int = ds_field(1_000_000_000, ge=0)
    stage3_prefetch_bucket_size: int = ds_field(50_000_000, ge=0)
    stage3_param_persistence_threshold: int = ds_field(100_000, ge=0)
    stage3_model_persistence_threshold: int = ds_field(9_223_372_036_854_775_807, ge=0)
    stage3_gather_16bit_weights_on_model_save: bool = False
    ignore_unused_parameters: bool = True
    round_robin_gradients: bool = False
    # ZeRO++ knobs (hpZ / qwZ / qgZ). Reference: zero/config.py:264-280.
    zero_hpz_partition_size: int = ds_field(1, ge=1)
    zero_quantized_weights: bool = False
    zero_quantized_nontrainable_weights: bool = False
    zero_quantized_gradients: bool = False
    # MiCS. Reference: runtime/zero/mics.py.
    mics_shard_size: int = ds_field(-1)
    mics_hierarchical_params_gather: bool = False
    memory_efficient_linear: bool = True
    param_persistence_threshold_auto: bool = False

    def validate(self):
        if self.cpu_offload is not None and self.offload_optimizer.device == "none":
            self.offload_optimizer.device = "cpu" if self.cpu_offload else "none"
        if self.cpu_offload_params is not None and self.offload_param.device == "none":
            self.offload_param.device = "cpu" if self.cpu_offload_params else "none"
        if self.overlap_comm is None:
            self.overlap_comm = self.stage == 3


@dataclass
class ActivationCheckpointingConfig(DeepSpeedConfigModel):
    """Reference: ``runtime/activation_checkpointing/checkpointing.py`` config block."""
    partition_activations: bool = False
    cpu_checkpointing: bool = False
    contiguous_memory_optimization: bool = False
    number_checkpoints: Optional[int] = None
    synchronize_checkpoint_boundary: bool = False
    profile: bool = False


@dataclass
class CommsLoggerConfig(DeepSpeedConfigModel):
    """Reference: ``utils/comms_logging.py`` + comms_logger section."""
    enabled: bool = False
    verbose: bool = False
    prof_all: bool = True
    debug: bool = False
    prof_ops: List[str] = ds_field(default_factory=list)


@dataclass
class FlopsProfilerConfig(DeepSpeedConfigModel):
    """Reference: ``profiling/config.py``."""
    enabled: bool = False
    recompute_fwd_factor: float = ds_field(0.0, ge=0.0)
    profile_step: int = ds_field(1, ge=0)
    module_depth: int = -1
    top_modules: int = ds_field(1, ge=1)
    detailed: bool = True
    output_file: Optional[str] = None


@dataclass
class TensorBoardConfig(DeepSpeedConfigModel):
    enabled: bool = False
    output_path: str = ""
    job_name: str = "DeepSpeedJobName"


@dataclass
class WandbConfig(DeepSpeedConfigModel):
    enabled: bool = False
    group: Optional[str] = None
    team: Optional[str] = None
    project: str = "deepspeed_tpu"


@dataclass
class CSVConfig(DeepSpeedConfigModel):
    enabled: bool = False
    output_path: str = ""
    job_name: str = "DeepSpeedJobName"


@dataclass
class OptimizerConfig(DeepSpeedConfigModel):
    type: Optional[str] = None
    params: Dict[str, Any] = ds_field(default_factory=dict)
    legacy_fusion: bool = False


@dataclass
class SchedulerConfig(DeepSpeedConfigModel):
    type: Optional[str] = None
    params: Dict[str, Any] = ds_field(default_factory=dict)


@dataclass
class PipelineConfig(DeepSpeedConfigModel):
    """Pipeline-engine knobs. Reference: engine pipeline section + ``runtime/pipe``."""
    stages: str = "auto"
    partition: str = "best"
    seed_layers: bool = False
    activation_checkpoint_interval: int = ds_field(0, ge=0)
    pipe_partitioned: bool = True
    grad_partitioned: bool = True
    use_reentrant: bool = True
    # "1f1b": O(stages) activation memory, manual interleaved fwd/bwd clocks
    # (reference TrainSchedule semantics, schedule.py:189); "gpipe": all-
    # forward scan then autodiff (O(microbatches) activation memory)
    schedule: str = "1f1b"


@dataclass
class HybridEngineConfig(DeepSpeedConfigModel):
    """RLHF hybrid engine (reference ``runtime/hybrid_engine.py`` config):
    one engine flipping between ZeRO training and TP inference over the
    same live weights."""
    enabled: bool = False
    max_out_tokens: int = ds_field(512, ge=1)
    inference_tp_size: int = ds_field(1, ge=1)
    release_inference_cache: bool = False
    pin_parameters: bool = True  # n/a on TPU (no pinned host staging); kept for config parity
    tp_gather_partition_size: int = ds_field(8, ge=1)


@dataclass
class MeshConfig(DeepSpeedConfigModel):
    """TPU-native: named mesh-axis sizes replacing the reference's mpu/rank-grid.

    A size of -1 on exactly one axis means "absorb all remaining devices".
    ``fsdp`` is the ZeRO sharding axis; when left at 1 while ``zero_optimization.stage>0``,
    the engine folds it into ``data`` (param/optimizer shards over the data axis,
    matching the reference semantics of ZeRO over the DP group).
    """
    data: int = -1
    fsdp: int = 1
    tensor: int = 1
    pipe: int = 1
    expert: int = 1
    seq: int = 1
    context: int = 1  # ring-attention context parallelism (superset feature)
    axis_order: List[str] = ds_field(
        default_factory=lambda: ["pipe", "data", "fsdp", "expert", "seq", "context", "tensor"])


@dataclass
class AIOConfig(DeepSpeedConfigModel):
    """Reference: ``runtime/swap_tensor/aio_config.py``."""
    block_size: int = ds_field(1048576, ge=1)
    queue_depth: int = ds_field(8, ge=1)
    thread_count: int = ds_field(1, ge=1)
    single_submit: bool = False
    overlap_events: bool = True
    use_gds: bool = False


@dataclass
class CheckpointConfig(DeepSpeedConfigModel):
    tag_validation: str = "Warn"  # Ignore | Warn | Fail
    load_universal: bool = False
    use_node_local_storage: bool = False
    parallel_write_pipeline: bool = False
    async_save: bool = False
    # msgpack | orbax | auto ("auto": orbax when multi-process — per-shard
    # tensorstore writes — else msgpack). async_save wraps either with the
    # background-commit engine (reference Nebula analogue).
    engine: str = "auto"


@dataclass
class DataTypesConfig(DeepSpeedConfigModel):
    grad_accum_dtype: Optional[str] = None


@dataclass
class EigenvalueConfig(DeepSpeedConfigModel):
    """Reference ``runtime/config.py:564 get_eigenvalue_config`` (MoQ
    curvature signal; consumed by ``runtime/eigenvalue.py``)."""
    enabled: bool = False
    verbose: bool = False
    max_iter: int = 100
    tol: float = 1e-2
    stability: float = 1e-6
    gas_boundary_resolution: int = 1
    layer_name: str = "layer_"
    layer_num: int = 0


@dataclass
class AutotuningConfig(DeepSpeedConfigModel):
    """Reference: ``autotuning/config.py``."""
    enabled: bool = False
    start_step: Optional[int] = None
    end_step: Optional[int] = None
    metric_path: Optional[str] = None
    arg_mappings: Optional[Dict[str, str]] = None
    metric: str = "throughput"
    model_info: Optional[Dict[str, Any]] = None
    results_dir: str = "autotuning_results"
    exps_dir: str = "autotuning_exps"
    overwrite: bool = False
    fast: bool = True
    start_profile_step: int = 3
    end_profile_step: int = 5
    tuner_type: str = "gridsearch"
    tuner_early_stopping: int = 5
    tuner_num_trials: int = 50
    max_train_batch_size: Optional[int] = None
    min_train_batch_size: int = 1
    max_train_micro_batch_size_per_gpu: Optional[int] = None
    min_train_micro_batch_size_per_gpu: int = 1
    num_tuning_micro_batch_sizes: int = 3


@dataclass
class ElasticityConfig(DeepSpeedConfigModel):
    """Reference: ``elasticity/config.py``."""
    enabled: bool = False
    max_train_batch_size: int = 2000
    micro_batch_sizes: List[int] = ds_field(default_factory=lambda: [2, 4, 6])
    min_gpus: int = 1
    max_gpus: int = 10000
    min_time: int = 0
    prefer_larger_batch: bool = True
    ignore_non_elastic_batch_info: bool = False
    version: float = 0.1
    # v0.2 (node-granular) knobs; "gpus" kept for config-key parity — on TPU
    # these count chips
    num_gpus_per_node: int = 1
    model_parallel_size: int = 1


def _load_config_dict(config: Union[str, Dict]) -> Dict:
    if isinstance(config, dict):
        return dict(config)
    if isinstance(config, str):
        if not os.path.exists(config):
            raise FileNotFoundError(f"DeepSpeed config path does not exist: {config}")
        with open(config) as f:
            return json.load(f)
    raise TypeError(f"Expected dict or path to JSON config, got {type(config)}")


class DeepSpeedConfig:
    """Parsed top-level config. Reference: ``runtime/config.py:705``."""

    def __init__(self, config: Union[str, Dict, None], mesh_shape: Optional[Dict[str, int]] = None,
                 world_size: Optional[int] = None):
        d = _load_config_dict(config or {})
        self._param_dict = d

        self.train_batch_size = d.get(TRAIN_BATCH_SIZE)
        self.train_micro_batch_size_per_gpu = d.get(TRAIN_MICRO_BATCH_SIZE_PER_GPU)
        self.gradient_accumulation_steps = d.get(GRADIENT_ACCUMULATION_STEPS)

        self.optimizer = OptimizerConfig.from_dict(d.get("optimizer", {}))
        self.scheduler = SchedulerConfig.from_dict(d.get("scheduler", {}))
        self.fp16 = FP16Config.from_dict(d.get("fp16", {}))
        self.bf16 = BF16Config.from_dict(d.get("bf16", d.get("bfloat16", {})))
        self.zero_config = ZeroConfig.from_dict(d.get("zero_optimization", {}))
        self.activation_checkpointing = ActivationCheckpointingConfig.from_dict(d.get("activation_checkpointing", {}))
        self.comms_logger = CommsLoggerConfig.from_dict(d.get("comms_logger", {}))
        self.flops_profiler = FlopsProfilerConfig.from_dict(d.get("flops_profiler", {}))
        self.tensorboard = TensorBoardConfig.from_dict(d.get("tensorboard", {}))
        self.wandb = WandbConfig.from_dict(d.get("wandb", {}))
        self.csv_monitor = CSVConfig.from_dict(d.get("csv_monitor", {}))
        self.pipeline = PipelineConfig.from_dict(d.get("pipeline", {}))
        self.hybrid_engine = HybridEngineConfig.from_dict(d.get("hybrid_engine", {}))
        self.mesh = MeshConfig.from_dict(d.get("mesh", mesh_shape or {}))
        # MiCS sugar (reference runtime/zero/mics.py): mics_shard_size=k IS
        # the mesh layout {fsdp: k, data: replicas}; size fsdp if unset.
        # (zero_config is parsed below; peek with the validated model here)
        _mics = ZeroConfig.from_dict(d.get("zero_optimization", {})).mics_shard_size
        if _mics > 0 and "fsdp" not in d.get("mesh", mesh_shape or {}):
            self.mesh.fsdp = int(_mics)
        self.aio = AIOConfig.from_dict(d.get("aio", {}))
        self.checkpoint_config = CheckpointConfig.from_dict(d.get("checkpoint", {}))
        self.data_types = DataTypesConfig.from_dict(d.get("data_types", {}))
        self.autotuning = AutotuningConfig.from_dict(d.get("autotuning", {}))
        self.elasticity = ElasticityConfig.from_dict(d.get("elasticity", {}))
        self.compression_config = d.get("compression_training", {})
        self.eigenvalue = EigenvalueConfig.from_dict(d.get("eigenvalue", {}))
        self.data_efficiency_config = d.get("data_efficiency", {})
        # legacy curriculum section (reference constants.py CURRICULUM_LEARNING_LEGACY)
        self.curriculum_learning_legacy = d.get("curriculum_learning", {})
        self.random_ltd_config = d.get("random_ltd", {})
        self.pld_config = d.get("progressive_layer_drop", {})

        self.gradient_clipping = float(d.get("gradient_clipping", 0.0))
        # one-dispatch fwd+bwd+optimizer step (engine auto-disables it when
        # accumulation/compression/offload/eigenvalue interpose)
        _fs = d.get("fused_step", True)
        if not isinstance(_fs, bool):
            raise ValueError(f"fused_step must be a boolean, got {_fs!r}")
        self.fused_step = _fs
        self.prescale_gradients = bool(d.get("prescale_gradients", False))
        self.gradient_predivide_factor = float(d.get("gradient_predivide_factor", 1.0))
        # accepted-but-moot (PARITY.md "Sparse gradients"): the embedding
        # vjp is a dense scatter-add fused into the compiled step and DP
        # reduction is a GSPMD psum/reduce-scatter; there is no separate
        # allreduce for a sparse path to shortcut
        self.sparse_gradients_enabled = bool(d.get("sparse_gradients", False))
        self.steps_per_print = int(d.get("steps_per_print", 10))
        self.wall_clock_breakdown = bool(d.get("wall_clock_breakdown", False))
        self.memory_breakdown = bool(d.get("memory_breakdown", False))
        self.dump_state = bool(d.get("dump_state", False))
        self.disable_allgather = bool(d.get("disable_allgather", False))
        self.communication_data_type = d.get("communication_data_type")
        self.seq_parallel_communication_data_type = d.get("seq_parallel_communication_data_type", "fp32")
        self.sequence_parallel_size = int(d.get("sequence_parallel_size", self.mesh.seq))
        self.gradient_accumulation_dtype = self.data_types.grad_accum_dtype
        self.train_micro_batch_size_per_gpu  # triangulated below

        if self.fp16.enabled and self.bf16.enabled:
            raise ValueError("fp16 and bf16 cannot both be enabled")

        self.world_size = world_size
        self._batch_assertion_done = False
        if world_size is not None:
            self.resolve_batch_sizes(self._dp_world_size_from(world_size))

    def _dp_world_size_from(self, world_size: int) -> int:
        m = self.mesh
        non_data = max(1, m.fsdp) * max(1, m.tensor) * max(1, m.pipe) * max(1, m.seq) * max(1, m.context)
        if m.data == -1:
            if world_size % non_data != 0:
                raise ValueError(f"world size {world_size} not divisible by non-data mesh axes product {non_data}")
            return (world_size // non_data) * max(1, m.fsdp)
        # ZeRO shards ride the fsdp axis but are still "data parallel" replicas for batch math
        return m.data * max(1, m.fsdp)

    def resolve_batch_sizes(self, dp_world_size: int):
        """Batch-size triangulation: micro × gas × dp == global.

        Reference: ``runtime/config.py:765`` ``_configure_train_batch_size``.
        """
        train = self.train_batch_size
        micro = self.train_micro_batch_size_per_gpu
        gas = self.gradient_accumulation_steps

        if train is not None and micro is not None and gas is not None:
            pass
        elif train is not None and micro is not None:
            gas = train // (micro * dp_world_size)
        elif train is not None and gas is not None:
            micro = train // (gas * dp_world_size)
        elif micro is not None and gas is not None:
            train = micro * gas * dp_world_size
        elif train is not None:
            gas = 1
            micro = train // dp_world_size
        elif micro is not None:
            gas = 1
            train = micro * dp_world_size
        else:
            train, micro, gas = dp_world_size, 1, 1

        self.train_batch_size, self.train_micro_batch_size_per_gpu, self.gradient_accumulation_steps = train, micro, gas
        if train != micro * gas * dp_world_size or min(train, micro, gas) < 1:
            raise ValueError(
                f"Batch sizes inconsistent: train_batch_size={train} != micro_batch={micro} * "
                f"gradient_accumulation_steps={gas} * dp_world_size={dp_world_size}")
        self._batch_assertion_done = True

    # -- convenience accessors mirroring the engine's config properties --
    @property
    def zero_enabled(self) -> bool:
        return self.zero_config.stage > 0

    @property
    def zero_optimization_stage(self) -> int:
        return self.zero_config.stage

    @property
    def precision_dtype(self):
        import jax.numpy as jnp
        if self.bf16.enabled:
            return jnp.bfloat16
        if self.fp16.enabled:
            return jnp.float16
        return jnp.float32

    def print_config(self):
        logger.info(f"DeepSpeedConfig: {json.dumps(self._param_dict, indent=2, default=str)}")
