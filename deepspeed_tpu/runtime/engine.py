"""The training engine.

Parity target: reference ``runtime/engine.py`` (``DeepSpeedEngine``, 3.6k
LoC) and ``deepspeed.initialize`` (``deepspeed/__init__.py:70``). The user
contract is identical —

    engine, _, loader, sched = deepspeed_tpu.initialize(model=..., config=...)
    loss = engine(batch); engine.backward(loss); engine.step()

— but the machinery is TPU-native: instead of eager autograd + per-param
grad hooks + hand-rolled collectives, the engine builds three compiled
functions (forward+backward, gradient accumulate, optimizer apply) whose
input/output shardings realize the configured ZeRO stage (see
``runtime/zero/partition.py``). XLA inserts all-gathers / reduce-scatters
where the reference had the IPG-bucket machinery
(``stage_1_and_2.py:927-1037``) and the stage-3 param coordinator.

Mixed precision follows the reference contract: fp32 master weights,
compute in bf16/fp16, fp32 grad accumulation, dynamic loss scaling for
fp16 with overflow-skip (``stage_1_and_2.py:1995``).
"""

import collections
import contextlib
import functools
import json
import os
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from .. import comm as dist
from ..accelerator import get_accelerator
from ..analysis import knobs
from ..analysis.jit_audit import leaf_signature
from ..models import transformer as layer_kinds
from ..parallel.mesh import MeshTopology, get_mesh_topology, initialize_mesh
from ..telemetry import MonitorBridge
from ..telemetry import get_registry as get_telemetry_registry
from ..telemetry import device_counts
from ..telemetry import profiler as device_profiler
from ..telemetry import span as telemetry_span
from ..telemetry.costs import first_call
from ..telemetry.tracing import PHASES, get_tracer, open_span, region, regions_traced, regions_traced_by, self_times
from ..telemetry.health import (GradNormSpikeDetector, NonFiniteLossDetector, StepStallDetector,
                                get_health_monitor)
from ..utils.compile_cache import register_cache_metrics
from ..utils.logging import log_dist, logger
from ..utils.timer import (BACKWARD_GLOBAL_TIMER, FORWARD_GLOBAL_TIMER, STEP_GLOBAL_TIMER, NoopTimer,
                           SynchronizedWallClockTimer, ThroughputTimer, TRAIN_BATCH_TIMER)
from .checkpoint_engine import create_checkpoint_engine
from .config import DeepSpeedConfig
from .dataloader import DeepSpeedDataLoader
from .fp16.loss_scaler import create_loss_scaler
from .lr_schedules import create_lr_scheduler
from .optimizers import create_optimizer
from .zero import overlap as zero_overlap
from .zero.partition import (batch_specs, plan_grad_specs, plan_opt_state_specs, plan_param_specs, specs_to_shardings)

MODEL_STATES_FILENAME = "model_states.msgpack"
OPTIM_STATES_FILENAME = "optim_states.msgpack"
CLIENT_STATE_FILENAME = "client_state.msgpack"
CURRICULUM_STATE_FILENAME = "curriculum_state.msgpack"
TRAIN_META_FILENAME = "train_meta.json"
LATEST_FILENAME = "latest"


# marker stored in _cached_grads when the fused one-dispatch step already
# consumed the gradients inside the forward() call
_FUSED = object()


def _cast_tree(tree, dtype):
    return jax.tree_util.tree_map(lambda x: x.astype(dtype) if hasattr(x, "astype") else x, tree)


def _put_divided(tree, shardings, release: bool):
    """``jax.device_put(tree, shardings, donate=release)`` that holds to the donation whatever the backend makes of the
    hint (a put that slices one chip's array onto several takes none): with ``release``, a leaf that was a committed array
    on FEWER devices than its sharding names is deleted once its shards are there, unless a shard IS its buffer (a small
    leaf that stays whole on every chip: the first chip's copy is the array handed in). A leaf that already lies where it
    is to lie, a numpy array and a leaf put onto as many devices as it had (one chip: an alias) are left alone."""
    put = jax.device_put(tree, shardings, donate=release)
    for was, now in zip(jax.tree_util.tree_leaves(tree) if release else (), jax.tree_util.tree_leaves(put)):
        if isinstance(was, jax.Array) and was is not now and not was.is_deleted() and len(was.sharding.device_set) < len(now.sharding.device_set):
            now.block_until_ready()
            held = {shard.data.unsafe_buffer_pointer() for shard in now.addressable_shards}
            if not any(shard.data.unsafe_buffer_pointer() in held for shard in was.addressable_shards):
                was.delete()
    return put


def _global_norm(tree):
    leaves = [jnp.sum(jnp.square(x.astype(jnp.float32))) for x in jax.tree_util.tree_leaves(tree)]
    return jnp.sqrt(jnp.sum(jnp.stack(leaves)))


def _all_finite(tree):
    leaves = [jnp.all(jnp.isfinite(x)) for x in jax.tree_util.tree_leaves(tree)]
    return jnp.all(jnp.stack(leaves))


def _declared(what, records=None):
    """What the kinds' records declare for the first-call line (``LayerKind.paths``, ``path_words`` or ``joined``) as one dict."""
    return {key: value for record in (layer_kinds.records() if records is None else records) for key, value in getattr(record, what).items()}


def _paths_traced(records=None):
    """{key of the records' ``paths``: (forward call sites traced as a kernel, in XLA's form) so far; key of their
    ``joined``: the count of each of its words so far}."""
    traced = {}
    for key, (name, labels) in _declared("paths", records).items():
        xla = int(regions_traced(name, path="xla", **labels))
        traced[key] = (int(regions_traced(name, **labels)) - xla, xla)
    for key, (name, words, *rest) in _declared("joined", records).items():
        label, also = rest[0] if rest else "path", rest[1] if len(rest) > 1 else {}
        if words is None:  # whatever values the sites gave the label: a number worked out where it is counted
            traced[key] = regions_traced_by(name, label)
        else:
            traced[key] = tuple(int(regions_traced(name, **{label: word}, **also)) for word in words)
    return traced


# of the table as imported: a key's word for its kernel form, and ``moe_router``'s words as ``_paths_traced`` counts them
_PATH_WORDS, _ROUTER_WORDS = _declared("path_words"), _declared("joined")["moe_router"][1]


def _program_text(program, args):
    """A function that gives the text of the executable ``program`` runs on
    ``args`` (``telemetry/profiler.py::region_card`` reads the regions off its
    instructions' metadata). Nothing is lowered until it is called: it keeps
    the arguments' shapes and shardings, not the arrays, and ``jax.jit``
    answers from the lowering and the executable it already has."""
    shapes = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding) if isinstance(x, jax.Array) else x, args)

    def text():
        jitted = program
        while not hasattr(jitted, "lower") and hasattr(jitted, "__wrapped__"):
            jitted = jitted.__wrapped__
        return jitted.lower(*shapes).compile().as_text()

    return text


def _period_split(since: float, period_s: float) -> Dict:
    """Where a step's period went, off the span ring: the trainer's spans that
    began at or after ``since``, in seconds by span and phase (``forward.self``:
    the span's self time less its phases), ``first_calls`` (the first calls
    that began in it) and ``outside_s``: the period less every trainer span,
    which is the caller's code, its data and its wait on a loss."""
    spans = [s for s in get_tracer().spans() if s["start_s"] >= since]
    own, split, spent = self_times(spans), {}, 0.0
    for s in spans:
        if not s["name"].startswith("train/"):
            continue
        part, phases = s["name"][len("train/"):], s["attrs"].get("phase_s") or {}
        spent += s["dur_s"]
        for name, sec in phases.items():
            split[f"{part}.{name}"] = split.get(f"{part}.{name}", 0.0) + sec
        rest = part if part == "backward" else part + ".self"  # backward has no phases
        split[rest] = split.get(rest, 0.0) + max(0.0, own[s["id"]] - sum(phases.values()))
    split = {k: round(v, 6) for k, v in split.items()}
    split.update(first_calls=sum(s["name"] == "program/first_call" for s in spans), outside_s=round(period_s - spent, 6))
    return split


def _batch_tokens(batch) -> int:
    """Token count of a microbatch from shape metadata only (never reads
    device data, so it is safe on the dispatch path)."""
    ids = batch.get("input_ids") if isinstance(batch, dict) else None
    shape = getattr(ids, "shape", None)
    if shape is not None and len(shape) >= 2:
        return int(shape[0]) * int(shape[1])
    return 0


_NOTHING = contextlib.nullcontext()  # what ``_first`` hands a program that has had its first call


def _rooted(init):
    """An engine class's ``__init__`` under ONE ``init/engine`` span, whichever class of the family is built (a subclass's
    constructor opens it and ``DeepSpeedEngine``'s, called from inside, finds it open): the three ``init/*`` spans are its
    children, its self time is the rest of construction, and as it closes ``engine_init_seconds_total{part="rest"}`` rises
    by that (``_init_part`` raises the other parts: the four sum to the span's seconds). Ahead of the span: the listeners of
    the first calls' seconds, and the device profiler's arming (``DS_TPU_PROFILE=setup`` starts its capture here)."""

    @functools.wraps(init)
    def __init__(self, *args, **kwargs):
        if getattr(self, "_init_parts_s", None) is not None:  # a subclass's constructor holds the root
            return init(self, *args, **kwargs)
        register_cache_metrics(jax)  # seconds of every first call, by phase (program_*_seconds_total)
        device_profiler.maybe_arm_profiler()  # DS_TPU_PROFILE=1: the first steps that make no first call are captured
        self._init_parts_s = 0.0
        t0 = time.perf_counter()
        with telemetry_span("init/engine"):
            init(self, *args, **kwargs)
        rest = time.perf_counter() - t0 - self._init_parts_s
        get_telemetry_registry().counter("engine_init_seconds_total", part="rest").inc(rest)
        self._init_parts_s = None

    return __init__


class DeepSpeedEngine:
    """Wraps a model (loss function + params) with distributed training state."""

    def __init_subclass__(cls, **kwargs):
        super().__init_subclass__(**kwargs)
        if "__init__" in cls.__dict__:
            cls.__init__ = _rooted(cls.__dict__["__init__"])

    @contextlib.contextmanager
    def _init_part(self, part):
        """The span ``init/<part>`` of construction; as it closes, ``engine_init_seconds_total{part}`` rises by its wall
        seconds. Nothing here waits for the device: a part that only dispatches is timed as its dispatch."""
        t0 = time.perf_counter()
        with telemetry_span(f"init/{part}") as sp:
            yield sp
        took = time.perf_counter() - t0
        self._init_parts_s += took
        get_telemetry_registry().counter("engine_init_seconds_total", part=part).inc(took)

    @_rooted
    def __init__(self,
                 args=None,
                 model=None,
                 optimizer=None,
                 model_parameters=None,
                 training_data=None,
                 lr_scheduler=None,
                 mesh=None,
                 mpu=None,
                 dist_init_required: Optional[bool] = None,
                 collate_fn=None,
                 config=None,
                 dont_change_device: bool = False):
        with self._init_part("mesh"):
            if dist_init_required is None or dist_init_required:
                dist.init_distributed(verbose=False)

            self.config = config if isinstance(config, DeepSpeedConfig) else DeepSpeedConfig(config)
            self.topology: MeshTopology = mesh if isinstance(mesh, MeshTopology) else initialize_mesh(self.config.mesh)
            from .zero.mics import validate_mics_mesh

            validate_mics_mesh(self.config, self.topology)
            self.config.resolve_batch_sizes(self.topology.data_parallel_size)
            dist.configure(self.config)

        self.module = model
        self.client_optimizer = optimizer
        self.client_lr_scheduler = lr_scheduler
        self.training_dataloader = None
        self.collate_fn = collate_fn

        # --- loss function contract ---
        if callable(getattr(model, "loss_fn", None)):
            self._loss_fn = model.loss_fn
        elif callable(model):
            self._loss_fn = model
        else:
            raise TypeError("model must be callable (params, batch, rng) -> loss, or expose .loss_fn")

        # --- parameters (fp32 master, sharded per plan) ---
        # every program construction sends to the backend is inside a first call of family ``init`` (the init fn's, the
        # casts', whatever a divided put lowers, the optimizer state's): the same span, seconds by phase and log line as a
        # step program's, so that what reached the backend in NO span is the caller's alone
        with self._init_part("shard_state") as sp:
            if model_parameters is None:
                raise ValueError("model_parameters (the parameter pytree, or an init fn taking a PRNG key) is required")
            if callable(model_parameters) and not hasattr(model_parameters, "keys"):
                # documented init-fn form, resolved HERE so every engine class
                # (pipeline/hybrid subclasses included) honors it with the
                # accelerator's configured seed
                with sp.phase("cast"), first_call("init", "model_init"):
                    model_parameters = model_parameters(jax.random.PRNGKey(get_accelerator().initial_seed()))
            params_host = model_parameters
            tp_rules = model.partition_rules() if hasattr(model, "partition_rules") else []
            self._tp_rules = tp_rules
            with sp.phase("cast"), first_call("init", "cast"):
                params_host = _cast_tree(params_host, jnp.float32)
            with sp.phase("plan"), first_call("init", "plan"):  # no program: the tree's abstract evaluation is a trace JAX times
                param_shapes = jax.eval_shape(lambda: params_host)
                self.param_specs = plan_param_specs(param_shapes, self.config, self.topology, tp_rules)
                self.param_shardings = specs_to_shardings(self.param_specs, self.topology)

                # ZeRO-3 parameter offload: large leaves stored in pinned host
                # memory, streamed to HBM inside each compiled step (reference
                # partitioned_param_swapper.py:36, wired at stage3.py:583)
                from .zero.param_offload import maybe_enable_param_offload
                from .zero.zeropp import zeropp_applicable as _zpp_applicable

                # gate on the path that will actually run: merely *requesting* ZeRO++
                # on an ineligible topology falls back to GSPMD, where offload works
                _zpp_active = (_zpp_applicable(self.config, self.topology)[0]
                               and not self.config.compression_config)
                if _zpp_active and self.config.zero_config.offload_param.device in ("cpu", "nvme"):
                    logger.warning("offload_param is incompatible with the ZeRO++ manual shard_map path — "
                                   "parameters stay in device memory")
                    self.param_store_shardings, self._param_offload = self.param_shardings, False
                else:
                    self.param_store_shardings, self._param_offload = maybe_enable_param_offload(
                        self.config, self.topology, self.param_shardings, param_shapes)
            # the tree handed in is the engine's from here on, as the reference's ZeRO-3 partitions a module's parameters in
            # place: where it is DIVIDED (a tree that lies on one chip, put over several), each leaf's whole copy is let go
            # as its shards stand, so the first chip never holds the tree beside its share of it, the moments and the carried
            # copy (``_put_divided``). On one chip the put is an alias and nothing is let go
            with sp.phase("place"), first_call("init", "place"):
                self.params = _put_divided(params_host, self.param_store_shardings, release=self.config.zero_config.stage == 3)
            del params_host

            with sp.phase("plan"):  # the gradients' plan: the phase's seconds are the two stretches' sum
                self.grad_specs = plan_grad_specs(param_shapes, self.param_specs, self.config, self.topology)
                self.grad_shardings = specs_to_shardings(self.grad_specs, self.topology)

        # --- optimizer ---
        with self._init_part("optimizer") as sp:
            with sp.phase("build"):  # the transformation; with the optimizer offloaded, the host's copy of the tree (a wait: `device_get`)
                if optimizer is not None and not isinstance(optimizer, optax.GradientTransformation):
                    raise TypeError("client optimizer must be an optax.GradientTransformation")
                self.optimizer = optimizer if optimizer is not None else create_optimizer(
                    self.config.optimizer.type, self.config.optimizer.params)

                # ZeRO-Offload: optimizer states leave the device entirely
                # (reference stage_1_and_2.py:1182-1277 cpu, stage3.py:1877 nvme)
                self._host_offload = None
                off = self.config.zero_config.offload_optimizer
                if self.config.zero_enabled and off.device in ("cpu", "nvme"):
                    opt_name = (self.config.optimizer.type or "adamw").lower()
                    if optimizer is not None:
                        logger.warning("offload_optimizer requires a config-defined adam-family optimizer; a client "
                                       "optimizer object was passed — keeping optimizer states on device")
                    elif "adam" not in opt_name:
                        logger.warning(f"offload_optimizer supports adam-family optimizers; got {opt_name} — "
                                       "keeping optimizer states on device")
                    else:
                        from .zero.offload import HostOffloadOptimizer

                        off_p = self.config.zero_config.offload_param
                        self._host_offload = HostOffloadOptimizer(jax.device_get(self.params),
                                                                  self.config.optimizer.params, offload_device=off.device,
                                                                  nvme_path=off.nvme_path,
                                                                  aio_threads=self.config.aio.thread_count,
                                                                  pipeline=off.pipeline_read or off.pipeline_write,
                                                                  params_on_nvme=(off_p.device == "nvme"
                                                                                  and bool(self._param_offload)),
                                                                  params_nvme_path=off_p.nvme_path)
            with sp.phase("init_state"):
                if self._host_offload is None:
                    with first_call("init", "optimizer_state"):  # the state's plan (an abstract ``init``: a trace) and its program
                        opt_specs, _ = plan_opt_state_specs(self.optimizer, param_shapes, self.param_specs, self.config,
                                                            self.topology)
                        self.opt_state_shardings = specs_to_shardings(opt_specs, self.topology)
                        self.opt_state = jax.jit(self.optimizer.init, out_shardings=self.opt_state_shardings)(self.params)
                else:
                    self.opt_state_shardings = None
                    self.opt_state = None

        # --- lr scheduler ---
        self.lr_scheduler = lr_scheduler
        if self.lr_scheduler is None and self.config.scheduler.type:
            self.lr_scheduler = create_lr_scheduler(self.config.scheduler.type, self.config.scheduler.params)
        self._base_lr = self.config.optimizer.params.get("lr", 1e-3) if self.config.optimizer.params else 1e-3
        if self.lr_scheduler is not None and hasattr(self.lr_scheduler, "set_base_lr"):
            self.lr_scheduler.set_base_lr(self._base_lr)

        # --- precision ---
        self.compute_dtype = self.config.precision_dtype
        self.loss_scaler = create_loss_scaler(self.config.fp16, self.compute_dtype)
        self.communication_data_type = self.config.communication_data_type

        # --- counters / timers ---
        self.micro_steps = 0
        self.global_steps = 0
        self.global_samples = 0
        self._skipped_host = 0
        self._skipped_dev = None  # lazily-summed device overflow flags (static-scale path)
        self._last_overflow = None  # latest applied step's overflow flag (None = no step applied yet)
        self._lr_override = None  # one-shot manual lr (set_lr) consumed by the next step
        self._accum_base = 0  # micro_steps value at the start of the current accumulation regime
        self._grad_acc = None
        self._cached_grads = None
        self._last_loss = None
        self._global_grad_norm = None
        self.gradient_accumulation_steps = self.config.gradient_accumulation_steps
        self.train_batch_size = self.config.train_batch_size
        self.train_micro_batch_size_per_gpu = self.config.train_micro_batch_size_per_gpu

        self.wall_clock_breakdown = self.config.wall_clock_breakdown
        self.timers = SynchronizedWallClockTimer() if self.wall_clock_breakdown else NoopTimer()
        self.tput_timer = ThroughputTimer(
            config=type("TC", (), {"enabled": True})(), batch_size=self.train_batch_size,
            steps_per_output=self.config.steps_per_print)

        self._rng = jax.random.PRNGKey(get_accelerator().initial_seed())
        self.checkpoint_engine = create_checkpoint_engine(self.config)
        self.monitor = self._configure_monitor()
        self.flops_profiler = None  # built lazily at the configured profile step

        # --- telemetry (docs/OBSERVABILITY.md) ---
        # handles resolved once; per-step cost is attribute checks + float
        # adds. Gauges that need a device->host sync (loss, grad norm) are
        # only set where a sync already happens (_report / monitor flush).
        tele = get_telemetry_registry()
        self.telemetry = tele
        self._m_steps = tele.counter("train_steps_total")
        self._m_tokens = tele.counter("train_tokens_total")
        self._m_overflow = tele.counter("train_overflow_steps_total")
        self._m_loss_scale = tele.gauge("train_loss_scale")
        self._m_lr = tele.gauge("train_lr")
        self._m_loss = tele.gauge("train_loss")
        self._m_gnorm = tele.gauge("train_grad_norm")
        self._m_tps = tele.gauge("train_tokens_per_sec")
        self._m_mfu = tele.gauge("train_mfu")
        self._m_heartbeat = tele.gauge("last_step_completed_unix")
        self._m_grad_sync_bytes = tele.counter("comm_bytes_total", op="grad_sync_estimated")
        self._last_microbatch_tokens = 0
        self._last_step_pc = None
        self._step_end_pc = None  # the end of the last ``step()``: a step's period, for the stall detector, runs from there
        # analytic fwd+bwd FLOPs for the MFU gauge: traced once per batch
        # shape (keyed on token count) via the same jaxpr walk the serving
        # cost cards use; 0 means unavailable/disabled and the gauge stays 0
        self._step_flops = 0
        self._step_flops_by_phase = {}
        self._step_flops_tokens = -1
        self._made_first_call = False
        self._step_programs_seen = set()  # (program, batch shapes) that have had their first call
        self._peak_flops: Optional[float] = None
        self._monitor_bridge = MonitorBridge(
            tele, self.monitor,
            every_n_steps=knobs.get_int("DS_TPU_TELEMETRY_FLUSH_STEPS"))
        # health sentinels observe at the SAME host-sync points as the
        # gauges above — anomaly detection never adds a device readback
        self.health = get_health_monitor()
        self.health.ensure_detector(NonFiniteLossDetector())
        self.health.ensure_detector(GradNormSpikeDetector())
        self.health.ensure_detector(StepStallDetector()).reset()  # a new engine's periods are a new series
        # live ops plane: introspection server (DS_TPU_OPS_PORT) and
        # flight recorder (DS_TPU_FLIGHT_DIR) — a NaN loss mid-run leaves
        # a black-box capture behind. Both default off.
        from ..telemetry.ops_plane import maybe_start_ops_server
        from ..telemetry.flight import maybe_attach_flight_recorder
        maybe_start_ops_server()
        maybe_attach_flight_recorder(self.health)

        # legacy curriculum learning (reference engine.py:1821-1833): the
        # scheduler's difficulty is a sequence length; forward() truncates
        # batches to it (each new length = one XLA re-specialization,
        # bounded by schedule_config.difficulty_step)
        self.curriculum_scheduler = None
        cl = self.config.curriculum_learning_legacy
        if cl.get("enabled", False):
            from .data_pipeline.curriculum_scheduler import CurriculumScheduler

            self.curriculum_scheduler = CurriculumScheduler(cl)
            self._curriculum_type = cl.get("curriculum_type", "seqlen")

        # random-LTD (reference engine.py:344-348): the engine owns the
        # kept-seq-length scheduler; models apply the token routing via
        # data_pipeline.data_routing.apply_random_ltd
        self.random_ltd_scheduler = None
        rltd = self.config.random_ltd_config
        if rltd.get("enabled", False):
            from .data_pipeline.data_routing.scheduler import RandomLTDScheduler

            self.random_ltd_scheduler = RandomLTDScheduler(rltd)

        # progressive layer drop (reference engine.py:1821 pld kwargs
        # injection): engine owns the theta schedule; forward() threads the
        # current theta into the batch as a traced scalar
        self.progressive_layer_drop = None
        pld_cfg = self.config.pld_config
        if pld_cfg.get("enabled", False):
            from .progressive_layer_drop import ProgressiveLayerDrop

            self.progressive_layer_drop = ProgressiveLayerDrop(theta=pld_cfg.get("theta", 0.5),
                                                               gamma=pld_cfg.get("gamma", 0.001))
            if not (hasattr(model, "cfg") and hasattr(model, "module")):
                # theta rides in the batch under the CausalLM convention; a
                # custom loss_fn that never reads it silently trains at
                # full depth
                log_dist("progressive_layer_drop: model does not look like models.CausalLM — "
                         "ensure its loss_fn consumes batch['pld_theta'] or PLD is a no-op", ranks=[0])

        # --- training data ---
        if training_data is not None:
            self.training_dataloader = self.deepspeed_io(training_data)

        # compression training (reference compression/compress.py): a pure
        # params transform applied inside the differentiated loss
        self.compression_engine = None
        if self.config.compression_config:
            from ..compression.compress import CompressionEngine

            model_cfg = getattr(model, "cfg", None)
            self.compression_engine = CompressionEngine(self.params, self.config.compression_config,
                                                        num_heads=getattr(model_cfg, "n_heads", None))

        # Hessian-eigenvalue curvature signal (reference engine.py:217,335)
        self.eigenvalue = None
        self.block_eigenvalue: Dict[str, float] = {}
        if self.config.eigenvalue.enabled:
            from .eigenvalue import Eigenvalue

            ev = self.config.eigenvalue
            n_layers = ev.layer_num or getattr(getattr(model, "cfg", None), "n_layers", 0)
            self.eigenvalue = Eigenvalue(verbose=ev.verbose, max_iter=ev.max_iter, tol=ev.tol,
                                         stability=ev.stability,
                                         gas_boundary_resolution=ev.gas_boundary_resolution,
                                         layer_name=ev.layer_name, layer_num=n_layers)

        # reference wires checkpointing.configure from the engine too;
        # unconditional so a previous engine's flags never leak into this
        # one through the module-level config
        from .activation_checkpointing import configure as _ac_configure

        _ac_configure(deepspeed_config=self.config)

        self._build_compiled_fns()
        log_dist(
            f"DeepSpeedEngine: stage={self.zero_optimization_stage()} dtype={self.compute_dtype.__name__} "
            f"micro_bs={self.train_micro_batch_size_per_gpu} gas={self.gradient_accumulation_steps} "
            f"global_bs={self.train_batch_size} mesh={self.topology.axis_sizes}", ranks=[0])

    @property
    def params(self):
        """The float32 master weights."""
        return self._params

    @params.setter
    def params(self, tree):
        # whoever replaces the master from outside a step (construction, a checkpoint's load, the host optimizer) drops
        # the compute copy that was cast from the old one; a step that carries the copy installs both (``_install``)
        self._params, self._params_c = tree, None

    def _install(self, params, params_c):
        """A step's outputs: the new master and the compute copy its update wrote from it (None where none is carried)."""
        self._params, self._params_c = params, params_c

    def _compute_params(self):
        """What a step differentiates at and an evaluation runs on: the carried compute copy, cast from the master once
        when there is none yet; the master itself where no copy is carried (the program then casts it)."""
        if self._params_c is None and self._cast_copy is not None:
            with self._first("compute_copy", family="init"):
                self._params_c = self._cast_copy(self._params)
        return self._params if self._params_c is None else self._params_c

    def _first(self, name, family="train"):
        """The first call of one of the engine's small programs beside ``_step_program``'s: a ``program/first_call`` span, so
        that no program of the engine's reaches the backend outside one, and the step it falls in is passed by as one that
        made a first call. Family ``train``: a step that is not fused (the accumulator's cast and add, the update) and an
        evaluation. Family ``init``: what sets the engine's own state up once it is built (the first compute copy's cast in
        the first forward, the overflow count's cast and sum in the first two steps): its wall seconds are
        ``engine_init_seconds_total{part="after"}``, and the first line of family ``train`` stays the step's. Afterwards
        nothing: one look into a set."""
        if name in self._step_programs_seen:
            return _NOTHING
        self._step_programs_seen.add(name)
        self._made_first_call = True
        return first_call("train", name) if family == "train" else self._after(name)

    @contextlib.contextmanager
    def _after(self, name):
        t0 = time.perf_counter()
        with first_call("init", name):
            yield
        get_telemetry_registry().counter("engine_init_seconds_total", part="after").inc(time.perf_counter() - t0)

    # ------------------------------------------------------------------
    # compiled functions
    # ------------------------------------------------------------------
    def _build_compiled_fns(self):
        loss_fn = self._loss_fn
        compute_dtype = self.compute_dtype
        comp = self.compression_engine
        base_rng = self._rng

        from .zero.param_offload import fetch_params

        store_shardings = self.param_store_shardings
        jit_stream = self._param_offload == "jit"
        # jit mode: compiled fns consume the host store directly (fetch is
        # traced in, updated params stream back via host-kind out_shardings).
        # eager mode: compiled fns are plain device functions and the swap
        # happens in wrappers built at the end of this method.
        param_out_shardings = store_shardings if jit_stream else self.param_shardings

        def _fetch(params32):
            # host->HBM stream of offloaded leaves, traced into the jit so
            # XLA overlaps the DMA with compute (grads are taken w.r.t. the
            # fetched device copy, so they land in device memory)
            return fetch_params(params32, store_shardings) if jit_stream else params32

        # overlap_comm: how the step's backward reduces weight gradients (zero/overlap.py); None: as XLA partitions it
        gather_plan = None
        if comp is None and not self._param_offload:
            gather_plan = zero_overlap.plan_for(self.config, self.topology, self.param_specs)

        from .zero.zeropp import build_zeropp_fwd_bwd, zeropp_applicable, zeropp_requested

        use_zeropp, zeropp_reason = zeropp_applicable(self.config, self.topology)
        if use_zeropp and comp is not None:
            use_zeropp = False
            zeropp_reason = "compression_training and ZeRO++ manual path are mutually exclusive"
        if zeropp_requested(self.config) and not use_zeropp:
            log_dist(f"ZeRO++ requested but falling back to GSPMD path: {zeropp_reason}", ranks=[0])

        # A parameter's bytes cross between master and compute precision once a step, inside the update's own fusion: the
        # step differentiates AT the compute copy (the gradient has its dtype, and its consumers upcast inside their own
        # fusions) and the update writes the next step's copy beside the new master. The copy is carried where one exists
        # and the master lies on the device: not under fp32 compute (no copy), parameter or optimizer offload (the master
        # is kept OFF the device on purpose, or is replaced on the host), compression (it rewrites the copy every step),
        # nor where ZeRO stage 1 or 2 spreads the optimizer's state over chips that each hold the whole master: the update
        # then runs on a shard and its results are all-gathered, the copy's 2 bytes on top of the master's 4 (seen in the
        # executable for four described chips), which costs more than casting the gathered master as the step always did.
        cast = lambda tree: _cast_tree(tree, compute_dtype)
        state_on_shards = self.config.zero_config.stage in (1, 2) and self.topology.data_parallel_size > 1
        self._cast_copy = None  # master -> the carried compute copy (``_compute_params``); None: nothing is carried
        if (compute_dtype != jnp.float32 and comp is None and not use_zeropp and not self._param_offload
                and self._host_offload is None and not state_on_shards):
            self._cast_copy = jax.jit(cast, out_shardings=self.param_shardings)
        copy_shardings = None if self._cast_copy is None else self.param_shardings
        # grad-accumulation dtype (reference data_types.grad_accum_dtype,
        # config.py:898): bf16 halves the accumulator's HBM footprint and
        # add bandwidth across the gas window; the optimizer math still
        # runs fp32 (apply_updates upcasts). Default fp32.
        _acc_names = {None: jnp.float32, "fp32": jnp.float32, "float32": jnp.float32,
                      "bf16": jnp.bfloat16, "bfloat16": jnp.bfloat16,
                      "fp16": jnp.float16, "float16": jnp.float16, "half": jnp.float16}
        acc_name = self.config.gradient_accumulation_dtype
        if acc_name not in _acc_names:
            raise ValueError(f"data_types.grad_accum_dtype must be one of "
                             f"{sorted(k for k in _acc_names if k)}, got {acc_name!r}")
        self._grad_acc_dtype = _acc_names[acc_name]
        self._to_acc_dtype = None
        if self._grad_acc_dtype != jnp.float32:
            self._to_acc_dtype = jax.jit(
                lambda g: jax.tree_util.tree_map(lambda x: x.astype(self._grad_acc_dtype), g),
                out_shardings=self.grad_shardings)

        def scaled_loss_fn(params_c, batch, rng, scale, comp_state):
            if comp is not None:
                params_c = comp.apply(params_c, comp_state)
            # read by the model while its loss is traced: the gather plan, and who takes what it counts on the device
            with zero_overlap.active(gather_plan), device_counts.collecting() as reported:
                loss = loss_fn(params_c, batch, rng)
            return (loss * scale).astype(jnp.float32), ((loss, reported) if reported else loss)  # nothing counted: the loss alone

        copy_path = "cast" if self._cast_copy is None else "carried_copy"

        def differentiate(params, batch, step, scale, comp_state, grads_dtype):
            """(loss and what the model reported, gradients in ``grads_dtype``) at the compute copy. ``params`` is the
            carried copy, or the master, which is cast here, OUTSIDE the differentiated function (a copy is cast to
            itself: nothing), so the cotangent that comes back has the compute dtype."""
            rng = jax.random.fold_in(base_rng, step)  # rng derivation lives inside the jit: one less per-step dispatch
            with region("optimizer", path=copy_path, grads=jnp.dtype(grads_dtype).name):
                params_c = cast(params)
            (_, loss_and_reported), grads = jax.value_and_grad(scaled_loss_fn, has_aux=True)(
                params_c, batch, rng, scale, comp_state)
            with region("optimizer"):
                return loss_and_reported, _cast_tree(grads, grads_dtype)

        def fwd_bwd(params, batch, step, scale, comp_state):
            # A gradient that LEAVES its program is float32, widened inside the program that made it, ahead of the
            # sharding its outputs ask for: op for op the program that differentiated at the master through the cast, so
            # no cross-chip reduction's dtype falls and the accumulator sums what it always did. (Inside ``fused_step``
            # it never leaves: the update reads the cotangent as the backward made it, in the compute dtype.)
            return differentiate(_fetch(params), batch, step, scale, comp_state, jnp.float32)

        if use_zeropp:
            zpp = build_zeropp_fwd_bwd(loss_fn, self.param_specs, self.grad_specs,
                                       self.topology, self.config, compute_dtype)
            self._fwd_bwd = lambda p, b, step, s: zpp(p, b, jax.random.fold_in(base_rng, step), s)
        elif comp is None:
            self._fwd_bwd = jax.jit(lambda p, b, step, s: fwd_bwd(p, b, step, s, None),
                                    out_shardings=(None, self.grad_shardings))
        else:
            self._fwd_bwd_comp = jax.jit(fwd_bwd, out_shardings=(None, self.grad_shardings))
            self._fwd_bwd = lambda p, b, step, s: self._fwd_bwd_comp(p, b, step, s, comp.comp_state())

        def accumulate(acc, grads):
            return jax.tree_util.tree_map(lambda a, g: a + g.astype(a.dtype), acc, grads)

        self._accumulate = jax.jit(accumulate, donate_argnums=(0,), out_shardings=self.grad_shardings)

        clip = self.config.gradient_clipping
        opt = self.optimizer

        def apply_updates(params32, params_c, opt_state, acc_grads, inv_scale, lr):
            with region("optimizer"):  # unscale, global norm, the update, the next step's compute copy
                return _apply_updates(params32, params_c, opt_state, acc_grads, inv_scale, lr)

        def _apply_updates(params32, params_c, opt_state, acc_grads, inv_scale, lr):
            params32 = _fetch(params32)
            grads = jax.tree_util.tree_map(lambda g: g.astype(jnp.float32) * inv_scale, acc_grads)
            finite = _all_finite(grads)
            gnorm = _global_norm(grads)
            if clip > 0:
                coef = jnp.minimum(1.0, clip / (gnorm + 1e-6))
                grads = jax.tree_util.tree_map(lambda g: g * coef, grads)
            if hasattr(opt_state, "hyperparams"):
                opt_state = opt_state._replace(hyperparams={**opt_state.hyperparams,
                                                            "learning_rate": jnp.asarray(lr, jnp.float32)})
            updates, new_opt_state = opt.update(grads, opt_state, params32)
            new_params = optax.apply_updates(params32, updates)
            # overflow => skip the step entirely (reference stage_1_and_2.py:1995)
            pick = lambda new, old: jax.tree_util.tree_map(
                lambda n, o: jnp.where(finite, n, o), new, old)
            new_params = pick(new_params, params32)
            # the next step's compute copy, from the value that is written as the new master (a skipped step's is the old
            # master's, and the old copy, donated for its buffer, is not read); None where none is carried
            new_params_c = None if params_c is None else cast(new_params)
            return new_params, new_params_c, pick(new_opt_state, opt_state), gnorm, ~finite

        # donate params, the copy and opt_state only: their buffers alias the
        # outputs one-to-one (donating grads too leaves an unusable donated
        # buffer — XLA's "Some donated buffers were not usable" warning)
        self._apply_updates = jax.jit(apply_updates, donate_argnums=(0, 1, 2),
                                      out_shardings=(param_out_shardings, copy_shardings, self.opt_state_shardings,
                                                     None, None))

        # one-dispatch fused step: fwd+bwd+optimizer in a single XLA module.
        # Same math and rng derivation as the split path (XLA can overlap the
        # optimizer with the backward tail and never materialize the full
        # fp32 grad tree between dispatches); eligible when every micro-batch
        # IS a full step and no host-side stage interposes.
        self._fused_step = None
        self._fused_pending = None
        self._reported = []  # device counts of steps dispatched and not yet read (``_take_reported``)
        if (comp is None and not use_zeropp
                and self._host_offload is None and self.eigenvalue is None
                and self.config.fused_step):
            # built whenever eligible (compiles lazily on first use); USED
            # only while gas == 1 — set_train_batch_size can move gas in
            # either direction at runtime

            def fused_step(params32, params_c, opt_state, batch, step, scale, inv_scale, lr):
                params_dev = _fetch(params32)  # one stream-in, shared by grad + update
                loss_and_reported, grads = differentiate(params_dev if params_c is None else params_c, batch, step, scale, None,
                                                         compute_dtype)
                return (loss_and_reported, *apply_updates(params_dev, params_c, opt_state, grads, inv_scale, lr))

            self._fused_step = jax.jit(
                fused_step, donate_argnums=(0, 1, 2),
                out_shardings=(None, param_out_shardings, copy_shardings, self.opt_state_shardings, None, None))
            if self.config.wall_clock_breakdown and self.gradient_accumulation_steps == 1:
                self._log_fused_timer_note()

        def eval_loss(params, batch, rng):  # the carried copy, or the master
            return loss_fn(cast(_fetch(params)), batch, rng)

        self._eval_loss = jax.jit(eval_loss)

        # Per-step gradient-reduction traffic estimate. GSPMD inserts the
        # data-parallel grad collectives inside the compiled step, so the
        # eager comm façade never sees them; this dispatch-side estimate
        # (full grad tree, accumulation dtype) keeps comm_bytes_total
        # meaningful for compiled training.
        dp = self.topology.data_parallel_size
        if dp > 1:
            n_grad_elems = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(self.params))
            self._grad_sync_bytes = n_grad_elems * jnp.dtype(self._grad_acc_dtype).itemsize
        else:
            self._grad_sync_bytes = 0

        if self._param_offload == "eager":
            # engine-level swap: async device_put of the host store before
            # each compiled call, updated params put back after (the
            # transient device copy is freed when its last reference drops)
            dev_sh, host_sh = self.param_shardings, store_shardings
            base_fwd_bwd, base_apply = self._fwd_bwd, self._apply_updates
            base_eval = self._eval_loss

            self._fwd_bwd = lambda p, b, step, s: base_fwd_bwd(jax.device_put(p, dev_sh), b, step, s)
            self._eval_loss = lambda p, b, rng: base_eval(jax.device_put(p, dev_sh), b, rng)

            def apply_with_swap(params_host, params_c, *rest):
                new_p, new_c, *out = base_apply(jax.device_put(params_host, dev_sh), params_c, *rest)
                return (jax.device_put(new_p, host_sh), new_c, *out)

            self._apply_updates = apply_with_swap

            if self._fused_step is not None:
                base_fused = self._fused_step

                def fused_with_swap(params_host, params_c, *rest):
                    loss, new_p, *out = base_fused(jax.device_put(params_host, dev_sh), params_c, *rest)
                    return (loss, jax.device_put(new_p, host_sh), *out)

                self._fused_step = fused_with_swap

    # ------------------------------------------------------------------
    # data
    # ------------------------------------------------------------------
    def deepspeed_io(self, dataset, batch_size=None, route=None, data_sampler=None, collate_fn=None,
                     num_local_io_workers=None, per_host=False):
        """Reference ``engine.py:1692``: build the distributed loader. Batch
        size here is the GLOBAL micro-batch (micro × dp degree). By default
        one host feeds the whole mesh; ``per_host=True`` makes each process
        collate only the rows its devices own (multi-host IO scaling — the
        reference's DistributedSampler contract)."""
        global_micro = (batch_size or self.train_micro_batch_size_per_gpu) * self.topology.data_parallel_size
        return DeepSpeedDataLoader(dataset, batch_size=global_micro, collate_fn=collate_fn or self.collate_fn,
                                   topology=self.topology, per_host=per_host)

    def _put_batch(self, batch):
        if isinstance(batch, (dict, tuple, list)):
            leaves = jax.tree_util.tree_leaves(batch)
            if leaves and isinstance(leaves[0], jax.Array) and leaves[0].committed:
                return batch
        # sequence/context parallelism: tokens shard over the seq axes too
        # (reference sequence_parallel_size — Ulysses/ring CP input layout);
        # GSPMD inserts the attention collectives from this layout
        sp = (self.topology.axis_size("seq") > 1 or self.topology.axis_size("context") > 1)
        shardings = specs_to_shardings(batch_specs(batch, self.topology, seq_axis_for_dim1=sp),
                                       self.topology)
        return jax.device_put(batch, shardings)

    # ------------------------------------------------------------------
    # train loop API (reference engine.py:1787,1926,2125)
    # ------------------------------------------------------------------
    def curriculum_difficulty(self) -> int:
        assert self.curriculum_scheduler is not None, "curriculum_learning is not enabled"
        return self.curriculum_scheduler.get_current_difficulty()

    def _apply_curriculum(self, batch):
        seqlen = self.curriculum_scheduler.update_difficulty(self.global_steps + 1)
        if self._curriculum_type != "seqlen" or not isinstance(batch, dict):
            return batch
        out = dict(batch)
        for key in ("input_ids", "labels", "attention_mask", "position_ids", "segment_ids"):
            if key in out and getattr(out[key], "ndim", 0) >= 2 and out[key].shape[1] > seqlen:
                out[key] = out[key][:, :seqlen]
        return out

    def forward(self, batch):
        if self._fused_pending is not None and getattr(self, "_training", True):
            # raised BEFORE the timer starts: a caught-and-retried error must
            # not leave the forward timer running across the exception
            raise RuntimeError("fused_step: forward() called again before step() consumed the previous one")
        self.timers(FORWARD_GLOBAL_TIMER).start()
        with telemetry_span("train/forward") as sp:
            if self.curriculum_scheduler is not None:
                batch = self._apply_curriculum(batch)
            if self.progressive_layer_drop is not None and isinstance(batch, dict):
                # traced scalar, not a python float: theta changes every step
                # and must not retrigger compilation
                batch = dict(batch)
                batch["pld_theta"] = np.asarray(self.progressive_layer_drop.get_theta(), np.float32)
            self._last_microbatch_tokens = _batch_tokens(batch)
            with sp.phase("put_batch"):
                batch = self._put_batch(batch)
            scale = self.loss_scaler.loss_scale / self.gradient_accumulation_steps
            profiling = (self.config.flops_profiler.enabled
                         and self.global_steps == self.config.flops_profiler.profile_step
                         and (self.micro_steps - self._accum_base) % self.gradient_accumulation_steps == 0)  # first micro-batch only
            if profiling:
                self._start_flops_profile(batch, self.micro_steps, scale)
            if (self._fused_step is not None and self.gradient_accumulation_steps == 1
                    and not profiling and getattr(self, "_training", True)):
                lr = self._next_lr()
                inv_scale = 1.0 / self.loss_scaler.loss_scale
                self._compute_params()
                args = (self.params, self._params_c, self.opt_state, batch, self.micro_steps, scale, inv_scale, lr)
                loss, params, params_c, self.opt_state, gnorm, overflow = self._step_program(
                    "fused_step", self._fused_step, args, batch)
                self._install(params, params_c)
                self._fused_pending = (gnorm, overflow, lr)
                self._cached_grads = _FUSED
            else:
                args = (self._compute_params(), batch, self.micro_steps, scale)
                loss, grads = self._step_program("fwd_bwd", self._fwd_bwd, args, batch)
                self._cached_grads = grads
            loss = self._take_reported(loss, sp)
            self._last_loss = loss
            if self.eigenvalue is not None:
                self._last_batch = batch  # retained for the gas-boundary eigenvalue pass
            if profiling:
                self._stop_flops_profile()
        self.timers(FORWARD_GLOBAL_TIMER).stop()
        return loss

    __call__ = forward

    def _step_program(self, name, program, args, batch):
        """Run a step program; its first call with a batch of these shapes is
        a ``program/first_call`` span and log line (``telemetry/costs.py``)
        that also says how the step reduces weight gradients: ``bucket``
        where blocks of the model gather their own parameters and reduce
        their gradients by ``zero/overlap.py``'s rings (how many layers, the
        rings of a kind of block, how many of the layers gather a second time
        in their backward, and whether the embedding and the loss head are
        such a region too), else ``xla``."""
        shapes = leaf_signature(batch)
        sp = open_span("train/forward")
        if (name, shapes) in self._step_programs_seen:
            with sp.phase("dispatch"):
                return program(*args)
        self._step_programs_seen.add((name, shapes))
        self._made_first_call = True  # a capture of the device's time passes this step by (``_note_profiled_step``)
        prof = device_profiler.get_device_profiler()
        if prof is not None and name in ("fused_step", "fwd_bwd"):
            prof.describe(_program_text(program, args))
        counted = ("layers", "regathers", "rings", "head")
        before = [zero_overlap.traced(what) for what in counted]
        paths_before = _paths_traced()
        copy_before = {label: regions_traced_by("optimizer", label) for label in ("path", "grads")}
        notes = {}
        with first_call("train", name, notes):
            with open_span("program/first_call").phase("flops_count"):
                self._count_step_flops(program, args)  # the one Python trace of the model: jax.jit keeps it for the call
            # what share of the executed products is the second forward (``remat``): the FLOPs the gauge's walk counted,
            # by the phase each equation's name stack says
            notes.update({f"flops_{phase}": int(self._step_flops_by_phase.get(phase, 0)) for phase in PHASES}
                         if self._step_flops_by_phase else {})
            with sp.phase("dispatch"):
                out = program(*args)
            # where the step took its compute copy from, and the dtype its gradient reaches the update (``fused_step``) or
            # leaves the program (``fwd_bwd``) in, as ``_build_compiled_fns::differentiate`` counted them in this trace
            rose = {label: "+".join(sorted(word for word, n in regions_traced_by("optimizer", label).items() if n > was.get(word, 0)))
                    for label, was in copy_before.items()}
            if rose["path"]:
                notes.update(compute_copy=rose["path"].replace("carried_copy", "carried"), grads=rose["grads"])
            layers, regathers, rings, head = (zero_overlap.traced(what) - was for what, was in zip(counted, before))
            notes.update(grad_reduce="bucket" if layers or head else "xla", bucket_layers=layers, bucket_rings=rings,
                         bucket_regather=regathers, bucket_head=int(head > 0))
            notes.update(self._layer_kind_notes(paths_before))
        return out

    def _take_reported(self, first, span):
        """A step program's first output is (loss, what the model counted on
        the device: ``telemetry/device_counts.py``), or the loss alone where
        it counted nothing. The counts go to the registry once their step
        has ended, which is looked up here at the next dispatches and never
        waited for (past eight steps in flight the oldest is, and ``span``
        then says ``waited=1``): the counters lag the device by a step or two
        and the host path is not held up. Returns the loss."""
        loss, reported = first if isinstance(first, tuple) else (first, None)
        if reported:
            self._reported.append(reported)
            ended = lambda counts: all(v.is_ready() for v in counts.values())
            with span.phase("device_counts"):
                while self._reported:
                    if not ended(self._reported[0]):
                        if len(self._reported) <= 8:
                            break
                        span.set(waited=1)
                    device_counts.count(self._reported.pop(0))
        return loss

    def _layer_kind_notes(self, traced_before):
        """For a model whose layers are of several kinds (``TransformerConfig.kinds``), or of one whose record says so
        (``LayerKind.alone``): how many layers of each (mixer, ffn) pair, and the keys its kinds' records declare
        (``LayerKind.paths``, ``joined``), by the counters that count each choice where it is made: ``kernel`` (Pallas),
        ``xla`` (the fallback), ``mixed``, a word of the record's own, or no key where this program traced no such call
        site; a joined key's word is the labels that rose, ``+`` between. A model of ONE plain kind says the joined keys
        alone (what its kernels chose for themselves: the flash kernels' tiles a trip). Whatever the kinds, under
        ``remat``: what a checkpointed block keeps beside its inputs (``remat_keeps``: its policy's names, or ``inputs``)."""
        cfg = getattr(self.module, "cfg", None)
        kinds = getattr(cfg, "kinds", None)
        if not kinds:
            return {}
        notes = {}
        if cfg.remat:  # unrolled, looped or stacked (``nn.remat(Block, policy=...)``): one rule
            names = sorted({name for kind in kinds for name in layer_kinds.remat_keeps(kind)})
            notes["remat_keeps"] = "+".join(names) or "inputs"
        if cfg.loop_steps > 1:  # a looped stack: how many passes over the same layers
            notes["loop_steps"] = cfg.loop_steps
        records = layer_kinds.records(kinds)
        # one kind of plain block: no form of a layer was chosen, only what its kernels chose for themselves (``joined``)
        plain = len(set(kinds)) == 1 and not any(record.alone for record in records)
        traced, words = _paths_traced(records), _declared("path_words", records)
        if not plain:
            notes["layer_kinds"] = ",".join(f"{k}:{n}" for k, n in sorted(collections.Counter(f"{mixer}+{ffn}" for mixer, ffn in kinds).items()))
            for key in _declared("paths", records):
                kernel, xla = (now - was for now, was in zip(traced[key], traced_before[key]))
                if kernel or xla:
                    notes[key] = "mixed" if kernel and xla else words.get(key, "kernel") if kernel else "xla"
        for key, (_, labels, *_) in _declared("joined", records).items():
            if labels is None:
                rose = sorted(value for value, now in traced[key].items() if now > traced_before[key].get(value, 0))
            else:
                rose = [label for label, now, was in zip(labels, traced[key], traced_before[key]) if now > was]
            if rose:
                notes[key] = "+".join(rose)
        return notes

    def _count_step_flops(self, program, args):
        """FLOPs of a micro-batch for the MFU gauge, walked off the jaxpr of
        the program that is about to run, on its own arguments: the trace is
        the one ``jax.jit`` keeps for the call that follows, so the model is
        traced once a shape, not once for the gauge and once for the step.
        The fused step's count includes the optimizer's elementwise update
        (a few operations a parameter against 6 x tokens)."""
        if self._step_flops_tokens == self._last_microbatch_tokens or not knobs.get_int("DS_TPU_PERF_ACCOUNT"):
            return
        self._step_flops_tokens = self._last_microbatch_tokens
        self._step_flops_by_phase = {}
        try:
            from ..profiling.flops_profiler import flops_of_fn
            self._step_flops, _ = flops_of_fn(program, *args, phases=self._step_flops_by_phase)
        except Exception:
            self._step_flops = 0  # MFU gauge stays dark; never block training

    def backward(self, loss=None, retain_graph=False):
        """Accumulate the gradients computed by the paired ``forward``."""
        if self._cached_grads is None:
            raise RuntimeError("backward() called without a preceding forward()")
        self.timers(BACKWARD_GLOBAL_TIMER).start()
        with telemetry_span("train/backward"):
            if self._cached_grads is _FUSED:
                pass  # grads were consumed inside the fused forward dispatch
            elif self._grad_acc is None:
                if self._to_acc_dtype is None:
                    self._grad_acc = self._cached_grads
                else:
                    with self._first("to_acc_dtype"):
                        self._grad_acc = self._to_acc_dtype(self._cached_grads)
            else:
                with self._first("accumulate"):
                    self._grad_acc = self._accumulate(self._grad_acc, self._cached_grads)
            self._cached_grads = None
            self.micro_steps += 1
            self.global_samples += self.train_micro_batch_size_per_gpu * self.topology.data_parallel_size
            if self._last_microbatch_tokens:
                self._m_tokens.inc(self._last_microbatch_tokens)
        self.timers(BACKWARD_GLOBAL_TIMER).stop()
        return loss

    def is_gradient_accumulation_boundary(self) -> bool:
        """Reference ``engine.py:2009``."""
        done = self.micro_steps - self._accum_base
        return done % self.gradient_accumulation_steps == 0 and done > 0

    def step(self):
        if not self.is_gradient_accumulation_boundary():
            self._last_overflow = None  # no-op step (reference was_step_applied contract)
            return
        self.timers(STEP_GLOBAL_TIMER).start()
        with telemetry_span("train/step") as sp:  # to the end of ``step()``: the report's and the monitor's reads of a loss are its phases
            if (self.eigenvalue is not None
                    and self.global_steps % self.eigenvalue.gas_boundary_resolution == 0
                    and getattr(self, "_last_batch", None) is not None):
                # curvature signal at the accumulation boundary (ref engine.py:2029).
                # _loss_fn is a stable bound callable, so the per-layer HVP jits
                # compile once; the step-derived rng feeds dropout-style losses.
                params_c = _cast_tree(self.params, self.compute_dtype)
                self.block_eigenvalue = self.eigenvalue.compute_eigenvalue(
                    self._loss_fn, params_c, self._last_batch,
                    loss_rng=jax.random.fold_in(self._rng, self.global_steps))
            if self._fused_pending is not None:
                # params/opt_state were installed by the fused forward dispatch
                gnorm, overflow, lr = self._fused_pending
                self._fused_pending = None
            else:
                lr = self._next_lr()
                # grads were pre-scaled by loss_scale/gas in forward; undo loss_scale
                # here (the 1/gas factor stays: summed micro-grads become the mean)
                inv_scale = 1.0 / self.loss_scaler.loss_scale
                with sp.phase("apply"):
                    if self._host_offload is not None:
                        new_params, gnorm, overflow = self._host_offload.step(jax.device_get(self._grad_acc), lr,
                                                                              inv_scale=inv_scale,
                                                                              grad_clip=self.config.gradient_clipping,
                                                                              shardings=self.param_store_shardings)
                        if not overflow:
                            self.params = new_params
                    else:
                        self._compute_params()  # a copy dropped since the forward is made again: the update takes its buffer
                        with self._first("apply_updates"):
                            params, params_c, self.opt_state, gnorm, overflow = self._apply_updates(
                                self.params, self._params_c, self.opt_state, self._grad_acc, inv_scale, lr)
                        self._install(params, params_c)
            self._grad_acc = None
            self._global_grad_norm = gnorm
            self._last_overflow = overflow
            if self.loss_scaler.dynamic or self._host_offload is not None:
                # dynamic fp16 scaling needs the overflow bit on the host NOW
                # (the scale feeds the next step) — this device->host sync is
                # inherent to the algorithm, as in the reference
                with sp.phase("overflow_sync"):
                    overflow_host = bool(overflow)
                self.loss_scaler.update_scale(overflow_host)
                if overflow_host:
                    self._skipped_host += 1
                    self._m_overflow.inc()
                    log_dist(f"step {self.global_steps}: grad overflow — step skipped, "
                             f"loss scale -> {self.loss_scaler.loss_scale}", ranks=[0])
            else:
                # static scale (bf16/fp32): never block the dispatch pipeline on a
                # per-step device->host readback (a scalar sync drains the whole
                # queue of dispatched steps). The skip-on-overflow happens in-graph;
                # the counter folds lazily (see skipped_steps property).
                with self._first("overflow_count" if self._skipped_dev is None else "overflow_sum", family="init"):  # two eager programs: the cast, the add
                    self._skipped_dev = overflow.astype(jnp.int32) if self._skipped_dev is None \
                        else self._skipped_dev + overflow.astype(jnp.int32)
            self.global_steps += 1
            if self.random_ltd_scheduler is not None:
                self.random_ltd_scheduler.update_seq(self.global_steps)
            if self.progressive_layer_drop is not None:
                self.progressive_layer_drop.update_state(self.global_steps)
            if self.compression_engine is not None:
                self.compression_engine.scheduler.step()
            self.timers(STEP_GLOBAL_TIMER).stop()
            # dispatch-boundary telemetry: counters, gauges, heartbeat. No device
            # reads here — loss/grad-norm gauges update where a sync already
            # happens (_report, monitor flush).
            self._m_steps.inc()
            self._m_loss_scale.set(self.loss_scaler.loss_scale)
            self._m_lr.set(lr)
            self._m_heartbeat.set(time.time())
            if self._grad_sync_bytes:
                self._m_grad_sync_bytes.inc(self._grad_sync_bytes)
            now_pc = time.perf_counter()
            if self._last_step_pc is not None and now_pc > self._last_step_pc and self._last_microbatch_tokens:
                # dispatch rate, not device rate: honest once the pipeline is
                # deep enough that dispatch tracks execution
                self._m_tps.set(self._last_microbatch_tokens * self.gradient_accumulation_steps
                                / (now_pc - self._last_step_pc))
                if self._step_flops:
                    if self._peak_flops is None:
                        from ..telemetry.costs import resolve_peaks
                        self._peak_flops = resolve_peaks()[0]
                    if self._peak_flops > 0:
                        self._m_mfu.set(self._step_flops * self.gradient_accumulation_steps
                                        / (now_pc - self._last_step_pc) / self._peak_flops)
            self._last_step_pc = now_pc
            if self.global_steps % self.config.steps_per_print == 0:
                with sp.phase("report"):
                    self._report(lr)
            if self.monitor is not None:
                # registry -> monitor bridge; the legacy Train/Samples/* series
                # ride along verbatim (same host sync the old write_events paid)
                with sp.phase("monitor_flush"):
                    extra = [("Train/Samples/lr", lr, self.global_samples)]
                    if self._last_loss is not None:
                        loss_host = float(self._last_loss)
                        self._m_loss.set(loss_host)
                        self.health.observe_loss(loss_host)
                        extra.append(("Train/Samples/train_loss", loss_host, self.global_samples))
                    self._monitor_bridge.maybe_flush(self.global_steps, extra_events=extra)
        # the step's period on the host, this ``step()``'s end from the last one's, to the stall detector: a step that made
        # a first call, or in which a capture's ends waited for the device, is passed by
        passed_by, self._made_first_call = self._made_first_call, False
        prof = device_profiler.get_device_profiler()  # None unless a capture was ever armed (DS_TPU_PROFILE, the ops plane)
        if prof is not None and not passed_by:
            passed_by = self._note_profiled_step(prof)
        since, self._step_end_pc = self._step_end_pc, time.perf_counter()
        if since is not None:
            period = self._step_end_pc - since
            self.health.observe_step_period(period, step=self.global_steps, passed_by=passed_by,
                                            split=lambda: _period_split(since, period))

    def _note_profiled_step(self, prof) -> bool:
        """A step's end as the device profiler's quantum (a step that made a
        first call is passed by: the capture is of the program running, not
        of its compilation). Steps are dispatched ahead of the device, so
        before the marker that starts the capture and before the one that
        closes it the host waits for the last step's results: the trace then
        holds whole steps, and every captured step's device time. Returns
        whether this was such an end."""
        edge = prof.state == "armed" or prof.closes_next()
        if edge:
            jax.block_until_ready((self._last_loss, self.params))
        prof.note_quantum("train/step", step=self.global_steps, tokens=self._last_microbatch_tokens)
        return edge

    def _start_flops_profile(self, batch, step, scale):
        """Reference ``engine.py:1800,1817``: flops profiler on a configured step.
        The profiled unit here is the fused fwd+bwd jit (what actually runs)."""
        from ..profiling.flops_profiler import FlopsProfiler

        self.flops_profiler = FlopsProfiler(ds_engine=self,
                                            recompute_fwd_factor=self.config.flops_profiler.recompute_fwd_factor)
        self.flops_profiler.analyze_fn(lambda p, b, st, s: self._fwd_bwd(p, b, st, s),
                                       self._compute_params(), batch, step, scale, params_tree=self.params)
        self.flops_profiler.start_profile()

    def _stop_flops_profile(self):
        prof = self.flops_profiler
        prof.stop_profile()
        cfg = self.config.flops_profiler
        prof.print_model_profile(profile_step=self.global_steps, module_depth=cfg.module_depth,
                                 top_modules=cfg.top_modules, detailed=cfg.detailed, output_file=cfg.output_file)
        prof.end_profile()

    def _next_lr(self) -> float:
        lr = float(self._base_lr)
        if self.lr_scheduler is not None:
            # reference ordering (engine.py: lr_scheduler.step() runs AFTER
            # optimizer.step()): an optimizer step consumes the lr the
            # PREVIOUS scheduler step installed. The first step therefore
            # runs at the pre-schedule value — the optimizer's construction
            # lr for the Warmup* family, or the schedule's documented start
            # point (range-test min_lr / 1-cycle cycle_min_lr).
            if getattr(self.lr_scheduler, "_last_lr", None) is not None:
                lr = float(self.lr_scheduler.get_last_lr()[0])
            else:
                init = getattr(self.lr_scheduler, "initial_lr", lambda: None)()
                if init is not None:
                    lr = float(init)
            # the schedule clock ALWAYS advances (a manual set_lr only
            # masks one consumption)
            self.lr_scheduler.step()
        if self._lr_override is not None:
            lr, self._lr_override = self._lr_override, None
        return lr

    def _report(self, lr):
        loss = float(self._last_loss) if self._last_loss is not None else float("nan")
        # the periodic report already pays a host sync — fold the lazy
        # overflow counter here so static-scale overflow skips surface
        # without a per-step readback
        skipped = self.skipped_steps
        self._m_loss.set(loss)
        if self._last_loss is not None:
            self.health.observe_loss(loss)
        if self._global_grad_norm is not None:
            self._m_gnorm.set(float(self._global_grad_norm))
            self.health.observe_grad_norm(float(self._global_grad_norm))
        skip_note = f" skipped={skipped}" if skipped else ""
        log_dist(
            f"step={self.global_steps} loss={loss:.4f} lr={lr:.3e} "
            f"loss_scale={self.loss_scaler.loss_scale:.0f} gnorm={float(self._global_grad_norm):.3f}{skip_note}",
            ranks=[0])
        if self.wall_clock_breakdown:
            self.timers.log([FORWARD_GLOBAL_TIMER, BACKWARD_GLOBAL_TIMER, STEP_GLOBAL_TIMER],
                            memory_breakdown=self.config.memory_breakdown)

    def train_batch(self, data_iter=None):
        """Run one full (gas micro-batches) optimizer step; returns mean loss.
        Mirrors ``PipelineEngine.train_batch`` for the non-pipeline engine."""
        if data_iter is None:
            if self.training_dataloader is None:
                raise ValueError("train_batch needs a data_iter or training_data at initialize()")
            data_iter = iter(self.training_dataloader)
        self.tput_timer.start()
        losses = []
        for _ in range(self.gradient_accumulation_steps):
            batch = next(data_iter)
            loss = self.forward(batch)
            self.backward(loss)
            losses.append(loss)
        self.step()
        self.tput_timer.stop(global_step=True)
        return jnp.mean(jnp.stack(losses))

    def eval_batch(self, batch, rng=None):
        batch = self._put_batch(batch)
        # disjoint from the train-step folds, which use micro_steps directly
        # (fold_in data must be non-negative: it coerces to uint32)
        params = self._compute_params()
        with self._first(("eval_loss", leaf_signature(batch))):
            rng = rng if rng is not None else jax.random.fold_in(self._rng, (1 << 30) + self.micro_steps)
            return self._eval_loss(params, batch, rng)

    def zero_grad(self):
        if self._fused_pending is not None:
            # the fused dispatch already applied the update in-graph (params
            # donated — there is nothing to roll back), and silently dropping
            # the bookkeeping would drift the lr schedule and loss scaler
            raise RuntimeError(
                "zero_grad: a fused step is pending — fused mode makes forward()+step() atomic, so a "
                "forward() cannot be discarded. Call step() to commit it, or set {'fused_step': false} "
                "if your loop needs discardable forwards")
        self._grad_acc = None
        self._cached_grads = None
        # discarding a partial window restarts the accumulation clock, so
        # the next step applies exactly gas fresh micro-grads (same
        # mis-scaling hazard set_train_batch_size guards against)
        self._accum_base = self.micro_steps

    # ------------------------------------------------------------------
    # introspection (reference engine accessors)
    # ------------------------------------------------------------------
    @property
    def skipped_steps(self) -> int:
        """Overflow-skipped step count. Reading this syncs the lazily
        accumulated device counter (one host roundtrip)."""
        dev = 0 if self._skipped_dev is None else int(self._skipped_dev)
        return self._skipped_host + dev

    @skipped_steps.setter
    def skipped_steps(self, value: int):
        self._skipped_host = int(value)
        self._skipped_dev = None

    def zero_optimization_stage(self) -> int:
        return self.config.zero_config.stage

    def zero_optimization(self) -> bool:
        return self.config.zero_enabled

    def get_lr(self):
        if self._lr_override is not None:  # pending manual override (set_lr)
            return [self._lr_override]
        if self.lr_scheduler is not None and hasattr(self.lr_scheduler, "_last_lr"):
            return self.lr_scheduler.get_last_lr()
        return [self._base_lr]

    def set_lr(self, lr: float):
        """Reference ``engine.py`` ``set_lr``: the manual value drives the
        NEXT optimizer step; a configured scheduler resumes control after
        its next recomputation (matching 'until the next scheduler.step()')."""
        self._base_lr = float(lr)
        self._lr_override = float(lr)

    def set_train_batch_size(self, train_batch_size: int):
        """Adjust the global batch size by changing the number of gradient
        accumulation steps; micro-batch size and DP degree are fixed
        (reference ``engine.py:411``)."""
        self._check_no_pending_fused("set_train_batch_size")
        if self._grad_acc is not None or self._cached_grads is not None:
            # (a fused _FUSED marker can't reach here: _check_no_pending_fused raised)
            raise RuntimeError("set_train_batch_size mid-accumulation: step() the pending micro-batches "
                               "first (mixing 1/gas-scaled gradients across regimes would mis-scale them)")
        micro_dp = self.train_micro_batch_size_per_gpu * self.topology.data_parallel_size
        if train_batch_size < micro_dp or train_batch_size % micro_dp != 0:
            raise ValueError(f"train_batch_size {train_batch_size} must be a positive multiple of "
                             f"micro-batch x data parallelism ({micro_dp})")
        self.gradient_accumulation_steps = train_batch_size // micro_dp
        self.config.gradient_accumulation_steps = self.gradient_accumulation_steps
        self.config.train_batch_size = train_batch_size
        self.train_batch_size = train_batch_size
        # new throughput window: retroactively applying the new batch size
        # to already-timed steps would mis-scale avg samples/sec
        self.tput_timer.batch_size = max(1, train_batch_size)
        self.tput_timer.total_elapsed_time = 0.0
        self.tput_timer.global_step_count = 0
        self.tput_timer.micro_step_count = 0
        # the boundary clock restarts here so the next window is exactly gas
        # micro-batches regardless of the cumulative micro_steps residue
        self._accum_base = self.micro_steps
        if self._fused_step is not None:
            # forward() gates the fused one-dispatch path on gas == 1 — no
            # state to juggle here, just say which path the new gas takes
            fused_on = self.gradient_accumulation_steps == 1
            log_dist(f"set_train_batch_size: gas={self.gradient_accumulation_steps} — "
                     f"fused one-dispatch step {'active' if fused_on else 'inactive'}", ranks=[0])
            if fused_on and self.config.wall_clock_breakdown:
                self._log_fused_timer_note()

    @staticmethod
    def _log_fused_timer_note():
        log_dist("fused_step active: the 'forward' wall-clock bucket covers the whole "
                 "fwd+bwd+optimizer dispatch; the backward/step timers measure nothing", ranks=[0])

    def gradient_clipping(self) -> float:
        return self.config.gradient_clipping

    def zero_gather_16bit_weights_on_model_save(self) -> bool:
        """Reference ``engine.py:773`` accessor."""
        return bool(self.config.zero_config.stage3_gather_16bit_weights_on_model_save)

    def dynamic_loss_scale(self) -> bool:
        return bool(self.loss_scaler.dynamic)

    def was_step_applied(self) -> bool:
        """True iff the latest ``step()`` modified parameters — False for
        accumulation-boundary no-ops and overflow-skipped steps (reference
        ``engine.py:1682``). Querying syncs the overflow flag."""
        if self._last_overflow is None:
            return False
        return not bool(self._last_overflow)

    def get_loss_scale(self) -> float:
        return self.loss_scaler.loss_scale

    @property
    def cur_scale(self):
        return self.loss_scaler.loss_scale

    def get_global_grad_norm(self):
        return None if self._global_grad_norm is None else float(self._global_grad_norm)

    def get_world_size(self) -> int:
        return self.topology.n_devices

    def train(self, mode: bool = True):
        self._training = mode
        return self

    def eval(self):
        return self.train(False)

    def module_state_dict(self):
        return jax.device_get(self.params)

    def _configure_monitor(self):
        try:
            from ..monitor.monitor import MonitorMaster

            m = MonitorMaster(self.config)
            return m if m.enabled else None
        except Exception:
            return None

    # ------------------------------------------------------------------
    # checkpointing (reference engine.py:3049 save, :2705 load)
    # ------------------------------------------------------------------
    def _ckpt_dir(self, save_dir: str, tag: str) -> str:
        return os.path.join(save_dir, str(tag))

    def _check_no_pending_fused(self, what: str):
        if self._fused_pending is not None:
            raise RuntimeError(f"{what}: a fused step is pending — its parameter update is already applied "
                               "but global_steps/scheduler state are not; call step() first (resuming a "
                               "checkpoint taken here would double-apply the update)")

    def save_16bit_model(self, save_dir: str, save_filename: str = "model.safetensors"):
        """Consolidated half-precision model export (reference
        ``engine.py:3547`` ``save_16bit_model`` / ``:3478``
        ``_zero3_consolidated_16bit_state_dict``): gathers every shard
        (ZeRO-3 included — ``np.asarray`` on a sharded array is the
        allgather) and writes ONE safetensors file of bf16 weights with
        ``/``-joined native param paths. The HF-interop converters invert
        per-arch naming; this export is the serve-anywhere artifact."""
        import torch as _torch
        from safetensors.torch import save_file as _save_file

        from ..utils.pytree import path_str
        from .checkpoint_engine import _to_host

        self._check_no_pending_fused("save_16bit_model")
        if self.config.zero_config.stage == 3 and not self.zero_gather_16bit_weights_on_model_save():
            # reference engine.py:3565: consolidation is expensive and isn't
            # a default — refuse rather than save a bogus partial model
            log_dist(f"Did not save the model {os.path.join(save_dir, save_filename)} because "
                     "`stage3_gather_16bit_weights_on_model_save` is False", ranks=[0])
            return False
        # every process participates in the gather (non-addressable ZeRO-3
        # shards allgather across hosts); only process 0 writes the file
        host_tree = _to_host(self.params)
        out = os.path.join(save_dir, save_filename)
        if jax.process_index() == 0:
            flat = {}
            for path, leaf in jax.tree_util.tree_leaves_with_path(host_tree):
                t = _torch.from_numpy(np.asarray(leaf, dtype=np.float32))
                flat[path_str(path)] = t.to(_torch.bfloat16).contiguous()
            os.makedirs(save_dir, exist_ok=True)
            _save_file(flat, out)
            log_dist(f"save_16bit_model: {len(flat)} tensors -> {out}", ranks=[0])
        dist.barrier(log_name="save_16bit_model")
        return out

    def save_checkpoint(self, save_dir: str, tag=None, client_state: Optional[Dict] = None, save_latest: bool = True,
                        exclude_frozen_parameters: bool = False):
        self._check_no_pending_fused("save_checkpoint")
        tag = str(tag) if tag is not None else f"global_step{self.global_steps}"
        d = self._ckpt_dir(save_dir, tag)
        self.checkpoint_engine.makedirs(d)
        self.checkpoint_engine.create(tag)
        self.checkpoint_engine.save(self.params, os.path.join(d, MODEL_STATES_FILENAME))
        optim_state = {
            "opt_state": self.opt_state if self._host_offload is None else self._host_offload.state_dict(),
            "loss_scaler": self.loss_scaler.state_dict(),
            "lr_scheduler": self.lr_scheduler.state_dict() if self.lr_scheduler is not None else None,
            "global_steps": self.global_steps,
            "micro_steps": self.micro_steps,
            "global_samples": self.global_samples,
            "skipped_steps": self.skipped_steps,
        }
        self.checkpoint_engine.save(optim_state, os.path.join(d, OPTIM_STATES_FILENAME))
        if jax.process_index() == 0:
            # plain-JSON step counters so module-only loads (which skip the
            # optimizer states) can still restore step-indexed schedules
            with open(os.path.join(d, TRAIN_META_FILENAME), "w") as f:
                json.dump({"global_steps": self.global_steps, "micro_steps": self.micro_steps,
                           "global_samples": self.global_samples, "accum_base": self._accum_base}, f)
        if self.curriculum_scheduler is not None:
            # own file: plain-python state, no array template needed on load
            self.checkpoint_engine.save(self.curriculum_scheduler.get_state(),
                                        os.path.join(d, CURRICULUM_STATE_FILENAME))
        if client_state:
            self.checkpoint_engine.save(client_state, os.path.join(d, CLIENT_STATE_FILENAME))
        if save_latest and jax.process_index() == 0:
            with open(os.path.join(save_dir, LATEST_FILENAME), "w") as f:
                f.write(tag)
        self.checkpoint_engine.commit(tag)
        return True

    def load_checkpoint(self, load_dir: str, tag=None, load_module_strict: bool = True,
                        load_optimizer_states: bool = True, load_lr_scheduler_states: bool = True,
                        load_module_only: bool = False):
        if self.config.checkpoint_config.load_universal:
            # reference checkpoint.load_universal=true routes resume through
            # the degree-independent layout (universal_checkpoint.py:22),
            # keeping this method's contract: (path, client_state) return,
            # warn-and-fresh-start on a missing 'latest', fused-pending
            # handling identical to the regular route
            if load_module_only:
                # reference load_module_only: weights only, optimizer and
                # schedule stay fresh
                load_optimizer_states = False
                load_lr_scheduler_states = False
            if tag is None and not os.path.exists(os.path.join(load_dir, LATEST_FILENAME)):
                logger.warning(f"no 'latest' file at {load_dir}; nothing loaded")
                return None, {}
            if self._fused_pending is not None:
                if not load_optimizer_states:
                    raise RuntimeError("load_checkpoint: a fused step is pending and this partial load "
                                       "(load_module_only / load_optimizer_states=False) would not "
                                       "overwrite the optimizer state it touched; call step() first")
                self._fused_pending = None
                self._cached_grads = None
                log_dist("load_checkpoint: discarding a pending fused step — its state is being overwritten",
                         ranks=[0])
            path = self.load_universal_checkpoint(load_dir, tag=tag,
                                                  load_optimizer_states=load_optimizer_states,
                                                  load_lr_scheduler_states=load_lr_scheduler_states)
            self._post_load_derived_state()
            if not load_optimizer_states and self.compression_engine is not None and path is not None:
                # step-indexed compression schedules (QAT bit annealing,
                # pruning offsets) anneal from the SAVED step even when the
                # counters stay fresh — the native route's contract (see the
                # TRAIN_META restore below)
                from ..checkpoint.universal import inspect_universal_checkpoint

                saved = inspect_universal_checkpoint(load_dir, tag).get("counters", {})
                self.compression_engine.scheduler.training_steps = int(saved.get("global_steps", 0))
            return path, {}
        if tag is None:
            latest = os.path.join(load_dir, LATEST_FILENAME)
            if not os.path.exists(latest):
                logger.warning(f"no 'latest' file at {load_dir}; nothing loaded")
                return None, {}
            with open(latest) as f:
                tag = f.read().strip()
        d = self._ckpt_dir(load_dir, tag)
        if self._fused_pending is not None:
            # a FULL load replaces params/opt_state/schedule, so the pending
            # fused step's bookkeeping can be dropped; a partial load would
            # leave the already-applied optimizer update inconsistent with
            # the retained schedule state — refuse that combination
            if load_module_only or not load_optimizer_states:
                raise RuntimeError("load_checkpoint: a fused step is pending and this partial load "
                                   "(load_module_only / load_optimizer_states=False) would not overwrite "
                                   "the optimizer state it touched; call step() first")
            self._fused_pending = None
            self._cached_grads = None
            log_dist("load_checkpoint: discarding a pending fused step — its state is being overwritten",
                     ranks=[0])
        params_host = self.checkpoint_engine.load(os.path.join(d, MODEL_STATES_FILENAME),
                                                  template=self.checkpoint_engine.prepare_template(self.params))
        self.params = jax.device_put(params_host, self.param_store_shardings)
        if self._host_offload is not None:
            # keep the host master copies in sync even when optimizer states
            # are not loaded, or the next step reverts to init-time weights
            self._host_offload.set_master(params_host)
        client_state = {}
        if not load_module_only:
            optim_path = os.path.join(d, OPTIM_STATES_FILENAME)
            if load_optimizer_states and os.path.exists(optim_path):
                template = {
                    "opt_state": self.opt_state if self._host_offload is None else
                    self._host_offload.template_state_dict(),
                    "loss_scaler": self.loss_scaler.state_dict(),
                    "lr_scheduler": self.lr_scheduler.state_dict() if self.lr_scheduler is not None else None,
                    "global_steps": 0, "micro_steps": 0, "global_samples": 0, "skipped_steps": 0,
                }
                state = self.checkpoint_engine.load(optim_path,
                                                    template=self.checkpoint_engine.prepare_template(template))
                if self._host_offload is not None:
                    self._host_offload.load_state_dict(state["opt_state"])
                else:
                    self.opt_state = jax.device_put(state["opt_state"], self.opt_state_shardings)
                self.loss_scaler.load_state_dict(state["loss_scaler"])
                if load_lr_scheduler_states and self.lr_scheduler is not None and state["lr_scheduler"] is not None:
                    self.lr_scheduler.load_state_dict(state["lr_scheduler"])
                self.global_steps = int(state["global_steps"])
                self.micro_steps = int(state["micro_steps"])
                # accum_base rides the JSON meta (kept OUT of the msgpack
                # template so pre-existing checkpoints still deserialize)
                meta_path = os.path.join(d, TRAIN_META_FILENAME)
                if os.path.exists(meta_path):
                    with open(meta_path) as f:
                        self._accum_base = int(json.load(f).get("accum_base", 0))
                else:  # meta-less checkpoint: never leave a stale clock ahead
                    self._accum_base = 0
                if self._accum_base > self.micro_steps:
                    self._accum_base = self.micro_steps
                self.global_samples = int(state["global_samples"])
                self.skipped_steps = int(state["skipped_steps"])
                self._post_load_derived_state()
            curriculum_path = os.path.join(d, CURRICULUM_STATE_FILENAME)
            if self.curriculum_scheduler is not None and os.path.exists(curriculum_path):
                self.curriculum_scheduler.set_state(self.checkpoint_engine.load(curriculum_path))
            cs_path = os.path.join(d, CLIENT_STATE_FILENAME)
            if os.path.exists(cs_path):
                client_state = self.checkpoint_engine.load(cs_path)
        if self.compression_engine is not None:
            # restore step-indexed compression schedules (QAT bit annealing,
            # pruning offsets) even when the optimizer states were skipped
            meta_path = os.path.join(d, TRAIN_META_FILENAME)
            if os.path.exists(meta_path):
                with open(meta_path) as f:
                    self.compression_engine.scheduler.training_steps = int(json.load(f)["global_steps"])
            else:
                self.compression_engine.scheduler.training_steps = self.global_steps
        return d, client_state

    def _post_load_derived_state(self):
        """Step-derived state shared by BOTH load routes: PLD theta and the
        compression schedule are pure functions of the restored step (or the
        first resumed step trains with theta=1 / un-annealed schedules), and
        the accumulation clock must never sit ahead of micro_steps."""
        if self.progressive_layer_drop is not None:
            self.progressive_layer_drop.update_state(self.global_steps)
        if self.compression_engine is not None:
            self.compression_engine.scheduler.training_steps = self.global_steps
        if self._accum_base > self.micro_steps:
            self._accum_base = self.micro_steps

    def save_universal_checkpoint(self, save_dir: str, tag=None):
        """Write the degree-independent universal layout directly
        (reference needs offline ``ds_to_universal.py`` for this)."""
        self._check_no_pending_fused("save_universal_checkpoint")
        from ..checkpoint.universal import save_universal_checkpoint

        return save_universal_checkpoint(self, save_dir, tag)

    def load_universal_checkpoint(self, load_dir: str, tag=None, load_optimizer_states: bool = True,
                                  load_lr_scheduler_states: bool = True):
        """Resume from a universal checkpoint at ANY mesh/zero-stage
        (reference ``universal_checkpoint.py:22``)."""
        from ..checkpoint.universal import load_universal_checkpoint

        return load_universal_checkpoint(self, load_dir, tag, load_optimizer_states=load_optimizer_states,
                                         load_lr_scheduler_states=load_lr_scheduler_states)


def initialize(args=None, model=None, optimizer=None, model_parameters=None, training_data=None, lr_scheduler=None,
               mesh=None, mpu=None, dist_init_required=None, collate_fn=None, config=None, **kwargs):
    """Reference ``deepspeed/__init__.py:70``. Returns (engine, optimizer,
    dataloader, lr_scheduler).

    At ZeRO stage 3 on several chips ``model_parameters`` is CONSUMED, as the
    reference's stage 3 partitions a module's parameters in place: every leaf
    that is divided over the mesh is deleted once its shards stand
    (``_put_divided``), so that no chip holds the whole tree beside its share.
    A caller that reads its tree after this call meets deleted arrays there;
    ``engine.params`` is the tree from then on. At the other stages, and on one
    chip, the tree handed in is left as it was."""
    if model is None:
        raise ValueError("deepspeed_tpu.initialize: model is required")
    if model_parameters is None and hasattr(model, "init_params"):
        model_parameters = model.init_params(jax.random.PRNGKey(get_accelerator().initial_seed()))

    from .pipe.module import PipelineModule

    cfg = config if isinstance(config, DeepSpeedConfig) else DeepSpeedConfig(config)
    wants_pipeline = isinstance(model, PipelineModule) or (cfg.mesh.pipe not in (0, 1)
                                                           and hasattr(model, "to_pipeline"))
    if wants_pipeline:
        from .pipe.engine import PipelineEngine

        engine = PipelineEngine(args=args, model=model, optimizer=optimizer, model_parameters=model_parameters,
                                training_data=training_data, lr_scheduler=lr_scheduler, mesh=mesh,
                                dist_init_required=dist_init_required, collate_fn=collate_fn, config=cfg, **kwargs)
    elif cfg.hybrid_engine.enabled:
        from .hybrid_engine import DeepSpeedHybridEngine

        engine = DeepSpeedHybridEngine(args=args, model=model, optimizer=optimizer,
                                       model_parameters=model_parameters, training_data=training_data,
                                       lr_scheduler=lr_scheduler, mesh=mesh,
                                       dist_init_required=dist_init_required, collate_fn=collate_fn, config=cfg,
                                       **kwargs)
    else:
        engine = DeepSpeedEngine(args=args, model=model, optimizer=optimizer, model_parameters=model_parameters,
                                 training_data=training_data, lr_scheduler=lr_scheduler, mesh=mesh,
                                 dist_init_required=dist_init_required, collate_fn=collate_fn, config=cfg, **kwargs)
    return engine, engine.optimizer, engine.training_dataloader, engine.lr_scheduler
