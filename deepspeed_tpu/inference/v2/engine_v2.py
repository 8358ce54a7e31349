"""InferenceEngineV2 — continuous-batching ragged inference.

Parity: reference ``inference/v2/engine_v2.py`` (``InferenceEngineV2``:
``put(uids, tokens)`` ragged forward :107, scheduling feasibility
``query``/``can_put`` :184, ``flush`` :171) + ``DSStateManager`` and
paged-KV plumbing. TPU re-design:

- the KV cache is a stacked page pool ``(layers, blocks, block_size,
  KVH, D)`` pair, functionally updated under jit with buffer donation
  (no in-place CUDA workspace);
- one jitted *decode* program (Pallas paged attention, batch bucketed to
  powers of two) and one jitted *prefill* program (chunk of one sequence,
  length bucketed) replace the CUDA ragged kernel suite;
- block 0 of the pool is reserved as a garbage page: padded tokens in a
  bucket write their KV there, so padding never corrupts live sequences.
"""

import dataclasses
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...analysis import knobs
from ...analysis.transfer_guard import maybe_guard
from ...models.transformer import TransformerConfig
from ...telemetry import get_registry as get_telemetry_registry
from ...telemetry import get_tracer
from ...telemetry import span as telemetry_span
from ...telemetry.costs import get_perf_accountant
from ...telemetry.events import get_event_log
from ...telemetry.flight import maybe_attach_flight_recorder
from ...telemetry.health import (HBMPressureDetector, QueueStallDetector,
                                 SLOBurnRateDetector, get_health_monitor)
from ...telemetry.journal import get_journal
from ...telemetry.ops_plane import maybe_start_ops_server
from ...telemetry import profiler as device_profiler
from ...utils.compile_cache import register_cache_metrics
from ...utils.logging import log_dist, logger
from ...ops.pallas.paged_attention import make_kv_pool
from .model_runner import (TPContext, make_burst_fn, make_fused_step_fn,
                           make_spec_verify_fn, make_step_fns)
from .ragged.manager import DSStateManager, RaggedBatchConfig
from .scheduler import FusedQuantum, RaggedBatchScheduler, RaggedRequest
from .spec import make_drafter


def _burst_context_tokens(ctx: np.ndarray, steps: int) -> int:
    """Context tokens ``steps`` decode steps read: each row's context grows by one a step."""
    return int(ctx.sum()) * steps + len(ctx) * steps * (steps - 1) // 2


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


@dataclass
class RaggedInferenceEngineConfig:
    """Parity: reference ``inference/v2/config_v2.py`` (RaggedInferenceEngineConfig)."""
    state_manager: RaggedBatchConfig = field(default_factory=RaggedBatchConfig)
    tensor_parallel: int = 1
    dtype: str = "bfloat16"
    interpret_kernels: Optional[bool] = None  # Pallas interpret mode; default: on unless running on real TPU
    decode_burst: Optional[int] = None  # max fused greedy-decode steps per dispatch
    # (0 disables bursting). None: DS_TPU_DECODE_BURST (default 32).
    fused_step: Optional[bool] = None  # ONE dispatched program per scheduler quantum (SplitFuse
    # mixed prefill+decode). None: on unless DS_TPU_SERVE_FUSED=0; the unfused
    # per-phase dispatch loop stays available as the fallback.
    enable_prefix_cache: Optional[bool] = None  # radix prefix cache: retired prompts keep their
    # KV blocks in a radix tree, new requests skip prefilling a cached prefix
    # (docs/SERVING.md). None: on unless DS_TPU_PREFIX_CACHE=0.
    spec_decode: Optional[bool] = None  # speculative decoding: draft K tokens per decode row
    # and verify them in ONE dispatch (docs/SERVING.md "Speculative decoding").
    # None: off unless DS_TPU_SPEC_DECODE=1.
    spec_k: Optional[int] = None  # max draft tokens per row per step. None: DS_TPU_SPEC_K (default 4).
    spec_drafter: str = "prompt_lookup"  # drafter registry name (inference/v2/spec.py)
    min_decode_bucket: Optional[int] = None  # floor for the padded decode batch: fewer
    # compiled (B, steps) shapes (padded rows write to the garbage page, so a
    # bigger bucket costs nothing real); 1 restores exact power-of-two
    # bucketing. None: DS_TPU_MIN_DECODE_BUCKET (default 8).
    # weight-only quantization (ref inference/quantization + mixed-GEMM):
    # matmul kernels stored int8-in-HBM, dequantized in-kernel per tile
    quant_bits: int = 0  # 0 = off; 8, or 4 (TRUE packed int4 storage, 2 codes/byte)
    quant_group_size: int = 128
    quant_min_size: int = 4096  # leave smaller weights dense
    # tiered KV economy (docs/SERVING.md): int8 paged-KV pools with fused
    # in-kernel dequant, and a host-RAM spill tier behind the prefix cache
    kv_quant_bits: Optional[int] = None  # 8 = int8 K/V pages + per-block-per-head
    # scales (~4x blocks per HBM byte at fp32 baseline). None: DS_TPU_KV_QUANT.
    kv_spill: Optional[bool] = None  # spill prefix-cache evictions to host RAM and
    # re-admit matches via h2d DMA. None: off unless DS_TPU_KV_SPILL=1.

    @classmethod
    def from_dict(cls, d: Dict) -> "RaggedInferenceEngineConfig":
        d = dict(d or {})
        sm = d.pop("state_manager", {})
        if isinstance(sm, dict):
            sm = RaggedBatchConfig(**sm)
        return cls(state_manager=sm, **d)


class InferenceEngineV2:

    def __init__(self, model, params, config: Optional[RaggedInferenceEngineConfig] = None, mesh=None):
        """``model`` is a ``CausalLM`` (or anything exposing ``.cfg``).

        ``config.tensor_parallel > 1`` serves TP-sharded (reference
        ``v2/model_implementations/sharding/``): params shard per the
        model's partition rules, KV pages split over heads, and the
        decode kernel runs under shard_map on the ``tensor`` axis.
        """
        # tuned device profile (docs/OBSERVABILITY.md "Closing the loop"):
        # install the DS_TPU_TUNED_PROFILE knob overlay before ANY knob is
        # resolved, so every None config field below sees the tuned value
        # (explicit env still wins inside the registry)
        from ...autotune.profile import maybe_load_tuned_profile
        maybe_load_tuned_profile()
        if config is None:
            config = RaggedInferenceEngineConfig()
        elif isinstance(config, dict):
            config = RaggedInferenceEngineConfig.from_dict(config)
        self._config = config
        if config.decode_burst is None:
            config.decode_burst = knobs.get_int("DS_TPU_DECODE_BURST")
        if config.min_decode_bucket is None:
            config.min_decode_bucket = max(1, knobs.get_int("DS_TPU_MIN_DECODE_BUCKET"))
        self.model = model
        cfg: TransformerConfig = model.cfg
        if cfg.unstackable:  # by the kinds' records (``layer_kind.py::LayerKind.stackable``)
            raise NotImplementedError(
                f"inference/v2 serves softmax attention over one head size with dense or capacity-gated MoE FFNs; this "
                f"model has layers of kind {list(cfg.unstackable)}: a recurrent state beside the paged KV, a latent cache, a "
                f"choice of keys and a routed FFN's gate are training-side only")
        if cfg.shares:  # (``LayerKind.gives``, ``takes``)
            raise NotImplementedError(f"inference/v2's stacked layers carry activations alone; this model's layers give or take "
                                      f"{list(cfg.shares)} between blocks: training-side only")
        if cfg.attn_output_gate:
            raise NotImplementedError("inference/v2 has no output gate on its attention (attn_output_gate): training-side only")
        if cfg.loop_steps > 1 or cfg.norm_scheme in ("sandwich", "output"):
            raise NotImplementedError(f"inference/v2's step is ONE pass of pre- or post-norm blocks over one cache a layer; loop_steps="
                                      f"{cfg.loop_steps} (a looped model needs a cache a pass and an exit decided a token at decode) and "
                                      f"norm_scheme={cfg.norm_scheme!r} are training-side only")
        self.cfg = cfg
        self.dtype = jnp.bfloat16 if config.dtype in ("bfloat16", "bf16") else jnp.float32

        self._tp = int(config.tensor_parallel)
        if self._tp <= 1:
            # DS_TPU_TP applies only when the config left TP at the default:
            # an explicit config (replay rebuilding a recorded engine, tests
            # pinning a degree) always wins over the environment
            self._tp = max(1, knobs.get_int("DS_TPU_TP") or 1)
            config.tensor_parallel = self._tp
        tp_bits = knobs.get_int("DS_TPU_TP_ALLREDUCE_BITS")
        if tp_bits not in (0, 4, 8):
            raise ValueError(f"DS_TPU_TP_ALLREDUCE_BITS must be 0, 4 or 8, got {tp_bits}")
        self._tp_bits = int(tp_bits)
        self._mesh_topo = None
        if self._tp > 1:
            from ...parallel.mesh import MeshTopology, serving_mesh

            self._mesh_topo = mesh if isinstance(mesh, MeshTopology) else serving_mesh(self._tp)
            if self._mesh_topo.model_parallel_size != self._tp:
                raise ValueError(f"mesh tensor axis {self._mesh_topo.model_parallel_size} != "
                                 f"tensor_parallel {self._tp}")
            if cfg.kv_heads % self._tp or cfg.n_heads % self._tp:
                raise ValueError(f"n_heads {cfg.n_heads} and kv_heads {cfg.kv_heads} must be divisible by "
                                 f"tp={self._tp}")

        smc = config.state_manager
        if smc.max_context > cfg.max_seq_len:
            # positions past max_seq_len would silently clamp the rope/wpe
            # gathers under jit — cap the KV contract to the model's window
            log_dist(f"max_context {smc.max_context} > model max_seq_len {cfg.max_seq_len}; capping", ranks=[0])
            smc = dataclasses.replace(smc, max_context=cfg.max_seq_len)
            config.state_manager = smc
        run_cfg = dataclasses.replace(cfg, dtype=self.dtype)
        if run_cfg.window_layers is not None and len(run_cfg.window_layers) == 0:
            # window_for() applies no window anywhere, but the paged runner
            # reads sliding_window directly — normalize so they agree
            run_cfg = dataclasses.replace(run_cfg, sliding_window=None, window_layers=None)
        if (run_cfg.uniform_window and run_cfg.sliding_window is not None
                and run_cfg.sliding_window >= smc.max_context):
            # the window can never mask inside this engine's context budget;
            # dropping it keeps decode on the Pallas paged kernel (per-layer
            # window models keep their pattern — the runner bakes one kernel
            # variant per distinct per-layer window value)
            run_cfg = dataclasses.replace(run_cfg, sliding_window=None, window_layers=None)
        kvq = config.kv_quant_bits
        if kvq is None:
            kvq = knobs.get_int("DS_TPU_KV_QUANT")
        if kvq not in (0, 8):
            raise ValueError(f"kv_quant_bits must be 0 or 8, got {kvq}")
        self._kv_quant_bits = int(kvq)
        kv_spill = config.kv_spill
        if kv_spill is None:
            kv_spill = knobs.get_bool("DS_TPU_KV_SPILL")
        self._kv_spill = bool(kv_spill)
        if self._tp > 1 and (self._kv_quant_bits or self._kv_spill):
            # the int8 pool is a (codes, scales) pytree and the spill
            # gather/scatter assume single-device pools; the shard_map
            # in_specs and host slabs would both need per-shard layouts
            raise ValueError("kv_quant_bits / kv_spill do not compose with "
                             f"tensor_parallel={self._tp} yet")
        n_blocks = smc.num_kv_blocks
        if n_blocks is None:
            n_blocks = max(8, int(smc.memory_gb * (1 << 30) // self._device_bytes_per_block(smc.kv_block_size)))
        self.state = DSStateManager(smc, n_blocks, enable_prefix_cache=config.enable_prefix_cache)
        self._n_kv_blocks = int(n_blocks)
        # scheduler token budgets: quantum budget defaults to the state
        # config; both are autotune dimensions (DS_TPU_MAX_BATCH_TOKENS=0
        # keeps the config value)
        quantum_tokens = knobs.get_int("DS_TPU_MAX_BATCH_TOKENS") or smc.max_ragged_batch_size
        self.scheduler = RaggedBatchScheduler(self.state,
                                              max_batch_tokens=int(quantum_tokens),
                                              max_sequences=smc.max_ragged_sequence_count,
                                              prefill_chunk=knobs.get_int("DS_TPU_PREFILL_CHUNK"),
                                              shard_degree=self._tp)

        # --- telemetry (docs/OBSERVABILITY.md) ---
        tele = get_telemetry_registry()
        self._m_requests = tele.counter("infer_requests_total")
        self._m_prefill_tokens = tele.counter("infer_prefill_tokens_total")
        self._m_decode_tokens = tele.counter("infer_decode_tokens_total")
        self._m_decode_steps = tele.counter("infer_decode_steps_total")
        self._m_bursts = tele.counter("infer_decode_bursts_total")
        self._m_decode_fill = tele.gauge("infer_decode_batch_fill")
        self._m_prefill_fill = tele.gauge("infer_prefill_batch_fill")
        # fused serving loop: dispatches/quantum invariant + fill factor
        self._m_dispatches = tele.counter("infer_dispatches_total")
        # sum of the context lengths the paged-attention kernels read, over
        # real rows and steps: the count a roofline share of them needs
        self._m_ctx_tokens = tele.counter("paged_attention_context_tokens_total")
        self._tracer = get_tracer()
        register_cache_metrics(jax)  # seconds of every first call, by phase (program_*_seconds_total)
        self._m_fused_quanta = tele.counter("infer_fused_quanta_total")
        self._m_fused_fill = tele.gauge("infer_fused_batch_fill")
        # tensor-parallel serving (docs/SERVING.md "Tensor-parallel
        # serving"): degree gauge + analytic allreduce traffic counter
        self._m_tp_degree = tele.gauge("tp_degree")
        self._m_tp_degree.set(float(self._tp))
        self._m_tp_bytes = tele.counter("infer_tp_allreduce_bytes_total")
        # speculative decoding: draft/accept accounting (the rollback
        # counter lives in the state manager next to the block bookkeeping)
        self._m_spec_proposed = tele.counter("spec_tokens_proposed_total")
        self._m_spec_accepted = tele.counter("spec_tokens_accepted_total")
        self._m_spec_rate = tele.gauge("spec_acceptance_rate")
        # request-lifecycle event log + serving health detectors
        self._events = get_event_log()
        self._health = get_health_monitor()
        self._health.ensure_detector(QueueStallDetector())
        self._health.ensure_detector(SLOBurnRateDetector())
        self._health.ensure_detector(HBMPressureDetector())
        # performance accounting (docs/OBSERVABILITY.md "Performance
        # accounting"): cost cards per compiled program, goodput ledger,
        # per-pool HBM gauges feeding the pressure detector
        self._acct = get_perf_accountant()
        self._m_cow_bytes = tele.counter("kv_cow_bytes_total")
        # expected RMS dequant error of the int8 KV pool (0.0 when off)
        self._m_quant_err = tele.gauge("kv_quant_dequant_error")
        # live ops plane (docs/OBSERVABILITY.md "Ops plane & flight
        # recorder"): introspection server when DS_TPU_OPS_PORT is set,
        # black-box flight recorder when DS_TPU_FLIGHT_DIR is set — both
        # default off, and the disabled path is one int compare each.
        maybe_start_ops_server()
        _rec = maybe_attach_flight_recorder(self._health)
        if _rec is not None:
            _rec.register_provider("residency", self._residency_summary)
            _rec.register_provider("jit_cache", self._jit_cache_summary)
        # device-timeline profiler (telemetry/profiler.py): DS_TPU_PROFILE=1
        # arms a one-shot per-quantum waterfall capture; unset, one bool read
        device_profiler.maybe_arm_profiler()

        # garbage page for padded-token KV writes (allocator's first pop is 0)
        self._garbage_block = self.state._allocator.allocate(1)[0]
        assert self._garbage_block == 0
        self.state.register_sanitizer_root(self._garbage_block)

        L, bs = cfg.n_layers, smc.kv_block_size
        pool_shape = (L, n_blocks, bs, cfg.kv_heads, cfg.head_dim)
        self.k_pages = make_kv_pool(pool_shape, self.dtype, self._kv_quant_bits)
        self.v_pages = make_kv_pool(pool_shape, self.dtype, self._kv_quant_bits)
        self._max_blocks_per_seq = -(-smc.max_context // bs)
        # K+V bytes one block holds across every layer (codes + scales for
        # the int8 pool) — the unit of COW copy traffic, of prefix-cache-
        # held HBM, and of host-tier slot sizing
        self._block_bytes = sum(int(x.nbytes) for x in jax.tree_util.tree_leaves(
            (self.k_pages, self.v_pages))) // n_blocks
        # host spill tier (docs/SERVING.md "Tiered KV economy"): the prefix
        # cache demotes LRU evictions to a host-RAM pool through a dedicated
        # d2h thread and re-admits radix matches via jitted h2d scatter
        self._gather_fn = None   # lazily-jitted per-block pool gather (spill snapshot)
        self._readmit_fn = None  # lazily-jitted donated h2d scatter (re-admission)
        self._spill_mgr = None
        if self._kv_spill and self.state.prefix_cache is not None:
            from .ragged.host_tier import HostKVPool, SpillManager

            leaves = jax.tree_util.tree_leaves((self.k_pages, self.v_pages))
            host_pool = HostKVPool(
                max(1, (knobs.get_int("DS_TPU_KV_HOST_POOL_MB") << 20) // max(1, self._block_bytes)),
                [leaf.shape[:1] + leaf.shape[2:] for leaf in leaves],  # drop the block axis
                [leaf.dtype for leaf in leaves])
            self._spill_mgr = SpillManager(host_pool, self._gather_block)
            self.state.prefix_cache.attach_spill_tier(
                self._spill_mgr, self._readmit_block,
                watermark_blocks=int(knobs.get_float("DS_TPU_KV_SPILL_WATERMARK") * n_blocks))

        cast = lambda x: x.astype(self.dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x
        self.params = jax.tree_util.tree_map(cast, params)
        self._tp_ctx = None
        if self._tp > 1:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from ...module_inject.load_checkpoint import shard_params

            self.params = shard_params(self.params, self.model, mesh=self._mesh_topo, tp_size=self._tp)
            from ...ops.pallas.paged_attention import shard_kv_pool
            self.k_pages = shard_kv_pool(self.k_pages, self._mesh_topo.mesh)
            self.v_pages = shard_kv_pool(self.v_pages, self._mesh_topo.mesh)
            if not config.quant_bits:
                # explicit-collective TP: the per-layer stack runs in ONE
                # shard_map region with tp_all_reduce seams (T3 interleave +
                # optional EQuARX-quantized psum). Weight-only-quantized
                # engines keep the GSPMD path: their matmuls lower through a
                # custom_partitioning that cannot run under manual sharding.
                layer_params = {k: v for k, v in self.params.items()
                                if k.startswith("layer_")}
                specs = jax.tree_util.tree_map(
                    lambda a: getattr(getattr(a, "sharding", None), "spec", P()),
                    layer_params)
                self._tp_ctx = TPContext(mesh=self._mesh_topo.mesh, tp=self._tp,
                                         bits=self._tp_bits, interleave=self._tp,
                                         param_specs=specs)
        # sharding signature: part of every program-cache key and of the
        # journal fingerprint — toggling TP (or the allreduce mode) can
        # never hit a stale compiled program or replay across topologies
        if self._tp_ctx is not None:
            self._shard_sig = self._tp_ctx.signature()
        elif self._tp > 1:
            from ...parallel.mesh import mesh_signature
            self._shard_sig = f"tp{self._tp}:gspmd:{mesh_signature(self._mesh_topo)}"
        else:
            self._shard_sig = "tp1"
        if config.quant_bits:
            # quantize AFTER sharding (the reference's order, GroupQuantizer
            # post-mp-shard in module_inject/replace_module.py:43): K-groups
            # align to the shard split so every shard's scales are local
            from ..quantization import quantize_for_serving

            self.params = quantize_for_serving(self.params, num_bits=config.quant_bits,
                                               group_size=config.quant_group_size,
                                               min_size=config.quant_min_size)
        interpret = config.interpret_kernels
        if interpret is None:
            from ...ops.registry import pallas_available
            interpret = not pallas_available()
        run_mesh = self._mesh_topo.mesh if self._mesh_topo is not None else None
        self._prefill_fn, self._decode_fn = make_step_fns(run_cfg, interpret=interpret, mesh=run_mesh,
                                                          tp=self._tp, tp_ctx=self._tp_ctx)
        self._run_cfg, self._interpret, self._run_mesh = run_cfg, interpret, run_mesh
        # the accountant wraps the RAW jitted programs (innermost), so cost
        # cards trace/AOT-analyze the real executable; the JitAuditor wraps
        # outside and its recompile semantics are untouched
        self._prefill_fn = self._acct.wrap("prefill", self._prefill_fn, family="prefill")
        self._decode_fn = self._acct.wrap("decode", self._decode_fn, family="decode")
        for family in ("prefill", "decode"):
            tele.counter("program_builds_total", family=family).inc()
        # runtime sanitizers (analysis/, all off by default): recompile audit
        # wraps every jitted serving program; the transfer guard scopes the
        # serving loops so implicit device->host syncs raise
        self.jit_auditor = None
        if knobs.get_bool("DS_TPU_JIT_AUDIT"):
            from ...analysis.jit_audit import JitAuditor

            self.jit_auditor = JitAuditor(monitor=self._health)
            self._prefill_fn = self.jit_auditor.wrap("prefill", self._prefill_fn)
            self._decode_fn = self.jit_auditor.wrap("decode", self._decode_fn)
        self._guard_enabled = knobs.get_bool("DS_TPU_TRANSFER_GUARD")
        # program-cache capacity (burst/fused/spec families share it); an
        # autotune dimension — bigger caches trade HBM for fewer recompiles
        self._max_program_variants = max(1, knobs.get_int("DS_TPU_PROGRAM_CACHE"))
        self._program_builds = 0  # misses of the three caches below, so far
        self._bursts: Dict[tuple, object] = {}  # sampling signature -> jitted burst
        self._fused_fns: Dict[tuple, object] = {}  # (bucket shape, sampling) -> jitted fused step
        self._cow_fn = None  # lazily-jitted donated page copy for copy-on-write
        fused = config.fused_step
        if fused is None:
            fused = knobs.get_bool("DS_TPU_SERVE_FUSED")
        self._fused_enabled = bool(fused)
        spec = config.spec_decode
        if spec is None:
            spec = knobs.get_bool("DS_TPU_SPEC_DECODE")
        self._spec_enabled = bool(spec)
        spec_k = config.spec_k
        if spec_k is None:
            spec_k = knobs.get_int("DS_TPU_SPEC_K")
        self._spec_k = max(1, int(spec_k))
        self._drafter = make_drafter(config.spec_drafter)
        self._spec_fns: Dict[tuple, object] = {}  # (chunk, sampling) -> jitted verify
        self._spec_proposed_run = 0  # cumulative, for the acceptance-rate gauge
        self._spec_accepted_run = 0
        self._sampling = None  # (do_sample, temperature, top_k, top_p) during generate()
        self._rng = jax.random.PRNGKey(0)
        self._update_hbm_gauges()
        log_dist(f"InferenceEngineV2: {n_blocks} KV blocks x {bs} tokens "
                 f"({n_blocks * bs} cached tokens), dtype={config.dtype}"
                 + (f", kv_quant=int{self._kv_quant_bits}" if self._kv_quant_bits else "")
                 + (", kv_spill=host" if self._spill_mgr is not None else ""), ranks=[0])

    def _device_bytes_per_block(self, block_size: int) -> int:
        """Bytes ONE KV block (K and V, every layer) takes inside a compiled
        program, summed over the devices it is sharded on. On a TPU that is
        not the logical size: XLA lays arrays out in (8, 128) tiles of the
        two minor dims, so a bf16 pool's (KVH, D) = (12, 64) occupies
        (16, 128) — 2.67x — and under TP every shard pads its own KVH slice.
        A pool at rest is stored compactly, but the K-step decode burst works
        on tiled copies of both pools: sized from logical bytes, the default
        4 GB budget asked a 16 GB chip for 17.5 GB. Other backends do not
        tile, and there the logical size is the answer."""
        cfg, tp = self.cfg, self._tp
        pool = jax.eval_shape(lambda: make_kv_pool(
            (cfg.n_layers, 1, block_size, cfg.kv_heads // tp, cfg.head_dim), self.dtype, self._kv_quant_bits))
        tiled = jax.default_backend() == "tpu"

        def nbytes(leaf):
            shape = list(leaf.shape)
            if tiled:
                shape[-2:] = [-(-shape[-2] // 8) * 8, -(-shape[-1] // 128) * 128]
            return int(np.prod(shape)) * leaf.dtype.itemsize

        return 2 * tp * sum(nbytes(leaf) for leaf in jax.tree_util.tree_leaves(pool))

    _MAX_BURST_VARIANTS = 8  # class default; instances use DS_TPU_PROGRAM_CACHE

    def _burst_for(self, sampling):
        """Cached jitted burst per sampling signature (greedy = None).

        The cache is bounded: sampling params are user floats, so a
        frontend forwarding per-request temperatures would otherwise grow
        compiled burst programs without limit — least-recently-used
        signature evicted (its executables free with the jit wrapper)."""
        if self._config.decode_burst < 2:
            return None
        key = (sampling or (False, 1.0, 0, 1.0)) + (self._shard_sig,)
        do, t, k, p = key[:4]
        return self._cached_program(self._bursts, "burst", key, lambda: make_burst_fn(
            self._run_cfg, interpret=self._interpret, mesh=self._run_mesh, tp=self._tp, tp_ctx=self._tp_ctx,
            do_sample=do, temperature=t, top_k=k, top_p=p))

    def _cached_program(self, cache: Dict[tuple, object], family: str, key: tuple, build):
        """One lookup in an LRU-bounded program cache (burst, fused and spec
        share the discipline and the capacity): a miss builds, wraps and
        counts the program (``program_builds_total``) and evicts the least
        recently used one past capacity (``program_evictions_total``; its
        executables free with the jit wrapper); a hit moves the key to the
        tail, so a hot signature (greedy) survives a frontend cycling
        through sampling configs."""
        fn = cache.pop(key, None)
        if fn is None:
            tele = get_telemetry_registry()
            if len(cache) >= self._max_program_variants:
                cache.pop(next(iter(cache)))
                tele.counter("program_evictions_total", family=family).inc()
            fn = self._acct.wrap(f"{family}{key}", build(), family=family, bucket=key)
            if self.jit_auditor is not None:
                fn = self.jit_auditor.wrap(f"{family}{key}", fn)
            tele.counter("program_builds_total", family=family).inc()
            self._program_builds += 1
        cache[key] = fn
        return fn

    def _account_tp_allreduce(self, tokens: int) -> None:
        """Analytic TP-collective traffic for one dispatch: every padded
        token crosses the two per-layer row-parallel reduces (post-attention
        and post-MLP), each moving d_model elements per layer — at the
        quantized width when the EQuARX reduce is on, else at the activation
        dtype. Pure host arithmetic; zero when tp=1."""
        if self._tp <= 1 or tokens <= 0:
            return
        nbits = self._tp_bits if (self._tp_bits and self._tp_ctx is not None) \
            else jnp.dtype(self.dtype).itemsize * 8
        self._m_tp_bytes.inc(tokens * self.cfg.d_model * 2 * self.cfg.n_layers * nbits // 8)

    def _choose_tokens_dev(self, logits):
        """Device-side token choice for (n, V) logits: argmax, or the shared
        sampler during a sampling generate(). Returns a DEVICE (n,) array —
        callers that need host ints go through ``_choose_tokens``; the
        deferred serving loop keeps the array on device instead."""
        if self._sampling is None:
            return jnp.argmax(logits, axis=-1)
        from ..generation import sample_logits

        do, t, k, p = self._sampling
        self._rng, r = jax.random.split(self._rng)
        return sample_logits(logits, r, do, t, k, p)

    def _choose_tokens(self, logits) -> np.ndarray:
        # the serving loop's per-step token fetch: B ints, not B*V logits
        return jax.device_get(self._choose_tokens_dev(logits))  # graft-lint: readback

    # ---------------------------------------------------------- feasibility
    def query(self, uid: int, max_request_length: int) -> Tuple[int, int]:
        """(max new tokens schedulable, free KV blocks). Reference engine_v2.py:184."""
        seq = self.state.get_sequence(uid)
        # feasibility plans against free + cache-reclaimable blocks (the
        # allocator evicts cached prefixes on demand under pressure)
        free_tokens = self.state.available_blocks * self.state.block_size
        if seq is not None:
            free_tokens += seq.max_context - seq.seen_tokens
        return min(max_request_length, free_tokens), self.state.free_blocks

    def can_put(self, uid: int, tokens: Sequence[int]) -> bool:
        seq = self.state.get_sequence(uid)
        bs = self.state.block_size
        if seq is None:
            need = -(-len(tokens) // bs)
        else:
            need = seq.blocks_needed(len(tokens))
        return self.state.can_allocate(need)

    # ---------------------------------------------------------- core step
    def put(self, batch_uids: Sequence[int], batch_tokens: Sequence[Sequence[int]],
            return_tokens: bool = False, _defer: bool = False):
        """Run one engine step over a ragged batch; returns next-token logits (B, V).

        Sequences with multiple tokens run as (chunked) prefill; known
        sequences with a single token join one batched paged-decode call.
        ``return_tokens=True`` argmaxes ON DEVICE and returns (B,) token
        ids — the serving loop's per-step readback shrinks from B*V floats
        (~6 MB at batch 32 / 50k vocab) to B ints.

        ``_defer`` (internal, serving loop): identical routing, but token
        entries may be 0-d DEVICE arrays and the return is a list of
        per-row device arrays — nothing syncs to the host.
        """
        if len(batch_uids) != len(batch_tokens):
            raise ValueError("uids and token lists must align")
        with telemetry_span("infer/put", rows=len(batch_uids), tokens=sum(len(t) for t in batch_tokens)):
            return self._put(batch_uids, batch_tokens, return_tokens, _defer)

    def _put(self, batch_uids, batch_tokens, return_tokens: bool, _defer: bool):
        if len(set(batch_uids)) != len(batch_uids):
            # two chunks of one sequence in a single step would read the same
            # start position and overwrite each other's KV slots — the
            # scheduler never emits this; refuse instead of corrupting
            raise ValueError("duplicate uid in one put() batch: submit a sequence's chunks "
                             "in separate steps")
        logits_by_idx: Dict[int, object] = {}

        decode_idx: List[int] = []
        prefill_groups: Dict[int, List[int]] = {}  # padded length bucket -> indices
        for i, (uid, toks) in enumerate(zip(batch_uids, batch_tokens)):
            seq = self.state.get_sequence(uid)
            if seq is not None and len(toks) == 1:
                decode_idx.append(i)
            else:
                prefill_groups.setdefault(max(16, _next_pow2(len(toks))), []).append(i)

        # prefills sharing a length bucket run as ONE batched dispatch (the
        # reference's ragged batch mixes all prefills into one forward;
        # here same-bucket grouping keeps shapes static). The scheduler
        # hands out uniform prefill chunks, so admission phases coalesce.
        for S, idxs in prefill_groups.items():
            rows = self._run_prefill_batch([batch_uids[i] for i in idxs],
                                           [list(batch_tokens[i]) for i in idxs], S,
                                           return_tokens=return_tokens, defer=_defer)
            for j, i in enumerate(idxs):
                logits_by_idx[i] = rows[j]

        if decode_idx:
            uids = [batch_uids[i] for i in decode_idx]
            carried = [batch_tokens[i][0] for i in decode_idx]
            if _defer:
                # device scalars (or host ints from a 1-token tail chunk)
                # stack into the input ids without a host sync
                ids_dev = self._ids_from_carry(carried, self._decode_bucket(len(uids)))
                out = self._run_decode(uids, [0] * len(uids), return_tokens=return_tokens,
                                       ids_dev=ids_dev, defer=True)
            else:
                out = self._run_decode(uids, [int(t) for t in carried], return_tokens=return_tokens)
            for j, i in enumerate(decode_idx):
                logits_by_idx[i] = out[j]
        if _defer:
            return [logits_by_idx[i] for i in range(len(batch_uids))]
        return np.stack([logits_by_idx[i] for i in range(len(batch_uids))])

    def flush(self, uids: Sequence[int]) -> None:
        for uid in uids:
            self.state.flush_sequence(uid)

    # ---------------------------------------------------------- internals
    def _seq_block_row(self, seq) -> np.ndarray:
        return self.state.block_table_row(seq, self._max_blocks_per_seq, self._garbage_block)

    def _garbage_slots(self, n: int) -> np.ndarray:
        # round-robin within the garbage page so padded writes stay cheap
        return (self._garbage_block * self.state.block_size + np.arange(n) % self.state.block_size).astype(np.int32)

    def _copy_block(self, src: int, dst: int) -> None:
        """Copy-on-write page copy: duplicate block ``src`` into ``dst``
        across every layer's K/V pool. Jitted with donation so the pools
        update in place; src/dst are traced scalars, so one compiled
        program serves every copy. The tree_map makes one program cover
        both pool representations: a plain page array, or the int8
        ``(codes, scales)`` pytree — a COW'd quantized block copies its
        scale plane with its codes, so dequant stays exact."""
        if self._cow_fn is None:
            # page-copy sharding note: the program specializes on the donated
            # pools' shardings (GSPMD keeps the head axis split under TP), and
            # the cache is per-engine — toggling TP builds a new engine, so a
            # stale single-chip copy program is unreachable by construction
            copy_at = lambda pool, s, d: jax.tree_util.tree_map(
                lambda p: p.at[:, d].set(p[:, s]), pool)
            self._cow_fn = jax.jit(
                lambda kp, vp, s, d: (copy_at(kp, s, d), copy_at(vp, s, d)),
                donate_argnums=(0, 1))
            # timed=False: COW dispatches inside another quantum's window,
            # so it must not steal that quantum's time attribution — its
            # cost is accounted in bytes, not seconds
            self._cow_fn = self._acct.wrap("cow_copy", self._cow_fn, timed=False)
            if self.jit_auditor is not None:
                self._cow_fn = self.jit_auditor.wrap("cow_copy", self._cow_fn)
        self.k_pages, self.v_pages = self._cow_fn(self.k_pages, self.v_pages, src, dst)
        self._m_cow_bytes.inc(self._block_bytes)
        self._acct.note_cow(self._block_bytes)

    def _cow_ready(self, seq, start_pos: int) -> None:
        self.state.ensure_writable(seq, start_pos, self._copy_block)

    # ----------------------------------------------------- host spill tier
    def _gather_block(self, block: int):
        """Device snapshot of one block's pages across every pool leaf —
        independent buffers, so the spill thread's later d2h readback
        cannot race the donated in-place pool updates that follow. The
        block id is traced: one compiled program serves every spill."""
        if self._gather_fn is None:
            fn = jax.jit(lambda pools, b: [p[:, b] for p in jax.tree_util.tree_leaves(pools)])
            # timed=False: like the COW copy, the gather dispatches inside
            # another quantum's attribution window
            fn = self._acct.wrap("kv_spill_gather", fn, timed=False)
            if self.jit_auditor is not None:
                fn = self.jit_auditor.wrap("kv_spill_gather", fn)
            self._gather_fn = fn
        return self._gather_fn((self.k_pages, self.v_pages), block)

    def _readmit_block(self, block: int, host_leaves) -> None:
        """Re-admission h2d: scatter one host-tier block's leaves back
        into the device pools at ``block``. Donated like the COW copy so
        the pools update in place; the host buffers ride the dispatch as
        ordinary operands (the transfer IS the DMA)."""
        if self._readmit_fn is None:
            def scat(pools, b, bufs):
                flat, treedef = jax.tree_util.tree_flatten(pools)
                return jax.tree_util.tree_unflatten(
                    treedef, [p.at[:, b].set(u) for p, u in zip(flat, bufs)])
            fn = jax.jit(scat, donate_argnums=(0,))
            fn = self._acct.wrap("kv_readmit", fn, timed=False)
            if self.jit_auditor is not None:
                fn = self.jit_auditor.wrap("kv_readmit", fn)
            self._readmit_fn = fn
        self.k_pages, self.v_pages = self._readmit_fn(
            (self.k_pages, self.v_pages), block, list(host_leaves))

    def _run_prefill_batch(self, uids: List[int], token_lists: List[List[int]], S: int,
                           return_tokens: bool = False, defer: bool = False):
        """Prefill a bucket of sequence chunks (each possibly with prior
        context) in one dispatch; the batch dim pads to a power of two so
        the compile ladder stays logarithmic. Padded rows write their KV
        to the garbage page and their outputs are dropped."""
        n = len(uids)
        B = _next_pow2(n)
        bs = self.state.block_size
        # validate the WHOLE bucket before mutating any state: a mid-loop
        # allocation failure would otherwise leave earlier sequences with
        # in-flight tokens and allocated blocks whose forward never ran —
        # and the validation itself must not register new uids in the
        # tracker (a rejected request would leak its descriptor slot)
        total_need = 0
        for uid, tokens in zip(uids, token_lists):
            seq = self.state.get_sequence(uid)
            seen = (seq.seen_tokens + seq.in_flight_tokens) if seq is not None else 0
            if seen + len(tokens) > self.state.max_context:
                raise RuntimeError(f"sequence {uid}: {seen + len(tokens)} tokens exceeds max_context "
                                   f"{self.state.max_context}")
            if seq is not None:
                total_need += seq.blocks_needed(len(tokens)) + seq.cow_blocks_needed(seen)
            else:
                total_need += -(-len(tokens) // bs)
        if not self.state.can_allocate(total_need):
            raise RuntimeError(f"prefill bucket needs {total_need} KV blocks, "
                               f"{self.state.free_blocks} free")
        ids = np.zeros((B, S), np.int32)
        positions = np.zeros((B, S), np.int32)
        slots = np.tile(self._garbage_slots(S), B).reshape(B, S)
        ctx = np.ones((B,), np.int32)
        bt = np.full((B, self._max_blocks_per_seq), self._garbage_block, np.int32)
        last = np.zeros((B,), np.int32)
        seqs = []
        for j, (uid, tokens) in enumerate(zip(uids, token_lists)):
            seq = self.state.get_or_create_sequence(uid)
            self._cow_ready(seq, seq.seen_tokens)
            self.state.allocate_for(seq, len(tokens))
            self.state.sanitize_write(seq, seq.seen_tokens, len(tokens))
            seq.record_tokens(tokens)
            seq.pre_forward(len(tokens))
            start, m = seq.seen_tokens, len(tokens)
            ids[j, :m] = tokens
            positions[j, :m] = np.arange(start, start + m)
            pos = start + np.arange(m)
            slots[j, :m] = np.asarray(seq.blocks, np.int32)[pos // bs] * bs + pos % bs
            ctx[j] = start + m
            bt[j] = self._seq_block_row(seq)
            last[j] = m - 1
            seqs.append(seq)

        with telemetry_span("infer/prefill", bucket=S, rows=n):
            logits, self.k_pages, self.v_pages = self._prefill_fn(self.params, jnp.asarray(ids),
                                                                  jnp.asarray(positions),
                                                                  self.k_pages, self.v_pages, jnp.asarray(bt),
                                                                  jnp.asarray(ctx), jnp.asarray(slots.reshape(-1)),
                                                                  jnp.asarray(last))
        self._m_dispatches.inc()
        self._m_ctx_tokens.inc(int(ctx[:n].sum()))
        self._m_prefill_tokens.inc(sum(len(t) for t in token_lists))
        self._m_prefill_fill.set(n / B)
        for seq in seqs:
            seq.post_forward()
        useful = sum(len(t) for t in token_lists)
        self._account_tp_allreduce(B * S)
        if defer:
            out_dev = self._choose_tokens_dev(logits[:n])  # device (n,) ids, no readback
            self._acct.attribute(useful, B * S)
            device_profiler.note_quantum("prefill", rows=n, bucket=S, tokens=useful)
            return out_dev
        if return_tokens:
            out = self._choose_tokens(logits[:n])  # device argmax/sample, tiny readback
        else:
            out = jax.device_get(logits[:n])  # graft-lint: readback (caller asked for host logits)
        # attribution window closes AFTER the readback: in synchronous
        # paths the wall time covers the device execution
        self._acct.attribute(useful, B * S)
        device_profiler.note_quantum("prefill", rows=n, bucket=S, tokens=useful)
        return [out[j] for j in range(n)]

    def _decode_bucket(self, n: int) -> int:
        return max(self._config.min_decode_bucket, _next_pow2(n))

    def _assemble_decode(self, uids: List[int], tokens: List[int], steps: int):
        """Shared decode-batch assembly for single steps and bursts.

        Allocates ``steps`` KV tokens per sequence and builds the padded
        (ids, positions, ctx, block tables, (steps, B) slot table, last)
        arrays; padded rows write every step's KV into the garbage page.
        """
        n = len(uids)
        B = self._decode_bucket(n)
        bs = self.state.block_size
        ids = np.zeros((B, 1), np.int32)
        positions = np.zeros((B, 1), np.int32)
        ctx = np.zeros((B,), np.int32)
        bt = np.full((B, self._max_blocks_per_seq), self._garbage_block, np.int32)
        slots = np.tile(self._garbage_slots(B)[None], (steps, 1))
        seqs = []
        step_idx = np.arange(steps)
        for j, (uid, tok) in enumerate(zip(uids, tokens)):
            seq = self.state.get_sequence(uid)
            self._cow_ready(seq, seq.seen_tokens)
            self.state.allocate_for(seq, steps)
            self.state.sanitize_write(seq, seq.seen_tokens, steps)
            seq.record_tokens(None)  # decode ids may be device-side: freeze the log
            seq.pre_forward(steps)
            pos0 = seq.seen_tokens
            ids[j, 0] = tok
            positions[j, 0] = pos0
            ctx[j] = pos0 + 1
            bt[j] = self._seq_block_row(seq)
            p = pos0 + step_idx
            slots[:, j] = np.asarray(seq.blocks, np.int32)[p // bs] * bs + p % bs
            seqs.append(seq)
        last = np.zeros((B,), np.int32)
        return ids, positions, ctx, bt, slots, last, seqs, n

    def _ids_from_carry(self, carried, B: int):
        """(B, 1) decode input ids from per-sequence DEVICE scalars — a
        stack + pad that never touches the host (the deferred serving
        loop's replacement for the ``ids[j, 0] = int(tok)`` host write)."""
        n = len(carried)
        # pad the scalar list to the bucket BEFORE stacking: the stacked shape
        # (and the whole eager op chain) then depends only on B, not on n —
        # per-n shapes were a one-program-per-batch-size compile ladder
        col = [jnp.asarray(t, jnp.int32).reshape(()) for t in carried]
        col.extend([jnp.zeros((), jnp.int32)] * (B - n))  # padded rows feed the garbage page
        return jnp.stack(col).reshape(B, 1)

    def _run_decode(self, uids: List[int], tokens: List[int], return_tokens: bool = False,
                    ids_dev=None, defer: bool = False):
        ids, positions, ctx, bt, slots, last, seqs, n = self._assemble_decode(uids, tokens, steps=1)
        ids_in = ids_dev if ids_dev is not None else jnp.asarray(ids)
        with telemetry_span("infer/decode", rows=n):
            logits, self.k_pages, self.v_pages = self._decode_fn(self.params, ids_in, jnp.asarray(positions),
                                                                 self.k_pages, self.v_pages, jnp.asarray(bt),
                                                                 jnp.asarray(ctx), jnp.asarray(slots[0]),
                                                                 jnp.asarray(last))
        self._m_dispatches.inc()
        self._m_ctx_tokens.inc(int(ctx[:n].sum()))
        self._m_decode_steps.inc()
        self._m_decode_tokens.inc(n)
        self._m_decode_fill.set(n / len(ctx))
        if self._events.enabled:
            q = self.scheduler.last_quantum_id
            for uid in uids:
                self._events.emit("decode", uid, q=q, k=1)
        for seq in seqs:
            seq.post_forward()
        self._account_tp_allreduce(len(ctx))
        if defer:
            out_dev = self._choose_tokens_dev(logits[:n])  # device (n,) ids, no readback
            self._acct.attribute(n, len(ctx))
            device_profiler.note_quantum("decode", rows=n)
            return out_dev
        if return_tokens:
            out = self._choose_tokens(logits[:n])  # device argmax/sample, tiny readback
        else:
            out = jax.device_get(logits[:n])  # graft-lint: readback (caller asked for host logits)
        self._acct.attribute(n, len(ctx))
        device_profiler.note_quantum("decode", rows=n)
        return out

    def _burst_steps(self, live: Dict[int, int], remaining: int) -> int:
        """Largest power-of-two burst length every live sequence can take.

        Powers of two keep the number of distinct (B, steps) compiles to a
        log ladder. 0 means burst is not worthwhile/feasible.
        """
        if self._config.decode_burst < 2 or not live:
            return 0
        cap = min(remaining, self._config.decode_burst,
                  *(self._config.state_manager.max_context - self.state.get_sequence(u).seen_tokens
                    for u in live))
        k = 1
        while k * 2 <= cap:
            k *= 2
        while k >= 2:
            need = sum(self.state.get_sequence(u).blocks_needed(k) for u in live)
            if self.state.can_allocate(need):
                return k
            k //= 2
        return 0

    def _run_decode_burst(self, uids: List[int], tokens: List[int], steps: int,
                          ids_dev=None, defer: bool = False) -> np.ndarray:
        """``steps`` fused greedy-decode steps; returns (len(uids), steps) tokens."""
        ids, positions, ctx, bt, slots, last, seqs, n = self._assemble_decode(uids, tokens, steps)
        ids_in = ids_dev if ids_dev is not None else jnp.asarray(ids)
        self._rng, burst_rng = jax.random.split(self._rng)
        with telemetry_span("infer/decode_burst", rows=n, steps=steps):
            toks, self.k_pages, self.v_pages = self._burst_for(self._sampling)(
                self.params, ids_in, jnp.asarray(positions), self.k_pages, self.v_pages,
                jnp.asarray(bt), jnp.asarray(ctx), jnp.asarray(slots), jnp.asarray(last), burst_rng)
        self._m_dispatches.inc()
        self._m_ctx_tokens.inc(_burst_context_tokens(ctx[:n], steps))
        self._m_bursts.inc()
        self._m_decode_steps.inc(steps)
        self._m_decode_tokens.inc(n * steps)
        self._m_decode_fill.set(n / len(ctx))
        # out-of-band burst: claims its own quantum id (no schedule call)
        q = self.scheduler.next_quantum()
        if self._events.enabled:
            for uid in uids:
                self._events.emit("decode", uid, q=q, k=steps)
        journal = get_journal()
        if journal is not None and journal.active:
            journal.record_quantum(q, uids, [], steps=steps)
        for seq in seqs:
            seq.post_forward()
        self._account_tp_allreduce(len(ctx) * steps)
        if defer:
            self._acct.attribute(n * steps, len(ctx) * steps)
            device_profiler.note_quantum("decode_burst", rows=n, steps=steps)
            return toks[:n]  # device (n, steps), no readback
        out = jax.device_get(toks[:n])  # graft-lint: readback (n*steps ints, the burst's one fetch)
        self._acct.attribute(n * steps, len(ctx) * steps)
        device_profiler.note_quantum("decode_burst", rows=n, steps=steps)
        return out

    # ---------------------------------------------------------- fused quantum
    def _fused_bucket(self, n_dec: int, n_pre: int, max_chunk: int) -> Tuple[int, int, int]:
        """Padded (decode rows, prefill rows, chunk) bucket for a quantum —
        the (total_tokens_pow2, n_seqs_pow2) ladder that keeps the fused
        program cache logarithmic: the decode segment rides the existing
        decode bucket floor, prefill rows pad to a power of two, and the
        chunk length pads like the unfused prefill buckets (single-token
        tail chunks keep chunk == 1: they are decode-shaped and unify
        into one kernel launch)."""
        D = self._decode_bucket(n_dec) if n_dec else 0
        P = _next_pow2(n_pre) if n_pre else 0
        if n_pre == 0:
            S = 0
        elif max_chunk == 1:
            S = 1
        else:
            S = max(16, _next_pow2(max_chunk))
        return D, P, S

    _MAX_FUSED_VARIANTS = 8  # class default; instances use DS_TPU_PROGRAM_CACHE

    def _fused_for(self, n_dec: int, n_pre: int, chunk: int, sampling):
        """LRU-bounded cache of fused-step programs keyed on the padded
        bucket shape + sampling signature — the fused sibling of
        ``_burst_for`` (same eviction discipline: each value owns its jit
        wrapper, so eviction frees the compiled executables). The burst
        step count is NOT part of the key: it rides the follow-on slot
        table's leading dim, so one wrapper serves the whole ladder."""
        key = (n_dec, n_pre, chunk) + (sampling or (False, 1.0, 0, 1.0)) + (self._shard_sig,)
        do, t, k, p = key[3:7]
        return self._cached_program(self._fused_fns, "fused", key, lambda: make_fused_step_fn(
            self._run_cfg, interpret=self._interpret, mesh=self._run_mesh, tp=self._tp, tp_ctx=self._tp_ctx,
            n_dec=n_dec, n_pre=n_pre, chunk=chunk, do_sample=do, temperature=t, top_k=k, top_p=p))

    def _run_fused(self, quantum: FusedQuantum, decode_carry: List, steps: int, defer: bool,
                   eos_token_id: Optional[int]) -> Dict[int, object]:
        """ONE dispatch for a whole scheduler quantum: decode rows and
        chunked-prefill rows run as a single flat ragged batch, then the
        batch advances ``steps - 1`` more decode steps in-graph (pure-
        decode quanta only — mixed quanta run with steps == 1 so the next
        admission wave isn't starved).

        Returns uid -> (steps,) token row (device array when ``defer``,
        np otherwise), or None for a mid-prompt prefill chunk (its logits
        are not a sampled token yet).
        """
        dec_uids = quantum.decode_uids
        prefills = quantum.prefills
        n_dec, n_pre = len(dec_uids), len(prefills)
        assert steps == 1 or n_pre == 0, "multi-step bursts are pure-decode"
        max_chunk = max((len(p.tokens) for p in prefills), default=0)
        D, P, S = self._fused_bucket(n_dec, n_pre, max_chunk)
        N = D + P
        pre_tokens = sum(len(p.tokens) for p in prefills)
        real, slot_tokens = n_dec * steps + pre_tokens, D * steps + P * S
        # the quantum's span tree (docs/OBSERVABILITY.md): one span for the
        # whole call, one child a phase, each with the scheduler's quantum id
        q = self.scheduler.last_quantum_id
        attrs = {}
        if self._tracer.enabled:
            attrs = dict(q=q, kind="decode" if not n_pre else ("mixed" if n_dec else "prefill"), n_dec=n_dec,
                         n_pre=n_pre, prefill_tokens=pre_tokens, steps=steps, bucket=(D, P, S),
                         tokens=real, slots=slot_tokens)
        with telemetry_span("infer/fused_step", **attrs):
            with telemetry_span("fused/validate", q=q):
                self._validate_fused(dec_uids, prefills, steps)
            with telemetry_span("fused/operands", q=q):
                operands, ctx, seqs = self._fused_operands(dec_uids, prefills, decode_carry, steps, defer,
                                                           eos_token_id, D, P, S)
            with telemetry_span("fused/program", q=q) as sp:
                built = self._program_builds
                fn = self._fused_for(D, P, S, self._sampling)
                sp.set(miss=self._program_builds != built)
            with telemetry_span("fused/dispatch", q=q, steps=steps):
                toks, self.k_pages, self.v_pages = fn(self.params, operands[0], operands[1], self.k_pages,
                                                      self.v_pages, *operands[2:])
            with telemetry_span("fused/account", q=q):
                # while the device runs: nothing here waits for it
                self._m_dispatches.inc()
                self._m_fused_quanta.inc()
                self._m_ctx_tokens.inc(_burst_context_tokens(ctx[:n_dec], steps) + int(ctx[D:D + n_pre].sum()))
                self._m_fused_fill.set(real / max(1, slot_tokens))
                self._account_tp_allreduce(slot_tokens)
                if self._events.enabled and dec_uids:
                    for uid in dec_uids:
                        self._events.emit("decode", uid, q=q, k=steps)
                if n_dec:
                    self._m_decode_steps.inc(steps)
                    self._m_decode_tokens.inc(n_dec * steps)
                if prefills:
                    self._m_prefill_tokens.inc(pre_tokens)
                for seq in seqs:
                    seq.post_forward()
            with telemetry_span("fused/readback", q=q):
                # non-deferred mode fetches the quantum's sampled tokens in ONE
                # readback (N*steps ints) instead of one tiny transfer per row;
                # the accountant's window closes at that readback
                toks_host = None if defer else jax.device_get(toks)  # graft-lint: readback
                self._acct.attribute(real, slot_tokens)
                device_profiler.note_quantum("fused_step", rows=N, tokens=real, steps=steps)
                out: Dict[int, object] = {}
                for j, uid in enumerate(dec_uids):
                    out[uid] = toks[j] if defer else toks_host[j]
                for r, pf in enumerate(prefills):
                    if pf.final:
                        out[pf.uid] = toks[D + r] if defer else toks_host[D + r]
                    else:
                        out[pf.uid] = None
        return out

    def _validate_fused(self, dec_uids: List[int], prefills, steps: int) -> None:
        """Validate the WHOLE quantum before mutating any state (same
        discipline as _run_prefill_batch: a mid-loop allocation failure
        must not strand in-flight tokens or leak descriptor slots)."""
        bs = self.state.block_size
        total_need = 0
        for uid in dec_uids:
            seq = self.state.get_sequence(uid)
            if seq.seen_tokens + seq.in_flight_tokens + steps > self.state.max_context:
                raise RuntimeError(f"sequence {uid}: {seq.seen_tokens + steps} tokens exceeds "
                                   f"max_context {self.state.max_context}")
            total_need += seq.blocks_needed(steps) + seq.cow_blocks_needed(seq.seen_tokens)
        for pf in prefills:
            seq = self.state.get_sequence(pf.uid)
            seen = (seq.seen_tokens + seq.in_flight_tokens) if seq is not None else 0
            if seen + len(pf.tokens) > self.state.max_context:
                raise RuntimeError(f"sequence {pf.uid}: {seen + len(pf.tokens)} tokens exceeds "
                                   f"max_context {self.state.max_context}")
            if seq is not None:
                total_need += seq.blocks_needed(len(pf.tokens)) + seq.cow_blocks_needed(seen)
            else:
                total_need += -(-len(pf.tokens) // bs)
        if not self.state.can_allocate(total_need):
            raise RuntimeError(f"fused quantum needs {total_need} KV blocks, "
                               f"{self.state.free_blocks} free")

    def _fused_operands(self, dec_uids: List[int], prefills, decode_carry: List, steps: int, defer: bool,
                        eos_token_id: Optional[int], D: int, P: int, S: int):
        """Allocate the quantum's KV slots and build the fused program's
        operands on the device: ``(ids, positions, block table, ctx, slots,
        last, follow-on slots, garbage slots, eos, rng)``; also the host
        ``ctx`` and the sequences to ``post_forward``."""
        n_dec = len(dec_uids)
        T, N = D + P * S, D + P
        bs = self.state.block_size
        ids = np.zeros((T,), np.int32)
        positions = np.zeros((T,), np.int32)
        slots0 = self._garbage_slots(T)
        ctx = np.ones((N,), np.int32)
        bt = np.full((N, self._max_blocks_per_seq), self._garbage_block, np.int32)
        last = np.zeros((N,), np.int32)
        gslots = self._garbage_slots(N)
        adv = np.tile(gslots[None], (steps - 1, 1))
        step_idx = np.arange(1, steps)
        seqs = []

        for j, uid in enumerate(dec_uids):
            seq = self.state.get_sequence(uid)
            self._cow_ready(seq, seq.seen_tokens)
            self.state.allocate_for(seq, steps)
            self.state.sanitize_write(seq, seq.seen_tokens, steps)
            seq.record_tokens(None)  # decode ids may be device-side: freeze the log
            seq.pre_forward(steps)
            pos0 = seq.seen_tokens
            blocks = np.asarray(seq.blocks, np.int32)
            if not defer:
                ids[j] = int(decode_carry[j])
            positions[j] = pos0
            ctx[j] = pos0 + 1
            bt[j] = self._seq_block_row(seq)
            last[j] = j
            slots0[j] = blocks[pos0 // bs] * bs + pos0 % bs
            if steps > 1:
                p = pos0 + step_idx
                adv[:, j] = blocks[p // bs] * bs + p % bs
            seqs.append(seq)

        for r, pf in enumerate(prefills):
            seq = self.state.get_or_create_sequence(pf.uid)
            m = len(pf.tokens)
            self._cow_ready(seq, seq.seen_tokens)
            self.state.allocate_for(seq, m)
            self.state.sanitize_write(seq, seq.seen_tokens, m)
            seq.record_tokens(pf.tokens)
            seq.pre_forward(m)
            start = seq.seen_tokens
            blocks = np.asarray(seq.blocks, np.int32)
            base, row = D + r * S, D + r
            ids[base:base + m] = pf.tokens
            pos = start + np.arange(m)
            positions[base:base + m] = pos
            slots0[base:base + m] = blocks[pos // bs] * bs + pos % bs
            ctx[row] = start + m
            bt[row] = self._seq_block_row(seq)
            last[row] = base + m - 1
            seqs.append(seq)

        ids_dev = jnp.asarray(ids)
        if n_dec and defer:
            # device token scalars from the previous quantum stack into the
            # decode segment without a host sync; the list pads to the decode
            # bucket D so the stack/set shapes never depend on the raw row
            # count (per-n_dec shapes were a compile ladder)
            col = [jnp.asarray(t, jnp.int32).reshape(()) for t in decode_carry]
            col.extend([jnp.zeros((), jnp.int32)] * (D - n_dec))  # padded rows feed the garbage page
            ids_dev = ids_dev.at[:D].set(jnp.stack(col))

        self._rng, rng = jax.random.split(self._rng)
        eos = jnp.int32(-1 if eos_token_id is None else int(eos_token_id))
        operands = (ids_dev, jnp.asarray(positions), jnp.asarray(bt), jnp.asarray(ctx), jnp.asarray(slots0),
                    jnp.asarray(last), jnp.asarray(adv), jnp.asarray(gslots), eos, rng)
        return operands, ctx, seqs

    # ---------------------------------------------------------- speculative decode
    def _spec_for(self, chunk: int, sampling):
        """LRU-bounded cache of spec-verify programs keyed on (window
        length, sampling signature) — same eviction discipline as
        ``_burst_for``/``_fused_for``. The padded row count rides jit's
        shape specialization; only the verify window is static."""
        key = (chunk,) + (sampling or (False, 1.0, 0, 1.0)) + (self._shard_sig,)
        do, t, k, p = key[1:5]
        return self._cached_program(self._spec_fns, "spec", key, lambda: make_spec_verify_fn(
            self._run_cfg, interpret=self._interpret, mesh=self._run_mesh, tp=self._tp, tp_ctx=self._tp_ctx,
            chunk=chunk, do_sample=do, temperature=t, top_k=k, top_p=p))

    def _run_spec_step(self, uids: List[int], carries: List[int], histories: List[Sequence[int]],
                       budgets: List[int]) -> Optional[Dict[int, List[int]]]:
        """One draft→verify speculative-decode quantum over pure-decode rows.

        Host side: the drafter proposes up to K tokens per row from its
        prompt+generated history; the verify window is ``chunk = kmax
        rounded up to a power of two, + 1`` (carry token + drafts), so
        draft-poor steps compile/pad small. Device side: ONE dispatch runs
        every row as a (start, len=chunk) ragged chunked-prefill through
        the same paged-attention machinery as the fused step, writing the
        window's KV optimistically, and ``select_committed`` picks each
        row's accepted prefix + bonus token in-graph — the readback is
        (B, chunk) committed ids + (B,) counts, ints only. Rejected tail
        positions roll back via ``DSStateManager.rollback_tokens``.

        Returns uid -> committed tokens (1..chunk each) for the rows that
        ran, or None when no row drafted anything / none were admitted —
        the caller falls back to a plain decode step, so a cold drafter
        costs zero extra verify positions.
        """
        K = self._spec_k
        drafts: List[List[int]] = []
        for uid, hist, budget in zip(uids, histories, budgets):
            seq = self.state.get_sequence(uid)
            cap = min(K, budget - 1, self.state.max_context - seq.seen_tokens - 1)
            d = self._drafter.propose(hist, cap) if cap > 0 else []
            drafts.append([int(t) for t in d[:max(0, cap)]])
        kmax = max((len(d) for d in drafts), default=0)
        if kmax == 0:
            return None  # nothing to verify: plain decode is strictly cheaper
        chunk = min(K, _next_pow2(kmax)) + 1
        admitted, q = self.scheduler.schedule_spec(uids, chunk)
        if not admitted:
            return None
        by_uid = {u: i for i, u in enumerate(uids)}
        n = len(admitted)
        B = self._decode_bucket(n)
        T = B * chunk
        bs = self.state.block_size

        ids = np.zeros((T,), np.int32)
        positions = np.tile(np.arange(chunk, dtype=np.int32), B)
        slots = self._garbage_slots(T)
        ctx = np.full((B,), chunk, np.int32)  # padded rows attend inside the garbage page
        bt = np.full((B, self._max_blocks_per_seq), self._garbage_block, np.int32)
        n_draft = np.zeros((B,), np.int32)
        seqs = []
        for j, uid in enumerate(admitted):
            i = by_uid[uid]
            seq = self.state.get_sequence(uid)
            self._cow_ready(seq, seq.seen_tokens)
            self.state.allocate_for(seq, chunk)
            self.state.sanitize_write(seq, seq.seen_tokens, chunk)
            seq.record_tokens(None)  # committed tokens are resolved post-verify
            seq.pre_forward(chunk)
            pos0 = seq.seen_tokens
            blocks = np.asarray(seq.blocks, np.int32)
            d = drafts[i]
            base = j * chunk
            ids[base] = int(carries[i])
            ids[base + 1:base + 1 + len(d)] = d
            pos = pos0 + np.arange(chunk)
            positions[base:base + chunk] = pos
            slots[base:base + chunk] = blocks[pos // bs] * bs + pos % bs
            ctx[j] = pos0 + chunk
            bt[j] = self._seq_block_row(seq)
            n_draft[j] = len(d)
            seqs.append(seq)

        fn = self._spec_for(chunk, self._sampling)
        self._rng, rng = jax.random.split(self._rng)
        with telemetry_span("infer/spec_verify", rows=n, k=chunk - 1):
            committed, accepted, self.k_pages, self.v_pages = fn(
                self.params, jnp.asarray(ids), jnp.asarray(positions), self.k_pages,
                self.v_pages, jnp.asarray(bt), jnp.asarray(ctx), jnp.asarray(slots),
                jnp.asarray(n_draft), rng)
        self._m_dispatches.inc()
        self._m_decode_steps.inc()
        self._m_decode_fill.set(n / B)
        # (B, chunk) ids + (B,) counts: the whole readback for up to B*chunk tokens
        committed, accepted = jax.device_get((committed, accepted))  # graft-lint: readback
        for seq in seqs:
            seq.post_forward()

        out: Dict[int, List[int]] = {}
        total_acc = 0
        ev = self._events.enabled
        for j, uid in enumerate(admitted):
            acc = int(accepted[j])
            n_commit = acc + 1
            self.state.rollback_tokens(seqs[j], chunk - n_commit)
            out[uid] = [int(t) for t in committed[j, :n_commit]]
            total_acc += acc
            if ev:
                self._events.emit("decode", uid, q=q, k=n_commit, accepted=acc,
                                  proposed=int(n_draft[j]))
        total_prop = int(n_draft[:n].sum())
        # useful = committed tokens (carry + accepted drafts); slots = the
        # whole padded verify window the program actually computed
        self._acct.attribute(n + total_acc, B * chunk)
        self._account_tp_allreduce(B * chunk)
        self._acct.note_spec(total_prop, total_acc)
        device_profiler.note_quantum("spec_verify", rows=n, accepted=total_acc)
        self._m_decode_tokens.inc(n + total_acc)
        self._m_spec_proposed.inc(total_prop)
        self._m_spec_accepted.inc(total_acc)
        self._spec_proposed_run += total_prop
        self._spec_accepted_run += total_acc
        if self._spec_proposed_run:
            self._m_spec_rate.set(self._spec_accepted_run / self._spec_proposed_run)
        return out

    # ---------------------------------------------------------- serving loop
    def generate(self, prompts: Sequence[Sequence[int]], max_new_tokens: int = 32,
                 eos_token_id: Optional[int] = None, do_sample: bool = False, temperature: float = 1.0,
                 top_k: int = 0, top_p: float = 1.0, seed: int = 0,
                 on_token=None) -> List[List[int]]:
        """Continuous-batching generation over a set of prompts — greedy by
        default, or sampled (``do_sample`` + temperature/top-k/top-p, the
        MII frontend's sampling surface). Sampling happens on device (the
        fused burst threads the rng through its scan), so the per-step
        readback stays one int per sequence either way.

        Drives the scheduler the way a serving frontend (MII) drives the
        reference engine: admit prefills as KV blocks free up, batch all
        live decodes each step.

        ``on_token(uid, token)`` streams tokens as they are committed
        (MII's streaming surface): one call per token, per-request order
        preserved; a fused K-step burst delivers its K tokens back to
        back when the burst completes — streaming granularity is the
        price of burst throughput, and callers that need strict
        per-token latency should configure ``decode_burst=0``.
        """
        self._sampling = (True, float(temperature), int(top_k), float(top_p)) if do_sample else None
        self._rng = jax.random.PRNGKey(seed)
        self._m_requests.inc(len(prompts))
        if self._events.enabled:
            for i, p in enumerate(prompts):
                self._events.emit("enqueue", i, prompt=len(p))
        journal = get_journal()
        if journal is not None:
            journal.begin_session(
                self._journal_fingerprint(), kind="generate",
                run={"max_new_tokens": int(max_new_tokens), "eos_token_id": eos_token_id,
                     "do_sample": bool(do_sample), "temperature": float(temperature),
                     "top_k": int(top_k), "top_p": float(top_p), "seed": int(seed)})
            for i, p in enumerate(prompts):
                journal.record_request(i, list(p), arrival_s=0.0,
                                       arrival_q=self.scheduler.last_quantum_id,
                                       max_new_tokens=int(max_new_tokens))
        try:
            with maybe_guard(self._guard_enabled):
                out = self._generate(prompts, max_new_tokens, eos_token_id, on_token)
            if journal is not None:
                # deferred mode keeps tokens on device until the final
                # fetch, so those requests have no per-commit records —
                # journal each one's full stream now (quantum unknown: -1)
                for i, toks in enumerate(out):
                    if not journal.has_commits(i):
                        journal.record_commit(i, -1, toks)
            return out
        finally:
            if journal is not None:
                journal.end_session(self._journal_run_summary())
            self._sampling = None
            self._update_hbm_gauges()

    # ---------------------------------------------------------- journal
    def _program_signatures(self) -> List[str]:
        """Compiled-program cache signatures at this instant — part of the
        journal fingerprint (a replay that compiles a different program
        set is suspect before a single token diverges)."""
        sigs = [f"prefill:{self._shard_sig}", f"decode:{self._shard_sig}"]
        sigs += [f"burst{k}" for k in self._bursts]
        sigs += [f"fused{k}" for k in self._fused_fns]
        sigs += [f"spec{k}" for k in self._spec_fns]
        return sorted(str(s) for s in sigs)

    def _journal_fingerprint(self) -> Dict:
        """Everything the replay harness needs to rebuild this engine:
        model config, resolved engine geometry/loop flags, the knob
        registry as resolved, and the program-cache signatures."""
        from ...parallel.mesh import mesh_signature
        from ...telemetry.flight import resolved_knobs

        smc = self._config.state_manager
        return {
            "model_cfg": dataclasses.asdict(self.cfg),
            "engine": {
                "dtype": self._config.dtype,
                "fused_step": self._fused_enabled,
                "spec_decode": self._spec_enabled,
                "spec_k": self._spec_k,
                "spec_drafter": self._config.spec_drafter,
                "decode_burst": self._config.decode_burst,
                "min_decode_bucket": self._config.min_decode_bucket,
                "quant_bits": self._config.quant_bits,
                "kv_quant_bits": self._kv_quant_bits,
                "kv_spill": self._kv_spill,
                "enable_prefix_cache": self.state.prefix_cache is not None,
                "tensor_parallel": self._tp,
                "tp_allreduce_bits": self._tp_bits,
                "shard_sig": self._shard_sig,
                "mesh": mesh_signature(self._mesh_topo) if self._mesh_topo is not None else "mesh[none]",
                "num_kv_blocks": self._n_kv_blocks,
                "kv_block_size": smc.kv_block_size,
                "max_context": smc.max_context,
                "max_ragged_batch_size": smc.max_ragged_batch_size,
                "max_ragged_sequence_count": smc.max_ragged_sequence_count,
            },
            "knobs": resolved_knobs(),
            "programs": self._program_signatures(),
        }

    def _journal_run_summary(self) -> Dict:
        """Run-level accounting folded into the journal's end record —
        the baseline side of a what-if comparison."""
        out: Dict = {"dispatches": get_telemetry_registry().peek("infer_dispatches_total") or 0.0,
                     "programs": self._program_signatures()}
        if self._acct.enabled:
            out["acct_totals"] = dict(self._acct.totals())
        return out

    def _residency_summary(self) -> Dict:
        """Allocator / prefix-cache / host-tier residency — the flight
        recorder's view of where every KV block lives at capture time."""
        pc = self.state.prefix_cache
        return {
            "kv_blocks_total": self._n_kv_blocks,
            "kv_blocks_free": int(self.state.free_blocks),
            "block_bytes": int(self._block_bytes),
            # per-shard view: KV heads split over the tensor axis, so each
            # chip holds 1/tp of every block's bytes (block tables replicated)
            "tp_degree": int(self._tp),
            "block_bytes_per_shard": int(self.state.shard_geometry(
                self._block_bytes, self._tp)["block_bytes_per_shard"]),
            "kv_quant_bits": int(self._kv_quant_bits),
            "prefix_cached_blocks": int(pc.cached_blocks) if pc is not None else 0,
            "host_tier_bytes": int(pc.host_tier_bytes) if pc is not None else 0,
        }

    def _jit_cache_summary(self) -> Dict:
        """JitAuditor view for flight captures: total compiles and any
        steady-state recompiles (the recompile-storm signal)."""
        a = self.jit_auditor
        if a is None:
            return {"enabled": False}
        return {"enabled": True, "compiles": int(a.compiles),
                "steady": bool(a.steady),
                "steady_recompiles": int(a.steady_recompiles)}

    def _update_hbm_gauges(self) -> None:
        """Refresh the per-pool HBM gauges (weights, paged KV, prefix-held
        blocks, host-tier bytes, compiled-program temp peak) and feed the
        pressure detector. Pure host arithmetic over already-known sizes —
        no device sync, except the one-scalar dequant-error readback when
        the int8 KV pool is on (once per generate, off the dispatch path)."""
        if not self._acct.enabled:
            return
        weights = sum(int(getattr(x, "nbytes", 0))
                      for x in jax.tree_util.tree_leaves(self.params))
        pages = sum(int(x.nbytes) for x in jax.tree_util.tree_leaves(
            (self.k_pages, self.v_pages)))
        pc = self.state.prefix_cache
        prefix = pc.cached_blocks * self._block_bytes if pc is not None else 0
        host_spill = pc.host_tier_bytes if pc is not None else 0
        limit = 0
        try:
            stats = jax.devices()[0].memory_stats() or {}
            limit = int(stats.get("bytes_limit", 0))
        except Exception:
            pass  # CPU/interpret backends expose no memory stats
        pressure = self._acct.set_hbm(limit=limit, weights=weights,
                                      kv_pages=pages, prefix=prefix,
                                      host_spill=host_spill)
        self._health.observe_hbm(pressure, weights_bytes=weights,
                                 kv_pages_bytes=pages)
        if self._kv_quant_bits == 8:
            # expected RMS dequant error of live pages: a uniform quantizer
            # with step = scale has RMS error scale/sqrt(12); average over
            # written (scale > 0) slot-heads of both pools
            s = jnp.concatenate([self.k_pages[1].ravel(), self.v_pages[1].ravel()])
            live = s > 0
            err = jnp.sum(jnp.where(live, s, 0.0)) / jnp.maximum(1, jnp.sum(live)) / (12.0 ** 0.5)
            self._m_quant_err.set(float(err))  # graft-lint: readback (one scalar, per generate)

    def _commit_closures(self, reqs, results, pieces, counts, decode_ready, eos_token_id, on_token):
        """(commit, commit_dev) shared by the fused and unfused loops."""
        events = self._events
        journal = get_journal()
        if journal is not None and not journal.active:
            journal = None

        def commit(uid: int, toks_out: List[int]) -> None:
            """Record sampled tokens and retire/continue the request."""
            req = reqs[uid]
            # a multi-token commit (burst tail, speculative window) never
            # outlives the request budget: clamp BEFORE recording, so
            # results and the streaming callback agree token-for-token
            toks_out = list(toks_out)[:req.max_new_tokens - len(results[uid])]
            if not toks_out:
                return
            if eos_token_id is not None and eos_token_id in toks_out:
                toks_out = toks_out[:toks_out.index(eos_token_id) + 1]
            if journal is not None:
                journal.record_commit(uid, self.scheduler.last_quantum_id, toks_out)
            if on_token is not None:
                for tok in toks_out:
                    on_token(uid, tok)
            first = not results[uid]
            results[uid].extend(toks_out)
            if first:
                events.emit("first_token", uid)
            done = (len(results[uid]) >= req.max_new_tokens or
                    (eos_token_id is not None and toks_out[-1] == eos_token_id))
            if done:
                req.done = True
                events.emit("finish", uid, n_new=len(results[uid]))
                self.flush([uid])
            else:
                decode_ready[uid] = toks_out[-1]

        def commit_dev(uid: int, row) -> None:
            """Deferred commit: ``row`` is a device (k,) or 0-d array."""
            req = reqs[uid]
            row = jnp.atleast_1d(row)
            pieces[uid].append(row)
            first = counts[uid] == 0
            counts[uid] += int(row.shape[0])
            if first:
                events.emit("first_token", uid)
            if counts[uid] >= req.max_new_tokens:
                req.done = True
                events.emit("finish", uid, n_new=counts[uid])
                self.flush([uid])
            else:
                decode_ready[uid] = row[-1]

        return commit, commit_dev

    @staticmethod
    def _collect_results(prompts, deferred, results, pieces) -> List[List[int]]:
        if not deferred:
            return [results[i] for i in range(len(prompts))]
        # one fetch for everything: equal lengths (no EOS) stack into a
        # single (n_prompts, max_new_tokens) transfer
        rows = [jnp.concatenate(pieces[i]) if len(pieces[i]) > 1 else pieces[i][0] for i in range(len(prompts))]
        lens = {int(r.shape[0]) for r in rows}
        if len(lens) == 1:
            arr = jax.device_get(jnp.stack(rows))  # graft-lint: readback (the generate's ONE fetch)
            return [arr[i].tolist() for i in range(len(prompts))]
        return [jax.device_get(r).tolist() for r in rows]  # graft-lint: readback (ragged final fetch)

    def _generate(self, prompts, max_new_tokens, eos_token_id, on_token=None) -> List[List[int]]:
        if self._fused_enabled:
            return self._generate_fused(prompts, max_new_tokens, eos_token_id, on_token)
        return self._generate_unfused(prompts, max_new_tokens, eos_token_id, on_token)

    def _generate_fused(self, prompts, max_new_tokens, eos_token_id, on_token=None) -> List[List[int]]:
        """The SplitFuse hot path: the host only admits/evicts, allocates
        blocks, and commits streams — every scheduler quantum (mixed
        chunked-prefill + decode rows) is ONE dispatched program, and
        pure-decode quanta between admission waves extend to multi-step
        fused bursts inside the same program (lax.scan tail). Unlike the
        unfused burst path, bursts stay on even with an EOS cut or a
        streaming callback: finished rows are masked in-graph and the
        host truncates at commit."""
        # speculation needs committed token VALUES on the host each step
        # (the drafter reads the history), so it forces non-deferred mode
        deferred = eos_token_id is None and on_token is None and not self._spec_enabled
        reqs = {i: RaggedRequest(uid=i, tokens=list(p), max_new_tokens=max_new_tokens) for i, p in enumerate(prompts)}
        pending = list(reqs.values())
        decode_ready: Dict[int, object] = {}  # uid -> next token to feed (int, or device scalar when deferred)
        results: Dict[int, List[int]] = {i: [] for i in reqs}
        pieces: Dict[int, List[object]] = {i: [] for i in reqs}  # deferred: device arrays
        counts: Dict[int, int] = {i: 0 for i in reqs}
        commit, commit_dev = self._commit_closures(reqs, results, pieces, counts, decode_ready,
                                                   eos_token_id, on_token)

        while pending or decode_ready:
            self._health.poll()
            # host-tier pre-spill: start d2h demotions while the pool is
            # under the spill watermark so they overlap the next dispatch
            self.state.spill_tick()
            if self._spec_enabled and decode_ready and not pending:
                # pure-decode situation: try a draft→verify quantum. Rows
                # the drafter/scheduler skipped stay in decode_ready and
                # rotate to the front of the next step.
                sp_uids = list(decode_ready)
                rows = self._run_spec_step(
                    sp_uids, [decode_ready[u] for u in sp_uids],
                    [list(prompts[u]) + results[u] for u in sp_uids],
                    [reqs[u].max_new_tokens - len(results[u]) for u in sp_uids])
                if rows is not None:
                    for uid, toks in rows.items():
                        decode_ready.pop(uid)
                        commit(uid, toks)
                    continue
            quantum = self.scheduler.schedule_fused([r for r in pending if r.remaining_prefill],
                                                    list(decode_ready))
            if quantum.empty:
                raise RuntimeError("scheduler deadlock: no work schedulable (KV pool too small?)")
            for pf in quantum.prefills:
                reqs[pf.uid].tokens = reqs[pf.uid].tokens[len(pf.tokens):]
            steps = 1
            if quantum.decode_uids and not quantum.prefills and not pending:
                # between admission waves: everyone is decoding — extend the
                # quantum to a fused multi-step burst (pow2 ladder, bounded
                # by budgets / max_context / free blocks like _burst_steps)
                done_count = counts if deferred else {u: len(results[u]) for u in quantum.decode_uids}
                rem = min(reqs[u].max_new_tokens - done_count[u] for u in quantum.decode_uids)
                steps = max(1, self._burst_steps({u: True for u in quantum.decode_uids}, rem))
            carry = [decode_ready.pop(u) for u in quantum.decode_uids]
            rows = self._run_fused(quantum, carry, steps, deferred, eos_token_id)
            for uid, row in rows.items():
                if row is None:
                    continue  # mid-prompt prefill chunk: no sampled token yet
                if deferred:
                    commit_dev(uid, row)
                else:
                    commit(uid, row.tolist())
            pending = [r for r in pending if not r.done and r.remaining_prefill]

        return self._collect_results(prompts, deferred, results, pieces)

    def _generate_unfused(self, prompts, max_new_tokens, eos_token_id, on_token=None) -> List[List[int]]:
        # Deferred mode: when nothing on the host needs token VALUES
        # mid-stream (no EOS cut, no streaming callback), the scheduler's
        # decisions depend only on counts and block accounting — so the
        # inter-dispatch token carry stays ON DEVICE (decode_ready maps
        # uid -> 0-d device array) and the only host sync in the whole
        # generate is the final fetch: every avoided readback is a device
        # sync the dispatch pipeline does not stall on.
        deferred = eos_token_id is None and on_token is None and not self._spec_enabled
        reqs = {i: RaggedRequest(uid=i, tokens=list(p), max_new_tokens=max_new_tokens) for i, p in enumerate(prompts)}
        pending = list(reqs.values())
        decode_ready: Dict[int, object] = {}  # uid -> next token to feed (int, or device scalar when deferred)
        results: Dict[int, List[int]] = {i: [] for i in reqs}
        pieces: Dict[int, List[object]] = {i: [] for i in reqs}  # deferred: device arrays
        counts: Dict[int, int] = {i: 0 for i in reqs}
        commit, commit_dev = self._commit_closures(reqs, results, pieces, counts, decode_ready,
                                                   eos_token_id, on_token)

        while pending or decode_ready:
            self._health.poll()
            # host-tier pre-spill (see _generate_fused)
            self.state.spill_tick()
            if self._spec_enabled and not pending and decode_ready:
                # pure-decode situation: draft→verify quantum first; on a
                # dry drafter fall through to the burst / stepped path
                sp_uids = list(decode_ready)
                rows = self._run_spec_step(
                    sp_uids, [decode_ready[u] for u in sp_uids],
                    [list(prompts[u]) + results[u] for u in sp_uids],
                    [reqs[u].max_new_tokens - len(results[u]) for u in sp_uids])
                if rows is not None:
                    for uid, toks in rows.items():
                        decode_ready.pop(uid)
                        commit(uid, toks)
                    continue
            # Burst path: nothing left to admit and everyone is decoding —
            # run K fused steps on-device instead of K host roundtrips.
            # A sequence that hits EOS mid-burst wastes its tail steps
            # (tokens past EOS are discarded and its pages are flushed).
            if not pending and decode_ready:
                # respect the scheduler's per-step caps: a burst step decodes
                # one token per sequence, so both limits bound the batch
                cap = min(self.scheduler.max_sequences, self.scheduler.max_batch_tokens)
                burst_uids = list(decode_ready)[:cap]
                done_count = counts if deferred else {u: len(results[u]) for u in burst_uids}
                rem = min(reqs[u].max_new_tokens - done_count[u] for u in burst_uids)
                k = self._burst_steps({u: True for u in burst_uids}, rem)
                if k >= 2:
                    uids = burst_uids
                    carried = [decode_ready.pop(u) for u in uids]
                    if deferred:
                        ids_dev = self._ids_from_carry(carried, self._decode_bucket(len(uids)))
                        out = self._run_decode_burst(uids, [0] * len(uids), k, ids_dev=ids_dev, defer=True)
                        for uid, row in zip(uids, out):
                            commit_dev(uid, row)
                    else:
                        out = self._run_decode_burst(uids, carried, k)
                        for uid, row in zip(uids, out):
                            commit(uid, row.tolist())
                    continue
            step = self.scheduler.schedule([r for r in pending if r.remaining_prefill], list(decode_ready))
            if step.empty:
                raise RuntimeError("scheduler deadlock: no work schedulable (KV pool too small?)")
            uids, toks = [], []
            for uid in step.decode_uids:
                uids.append(uid)
                toks.append([decode_ready.pop(uid)])
            for pf in step.prefills:
                req = reqs[pf.uid]
                uids.append(pf.uid)
                toks.append(pf.tokens)
                req.tokens = req.tokens[len(pf.tokens):]
            nxt = self.put(uids, toks, return_tokens=True, _defer=deferred)
            for uid, tok in zip(uids, nxt):
                if reqs[uid].remaining_prefill:
                    continue  # mid-prefill chunk: logits not a sampled token yet
                if deferred:
                    commit_dev(uid, tok)
                else:
                    commit(uid, [int(tok)])
            pending = [r for r in pending if not r.done and r.remaining_prefill]

        return self._collect_results(prompts, deferred, results, pieces)
