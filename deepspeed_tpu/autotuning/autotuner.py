"""Autotuner: search ZeRO stage x micro-batch space for best throughput.

Parity: reference ``autotuning/autotuner.py`` (``Autotuner`` :42,
``_generate_experiments`` :304, ``tune`` :404, ``model_info_profile_run``
:663, best-config selection :714). The reference launches every
experiment as a separate multi-process job via the resource manager; the
TPU-native autotuner runs trials IN PROCESS — an engine under a candidate
config is just another jit compilation on the same mesh, so a trial is
build-engine -> few steps -> read samples/sec -> free. Failures (OOM,
compile errors) score ``None`` and prune that region of the space.
"""

import gc
import json
import os
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..utils.logging import logger
from .tuner import BaseTuner, GridSearchTuner, ModelBasedTuner, RandomTuner

TUNERS = {"gridsearch": GridSearchTuner, "random": RandomTuner, "model_based": ModelBasedTuner}
DEFAULT_TUNING_SPACE_ZERO_STAGES = [0, 1, 2, 3]


def run_trial(model, params, config: Dict, batches: Sequence, steps_per_trial: int,
              warmup_steps: int, metric: str) -> Tuple[float, Optional[int]]:
    """The trial loop itself: build an engine under ``config``, run
    warmup + timed steps, return (metric value, peak memory bytes).
    Raises on failure — callers decide the failure policy. Shared by the
    in-process path and the subprocess ``trial_runner``."""
    import jax
    import jax.numpy as jnp

    import deepspeed_tpu

    engine, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params, config=config)
    mb = config.get("train_micro_batch_size_per_gpu", 1)
    dp = engine.topology.data_parallel_size

    def batch_at(i):
        b = batches[i % len(batches)]
        leaves = jax.tree_util.tree_leaves(b)
        need = mb * dp
        if leaves and leaves[0].shape[0] != need:
            reps = -(-need // leaves[0].shape[0])
            return jax.tree_util.tree_map(lambda x: np.tile(x, (reps,) + (1,) * (x.ndim - 1))[:need], b)
        return b

    for i in range(warmup_steps):
        engine.forward(batch_at(i))
        engine.backward()
        engine.step()
    t0 = time.perf_counter()
    for i in range(steps_per_trial):
        engine.forward(batch_at(warmup_steps + i))
        engine.backward()
        engine.step()
    (jnp.zeros(()) + 0).block_until_ready()
    dt = time.perf_counter() - t0

    mem_bytes = measure_memory(engine, batch_at(0))
    samples = steps_per_trial * mb * dp * engine.gradient_accumulation_steps
    val = -dt / steps_per_trial if metric == "latency" else samples / dt
    return val, mem_bytes


def measure_memory(engine, batch) -> Optional[int]:
    """Peak per-chip memory of the trial. Prefers the backend's live
    allocator stats (true runtime peak, zero extra compilation);
    falls back to XLA buffer-assignment totals of the train step
    (pays one re-lower, but lower()/compile() hit the jit cache's
    already-built executable on most backends).

    The allocator peak is PROCESS-LIFETIME: in a sequential in-process
    search a small trial after a big one would inherit the big trial's
    peak and be wrongly budget-rejected. The peak is only trusted when
    it ADVANCED past the previous measurement (this trial set it);
    otherwise fall through to the per-compile estimate. Subprocess-
    isolated trials (trial_runner) never hit this — fresh process each."""
    import jax

    try:
        stats = jax.local_devices()[0].memory_stats()
        peak = int(stats.get("peak_bytes_in_use", 0)) if stats else 0
        if peak:
            prev = getattr(measure_memory, "_last_peak", 0)
            measure_memory._last_peak = max(prev, peak)
            if peak > prev:
                return peak
    except Exception:
        pass
    try:
        fwd_bwd = engine._fwd_bwd
        if not hasattr(fwd_bwd, "lower"):
            return None
        compiled = fwd_bwd.lower(engine._compute_params(), engine._put_batch(batch), 0, 1.0).compile()
        mem = compiled.memory_analysis()
        if mem is None:
            return None
        total = 0
        for attr in ("argument_size_in_bytes", "output_size_in_bytes", "temp_size_in_bytes",
                     "generated_code_size_in_bytes"):
            total += int(getattr(mem, attr, 0) or 0)
        return total or None
    except Exception:
        return None


def _deep_update(base: Dict, override: Dict) -> Dict:
    out = json.loads(json.dumps(base))
    for k, v in override.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = _deep_update(out[k], v)
        else:
            out[k] = v
    return out


class Autotuner:

    def __init__(self,
                 model_factory: Callable[[], Any],
                 base_config: Dict,
                 train_batches: Sequence,
                 params_factory: Optional[Callable[[], Any]] = None,
                 metric: str = "throughput",
                 steps_per_trial: int = 4,
                 warmup_steps: int = 1,
                 model_spec=None):
        """``model_factory()`` returns a fresh model; ``train_batches`` is a
        list of batches each trial iterates over (repeated as needed).

        ``model_spec`` (TransformerConfig kwargs dict, or an import path
        ``"pkg.module:factory"``) enables SUBPROCESS trial isolation —
        ``autotuning: {"trial_isolation": true}`` — because a live
        factory callable cannot cross a process boundary. With
        ``"parallel_trials": N`` grid/random searches additionally fan
        N trials over worker slots (scheduler.py), including remote
        slots via ``"hostfile"``."""
        self.model_factory = model_factory
        self.params_factory = params_factory
        self.base_config = dict(base_config)
        self.train_batches = list(train_batches)
        self.at_cfg = base_config.get("autotuning", {})
        self.metric = self.at_cfg.get("metric", metric)
        self.steps_per_trial = steps_per_trial
        self.warmup_steps = warmup_steps
        self.model_spec = model_spec
        self.records: List[Dict] = []

    # ------------------------------------------------------------------
    def model_info_profile_run(self) -> Dict:
        """Param count + per-step FLOPs of the model under the base config
        (reference :663 runs a whole profiling job for this)."""
        import jax

        from ..profiling.flops_profiler import get_model_profile

        model = self.model_factory()
        flops, macs, n_params = get_model_profile(model=model, args=(self.train_batches[0],),
                                                  print_profile=False, as_string=False)
        return {"num_params": int(n_params), "flops_per_step": int(flops), "macs": int(macs)}

    def _generate_experiments(self, stages: Optional[List[int]] = None,
                              micro_batches: Optional[List[int]] = None) -> List[Dict]:
        stages = stages if stages is not None else DEFAULT_TUNING_SPACE_ZERO_STAGES
        if micro_batches is None:
            base_mb = self.base_config.get("train_micro_batch_size_per_gpu", 1)
            n = self.at_cfg.get("num_tuning_micro_batch_sizes", 3)
            lo = self.at_cfg.get("min_train_micro_batch_size_per_gpu", 1)
            hi = self.at_cfg.get("max_train_micro_batch_size_per_gpu", None)
            micro_batches = sorted({max(lo, base_mb * (2**i)) for i in range(n)})
            if hi:
                micro_batches = [m for m in micro_batches if m <= hi] or [lo]
        exps = []
        for stage in stages:
            for mb in micro_batches:
                exps.append({
                    "zero_optimization": {"stage": stage},
                    "train_micro_batch_size_per_gpu": int(mb),
                })
        return exps

    def run_experiment(self, exp: Dict) -> Optional[float]:
        """One in-process trial; returns the metric value or None on
        failure (the reference's failed-experiment path)."""
        import jax

        config = _deep_update(self.base_config, exp)
        config.pop("autotuning", None)
        self._last_memory_bytes = None
        try:
            model = self.model_factory()
            params = self.params_factory() if self.params_factory else model.init(
                jax.random.PRNGKey(0), self.train_batches[0])
            val, mem_bytes = run_trial(model, params, config, self.train_batches,
                                       self.steps_per_trial, self.warmup_steps, self.metric)
            self._last_memory_bytes = mem_bytes
            if self._over_memory_budget(exp, mem_bytes):
                return None
            return val
        except Exception as e:  # noqa: BLE001 — OOM/compile failures score None
            logger.warning(f"autotuning experiment {exp} failed: {type(e).__name__}: {e}")
            return None
        finally:
            gc.collect()

    def _over_memory_budget(self, exp: Dict, mem_bytes: Optional[int]) -> bool:
        """Memory audit (reference gap: throughput-only tuning can pick a
        config one batch from OOM): budget-gate the measured peak."""
        budget_gb = self.at_cfg.get("max_memory_per_chip_gb")
        if budget_gb and mem_bytes is None:
            logger.warning(f"autotuning experiment {exp}: memory budget set but peak memory is "
                           "unmeasurable for this config (custom fwd_bwd path) — budget NOT enforced")
        if mem_bytes is not None and budget_gb and mem_bytes > float(budget_gb) * (1 << 30):
            logger.warning(f"autotuning experiment {exp} over memory budget: "
                           f"{mem_bytes / (1 << 30):.2f} GiB > {budget_gb} GiB")
            return True
        return False

    # ---------------------------------------------------------- isolation
    def _trial_spec(self, exp: Dict, batches_npz: str) -> Dict:
        import dataclasses

        model_ref = self.model_spec
        if dataclasses.is_dataclass(model_ref):
            model_ref = dataclasses.asdict(model_ref)
            # dtype is a jax type, not JSON-able; the runner's
            # TransformerConfig default reapplies it
            model_ref.pop("dtype", None)
        config = _deep_update(self.base_config, exp)
        config.pop("autotuning", None)
        return {"config": config, "model": model_ref, "batches_npz": batches_npz,
                "steps_per_trial": self.steps_per_trial, "warmup_steps": self.warmup_steps,
                "metric": self.metric}

    def _make_scheduler(self):
        from .scheduler import TrialScheduler, ssh_prefixes_from_hostfile

        prefixes = None
        if self.at_cfg.get("hostfile"):
            prefixes = ssh_prefixes_from_hostfile(self.at_cfg["hostfile"])
        return TrialScheduler(n_workers=int(self.at_cfg.get("parallel_trials", 1)),
                              launch_prefixes=prefixes,
                              timeout_s=float(self.at_cfg.get("trial_timeout_s", 600)))

    def _dump_batches(self, d: str) -> str:
        path = os.path.join(d, "batches.npz")
        stacks = {k: np.stack([np.asarray(b[k]) for b in self.train_batches])
                  for k in self.train_batches[0]}
        np.savez(path, **stacks)
        return path

    def tune(self, stages: Optional[List[int]] = None, micro_batches: Optional[List[int]] = None) -> Dict:
        """Run the search; returns the best merged config (reference :404).

        ``autotuning.trial_isolation`` runs each trial in a subprocess via
        ``trial_runner`` (crash/OOM-proof); with ``parallel_trials`` > 1,
        order-independent tuners (grid/random) fan trials over worker
        slots (reference: scheduler.py resource manager)."""
        exps = self._generate_experiments(stages, micro_batches)
        tuner_type = self.at_cfg.get("tuner_type", "gridsearch")
        tuner: BaseTuner = TUNERS[tuner_type](exps, metric=self.metric)
        early_stop = self.at_cfg.get("tuner_early_stopping", 5)
        max_trials = self.at_cfg.get("tuner_num_trials", 50)

        isolated = bool(self.at_cfg.get("trial_isolation"))
        if isolated and self.model_spec is None:
            raise ValueError("autotuning.trial_isolation needs model_spec (a TransformerConfig "
                             "or 'module:factory' import path) — live factories cannot cross "
                             "the subprocess boundary")
        n_workers = int(self.at_cfg.get("parallel_trials", 1))
        parallel = isolated and n_workers > 1 and tuner_type in ("gridsearch", "random")

        import tempfile

        with tempfile.TemporaryDirectory(prefix="ds_autotune_") as tmp:
            sched = self._make_scheduler() if isolated else None
            npz = self._dump_batches(tmp) if isolated else None

            def score(exp: Dict, result: Optional[Dict]) -> Tuple[Optional[float], Optional[int]]:
                if result is None:
                    return None, None
                mem = result.get("memory_bytes")
                if self._over_memory_budget(exp, mem):
                    return None, mem
                return result["value"], mem

            n_run = 0
            while n_run < max_trials:
                batch = tuner.next_batch(n_workers if parallel else 1)
                if not batch:
                    break
                batch = batch[:max_trials - n_run]
                if isolated:
                    results = sched.run_many([self._trial_spec(e, npz) for e in batch]) \
                        if len(batch) > 1 else [(None, sched.run_one(self._trial_spec(batch[0], npz)))]
                    scored = [(exp, *score(exp, res)) for exp, (_, res) in zip(batch, results)]
                else:
                    scored = [(batch[0], self.run_experiment(batch[0]),
                               getattr(self, "_last_memory_bytes", None))]
                for exp, val, mem in scored:
                    tuner.record(exp, val)
                    self.records.append({"exp": exp, self.metric: val, "memory_bytes": mem})
                    n_run += 1
                    logger.info(f"autotuning [{n_run}/{min(max_trials, len(exps))}] {exp} -> {val}")
                if tuner.should_stop(early_stop):
                    logger.info("autotuning early stop: no improvement")
                    break
        best_exp, best_val = tuner.best()
        if best_exp is None:
            raise RuntimeError("autotuning: every experiment failed")
        result = _deep_update(self.base_config, best_exp)
        result.pop("autotuning", None)
        logger.info(f"autotuning best ({self.metric}={best_val:.2f}): {best_exp}")
        return result

    def write_results(self, results_dir: Optional[str] = None) -> str:
        d = results_dir or self.at_cfg.get("results_dir", "autotuning_results")
        os.makedirs(d, exist_ok=True)
        path = os.path.join(d, "autotuning_results.json")
        with open(path, "w") as f:
            json.dump(self.records, f, indent=2, default=str)
        return path


def autotune(model_factory, base_config, train_batches, **kwargs) -> Dict:
    """One-call API: returns the best config found."""
    return Autotuner(model_factory, base_config, train_batches, **kwargs).tune()
