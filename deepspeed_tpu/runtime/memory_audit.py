"""AOT per-chip memory audit of a ZeRO train step.

Reference analogue: DeepSpeed's ``estimate_zero3_model_states_mem_needs``
(``runtime/zero/stage3.py`` helpers) plus the autotuner's memory model —
but TPU-native: instead of a closed-form estimate, the *actual* train step
is lowered and compiled ahead-of-time (no parameters are ever
materialized, so a 7B-parameter audit runs on a laptop CPU) and XLA's
``memory_analysis()`` reports the real per-chip argument/temp/output
bytes for the chosen mesh. The HLO is also scanned for collective
pathologies (every all-gather re-materializing the full parameter tree at
once would show up as temp bytes ~= the unsharded model).

Used by ``tests/unit/test_memory_audit.py`` to hold ZeRO-3 Llama-2-7B on
v5e under the 16 GB HBM budget, and
available to users via ``deepspeed_tpu.runtime.memory_audit.audit_train_step``.
"""

from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ..parallel.mesh import initialize_mesh
from .config import DeepSpeedConfig
from .optimizers import create_optimizer
from .zero.partition import (batch_specs, plan_grad_specs, plan_opt_state_specs, plan_param_specs,
                             specs_to_shardings)


@dataclass
class MemoryAudit:
    argument_bytes: int      # per-chip resident inputs: param + opt shards (+ batch)
    temp_bytes: int          # per-chip transient peak (activations, collective buffers)
    output_bytes: int
    generated_code_bytes: int
    param_bytes_per_chip: int
    opt_bytes_per_chip: int
    allgather_count: int
    reduce_scatter_count: int
    allreduce_count: int
    n_params: int

    def total_bytes(self) -> int:
        return self.argument_bytes + self.temp_bytes

    def scaled_state_bytes(self, target_chips: int, audited_chips: int) -> int:
        """Param+optimizer resident bytes per chip at a larger ZeRO degree.

        ZeRO-3 state shards scale ~1/chips while temp (activation) bytes
        track the fixed per-chip micro-batch, so the audited mesh's state
        bytes can be rescaled to the target topology analytically.
        """
        return (self.param_bytes_per_chip + self.opt_bytes_per_chip) * audited_chips // target_chips


def _tree_bytes_per_chip(shapes, shardings) -> int:
    total = 0
    for leaf, sh in zip(jax.tree_util.tree_leaves(shapes), jax.tree_util.tree_leaves(
            shardings, is_leaf=lambda x: hasattr(x, "shard_shape"))):
        shard = sh.shard_shape(tuple(leaf.shape))
        total += int(np.prod(shard)) * leaf.dtype.itemsize if shard else leaf.dtype.itemsize
    return total


def audit_train_step(model, ds_config: Dict, mesh_axes: Optional[Dict[str, int]] = None,
                     micro_bs: int = 1, seq: int = 2048,
                     compute_dtype=jnp.bfloat16, attention_impl: Optional[str] = "chunked") -> MemoryAudit:
    """Compile (never run) one fused train step with abstract inputs and
    report XLA's per-chip memory analysis.

    ``attention_impl`` defaults to the chunked online-softmax op so a CPU
    audit reflects the flash kernel's O(S) memory profile; the plain XLA
    fallback would dominate temps with (B,H,S,S) logits blocks that never
    exist on TPU. Pass ``None`` to audit whatever the registry selects.
    """
    if isinstance(ds_config, DeepSpeedConfig):
        if mesh_axes is not None:
            raise ValueError("mesh_axes cannot override an already-built DeepSpeedConfig — "
                             "pass the mesh in the config, or pass the config as a dict")
        config = ds_config
    else:
        ds_config = dict(ds_config)
        if mesh_axes is not None:
            ds_config["mesh"] = dict(mesh_axes)
        config = DeepSpeedConfig(ds_config)
    topo = initialize_mesh(config.mesh, force=True)
    config.resolve_batch_sizes(topo.data_parallel_size)

    batch = {"input_ids": jax.ShapeDtypeStruct((micro_bs * topo.data_parallel_size, seq), jnp.int32)}
    param_shapes = jax.eval_shape(lambda k: model.init(k, {"input_ids": np.zeros((1, 4), np.int32)}),
                                  jax.random.PRNGKey(0))
    tp_rules = model.partition_rules() if hasattr(model, "partition_rules") else []

    param_specs = plan_param_specs(param_shapes, config, topo, tp_rules)
    param_shardings = specs_to_shardings(param_specs, topo)
    grad_specs = plan_grad_specs(param_shapes, param_specs, config, topo)
    opt = create_optimizer(config.optimizer.type or "adamw", config.optimizer.params)
    opt_specs, opt_state_shapes = plan_opt_state_specs(opt, param_shapes, param_specs, config, topo)
    opt_shardings = specs_to_shardings(opt_specs, topo)
    batch_shardings = specs_to_shardings(batch_specs(batch, topo), topo)

    loss_fn = model.loss_fn if hasattr(model, "loss_fn") else model
    grad_shardings = specs_to_shardings(grad_specs, topo)

    def fused_step(params32, opt_state, batch):
        # cast stays sharded: the all-gather then happens per-use in bf16
        # (half the bytes) instead of materializing the full fp32 master
        params_c = jax.tree_util.tree_map(
            lambda x, s: jax.lax.with_sharding_constraint(x.astype(compute_dtype), s),
            params32, param_shardings)
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(p, batch, jax.random.PRNGKey(0)).astype(jnp.float32))(params_c)
        # pin grads to their ZeRO shard right away: forces the per-layer
        # reduce-scatter instead of a full-tree gradient materialization
        grads = jax.tree_util.tree_map(
            lambda g, s: jax.lax.with_sharding_constraint(g.astype(jnp.float32), s),
            grads, grad_shardings)
        updates, new_opt = opt.update(grads, opt_state, params32)
        return loss, optax.apply_updates(params32, updates), new_opt

    jitted = jax.jit(fused_step, donate_argnums=(0, 1),
                     in_shardings=(param_shardings, opt_shardings, batch_shardings),
                     out_shardings=(None, param_shardings, opt_shardings))
    abstract_params = jax.tree_util.tree_map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), param_shapes)
    abstract_opt = jax.tree_util.tree_map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), opt_state_shapes)

    from ..ops.registry import REGISTRY
    prev = REGISTRY.set_impl("attention", attention_impl) if attention_impl is not None else None
    try:
        compiled = jitted.lower(abstract_params, abstract_opt, batch).compile()
    finally:
        if attention_impl is not None:
            REGISTRY.set_impl("attention", prev)

    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    n_params = sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(param_shapes))

    return MemoryAudit(
        argument_bytes=getattr(mem, "argument_size_in_bytes", 0),
        temp_bytes=getattr(mem, "temp_size_in_bytes", 0),
        output_bytes=getattr(mem, "output_size_in_bytes", 0),
        generated_code_bytes=getattr(mem, "generated_code_size_in_bytes", 0),
        param_bytes_per_chip=_tree_bytes_per_chip(param_shapes, param_shardings),
        opt_bytes_per_chip=_tree_bytes_per_chip(opt_state_shapes, opt_shardings),
        allgather_count=hlo.count("all-gather"),
        reduce_scatter_count=hlo.count("reduce-scatter"),
        allreduce_count=hlo.count("all-reduce"),
        n_params=n_params,
    )
