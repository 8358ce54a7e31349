"""ZeRO partition-planner tests (sharding-spec invariants, the analogue of
the reference's shard-by-shard partitioning checks in ``test_zero.py:827-980``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.parallel.mesh import MeshTopology
from deepspeed_tpu.runtime.config import DeepSpeedConfig, MeshConfig
from deepspeed_tpu.runtime.zero.partition import (fit_spec, plan_grad_specs, plan_opt_state_specs,
                                                  plan_param_specs, shard_leaf_spec, specs_to_shardings,
                                                  zero_axes_for)


def _cfg(stage, mesh=None):
    return DeepSpeedConfig({"zero_optimization": {"stage": stage}, "mesh": mesh or {}})


def _params():
    return {
        "dense": {"kernel": jnp.zeros((64, 32)), "bias": jnp.zeros((32,))},
        "emb": {"wte": jnp.zeros((128, 64))},
        "scalarish": {"scale": jnp.zeros((3,))},  # not divisible by 8
    }


def test_shard_leaf_spec_largest_dim():
    spec = shard_leaf_spec((64, 32), None, ("data",), 8)
    assert spec == P("data")


def test_shard_leaf_spec_respects_existing():
    spec = shard_leaf_spec((64, 32), P("tensor", None), ("data",), 8)
    assert spec == P("tensor", "data")


def test_shard_leaf_spec_indivisible():
    assert shard_leaf_spec((3,), None, ("data",), 8) == P()


@pytest.mark.parametrize("shape,spec,want", [
    ((50257, 768), P("tensor", None), P()),               # GPT-2's vocab: no tensor degree divides it
    ((50256, 768), P("tensor", None), P("tensor")),
    ((50257, 768), P("tensor", "fsdp"), P(None, "fsdp")),  # only the axis that does not fit is dropped
    ((768, 12, 64), P(None, "tensor", None), P(None, "tensor")),
])
def test_fit_spec_drops_axes_that_do_not_divide(shape, spec, want):
    topo = MeshTopology(MeshConfig.from_dict({"data": 2, "fsdp": 2, "tensor": 2}))
    assert fit_spec(spec, shape, topo) == want


def test_param_specs_with_an_indivisible_vocab_place():
    """A TP rule over a vocab the tensor axis does not divide plans a spec
    device_put accepts (it raised before: 50257 % 2)."""
    topo = MeshTopology(MeshConfig.from_dict({"data": 4, "tensor": 2}))
    params = {"wte": jnp.zeros((131, 16)), "dense": {"kernel": jnp.zeros((16, 32))}}
    rules = [(("wte",), P("tensor", None)), (("dense", "kernel"), P(None, "tensor"))]
    specs = plan_param_specs(jax.eval_shape(lambda: params), _cfg(0, {"data": 4, "tensor": 2}), topo, rules)
    assert specs["wte"] == P() and specs["dense"]["kernel"] == P(None, "tensor")
    jax.device_put(params, specs_to_shardings(specs, topo))


def test_stage0_replicated():
    topo = MeshTopology(MeshConfig.from_dict({"data": 8}))
    shapes = jax.eval_shape(lambda: _params())
    specs = plan_param_specs(shapes, _cfg(0), topo)
    assert all(s == P() for s in jax.tree_util.tree_leaves(specs, is_leaf=lambda x: isinstance(x, P)))


def test_stage3_params_sharded():
    topo = MeshTopology(MeshConfig.from_dict({"data": 8}))
    cfg = _cfg(3)
    cfg.zero_config.stage3_param_persistence_threshold = 0
    shapes = jax.eval_shape(lambda: _params())
    specs = plan_param_specs(shapes, cfg, topo)
    assert specs["dense"]["kernel"] == P("data")
    assert specs["emb"]["wte"] == P("data")
    assert specs["scalarish"]["scale"] == P()  # indivisible stays whole


def test_stage3_persistence_threshold():
    topo = MeshTopology(MeshConfig.from_dict({"data": 8}))
    cfg = _cfg(3)
    cfg.zero_config.stage3_param_persistence_threshold = 10_000
    shapes = jax.eval_shape(lambda: _params())
    specs = plan_param_specs(shapes, cfg, topo)
    assert specs["dense"]["kernel"] == P()  # 2048 < 10k → persisted (replicated)


def test_fsdp_axis_preferred():
    topo = MeshTopology(MeshConfig.from_dict({"data": 2, "fsdp": 4}))
    assert zero_axes_for(topo) == ("fsdp",)


def test_grad_specs_stage2_sharded():
    topo = MeshTopology(MeshConfig.from_dict({"data": 8}))
    shapes = jax.eval_shape(lambda: _params())
    pspecs = plan_param_specs(shapes, _cfg(2), topo)
    gspecs = plan_grad_specs(shapes, pspecs, _cfg(2), topo)
    assert gspecs["dense"]["kernel"] == P("data")
    # stage 1 leaves grads replicated
    g1 = plan_grad_specs(shapes, plan_param_specs(shapes, _cfg(1), topo), _cfg(1), topo)
    assert g1["dense"]["kernel"] == P()


def test_opt_state_specs_stage1_sharded():
    import optax

    topo = MeshTopology(MeshConfig.from_dict({"data": 8}))
    opt = optax.inject_hyperparams(optax.adamw)(learning_rate=1e-3)
    shapes = jax.eval_shape(lambda: _params())
    pspecs = plan_param_specs(shapes, _cfg(1), topo)
    ospecs, oshapes = plan_opt_state_specs(opt, shapes, pspecs, _cfg(1), topo)
    leaves_spec = jax.tree_util.tree_leaves(ospecs, is_leaf=lambda x: isinstance(x, P))
    leaves_shape = jax.tree_util.tree_leaves(oshapes)
    # every parameter-shaped state leaf (mu/nu) must be sharded over data
    n_sharded = sum(1 for sp, sh in zip(leaves_spec, leaves_shape)
                    if getattr(sh, "shape", ()) == (64, 32) and sp == P("data"))
    assert n_sharded >= 2  # mu and nu of dense/kernel


def test_opt_state_specs_stage0_replicated():
    import optax

    topo = MeshTopology(MeshConfig.from_dict({"data": 8}))
    opt = optax.inject_hyperparams(optax.adamw)(learning_rate=1e-3)
    shapes = jax.eval_shape(lambda: _params())
    pspecs = plan_param_specs(shapes, _cfg(0), topo)
    ospecs, _ = plan_opt_state_specs(opt, shapes, pspecs, _cfg(0), topo)
    assert all(s == P() for s in jax.tree_util.tree_leaves(ospecs, is_leaf=lambda x: isinstance(x, P)))

# quick tier: `pytest -m fast` smoke run
pytestmark = pytest.mark.fast


class TestMemEstimators:
    """Reference stage_1_and_2.py:2423 / stage3.py:2674 estimator parity."""

    def test_zero3_formula_matches_reference_arithmetic(self):
        from deepspeed_tpu.runtime.zero import estimate_zero3_model_states_mem_needs

        total, largest = 7_000_000_000, 400_000_000
        # full offload: chip holds only the largest gathered layer
        host, chip, big = estimate_zero3_model_states_mem_needs(
            total, largest, num_chips_per_host=8, num_hosts=4)
        assert chip == big == 4 * largest
        assert host == int(total * 18 * (1 / 4) * 1.5)
        # no offload: 18 bytes/param sharded over all chips + gathered layer
        host, chip, _ = estimate_zero3_model_states_mem_needs(
            total, largest, num_chips_per_host=8, num_hosts=4,
            cpu_offload=False, cpu_offload_params=False)
        assert chip == 4 * largest + int(18 * total / 32)

    def test_zero2_formula_matches_reference_arithmetic(self):
        from deepspeed_tpu.runtime.zero import estimate_zero2_model_states_mem_needs

        # reference stage_1_and_2.py:2423: 4 bytes/param on chip + 16/dp sharded
        host, chip = estimate_zero2_model_states_mem_needs(1_000_000, num_chips_per_host=4,
                                                           cpu_offload=False)
        assert chip == 4 * 1_000_000 + int(16 * 1_000_000 / 4)
        assert host == int(1_000_000 * 4 * 4 * 1.5)
        # offload: chip holds bf16 params only
        host, chip = estimate_zero2_model_states_mem_needs(1_000_000, num_chips_per_host=4)
        assert chip == 2 * 1_000_000
        assert host == int(1_000_000 * max(4 * 4, 16) * 1.5)

    def test_scan_layers_override_and_pytree_validation(self):
        import pytest as _pytest

        from deepspeed_tpu.runtime.zero import estimate_zero3_model_states_mem_needs_all_live
        from deepspeed_tpu.runtime.zero.estimator import params_of_tree

        with _pytest.raises(ValueError, match="parameter pytree"):
            params_of_tree(object())

    def test_all_live_prints_scenarios(self, capsys):
        import jax
        import numpy as np

        from deepspeed_tpu.models import CausalLM, gpt2_tiny
        from deepspeed_tpu.runtime.zero import (estimate_zero2_model_states_mem_needs_all_live,
                                                estimate_zero3_model_states_mem_needs_all_live)

        model = CausalLM(gpt2_tiny())
        params = model.init(jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 8), np.int32)})
        estimate_zero3_model_states_mem_needs_all_live(params, num_chips_per_host=8)
        estimate_zero2_model_states_mem_needs_all_live(params, num_chips_per_host=8)
        out = capsys.readouterr().out
        assert "per Chip" in out and "offload_param=cpu" in out and "offload_optimizer=cpu" in out
        assert out.count("|") >= 16  # both tables rendered
