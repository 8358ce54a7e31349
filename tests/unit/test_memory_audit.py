"""AOT memory audit: ZeRO-3 Llama-2-7B must fit the v5e HBM budget.

The configuration: ZeRO-3 Llama-2-7B training on v5e-256 (16 GB HBM
per chip). The audit compiles the real train step with abstract inputs on
the virtual mesh (no parameters materialize) and reads XLA's per-chip
memory analysis. Round-3 findings baked in as assertions:

- unrolled layers let the CPU scheduler hoist every ZeRO all-gather up
  front (~85 GB temps — the round-1 'involuntary full rematerialization'
  warning made concrete); ``scan_layers`` forces per-layer liveness
- plain XLA attention materializes (B,H,S,S) fp32 logits; the chunked
  online-softmax op (ops/attention.py, flash-kernel memory profile) is
  what the TPU path actually does
- ``remat`` turns the scan stash from O(layers x layer-state) into
  O(layers x boundary-hidden)
"""

import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.models import CausalLM, llama2_7b, llama_tiny
from deepspeed_tpu.runtime.memory_audit import audit_train_step

HBM_BUDGET = 16 * 1024**3  # v5e
DS_CONFIG = {
    "train_micro_batch_size_per_gpu": 1,
    "optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
    "zero_optimization": {"stage": 3},
}


def test_tiny_audit_sanity(mesh8):
    a = audit_train_step(CausalLM(llama_tiny()), DS_CONFIG,
                         mesh_axes={"data": 2, "fsdp": 4}, micro_bs=1, seq=128)
    assert a.n_params > 0
    # exact arithmetic: argument bytes == per-chip param + optimizer shards
    assert abs(a.argument_bytes - (a.param_bytes_per_chip + a.opt_bytes_per_chip)) < 1e6
    assert a.temp_bytes > 0


def test_llama7b_fits_v5e_budget(mesh8):
    """The ladder-rung config (scan + remat + bf16 + chunked attention)
    holds ZeRO-3 Llama-2-7B under 16 GB/chip at the north-star ZeRO degree."""
    model = CausalLM(llama2_7b(remat=True, scan_layers=True, dtype=jnp.bfloat16))
    a = audit_train_step(model, DS_CONFIG, mesh_axes={"data": 1, "fsdp": 8},
                         micro_bs=1, seq=2048)
    assert 6.5e9 < a.n_params < 7.0e9
    # transient working set must fit alongside the v5e-256 state shard
    state_at_256 = a.scaled_state_bytes(target_chips=256, audited_chips=8)
    assert a.temp_bytes + state_at_256 < HBM_BUDGET, (
        f"temps {a.temp_bytes/1e9:.1f} GB + state@256 {state_at_256/1e9:.2f} GB "
        f"exceed the 16 GB v5e budget")
    # per-layer gather liveness: the scan emits O(1) collectives in code,
    # not O(layers) hoisted gathers
    assert a.allgather_count < 200, a.allgather_count


@pytest.mark.nightly  # slow-parity tier: sibling tests keep this subsystem's oracle in the default run
def test_llama7b_unrolled_is_pathological(mesh8):
    """Document WHY the defaults matter: the unrolled fp32 graph blows the
    budget (weight gathers hoisted + quadratic attention + no remat)."""
    model = CausalLM(llama2_7b())  # fp32, unrolled, no remat
    a = audit_train_step(model, DS_CONFIG, mesh_axes={"data": 1, "fsdp": 8},
                         micro_bs=1, seq=2048, attention_impl=None)
    assert a.temp_bytes > 2 * HBM_BUDGET
