"""Regression tests for the round-2 advisor findings."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.module_inject.load_checkpoint import config_from_hf
from deepspeed_tpu.ops.attention import attention_xla
from deepspeed_tpu.ops.fused_ce import _pick_chunk
from deepspeed_tpu.runtime.eigenvalue import Eigenvalue


LLAMA_BASE = {
    "model_type": "llama",
    "vocab_size": 64,
    "num_hidden_layers": 1,
    "num_attention_heads": 2,
    "num_key_value_heads": 2,
    "hidden_size": 16,
    "intermediate_size": 32,
}


class TestRopeScalingConfig:
    """Round 4 turned the blanket rejection into support: linear/dynamic/
    llama3/yarn map onto TransformerConfig rope_* fields (oracle parity in
    test_hf_interop_archs); only longrope-class per-dim tables still raise."""

    @pytest.mark.parametrize("kind,extra", [
        ("linear", {}), ("dynamic", {}),
        ("llama3", {"low_freq_factor": 1.0, "high_freq_factor": 4.0,
                    "original_max_position_embeddings": 32}),
        ("yarn", {"original_max_position_embeddings": 32}),
    ])
    def test_supported_variants_map(self, kind, extra):
        hf = dict(LLAMA_BASE, rope_scaling={"rope_type": kind, "factor": 2.0, **extra})
        cfg = config_from_hf(hf)
        assert cfg.rope_scaling == kind and cfg.rope_factor == 2.0

    def test_longrope_rejected(self):
        hf = dict(LLAMA_BASE, rope_scaling={"rope_type": "longrope", "factor": 4.0,
                                            "short_factor": [1.0], "long_factor": [2.0]})
        with pytest.raises(NotImplementedError, match="longrope"):
            config_from_hf(hf)

    def test_trivial_or_absent_rope_scaling_ok(self):
        for hf in (dict(LLAMA_BASE), dict(LLAMA_BASE, rope_scaling=None),
                   dict(LLAMA_BASE, rope_scaling={"type": "default", "factor": 1.0}),
                   # linear/dynamic at factor 1.0 are identity scalings
                   dict(LLAMA_BASE, rope_scaling={"type": "linear", "factor": 1.0}),
                   dict(LLAMA_BASE, rope_scaling={"type": "dynamic", "factor": 1.0})):
            assert config_from_hf(hf).rope_scaling is None


class TestWindowWithoutCausal:
    def test_window_implies_upper_bound(self):
        """window='(i-w, i]' must hold even with causal=False."""
        rng = jax.random.PRNGKey(0)
        kq, kk, kv = jax.random.split(rng, 3)
        q = jax.random.normal(kq, (1, 8, 2, 4), jnp.float32)
        k = jax.random.normal(kk, (1, 8, 2, 4), jnp.float32)
        v = jax.random.normal(kv, (1, 8, 2, 4), jnp.float32)
        o_nc = attention_xla(q, k, v, causal=False, window=3)
        o_c = attention_xla(q, k, v, causal=True, window=3)
        np.testing.assert_allclose(np.asarray(o_nc), np.asarray(o_c), rtol=1e-6)


class TestEigenvalueMaxIter:
    def test_max_iter_zero_rejected(self):
        with pytest.raises(ValueError, match="max_iter"):
            Eigenvalue(max_iter=0)

    def test_max_iter_negative_rejected(self):
        with pytest.raises(ValueError, match="max_iter"):
            Eigenvalue(max_iter=-3)


class TestPickChunkDivisor:
    def test_prime_seq_len_warns_and_takes_full_block(self):
        with pytest.warns(UserWarning, match="no divisor"):
            c = _pick_chunk(509, target=128)  # 509 is prime
        assert c == 509  # full block beats 509 near-scalar matmuls

    def test_odd_composite_picks_largest_divisor(self):
        c = _pick_chunk(513, target=128)  # 513 = 27 * 19
        assert c == 57  # largest divisor of 513 that is <= 128
        assert 513 % c == 0

    def test_divisible_unchanged(self):
        assert _pick_chunk(1024, target=512) == 512
        assert _pick_chunk(96, target=512) == 32  # first power-of-two candidate that divides


class TestAutoChunkBudget:
    """Round-3 hardware A/B: chunk=S beat chunk=512 by 2.2%, so the default
    is now the largest chunk whose fp32 logits block fits the budget."""

    def test_small_batch_takes_full_sequence(self):
        assert _pick_chunk(1024, B=8, V=50257) == 1024
        assert _pick_chunk(1024, B=16, V=50257) == 1024

    def test_large_batch_budgets_down(self):
        c = _pick_chunk(1024, B=256, V=50257)
        assert c < 1024 and 1024 % c == 0

    def test_explicit_target_still_wins(self):
        assert _pick_chunk(1024, target=256, B=8, V=50257) == 256
