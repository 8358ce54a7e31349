"""The main path's Pallas kernels, compiled for one described TPU v5e chip.

No chip is attached: the TPU compiler that ships with jax compiles for a
topology that is only described, and refuses what the chip's compiler would
refuse (tiling, VMEM, layouts) — what interpret mode cannot see. Shapes are
the ones ``chip_smoke.py`` runs on the real chip: GPT-2-124M widths for the
serving kernels, the llama-7B geometry for the GQA flash kernels, and the
benchmark's training cell (OLMo-1B: 16 heads of 128, S=2048, micro-batch 2).

The topology is described inside a fixture, never at import: only one process
may load libtpu, and every xdist worker imports every test file.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

H, D, BS, POOL, PAGES = 12, 64, 128, 64, 8  # GPT-2 heads/head_dim, KV block, pool blocks, pages/seq
BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32
S = jax.ShapeDtypeStruct  # a case lists its operands as shapes; the test places them on the described chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe means these tests cannot run here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    """A program compiled for a described chip is written to the persistent
    cache but cannot be read back without one (the next compile warns and
    recompiles), so the suite-wide cache (tests/conftest.py) is off here."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _flash(shape, bwd_path="fused"):
    """Forward + vjp: 2 kernels, the forward and the fused backward; a head
    whose q, do and dq do not fit VMEM at once is refused while tracing."""
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    B, Sq, Hq, KVH, Dh = shape

    def fwd_bwd(q, k, v, do):
        o, vjp = jax.vjp(lambda q, k, v: flash_attention(q, k, v, causal=True), q, k, v)
        return (o,) + vjp(do)

    q = S((B, Sq, Hq, Dh), BF16)
    kv = S((B, Sq, KVH, Dh), BF16)
    return fwd_bwd, (q, kv, kv, q), 2, bwd_path


def _fused_adam(shape):
    from deepspeed_tpu.ops.pallas.fused_adam import fused_adam_flat

    leaf = S(shape, F32)
    return (lambda p, g, m, v, step: fused_adam_flat(p, g, m, v, 1e-4, step, weight_decay=0.01),
            (leaf, leaf, leaf, leaf, S((), I32)), 1)


def _norm(kind):
    from deepspeed_tpu.ops.pallas import norms

    x, w = S((1, 264, 768), BF16), S((768,), BF16)  # the fused step's flat (1, T, d_model) batch
    if kind == "layer_norm":
        return (lambda x, w, b: norms.layer_norm(x, w, b, 1e-5)), (x, w, w), 1
    return (lambda x, w: norms.rms_norm(x, w, 1e-5)), (x, w), 1


def _pool(kvq):
    page = S((POOL, BS, H, D), jnp.int8 if kvq else BF16)
    return (page, S((POOL, BS, H), F32)) if kvq else page


def _paged_decode(kvq):
    from deepspeed_tpu.ops.pallas.paged_attention import paged_attention_decode

    B = 8
    return (paged_attention_decode,
            (S((B, H, D), BF16), _pool(kvq), _pool(kvq), S((B, PAGES), I32), S((B,), I32)), 1)


def _paged_prefill():
    from deepspeed_tpu.ops.pallas.paged_attention import paged_attention_prefill, prefill_path

    B, chunk = 4, 128
    assert prefill_path(chunk, H, D) == "kernel"
    return (paged_attention_prefill,
            (S((B, chunk, H, D), BF16), _pool(0), _pool(0), S((B, PAGES), I32), S((B,), I32), S((B, chunk), I32)), 1)


def _paged_mixed():
    from deepspeed_tpu.ops.pallas.paged_attention import (paged_attention_decode, paged_attention_mixed,
                                                          paged_attention_prefill)

    n_dec, n_pre, chunk = 8, 2, 128
    T, N = n_dec + n_pre * chunk, n_dec + n_pre
    fn = functools.partial(paged_attention_mixed, n_dec=n_dec, chunk=chunk, decode_fn=paged_attention_decode,
                           prefill_fn=paged_attention_prefill)
    return fn, (S((T, H, D), BF16), _pool(0), _pool(0), S((N, PAGES), I32), S((N,), I32), S((T,), I32)), 2


CASES = {
    "flash_mha_b8_s1024_h12_d64": lambda: _flash((8, 1024, 12, 12, 64)),
    "flash_gqa_b2_s4096_h32_kvh4_d128": lambda: _flash((2, 4096, 32, 4, 128)),
    "flash_mha_b2_s2048_h16_d128": lambda: _flash((2, 2048, 16, 16, 128)),  # olmo-1b.pretrain-z3, one chip's share
    "flash_mha_b1_s8192_h16_d128": lambda: _flash((1, 8192, 16, 16, 128)),  # 24 MiB resident: fused, limit raised
    "flash_mha_b1_s32768_h2_d128": lambda: _flash((1, 32768, 2, 2, 128), "refused"),  # 77 MiB resident: over the budget
    "fused_adam_wte_50257x768": lambda: _fused_adam((50257, 768)),
    "fused_adam_mlp_768x3072": lambda: _fused_adam((768, 3072)),
    "fused_adam_bias_768": lambda: _fused_adam((768,)),
    "layer_norm_t264_d768": lambda: _norm("layer_norm"),
    "rms_norm_t264_d768": lambda: _norm("rms_norm"),
    "paged_decode_bf16": lambda: _paged_decode(0),
    "paged_decode_int8": lambda: _paged_decode(8),
    "paged_prefill_s128": _paged_prefill,
    "paged_mixed_8dec_2x128": _paged_mixed,
}


@pytest.mark.parametrize("case", list(CASES))
def test_kernel_compiles_for_v5e(case, one_chip):
    from deepspeed_tpu.telemetry.registry import get_registry

    fn, shapes, kernels, *bwd_path = CASES[case]()
    args = jax.tree_util.tree_map(lambda s: S(s.shape, s.dtype, sharding=one_chip), shapes)
    traced = lambda: {p: get_registry().peek("flash_attention_traced_total", **{"pass": "bwd", "path": p}) or 0.0
                      for p in ("fused", "split")}
    before = traced()
    if bwd_path == ["refused"]:
        with pytest.raises(NotImplementedError, match="seq_q=32768.*VMEM"):
            jax.jit(fn).lower(*args)
        return
    compiled = jax.jit(fn).lower(*args).compile()
    if not bwd_path:
        assert compiled.as_text().count("tpu_custom_call") >= kernels
        return
    # the flash cases name their backward: exactly that many kernels, and the rule picked that path
    assert compiled.as_text().count("tpu_custom_call") == kernels
    assert {p: n - before[p] for p, n in traced().items()} == {"fused": 0.0, "split": 0.0, bwd_path[0]: 1.0}
