"""Static validation of Pallas BlockSpecs against real-TPU lowering rules.

Interpret mode (all CPU CI) skips Mosaic's layout checks, so a kernel can
pass every numeric test and still be rejected the first time it runs on
hardware. That happened in round 3: the flash-attention ALiBi ``slopes``
input used a ``(1, LANES)`` block over a 2D ``(B*H, LANES)`` array, which
real lowering rejects — every training bench config failed on the live
chip while CI was green.

The rule (from the TPU lowering error text): for every block in the
default (VMEM) memory space, the last two block dims must each be
divisible by (8, 128) respectively OR equal the corresponding array dim.
Rank-1 blocks need the last dim divisible by 128 or equal.

This test monkeypatches ``pallas_call`` to capture (specs, array shapes)
for every kernel invocation, drives each in-tree Pallas op through its
public API in interpret mode, and asserts the rule for all captured
blocks — so CPU CI now fails where hardware would.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as real_pallas

pytestmark = pytest.mark.fast


def _block_violations(spec, shape, where):
    out = []
    block = getattr(spec, "block_shape", None)
    if block is None:  # full-array spec (incl. un-blocked SMEM scalar tables)
        return out
    # NOTE: hardware applies the tiling rule to every spec WITH a block
    # shape, even in SMEM (verified on the live chip, round 3) — no
    # memory-space exemption here.
    block = tuple(1 if b is None else int(b) for b in block)
    if len(block) != len(shape):
        out.append(f"{where}: block rank {block} != array rank {shape}")
        return out
    if len(block) >= 2:
        if block[-1] % 128 != 0 and block[-1] != shape[-1]:
            out.append(f"{where}: last block dim {block[-1]} not %128 nor == array {shape[-1]} "
                       f"(block={block} array={shape})")
        if block[-2] % 8 != 0 and block[-2] != shape[-2]:
            out.append(f"{where}: 2nd-minor block dim {block[-2]} not %8 nor == array {shape[-2]} "
                       f"(block={block} array={shape})")
    elif len(block) == 1:
        if block[0] % 128 != 0 and block[0] != shape[0]:
            out.append(f"{where}: 1D block {block[0]} not %128 nor == array {shape[0]}")
    return out


_ORIG_PALLAS_CALL = real_pallas.pallas_call


class _Recorder:
    def __init__(self):
        self.violations = []
        self.calls = 0

    def patched_pallas_call(self, kernel, **kwargs):
        real = _ORIG_PALLAS_CALL(kernel, **kwargs)
        grid_spec = kwargs.get("grid_spec")
        if grid_spec is not None:
            in_specs = list(grid_spec.in_specs)
            out_specs = grid_spec.out_specs
            skip = int(getattr(grid_spec, "num_scalar_prefetch", 0) or 0)
        else:
            in_specs = list(kwargs.get("in_specs") or [])
            out_specs = kwargs.get("out_specs")
            skip = 0
        out_shape = kwargs.get("out_shape")
        out_specs = list(out_specs) if isinstance(out_specs, (list, tuple)) else [out_specs]
        out_shapes = out_shape if isinstance(out_shape, (list, tuple)) else [out_shape]
        name = getattr(kernel, "func", kernel)
        name = getattr(name, "__name__", str(name))

        @functools.wraps(real)
        def wrapper(*args):
            self.calls += 1
            for i, (spec, arg) in enumerate(zip(in_specs, args[skip:])):
                self.violations += _block_violations(spec, jnp.shape(arg), f"{name} in[{i}]")
            for i, (spec, sds) in enumerate(zip(out_specs, out_shapes)):
                if spec is not None and sds is not None:
                    self.violations += _block_violations(spec, tuple(sds.shape), f"{name} out[{i}]")
            return real(*args)

        return wrapper


@pytest.fixture
def record(monkeypatch):
    rec = _Recorder()
    monkeypatch.setattr(real_pallas, "pallas_call", rec.patched_pallas_call)
    yield rec
    assert rec.calls > 0, "op under test never reached pallas_call — checker exercised nothing"
    assert not rec.violations, "TPU lowering rule violations:\n" + "\n".join(rec.violations)


def _qkv(B=2, S=256, H=4, D=64, dtype=jnp.bfloat16):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    return (jax.random.normal(k1, (B, S, H, D), dtype), jax.random.normal(k2, (B, S, H, D), dtype),
            jax.random.normal(k3, (B, S, H, D), dtype))


def test_flash_attention_specs(record):
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    q, k, v = _qkv()
    H = q.shape[2]
    slopes = np.geomspace(0.25, 0.001, H).astype(np.float32)
    bias_collapsed = jax.random.normal(jax.random.PRNGKey(1), (1, 1, 1, q.shape[1]), jnp.float32)
    bias_full = jax.random.normal(jax.random.PRNGKey(2), (q.shape[0], H, q.shape[1], q.shape[1]), jnp.float32)

    for kwargs in (dict(causal=True), dict(causal=True, alibi_slopes=slopes), dict(causal=True, window=64),
                   dict(causal=False, bias=bias_collapsed), dict(causal=True, bias=bias_full)):
        fn = lambda q, k, v: flash_attention(q, k, v, interpret=True, **kwargs).astype(jnp.float32).sum()
        jax.grad(fn, argnums=(0, 1, 2))(q, k, v)


def test_flash_attention_gqa_collapsed_specs(record):
    """Round-4 rewrite: GQA keeps KV collapsed at (B, S, KVH, D) through
    fwd AND bwd (``_bwd_fused_kernel`` runs a (B*KVH, n_rep, Sk//bk) grid).
    Every collapsed-KV BlockSpec — including the ALiBi slopes table and
    window masking that broke on real Mosaic in round 3 — must satisfy
    the (8, 128) tiling rule at GQA shapes too."""
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    B, S, H, KVH, D = 2, 256, 8, 2, 64
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(k1, (B, S, H, D), jnp.bfloat16)
    k = jax.random.normal(k2, (B, S, KVH, D), jnp.bfloat16)
    v = jax.random.normal(k3, (B, S, KVH, D), jnp.bfloat16)
    slopes = np.geomspace(0.25, 0.001, H).astype(np.float32)
    bias_collapsed = jax.random.normal(jax.random.PRNGKey(1), (1, 1, 1, S), jnp.float32)

    for kwargs in (dict(causal=True), dict(causal=True, alibi_slopes=slopes),
                   dict(causal=True, window=64), dict(causal=False, bias=bias_collapsed)):
        fn = lambda q, k, v: flash_attention(q, k, v, interpret=True, **kwargs).astype(jnp.float32).sum()
        jax.grad(fn, argnums=(0, 1, 2))(q, k, v)

    # the llama-7B bench geometry's GQA ratio (8:1) at a CI-sized S
    q8 = jax.random.normal(k1, (1, 256, 8, 128), jnp.bfloat16)
    kv8 = jax.random.normal(k2, (1, 256, 1, 128), jnp.bfloat16)
    fn = lambda q, k, v: flash_attention(q, k, v, causal=True, interpret=True).astype(jnp.float32).sum()
    jax.grad(fn, argnums=(0, 1, 2))(q8, kv8, kv8)


def test_quantized_matmul_tp_shard_specs(record):
    """Round-4 rewrite: under TP serving, ``quantized_matmul_sharded``'s
    ``custom_partitioning`` re-invokes the fused kernel with PER-SHARD
    shapes (column-parallel: N/tp columns; row-parallel: K/tp rows with
    K-groups shard-local). Those shard shapes — not the full-array ones
    the plain spec test drives — are what real Mosaic lowers on a pod,
    so the tiling rule must hold for every TP degree the engines use."""
    from deepspeed_tpu.ops.pallas.quantized_matmul import (quantize_weight_kgroups,
                                                           quantized_matmul_pallas)

    K, N, group = 256, 512, 128
    w = jax.random.normal(jax.random.PRNGKey(0), (K, N), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (16, K), jnp.bfloat16)
    for tp in (2, 4, 8):
        # column-parallel shard: full K, N/tp columns (quantize-after-sharding)
        qc, sc = quantize_weight_kgroups(w[:, : N // tp], group_size=group)
        quantized_matmul_pallas(x, qc, sc, interpret=True)
        quantized_matmul_pallas(x[:2], qc, sc, interpret=True)  # decode M
        # row-parallel shard: K/tp rows; groups align to the split so the
        # shard quantizes standalone (group <= K/tp enforced by serving)
        k_shard = K // tp
        qr, sr = quantize_weight_kgroups(w[:k_shard], group_size=min(group, k_shard))
        quantized_matmul_pallas(x[:, :k_shard], qr, sr, interpret=True)
    # int4 packed at tp=2, both parallelisms
    q4c, s4c = quantize_weight_kgroups(w[:, : N // 2], group_size=group, bits=4, pack=True)
    quantized_matmul_pallas(x, q4c, s4c, packed=True, interpret=True)
    q4r, s4r = quantize_weight_kgroups(w[: K // 2], group_size=group, bits=4, pack=True)
    quantized_matmul_pallas(x[:, : K // 2], q4r, s4r, packed=True, interpret=True)


def test_paged_attention_specs(record):
    pltpu = pytest.importorskip("jax.experimental.pallas.tpu")  # noqa: F841
    from deepspeed_tpu.ops.pallas.paged_attention import paged_attention_decode, paged_attention_prefill

    B, H, D, bs, N, P = 2, 8, 64, 16, 8, 3
    q = jax.random.normal(jax.random.PRNGKey(0), (B, H, D), jnp.bfloat16)
    k_pages = jax.random.normal(jax.random.PRNGKey(1), (N, bs, H, D), jnp.bfloat16)
    v_pages = jax.random.normal(jax.random.PRNGKey(2), (N, bs, H, D), jnp.bfloat16)
    tables = jnp.arange(B * P, dtype=jnp.int32).reshape(B, P) % N
    ctx = jnp.array([20, 33], jnp.int32)
    paged_attention_decode(q, k_pages, v_pages, tables, ctx, interpret=True)

    S = 8
    qp = jax.random.normal(jax.random.PRNGKey(3), (B, S, H, D), jnp.bfloat16)
    qpos = jnp.stack([jnp.arange(S, dtype=jnp.int32) + 12, jnp.arange(S, dtype=jnp.int32) + 25])
    paged_attention_prefill(qp, k_pages, v_pages, tables, ctx, qpos, interpret=True)


def test_norms_specs(record):
    from deepspeed_tpu.ops.pallas.norms import layer_norm, rms_norm

    x = jax.random.normal(jax.random.PRNGKey(0), (4, 128, 256), jnp.bfloat16)
    w = jnp.ones((256,), jnp.float32)
    b = jnp.zeros((256,), jnp.float32)
    rms_norm(x, w, interpret=True)
    layer_norm(x, w, b, interpret=True)


def test_fused_adam_lamb_specs(record):
    from deepspeed_tpu.ops.pallas.fused_adam import fused_adam_flat
    from deepspeed_tpu.ops.pallas.fused_lamb import fused_lamb_flat

    n = 1000  # deliberately not a multiple of the block: exercises padding
    p = jnp.ones((n,), jnp.float32)
    g = jnp.full((n,), 0.1, jnp.float32)
    m = jnp.zeros((n,), jnp.float32)
    v = jnp.zeros((n,), jnp.float32)
    fused_adam_flat(p, g, m, v, lr=1e-3, step=1, block=512, interpret=True)
    fused_lamb_flat(p, g, m, v, lr=1e-3, step=1, block=512, interpret=True)


def test_quantization_specs(record):
    from deepspeed_tpu.ops.pallas.quantization import dequantize_groupwise, quantize_groupwise

    x = jax.random.normal(jax.random.PRNGKey(0), (64, 512), jnp.float32)
    qv, scales = quantize_groupwise(x, group_size=128, bits=8, interpret=True)
    dequantize_groupwise(qv, scales, out_shape=x.shape, interpret=True)


def test_quantized_matmul_specs(record):
    from deepspeed_tpu.ops.pallas.quantized_matmul import quantize_weight_kgroups, quantized_matmul_pallas

    w = jax.random.normal(jax.random.PRNGKey(0), (256, 384), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (16, 256), jnp.bfloat16)
    q, s = quantize_weight_kgroups(w, group_size=128)
    quantized_matmul_pallas(x, q, s, interpret=True)
    # decode-shaped tiny M goes through the sublane pad path
    quantized_matmul_pallas(x[:2], q, s, interpret=True)
    q4, s4 = quantize_weight_kgroups(w, group_size=128, bits=4, pack=True)
    quantized_matmul_pallas(x, q4, s4, packed=True, interpret=True)


def test_sparse_attention_specs(record):
    from deepspeed_tpu.ops.sparse_attention import FixedSparsityConfig, sparse_attention

    B, S, H, D = 2, 256, 4, 64
    q, k, v = _qkv(B, S, H, D)
    cfg = FixedSparsityConfig(num_heads=H, block=64)
    fn = lambda q, k, v: sparse_attention(q, k, v, config=cfg, causal=True,
                                          interpret=True).astype(jnp.float32).sum()
    jax.grad(fn, argnums=(0, 1, 2))(q, k, v)
