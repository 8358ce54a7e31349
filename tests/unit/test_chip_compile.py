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
import re

import jax
import jax.numpy as jnp
import numpy as np
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


def _flash(shape, bwd_path="fused", window=None):
    """Forward + vjp: 2 kernels, the forward and the fused backward; a head
    whose q, do and dq do not fit VMEM at once is refused while tracing."""
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    B, Sq, Hq, KVH, Dh = shape

    def fwd_bwd(q, k, v, do):
        o, vjp = jax.vjp(lambda q, k, v: flash_attention(q, k, v, causal=True, window=window), q, k, v)
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


def _flash_latent(shape):
    """Latent attention's call: keys of ``Dk`` beside values of ``Dv``, unpadded, forward and the fused backward."""
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    B, Sq, Hq, Dk, Dv = shape

    def fwd_bwd(q, k, v, do):
        o, vjp = jax.vjp(lambda q, k, v: flash_attention(q, k, v, causal=True), q, k, v)
        return (o,) + vjp(do)

    qk, v = S((B, Sq, Hq, Dk), BF16), S((B, Sq, Hq, Dv), BF16)
    return fwd_bwd, (qk, qk, v, v), 2, "fused"


def _kda(shape):
    """The chunked delta-rule scan, forward and backward: 2 kernels (heads before the sequence)."""
    from deepspeed_tpu.ops.kda import kda_chunked

    B, Hh, Sq, Dh = shape

    def fwd_bwd(q, k, v, g, beta, do):
        o, vjp = jax.vjp(kda_chunked, q, k, v, g, beta)
        return (o,) + vjp(do)

    x = S((B, Hh, Sq, Dh), BF16)
    return fwd_bwd, (x, x, x, S((B, Hh, Sq, Dh), F32), S((B, Hh, Sq), F32), x), 2


def _gdn(shape):
    """The same scan with one decay a head and token, value heads sharing a key head's q and k: 2 kernels."""
    from deepspeed_tpu.ops.kda import gdn_chunked

    B, Hk, Hv, Sq, Dh = shape

    def fwd_bwd(q, k, v, g, beta, do):
        o, vjp = jax.vjp(gdn_chunked, q, k, v, g, beta)
        return (o,) + vjp(do)

    qk, v, gate = S((B, Hk, Sq, Dh), BF16), S((B, Hv, Sq, Dh), BF16), S((B, Hv, Sq), F32)
    return fwd_bwd, (qk, qk, v, gate, gate, v), 2


def _scan_operands(shape, decay):
    """What the delta-rule scans read, made from the kept projections, forward and backward: 2 kernels, a key head's tile
    of rows a grid step with its halo blocks; ``decay``: a channel (KDA: ``g`` is made too), else the caller's (Gated DeltaNet)."""
    from deepspeed_tpu.ops.pallas.scan_operands import scan_operands

    B, Hk, Hv, Sq, Dh, K = shape
    key, value, w = S((B, Hk, Sq, Dh), BF16), S((B, Hv, Sq, Dh), BF16), lambda H: S((K, H, Dh), F32)
    args = [key, key, value, S((B, Sq, Hv), F32), w(Hk), w(Hk), w(Hv)] + ([S((B, Hk, Sq, Dh), F32), S((Hk,), F32), S((Hk, Dh), F32)] if decay else [])
    cts = (key, key, value, value) + ((S((B, Hk, Sq, Dh), F32),) if decay else ())

    def fwd_bwd(args, cts):
        out, vjp = jax.vjp(scan_operands, *args)
        return out, vjp(cts)

    return fwd_bwd, (args, cts), 2


def _conv_silu(shape, start, widths):
    """A Mamba layer's convolution, bias and SiLU over a column range of the kept product, forward and backward: 2
    kernels, a tile of rows of a part of every output a grid step, the product's columns reached in place."""
    from deepspeed_tpu.ops.pallas.conv_silu import conv_silu

    B, Sq, columns, K = shape
    args = (S((B, Sq, columns), BF16), S((K, sum(widths)), F32), S((sum(widths),), F32))

    def fwd_bwd(args, cts):
        out, vjp = jax.vjp(lambda x, w, b: conv_silu(x, w, b, start, widths), *args)
        return out, vjp(cts)

    return fwd_bwd, (args, tuple(S((B, Sq, W), BF16) for W in widths)), 2


def _ssm(shape):
    """The selective scan, forward and backward: 2 kernels (channels along the lanes, the whole state in VMEM)."""
    from deepspeed_tpu.ops.ssm import ssm_chunked

    B, Sq, channels, N = shape

    def fwd_bwd(u, delta, A, Bm, Cm, D, dy):
        y, vjp = jax.vjp(ssm_chunked, u, delta, A, Bm, Cm, D)
        return (y,) + vjp(dy)

    x, cols = S((B, Sq, channels), BF16), S((B, Sq, N), BF16)
    return fwd_bwd, (x, S((B, Sq, channels), F32), S((channels, N), F32), cols, cols, S((channels,), F32), x), 2


def _ssd(shape):
    """The Mamba-2 (SSD) scan, forward and backward: 2 kernels (a group's heads along the lanes, its state in VMEM); the
    cumulative sums and the per-token floats around them are XLA's."""
    from deepspeed_tpu.ops.ssd import ssd_chunked

    B, Sq, Hh, P, G, N = shape

    def fwd_bwd(x, delta, A, Bm, Cm, D, dy):
        y, vjp = jax.vjp(ssd_chunked, x, delta, A, Bm, Cm, D)
        return (y,) + vjp(dy)

    x, cols, head = S((B, Sq, Hh, P), BF16), S((B, Sq, G, N), BF16), S((Hh,), F32)
    return fwd_bwd, (x, S((B, Sq, Hh), F32), head, cols, cols, head, x), 2


def _flash_diff(shape, window):
    """One of differential attention's two calls: keys of 64 beside values of 128, grouped, under a window or none."""
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    B, Sq, Hq, KVH, Dk, Dv = shape

    def fwd_bwd(q, k, v, do):
        o, vjp = jax.vjp(lambda q, k, v: flash_attention(q, k, v, causal=True, window=window, scale=Dk ** -0.5), q, k, v)
        return (o,) + vjp(do)

    return fwd_bwd, (S((B, Sq, Hq, Dk), BF16), S((B, Sq, KVH, Dk), BF16), S((B, Sq, KVH, Dv), BF16), S((B, Sq, Hq, Dv), BF16)), 2, "fused"


def _flash_blockdiff(shape, block):
    """Attention under the block-diffusion mask over a doubled row (``ops/masks.py::BlockDiffusion``): forward and the
    fused backward, which at 16,384 rows and 8 query heads a KV head runs a head at a time on copies of the KV heads."""
    from deepspeed_tpu.ops import masks
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    B, Sq, Hq, KVH, Dh = shape
    mask = masks.BlockDiffusion(block, Sq // 2)

    def fwd_bwd(q, k, v, do):
        o, vjp = jax.vjp(lambda q, k, v: flash_attention(q, k, v, mask=mask), q, k, v)
        return (o,) + vjp(do)

    q, kv = S((B, Sq, Hq, Dh), BF16), S((B, Sq, KVH, Dh), BF16)
    return fwd_bwd, (q, kv, kv, q), 2, "kernel"


def _short_conv(shape):
    """The gated short convolution between a ``conv`` layer's two products, forward and backward: 2 kernels, a tile of
    rows at the full width a grid step, the rows it reaches back to (and ahead) as halo blocks."""
    from deepspeed_tpu.ops.pallas.short_conv import short_conv

    B, Sq, Dm, K = shape

    def fwd_bwd(x, w, dy):
        y, vjp = jax.vjp(short_conv, x, w)
        return (y,) + vjp(dy)

    return fwd_bwd, (S((B, Sq, 3 * Dm), BF16), S((K, Dm), F32), S((B, Sq, Dm), BF16)), 2


def _grouped_products(shape, tilings):
    """A routed layer's up and down products through the grouped matmul at the tiles ``moe/sharded_moe.py::_grouped`` picks
    for them (``tests/unit/test_short_conv_layers.py`` and ``test_mamba2_layers.py`` hold it to these), forward and backward:
    gmm, gmm and tgmm each."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    rows, n, d, f = shape

    def fwd_bwd(xs, wg, wo, sizes, dy):
        def part(xs, wg, wo):
            hidden = gmm(xs, wg, sizes, preferred_element_type=xs.dtype, tiling=tilings[0])
            return gmm(hidden, wo, sizes, preferred_element_type=xs.dtype, tiling=tilings[1])
        y, vjp = jax.vjp(part, xs, wg, wo)
        return (y,) + vjp(dy)

    return fwd_bwd, (S((rows, d), BF16), S((n, d, f), BF16), S((n, f, d), BF16), S((n,), I32), S((rows, d), BF16)), 6


def _moe_sum_rows(shape):
    """A routed layer's tokens sum their own rows off the expert-sorted buffer, each row times its weight: the combine,
    and with weights of one the backward of the rows' gather."""
    from deepspeed_tpu.ops.pallas.moe_sum_rows import TOKENS, sum_rows

    N, d, held, rows = shape

    return (lambda buffer, tok_of_row, w_row, spans: sum_rows(buffer, tok_of_row, w_row, spans, N),
            (S((rows, d), BF16), S((rows,), I32), S((rows,), F32), S((2, N // TOKENS * held), I32)), 1)


def _indexed(which):
    """One of the six calls of attention over an indexer's choice, at ``keye-vl2-30b-l4e16``'s shapes: 32 query heads on
    4 KV heads of 128, an indexer of 16 heads of 64, 8,192 positions, 2,048 keys a query, the mask in 512 x 512 tiles."""
    from deepspeed_tpu.ops.pallas import indexed_attention as K

    Sq, Hq, KVH, Dh, J, Di, n = 8192, 32, 4, 128, 16, 64, 16
    q, kv, rows, tiles = S((Hq, Sq, Dh), BF16), S((KVH, Sq, Dh), BF16), S((Hq, Sq), F32), S((1, n, n, 512, 512), jnp.int8)
    q_i, k_i, w, square = S((1, J, Sq, Di), BF16), S((1, Sq, Di), BF16), S((1, J, Sq), F32), S((1, Sq, Sq), F32)
    scale = Dh ** -0.5
    return {
        "index_scores": (lambda q_i, k_i, w: K.index_scores(q_i, k_i, w), (q_i, k_i, w), 1),
        "index_select": (lambda s: K.index_select(s, 2048), (square,), 1),
        "sparse_fwd": (lambda q, k, v, m: K.sparse_fwd(q, k, v, m, scale, Hq, KVH), (q, kv, kv, tiles), 1),
        "sparse_bwd": (lambda q, k, v, o, lse, do, m: K.sparse_bwd(q, k, v, o, lse, do, m, scale, Hq, KVH), (q, kv, kv, q, rows, q, tiles), 1),
        "index_loss": (lambda q, k, lse, m, s: K.index_loss(q, k, lse, m, s, scale, Hq, KVH, BF16), (q, kv, rows, tiles, square), 1),
        "index_scores_bwd": (lambda g, q_i, k_i, w: K.index_scores_bwd(g, q_i, k_i, w), (S((1, Sq, Sq), BF16), q_i, k_i, w), 1),
    }[which]


CASES = {
    "moe_sum_rows_t8192_d2048_e8_r24576": lambda: _moe_sum_rows((8192, 2048, 8, 24576)),  # kimi-vl-a3b-l6e8's routed layers, the usual buffer
    "moe_sum_rows_t8192_d2048_e8_r12288": lambda: _moe_sum_rows((8192, 2048, 8, 12288)),  # ... and since PR 54 the ladder's first rung, twice the uniform load
    "moe_sum_rows_t8192_d2304_e8_r8192": lambda: _moe_sum_rows((8192, 2304, 8, 8192)),    # kimi-linear-48b-l5e8's
    "moe_sum_rows_t8192_d2304_e8_r4096": lambda: _moe_sum_rows((8192, 2304, 8, 4096)),    # ... its first rung
    "moe_sum_rows_t8192_d2304_e8_r65536": lambda: _moe_sum_rows((8192, 2304, 8, 65536)),  # ... and the buffer of every pair (the cond's other branch)
    "moe_sum_rows_t8192_d2048_e32_r20480": lambda: _moe_sum_rows((8192, 2048, 32, 20480)),  # qwen3-next-80b-l4e32's: windows of 32 rows
    "moe_sum_rows_t8192_d2048_e32_r81920": lambda: _moe_sum_rows((8192, 2048, 32, 81920)),  # ... and every pair
    "flash_latent_b1_s8192_h32_d192_v128": lambda: _flash_latent((1, 8192, 32, 192, 128)),  # kimi-linear-48b-l5e8's MLA layer
    "flash_latent_b1_s8192_h16_d192_v128": lambda: _flash_latent((1, 8192, 16, 192, 128)),  # kimi-vl-a3b-l6e8's, every layer
    "kda_scan_b1_h32_s8192_d128": lambda: _kda((1, 32, 8192, 128)),                         # ... and its KDA layers
    "kda_scan_b2_h4_s1000_d128": lambda: _kda((2, 4, 1000, 128)),                          # a length that is padded to chunks
    "gdn_scan_b1_h16_v32_s8192_d128": lambda: _gdn((1, 16, 32, 8192, 128)),                 # qwen3-next-80b-l4e32's DeltaNet layers
    "gdn_scan_b2_h2_v4_s1000_d128": lambda: _gdn((2, 2, 4, 1000, 128)),
    "ssm_scan_b1_s8192_c5120_n16": lambda: _ssm((1, 8192, 5120, 16)),                      # phi4-mini-flash-l6's two scan layers
    "ssm_scan_b2_s1000_c256_n16": lambda: _ssm((2, 1000, 256, 16)),                        # a length that is padded to chunks
    "flash_diff_b1_s8192_h20_kvh10_d64_v128": lambda: _flash_diff((1, 8192, 20, 10, 64, 128), None),     # ... its full and cross layers' calls
    "flash_diff_b1_s8192_h20_kvh10_d64_v128_w512": lambda: _flash_diff((1, 8192, 20, 10, 64, 128), 512),  # ... and its window layer's
    "flash_blockdiff_b1_s16384_h32_kvh4_d128_blk4": lambda: _flash_blockdiff((1, 16384, 32, 4, 128), 4),    # sdar-30b-a3b-l4e16's every layer
    "flash_blockdiff_b1_s16384_h32_kvh4_d128_blk16": lambda: _flash_blockdiff((1, 16384, 32, 4, 128), 16),  # ... another block length
    "flash_blockdiff_b1_s6144_h8_kvh2_d128_blk12": lambda: _flash_blockdiff((1, 6144, 8, 2, 128), 12),      # ... one that is no power of two, grouped in the kernel
    "flash_mha_b8_s1024_h12_d64": lambda: _flash((8, 1024, 12, 12, 64)),
    "flash_gqa_b2_s4096_h32_kvh4_d128": lambda: _flash((2, 4096, 32, 4, 128)),
    "flash_mha_b2_s2048_h16_d128": lambda: _flash((2, 2048, 16, 16, 128)),  # olmo-1b.pretrain-z3, one chip's share
    "flash_mha_b1_s8192_h16_d128": lambda: _flash((1, 8192, 16, 16, 128)),  # 24 MiB resident: fused, limit raised
    "flash_gqa_b1_s8192_h16_kvh2_d256": lambda: _flash((1, 8192, 16, 2, 256)),  # qwen3-next-80b-l4e32's full layer: a head at a time in the backward
    "flash_mha_b1_s32768_h2_d128": lambda: _flash((1, 32768, 2, 2, 128), "refused"),  # 77 MiB resident: over the budget
    "flash_gqa_b1_s16384_h28_kvh4_d128": lambda: _flash((1, 16384, 28, 4, 128)),  # smallthinker-21b-l4e8's full layer: a head at a time in the backward
    "flash_gqa_b1_s16384_h28_kvh4_d128_w4096": lambda: _flash((1, 16384, 28, 4, 128), window=4096),  # ... and its three window layers
    "moe_sum_rows_t16384_d2560_e8_r49152": lambda: _moe_sum_rows((16384, 2560, 8, 49152)),  # ... and its routed layers, the usual buffer
    "moe_sum_rows_t16384_d2560_e8_r24576": lambda: _moe_sum_rows((16384, 2560, 8, 24576)),  # ... the first rung
    "moe_sum_rows_t16384_d2048_e16_r32768": lambda: _moe_sum_rows((16384, 2048, 16, 32768)),  # sdar-30b-a3b-l4e16's first rung
    "moe_sum_rows_t16384_d2560_e8_r98304": lambda: _moe_sum_rows((16384, 2560, 8, 98304)),  # ... and every pair
    "short_conv_b1_s16384_d2048_k3": lambda: _short_conv((1, 16384, 2048, 3)),  # lfm2-8b-a1b-l5e8's four conv layers
    "short_conv_b2_s1008_d384_k4": lambda: _short_conv((2, 1008, 384, 4)),  # tiles of 16 rows, lanes of 128, four taps
    "flash_gqa_b1_s16384_h32_kvh8_d64": lambda: _flash((1, 16384, 32, 8, 64)),  # ... its one attention layer: heads of 64
    "moe_sum_rows_t16384_d2048_e8_r32768": lambda: _moe_sum_rows((16384, 2048, 8, 32768)),  # ... and its routed layers' first rung
    "moe_sum_rows_t16384_d2048_e8_r65536": lambda: _moe_sum_rows((16384, 2048, 8, 65536)),  # ... four times the uniform load is every pair
    "gmm_r32768_e8_d2048_f1792_rows512": lambda: _grouped_products((32768, 8, 2048, 1792), ((512, 512, 896), (512, 896, 1024))),  # ... its grouped products: 1,792 in tiles of 896, full groups in rows of 512
    "gmm_r65536_e8_d2048_f1792_rows256": lambda: _grouped_products((65536, 8, 2048, 1792), ((256, 512, 896), (256, 896, 1024))),  # ... and on the rung above
    "scan_operands_b1_h32_s8192_d128_k4_decay": lambda: _scan_operands((1, 32, 32, 8192, 128, 4), True),  # kimi-linear-48b-l5e8's four KDA layers
    "scan_operands_b1_h16_v32_s8192_d128_k4": lambda: _scan_operands((1, 16, 32, 8192, 128, 4), False),  # qwen3-next-80b-l4e32's three DeltaNet layers
    "scan_operands_b2_h2_v4_s384_d256_k2": lambda: _scan_operands((2, 2, 4, 384, 256, 2), False),  # tiles of 128 rows, two vregs of lanes, two taps
    "ssd_scan_b1_s8192_h64_p64_g8_n128": lambda: _ssd((1, 8192, 64, 64, 8, 128)),  # nemotron3-nano-30b-l9e8's four Mamba-2 layers: 8 heads a group
    "conv_silu_b1_s8192_c10304_at4096_4096_1024_1024_k4": lambda: _conv_silu((1, 8192, 10304, 4), 4096, (4096, 1024, 1024)),  # ... their convolution, bias and SiLU: x, B and C from [z, xBC, dt]
    "ssd_scan_b2_s1000_h4_p64_g2_n128": lambda: _ssd((2, 1000, 4, 64, 2, 128)),  # a length that is padded to chunks, two heads a group (one tile)
    "flash_gqa_b1_s8192_h32_kvh2_d128": lambda: _flash((1, 8192, 32, 2, 128)),  # ... its one attention layer: SIXTEEN query heads a key head
    "flash_gqa_b1_s8192_h64_kvh8_d128": lambda: _flash((1, 8192, 64, 8, 128)),  # k-exaone-236b-l5e8's full layer (a chip's call under ``shard_map``)
    "flash_gqa_b1_s8192_h64_kvh8_d128_w128": lambda: _flash((1, 8192, 64, 8, 128), window=128),  # ... and its four window layers: blocks of 512 walked in strips of 128 (PR 69)
    "moe_sum_rows_t8192_d2688_e8_r6144": lambda: _moe_sum_rows((8192, 2688, 8, 6144)),  # ... its routed layers' first rung: 384 rows an expert, twice over
    "moe_sum_rows_t8192_d2688_e8_r49152": lambda: _moe_sum_rows((8192, 2688, 8, 49152)),  # ... and every pair
    "moe_sum_rows_t8192_d6144_e4_r16384": lambda: _moe_sum_rows((8192, 6144, 4, 16384)),  # k-exaone-236b-l5e8's SENDER: the groups are the four chips, a slab of 4,096 slots each
    "moe_sum_rows_t1024_d6144_e4_r8192": lambda: _moe_sum_rows((1024, 6144, 4, 8192)),    # ... and its last rung's chunk of 1,024 tokens through slabs of 2,048
    "gmm_r6144_e8_d2688_f1856_rows256": lambda: _grouped_products((6144, 8, 2688, 1856), ((256, 896, 1024), (256, 1024, 896))),  # ... its two grouped products: 1,856 = 29 x 64 in two tiles of 1,024, the second part empty
    "conv_silu_b1_s8192_c10240_at0_5120_k4": lambda: _conv_silu((1, 8192, 10240, 4), 0, (5120,)),  # phi4-mini-flash-l6's two scan layers: u from [u, z]
    "fused_adam_wte_50257x768": lambda: _fused_adam((50257, 768)),
    **{f"indexed_{which}_s8192_h32_kv4_d128": (lambda which=which: _indexed(which))  # keye-vl2-30b-l4e16's six calls
       for which in ("index_scores", "index_select", "sparse_fwd", "sparse_bwd", "index_loss", "index_scores_bwd")},
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
    from deepspeed_tpu.telemetry.tracing import regions_traced

    fn, shapes, kernels, *bwd_path = CASES[case]()
    args = jax.tree_util.tree_map(lambda s: S(s.shape, s.dtype, sharding=one_chip), shapes)
    traced = lambda: {p: regions_traced("mixer/kernel", **{"pass": "bwd", "path": p})  # whichever ``op``
                      for p in ("fused", "split", "kernel")}  # (``kernel``: a mask with a walk of its own counts its calls so)
    trips = lambda: [regions_traced("mixer/kernel", **{"pass": p, "tiles_a_trip": n}) for p, n in (("fwd", "1"), ("fwd", "2"), ("bwd", "1"), ("bwd", "2"))]
    before, trips_before = traced(), trips()
    if bwd_path == ["refused"]:
        with pytest.raises(NotImplementedError, match="seq_q=32768.*VMEM"):
            jax.jit(fn).lower(*args)
        return
    compiled = jax.jit(fn).lower(*args).compile()
    if case.startswith(("kda_scan", "gdn_scan")):
        _the_scan_hands_its_inverses_on_and_walks_its_heads_by_the_rule(compiled, jax.make_jaxpr(fn)(*args), case[:8], shapes[2].shape)
    if not bwd_path:
        assert compiled.as_text().count("tpu_custom_call") >= kernels
        return
    # the flash cases name their backward: exactly that many kernels, and the rule picked that path
    assert compiled.as_text().count("tpu_custom_call") == kernels
    assert {p: n - before[p] for p, n in traced().items()} == {"fused": 0.0, "split": 0.0, "kernel": 0.0, bwd_path[0]: 1.0}
    # ... in the new form (PR 50): the forward takes two tiles a trip over its unmasked runs at every cell's shape but
    # under Phi-4's window of one tile's width, which has no unmasked run (and at GPT-2's 1,024 positions, two tiles a
    # side: no run of two); the backward keeps one
    # (and under K-EXAONE's of 128, a band whose walk has no loop: PR 69)
    pairs = 0.0 if case.endswith(("_w512", "_w128")) or "_s1024_" in case else 1.0
    assert [now - was for now, was in zip(trips(), trips_before)] == [1.0 - pairs, pairs, 1.0, 0.0]


def _mosaic_bodies(lowered_text: str):
    """Every Mosaic call's body in a lowered program, printed WITHOUT locations: the serialized module holds the source's
    line numbers, so the raw bytes differ after any edit of the kernels' file and prove nothing (PERF.md section 6, PR 49)."""
    import base64

    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    bodies = []
    for body in re.findall(r'body\\22: \\22([A-Za-z0-9+/=]+)\\22', lowered_text):
        context = mlir.make_ir_context()
        context.allow_unregistered_dialects = True
        with context:
            bodies.append(ir.Module.parse(base64.b64decode(body)).operation.get_asm(enable_debug_info=False))
    return bodies


# sha256 (first 16 hex digits) of the forward's and the backward's Mosaic bodies AT PR 68, the parent of the PR that gave
# a narrow band its strips: from ``_mosaic_bodies`` run in a checkout of that commit. A PR that edits these kernels' text
# for every mask pins its own
PARENTS_BODIES = {
    "flash_diff_b1_s8192_h20_kvh10_d64_v128_w512": ("e17d62aa94483d76", "993a748ab9a6bebb"),    # phi4-mini-flash-l6's window layer: a window of one block
    "flash_gqa_b1_s16384_h28_kvh4_d128_w4096": ("28aec4bf0cf9b062", "c915f8b9ac53fe3f"),       # smallthinker-21b-l4e8's three: of eight
    "flash_gqa_b1_s8192_h64_kvh8_d128": ("e8b5a670d1e1e087", "be20a0a2413d5915"),               # k-exaone-236b-l5e8's full layer
    "flash_blockdiff_b1_s16384_h32_kvh4_d128_blk4": ("2601baae981bae6e", "9260160424018e18"),  # sdar-30b-a3b-l4e16's
}


@pytest.mark.parametrize("case", list(PARENTS_BODIES))
def test_a_band_wider_than_half_a_block_lowers_to_the_parents_kernels(case, one_chip):
    """The rule that gives a narrow band its strips (``ops/masks.py::band_strip``) reads the window and the block alone:
    Phi-4's window of 512, SmallThinker's of 4,096, a causal call and SDAR's block mask never reach the strips, and their
    calls' Mosaic bodies are, operation for operation, what they were before the rule stood."""
    import hashlib

    fn, shapes, *_ = CASES[case]()
    args = jax.tree_util.tree_map(lambda s: S(s.shape, s.dtype, sharding=one_chip), shapes)
    bodies = _mosaic_bodies(jax.jit(fn).lower(*args).as_text())
    assert tuple(hashlib.sha256(body.encode()).hexdigest()[:16] for body in bodies) == PARENTS_BODIES[case]


def test_the_rotation_is_one_pass_over_q_at_its_full_width(one_chip):
    """``apply_rope`` forward + vjp at SDAR's q, ``(1, 16384, 32, 128)`` bf16, compiled for the described v5e: TWO fusions
    write an array of q's size, the rotation and its transpose, each with the partners' product inside (``kOutput``: a
    convolution's fusion), and nothing over the 16,384 positions has a last axis of 64: neither a half of q (which fills
    half of every 128-lane tile, so it takes q's full bytes) nor a half-width table. The halves written out
    (``x1 * c - x2 * s`` joined to ``x2 * c + x1 * s``) compiled to a pass that wrote two such halves and a pass that
    joined them, forward and backward; XLA's code alone, so no Mosaic call for a roofline reader to mistake."""
    from deepspeed_tpu.models.layers import apply_rope

    rd, rows = 128, 16384

    def fwd_bwd(x, cos, sin, positions, g):
        out, pull = jax.vjp(lambda x: apply_rope(x, cos, sin, positions), x)
        return out, pull(g)[0]

    x, table = S((1, rows, 32, rd), BF16, sharding=one_chip), S((8192, rd // 2), F32, sharding=one_chip)
    text = jax.jit(fwd_bwd).lower(x, table, table, S((1, rows), I32, sharding=one_chip), x).compile().as_text()
    entry = text[text.index("ENTRY"):]
    assert not re.findall(rf"\[(?:\d+,)*{rows},(?:\d+,)*{rd // 2}\]", text) and "tpu_custom_call" not in text
    passes = re.findall(rf"= bf16\[1,{rows},32,{rd}\]\S* fusion\(.*kind=(\w+)", entry)
    assert passes == ["kOutput", "kOutput"]


@pytest.mark.parametrize("case,heads", [("flash_blockdiff_b1_s16384_h32_kvh4_d128_blk4", (32, 4)), ("flash_gqa_b1_s8192_h16_kvh2_d256", (16, 2))])
def test_the_longest_heads_fit_their_own_vmem_count_with_two_tiles_in_the_forward(case, heads, one_chip, monkeypatch):
    """SDAR's rows of 16,384 at 128 and Qwen3-Next's of 8,192 at 256, the two calls nearest ``vmem_budget()`` (48 MiB): the
    backward's count for a head alone is 43.0 MiB in both, under it, and its group's sums over the whole sequence do not fit
    (a head at a time, as before PR 50: a second tile in the backward was measured and not taken); the forward's count,
    whose 8 MiB of temporaries hold the pair, is 24.5 and 25 MiB and the rule gives it two tiles. And the counts are enough:
    both calls compile for the described v5e under a limit of the count ITSELF, without the 16 MiB ``compiler_params`` adds
    (Mosaic's own allocation: forward 21.2 MiB with two tiles, backward 22.2 and 24.4)."""
    from jax.experimental.pallas import tpu as pltpu

    from deepspeed_tpu.ops.pallas import flash_attention as F
    from deepspeed_tpu.telemetry.tracing import regions_traced

    fn, shapes, *_ = CASES[case]()
    (_, Sq, H, D), KVH = shapes[0].shape, shapes[1].shape[2]
    assert (H, KVH) == heads
    head, group = (F._fused_bwd_vmem(Sq, Sq, D, 2, 512, 512, n_rep, D) for n_rep in (1, H // KVH))
    forward = 2 * (512 + Sq) * 2 * D * 2 + F._tile_bytes(512, 512)
    assert (head >> 10, forward >> 10) == {128: (44032, 25088), 256: (44032, 25600)}[D]  # KiB
    assert forward < head <= F.vmem_budget() < group
    limits = []

    def exactly(*semantics, interpret, vmem_bytes=0):
        limits.append(vmem_bytes)
        return pltpu.CompilerParams(dimension_semantics=semantics, vmem_limit_bytes=vmem_bytes)

    monkeypatch.setattr(F, "_compiler_params", exactly)
    two = lambda: regions_traced("mixer/kernel", **{"pass": "fwd", "tiles_a_trip": "2"})
    before = two()
    args = jax.tree_util.tree_map(lambda s: S(s.shape, s.dtype, sharding=one_chip), shapes)
    assert jax.jit(fn).lower(*args).compile().as_text().count("tpu_custom_call") == 2
    assert limits == [forward, head] and two() == before + 1


def _pallas_calls(jaxpr):
    """The ``pallas_call`` equations of a jaxpr, inner jaxprs too, in order."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for value in eqn.params.values():
            for inner in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _pallas_calls(inner)


def _the_scan_hands_its_inverses_on_and_walks_its_heads_by_the_rule(compiled, jaxpr, name, shape):
    """Exactly the two kernels; the forward's result holds, beside the outputs, two float32 residuals a (value head,
    chunk): the incoming state (d_v, d_k) and ``(I + A)^-1`` (CHUNK, CHUNK), and the backward call TAKES both as operands
    (it makes no inverse: with the construction in it the program of one head a step was a quarter larger, which the
    size of a body of several heads no longer shows); and each call's grid is (heads / H, chunks) for the H that
    ``heads_a_step`` gives that call at this shape: 4 at the cells' 32 heads and at the padded cases' 8."""
    from deepspeed_tpu.ops.pallas import kda as K

    B, Hv, Sq, Dh = shape  # v's: the value heads
    heads, chunks = B * Hv, -(-Sq // K.CHUNK)
    calls = [line for line in compiled.as_text().splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 2 and f"{name}_fwd" in calls[0] and f"{name}_bwd" in calls[1]
    assert Dh == K.CHUNK  # heads of 128: a state and an inverse have one shape
    residual = f"f32[{heads},{chunks},{K.CHUNK},{K.CHUNK}]"
    assert calls[0].split(" custom-call(")[0].count(residual) == 2 and calls[1].split(" custom-call(")[1].count(residual) == 2
    fwd, bwd = _pallas_calls(jaxpr.jaxpr)
    for call, backward in ((fwd, False), (bwd, True)):
        q, _, _, vb, g = (v.aval for v in call.invars[:5])
        g = g if g.ndim == 3 else S((heads * chunks, 1, K.CHUNK), g.dtype)  # a decay a token, as ``scan_fwd`` is handed it
        H = K.heads_a_step(q, vb, g, backward)
        assert H == 4 and call.params["grid_mapping"].grid == (heads // H, chunks)
        assert [v.aval.shape for v in call.invars[5:7]] == [(heads, chunks, K.CHUNK, K.CHUNK)] * 2 if backward else len(call.invars) == 5


# ---------------------------------------------------------------- the trainer's step on four described chips

_PERMUTE = re.compile(r" collective-permute-start\(.*?op_name=\"([^\"]*)\"")


def census(hlo_text):
    """What a compiled step does with gradients, read off its text (four
    seconds for the 16-layer step on the chip, so not the engine's business:
    my chip run, PR 28): the collective-permutes that are hops of a
    weight-gradient matmul's own ring (``ring``), those issued by
    ``zero/overlap.py``'s ``_ring_reduce_scatter`` (``bucket``), and the
    reduce-scatter collectives, which hold the operation lane."""
    names = _PERMUTE.findall(hlo_text)
    in_matmul = sum(1 for n in names if "transpose(" in n and "dot_general" in n)
    issued = sum(1 for n in names if n.endswith("ppermute"))
    scatters = len(re.findall(r"calls=%all-reduce-scatter| reduce-scatter(?:-start)?\(", hlo_text))
    form = "bucket" if issued else "ring" if in_matmul else "xla"
    return {"grad_reduce": form, "permutes": len(names), "matmul_ring_permutes": in_matmul,
            "bucket_permutes": issued, "reduce_scatters": scatters}


PERMUTE = ('  %collective-permute-start.{i} = (bf16[8,8]{{1,0}}, bf16[8,8]{{1,0}}) collective-permute-start(bf16[8,8]{{1,0}} %x), '
           'channel_id=1, metadata={{op_name="{op}" stack_frame_id=1}}\n')


@pytest.mark.parametrize("ops,scatters,want", [
    (["jit(fused_step)/transpose(jvp(Transformer))/Block_0/attn/q_proj/dot_general"] * 3, 1,
     {"grad_reduce": "ring", "permutes": 3, "matmul_ring_permutes": 3, "bucket_permutes": 0, "reduce_scatters": 1}),
    (["jit(fused_step)/transpose(jvp(Transformer))/shard_map/ppermute"] * 6
     + ["jit(fused_step)/transpose(jvp())/while/body/closed_call/dot_general"], 1,
     {"grad_reduce": "bucket", "permutes": 7, "matmul_ring_permutes": 1, "bucket_permutes": 6, "reduce_scatters": 1}),
    ([], 2, {"grad_reduce": "xla", "permutes": 0, "matmul_ring_permutes": 0, "bucket_permutes": 0, "reduce_scatters": 2}),
])
def test_census_reads_the_form_of_the_reduction_off_the_text(ops, scatters, want):
    text = "".join(PERMUTE.format(i=i, op=op) for i, op in enumerate(ops))
    text += "  %fusion.4 = bf16[8,8]{1,0} fusion(bf16[32,8]{1,0} %g), kind=kCustom, calls=%all-reduce-scatter\n" * scatters
    assert census(text) == want



# a block of OLMo-1B: four attention weights of d^2 and three of the MLP of d x 4d, no bias and no norm parameter
OLMO_BLOCK = 4 * 2048 * 2048 + 3 * 2048 * 8192


ZERO3 = {"default": {}, "one_block": {"stage3_max_live_parameters": OLMO_BLOCK}, "partitioner": {"overlap_comm": False}}


@pytest.fixture(scope="module")
def zero3_step(topo):
    """``step(case)``: the benchmark's training cell at two layers, every
    width as published (OLMo-1B, ZeRO-3 over ``fsdp=4``, micro-batch 2 of 2048
    tokens), forward and backward as the engine builds them (its planners,
    its cast, the plan of ``zero/overlap.py`` around the model's loss),
    compiled for the four described chips once a case of ``ZERO3``: the
    executable's text and the bytes of its temporaries."""
    from deepspeed_tpu.models import CausalLM, TransformerConfig
    from deepspeed_tpu.parallel import mesh as mesh_mod
    from deepspeed_tpu.parallel.mesh import MeshTopology
    from deepspeed_tpu.runtime import engine as E
    from deepspeed_tpu.runtime.config import DeepSpeedConfig, MeshConfig
    from deepspeed_tpu.runtime.zero import overlap
    from deepspeed_tpu.runtime.zero.partition import plan_grad_specs, plan_param_specs, specs_to_shardings

    @functools.cache
    def step(case):
        model = CausalLM(TransformerConfig(vocab_size=50304, n_layers=2, n_heads=16, n_kv_heads=16, d_model=2048, d_ff=8192,
                                           max_seq_len=2048, norm="layernorm_np", activation="swiglu", pos_emb="rope",
                                           tie_embeddings=True, dtype=BF16))
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(overlap, "_backend", lambda: "tpu")  # jax's backend here is the CPU; the devices are not
            patch.setattr(mesh_mod, "_TOPOLOGY", MeshTopology(MeshConfig.from_dict({"fsdp": 4}), devices=list(topo.devices)))
            mesh = mesh_mod._TOPOLOGY
            config = DeepSpeedConfig({"train_micro_batch_size_per_gpu": 2, "bf16": {"enabled": True},
                                      "zero_optimization": dict(ZERO3[case], stage=3)}, mesh_shape=mesh.axis_sizes, world_size=4)
            shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 16), np.int32)}))
            specs = plan_param_specs(shapes, config, mesh, model.partition_rules())
            plan = overlap.plan_for(config, mesh, specs)
            assert (plan is not None) == (case != "partitioner")
            params = jax.tree_util.tree_map(lambda s, sh: S(s.shape, s.dtype, sharding=sh), shapes,
                                            specs_to_shardings(specs, mesh))
            batch = {"input_ids": S((8, 2048), I32, sharding=mesh.batch_sharding())}

            def loss(params32, batch):
                with overlap.active(plan):
                    return model.loss_fn(E._cast_tree(params32, BF16), batch, None)

            grad_shardings = specs_to_shardings(plan_grad_specs(shapes, specs, config, mesh), mesh)
            compiled = jax.jit(jax.value_and_grad(loss), out_shardings=(None, grad_shardings)).lower(params, batch).compile()
        return compiled.as_text(), compiled.memory_analysis().temp_size_in_bytes

    return step


@pytest.mark.parametrize("case", list(ZERO3))
def test_zero3_step_reduces_gradients_under_the_next_layer_or_in_the_matmuls_ring(zero3_step, case):
    """Under a plan, whatever the bound on live parameters: no hop of a
    weight-gradient matmul is left anywhere in the step and no
    reduce-scatter; 7 weights x 3 hops x 2 ways a layer are issued by the
    bucket's ring, each ``dW`` is one whole matmul, and two more rings of 3
    hops x 2 ways sum the look-up's partial rows and the head's activation
    gradient to the owner of each row of the batch (``dE`` is computed where
    it is kept). ``overlap_comm: false``: the partitioner's program, 21 hops a
    layer in the matmuls' own rings (16 x 21 + 12 = the 348 of PR 27's
    trace), 6 a program that start a ring with zeros, the head's activation
    gradient a ring of 5 hops in its matmul and the embedding's gradient one
    reduce-scatter fusion."""
    text, _ = zero3_step(case)
    said = census(text)
    in_blocks = sum(1 for op in _PERMUTE.findall(text) if "/Block_" in op and "dot_general" in op)
    if case == "partitioner":
        assert said["grad_reduce"] == "ring" and said["bucket_permutes"] == 0, said
        assert in_blocks == 21 * 2 + 6 and said["matmul_ring_permutes"] - in_blocks == 5 and said["reduce_scatters"] == 1, said
    else:
        assert said["grad_reduce"] == "bucket" and said["bucket_permutes"] == 7 * 3 * 2 * 2 + 2 * 3 * 2, said
        assert in_blocks == 0 and said["matmul_ring_permutes"] == 0 and said["reduce_scatters"] == 0, said


def test_a_layer_that_gathers_again_holds_less_than_one_that_keeps(zero3_step):
    """A bound of one block's parameters: the last layer keeps its gathered
    weights (134 MB in bf16) from its forward to its backward, the first
    gathers them a second time there, and XLA has not merged that gather with
    the forward's: the step's temporaries are smaller by most of those
    weights."""
    kept, again = zero3_step("default")[1], zero3_step("one_block")[1]
    assert 60e6 < kept - again < 2 * OLMO_BLOCK, (kept, again)


# ---------------------------------------------------------------- the casts around Adam (PR 56)

_ENTRY_INST = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (\(.*?\)|\S+) ([\w\-]+)\((.*)$")


def _entry(hlo_text):
    """{own name: (result type less its layout, opcode, operands)} of the executable's ENTRY computation."""
    insts = {}
    for line in hlo_text[hlo_text.index("\nENTRY "):].splitlines():
        m = _ENTRY_INST.match(line)
        if m:
            own, shape, opcode, rest = m.groups()
            insts[own] = (re.sub(r"\{[^}]*\}", "", shape), opcode, re.findall(r"%([\w.\-]+)", rest.split(", metadata=")[0].split(", calls=")[0]))
    return insts


def _engine_step(engine):
    """The Python function under the engine's jitted ``fused_step`` and the shapes of its state, as the engine hands them."""
    fn = engine._fused_step
    while not hasattr(fn, "lower") and hasattr(fn, "__wrapped__"):
        fn = fn.__wrapped__
    engine._compute_params()
    return fn.__wrapped__, (engine.params, engine._params_c, engine.opt_state)


def test_the_master_crosses_to_the_compute_dtype_inside_the_update_and_nowhere_else(one_chip, monkeypatch):
    """The engine's ``fused_step`` of a small routed decoder (lane-wide widths, the grouped matmul, the row sum and the
    flash kernels as custom calls, as on the chip), compiled for the described v5e. The float32 master is read by the
    update's fusions alone, which write the next step's bf16 copy beside the new master and moments: no ``convert`` of a
    whole parameter stands by itself ahead of the forward (at the parent one a leaf did), and the carried copy is what
    the forward's products read. No gradient is widened by a pass of its own either: whatever makes a float32 array of
    a weight's shape is an update fusion (it reads that leaf's moments)."""
    import types

    import deepspeed_tpu
    from deepspeed_tpu.models import CausalLM, TransformerConfig
    from deepspeed_tpu.ops.pallas import _utils
    from deepspeed_tpu.parallel.mesh import initialize_mesh, reset_mesh
    from deepspeed_tpu.runtime.config import MeshConfig

    seq = 1024
    model = CausalLM(TransformerConfig(vocab_size=1536, n_layers=2, n_heads=2, n_kv_heads=2, head_dims=128, d_model=384, max_seq_len=seq,
                                       norm="rmsnorm", activation="swiglu", pos_emb="rope", tie_embeddings=False, dtype=BF16,
                                       layer_kinds=(("full", "routed"),) * 2, moe_num_experts=8, moe_top_k=2, moe_d_ff=256,
                                       moe_shared_d_ff=256, moe_aux_loss_coef=0.0))
    params = model.init(jax.random.PRNGKey(0), {"input_ids": np.zeros((1, seq), np.int32)})
    reset_mesh()
    try:
        topo = initialize_mesh(MeshConfig.from_dict({"data": 1}), devices=jax.devices()[:1], force=True)
        engine = deepspeed_tpu.initialize(model=model, model_parameters=params, mesh=topo, config={
            "train_micro_batch_size_per_gpu": 1, "bf16": {"enabled": True}, "optimizer": {"type": "adam", "params": {"lr": 1e-4}},
            "zero_optimization": {"stage": 0}, "steps_per_print": 10**9})[0]
        step, state = _engine_step(engine)
        shapes = jax.tree_util.tree_map(lambda x: S(x.shape, x.dtype, sharding=one_chip), state)
        batch = {"input_ids": S((1, seq), I32, sharding=one_chip)}
        # the model asks the backend which form of a kernel to take, and the kernels ask the attached TPU for its VMEM
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(_utils.pltpu, "get_tpu_info", lambda: types.SimpleNamespace(vmem_capacity_bytes=128 << 20))
        text = jax.jit(step, donate_argnums=(0, 1, 2)).lower(*shapes, batch, 0, 1.0, 1.0, 1e-4).compile().as_text()
    finally:
        reset_mesh()
    assert "Some donated buffers were not usable" not in text and text.count("tpu_custom_call") >= 8
    insts = _entry(text)
    weights = {tuple(x.shape) for x in jax.tree_util.tree_leaves(state[0]) if x.ndim >= 2}
    big = lambda own, dtype: any(insts[own][0] == f"{dtype}[{','.join(map(str, w))}]" for w in weights)
    plumbing = ("parameter", "tuple", "get-tuple-element", "bitcast", "copy", "copy-start", "copy-done", "slice-start", "slice-done", "custom-call")
    masters = {own for own, (_, opcode, _) in insts.items() if opcode == "parameter" and own.startswith("params32") and big(own, "f32")}
    assert len(masters) == len([x for x in jax.tree_util.tree_leaves(state[0]) if x.ndim >= 2])
    through = set(masters)  # ... and what only hands a master on (a layout copy, a tuple's element)
    for own, (_, opcode, operands) in insts.items():  # the text lists an instruction after its operands
        if opcode in plumbing and through & set(operands):
            through.add(own)
    readers = {own for own, (_, opcode, operands) in insts.items() if opcode not in plumbing and through & set(operands)}
    kind = lambda dtype: [f"{dtype}[{','.join(map(str, w))}]" for w in weights]
    # an update fusion: the new copy, the new master and both new moments of one weight (and its share of the norm)
    update = lambda own: insts[own][1] == "fusion" and any(insts[own][0].count(f32) == 3 and bf16 in insts[own][0] for f32, bf16 in zip(kind("f32"), kind("bf16")))
    assert len(readers) >= len(masters) and all(update(own) for own in readers), {own: insts[own][:2] for own in readers if not update(own)}
    widened = {own: insts[own][:2] for own, (shape, opcode, operands) in insts.items()
               if opcode in ("convert", "fusion") and shape in kind("f32") and any(insts[o][0] == shape.replace("f32", "bf16") for o in operands if o in insts)}
    assert not widened, widened


_REDUCTION = re.compile(r"= (\(.*?\)|\S+) (?:all-reduce|reduce-scatter)(?:-start)?\(|= (\(.*?\)|\S+) fusion\(.*calls=%all-reduce-scatter")


def reduced(hlo_text, shapes):
    """{dtype: how many arrays of one of ``shapes`` the executable's all-reduces and reduce-scatters (the fused form
    too) produce}: the weight gradients' reductions across chips, by the dtype they run in. A reduce-scatter's result is
    a shard: a shape counts with any ONE dimension divided by the four chips too."""
    shards = {tuple(d // 4 if j == i else d for j, d in enumerate(s)) for s in shapes for i in range(len(s)) if s[i] % 4 == 0}
    out = {}
    for m in _REDUCTION.finditer(hlo_text):
        for dtype, dims in re.findall(r"(\w+)\[([\d,]+)\]", re.sub(r"\{[^}]*\}", "", m.group(1) or m.group(2))):
            if tuple(map(int, dims.split(","))) in set(shapes) | shards:
                out[dtype] = out.get(dtype, 0) + 1
    return out


@pytest.mark.parametrize("stage,carried", [(0, True), (1, False)])
def test_no_gradient_reduction_across_chips_runs_in_a_lower_dtype_than_the_parents(topo, monkeypatch, stage, carried):
    """``data: 4`` under the partitioner (no plan of ``zero/overlap.py``), the engine's ``fused_step`` and ``fwd_bwd``
    compiled for the four described chips. The partitioner reduces a weight's gradient where its product makes the
    partial sums, in the product's dtype: bf16 for the decoder's matmuls, float32 for the tied embedding (its two
    cotangents are summed first) and the norms' scales; the counts below are the PARENT's executables' (compiled from its
    tree at PR 56), where the step differentiated at the master through the cast. Stage 0 carries the compute copy
    (every chip updates the whole state); at stage 1 the update runs on a shard of the state and its results are
    all-gathered, so nothing is carried and no bf16 copy is gathered beside the master."""
    import deepspeed_tpu
    from deepspeed_tpu.models import CausalLM, TransformerConfig
    from deepspeed_tpu.parallel import mesh as mesh_mod
    from deepspeed_tpu.parallel.mesh import MeshTopology, initialize_mesh, reset_mesh
    from deepspeed_tpu.runtime.config import MeshConfig

    model = CausalLM(TransformerConfig(vocab_size=384, n_layers=2, n_heads=2, d_model=256, max_seq_len=128, dtype=BF16, tie_embeddings=True))
    params = model.init(jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 16), np.int32)})
    reset_mesh()
    try:
        here = initialize_mesh(MeshConfig.from_dict({"data": 4}), devices=jax.devices()[:4], force=True)
        engine = deepspeed_tpu.initialize(model=model, model_parameters=params, mesh=here, config={
            "train_micro_batch_size_per_gpu": 2, "bf16": {"enabled": True}, "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": stage}, "steps_per_print": 10**9})[0]
        step, state = _engine_step(engine)
        assert (state[1] is not None) == carried
        there = MeshTopology(MeshConfig.from_dict({"data": 4}), devices=list(topo.devices))
        monkeypatch.setattr(mesh_mod, "_TOPOLOGY", there)  # what the model asks for its mesh while it is traced
        move = lambda tree: jax.tree_util.tree_map(lambda sh: jax.sharding.NamedSharding(there.mesh, sh.spec), tree)
        shapes = jax.tree_util.tree_map(lambda x: S(x.shape, x.dtype, sharding=move(x.sharding)), state)
        batch = {"input_ids": S((8, 96), I32, sharding=there.batch_sharding())}  # 768 rows: no activation has a weight's shape
        outs = (None, move(engine.param_shardings), move(engine.param_shardings) if carried else None, move(engine.opt_state_shardings), None, None)
        fused = jax.jit(step, donate_argnums=(0, 1, 2), out_shardings=outs).lower(*shapes, batch, 0, 1.0, 1.0, 1e-3).compile().as_text()
        fwd_bwd = engine._fwd_bwd
        while not hasattr(fwd_bwd, "lower"):
            fwd_bwd = fwd_bwd.__wrapped__
        split = jax.jit(fwd_bwd.__wrapped__, out_shardings=(None, move(engine.grad_shardings))).lower(
            shapes[1] if carried else shapes[0], batch, 0, 1.0).compile().as_text()
    finally:
        reset_mesh()
    weights = {tuple(x.shape) for x in jax.tree_util.tree_leaves(state[0])}
    assert (reduced(fused, weights), reduced(split, weights)) == PARENTS_REDUCTIONS[stage]
    # (the tied embedding's rows are gathered in bf16 for the look-up and the head at the parent too: not the copy)
    gathered = [shape for shape in re.findall(r"= (\S+) all-gather(?:-start)?\(", fused) if shape.startswith("bf16")]
    assert not [s for s in gathered if any(s.startswith(f"bf16[{','.join(map(str, w))}]") for w in weights if len(w) >= 2 and w[0] != 384)], gathered


# (fused_step, fwd_bwd) by stage: _scratch-style, the parent's tree through this file's ``reduced`` (PR 56)
PARENTS_REDUCTIONS = {0: ({"bf16": 37}, {"bf16": 37}), 1: ({"f32": 16, "bf16": 49}, {"bf16": 37})}
