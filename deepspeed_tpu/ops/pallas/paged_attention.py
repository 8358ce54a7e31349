"""Paged (block-table) KV-cache attention for ragged serving.

Parity: reference ``inference/v2/kernels/ragged_ops/`` — the FastGen
CUDA suite (blocked flash attention over a paged KV cache, KV copy with
rotary, ``linear_blocked_kv_rotary/``). TPU re-design:

- KV pages are a flat pool ``(num_blocks, block_size, KVH, D)`` per layer;
  a per-batch ``block_table`` maps (sequence, page-slot) -> pool block.
- Decode (one query token per sequence) runs a Pallas kernel with the
  block table as a scalar-prefetch operand: the grid walks (batch, page)
  and the page index_map dereferences the table, so only live pages are
  streamed from HBM — the paged analogue of flash attention's online
  softmax.
- Chunked prefill runs the same page-walking kernel shape with a whole
  query block per sequence (``paged_attention_prefill``); the gather-based
  XLA path remains as reference/fallback (TP-sharded bias models, CPU).

New KV entries are written with ``update_kv_pages`` via a flat
"slot mapping" (token -> block*block_size+offset), computed host-side by
the engine.

int8 paged KV (``kv_quant_bits=8``): a pool is the pytree
``(codes int8 (N, bs, KVH, D), scales f32 (N, bs, KVH))`` — one symmetric
per-slot-per-head scale, i.e. per-block (bs, KVH) scale planes. Scales are
per *slot* rather than one scalar per block-head so quantize-on-append and
spec-decode rollback stay local: overwriting a slot rewrites its scale and
never re-quantizes neighbours, so dequantized history is independent of
rejected drafts. Every entry point below accepts either representation;
the Pallas kernels fuse the dequant in VMEM following the
``quantized_matmul.py`` idiom (int8 stream from HBM, ``codes * scale`` next
to the dot).
"""

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...utils.logging import logger
from ._utils import compiler_params as _compiler_params

NEG_INF = -1e30


# ------------------------------------------------------------------
# int8 pool representation
# ------------------------------------------------------------------
def kv_pool_is_quantized(pool) -> bool:
    """True when ``pool`` is the int8 ``(codes, scales)`` pytree."""
    return isinstance(pool, tuple)


def kv_pool_shape(pool) -> Tuple[int, ...]:
    """(..., bs, KVH, D) of a pool, plain array or ``(codes, scales)``."""
    return (pool[0] if isinstance(pool, tuple) else pool).shape


def make_kv_pool(shape: Tuple[int, ...], dtype, kv_quant_bits: int = 0):
    """Allocate one KV page pool of ``shape`` = (..., bs, KVH, D): a plain
    array, or at ``kv_quant_bits=8`` the ``(int8 codes, f32 scales)`` pair
    with per-slot-per-head scale planes ``shape[:-1]``."""
    if kv_quant_bits == 8:
        return (jnp.zeros(shape, jnp.int8), jnp.zeros(shape[:-1], jnp.float32))
    if kv_quant_bits:
        raise ValueError(f"kv_quant_bits must be 0 or 8, got {kv_quant_bits}")
    return jnp.zeros(shape, dtype)


def kv_pool_shard_spec(pool_or_ndim, axis: str = "tensor"):
    """PartitionSpec sharding a stacked ``(L, blocks, bs, KVH, D)`` pool
    over its KV-head axis for tensor-parallel serving: heads split on
    ``axis``, every other dim (layers, blocks, slots, head_dim) replicated
    so the block table stays global. Accepts a pool (plain array or the
    int8 ``(codes, scales)`` pair — NOT supported yet, the engine refuses
    that combination) or an ndim."""
    from jax.sharding import PartitionSpec as P
    ndim = pool_or_ndim if isinstance(pool_or_ndim, int) else \
        len(kv_pool_shape(pool_or_ndim))
    spec = [None] * ndim
    spec[-2] = axis  # the KVH axis
    return P(*spec)


def shard_kv_pool(pool, mesh, axis: str = "tensor"):
    """Place a pool on ``mesh`` with KV heads sharded over ``axis`` —
    the head-sharded routing every paged kernel then inherits: each shard's
    dispatch sees a shard-local KVH slice of the same global block ids, so
    the kernels need no TP awareness at all (they read KVH off the array)."""
    from jax.sharding import NamedSharding
    if isinstance(pool, tuple):  # int8 (codes, scales): gated off upstream
        raise NotImplementedError("int8 KV pools do not shard over the tensor axis yet")
    return jax.device_put(pool, NamedSharding(mesh, kv_pool_shard_spec(pool.ndim, axis)))


def quantize_kv(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric per-(slot, kv-head) int8: (..., KVH, D) -> codes of the
    same shape + f32 scales (..., KVH). ``quantize_weight_kgroups`` idiom:
    all-zero rows keep scale 1.0 so dequant is exact there too."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scales = jnp.where(amax == 0, 1.0, amax / 127.0)
    codes = jnp.clip(jnp.round(xf / scales[..., None]), -128, 127).astype(jnp.int8)
    return codes, scales


def dequantize_kv(pool) -> jnp.ndarray:
    """f32 view of an int8 ``(codes, scales)`` pool (oracle/debug path)."""
    codes, scales = pool
    return codes.astype(jnp.float32) * scales[..., None]


def kv_layer(pool, i: int):
    """Per-layer slice of a stacked (L, ...) pool, plain or quantized."""
    if isinstance(pool, tuple):
        return tuple(p[i] for p in pool)
    return pool[i]


def kv_set_layer(pool, i: int, new):
    """Functional per-layer write-back, the ``pool.at[i].set(new)`` of
    both representations."""
    if isinstance(pool, tuple):
        return tuple(p.at[i].set(n) for p, n in zip(pool, new))
    return pool.at[i].set(new)


# ------------------------------------------------------------------
# KV page update
# ------------------------------------------------------------------
def update_kv_pages(k_pages, v_pages, k_new: jnp.ndarray, v_new: jnp.ndarray,
                    slot_mapping: jnp.ndarray):
    """Scatter new KV entries into the page pool.

    k_pages/v_pages: (N, bs, KVH, D) — or the quantized ``(codes, scales)``
    pair, in which case the new entries are quantized on append, in-graph;
    k_new/v_new: (T, KVH, D); slot_mapping: (T,) int32 flat slot =
    block_id * bs + offset.
    """
    if isinstance(k_pages, tuple):
        (kc, ks), (vc, vs) = k_pages, v_pages
        n, bs, kvh, d = kc.shape
        k_q, k_s = quantize_kv(k_new)
        v_q, v_s = quantize_kv(v_new)
        kc = kc.reshape(n * bs, kvh, d).at[slot_mapping].set(k_q).reshape(n, bs, kvh, d)
        vc = vc.reshape(n * bs, kvh, d).at[slot_mapping].set(v_q).reshape(n, bs, kvh, d)
        ks = ks.reshape(n * bs, kvh).at[slot_mapping].set(k_s).reshape(n, bs, kvh)
        vs = vs.reshape(n * bs, kvh).at[slot_mapping].set(v_s).reshape(n, bs, kvh)
        return (kc, ks), (vc, vs)
    n, bs, kvh, d = k_pages.shape
    flat_k = k_pages.reshape(n * bs, kvh, d)
    flat_v = v_pages.reshape(n * bs, kvh, d)
    flat_k = flat_k.at[slot_mapping].set(k_new.astype(flat_k.dtype))
    flat_v = flat_v.at[slot_mapping].set(v_new.astype(flat_v.dtype))
    return flat_k.reshape(n, bs, kvh, d), flat_v.reshape(n, bs, kvh, d)


# ------------------------------------------------------------------
# Gather-based reference path (prefill + CPU fallback)
# ------------------------------------------------------------------
def paged_attention_ref(q: jnp.ndarray, k_pages: jnp.ndarray, v_pages: jnp.ndarray, block_tables: jnp.ndarray,
                        ctx_lens: jnp.ndarray, q_positions: jnp.ndarray, scale: Optional[float] = None,
                        alibi_slopes: Optional[jnp.ndarray] = None,
                        window: Optional[int] = None) -> jnp.ndarray:
    """Causal attention of q against paged context.

    q: (B, S, H, D); block_tables: (B, P); ctx_lens: (B,) total context
    (incl. the S new tokens); q_positions: (B, S) absolute positions.
    ``alibi_slopes``: optional (H,) per-head slopes — adds the
    shift-invariant ALiBi bias ``slope_h * key_position`` (bloom serving).
    ``window``: sliding-window width (mistral serving).
    Returns (B, S, H, D).
    """
    B, S, H, D = q.shape
    _, bs, KVH, _ = kv_pool_shape(k_pages)
    P = block_tables.shape[1]
    G = H // KVH
    scale = scale if scale is not None else D**-0.5

    if isinstance(k_pages, tuple):
        # gather int8 codes + scale planes for the live pages only, then
        # dequantize the (small) dense view — the oracle the kernels chase
        (kc, ksc), (vc, vsc) = k_pages, v_pages
        k = (kc[block_tables].reshape(B, P * bs, KVH, D).astype(jnp.float32)
             * ksc[block_tables].reshape(B, P * bs, KVH)[..., None])
        v = (vc[block_tables].reshape(B, P * bs, KVH, D).astype(jnp.float32)
             * vsc[block_tables].reshape(B, P * bs, KVH)[..., None])
    else:
        k = k_pages[block_tables].reshape(B, P * bs, KVH, D)  # (B, L, KVH, D)
        v = v_pages[block_tables].reshape(B, P * bs, KVH, D)
    L = P * bs

    qf = q.astype(jnp.float32).reshape(B, S, KVH, G, D) * scale
    s = jnp.einsum("bskgd,blkd->bskgl", qf, k.astype(jnp.float32))
    key_pos = jnp.arange(L, dtype=jnp.int32)[None, None, None, None, :]
    if alibi_slopes is not None:
        sl = jnp.asarray(alibi_slopes, jnp.float32).reshape(KVH, G)
        s = s + sl[None, None, :, :, None] * key_pos.astype(jnp.float32)
    valid = (key_pos < ctx_lens[:, None, None, None, None]) & (key_pos <= q_positions[:, :, None, None, None])
    if window is not None:
        valid = valid & (key_pos > q_positions[:, :, None, None, None] - window)
    s = jnp.where(valid, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bskgl,blkd->bskgd", p, v.astype(jnp.float32))
    return out.reshape(B, S, H, D).astype(q.dtype)


# ------------------------------------------------------------------
# Mixed decode+prefill dispatch (SplitFuse fused serving step)
# ------------------------------------------------------------------
def paged_attention_mixed(q: jnp.ndarray, k_pages: jnp.ndarray, v_pages: jnp.ndarray,
                          block_tables: jnp.ndarray, ctx_lens: jnp.ndarray, q_positions: jnp.ndarray, *,
                          n_dec: int, chunk: int, scale: Optional[float] = None,
                          alibi_slopes=None, window: Optional[int] = None,
                          decode_fn=None, prefill_fn=None, native: bool = True) -> jnp.ndarray:
    """Serve decode rows and chunked-prefill rows from the paged pool in
    one attention pass of a single traced program.

    q: (T, H, D) flat query tokens — rows [0, n_dec) are single-token
    decode rows; the remainder is the (n_pre, chunk) prefill segment,
    row-major. block_tables/ctx_lens are per-ROW with N = n_dec + n_pre
    (decode rows first); q_positions: (T,) absolute positions (decode
    rows sit at ctx - 1). Returns (T, H, D).

    The shapes unify into ONE kernel launch when either segment is empty
    or when ``chunk == 1`` (a one-token prefill chunk queries at ctx - 1,
    which is exactly the decode contract); otherwise the decode and
    prefill kernels launch back to back inside the caller's jitted
    program — still a single host dispatch either way.

    ``decode_fn``/``prefill_fn``: pre-bound kernel variants (ALiBi/window
    baked when ``native``); falls back to the gather reference otherwise,
    mirroring the v2 attention module's routing.
    """
    T, H, D = q.shape
    n_pre = (T - n_dec) // chunk if chunk else 0
    plain = alibi_slopes is None and window is None
    sl = jnp.asarray(alibi_slopes, jnp.float32) if alibi_slopes is not None else None

    def run_decode(qd, bt, cl):
        if decode_fn is not None and (native or plain):
            return decode_fn(qd, k_pages, v_pages, bt, cl)
        return paged_attention_ref(qd[:, None], k_pages, v_pages, bt, cl, (cl - 1)[:, None],
                                   scale, alibi_slopes=sl, window=window)[:, 0]

    def run_prefill(qp, bt, cl, pos):
        if prefill_fn is not None and (native or plain):
            return prefill_fn(qp, k_pages, v_pages, bt, cl, pos)
        return paged_attention_ref(qp, k_pages, v_pages, bt, cl, pos, scale,
                                   alibi_slopes=sl, window=window)

    if n_pre == 0 or chunk == 1:
        # pure decode, or every prefill row is a single token at ctx - 1:
        # ONE decode launch covers the whole batch
        return run_decode(q, block_tables, ctx_lens)
    if n_dec == 0:
        qp = q.reshape(n_pre, chunk, H, D)
        return run_prefill(qp, block_tables, ctx_lens,
                           q_positions.reshape(n_pre, chunk)).reshape(T, H, D)
    o_dec = run_decode(q[:n_dec], block_tables[:n_dec], ctx_lens[:n_dec])
    qp = q[n_dec:].reshape(n_pre, chunk, H, D)
    o_pre = run_prefill(qp, block_tables[n_dec:], ctx_lens[n_dec:],
                        q_positions[n_dec:].reshape(n_pre, chunk))
    return jnp.concatenate([o_dec, o_pre.reshape(n_pre * chunk, H, D)], axis=0)


# ------------------------------------------------------------------
# Pallas decode kernel
# ------------------------------------------------------------------
def _decode_kernel(block_tables_ref, ctx_lens_ref, q_ref, k_ref, v_ref, *rest,
                   bs: int, kvh: int, g: int, d: int, pages: int, scale: float, has_alibi: bool = False,
                   window: int = 0, quantized: bool = False):
    if quantized:  # extra per-block (bs, KVH) scale-plane operands
        ks_ref, vs_ref, slopes_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        ks_ref = vs_ref = None
        slopes_ref, o_ref, acc_ref, m_ref, l_ref = rest
    b = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    ctx = ctx_lens_ref[b]
    start = p * bs
    live = start < ctx
    if window > 0:  # query sits at ctx-1: pages fully before the band skip
        live = live & (start + bs > ctx - window)

    @pl.when(live)
    def _compute():
        # NOTE: the head dim is a STATIC python loop of 2D matmuls — Mosaic's
        # compiler crashes on batched 3D dots ("kgd,tkd->kgt"), bisected on
        # hardware in round 3. Decode is HBM-bound; skinny dots are fine.
        pos = start + jax.lax.broadcasted_iota(jnp.int32, (g, bs), 1)
        valid = pos < ctx
        if window > 0:
            valid = valid & (pos > ctx - 1 - window)
        for h in range(kvh):
            qh = q_ref[0, pl.dslice(h * g, g), :].astype(jnp.float32) * scale  # (g, d)
            kh = k_ref[0, :, h, :].astype(jnp.float32)  # (bs, d)
            vh = v_ref[0, :, h, :].astype(jnp.float32)
            if quantized:  # fused dequant in VMEM: int8 stream * per-slot scale
                kh = kh * ks_ref[0, :, h][:, None]
                vh = vh * vs_ref[0, :, h][:, None]
            s = jax.lax.dot_general(qh, kh, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)  # (g, bs)
            if has_alibi:
                sl = slopes_ref[pl.dslice(h * g, g), 0]  # (g,)
                s = s + sl[:, None] * pos.astype(jnp.float32)
            s = jnp.where(valid, s, NEG_INF)
            m_prev = m_ref[h]  # (g,)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
            alpha = jnp.exp(m_prev - m_new)
            pij = jnp.exp(s - m_new[:, None])
            l_ref[h] = l_ref[h] * alpha + jnp.sum(pij, axis=-1)
            acc_ref[h] = acc_ref[h] * alpha[:, None] + jax.lax.dot_general(
                pij, vh, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    @pl.when(p == pages - 1)
    def _finish():
        for h in range(kvh):
            l = l_ref[h]
            l = jnp.where(l == 0.0, 1.0, l)
            o_ref[0, pl.dslice(h * g, g), :] = (acc_ref[h] / l[:, None]).astype(o_ref.dtype)


def paged_attention_decode(q: jnp.ndarray, k_pages: jnp.ndarray, v_pages: jnp.ndarray, block_tables: jnp.ndarray,
                           ctx_lens: jnp.ndarray, scale: Optional[float] = None,
                           interpret: bool = False, alibi_slopes=None,
                           window: Optional[int] = None) -> jnp.ndarray:
    """One-token-per-sequence paged attention.

    q: (B, H, D); k_pages/v_pages: (N, bs, KVH, D); block_tables: (B, P);
    ctx_lens: (B,). ``alibi_slopes``: static per-head slopes (bloom);
    ``window``: static sliding-window width (mistral) — both are baked into
    the kernel at trace time. Returns (B, H, D). Rows with ctx_len == 0
    (padding) produce unspecified output.
    """
    B, H, D = q.shape
    N, bs, KVH, _ = kv_pool_shape(k_pages)
    P = block_tables.shape[1]
    G = H // KVH
    scale = scale if scale is not None else D**-0.5
    has_alibi = alibi_slopes is not None
    quantized = isinstance(k_pages, tuple)

    slopes_in = (jnp.broadcast_to(jnp.asarray(alibi_slopes, jnp.float32).reshape(H, 1), (H, 128))
                 if has_alibi else jnp.zeros((H, 128), jnp.float32))
    kernel = functools.partial(_decode_kernel, bs=bs, kvh=KVH, g=G, d=D, pages=P, scale=scale,
                               has_alibi=has_alibi, window=int(window or 0), quantized=quantized)
    page_spec = pl.BlockSpec((1, bs, KVH, D), lambda b, p, bt, cl: (bt[b, p], 0, 0, 0))
    scale_spec = pl.BlockSpec((1, bs, KVH), lambda b, p, bt, cl: (bt[b, p], 0, 0))
    in_specs = [pl.BlockSpec((1, H, D), lambda b, p, bt, cl: (b, 0, 0)), page_spec, page_spec]
    if quantized:
        in_specs += [scale_spec, scale_spec]
        operands = (q, k_pages[0], v_pages[0], k_pages[1], v_pages[1], slopes_in)
    else:
        operands = (q, k_pages, v_pages, slopes_in)
    in_specs.append(pl.BlockSpec((H, 128), lambda b, p, bt, cl: (0, 0)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, P),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, H, D), lambda b, p, bt, cl: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((KVH, G, D), jnp.float32),
            pltpu.VMEM((KVH, G), jnp.float32),
            pltpu.VMEM((KVH, G), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        interpret=interpret,
        compiler_params=_compiler_params("parallel", "arbitrary", interpret=interpret),
    )(block_tables, ctx_lens, *operands)


# ------------------------------------------------------------------
# Pallas chunked-prefill kernel
# ------------------------------------------------------------------
def _prefill_kernel(block_tables_ref, ctx_lens_ref, qpos0_ref, q_ref, k_ref, v_ref, *rest,
                    bs: int, s_q: int, kvh: int, g: int, d: int, pages: int, scale: float,
                    has_alibi: bool = False, window: int = 0, quantized: bool = False):
    """Grid (B, pages): stream the live pages of one sequence past a whole
    chunk of S_q query tokens with online softmax — the prefill sibling of
    ``_decode_kernel`` (reference blocked_flash over the paged pool).
    ``qpos0`` is each sequence's absolute position of query row 0 (chunked
    prefill continues a partially-written context). Per-kv-head rows are
    flattened to 2D (s_q*g, ...) — see the Mosaic 3D-dot note in
    ``_decode_kernel``."""
    if quantized:  # extra per-block (bs, KVH) scale-plane operands
        ks_ref, vs_ref, slopes_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        ks_ref = vs_ref = None
        slopes_ref, o_ref, acc_ref, m_ref, l_ref = rest
    b = pl.program_id(0)
    p = pl.program_id(1)
    sg = s_q * g

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    ctx = ctx_lens_ref[b]
    q0 = qpos0_ref[b]
    start = p * bs
    live = start < ctx
    if window > 0:  # every query row's band ends at its own position; the
        # earliest key any row can see is q0 - window + 1
        live = live & (start + bs > q0 - window + 1)

    @pl.when(live)
    def _compute():
        # flattened row r = s_idx * g + g_idx (row-major (s_q, g) collapse)
        rows_s = jax.lax.broadcasted_iota(jnp.int32, (sg, bs), 0) // g
        pos = start + jax.lax.broadcasted_iota(jnp.int32, (sg, bs), 1)
        qpos = q0 + rows_s
        valid = (pos < ctx) & (pos <= qpos)  # causal against absolute positions
        if window > 0:
            valid = valid & (pos > qpos - window)
        for h in range(kvh):
            qh = q_ref[0, :, pl.dslice(h * g, g), :].reshape(sg, d).astype(jnp.float32) * scale
            kh = k_ref[0, :, h, :].astype(jnp.float32)  # (bs, d)
            vh = v_ref[0, :, h, :].astype(jnp.float32)
            if quantized:  # fused dequant in VMEM: int8 stream * per-slot scale
                kh = kh * ks_ref[0, :, h][:, None]
                vh = vh * vs_ref[0, :, h][:, None]
            s = jax.lax.dot_general(qh, kh, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)  # (sg, bs)
            if has_alibi:
                if g == 1:
                    # scalar slope: a (1,) vector source becomes an illegal
                    # both-dims broadcast in Mosaic ("sublanes and lanes")
                    s = s + slopes_ref[h, 0] * pos.astype(jnp.float32)
                else:
                    sl = slopes_ref[pl.dslice(h * g, g), 0]  # (g,) -> per-row g_idx = r % g
                    sl_rows = jnp.broadcast_to(sl[None, :], (s_q, g)).reshape(sg, 1)
                    s = s + sl_rows * pos.astype(jnp.float32)
            s = jnp.where(valid, s, NEG_INF)
            m_prev = m_ref[h]  # (sg,)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
            alpha = jnp.exp(m_prev - m_new)
            pij = jnp.exp(s - m_new[:, None])
            pij = jnp.where(s <= NEG_INF, 0.0, pij)  # rows with no visible key yet
            l_ref[h] = l_ref[h] * alpha + jnp.sum(pij, axis=-1)
            acc_ref[h] = acc_ref[h] * alpha[:, None] + jax.lax.dot_general(
                pij, vh, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    @pl.when(p == pages - 1)
    def _finish():
        for h in range(kvh):
            l = l_ref[h]
            l = jnp.where(l == 0.0, 1.0, l)
            o_ref[0, :, pl.dslice(h * g, g), :] = (acc_ref[h] / l[:, None]).reshape(s_q, g, d).astype(o_ref.dtype)


PREFILL_MAX_CHUNK = 512
PREFILL_MAX_ACC_BYTES = 6 * 2**20


def prefill_path(S: int, H: int, D: int) -> str:
    """Which implementation ``paged_attention_prefill`` takes for a chunk of
    ``S`` query tokens: "kernel", or "gather" (the XLA reference) when the
    fp32 (H, S, D) accumulator would not fit VMEM."""
    return "gather" if S > PREFILL_MAX_CHUNK or H * S * D * 4 > PREFILL_MAX_ACC_BYTES else "kernel"


def paged_attention_prefill(q: jnp.ndarray, k_pages: jnp.ndarray, v_pages: jnp.ndarray,
                            block_tables: jnp.ndarray, ctx_lens: jnp.ndarray, q_positions: jnp.ndarray,
                            scale: Optional[float] = None, interpret: bool = False, alibi_slopes=None,
                            window: Optional[int] = None) -> jnp.ndarray:
    """Chunked-prefill attention of a whole query block against the paged
    context, never gathering pages into a dense (B, L, KVH, D) tensor.

    q: (B, S, H, D) new tokens (S static); q_positions: (B, S) absolute,
    consecutive per row; ctx_lens: (B,) total context incl. the new tokens.
    Chunks too long for the VMEM accumulator take the gather reference
    (logged once per traced shape). Returns (B, S, H, D).
    """
    B, S, H, D = q.shape
    N, bs, KVH, _ = kv_pool_shape(k_pages)
    P = block_tables.shape[1]
    G = H // KVH
    scale = scale if scale is not None else D**-0.5
    has_alibi = alibi_slopes is not None
    quantized = isinstance(k_pages, tuple)

    # the fp32 accumulator scratch is (KVH, G, S, D) — VMEM scales linearly
    # with the chunk length, so long un-chunked prompts (engine put() prefills
    # whole prompts) take the gather path rather than overflow VMEM
    if prefill_path(S, H, D) == "gather":
        logger.info(f"paged_attention_prefill: q {q.shape} exceeds the kernel's chunk limit "
                    f"(S <= {PREFILL_MAX_CHUNK}, accumulator <= {PREFILL_MAX_ACC_BYTES >> 20} MiB) "
                    "— taking the XLA gather path")
        sl = jnp.asarray(alibi_slopes, jnp.float32) if has_alibi else None
        return paged_attention_ref(q, k_pages, v_pages, block_tables, ctx_lens, q_positions, scale,
                                   alibi_slopes=sl, window=window)

    qpos0 = q_positions[:, 0].astype(jnp.int32)
    slopes_in = (jnp.broadcast_to(jnp.asarray(alibi_slopes, jnp.float32).reshape(H, 1), (H, 128))
                 if has_alibi else jnp.zeros((H, 128), jnp.float32))
    kernel = functools.partial(_prefill_kernel, bs=bs, s_q=S, kvh=KVH, g=G, d=D, pages=P, scale=scale,
                               has_alibi=has_alibi, window=int(window or 0), quantized=quantized)
    page_spec = pl.BlockSpec((1, bs, KVH, D), lambda b, p, bt, cl, q0: (bt[b, p], 0, 0, 0))
    scale_spec = pl.BlockSpec((1, bs, KVH), lambda b, p, bt, cl, q0: (bt[b, p], 0, 0))
    in_specs = [pl.BlockSpec((1, S, H, D), lambda b, p, bt, cl, q0: (b, 0, 0, 0)), page_spec, page_spec]
    if quantized:
        in_specs += [scale_spec, scale_spec]
        operands = (q, k_pages[0], v_pages[0], k_pages[1], v_pages[1], slopes_in)
    else:
        operands = (q, k_pages, v_pages, slopes_in)
    in_specs.append(pl.BlockSpec((H, 128), lambda b, p, bt, cl, q0: (0, 0)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, P),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, S, H, D), lambda b, p, bt, cl, q0: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((KVH, S * G, D), jnp.float32),
            pltpu.VMEM((KVH, S * G), jnp.float32),
            pltpu.VMEM((KVH, S * G), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, S, H, D), q.dtype),
        interpret=interpret,
        compiler_params=_compiler_params("parallel", "arbitrary", interpret=interpret),
    )(block_tables, ctx_lens, qpos0, *operands)
