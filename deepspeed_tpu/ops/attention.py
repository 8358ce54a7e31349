"""Attention ops.

The XLA path here is the always-available reference implementation; the
Pallas flash kernel (``ops/pallas/flash_attention.py``) registers itself at
higher priority when a real TPU backend is present. Capability parity:
reference fused attention kernels (``csrc/transformer``,
``csrc/transformer/inference``) and sparse attention (``ops/sparse_attention``).
"""

from typing import Optional

import jax
import jax.numpy as jnp

from ..telemetry.tracing import region
from . import masks, placement
from .registry import get_op, register_op


def _count_xla(q, v, record, count_as):
    """XLA's forms count themselves as the flash kernels do: ``path="xla"`` under the caller's words or the ``op`` the kernels derive."""
    placement.count(path="xla", **(count_as or {"op": masks.counted_op(record, v.shape[-1] != q.shape[-1])}))


def _repeat_kv(k: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    """Expand KV heads for grouped-query attention: (B,S,Hkv,D) -> (B,S,Hkv*n_rep,D)."""
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(b, s, h * n_rep, d)


@register_op("attention", "xla", priority=0)
def attention_xla(q: jnp.ndarray,
                  k: jnp.ndarray,
                  v: jnp.ndarray,
                  *,
                  causal: bool = True,
                  scale: Optional[float] = None,
                  bias: Optional[jnp.ndarray] = None,
                  segment_ids: Optional[jnp.ndarray] = None,
                  kv_len=None,
                  window: Optional[int] = None,
                  alibi_slopes: Optional[jnp.ndarray] = None,
                  mask=None,
                  count_as: Optional[dict] = None) -> jnp.ndarray:
    """Multi-head attention, shapes (B, S, H, D) / KV may have fewer heads (GQA).

    ``mask``: a record of ``ops/masks.py`` in place of ``causal`` / ``window``
    (which are its two oldest instances): the flash kernels, this form and the
    chunked one apply the one elementwise test, ``mask.keep(rows, cols)``.
    ``kv_len``: number of valid KV positions (for padded decode caches) —
    queries are placed at absolute positions [kv_len - sq, kv_len).
    ``window``: sliding-window width (mistral): query i attends keys in
    (i - window, i].
    ``alibi_slopes``: (H,) per-head slopes — shift-invariant ALiBi bias
    ``slope_h * key_position`` (bloom).
    ``count_as``: the caller's words for the count of this call site (``_count_xla``).
    Computed in fp32 accumulation regardless of input dtype (softmax
    numerics), returned in the input dtype. XLA fuses the whole block.
    """
    if window is not None and window < 1:
        raise ValueError(f"window must be >= 1 (got {window}); pass None to disable the sliding window")
    orig_dtype = q.dtype
    n_rep = q.shape[2] // k.shape[2]
    k = _repeat_kv(k, n_rep)
    v = _repeat_kv(v, n_rep)
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (d**0.5)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    if alibi_slopes is not None:
        # slopes are fixed constants (non-differentiable on every backend —
        # the Pallas kernel returns a zero cotangent for them too)
        sl = jax.lax.stop_gradient(jnp.asarray(alibi_slopes, jnp.float32))
        key_pos = jnp.arange(k.shape[1], dtype=jnp.float32)
        logits = logits + sl[None, :, None, None] * key_pos[None, None, None, :]
    if bias is not None:
        logits = logits + bias
    sq, sk = q.shape[1], k.shape[1]
    # window means '(i - window, i]': it implies the causal upper bound even when causal=False, matching the flash kernel
    record = mask if mask is not None else masks.of(causal, window)
    _count_xla(q, v, record, count_as)
    if record.masks or kv_len is not None:
        # offset supports decode where q is a suffix of the (valid) kv sequence
        valid = kv_len if kv_len is not None else sk
        offset = valid - sq
        qi = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 0) + offset
        ki = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        kept = ki < valid
        if record.masks:
            kept = kept & record.keep(qi, ki)
        logits = jnp.where(kept[None, None], logits, jnp.finfo(jnp.float32).min)
    if segment_ids is not None:
        seg_q, seg_k = segment_ids if isinstance(segment_ids, tuple) else (segment_ids, segment_ids)
        same = seg_q[:, :, None] == seg_k[:, None, :]
        logits = jnp.where(same[:, None], logits, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
    return out.astype(orig_dtype)


@register_op("attention", "chunked", priority=-1)
def attention_chunked(q: jnp.ndarray,
                      k: jnp.ndarray,
                      v: jnp.ndarray,
                      *,
                      causal: bool = True,
                      scale: Optional[float] = None,
                      bias: Optional[jnp.ndarray] = None,
                      segment_ids: Optional[jnp.ndarray] = None,
                      kv_len=None,
                      window: Optional[int] = None,
                      alibi_slopes: Optional[jnp.ndarray] = None,
                      chunk: int = 512,
                      mask=None,
                      count_as: Optional[dict] = None) -> jnp.ndarray:
    """Online-softmax attention over KV chunks — O(S·chunk) peak memory.

    The pure-XLA analogue of the flash kernel's memory behaviour (reference
    fused softmax, ``csrc/transformer/inference/csrc/softmax.cu``): logits
    never materialize as a full (B,H,Sq,Sk) block, only one (B,H,Sq,chunk)
    tile per scan step, and the scan body is rematted so backward re-forms
    each tile instead of saving them all. Numerically matches
    :func:`attention_xla` (fp32 accumulation, same masking contract).

    Used as the fallback for long sequences where the Pallas kernel is
    unavailable, and by the AOT memory audit so CPU compiles reflect the
    TPU kernel's memory profile rather than the quadratic XLA fallback.
    """
    if segment_ids is not None:
        # packing: take the materializing oracle
        return attention_xla(q, k, v, causal=causal, scale=scale, bias=bias, segment_ids=segment_ids,
                             kv_len=kv_len, window=window, alibi_slopes=alibi_slopes, mask=mask, count_as=count_as)
    if window is not None and window < 1:
        raise ValueError(f"window must be >= 1 (got {window}); pass None to disable the sliding window")
    record = mask if mask is not None else masks.of(causal, window)
    _count_xla(q, v, record, count_as)
    orig_dtype = q.dtype
    n_rep = q.shape[2] // k.shape[2]
    k = _repeat_kv(k, n_rep)
    v = _repeat_kv(v, n_rep)
    b, sq, h, d = q.shape
    sk = k.shape[1]
    scale = scale if scale is not None else 1.0 / (d**0.5)
    c = min(chunk, sk)
    n_chunks = -(-sk // c)
    pad = n_chunks * c - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    valid = kv_len if kv_len is not None else sk
    offset = valid - sq  # query absolute positions [valid - sq, valid)
    qf = q.astype(jnp.float32) * scale
    # no upcast of K: qf is fp32, so each tile's einsum promotes per chunk —
    # a whole-sequence fp32 K copy would defeat the op's memory contract
    kc = k.reshape(b, n_chunks, c, h, d).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, n_chunks, c, h, d).transpose(1, 0, 2, 3, 4)
    qi = jnp.arange(sq, dtype=jnp.int32) + offset  # (sq,) absolute
    sl = None if alibi_slopes is None else jax.lax.stop_gradient(
        jnp.asarray(alibi_slopes, jnp.float32))

    if bias is not None:
        # (B,H,Sq,Sk)-broadcastable additive bias, sliced per chunk inside
        # the rematted body: a broadcast view fuses with the slice, so the
        # expanded bias never materializes in the forward pass
        bias_full = jnp.broadcast_to(bias, (b, h, sq, sk))
        if pad:
            bias_full = jnp.pad(bias_full, ((0, 0), (0, 0), (0, 0), (0, pad)))

    def body(carry, inp):
        acc, m, denom = carry  # (b,h,sq,d) f32, (b,h,sq), (b,h,sq)
        kcb, vcb, base = inp  # (b,c,h,d), (b,c,h,d), scalar chunk start
        logits = jnp.einsum("bqhd,bkhd->bhqk", qf, kcb,
                            preferred_element_type=jnp.float32)  # (b,h,sq,c)
        ki = base + jnp.arange(c, dtype=jnp.int32)  # absolute key positions
        if sl is not None:
            logits = logits + sl[None, :, None, None] * ki.astype(jnp.float32)[None, None, None, :]
        if bias is not None:
            logits = logits + jax.lax.dynamic_slice_in_dim(bias_full, base, c, axis=3).astype(jnp.float32)
        kept = jnp.broadcast_to(ki[None, :] < valid, (sq, c))
        if record.masks:
            kept = kept & record.keep(qi[:, None], ki[None, :])
        neg = jnp.finfo(jnp.float32).min
        logits = jnp.where(kept[None, None], logits, neg)
        m_chunk = jnp.max(logits, axis=-1)
        m_new = jnp.maximum(m, m_chunk)
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(logits - m_new[..., None])
        p = jnp.where(kept[None, None], p, 0.0)  # rows with no valid keys yet
        acc = acc * alpha[..., None] + jnp.einsum("bhqk,bkhd->bhqd", p, vcb.astype(jnp.float32))
        denom = denom * alpha + jnp.sum(p, axis=-1)
        return (acc, m_new, denom), None

    init = (jnp.zeros((b, h, sq, d), jnp.float32),
            jnp.full((b, h, sq), jnp.finfo(jnp.float32).min),
            jnp.zeros((b, h, sq), jnp.float32))
    bases = (jnp.arange(n_chunks, dtype=jnp.int32) * c)
    # remat: backward re-forms each logits tile instead of stashing all of
    # them (which would reconstruct the quadratic buffer this op avoids)
    (acc, m, denom), _ = jax.lax.scan(jax.checkpoint(body), init, (kc, vc, bases))
    out = acc / jnp.maximum(denom, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(orig_dtype)


def attention(q, k, v, **kwargs):
    """Dispatch through the kernel registry (Pallas flash on TPU, XLA otherwise). ``count_as``: the words the caller alone knows for this call
    site's count (``{"op": its kind's name, ...}``); the form that takes the call counts them with the path it is (``mixer/kernel``, ``pass="fwd"``)."""
    with region("mixer/kernel"):  # the call and the transposes around it; the form that runs counts which path it is
        return get_op("attention")(q, k, v, **kwargs)
