"""Swappable serving modules for the v2 (ragged / continuous-batching) engine.

Capability parity: reference ``inference/v2/modules/interfaces/`` — the
attention/embedding/linear/moe/pre_norm/post_norm/unembed base classes with
registry-selected implementations (``v2/modules/implementations/``,
``heuristics.py`` picks one per config). The TPU-native counterpart reuses
the framework's single kernel registry (``ops/registry.py``): each module
is an op family (``v2_embedding``, ``v2_attention``, ``v2_mlp``,
``v2_moe``, ``v2_norm``, ``v2_unembed``) whose default "tpu"
implementation is registered here; alternates register at higher priority
or are forced via ``REGISTRY.set_impl`` / ``DS_TPU_OP_V2_*`` env — the
same selection semantics the rest of the framework uses, so `ds_tpu_report`
shows serving-module choices alongside kernels.

Module contracts (all pure functions over the flax param pytree):
- embedding(cfg, params, input_ids, positions) -> (B, S, d) hidden
- norm(cfg, p, x) -> normed x        (pre_norm/post_norm collapse to one;
  p is None iff cfg.norm == "layernorm_np" — param-free olmo norms)
- attention(cfg, q, kp, vp, block_tables, ctx_lens, positions, *, decode,
  slopes, decode_attn, decode_native, prefill_attn, window) -> (B, S, H, D)
  (``decode_native``: decode_attn/prefill_attn already bake ALiBi/window;
  ``window`` is THIS layer's sliding window — per-layer-window models pass
  a different value per layer, so an alternate that reads
  ``cfg.sliding_window`` instead of ``window`` will silently mis-mask
  gpt-neo-class stacks; implementations MUST accept ``**kwargs`` so future
  call-site arguments don't break registered alternates)
- mlp(cfg, p, x) -> (B, S, d)
- moe(cfg, p, x) -> (B, S, d)        (no-drop ragged dispatch)
- unembed(cfg, params, x, last_token_idx) -> (B, V) fp32 logits
"""

import functools
from typing import Any, Callable, Dict, NamedTuple

import jax
import jax.numpy as jnp

from ...models.config import GATED
from ...models.transformer import TransformerConfig
from ...ops.pallas.paged_attention import paged_attention_ref
from ...ops.registry import REGISTRY


def _norm_key(cfg: TransformerConfig) -> str:
    return "RMSNorm" if cfg.norm == "rmsnorm" else "LayerNorm"


def _norm_p(cfg: TransformerConfig, container, idx: int):
    """Resolve a norm's param dict; None ONLY for the param-free norm kind.
    Parametric norms index strictly so converter regressions fail fast
    instead of silently degrading to unparameterized normalization."""
    if cfg.norm == "layernorm_np":
        return None
    return container[f"{_norm_key(cfg)}_{idx}"]


def _qproj(x, qp, dtype):
    """Apply a kgroups-quantized kernel through the fused dequant-matmul
    (ref mixed-GEMM): flatten x's trailing dims to the contraction size,
    restore the kernel's output dims after. TP-sharded leaves (``+gspmd``
    layout) go through the ``custom_partitioning`` wrapper: each shard
    runs the fused kernel on its own rows/columns and row-parallel
    partials psum over the K axis — a bare Pallas custom call under jit
    would instead force a full all-gather of the codes."""
    from ...ops.registry import REGISTRY as _R

    packed = qp.layout.startswith("kgroups_p4")
    K = qp.q.shape[0] * (2 if packed else 1)
    t, i = 1, x.ndim
    while t < K:
        i -= 1
        t *= x.shape[i]
    assert t == K, (x.shape, qp.q.shape)
    t, j = 1, 0
    while t < K:
        t *= qp.shape[j]
        j += 1
    if qp.layout.endswith("+gspmd"):
        from ...ops.pallas.quantized_matmul import quantized_matmul_sharded

        mm = functools.partial(quantized_matmul_sharded, packed=packed)
    else:
        mm = functools.partial(_R.get("quantized_matmul"), packed=packed)
    out2 = mm(x.reshape(-1, K).astype(dtype), qp.q, qp.scales)
    return out2.reshape(x.shape[:i] + tuple(qp.shape[j:])).astype(dtype)


def _proj(x, p, spec, dtype):
    w = p["kernel"]
    if str(getattr(w, "layout", "")).startswith("kgroups"):  # QuantizedParam (weight-only serving quant)
        y = _qproj(x, w, dtype)
    else:
        y = jnp.einsum(spec, x, w.astype(dtype))
    if "bias" in p:
        y = y + p["bias"].astype(dtype)
    return y


# ----------------------------------------------------------------------
# default implementations (ref v2/modules/implementations/*)
# ----------------------------------------------------------------------
def embedding_tpu(cfg: TransformerConfig, params: Dict[str, Any], input_ids, positions):
    """ref ``implementations/embedding/ragged_embedding.py``."""
    # explicit clamp: single-device XLA gathers clip out-of-vocab ids, but a
    # vocab-sharded wte under GSPMD masks them to zero instead — pin the
    # single-device semantics so tp>1 stays token-identical to tp=1
    input_ids = jnp.clip(input_ids, 0, params["wte"].shape[0] - 1)
    x = params["wte"][input_ids].astype(cfg.dtype)
    if cfg.embed_scale:  # gemma normalizer
        x = x * jnp.asarray(cfg.d_model**0.5, cfg.dtype)
    if cfg.pos_emb == "learned":
        x = x + params["wpe"][positions].astype(cfg.dtype)
    if cfg.embedding_norm:  # bloom — honor a swapped v2_norm here too
        x = REGISTRY.get("v2_norm")(cfg, _norm_p(cfg, params, 0), x)
    return x


def norm_tpu(cfg: TransformerConfig, p, x):
    """ref ``implementations/{pre_norm,post_norm}/``: one fused norm serves
    both roles (the pre/post distinction is call-site placement here).
    ``p is None`` = non-parametric layernorm (olmo)."""
    if p is None:
        x32 = x.astype(jnp.float32)
        mean = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
        return ((x32 - mean) * jax.lax.rsqrt(var + cfg.norm_eps)).astype(cfg.dtype)
    if "bias" in p:
        return REGISTRY.get("layer_norm")(x, p["scale"], p["bias"], cfg.norm_eps).astype(cfg.dtype)
    # the (1+w) offset must add in fp32: serving params may be bf16 and HF's
    # GemmaRMSNorm computes (1.0 + weight.float()) — the classic gemma pitfall
    w = 1.0 + p["scale"].astype(jnp.float32) if cfg.rms_offset else p["scale"]
    return REGISTRY.get("rms_norm")(x, w, cfg.norm_eps).astype(cfg.dtype)


_CFG_WINDOW = object()  # sentinel: caller did not pass a per-layer window


def attention_tpu(cfg: TransformerConfig, q, kp, vp, block_tables, ctx_lens, positions, *, decode: bool,
                  slopes=None, decode_attn: Callable = None, decode_native: bool = False,
                  prefill_attn: Callable = None, window=_CFG_WINDOW, **_):
    """ref ``implementations/attention/dense_blocked_attention.py``: Pallas
    paged kernels on both hot paths — decode and chunked prefill, incl.
    ALiBi/window baked in-kernel when ``decode_native`` — gather-based
    reference attention for bias-carrying models under TP sharding.
    ``window``: THIS layer's sliding window (per-layer models pass each
    layer's own value; default = the model-wide ``cfg.sliding_window``)."""
    if window is _CFG_WINDOW:
        window = cfg.sliding_window
    plain = slopes is None and window is None
    native = plain or decode_native
    if decode and decode_attn is not None and native:
        return decode_attn(q[:, 0], kp, vp, block_tables, ctx_lens)[:, None]
    if not decode and prefill_attn is not None and native:
        return prefill_attn(q, kp, vp, block_tables, ctx_lens, positions)
    return paged_attention_ref(q, kp, vp, block_tables, ctx_lens, positions, scale=cfg.attn_scale,
                               alibi_slopes=slopes, window=window)


def mlp_tpu(cfg: TransformerConfig, p: Dict[str, Any], x):
    """ref ``implementations/linear/*``: the dense FFN pair."""
    dtype = cfg.dtype
    if cfg.activation in GATED:
        g = _proj(x, p["gate_proj"], "bsd,df->bsf", dtype)
        g = getattr(jax.nn, GATED[cfg.activation])(g)
        h = g * _proj(x, p["up_proj"], "bsd,df->bsf", dtype)
    else:
        h = _proj(x, p["up_proj"], "bsd,df->bsf", dtype)
        if cfg.activation == "relu":
            h = jax.nn.relu(h)
        else:
            h = jax.nn.gelu(h, approximate=cfg.activation != "gelu_exact")
    return _proj(h, p["down_proj"], "bsf,fd->bsd", dtype)


def moe_tpu(cfg: TransformerConfig, p: Dict[str, Any], x):
    """ref ``implementations/moe/cutlass_multi_gemm.py`` (+ the ragged
    moe_scatter/top_k_gating kernels): no-drop top-k dispatch through
    ``lax.ragged_dot`` grouped GEMMs; math matches the training gate."""
    dtype = cfg.dtype
    B, S, d = x.shape
    k, E = cfg.moe_top_k, cfg.moe_num_experts
    tokens = x.reshape(-1, d)
    N = tokens.shape[0]
    gates = jax.nn.softmax(tokens.astype(jnp.float32) @ p["gate"]["kernel"].astype(jnp.float32), axis=-1)
    topk_vals, topk_idx = jax.lax.top_k(gates, k)  # (N, k)
    if k > 1:  # training parity: topkgating normalizes, top1gating does not
        topk_vals = topk_vals / jnp.maximum(jnp.sum(topk_vals, axis=-1, keepdims=True), 1e-9)

    flat_e = topk_idx.reshape(-1)  # (N*k,)
    order = jnp.argsort(flat_e)  # stable: preserves token order within an expert
    tok_of = order // k
    xs = tokens[tok_of].astype(dtype)  # (N*k, d) sorted by expert
    group_sizes = jnp.bincount(flat_e, length=E).astype(jnp.int32)

    ep = p["experts"]
    h = jax.lax.ragged_dot(xs, ep["wi"].astype(dtype), group_sizes)
    if cfg.activation == "swiglu":
        g = jax.lax.ragged_dot(xs, ep["wg"].astype(dtype), group_sizes)
        h = jax.nn.silu(g) * h
    elif cfg.activation == "relu":
        h = jax.nn.relu(h)
    else:
        h = jax.nn.gelu(h, approximate=cfg.activation != "gelu_exact")
    out_s = jax.lax.ragged_dot(h, ep["wo"].astype(dtype), group_sizes)  # (N*k, d)

    w_flat = topk_vals.reshape(-1)[order].astype(dtype)
    out = jnp.zeros((N, d), dtype).at[tok_of].add(out_s * w_flat[:, None])
    return out.reshape(B, S, d)


def unembed_tpu(cfg: TransformerConfig, params: Dict[str, Any], x, last_token_idx):
    """ref ``implementations/unembed/ragged_unembed.py``: final norm +
    last-real-token logits gather + head projection."""
    top = 1 if cfg.embedding_norm else 0
    x = REGISTRY.get("v2_norm")(cfg, _norm_p(cfg, params, top), x)
    last = x[jnp.arange(x.shape[0]), last_token_idx, :]
    if cfg.tie_embeddings:
        logits = jnp.einsum("bd,vd->bv", last, params["wte"].astype(cfg.dtype))
    else:
        logits = _proj(last, params["lm_head"], "bd,dv->bv", cfg.dtype)
    return logits.astype(jnp.float32)


REGISTRY.register("v2_embedding", "tpu", embedding_tpu, priority=0)
REGISTRY.register("v2_norm", "tpu", norm_tpu, priority=0)
REGISTRY.register("v2_attention", "tpu", attention_tpu, priority=0)
REGISTRY.register("v2_mlp", "tpu", mlp_tpu, priority=0)
REGISTRY.register("v2_moe", "tpu", moe_tpu, priority=0)
REGISTRY.register("v2_unembed", "tpu", unembed_tpu, priority=0)


class V2Modules(NamedTuple):
    """Resolved module bundle (ref ``modules/heuristics.py`` result)."""
    embedding: Callable
    norm: Callable
    attention: Callable
    mlp: Callable
    moe: Callable
    unembed: Callable


def build_modules() -> V2Modules:
    """Resolve the serving modules from the registry (ref
    ``heuristics.instantiate_*``)."""
    return V2Modules(embedding=REGISTRY.get("v2_embedding"), norm=REGISTRY.get("v2_norm"),
                     attention=REGISTRY.get("v2_attention"), mlp=REGISTRY.get("v2_mlp"),
                     moe=REGISTRY.get("v2_moe"), unembed=REGISTRY.get("v2_unembed"))
