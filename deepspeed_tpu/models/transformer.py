"""Decoder-only transformer family (GPT-2 style and Llama style).

These play the role of the reference's test/bench models
(``tests/unit/simple_model.py``, Megatron/HF models in examples): the
framework is model-agnostic, but ships first-class implementations that
are TPU-shaped — einsum matmuls onto the MXU, bf16 activations, static
shapes, optional remat and scan-over-layers, attention dispatched through
the kernel registry (Pallas flash on TPU).

Tensor-parallel sharding is declared as partition rules (param-path ->
PartitionSpec) rather than module surgery: the AutoTP analogue
(reference ``module_inject/auto_tp.py``) consumes these rules.
"""

import contextlib
import contextvars
import functools
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ..ops.attention import attention
from ..telemetry.tracing import region


@dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    n_layers: int = 2
    n_heads: int = 4
    n_kv_heads: Optional[int] = None  # < n_heads => GQA (llama-70b style)
    head_dims: Optional[int] = None  # explicit head dim (gemma: != d_model/n_heads)
    d_model: int = 128
    d_ff: Optional[int] = None  # default: 4*d_model (gelu) or 8/3*d_model (swiglu)
    max_seq_len: int = 2048
    norm: str = "layernorm"  # layernorm | rmsnorm | layernorm_np (olmo: no affine params)
    activation: str = "gelu"  # gelu (tanh approx) | gelu_exact (erf) | swiglu | relu
    pos_emb: str = "learned"  # learned | rope | alibi | none
    rope_theta: float = 10000.0
    rotary_pct: float = 1.0  # fraction of head_dim rotated (gpt-neox/phi partial rotary)
    rotary_dims: Optional[int] = None  # exact rotated dim count (gpt-j rotary_dim); overrides rotary_pct
    rope_style: str = "neox"  # neox (rotate-half) | gptj (interleaved pairs)
    # HF rope_scaling variants (transformers modeling_rope_utils.py):
    # linear (position interpolation), dynamic (NTK-by-parts at max_seq_len),
    # llama3 (frequency-banded interpolation — llama-3.1+), yarn
    rope_scaling: Optional[str] = None  # linear | dynamic | llama3 | yarn
    rope_factor: float = 1.0
    rope_orig_max_seq: Optional[int] = None  # original_max_position_embeddings
    rope_low_freq_factor: float = 1.0   # llama3
    rope_high_freq_factor: float = 4.0  # llama3
    rope_beta_fast: float = 32.0        # yarn extrapolation boundary
    rope_beta_slow: float = 1.0         # yarn interpolation boundary
    rope_attn_factor: Optional[float] = None  # yarn cos/sin scale; None = 0.1*ln(factor)+1
    clip_qkv: Optional[float] = None  # olmo: clamp q/k/v activations to [-c, c]
    # block wiring: sequential (gpt2/llama), parallel (gpt-neox: two norms,
    # x + attn(ln1 x) + mlp(ln2 x)), parallel_shared (falcon-7b/phi/gpt-j:
    # one norm feeds both attn and mlp)
    block_type: str = "sequential"
    dense_bias: Optional[bool] = None  # default: norm == "layernorm" (falcon: LN but bias-free)
    qkv_bias: Optional[bool] = None  # override for q/k/v projections only (qwen2)
    qk_norm: bool = False  # qwen3: per-head RMSNorm on q/k before rope (zero-centered weights under ``rms_offset``)
    # qwen3-next: q_proj is twice as wide, a head's columns its query and then a gate, and the attention's output is
    # multiplied by sigmoid(gate) ahead of o_proj: out = (softmax(q k^T / sqrt(D)) v * sigmoid(gate)) W_o
    attn_output_gate: bool = False
    attn_out_bias: Optional[bool] = None  # override for o_proj only (gpt-j: biased MLP, bias-free attn)
    lm_head_bias: bool = False  # phi / gpt-j carry a bias on the untied head
    embedding_norm: bool = False  # bloom: layernorm directly after the token embedding
    embed_scale: bool = False  # gemma: scale embeddings by sqrt(d_model)
    rms_offset: bool = False  # gemma: rmsnorm weights stored zero-centered, applied as (1 + w)
    sliding_window: Optional[int] = None  # mistral: query i attends keys in (i - w, i]
    # per-layer window selection: tuple of layer indices that apply
    # ``sliding_window``; None = every layer (gpt-neo alternating
    # global/local layers, qwen2 ``max_window_layers`` suffix windows)
    window_layers: Optional[Tuple[int, ...]] = None
    attn_scale: Optional[float] = None  # softmax scale override; None = 1/sqrt(head_dim) (gpt-neo: 1.0)
    # encoder family (BERT): bidirectional attention, post-LN blocks,
    # token-type embeddings, MLM transform head (ref module_inject/containers/bert.py)
    causal: bool = True  # False: bidirectional encoder
    norm_scheme: str = "pre"  # pre (gpt/llama) | post (BERT: norm after residual add)
    type_vocab_size: int = 0  # >0: token_type embeddings added to the input
    mlm_head: bool = False  # BERT cls.predictions transform (dense+act+LN) before the tied decoder
    tie_embeddings: bool = True
    dtype: Any = jnp.float32  # activation/compute dtype
    norm_eps: float = 1e-5
    dropout: float = 0.0
    remat: bool = False  # jax.checkpoint each block (activation checkpointing)
    scan_layers: bool = False  # lax.scan over layers (fast compile, pipeline-friendly)
    # MoE (reference deepspeed/moe): >0 experts turns MLP slots into MoE layers
    moe_num_experts: int = 0
    moe_top_k: int = 1
    moe_capacity_factor: float = 1.25
    moe_layer_freq: int = 2  # every Nth block is MoE
    moe_aux_loss_coef: float = 0.01
    moe_min_capacity: int = 4
    # THE per-layer specification: one (mixer, ffn) pair a layer. mixer: full | window (``sliding_window``) |
    # kda (gated delta-rule linear attention, a decay a channel) | gdn (the same rule with a decay a head: Gated
    # DeltaNet) | mla (latent attention: its shared key part rotated where ``pos_emb`` is "rope", else no positions) |
    # sparse (grouped-query attention over the ``index_topk`` keys a learned indexer chooses for each query);
    # ffn: dense | moe (the softmax gate with a capacity above) | routed (``moe_scoring`` scores, no capacity, a
    # shared expert). None: the pairs that ``window_layers`` and ``moe_layer_freq`` describe (``kinds``)
    layer_kinds: Optional[Tuple[Tuple[str, str], ...]] = None
    kda_heads: int = 0  # kda: heads of ``kda_head_dim`` keys and values, a depthwise causal convolution of
    kda_head_dim: int = 128  # ``kda_conv_size`` on q, k and v, gates through ``kda_gate_rank``
    kda_conv_size: int = 4
    kda_gate_rank: int = 128
    # gdn: ``gdn_key_heads`` heads of q and k, each serving ``gdn_value_heads / gdn_key_heads`` value heads, all of
    # ``gdn_head_dim``; a depthwise causal convolution of ``gdn_conv_size`` on q, k and v; per value head and token
    # beta = sigmoid(x w_b), g = -exp(A_log) softplus(x w_a + dt_bias), S_t = (I - beta k k^T) exp(g) S_{t-1} + beta k v^T
    gdn_key_heads: int = 0
    gdn_value_heads: int = 0
    gdn_head_dim: int = 128
    gdn_conv_size: int = 4
    # sparse: an indexer of ``index_heads`` heads of ``index_head_dim`` on one key head scores every visible key; a
    # query attends the ``index_topk`` best (every key, through the dense program, where the sequence is no longer)
    index_heads: int = 16
    index_head_dim: int = 64
    index_topk: int = 2048
    # sparse: the attention's output projection starts at this times its usual standard deviation. At a random start
    # attention averages its keys, so every position gets nearly the same vector and the stream collapses onto it layer
    # by layer; a random router turns that into a load a seed decides. A small start leaves the stream the tokens' own
    sparse_out_init_scale: float = 1.0
    mla_kv_rank: int = 512  # mla: ``n_heads`` heads; q and k of nope + rope dims (the rope dims rotated under
    # ``pos_emb="rope"`` by ``rope_theta`` / ``rope_style``, else nothing is), v of its own
    mla_qk_nope_dim: int = 128
    mla_qk_rope_dim: int = 64
    mla_v_dim: int = 128
    # routed: ``moe_num_experts`` router outputs, ``moe_top_k`` a token, experts ``moe_d_ff`` wide
    moe_d_ff: Optional[int] = None  # None: ``ffn_dim``
    moe_shared_d_ff: int = 0  # width of the shared expert every token also takes (n shared SwiGLUs of f added are one
    # of n * f, their columns side by side: give the sum); 0: none
    moe_route_scale: float = 1.0  # the renormalised weights of a token's experts are multiplied by this
    moe_held: Optional[Tuple[int, int]] = None  # (first, count): the experts THIS program holds; None: all
    # routed: a token's scores over all experts. "sigmoid": s = sigmoid(x W_r), the top k of s + selection bias;
    # "softmax": p = softmax(x W_r), the top k of p; either way the chosen ones rescaled to sum to one, times
    # ``moe_route_scale``
    moe_scoring: str = "sigmoid"
    moe_shared_gate: bool = False  # routed: the shared expert's output is multiplied by sigmoid(x w_s), w_s (d_model, 1)

    @property
    def kinds(self) -> Tuple[Tuple[str, str], ...]:
        """(mixer, ffn) of every layer: ``layer_kinds``, or what the older
        fields say: a window on the layers ``window_layers`` lists (all of
        them where it is None), a MoE every ``moe_layer_freq``-th block."""
        return _kinds_of(self)

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def ffn_dim(self) -> int:
        if self.d_ff is not None:
            return self.d_ff
        if self.activation in ("swiglu", "geglu"):  # gated MLPs get the 8/3 sizing
            return int(8 * self.d_model / 3 + 127) // 128 * 128 if self.d_model >= 128 else 2 * self.d_model
        return 4 * self.d_model

    @property
    def head_dim(self) -> int:
        if self.head_dims is not None:
            return self.head_dims
        assert self.d_model % self.n_heads == 0
        return self.d_model // self.n_heads

    @property
    def use_dense_bias(self) -> bool:
        return self.norm == "layernorm" if self.dense_bias is None else self.dense_bias

    @property
    def use_qkv_bias(self) -> bool:
        return self.use_dense_bias if self.qkv_bias is None else self.qkv_bias

    @property
    def use_attn_out_bias(self) -> bool:
        return self.use_dense_bias if self.attn_out_bias is None else self.attn_out_bias

    def window_for(self, layer_idx: int) -> Optional[int]:
        """Sliding-window width for one layer (None = no window): a reading of ``kinds``."""
        return self.sliding_window if self.kinds[layer_idx][0] == "window" else None

    def moe_for(self, layer_idx: int) -> bool:
        """Whether one layer's FFN slot holds experts: a reading of ``kinds``."""
        return self.kinds[layer_idx][1] != "dense"

    @property
    def uniform_window(self) -> bool:
        """True when every layer shares one window config (scan/v2-servable)."""
        return len({self.window_for(i) for i in range(self.n_layers)}) <= 1

    @property
    def softmax_only(self) -> bool:
        """Every mixer is softmax attention over one head size and every FFN
        dense or the capacity-gated MoE: what the scan over layers, the
        pipeline's stacking and ``inference/v2`` can run."""
        return all(m in ("full", "window") and f in ("dense", "moe") for m, f in self.kinds)

    @property
    def sows(self) -> bool:
        """Whether a block of this model may sow (an expert layer's auxiliary loss and rows, a sparse mixer's index
        loss and key counts): its loss is then traced with those collections mutable."""
        return self.moe_num_experts > 0 or any(mixer == "sparse" for mixer, _ in self.kinds)

    @property
    def rotary_dim(self) -> int:
        # even; partial rotary rotates the leading dims
        if self.rotary_dims is not None:
            return self.rotary_dims
        return max(2, int(self.head_dim * self.rotary_pct) // 2 * 2)


MIXERS = ("full", "window", "kda", "gdn", "mla", "sparse")
FFNS = ("dense", "moe", "routed")

# The name a projection's result carries for a checkpoint policy: what a product over the model width gives (a mixer's
# q/k/v/gate projections, the dense FFN's gate and up), what one onto it gives where a backward reads it (the mixer's
# output added to the block's input), or a value after such a product from which the backward's needs follow elementwise.
# A checkpointed hybrid block keeps it (``remat_keeps``), so its backward makes no such product a second time; outside such
# a policy (serving, ``remat: false``, a ``full``/``dense`` block) ``checkpoint_name`` is an identity
SAVED = "projection"


@functools.lru_cache(maxsize=256)
def _kinds_of(cfg: TransformerConfig) -> Tuple[Tuple[str, str], ...]:
    """``TransformerConfig.kinds``, worked out once a configuration (it is frozen and hashable; nothing is kept on
    the instance, whose ``__dict__`` callers copy into new configurations)."""
    if cfg.layer_kinds is not None:
        kinds = tuple((str(m), str(f)) for m, f in cfg.layer_kinds)
        bad = [k for k in kinds if k[0] not in MIXERS or k[1] not in FFNS]
        if len(kinds) != cfg.n_layers or bad:
            raise ValueError(f"layer_kinds must give n_layers={cfg.n_layers} pairs of {MIXERS} x {FFNS}, got "
                             f"{len(kinds)} with {bad}")
        return kinds
    freq = max(1, cfg.moe_layer_freq)
    windowed = lambda i: cfg.sliding_window is not None and (cfg.window_layers is None or i in cfg.window_layers)
    return tuple(("window" if windowed(i) else "full",
                  "moe" if cfg.moe_num_experts > 0 and i % freq == freq - 1 else "dense")
                 for i in range(cfg.n_layers))


# -------------------- layers --------------------
class RMSNorm(nn.Module):
    eps: float = 1e-5
    dtype: Any = jnp.float32
    offset: bool = False  # gemma: weights zero-centered, applied as (1 + w)

    @nn.compact
    def __call__(self, x):
        init = nn.initializers.zeros if self.offset else nn.initializers.ones
        scale = self.param("scale", init, (x.shape[-1],), jnp.float32)
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps)
        w = 1.0 + scale if self.offset else scale
        return (y * w).astype(self.dtype)


class LayerNorm(nn.Module):
    eps: float = 1e-5
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (x.shape[-1],), jnp.float32)
        x32 = x.astype(jnp.float32)
        mean = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
        y = (x32 - mean) * jax.lax.rsqrt(var + self.eps)
        return (y * scale + bias).astype(self.dtype)


class LayerNormNP(nn.Module):
    """Non-parametric layernorm (olmo: ``elementwise_affine=False``)."""
    eps: float = 1e-5
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        x32 = x.astype(jnp.float32)
        mean = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
        return ((x32 - mean) * jax.lax.rsqrt(var + self.eps)).astype(self.dtype)


def make_norm(cfg: TransformerConfig):
    if cfg.norm == "rmsnorm":
        return RMSNorm(eps=cfg.norm_eps, dtype=cfg.dtype, offset=cfg.rms_offset)
    if cfg.norm == "layernorm_np":
        return LayerNormNP(eps=cfg.norm_eps, dtype=cfg.dtype)
    return LayerNorm(eps=cfg.norm_eps, dtype=cfg.dtype)


def _norm(cfg: TransformerConfig, x):
    """A block's norms and the model's final one, under their region's name."""
    with region("norm"):
        return make_norm(cfg)(x)


def rope_frequencies(head_dim: int, max_len: int, theta: float) -> Tuple[jnp.ndarray, jnp.ndarray]:
    inv = 1.0 / (theta**(jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    t = jnp.arange(max_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv)  # (L, D/2)
    return jnp.cos(freqs), jnp.sin(freqs)


def scaled_rope_frequencies(cfg: "TransformerConfig", head_dim: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """cos/sin tables honoring ``cfg.rope_scaling`` with HF semantics
    (``transformers/modeling_rope_utils.py`` — the parity oracle the
    interop tests check against). Precomputed with numpy: frequencies are
    static per config, and fp64 intermediate math avoids compounding the
    pow/log chain in fp32. The table is worked out once a configuration and
    width (``_rope_table``): every kind of block that is traced into a
    program, and every program, shares it."""
    cos, sin = _rope_table(cfg, head_dim)
    return jnp.asarray(cos), jnp.asarray(sin)


@functools.lru_cache(maxsize=32)
def _rope_table(cfg: "TransformerConfig", head_dim: int) -> Tuple[np.ndarray, np.ndarray]:
    rd, theta, factor = head_dim, cfg.rope_theta, cfg.rope_factor
    inv = 1.0 / (theta**(np.arange(0, rd, 2, dtype=np.float64) / rd))
    attn_factor = 1.0
    kind = cfg.rope_scaling
    if kind == "linear":
        inv = inv / factor
    elif kind == "dynamic":
        # NTK-aware base rescale at the engine's static max context (HF
        # recomputes per growing seq_len; compiled tables take the worst
        # case, which equals HF exactly while serving <= rope_orig_max_seq
        # and bounds it above)
        orig = cfg.rope_orig_max_seq or cfg.max_seq_len
        seq_len = max(cfg.max_seq_len, orig)
        base = theta * ((factor * seq_len / orig) - (factor - 1))**(rd / (rd - 2))
        inv = 1.0 / (base**(np.arange(0, rd, 2, dtype=np.float64) / rd))
    elif kind == "llama3":
        orig = cfg.rope_orig_max_seq or cfg.max_seq_len
        low_wav = orig / cfg.rope_low_freq_factor
        high_wav = orig / cfg.rope_high_freq_factor
        wavelen = 2 * np.pi / inv
        inv_l = np.where(wavelen > low_wav, inv / factor, inv)
        smooth = (orig / wavelen - cfg.rope_low_freq_factor) / \
            (cfg.rope_high_freq_factor - cfg.rope_low_freq_factor)
        smoothed = (1 - smooth) * inv_l / factor + smooth * inv_l
        medium = ~(wavelen < high_wav) & ~(wavelen > low_wav)
        inv = np.where(medium, smoothed, inv_l)
    elif kind == "yarn":
        orig = cfg.rope_orig_max_seq or cfg.max_seq_len

        def corr_dim(n_rot):
            return (rd * np.log(orig / (n_rot * 2 * np.pi))) / (2 * np.log(theta))

        low = max(np.floor(corr_dim(cfg.rope_beta_fast)), 0)
        high = min(np.ceil(corr_dim(cfg.rope_beta_slow)), rd - 1)
        if low == high:
            high += 0.001  # HF's singularity guard
        ramp = np.clip((np.arange(rd // 2, dtype=np.float64) - low) / (high - low), 0, 1)
        extrap_factor = 1 - ramp
        inv = (inv / factor) * (1 - extrap_factor) + inv * extrap_factor
        if cfg.rope_attn_factor is not None:
            attn_factor = cfg.rope_attn_factor
        else:
            attn_factor = 0.1 * np.log(factor) + 1.0 if factor > 1 else 1.0
    elif kind is not None:
        raise NotImplementedError(f"rope_scaling={kind!r} (supported: linear/dynamic/llama3/yarn)")
    t = np.arange(cfg.max_seq_len, dtype=np.float64)
    freqs = np.outer(t, inv)  # (L, rd/2)
    return (np.cos(freqs) * attn_factor).astype(np.float32), (np.sin(freqs) * attn_factor).astype(np.float32)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray, positions: jnp.ndarray,
               rotary_dim: Optional[int] = None, style: str = "neox") -> jnp.ndarray:
    """x: (B,S,H,D); positions: (B,S) absolute token positions.

    ``rotary_dim < D`` rotates only the leading dims (gpt-neox ``rotary_pct``,
    phi ``partial_rotary_factor``, gpt-j ``rotary_dim``); the tail passes
    through. ``style``: "neox" rotates half-split pairs (llama/neox/phi),
    "gptj" rotates adjacent interleaved pairs (gpt-j ``rotate_every_two``).
    """
    D = x.shape[-1]
    rd = D if rotary_dim is None else rotary_dim
    xr, xp = (x, None) if rd == D else (x[..., :rd], x[..., rd:])
    c = cos[positions][:, :, None, :]  # (B,S,1,rd/2)
    s = sin[positions][:, :, None, :]
    xr32 = xr.astype(jnp.float32)
    if style == "gptj":
        x1, x2 = xr32[..., 0::2], xr32[..., 1::2]
        out = jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1).reshape(xr.shape)
    else:
        x1, x2 = jnp.split(xr32, 2, axis=-1)
        out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    out = out.astype(x.dtype)
    return out if xp is None else jnp.concatenate([out, xp], axis=-1)


def alibi_slopes(n_heads: int) -> np.ndarray:
    """Per-head ALiBi slopes: geometric sequence of 2^(-8/n) for the closest
    power of two, interpolated for non-power-of-two head counts (ALiBi paper
    / bloom)."""
    def slopes(n: int):
        p = 2**int(np.floor(np.log2(n)))
        base = [2**(-(2.0**-(np.log2(p) - 3)) * (i + 1)) for i in range(p)]
        if p < n:
            base += slopes(2 * p)[0::2][:n - p]
        return base

    return np.asarray(slopes(n_heads), np.float32)


# (the shift-invariant bias form slope_h * key_position lives directly in
# attention_xla / the flash kernel — per query row it differs from the full
# slope * (j - i) by a row-constant, which softmax cancels)


class Attention(nn.Module):
    cfg: TransformerConfig
    window: Optional[int] = None

    @nn.compact
    def __call__(self, x, positions, kv_cache=None, segment_ids=None):
        cfg = self.cfg
        B, S, _ = x.shape
        H, KVH, D = cfg.n_heads, cfg.kv_heads, cfg.head_dim
        # named (``SAVED``): clipping, the norms, the rotation and the output gate follow from these by elementwise work
        dense = lambda feats, name: checkpoint_name(nn.DenseGeneral(feats, axis=-1, use_bias=cfg.use_qkv_bias, name=name,
                                                                    dtype=cfg.dtype, param_dtype=jnp.float32)(x), SAVED)
        with region("mixer/proj"):
            q = dense((H, 2 * D if cfg.attn_output_gate else D), "q_proj")
            if cfg.attn_output_gate:
                q, gate = q[..., :D], q[..., D:]
            k = dense((KVH, D), "k_proj")
            v = dense((KVH, D), "v_proj")
            if cfg.clip_qkv is not None:  # olmo: clamp projections before rope
                c = cfg.clip_qkv
                q, k, v = (jnp.clip(t, -c, c) for t in (q, k, v))
            if cfg.qk_norm:  # qwen3: head-dim RMSNorm before rope
                q = RMSNorm(eps=cfg.norm_eps, dtype=cfg.dtype, offset=cfg.rms_offset, name="q_norm")(q)
                k = RMSNorm(eps=cfg.norm_eps, dtype=cfg.dtype, offset=cfg.rms_offset, name="k_norm")(k)

        if cfg.pos_emb == "rope":
            with region("mixer/rope"):
                rd = cfg.rotary_dim
                cos, sin = scaled_rope_frequencies(cfg, rd)
                q = apply_rope(q, cos, sin, positions, rotary_dim=rd, style=cfg.rope_style)
                k = apply_rope(k, cos, sin, positions, rotary_dim=rd, style=cfg.rope_style)

        new_cache = None
        kv_len = None
        if kv_cache is not None:
            # decode: append to cache at position offset
            ck, cv, cache_len = kv_cache
            ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype), (0, cache_len, 0, 0))
            cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype), (0, cache_len, 0, 0))
            k, v = ck, cv
            kv_len = cache_len + S
            new_cache = (ck, cv, kv_len)

        slopes = jnp.asarray(alibi_slopes(H)) if cfg.pos_emb == "alibi" else None
        out = attention(q, k, v, causal=cfg.causal, segment_ids=segment_ids, kv_len=kv_len,
                        alibi_slopes=slopes, window=self.window, scale=cfg.attn_scale)
        with region("mixer/proj"):
            if cfg.attn_output_gate:
                out = out * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(out.dtype)
            out = nn.DenseGeneral(cfg.d_model, axis=(-2, -1), use_bias=cfg.use_attn_out_bias, name="o_proj",
                                  dtype=cfg.dtype, param_dtype=jnp.float32)(out)
        return (out, new_cache) if kv_cache is not None else out


class MLP(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        with region("ffn/dense"):
            cfg = self.cfg
            bias = cfg.use_dense_bias
            # named (``SAVED``): the activation and the down projection's operand follow from these by elementwise work
            wide = lambda name: checkpoint_name(
                nn.Dense(cfg.ffn_dim, use_bias=bias, name=name, dtype=cfg.dtype, param_dtype=jnp.float32)(x), SAVED)
            if cfg.activation in ("swiglu", "geglu"):
                gate, up = wide("gate_proj"), wide("up_proj")
                h = (nn.gelu(gate) if cfg.activation == "geglu" else nn.silu(gate)) * up
            else:
                h = wide("up_proj")
                if cfg.activation == "relu":
                    h = nn.relu(h)
                else:  # HF "gelu" is the exact erf form; "gelu_new"/tanh is our default
                    h = nn.gelu(h, approximate=cfg.activation != "gelu_exact")
            return nn.Dense(cfg.d_model, use_bias=bias, name="down_proj", dtype=cfg.dtype, param_dtype=jnp.float32)(h)


class Block(nn.Module):
    """One transformer block. Its fields are all a trace of it can depend on:
    a layer's place in the stack enters only through ``kind``
    (``cfg.kinds[i]``: its mixer and its FFN), so layers of one kind share
    one traced function (``block_fn``)."""

    cfg: TransformerConfig
    kind: Tuple[str, str] = ("full", "dense")
    is_training: bool = True  # static: MoE capacity-drop is train-only

    @property
    def moe(self) -> bool:
        return self.kind[1] != "dense"

    def _mlp(self, cfg, h):
        if self.kind[1] == "moe":
            from ..moe.layer import MoE

            return MoE(hidden_size=cfg.d_model, num_experts=cfg.moe_num_experts, k=cfg.moe_top_k,
                       capacity_factor=cfg.moe_capacity_factor, min_capacity=cfg.moe_min_capacity,
                       d_ff=cfg.ffn_dim, activation=cfg.activation, dtype=cfg.dtype,
                       name="moe")(h, train=self.is_training)
        if self.kind[1] == "routed":
            from ..moe.layer import RoutedMoE

            return RoutedMoE(hidden_size=cfg.d_model, num_experts=cfg.moe_num_experts, k=cfg.moe_top_k,
                             d_ff=cfg.moe_d_ff or cfg.ffn_dim, held=cfg.moe_held, shared_ff=cfg.moe_shared_d_ff,
                             scale=cfg.moe_route_scale, scoring=cfg.moe_scoring, shared_gate=cfg.moe_shared_gate,
                             dtype=cfg.dtype, name="routed")(h)
        return MLP(cfg, name="mlp")(h)

    def _mixer(self, cfg):
        """The layer's token mixer as ``fn(h, positions, kv_cache, segment_ids)``."""
        if self.kind[0] in ("kda", "gdn", "mla", "sparse"):
            from . import mixers

            mixer = {"kda": mixers.KDAMixer, "gdn": mixers.GDNMixer, "mla": mixers.MLAMixer,
                     "sparse": mixers.SparseMixer}[self.kind[0]](cfg, name=self.kind[0])

            def run(h, positions, kv_cache, segment_ids):
                if kv_cache is not None or segment_ids is not None:
                    raise NotImplementedError(f"a {self.kind[0]} layer takes no KV cache and no packed segments yet")
                return mixer(h, positions) if self.kind[0] in ("mla", "sparse") else mixer(h)

            return run
        return Attention(cfg, window=cfg.sliding_window if self.kind[0] == "window" else None, name="attn")

    @nn.compact
    def __call__(self, x, positions, kv_cache=None, segment_ids=None):
        cfg = self.cfg
        attn = self._mixer(cfg)

        def run_attn(h):
            if kv_cache is not None:
                return attn(h, positions, kv_cache, segment_ids)
            return attn(h, positions, None, segment_ids), None

        if cfg.block_type == "parallel_shared":  # falcon-7b / phi / gpt-j
            h = _norm(cfg, x)
            a, new_cache = run_attn(h)
            x = x + a + self._mlp(cfg, h)
        elif cfg.block_type == "parallel":  # gpt-neox use_parallel_residual
            a, new_cache = run_attn(_norm(cfg, x))
            x = x + a + self._mlp(cfg, _norm(cfg, x))
        elif cfg.norm_scheme == "post":  # BERT: norm AFTER each residual add
            a, new_cache = run_attn(x)
            x = _norm(cfg, x + a)
            x = _norm(cfg, x + self._mlp(cfg, x))
        else:
            a, new_cache = run_attn(_norm(cfg, x))
            # named (``SAVED``): the FFN half's backward starts from this sum, so a checkpointed block that keeps it does not
            # make the mixer's output projection again to get it back
            x = checkpoint_name(x + a, SAVED)
            x = x + self._mlp(cfg, _norm(cfg, x))
        return (x, new_cache) if kv_cache is not None else x


class Transformer(nn.Module):
    """Causal LM. ``__call__`` returns logits; ``loss`` the mean token CE."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, input_ids, positions=None, kv_caches=None, segment_ids=None, return_hidden=False,
                 train=None, pld_theta=None, token_type_ids=None):
        cfg = self.cfg
        # decode (kv caches) implies inference; forward-only callers pass
        # train=False so eval/serving never drops MoE tokens
        train = (kv_caches is None) if train is None else bool(train)
        if pld_theta is not None and cfg.scan_layers:
            raise ValueError("progressive layer drop needs the unrolled layer loop: set scan_layers=False")
        if cfg.scan_layers and len({mixer for mixer, _ in cfg.kinds}) > 1:
            raise ValueError("per-layer window_layers (layers of several mixers) needs heterogeneous blocks: set scan_layers=False")
        B, S = input_ids.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        emb = self.param("wte", nn.initializers.normal(0.02), (cfg.vocab_size, cfg.d_model), jnp.float32)
        hook = _BLOCK_HOOK.get() if kv_caches is None and not self.is_initializing() else None
        with region("embed"):
            x = hook.look_up(self.path + ("wte",), emb, input_ids, cfg.sows) if hook is not None else None
            x = (emb[input_ids] if x is None else x).astype(cfg.dtype)
            if cfg.embed_scale:  # gemma normalizer
                x = x * jnp.asarray(cfg.d_model**0.5, cfg.dtype)
            if cfg.pos_emb == "learned":
                wpe = self.param("wpe", nn.initializers.normal(0.02), (cfg.max_seq_len, cfg.d_model), jnp.float32)
                x = x + wpe[positions].astype(cfg.dtype)
            if cfg.type_vocab_size > 0:  # BERT segment embeddings
                tte = self.param("type_emb", nn.initializers.normal(0.02),
                                 (cfg.type_vocab_size, cfg.d_model), jnp.float32)
                tti = token_type_ids if token_type_ids is not None else jnp.zeros_like(input_ids)
                x = x + tte[tti].astype(cfg.dtype)
        if cfg.embedding_norm:  # bloom word_embeddings_layernorm / BERT embeddings.LayerNorm
            x = _norm(cfg, x)

        new_caches = [] if kv_caches is not None else None
        remat = cfg.remat and kv_caches is None
        if cfg.scan_layers and kv_caches is None:
            x = self._scan_blocks(nn.remat(Block, static_argnums=()) if remat else Block, x, positions,
                                  segment_ids, train)
        else:
            # one traced function a KIND of block and program, applied once a layer to that layer's
            # parameters: the block's Python body runs once, not n_layers times
            kinds = functools.cache(functools.partial(block_fn, cfg, train=train, remat=remat))
            layers = [] if self.is_initializing() else [self.get_variable("params", f"layer_{i}")
                                                        for i in range(cfg.n_layers)]
            paths = [self.path + (f"layer_{i}",) for i in range(cfg.n_layers)]
            may_sow = [cfg.moe_for(i) or cfg.kinds[i][0] == "sparse" for i in range(cfg.n_layers)]
            for i in range(cfg.n_layers):
                kind = cfg.kinds[i]
                kv_cache = kv_caches[i] if kv_caches is not None else None
                if self.is_initializing():  # makes the tree: flax has to see every layer as a submodule
                    y = Block(cfg, kind, is_training=train, name=f"layer_{i}")(x, positions, kv_cache, segment_ids)
                    y, cache = y if kv_caches is not None else (y, None)
                else:
                    wrap = None
                    if hook is not None:
                        wrap, x = hook(paths, layers, may_sow, i, x)
                    (y, cache), sown = kinds(kind, wrap=wrap)(layers[i], x, positions, kv_cache, segment_ids)
                    for col, tree in sown.items():  # what the block sowed (MoE auxiliary loss), where it was
                        if self.is_mutable_collection(col):
                            self.put_variable(col, f"layer_{i}", tree)
                if kv_caches is not None:
                    new_caches.append(cache)
                elif pld_theta is not None and train:
                    # progressive layer drop (arXiv:2010.13369): deeper
                    # layers drop more; keep prob 1-(1-theta)*l/L
                    pkeep = 1.0 - (1.0 - pld_theta) * (i + 1) / cfg.n_layers
                    keep = jax.random.bernoulli(self.make_rng("pld"), pkeep)
                    y = jnp.where(keep, y, x)
                x = y

        if cfg.norm_scheme != "post":  # post-LN blocks already end normalized
            x = _norm(cfg, x)
        if cfg.mlm_head:
            # BERT cls.predictions.transform: dense + act + LN before the
            # tied decoder — part of the hidden pipeline so the fused-CE
            # loss path projects the transformed hidden
            x = nn.Dense(cfg.d_model, name="mlm_dense", dtype=cfg.dtype, param_dtype=jnp.float32)(x)
            # HF BertPredictionHeadTransform applies config.hidden_act
            if cfg.activation == "relu":
                x = nn.relu(x)
            else:
                x = nn.gelu(x, approximate=cfg.activation != "gelu_exact")
            x = _norm(cfg, x)
            # created unconditionally (not only on the logits path) so the
            # param tree is identical between loss and logits calls
            mlm_bias = self.param("mlm_bias", nn.initializers.zeros, (cfg.vocab_size,), jnp.float32)
        if return_hidden:
            # loss path: the head projection happens inside the fused CE
            # (ops/fused_ce.py) so full (B,S,V) logits never hit HBM
            return (x, new_caches) if kv_caches is not None else x
        with region("head"):
            if cfg.tie_embeddings:
                logits = jnp.einsum("bsd,vd->bsv", x, emb.astype(cfg.dtype))
                if cfg.mlm_head:  # BERT cls.predictions.bias rides the tied decoder
                    logits = logits + mlm_bias.astype(cfg.dtype)
            else:
                logits = nn.Dense(cfg.vocab_size, use_bias=cfg.lm_head_bias, name="lm_head", dtype=cfg.dtype,
                                  param_dtype=jnp.float32)(x)
            logits = logits.astype(jnp.float32)
        return (logits, new_caches) if kv_caches is not None else logits

    def _scan_blocks(self, block_cls, x, positions, segment_ids, train=True):
        cfg = self.cfg

        class ScanBody(nn.Module):
            cfg: TransformerConfig

            @nn.compact
            def __call__(self, carry, _):
                y = block_cls(self.cfg, self.cfg.kinds[0], is_training=train,
                              name="block")(carry, positions, None, segment_ids)
                return y, None

        scanned = nn.scan(ScanBody, variable_axes={"params": 0}, split_rngs={"params": True}, length=cfg.n_layers,
                          metadata_params={nn.PARTITION_NAME: "layers"})
        x, _ = scanned(cfg, name="layers")(x, None)
        return x


_SOWN = ("losses", "intermediates")  # collections a block may write (``moe/layer.py``)

_BLOCK_HOOK: contextvars.ContextVar = contextvars.ContextVar("transformer_block_hook", default=None)


@contextlib.contextmanager
def block_hook(hook):
    """While a ``Transformer`` is traced inside, its loop over layers asks
    ``hook(paths, layers, sows, i, x)`` before each block it applies without
    a KV cache: every layer's path in the parameter tree, its parameters and
    whether it may sow (lists: the hook may replace the parameters of layers
    still to come), which layer this is, and the activations it is about to
    take. The answer is ``(wrap, x)``: the activations to feed it, and
    ``block_fn``'s ``wrap`` for it or None. Layers that are to share one
    trace get the same ``wrap`` object.

    The token embedding's look-up is offered as ``hook.look_up(path, table,
    ids, sows)``: the answer is ``table[ids]`` or None. The loss head is
    offered as ``hook.head(paths, leaves, fn, vocab_dim, sows)``: its
    parameters' paths in the tree, the parameters (the weight first, then a
    bias), ``fn(leaves, hidden, labels, vocab_axis=None)`` that gives the
    summed loss and the count of tokens, each of shape (1,), and which
    dimension of the weight is the vocabulary. The answer is None, or
    ``run(hidden, labels)`` whose results, summed, are ``fn``'s.

    This is how a trainer runs a region some other way than XLA's
    partitioner would without the model knowing how
    (``runtime/zero/overlap.py``)."""
    token = _BLOCK_HOOK.set(hook)
    try:
        yield
    finally:
        _BLOCK_HOOK.reset(token)


def block_fn(cfg: TransformerConfig, kind: Tuple[str, str], train: bool, remat: bool, wrap=None):
    """One kind of block as ONE traced function of (the layer's parameters,
    activations, positions, its KV cache, segment ids) ->
    ((activations, new cache), what the block sowed).

    ``jax.jit`` keys its trace on the abstract arguments, so every layer of
    the kind after the first reuses the jaxpr: the block's Python body (flax
    and all) runs once a kind, and each further layer replays the cached
    equations into the program (``inline=True``) with its constants shared.
    Why replayed and not called: XLA's TPU pipeline inlines a computation
    that has ONE call site before it partitions and keeps one with several,
    then partitions that computation once, as a function, without its callers
    in view. On four chips with ZeRO-3 that chose other collectives for the
    sixteen-fold block and cost 9% of the step (``PERF.md``, PR 27). Inlined
    at trace time the program is, equation for equation, the unrolled one.
    The v2 runner, whose programs are not partitioned by XLA, calls its layer
    (``inference/v2/model_runner.py`` ``_stack_body``).

    Built once a program (a call of ``Transformer.__call__``), never kept:
    what a trace reads of the process (the op registry, the mesh topology) is
    read as often as before. A block draws nothing today (no dropout, no
    router jitter); what it may draw later comes in as a key argument, as
    what it sows goes out. ``wrap`` takes the function and returns one of
    the same signature that runs the block some other way (``block_hook``);
    it is traced once a kind all the same."""
    block = Block(cfg, kind, is_training=train)

    def apply(params, x, positions, kv_cache, segment_ids):
        # the Python body runs once a trace, not once a call: its count is the kinds of block a program traced. What a
        # block does outside its parts (the residual adds) falls to this region
        with region("block", site="train"):
            out, sown = block.apply({"params": params}, x, positions, kv_cache, segment_ids, mutable=_SOWN)
        return (out if kv_cache is not None else (out, None)), sown

    fn = wrap(apply) if wrap is not None else apply
    if remat:
        # a hybrid block keeps, by name, its kernels' outputs and every projection's result, so its backward makes again
        # only the elementwise work between them (``remat_keeps``); any other kind keeps its inputs alone
        keeps = remat_keeps(kind)
        fn = jax.checkpoint(fn, policy=jax.checkpoint_policies.save_only_these_names(*keeps)) if keeps else jax.checkpoint(fn)
    return jax.jit(fn, inline=True)


def remat_keeps(kind: Tuple[str, str]) -> Tuple[str, ...]:
    """The names a checkpointed block of this kind keeps (``block_fn``'s policy); none: plain ``jax.checkpoint``, which
    keeps the block's inputs alone.

    The rule for a hybrid block (a ``kda``, ``gdn``, ``mla`` or ``sparse`` mixer, or a routed FFN): its backward runs no kernel, no
    product over or onto the model width, no top-k and no sort a second time. Kept by name are the kernels' outputs (the
    scan's with its states and inverses, the flash call's with its row statistics), every projection's result (``SAVED``)
    and the routed layer's scores, choice, sorted rows and grouped products; what lies between them is elementwise (and
    the low-rank gates' second products, over 128) and is made again.

    What it costs (``PERF.md`` section 6, PR 40): the projections are kept for every layer at once, not inside one
    layer's peak, so they grow with depth and tokens: 0.16 (``gdn``), 0.29 (``mla``) and 0.30 GB (``kda``, routed FFNs
    with a shared expert) of the step's temporaries a layer at 8,192 tokens, for 7-10% more tokens a second at 4 to 6
    layers. Qwen3-Next's four layers at 16,384 tokens, which the kernels' outputs alone let compile for a 16 GB chip,
    are refused with them by 2.0 GB. Where a step is refused, the names to give up are the widest a millisecond saved,
    by the block's own shapes: latent attention's assembled k and v (``kv_b_proj`` contracts the latent's 512, a quarter
    of the width: keep the latent alone), then the shared and dense FFN's gate and up (two values of ``d_ff`` a token for
    two products), then the mixers' q/k/v; the gates', the router's and the latent's few MB stay to the last.

    A kept value is rounded to the dtype the model states: ``jax.checkpoint`` puts a ``reduce_precision`` on every
    residual's producer, so forward and backward read the same number, where XLA's excess precision may carry a value
    that is made again on in float32 (``xla_allow_excess_precision``); the gradients are those of the block without a
    checkpoint."""
    if kind[0] not in ("kda", "gdn", "mla", "sparse") and kind[1] != "routed":
        return ()
    from ..moe.sharded_moe import SAVED as routed_ffn
    from ..ops.kda import SAVED as kda_scan
    from ..ops.pallas.flash_attention import SAVED as flash_attention
    from ..ops.indexed_attention import SAVED as sparse_attention

    # a sparse mixer's own: the choice, its attention call's output and row statistics, the index loss's cotangent
    return (kda_scan, routed_ffn, flash_attention, SAVED) + ((sparse_attention,) if kind[0] == "sparse" else ())


def cross_entropy_loss(logits: jnp.ndarray, labels: jnp.ndarray, ignore_index: int = -100) -> jnp.ndarray:
    """Mean CE over non-ignored positions; logits fp32 (B,S,V), labels (B,S)."""
    valid = labels != ignore_index
    safe_labels = jnp.where(valid, labels, 0)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, safe_labels[..., None], axis=-1)[..., 0]
    nll = (logz - gold) * valid
    return jnp.sum(nll) / jnp.maximum(jnp.sum(valid), 1)


def _head_sums(leaves, hidden, labels, dtype, vd_layout, vocab_axis=None):
    """The loss head: the summed token cross-entropy and the count of tokens
    that are not ignored, each of shape (1,). ``vocab_axis``: as
    ``fused_cross_entropy_sums`` has it, the weight being this device's
    slice of the vocabulary and a bias that slice's or whole."""
    from ..ops.fused_ce import fused_cross_entropy_sums

    w, bias = leaves[0].astype(dtype), leaves[1] if len(leaves) > 1 else None
    n_own = w.shape[0 if vd_layout else 1]
    if vocab_axis is not None and bias is not None and bias.shape[0] != n_own:
        bias = jax.lax.dynamic_slice_in_dim(bias, jax.lax.axis_index(vocab_axis) * n_own, n_own)
    total, count = fused_cross_entropy_sums(hidden, w, labels, vd_layout=vd_layout, bias=bias, vocab_axis=vocab_axis)
    return total[None], count[None]


def _sown(intermediates, name):
    return [leaf for path, leaf in jax.tree_util.tree_leaves_with_path(intermediates)
            if any(getattr(k, "key", None) == name for k in path)]


def _count_sparse(counts):
    from ..telemetry.registry import get_registry

    reg = get_registry()
    reg.counter("sparse_keys_chosen_total").inc(float(counts[:, 0].sum()))
    reg.counter("sparse_keys_visible_total").inc(float(counts[:, 1].sum()))
    reg.gauge("sparse_index_loss").set(float(counts[:, 2].mean()))


def _index_loss(intermediates):
    """The sum over the sparse layers of their indexer's loss (0.0 where none chose: a sequence no longer than
    ``index_topk``); a layer's (chosen pairs, visible pairs, loss) leave the step program for the registry
    (``telemetry/device_counts.py``: an output of the step, no host callback)."""
    from ..telemetry import device_counts

    losses, keys = _sown(intermediates, "index_loss"), _sown(intermediates, "sparse_keys")
    if not losses:
        return 0.0
    device_counts.report("sparse_keys", jnp.stack([jnp.concatenate([k, l[None]]) for k, l in zip(keys, losses)]), _count_sparse)
    return sum(losses)


class CausalLM:
    """Binds a Transformer to the engine's ``loss_fn(params, batch, rng)`` contract.

    Batch convention: dict with ``input_ids`` (B,S) int32 and optional
    ``labels`` (shifted internally if absent).
    """

    def __init__(self, cfg: TransformerConfig):
        self.cfg = cfg
        self.module = Transformer(cfg)

    def init(self, rng, example_batch) -> Dict:
        from ..utils.init_on_device import on_device_init

        return on_device_init(lambda: self.module.init(rng, example_batch["input_ids"])["params"])()

    def apply(self, params, input_ids, **kwargs):
        return self.module.apply({"params": params}, input_ids, **kwargs)

    def loss_fn(self, params, batch, rng=None) -> jnp.ndarray:
        from ..ops.fused_ce import fused_cross_entropy

        input_ids = batch["input_ids"]
        pld_theta = batch.get("pld_theta")  # injected by the engine when PLD is on
        extra = {}
        if self.cfg.type_vocab_size > 0 and "token_type_ids" in batch:
            extra["token_type_ids"] = batch["token_type_ids"]
        if pld_theta is not None:
            if rng is None:
                raise ValueError("progressive layer drop needs the engine's step rng")
            extra["pld_theta"] = pld_theta
            extra["rngs"] = {"pld": rng}
        cfg = self.cfg
        if cfg.tie_embeddings:
            head = (("wte",),) + ((("mlm_bias",),) if cfg.mlm_head else ())
        else:
            head = (("lm_head", "kernel"),) + ((("lm_head", "bias"),) if cfg.lm_head_bias else ())
        leaves = tuple(functools.reduce(lambda tree, name: tree[name], path, params) for path in head)
        index_loss = 0.0
        if cfg.sows:
            hidden, mods = self.module.apply({"params": params}, input_ids, return_hidden=True,
                                             mutable=_SOWN, **extra)
            aux_leaves = jax.tree_util.tree_leaves(mods.get("losses", {}))
            aux = sum(jnp.sum(l) for l in aux_leaves) if aux_leaves else 0.0
            if any(ffn == "routed" for _, ffn in cfg.kinds):
                from ..moe.layer import report_rows

                report_rows(mods.get("intermediates", {}))  # the routed layers' rows: an output of the step
            if any(mixer == "sparse" for mixer, _ in cfg.kinds):
                index_loss = _index_loss(mods.get("intermediates", {}))
        else:
            hidden = self.apply(params, input_ids, return_hidden=True, **extra)
            aux = 0.0
        with region("head"):
            w = leaves[0].astype(cfg.dtype)
            if "labels" in batch:
                labels = batch["labels"]
            else:
                # shift left; keep S intact (last position ignored) so the fused
                # CE's sequence chunking stays aligned
                labels = jnp.concatenate(
                    [input_ids[:, 1:], jnp.full((input_ids.shape[0], 1), -100, input_ids.dtype)], axis=1)
            hook = _BLOCK_HOOK.get()
            by_hook = None
            if hook is not None:
                by_hook = hook.head(head, leaves, functools.partial(_head_sums, dtype=cfg.dtype, vd_layout=cfg.tie_embeddings),
                                    0 if cfg.tie_embeddings else 1, cfg.sows)
            if by_hook is None:
                ce = fused_cross_entropy(hidden, w, labels, vd_layout=cfg.tie_embeddings,
                                         bias=leaves[1] if len(leaves) > 1 else None)
            else:  # a share of the sum and of the count from each device
                total, count = by_hook(hidden, labels)
                ce = jnp.sum(total) / jnp.maximum(jnp.sum(count), 1)
            # the indexer's own loss adds its gradient, which reaches the indexer's leaves alone, and not its value:
            # the step's loss stays the language model's (the value leaves the step as a device count)
            return ce + self.cfg.moe_aux_loss_coef * aux + (index_loss - jax.lax.stop_gradient(index_loss))

    def to_pipeline(self, num_stages: int, params=None, rng=None, example_batch=None):
        """Split the model into (embed, S stacked stages, head) for the
        pipeline engine. Stage params get a leading stage dim sharded over
        the ``pipe`` mesh axis; each stage runs n_layers/num_stages blocks.

        ``params``: existing parameter pytree to restructure (preferred);
        otherwise freshly initialized from ``rng`` + ``example_batch``.
        Returns (pipe_params, embed_fn, stage_fn, head_loss_fn, rules);
        ``embed_fn``/``head_loss_fn`` receive the shared non-stage param
        groups ``{"embed", "head"}`` so tied embeddings (reference
        ``TiedLayerSpec``, ``pipe/module.py:77``) are ONE leaf used by
        both ends — the compiler sums its two grad contributions, which is
        the reference's tied-grad allreduce (``pipe/engine.py:264``).
        """
        cfg = self.cfg
        if cfg.n_layers % num_stages != 0:
            raise ValueError(f"n_layers={cfg.n_layers} must divide evenly into {num_stages} pipeline stages")
        if cfg.scan_layers:
            raise ValueError("disable scan_layers for pipeline (stages are stacked instead)")
        if not cfg.softmax_only:
            raise NotImplementedError("kda, gdn, mla, sparse and routed layers are not pipeline-partitionable yet: the stages' stacking "
                                      "takes softmax attention and dense or capacity-gated MoE blocks")
        if cfg.mlm_head or cfg.type_vocab_size > 0:
            raise NotImplementedError("BERT-style models (mlm_head / token-type embeddings) are not "
                                      "pipeline-partitionable (the MLM head and segment embeddings are "
                                      "not part of the pipelined embed/loss functions)")
        layers_per_stage = cfg.n_layers // num_stages

        # Per-layer heterogeneity (MoE slots, sliding windows) pipelines by
        # stacking: sub-layer j of every stage shares one block program, so
        # the static per-layer metadata at global index s*lps+j must agree
        # across stages s. MoE (every moe_layer_freq-th block, reference
        # moe/layer.py:90 under pipe/module.py:86) aligns iff
        # layers_per_stage % moe_layer_freq == 0.
        if cfg.moe_num_experts > 0 and cfg.layer_kinds is None:
            freq = max(1, cfg.moe_layer_freq)
            if layers_per_stage % freq != 0:
                raise ValueError(
                    f"MoE x pipeline needs a stage-uniform expert pattern: layers_per_stage="
                    f"{layers_per_stage} must be a multiple of moe_layer_freq={freq} "
                    f"(pick num_stages so each stage holds whole MoE periods)")
        # sliding windows align iff each sub-layer's window is identical
        # across stages (gpt-neo's alternating global/local pattern aligns
        # whenever layers_per_stage is even; qwen2 suffix windows only when
        # the suffix starts on a stage boundary AND covers whole stages)
        kind_per_sub = []
        for j in range(layers_per_stage):
            ks = {cfg.kinds[s * layers_per_stage + j] for s in range(num_stages)}
            if len({k[0] for k in ks}) > 1:
                raise NotImplementedError(
                    f"per-layer window pattern is not stage-uniform (sub-layer {j} sees mixers {sorted(k[0] for k in ks)} "
                    f"across stages); choose num_stages so the window pattern repeats per stage")
            if len(ks) > 1:
                raise ValueError(f"MoE x pipeline needs a stage-uniform expert pattern: sub-layer {j} is {sorted(ks)} "
                                 f"across stages")
            kind_per_sub.append(ks.pop())

        if params is None:
            params = self.init(rng if rng is not None else jax.random.PRNGKey(0), example_batch)

        # flax auto-names the module-level norms in creation order: the
        # embedding norm (bloom) is created before the blocks, the final
        # norm after them; layernorm_np (olmo) creates no params at all
        auto_norm_keys = sorted((k for k in params if k.rsplit("_", 1)[0] in ("LayerNorm", "RMSNorm")),
                                key=lambda k: int(k.rsplit("_", 1)[1]))
        embed_norm_key = auto_norm_keys.pop(0) if (cfg.embedding_norm and auto_norm_keys) else None

        embed_params = {"wte": params["wte"]}
        if cfg.pos_emb == "learned":
            embed_params["wpe"] = params["wpe"]
        if embed_norm_key is not None:
            embed_params[embed_norm_key] = params[embed_norm_key]
        # stack block params: sub_j leaf -> (S, ...) over stages
        stages = {}
        for j in range(layers_per_stage):
            per_stage = [params[f"layer_{s * layers_per_stage + j}"] for s in range(num_stages)]
            structs = {jax.tree_util.tree_structure(p) for p in per_stage}
            if len(structs) > 1:
                raise ValueError(f"sub-layer {j} has mismatched param structure across stages "
                                 f"(per-layer heterogeneity must be stage-uniform): {structs}")
            stages[f"sub_{j}"] = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs, 0), *per_stage)
        head_params = {k: v for k, v in params.items()
                       if not (k.startswith("layer_") or k in ("wte", "wpe") or k == embed_norm_key)}
        pipe_params = {"embed": embed_params, "stages": stages, "head": head_params}

        # one block program per sub-layer: sub-layer j reproduces the global
        # MoE slot pattern (given the divisibility check above) and carries
        # the stage-uniform window
        blocks = [Block(cfg, kind_per_sub[j]) for j in range(layers_per_stage)]
        has_moe = cfg.moe_num_experts > 0
        norm_key = [k for k in head_params if "Norm" in k]
        paramless_norm = cfg.norm == "layernorm_np"

        def embed_fn(ps, input_ids):
            ep = ps["embed"]
            B, S = input_ids.shape
            positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
            x = ep["wte"][input_ids].astype(cfg.dtype)
            if cfg.embed_scale:  # gemma normalizer
                x = x * jnp.asarray(cfg.d_model**0.5, cfg.dtype)
            if cfg.pos_emb == "learned":
                x = x + ep["wpe"][positions].astype(cfg.dtype)
            if cfg.embedding_norm:  # bloom word_embeddings_layernorm
                if embed_norm_key is not None:
                    x = make_norm(cfg).apply({"params": ep[embed_norm_key]}, x)
                else:
                    x = make_norm(cfg).apply({"params": {}}, x)
            return x

        def stage_fn(sp, x):
            B, S = x.shape[0], x.shape[1]
            positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
            aux = jnp.zeros((), jnp.float32)
            for j in range(layers_per_stage):
                if blocks[j].moe:
                    x, mods = blocks[j].apply({"params": sp[f"sub_{j}"]}, x, positions, mutable=_SOWN)
                    leaves = jax.tree_util.tree_leaves(mods.get("losses", {}))
                    aux = aux + sum(jnp.sum(l).astype(jnp.float32) for l in leaves)
                else:
                    x = blocks[j].apply({"params": sp[f"sub_{j}"]}, x, positions)
            if has_moe:
                # pre-scaled: the pipeline engine adds this straight into the
                # loss (and seeds its cotangent with 1.0 on the bwd clock)
                return x, aux * cfg.moe_aux_loss_coef
            return x

        stage_fn.has_aux = has_moe

        def head_loss_fn(ps, x, labels_or_ids, labels_are_shifted: bool):
            from ..ops.fused_ce import fused_cross_entropy

            hp = ps["head"]
            if cfg.norm_scheme != "post":  # post-LN blocks already end normalized
                if paramless_norm:  # olmo: final norm has no params
                    x = make_norm(cfg).apply({"params": {}}, x)
                elif norm_key:
                    x = make_norm(cfg).apply({"params": hp[norm_key[0]]}, x)
            if labels_are_shifted:
                labels = labels_or_ids
            else:
                ids = labels_or_ids
                labels = jnp.concatenate([ids[:, 1:], jnp.full((ids.shape[0], 1), -100, ids.dtype)], axis=1)
            if cfg.tie_embeddings:
                return fused_cross_entropy(x, ps["embed"]["wte"].astype(cfg.dtype), labels, vd_layout=True)
            return fused_cross_entropy(x, hp["lm_head"]["kernel"].astype(cfg.dtype), labels, vd_layout=False,
                                       bias=hp["lm_head"]["bias"] if cfg.lm_head_bias else None)

        base_rules = self.partition_rules()
        rules = [(("stages",) + key, P(*(("pipe",) + tuple(spec)))) for key, spec in base_rules]
        rules += [(("stages",), P("pipe"))]
        rules += base_rules
        return pipe_params, embed_fn, stage_fn, head_loss_fn, rules

    def init_kv_caches(self, batch_size: int, max_len: int, dtype=None):
        """Preallocated per-layer KV caches for incremental decoding."""
        cfg = self.cfg
        dtype = dtype or cfg.dtype
        zeros = lambda: jnp.zeros((batch_size, max_len, cfg.kv_heads, cfg.head_dim), dtype)
        return [(zeros(), zeros(), jnp.asarray(0, jnp.int32)) for _ in range(cfg.n_layers)]

    def partition_rules(self):
        """(path-substring tuple, PartitionSpec) TP sharding rules — the
        AutoTP-analogue metadata (column-parallel QKV/up, row-parallel o/down,
        vocab-sharded embeddings). Paths are flax param path tuples."""
        from ..moe.layer import MOE_PARTITION_RULES

        return list(MOE_PARTITION_RULES) + [
            (("wte",), P("tensor", None)),
            (("wpe",), P(None, None)),
            (("q_proj", "kernel"), P(None, "tensor", None)),
            (("k_proj", "kernel"), P(None, "tensor", None)),
            (("v_proj", "kernel"), P(None, "tensor", None)),
            (("o_proj", "kernel"), P("tensor", None, None)),
            (("kv_b_proj", "kernel"), P(None, "tensor", None)),  # mla: heads expanded from the latent
            (("kv_a_proj", "kernel"), P(None, None)),
            (("gate_proj", "kernel"), P(None, "tensor")),
            (("up_proj", "kernel"), P(None, "tensor")),
            (("down_proj", "kernel"), P("tensor", None)),
            # column-parallel biases split with their kernel's output dim
            # (GPT-2-class models; row-parallel o/down biases stay whole
            # and add once, after the partial sums reduce)
            (("q_proj", "bias"), P("tensor", None)),
            (("k_proj", "bias"), P("tensor", None)),
            (("v_proj", "bias"), P("tensor", None)),
            (("gate_proj", "bias"), P("tensor")),
            (("up_proj", "bias"), P("tensor")),
            (("lm_head", "kernel"), P(None, "tensor")),
        ]


# -------------------- presets --------------------
def gpt2_tiny(**kw) -> TransformerConfig:
    return TransformerConfig(vocab_size=1024, n_layers=2, n_heads=4, d_model=64, max_seq_len=256, **kw)


def gpt2_125m(**kw) -> TransformerConfig:
    return TransformerConfig(vocab_size=50257, n_layers=12, n_heads=12, d_model=768, max_seq_len=1024, **kw)


def gpt2_1_3b(**kw) -> TransformerConfig:
    return TransformerConfig(vocab_size=50257, n_layers=24, n_heads=32, d_model=2048, max_seq_len=1024, **kw)


def llama_tiny(**kw) -> TransformerConfig:
    return TransformerConfig(vocab_size=1024, n_layers=2, n_heads=4, n_kv_heads=2, d_model=64, max_seq_len=256,
                             norm="rmsnorm", activation="swiglu", pos_emb="rope", tie_embeddings=False, **kw)


def llama2_7b(**kw) -> TransformerConfig:
    return TransformerConfig(vocab_size=32000, n_layers=32, n_heads=32, d_model=4096, d_ff=11008, max_seq_len=4096,
                             norm="rmsnorm", activation="swiglu", pos_emb="rope", tie_embeddings=False, **kw)


def llama3_8b(**kw) -> TransformerConfig:
    """Llama-3.1-8B geometry: GQA 4:1, theta 5e5, banded rope scaling."""
    return TransformerConfig(vocab_size=128256, n_layers=32, n_heads=32, n_kv_heads=8, d_model=4096, d_ff=14336,
                             max_seq_len=131072, norm="rmsnorm", activation="swiglu", pos_emb="rope",
                             rope_theta=500000.0, rope_scaling="llama3", rope_factor=8.0,
                             rope_orig_max_seq=8192, tie_embeddings=False, **kw)
