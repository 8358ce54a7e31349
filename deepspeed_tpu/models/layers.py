"""The layers every kind of block shares, above the configuration and below the kinds' own modules: what a kind's
record says (``LayerKind``), the norms, the rotation tables, and the two oldest kinds, softmax attention over one head
size (``full`` and ``window``) and the dense FFN."""

import functools
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from ..ops.attention import attention
from ..ops.pallas.flash_attention import SAVED as FLASH_SAVED, TILES_A_TRIP
from ..telemetry.tracing import region
from .config import TransformerFields

# The name a projection's result carries for a checkpoint policy: what a product over the model width gives (a mixer's
# q/k/v/gate projections, the dense FFN's gate and up), what one onto it gives where a backward reads it (the mixer's
# output added to the block's input), or a value after such a product from which the backward's needs follow elementwise.
# A checkpointed hybrid block keeps it (``remat_keeps``), so its backward makes no such product a second time; outside such
# a policy (serving, ``remat: false``, a ``full``/``dense`` block) ``checkpoint_name`` is an identity
SAVED = "projection"


class LayerKind:
    """A kind of layer's record: what its hosts read of it and never work out from its name. A kind IS its flax module with
    this class mixed in, saying only where it differs, under one name in the table (``transformer.py``). A mixer is called
    ``mixer(h, positions, kv_cache, segment_ids)`` and returns what ``Attention`` returns; an FFN ``ffn(h, train)``."""

    sows = ()  # the collections it may write (``Module.sow``): a model with such a layer traces its loss with them mutable
    # ``report(intermediates)`` hands what the model's layers of this kind sowed in a forward pass to ``telemetry/
    # device_counts.py`` and returns the kind's own loss (the step's loss takes its gradient and not its value) or None
    report = None
    keeps = ()  # the names its own ``checkpoint_name``s give, its kernels' among them
    hybrid = False  # a checkpointed block with it keeps, by name, what its two parts' ``keeps`` say; else its inputs alone
    # the trainer's first-call line. ``paths``: key -> (region, labels) of ``program_regions_traced_total``; the key's
    # word is ``xla`` where only ``path="xla"`` call sites rose, ``mixed``, else ``kernel`` (or ``path_words[key]``).
    # ``joined``: key -> (region, the ``path`` labels of it that may rise[, another label than ``path`` whose values they
    # are[, labels a series must also carry to be read]]): the word is those that rose, "+" between; ``None`` for the
    # labels: whatever values the sites gave (a number a site worked out, as the tiles a mask's walk visits). A model of
    # one plain kind says its joined keys too, where they rose (the flash kernels' ``tiles_a_trip_fwd``).
    # ``alone``: a model whose layers are all of one kind says nothing of kinds on that line, unless this
    paths, path_words, joined, alone = {}, {}, {}, False
    stackable = False  # the scan over layers, ``to_pipeline`` and ``inference/v2`` can run it
    # values between blocks, by name (a mixer's: no FFN has any). ``gives``: its call returns ``(out, {name: value})`` and
    # the model's loop over layers carries each value on; ``takes``: it is called with ``name=value`` of the nearest
    # earlier layer that gave the name (``layer``, the layer's own published index as an int32 scalar, is the model's to
    # give). The gradient flows back through them. A model with either is run by the unrolled loop alone
    gives, takes = (), ()
    # ``targets(cfg, input_ids)``: a kind whose OBJECTIVE is not next-token prediction over the whole row gives the loss
    # head (the hidden states' positions it runs over, their targets, a float32 weight a target, what the weighted sum is
    # divided by), all made from the ids (``CausalLM.loss_fn``); None: the model's own (every position predicts the next)
    targets = None

    @classmethod
    def from_config(cls, cfg, kind: str):
        """The module of a block of ``cfg`` for the table's name ``kind``, under its name in the parameter tree."""
        return cls(cfg, name=kind)

    def no_cache(self, kv_cache, segment_ids):
        if kv_cache is not None or segment_ids is not None:  # a training-side mixer's refusal
            raise NotImplementedError(f"a {self.name} layer takes no KV cache and no packed segments yet")


# -------------------- layers --------------------
class RMSNorm(nn.Module):
    eps: float = 1e-5
    dtype: Any = jnp.float32
    offset: bool = False  # gemma: weights zero-centered, applied as (1 + w)
    init_scale: float = 1.0  # what the weights start at (with ``offset``: zero, whatever this says)

    @nn.compact
    def __call__(self, x):
        init = nn.initializers.zeros if self.offset else nn.initializers.constant(self.init_scale)
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


def make_norm(cfg: TransformerFields):
    if cfg.norm == "rmsnorm":
        return RMSNorm(eps=cfg.norm_eps, dtype=cfg.dtype, offset=cfg.rms_offset)
    if cfg.norm == "layernorm_np":
        return LayerNormNP(eps=cfg.norm_eps, dtype=cfg.dtype)
    return LayerNorm(eps=cfg.norm_eps, dtype=cfg.dtype)


def _norm(cfg: TransformerFields, x):
    """A block's norms and the model's final one, under their region's name."""
    with region("norm"):
        return make_norm(cfg)(x)


def rope_frequencies(head_dim: int, max_len: int, theta: float) -> Tuple[jnp.ndarray, jnp.ndarray]:
    inv = 1.0 / (theta**(jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    t = jnp.arange(max_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv)  # (L, D/2)
    return jnp.cos(freqs), jnp.sin(freqs)


def scaled_rope_frequencies(cfg: TransformerFields, head_dim: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
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
def _rope_table(cfg: TransformerFields, head_dim: int) -> Tuple[np.ndarray, np.ndarray]:
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


class Attention(LayerKind, nn.Module):
    cfg: TransformerFields
    window: Optional[int] = None  # the kind ``window``: ``sliding_window`` keys; None: ``full``
    keeps, stackable, joined = (FLASH_SAVED, SAVED), True, TILES_A_TRIP

    @classmethod
    def from_config(cls, cfg, kind):
        return cls(cfg, window=cfg.sliding_window if kind == "window" else None, name="attn")

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


class MLP(LayerKind, nn.Module):
    cfg: TransformerFields
    keeps, stackable = (SAVED,), True

    @classmethod
    def from_config(cls, cfg, kind):
        return cls(cfg, name="mlp")

    @nn.compact
    def __call__(self, x, train=True):
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
