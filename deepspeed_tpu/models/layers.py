"""The layers every kind of block shares, above the configuration and below the kinds' own modules: the norms, the
rotation tables, and the two oldest kinds, softmax attention over one head size (``full`` and ``window``) and the dense
FFN. What a kind's record says is ``LayerKind`` (``layer_kind.py``, which ``moe/`` mixes in too)."""

import functools
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from ..layer_kind import LayerKind
from ..ops.attention import attention
from ..ops.pallas.flash_attention import SAVED as FLASH_SAVED, TILES_A_TRIP
from ..telemetry.tracing import region
from .config import GATED, TransformerFields

# The name a projection's result carries for a checkpoint policy: what a product over the model width gives (a mixer's
# q/k/v/gate projections, the dense FFN's gate and up), what one onto it gives where a backward reads it (the mixer's
# output added to the block's input), or a value after such a product from which the backward's needs follow elementwise.
# A checkpointed hybrid block keeps it (``remat_keeps``), so its backward makes no such product a second time; outside such
# a policy (serving, ``remat: false``, a ``full``/``dense`` block, whose policy lists its kernel's name alone)
# ``checkpoint_name`` is an identity
SAVED = "projection"


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


@functools.lru_cache(maxsize=32)
def _rope_partners(D: int, rd: int, offset: int, style: str) -> np.ndarray:
    """The (D, D) matrix of zeros and ones that brings every rotated lane its partner: ``(x @ R)[..., j]`` is ``x`` at the
    other lane of ``j``'s pair (``neox``: ``(i, i + rd/2)``; ``gptj``: ``(2i, 2i + 1)``) for ``j`` in ``[offset, offset +
    rd)`` and zero elsewhere. A pair swaps, so ``R`` is its own transpose. Built once a width, span and style."""
    R = np.zeros((D, D), np.float32)
    pairs = np.arange(rd // 2)
    a, b = (2 * pairs, 2 * pairs + 1) if style == "gptj" else (pairs, pairs + rd // 2)
    R[offset + a, offset + b] = R[offset + b, offset + a] = 1.0
    return R


def _rotate(x, cos, sin, positions, rd, offset, style, back=False):
    """``x * C + (x @ R) * S`` in float32, rounded once to ``x``'s dtype: ``C`` holds a pair's cosine at both of its lanes
    and one outside the rotated span, ``S`` minus its sine at the pair's first lane, plus its sine at the second and zero
    outside (``back``: the signs the other way round, the rotation by the opposite angle). The product is exact: an
    output is ONE input times one, summed in float32 (bf16 operands as they are; any other dtype in as many passes as
    it takes). The compiler makes one fusion of it, over ``x`` at its full last axis."""
    D = x.shape[-1]
    sin = jnp.asarray(sin)  # a backward's kept table is a constant of the trace, which has no unary minus
    first, second = (sin, -sin) if back else (-sin, sin)
    if style == "gptj":
        C, S = jnp.repeat(cos, 2, axis=-1), jnp.stack([first, second], axis=-1).reshape(sin.shape[0], rd)
    else:
        C, S = jnp.concatenate([cos, cos], axis=-1), jnp.concatenate([first, second], axis=-1)
    if rd != D:
        outside = ((0, 0), (offset, D - rd - offset))
        C, S = jnp.pad(C, outside, constant_values=1.0), jnp.pad(S, outside)
    partners = jnp.einsum("...d,de->...e", x, jnp.asarray(_rope_partners(D, rd, offset, style), x.dtype),
                          preferred_element_type=jnp.float32,
                          precision=None if x.dtype == jnp.bfloat16 else jax.lax.Precision.HIGHEST)
    out = x.astype(jnp.float32) * C[positions][:, :, None, :] + partners * S[positions][:, :, None, :]
    return out.astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _rope(x, cos, sin, positions, rd, offset, style):
    return _rotate(x, cos, sin, positions, rd, offset, style)


def _rope_fwd(x, cos, sin, positions, rd, offset, style):
    return _rotate(x, cos, sin, positions, rd, offset, style), (cos, sin, positions)


def _rope_bwd(rd, offset, style, kept, g):
    # a rotation's transpose is the rotation by the opposite angle: the same pass over the cotangent, and nothing kept
    # but the tables and the positions. The tables are constants of a configuration and get no gradient
    cos, sin, positions = kept
    return _rotate(g, cos, sin, positions, rd, offset, style, back=True), None, None, None


_rope.defvjp(_rope_fwd, _rope_bwd)
ROPE_PATH = "xla"  # the form ``apply_rope`` traces: XLA's own code, one fusion a call
# for a kind's record (``LayerKind.joined``): the series that say which form of the rotation its sites traced
ROPE_FORM = {"rope": ("mixer/rope", (ROPE_PATH,), "path", {"op": "qk"})}


def rope_region(op: str = "qk"):
    """A rotating site's region, counted by the form traced (``path``) and by what is rotated (``op``: ``qk``, whole
    heads of q and k; ``mla``, a head's second part and the shared key part)."""
    return region("mixer/rope", path=ROPE_PATH, op=op)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray, positions: jnp.ndarray,
               rotary_dim: Optional[int] = None, style: str = "neox", offset: int = 0) -> jnp.ndarray:
    """x: (B,S,H,D); positions: (B,S) absolute token positions; cos, sin: (L, rotary_dim/2) tables.

    ``rotary_dim < D`` rotates only the ``rotary_dim`` dims from ``offset`` on (gpt-neox ``rotary_pct``, phi
    ``partial_rotary_factor``, gpt-j ``rotary_dim``: the leading ones; latent attention: the trailing ones); the
    rest pass through. ``style``: "neox" rotates half-split pairs (llama/neox/phi), "gptj" rotates adjacent
    interleaved pairs (gpt-j ``rotate_every_two``). One pass over ``x`` at its full width, forward and backward
    (``_rotate``); the values are ``x1 * c - x2 * s`` and ``x2 * c + x1 * s`` in float32, rounded once.
    """
    rd = x.shape[-1] if rotary_dim is None else rotary_dim
    return _rope(x, cos, sin, positions, rd, offset, style)


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
    """Softmax attention over one head size, under its name ``attn`` in the tree whatever the kind: ``full`` (every
    earlier key), ``window`` (the last ``sliding_window`` keys) and ``nope`` (``UnrotatedAttention``: every earlier key,
    q and k as they are). A layer's mask AND its rotation follow its kind, so one stack may mix them."""

    cfg: TransformerFields
    window: Optional[int] = None  # the kind ``window``: ``sliding_window`` keys; None: every earlier key
    rotates: bool = True  # False (the kind ``nope``): q and k are not rotated, whatever ``pos_emb`` says of the others
    keeps, stackable = (FLASH_SAVED, SAVED), True
    # the first-call line, a kind: how its call was traced (``op`` is the kind's own name, counted at the call below) and,
    # of a window layer's call, the window, the tiles the forward's walk visits of the square's and the walk's tile
    paths = {f"{op}_path": ("mixer/kernel", {"op": op, "pass": "fwd"}) for op in ("full", "window")}
    joined = {**TILES_A_TRIP, **ROPE_FORM, "window_keys": ("mixer/kernel", None, "window"), "window_tiles": ("mixer/kernel", None, "window_tiles"),
              "window_tile": ("mixer/kernel", None, "window_tile")}

    @classmethod
    def from_config(cls, cfg, kind):
        return cls(cfg, window=cfg.sliding_window if kind == "window" else None, name="attn")

    @property
    def op(self) -> str:
        return "window" if self.window else "full" if self.rotates else "nope"

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

        if cfg.pos_emb == "rope" and self.rotates:
            with rope_region():
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
        # the kind's own words for the call's count; the form that takes the call counts them with the path it is
        out = attention(q, k, v, causal=cfg.causal, segment_ids=segment_ids, kv_len=kv_len,
                        alibi_slopes=slopes, window=self.window, scale=cfg.attn_scale,
                        count_as={"op": self.op, **({"window": str(self.window)} if self.window else {})})
        with region("mixer/proj"):
            if cfg.attn_output_gate:
                out = out * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(out.dtype)
            init = nn.initializers.variance_scaling(cfg.sparse_out_init_scale**2, "fan_in", "truncated_normal")  # 1: flax's own
            out = nn.DenseGeneral(cfg.d_model, axis=(-2, -1), use_bias=cfg.use_attn_out_bias, name="o_proj",
                                  dtype=cfg.dtype, param_dtype=jnp.float32, kernel_init=init)(out)
        return (out, new_cache) if kv_cache is not None else out


class UnrotatedAttention(Attention):
    """The kind ``nope``: causal attention over every earlier key with no positions at all, beside layers that rotate, in
    one stack and one parameter tree (``attn``). A record of its own because the stacked forms cannot run it:
    ``inference/v2``'s runner rotates by the model's ``pos_emb``, a layer whatever its kind."""

    stackable = False
    paths, joined = {"nope_path": ("mixer/kernel", {"op": "nope", "pass": "fwd"})}, TILES_A_TRIP  # no rotation, no window: no such key

    @classmethod
    def from_config(cls, cfg, kind):
        return cls(cfg, rotates=False, name="attn")


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
            if cfg.activation in GATED:
                gate, up = wide("gate_proj"), wide("up_proj")
                h = getattr(nn, GATED[cfg.activation])(gate) * up
            else:
                h = wide("up_proj")
                if cfg.activation == "relu":
                    h = nn.relu(h)
                elif cfg.activation == "relu2":
                    h = jnp.square(nn.relu(h))
                else:  # HF "gelu" is the exact erf form; "gelu_new"/tanh is our default
                    h = nn.gelu(h, approximate=cfg.activation != "gelu_exact")
            return nn.Dense(cfg.d_model, use_bias=bias, name="down_proj", dtype=cfg.dtype, param_dtype=jnp.float32)(h)
