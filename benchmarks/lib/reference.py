"""Plain reference of the decoder family both configurations belong to
(pre-norm blocks, rotary positions in the rotate-half layout, grouped-query
causal attention with an optional sliding window, SwiGLU, tied or untied
head), in straightforward ``jax.numpy``: no kernels, no cache, no batching
tricks. It shares nothing with ``deepspeed_tpu/models/transformer.py`` but the
names of the parameter tree it is handed.

``dtype=float32`` is the truth (weights upcast leaf by leaf, matmuls at the
highest precision); ``dtype=bfloat16`` is the plain low-precision path the
float32-referenced rule measures the program against. One layer is one jitted
call, so the float32 copy of a layer's weights never outlives the layer.
"""

import functools

import jax
import jax.numpy as jnp


def _norm(x, scale, kind, eps):
    x32 = x.astype(jnp.float32)
    if kind == "rmsnorm":
        y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
        return (y * scale.astype(jnp.float32)).astype(x.dtype)
    if kind == "layernorm_np":  # OLMo: no scale, no bias
        mu = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.mean((x32 - mu) ** 2, axis=-1, keepdims=True)
        return ((x32 - mu) * jax.lax.rsqrt(var + eps)).astype(x.dtype)
    raise ValueError(f"no plain reference for norm {kind!r}")


def _rope(x, positions, theta):
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[..., None] * inv  # (B, S, d/2)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., : d // 2].astype(jnp.float32), x[..., d // 2:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


@functools.partial(jax.jit, static_argnames=("m", "dtype"))
def _layer(p, x, positions, m, dtype):
    norm_kind, eps, theta, window = m
    w = lambda leaf: leaf.astype(dtype)
    B, S, _ = x.shape
    scale1 = p.get("RMSNorm_0", {}).get("scale")
    scale2 = p.get("RMSNorm_1", {}).get("scale")
    h = _norm(x, scale1, norm_kind, eps)
    q = jnp.einsum("bsd,dhk->bshk", h, w(p["attn"]["q_proj"]["kernel"]))
    k = jnp.einsum("bsd,dhk->bshk", h, w(p["attn"]["k_proj"]["kernel"]))
    v = jnp.einsum("bsd,dhk->bshk", h, w(p["attn"]["v_proj"]["kernel"]))
    q, k = _rope(q, positions, theta), _rope(k, positions, theta)
    H, KVH, D = q.shape[2], k.shape[2], q.shape[3]
    k = jnp.repeat(k, H // KVH, axis=2)
    v = jnp.repeat(v, H // KVH, axis=2)
    s = jnp.einsum("bqhk,bthk->bhqt", q, k).astype(jnp.float32) / jnp.sqrt(jnp.float32(D))
    qi, ti = positions[:, None, :, None], positions[:, None, None, :]
    keep = ti <= qi
    if window is not None:
        keep = keep & (ti > qi - window)
    s = jnp.where(keep, s, -1e30)
    a = jax.nn.softmax(s, axis=-1).astype(dtype)
    o = jnp.einsum("bhqt,bthk->bqhk", a, v)
    x = x + jnp.einsum("bqhk,hkd->bqd", o, w(p["attn"]["o_proj"]["kernel"]))
    h = _norm(x, scale2, norm_kind, eps)
    gate = h @ w(p["mlp"]["gate_proj"]["kernel"])
    up = h @ w(p["mlp"]["up_proj"]["kernel"])
    return x + (jax.nn.silu(gate) * up) @ w(p["mlp"]["down_proj"]["kernel"])


@functools.partial(jax.jit, static_argnames=("norm_kind", "eps", "dtype", "tied"))
def _head(params_top, x, norm_kind, eps, dtype, tied):
    scale = params_top.get("RMSNorm_0", {}).get("scale")
    h = _norm(x, scale, norm_kind, eps)
    wmat = params_top["wte"].astype(dtype).T if tied else params_top["lm_head"]["kernel"].astype(dtype)
    return (h @ wmat).astype(jnp.float32)


def decoder_logits(params, ids, published: dict, norm_kind: str, dtype=jnp.float32, window=None):
    """(B, S, V) float32 logits of the plain forward pass over ``ids`` (B, S)."""
    eps = float(published.get("rms_norm_eps", 1e-5))
    theta = float(published["rope_theta"])
    tied = bool(published.get("tie_word_embeddings"))
    ids = jnp.asarray(ids, jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(ids.shape[1], dtype=jnp.int32), ids.shape)
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["wte"], ids, axis=0).astype(dtype)
        for i in range(int(published["num_hidden_layers"])):
            x = _layer(params[f"layer_{i}"], x, positions, m=(norm_kind, eps, theta, window), dtype=dtype)
        top = {k: v for k, v in params.items() if not k.startswith("layer_")}
        return _head(top, x, norm_kind=norm_kind, eps=eps, dtype=dtype, tied=tied)


def causal_lm_loss(logits, ids):
    """Mean next-token cross-entropy over all positions but the last."""
    logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(logp, jnp.asarray(ids)[:, 1:, None], axis=-1)
    return -jnp.mean(picked)


F32_HEADROOM = 2.5  # chip_smoke.py's float32-referenced rule, copied


def f32_rule(ours, plain, truth, floor=1e-3):
    """Both contestants are bf16, so each is judged against a float32
    computation of the same math; ours fails only if its error clearly exceeds
    the plain bf16 path's own. A structural fault is orders of magnitude off."""
    err = lambda a: float(jnp.max(jnp.abs(jnp.asarray(a, jnp.float32) - jnp.asarray(truth, jnp.float32))))
    err_ours, err_plain = err(ours), err(plain)
    return err_ours, err_plain, bool(err_ours <= F32_HEADROOM * max(err_plain, floor))
