"""Plain ``jax.numpy`` forms of the hybrid layers, for the unit tests: the
same mathematics as the benchmark's reference of its hybrid configuration
(``benchmarks/configs/kimi-linear-48b-l5e8.reference.py``; the package and its
unit tests may not import ``benchmarks/``), on the program's parameter tree.
Float32 throughout; callers set the matmul precision."""

import jax
import jax.numpy as jnp

L2_EPS = 1e-6


def rms(x, scale, eps=1e-5):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def conv_silu(x, w):
    K, S = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0), (0, 0)))
    return jax.nn.silu(sum(padded[:, j:j + S] * w[j] for j in range(K)))


def l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def delta_rule(q, k, v, alpha, beta):
    """S_t = (I - b k k^T) Diag(a) S_{t-1} + b k v^T, o_t = S_t^T q_t, token by token."""
    def step(state, xs):
        q_t, k_t, v_t, a_t, b_t = xs
        state = state * a_t[..., None]
        state = state + k_t[..., None] * (b_t[..., None] * (v_t - jnp.einsum("bhk,bhkv->bhv", k_t, state)))[..., None, :]
        return state, jnp.einsum("bhk,bhkv->bhv", q_t, state)

    B, S, H, D = q.shape
    _, o = jax.lax.scan(step, jnp.zeros((B, H, D, v.shape[-1]), jnp.float32),
                        tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, alpha, beta)))
    return jnp.moveaxis(o, 0, 1)


def kda(p, h, eps=1e-5):
    heads = lambda name: conv_silu(jnp.einsum("bsd,dhk->bshk", h, p[f"{name}_proj"]["kernel"]), p[f"{name}_conv"])
    D = p["q_conv"].shape[-1]
    q, k, v = l2(heads("q")) * D ** -0.5, l2(heads("k")), heads("v")
    g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(
        jnp.einsum("bsr,rhk->bshk", h @ p["f_a"]["kernel"], p["f_b"]["kernel"]) + p["dt_bias"])
    o = delta_rule(q, k, v, jnp.exp(g), jax.nn.sigmoid(h @ p["b_proj"]["kernel"]))
    gate = jax.nn.sigmoid(jnp.einsum("bsr,rhk->bshk", h @ p["g_a"]["kernel"], p["g_b"]["kernel"]))
    return jnp.einsum("bshk,hkd->bsd", rms(o, p["o_norm"]["scale"], eps) * gate, p["o_proj"]["kernel"])


def rotate_pairs(x, theta):
    """x (B, S, heads, D) at positions 0 .. S-1: the pair (2i, 2i + 1) of token t turned by t * theta^(-2i / D)."""
    S, D = x.shape[1], x.shape[-1]
    angle = (jnp.arange(S, dtype=jnp.float32)[:, None] * theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D))[None, :, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * jnp.cos(angle) - odd * jnp.sin(angle), odd * jnp.cos(angle) + even * jnp.sin(angle)], axis=-1).reshape(x.shape)


def mla(p, h, eps=1e-5, theta=None):
    """``theta``: rotate the second part of every head's query and the one shared key part; None: no positions."""
    B, S, _ = h.shape
    q = jnp.einsum("bsd,dhk->bshk", h, p["q_proj"]["kernel"])
    latent = h @ p["kv_a_proj"]["kernel"]
    rank = p["kv_a_norm"]["scale"].shape[0]
    rope = latent.shape[-1] - rank
    nope = q.shape[-1] - rope
    kv = jnp.einsum("bsr,rhk->bshk", rms(latent[..., :rank], p["kv_a_norm"]["scale"], eps), p["kv_b_proj"]["kernel"])
    shared = latent[:, :, None, rank:]
    if theta is not None:
        q, shared = jnp.concatenate([q[..., :nope], rotate_pairs(q[..., nope:], theta)], axis=-1), rotate_pairs(shared, theta)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(shared, (B, S, q.shape[2], rope))], axis=-1)
    s = jnp.einsum("bqhk,bthk->bhqt", q, k) * q.shape[-1] ** -0.5
    keep = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    a = jax.nn.softmax(jnp.where(keep, s, -1e30), axis=-1)
    return jnp.einsum("bhqt,bthk,hkd->bqd", a, kv[..., nope:], p["o_proj"]["kernel"])


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def routed(p, h, first, top_k, scale, shared=1):
    """The part of the routed FFN the experts ``first ..`` (as many as the
    tree holds) add, as a loop over them with a dense mask, plus ``shared``
    shared experts, each a SwiGLU of its own on its columns of the tree's one."""
    x = h.reshape(-1, h.shape[-1])
    scores = jax.nn.sigmoid(x @ p["gate"]["kernel"])
    _, idx = jax.lax.top_k(scores + p["select_bias"], top_k)
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    weights = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20) * scale
    y = jnp.zeros_like(x)
    for i in range(int(shared)):
        f = p["shared_gate_proj"]["kernel"].shape[1] // int(shared)
        cols = slice(i * f, (i + 1) * f)
        y = y + swiglu(x, p["shared_gate_proj"]["kernel"][:, cols], p["shared_up_proj"]["kernel"][:, cols], p["shared_down_proj"]["kernel"][cols])
    for e in range(p["experts_wg"].shape[0]):
        w_e = jnp.sum(jnp.where(idx == first + e, weights, 0.0), axis=-1, keepdims=True)
        y = y + w_e * swiglu(x, p["experts_wg"][e], p["experts_wi"][e], p["experts_wo"][e])
    return y.reshape(h.shape)
