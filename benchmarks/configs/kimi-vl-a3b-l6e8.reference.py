"""Plain reference of ``kimi-vl-a3b-l6e8``: the language model of
Kimi-VL-A3B-Instruct as one chip's share of it. Pre-norm blocks of latent
attention whose shared key part is rotated by position (MLA) and a dense
SwiGLU (layer 1) or a sigmoid-routed FFN as the experts held here plus TWO
shared experts, a final RMSNorm and an untied head over the rows held.
Straightforward ``jax.numpy``: MLA as masked softmax over whole rows a few
heads at a time, the rotation written out pair by pair, the routed FFN as a
loop over the held experts with a dense mask, the two shared experts each
evaluated and added. It imports nothing of the program and shares with it
only the names of the parameter tree it is handed: the program keeps the two
shared experts as ONE SwiGLU of twice the width (columns side by side); this
file cuts that tree into the source's two and adds their outputs.

``dtype=float32`` is the truth (matmuls at the highest precision);
``dtype=bfloat16`` the plain low-precision path: weights and activations in
bf16, the norms' statistics, the softmax and the router's scores and gates in
float32 as the published modelling code has them.

Departures from the published modelling code are the configuration's
``assumed`` block's. ``ref_cfg`` (the configuration's ``reference`` block):
``held_first`` (the first expert held here; how many is ``published[
"n_routed_experts"]``, the router's width the parameter's), and for the
controls ``layers_short`` (leave out the last n layers), ``no_rope`` (rotate
nothing: latent attention without positions) and ``low_state`` (with
``dtype=bfloat16``: the norms' statistics, the router's scores and the gates
in bf16 too, the precision below the one the description states).

So that a gradient of it fits a chip at 8192 tokens a group of heads' softmax
is recomputed in the backward (``jax.checkpoint``), and so is every layer as
a whole: the same arithmetic, less of it kept.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

HEADS_AT_ONCE = 4  # 4 x S x S float32 scores are 1 GB at S = 8192


def _rms(x, scale, eps, stat=jnp.float32):
    xs = x.astype(stat)
    return (xs * jax.lax.rsqrt(jnp.mean(xs * xs, axis=-1, keepdims=True) + jnp.asarray(eps, stat)) * scale.astype(stat)).astype(x.dtype)


def _rotate_pairs(x, theta):
    """x (B, S, heads, D) at positions 0 .. S-1: the pair (2i, 2i + 1) of the
    token at t is turned by the angle t * theta^(-2i / D). The angles are
    worked out in float64 (S is static) and rounded once."""
    S, D = x.shape[1], x.shape[-1]
    angle = np.arange(S, dtype=np.float64)[:, None] * theta ** (-np.arange(0, D, 2, dtype=np.float64) / D)  # (S, D / 2)
    cos, sin = (jnp.asarray(f(angle), jnp.float32)[None, :, None, :] for f in (np.cos, np.sin))
    even, odd = x[..., 0::2].astype(jnp.float32), x[..., 1::2].astype(jnp.float32)
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1).reshape(x.shape).astype(x.dtype)


def _mla(p, h, eps, theta, dtype, stat):
    w = lambda leaf: leaf.astype(dtype)
    B, S, _ = h.shape
    q = jnp.einsum("bsd,dhk->bshk", h, w(p["q_proj"]["kernel"]))  # a head: [q_n; q_r]
    latent = h @ w(p["kv_a_proj"]["kernel"])                     # [c; k_r], ONE k_r for all heads
    rank = p["kv_a_norm"]["scale"].shape[0]
    rope = latent.shape[-1] - rank
    nope = q.shape[-1] - rope
    kv = jnp.einsum("bsr,rhk->bshk", _rms(latent[..., :rank], p["kv_a_norm"]["scale"], eps, stat), w(p["kv_b_proj"]["kernel"]))
    H = q.shape[2]
    q_r, k_r = q[..., nope:], latent[:, :, None, rank:]
    if theta is not None:
        q_r, k_r = _rotate_pairs(q_r, theta), _rotate_pairs(k_r, theta)
    q = jnp.concatenate([q[..., :nope], q_r], axis=-1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_r, (B, S, H, rope))], axis=-1)
    v = kv[..., nope:]
    keep = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]

    @jax.checkpoint
    def some_heads(qkv):  # (G, B, S, .) each
        qh, kh, vh = qkv
        s = jnp.einsum("gbqk,gbtk->gbqt", qh, kh).astype(jnp.float32) * q.shape[-1] ** -0.5
        a = jax.nn.softmax(jnp.where(keep, s, -1e30), axis=-1).astype(dtype)
        return jnp.einsum("gbqt,gbtk->gbqk", a, vh)

    G = HEADS_AT_ONCE if H % HEADS_AT_ONCE == 0 else 1
    grouped = lambda x: jnp.moveaxis(x, 2, 0).reshape(H // G, G, B, S, x.shape[-1])
    o = jax.lax.map(some_heads, (grouped(q), grouped(k), grouped(v))).reshape(H, B, S, v.shape[-1])
    return jnp.einsum("hbsk,hkd->bsd", o, w(p["o_proj"]["kernel"]))


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def _routed(p, h, dtype, first, held, top_k, scale, n_shared, stat):
    w = lambda leaf: leaf.astype(dtype)
    x = h.reshape(-1, h.shape[-1])
    scores = jax.nn.sigmoid(x.astype(stat) @ p["gate"]["kernel"].astype(stat))  # (N, all experts)
    _, idx = jax.lax.top_k(scores.astype(jnp.float32) + p["select_bias"], top_k)
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    weights = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + jnp.asarray(1e-20, stat)) * jnp.asarray(scale, stat)
    gate, up, down = (w(p[f"shared_{n}_proj"]["kernel"]) for n in ("gate", "up", "down"))
    f = gate.shape[1] // n_shared  # the program's one shared SwiGLU holds the source's n_shared side by side
    y = sum(_swiglu(x, gate[:, i * f:(i + 1) * f], up[:, i * f:(i + 1) * f], down[i * f:(i + 1) * f]) for i in range(n_shared))
    for e in range(held):  # what the experts held here add; the absent ones' part is left out, as in the program
        w_e = jnp.sum(jnp.where(idx == first + e, weights, 0), axis=-1, keepdims=True).astype(dtype)
        y = y + w_e * _swiglu(x, w(p["experts_wg"][e]), w(p["experts_wi"][e]), w(p["experts_wo"][e]))
    return y.reshape(h.shape)


@functools.partial(jax.jit, static_argnames=("dense", "m", "dtype"))
def _layer(p, x, dense, m, dtype):
    eps, theta, first, held, top_k, scale, n_shared, low_state = m
    stat = dtype if low_state else jnp.float32  # the type of the norms' statistics, the router's scores and the gates
    h = _rms(x, p["RMSNorm_0"]["scale"], eps, stat)
    x = x + _mla(p["mla"], h, eps, theta, dtype, stat)
    h = _rms(x, p["RMSNorm_1"]["scale"], eps, stat)
    if dense:
        mlp = p["mlp"]
        return x + _swiglu(h, *(mlp[n]["kernel"].astype(dtype) for n in ("gate_proj", "up_proj", "down_proj")))
    return x + _routed(p["routed"], h, dtype, first, held, top_k, scale, n_shared, stat)


@functools.partial(jax.jit, static_argnames=("eps", "dtype", "stat"))
def _head(top, x, eps, dtype, stat):
    return (_rms(x, top["RMSNorm_0"]["scale"], eps, stat) @ top["lm_head"]["kernel"].astype(dtype)).astype(jnp.float32)


def logits(params, ids, published, ref_cfg, dtype):
    """(B, S, rows held) float32 logits of the plain forward pass over ``ids`` (B, S)."""
    eps = float(published["rms_norm_eps"])
    layers = int(published["num_hidden_layers"]) - int(ref_cfg.get("layers_short", 0))
    low_state = bool(ref_cfg.get("low_state")) and dtype != jnp.float32
    m = (eps, None if ref_cfg.get("no_rope") else float(published["rope_theta"]), int(ref_cfg["held_first"]),
         int(published["n_routed_experts"]), int(published["num_experts_per_tok"]), float(published["routed_scaling_factor"]),
         int(published["n_shared_experts"]), low_state)
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["wte"], jnp.asarray(ids, jnp.int32), axis=0).astype(dtype)
        for i in range(layers):
            layer = functools.partial(_layer, dense=i < int(published["first_k_dense_replace"]), m=m, dtype=dtype)
            x = jax.checkpoint(layer)(params[f"layer_{i}"], x)  # differentiated: a layer keeps its input and no more
        top = {k: v for k, v in params.items() if not k.startswith("layer_")}
        return _head(top, x, eps=eps, dtype=dtype, stat=dtype if low_state else jnp.float32)
