"""Plain reference of ``kimi-linear-48b-l5e8``: pre-norm blocks of a gated
delta-rule mixer (KDA) or latent attention without positions (MLA), a dense
SwiGLU or a sigmoid-routed FFN as the share of it this chip holds plus a shared
expert, a final RMSNorm and an untied head over the rows held. Straightforward
``jax.numpy``: the KDA state token by token (a ``lax.scan``, no chunks), MLA as
masked softmax over whole rows a few heads at a time, the routed FFN as a loop
over the held experts with a dense mask. It imports nothing of the program and
shares with it only the names of the parameter tree it is handed.

``dtype=float32`` is the truth (matmuls at the highest precision);
``dtype=bfloat16`` the plain low-precision path: weights and activations in
bf16, the recurrent state, the softmax and the router's scores in float32 as
the published description has them.

Departures from the published modelling code are the configuration's
``assumed`` block's. ``ref_cfg`` (the configuration's ``reference`` block):
``held_first`` (the first expert held here; how many is ``published[
"num_experts"]``, the router's width the parameter's), and for the controls
``layers_short`` (leave out the last n layers), ``no_decay_layer`` (run that
layer, 1-indexed, with alpha = 1) and ``low_state`` (with ``dtype=bfloat16``:
the recurrent state, the gates and the router's scores in bf16 too, the
precision below the one the description states).

So that a gradient of it fits a chip at 8192 tokens, the token scan is cut
into stretches of ``STRETCH`` tokens and a stretch's steps are recomputed in
the backward (``jax.checkpoint``), and so are a group of heads' softmax and
every layer as a whole: the same arithmetic, less of it kept.
"""

import functools

import jax
import jax.numpy as jnp

HEADS_AT_ONCE = 4  # MLA: 4 x S x S float32 scores are 1 GB at S = 8192
STRETCH = 64       # KDA: tokens between two kept states when the scan is differentiated
L2_EPS = 1e-6


def _rms(x, scale, eps):
    x32 = x.astype(jnp.float32)
    return (x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps) * scale.astype(jnp.float32)).astype(x.dtype)


def _conv_silu(x, w):
    """Depthwise causal convolution over the sequence, then SiLU: x (B, S, H, D), w (K, H, D)."""
    K, S = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0), (0, 0)))
    return jax.nn.silu(sum(padded[:, j:j + S] * w[j] for j in range(K)))


def _l2(x):
    x32 = x.astype(jnp.float32)
    return x32 * jax.lax.rsqrt(jnp.sum(x32 * x32, axis=-1, keepdims=True) + L2_EPS)


def _kda(p, h, eps, dtype, decay, low_state):
    w = lambda leaf: leaf.astype(dtype)
    f32 = dtype if low_state else jnp.float32  # the state's and the gates' type
    heads = lambda name: _conv_silu(jnp.einsum("bsd,dhk->bshk", h, w(p[f"{name}_proj"]["kernel"])), w(p[f"{name}_conv"]))
    D = p["q_conv"].shape[-1]
    q, k, v = _l2(heads("q")) * D ** -0.5, _l2(heads("k")), heads("v").astype(f32)
    h32 = h.astype(f32)
    low = jnp.einsum("bsr,rhk->bshk", h32 @ p["f_a"]["kernel"].astype(f32), p["f_b"]["kernel"].astype(f32))
    g = -jnp.exp(p["A_log"])[:, None].astype(f32) * jax.nn.softplus(low + p["dt_bias"].astype(f32))  # log alpha, (B, S, H, D)
    alpha = jnp.exp(g) if decay else jnp.ones_like(g)
    beta = jax.nn.sigmoid(h32 @ p["b_proj"]["kernel"].astype(f32))  # (B, S, H)

    def step(state, xs):  # state (B, H, d_k, d_v): S_t = (I - b k k^T) Diag(a) S_{t-1} + b k v^T; o_t = S_t^T q_t
        q_t, k_t, v_t, a_t, b_t = xs
        state = state * a_t[..., None]
        state = state + k_t[..., None] * (b_t[..., None] * (v_t - jnp.einsum("bhk,bhkv->bhv", k_t, state)))[..., None, :]
        return state, jnp.einsum("bhk,bhkv->bhv", q_t, state)

    B, S, H, _ = q.shape
    xs = tuple(jnp.moveaxis(x.astype(f32), 1, 0) for x in (q, k, v, alpha, beta))
    state0 = jnp.zeros((B, H, D, v.shape[-1]), f32)
    if S % STRETCH:
        _, o = jax.lax.scan(step, state0, xs)
    else:  # the same steps, a stretch at a time
        stretch = jax.checkpoint(lambda state, part: jax.lax.scan(step, state, part))
        _, o = jax.lax.scan(stretch, state0, tuple(x.reshape(S // STRETCH, STRETCH, *x.shape[1:]) for x in xs))
        o = o.reshape(S, *o.shape[2:])
    o = jnp.moveaxis(o, 0, 1).astype(dtype)
    gate = jax.nn.sigmoid(jnp.einsum("bsr,rhk->bshk", h @ w(p["g_a"]["kernel"]), w(p["g_b"]["kernel"])))
    return jnp.einsum("bshk,hkd->bsd", _rms(o, p["o_norm"]["scale"], eps) * gate, w(p["o_proj"]["kernel"]))


def _mla(p, h, eps, dtype):
    w = lambda leaf: leaf.astype(dtype)
    B, S, _ = h.shape
    q = jnp.einsum("bsd,dhk->bshk", h, w(p["q_proj"]["kernel"]))  # nope + rope dims a head, nothing rotated
    latent = h @ w(p["kv_a_proj"]["kernel"])
    rank = p["kv_a_norm"]["scale"].shape[0]
    nope = q.shape[-1] - (latent.shape[-1] - rank)  # a key is the expanded part and then the shared one
    kv = jnp.einsum("bsr,rhk->bshk", _rms(latent[..., :rank], p["kv_a_norm"]["scale"], eps), w(p["kv_b_proj"]["kernel"]))
    H = q.shape[2]
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(latent[:, :, None, rank:], (B, S, H, latent.shape[-1] - rank))], axis=-1)
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


def _routed(p, h, dtype, first, held, top_k, scale, low_state):
    w = lambda leaf: leaf.astype(dtype)
    x = h.reshape(-1, h.shape[-1])
    f32 = dtype if low_state else jnp.float32
    scores = jax.nn.sigmoid(x.astype(f32) @ p["gate"]["kernel"].astype(f32)).astype(jnp.float32)  # (N, all experts)
    _, idx = jax.lax.top_k(scores + p["select_bias"], top_k)
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    weights = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20) * scale
    y = _swiglu(x, w(p["shared_gate_proj"]["kernel"]), w(p["shared_up_proj"]["kernel"]), w(p["shared_down_proj"]["kernel"]))
    for e in range(held):  # what the experts held here add; the absent ones' part is left out, as in the program
        w_e = jnp.sum(jnp.where(idx == first + e, weights, 0.0), axis=-1, keepdims=True).astype(dtype)
        y = y + w_e * _swiglu(x, w(p["experts_wg"][e]), w(p["experts_wi"][e]), w(p["experts_wo"][e]))
    return y.reshape(h.shape)


@functools.partial(jax.jit, static_argnames=("kind", "m", "dtype"))
def _layer(p, x, kind, m, dtype):
    eps, first, held, top_k, scale, low_state, decay = m
    h = _rms(x, p["RMSNorm_0"]["scale"], eps)
    x = x + (_kda(p["kda"], h, eps, dtype, decay, low_state) if kind[0] == "kda" else _mla(p["mla"], h, eps, dtype))
    h = _rms(x, p["RMSNorm_1"]["scale"], eps)
    if kind[1] == "dense":
        mlp = p["mlp"]
        return x + _swiglu(h, *(mlp[n]["kernel"].astype(dtype) for n in ("gate_proj", "up_proj", "down_proj")))
    return x + _routed(p["routed"], h, dtype, first, held, top_k, scale, low_state)


@functools.partial(jax.jit, static_argnames=("eps", "dtype"))
def _head(top, x, eps, dtype):
    return (_rms(x, top["RMSNorm_0"]["scale"], eps) @ top["lm_head"]["kernel"].astype(dtype)).astype(jnp.float32)


def logits(params, ids, published, ref_cfg, dtype):
    """(B, S, rows held) float32 logits of the plain forward pass over ``ids`` (B, S)."""
    eps = float(published["rms_norm_eps"])
    lin = published["linear_attn_config"]
    layers = int(published["num_hidden_layers"]) - int(ref_cfg.get("layers_short", 0))
    m = (eps, int(ref_cfg["held_first"]), int(published["num_experts"]),
         int(published["num_experts_per_token"]), float(published["routed_scaling_factor"]),
         bool(ref_cfg.get("low_state")) and dtype != jnp.float32)
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["wte"], jnp.asarray(ids, jnp.int32), axis=0).astype(dtype)
        for i in range(layers):  # the source's layer lists are 1-indexed
            kind = ("kda" if i + 1 in lin["kda_layers"] else "mla",
                    "dense" if i < int(published["first_k_dense_replace"]) else "routed")
            layer = functools.partial(_layer, kind=kind, m=m + (ref_cfg.get("no_decay_layer") != i + 1,), dtype=dtype)
            x = jax.checkpoint(layer)(params[f"layer_{i}"], x)  # differentiated: a layer keeps its input and no more
        top = {k: v for k, v in params.items() if not k.startswith("layer_")}
        return _head(top, x, eps=eps, dtype=dtype)
