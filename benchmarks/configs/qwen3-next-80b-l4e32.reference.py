"""Plain reference of ``qwen3-next-80b-l4e32``: pre-norm blocks whose mixer is
a Gated DeltaNet layer (a delta rule with one decay a head, two value heads a
key head) or, every ``full_attention_interval``-th layer, grouped-query softmax
attention with q/k norms, a quarter of each head rotated and a sigmoid output
gate; every FFN softmax-routed as the share of it this chip holds plus a shared
expert behind a sigmoid gate; a final RMSNorm and an untied head over the rows
held. Every RMSNorm but the DeltaNet output's is ``x / rms * (1 + w)``.
Straightforward ``jax.numpy``: the DeltaNet state token by token (a
``lax.scan``, no chunks), attention as masked softmax over whole rows a few
heads at a time, the routed FFN as a loop (a ``lax.scan``) over the held experts with a
dense mask. It imports nothing of the program and shares with it only the names of
the parameter tree it is handed.

``dtype=float32`` is the truth (matmuls at the highest precision);
``dtype=bfloat16`` the plain low-precision path: weights and activations in
bf16, the recurrent state, the decay, the softmaxes and the router's scores in
float32 as the published description has them.

``ref_cfg`` (the configuration's ``reference`` block): ``held_first`` (the
first expert held here; how many is ``published["num_experts"]``, the router's
width the parameter's), and for the controls ``layers_short`` (leave out the
last n layers), ``no_decay_layer`` (run that DeltaNet layer, 1-indexed, with
exp(g) = 1), ``no_output_gate`` (the attention layers without their sigmoid
gate) and ``low_state`` (with ``dtype=bfloat16``: the recurrent state, the
gates and the router's scores in bf16 too, the precision below the one the
description states).

So that a gradient of it fits a chip at 8192 tokens, the token scan is cut
into stretches of ``STRETCH`` tokens and a stretch's steps are recomputed in
the backward (``jax.checkpoint``), and so are a group of heads' softmax, a
convolution, each held expert and every layer as a whole: the same arithmetic, less of it kept.
"""

import functools

import jax
import jax.numpy as jnp

HEADS_AT_ONCE = 4  # attention: 4 x S x S float32 scores are 1 GB at S = 8192
STRETCH = 64       # DeltaNet: tokens between two kept states when the scan is differentiated
L2_EPS = 1e-6


def _rms(x, scale, eps, offset=1.0):
    x32 = x.astype(jnp.float32)
    return (x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps) * (offset + scale.astype(jnp.float32))).astype(x.dtype)


def _conv_silu(x, w):
    """Depthwise causal convolution over the sequence, then SiLU: x (B, S, H, D), w (K, H, D)."""
    K, S = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0), (0, 0)))
    return jax.nn.silu(sum(padded[:, j:j + S] * w[j] for j in range(K)))


def _l2(x):
    x32 = x.astype(jnp.float32)
    return x32 * jax.lax.rsqrt(jnp.sum(x32 * x32, axis=-1, keepdims=True) + L2_EPS)


def _deltanet(p, h, eps, dtype, decay, low_state):
    w = lambda leaf: leaf.astype(dtype)
    f32 = dtype if low_state else jnp.float32  # the state's and the gates' type
    heads = lambda name: jax.checkpoint(_conv_silu)(jnp.einsum("bsd,dhk->bshk", h, w(p[f"{name}_proj"]["kernel"])), w(p[f"{name}_conv"]))
    D = p["q_conv"].shape[-1]
    q, k, v = _l2(heads("q")) * D ** -0.5, _l2(heads("k")), heads("v").astype(f32)
    Hv = v.shape[2]
    q, k = (jnp.repeat(x, Hv // x.shape[2], axis=2) for x in (q, k))  # a key head's q and k for each of its value heads
    ba = h.astype(f32) @ p["ba_proj"]["kernel"].astype(f32)
    beta = jax.nn.sigmoid(ba[..., :Hv])  # (B, S, Hv)
    g = -jnp.exp(p["A_log"]).astype(f32) * jax.nn.softplus(ba[..., Hv:] + p["dt_bias"].astype(f32))
    alpha = jnp.exp(g) if decay else jnp.ones_like(g)

    def step(state, xs):  # state (B, H, d_k, d_v): S_t = (I - b k k^T) a S_{t-1} + b k v^T; o_t = S_t^T q_t
        q_t, k_t, v_t, a_t, b_t = xs
        state = state * a_t[..., None, None]
        state = state + k_t[..., None] * (b_t[..., None] * (v_t - jnp.einsum("bhk,bhkv->bhv", k_t, state)))[..., None, :]
        return state, jnp.einsum("bhk,bhkv->bhv", q_t, state)

    B, S = q.shape[:2]
    xs = tuple(jnp.moveaxis(x.astype(f32), 1, 0) for x in (q, k, v, alpha, beta))
    state0 = jnp.zeros((B, Hv, D, v.shape[-1]), f32)
    if S % STRETCH:
        _, o = jax.lax.scan(step, state0, xs)
    else:  # the same steps, a stretch at a time
        stretch = jax.checkpoint(lambda state, part: jax.lax.scan(step, state, part))
        _, o = jax.lax.scan(stretch, state0, tuple(x.reshape(S // STRETCH, STRETCH, *x.shape[1:]) for x in xs))
        o = o.reshape(S, *o.shape[2:])
    o = jnp.moveaxis(o, 0, 1).astype(dtype)
    z = jnp.einsum("bsd,dhk->bshk", h, w(p["z_proj"]["kernel"]))
    return jnp.einsum("bshk,hkd->bsd", _rms(o, p["o_norm"]["scale"], eps, offset=0.0) * jax.nn.silu(z), w(p["o_proj"]["kernel"]))


def _rope(x, theta, rotated):
    """The first ``rotated`` dims of each head, rotate-half within them; the rest pass."""
    S = x.shape[1]
    inv = 1.0 / (theta ** (jnp.arange(0, rotated, 2, dtype=jnp.float32) / rotated))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv  # (S, rotated / 2)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :rotated // 2].astype(jnp.float32), x[..., rotated // 2:rotated].astype(jnp.float32)
    return jnp.concatenate([(x1 * cos - x2 * sin).astype(x.dtype), (x2 * cos + x1 * sin).astype(x.dtype), x[..., rotated:]], axis=-1)


def _attention(p, h, eps, dtype, theta, rotary, gated):
    w = lambda leaf: leaf.astype(dtype)
    B, S, _ = h.shape
    qg = jnp.einsum("bsd,dhk->bshk", h, w(p["q_proj"]["kernel"]))  # a head's columns: its query, then its gate
    D = qg.shape[-1] // 2
    q, gate = qg[..., :D], qg[..., D:]
    k = jnp.einsum("bsd,dhk->bshk", h, w(p["k_proj"]["kernel"]))
    v = jnp.einsum("bsd,dhk->bshk", h, w(p["v_proj"]["kernel"]))
    rotated = int(D * rotary)
    q = _rope(_rms(q, p["q_norm"]["scale"], eps), theta, rotated)
    k = _rope(_rms(k, p["k_norm"]["scale"], eps), theta, rotated)
    H = q.shape[2]
    k, v = (jnp.repeat(x, H // x.shape[2], axis=2) for x in (k, v))
    keep = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]

    @jax.checkpoint
    def some_heads(qkv):  # (G, B, S, D) each
        qh, kh, vh = qkv
        s = jnp.einsum("gbqk,gbtk->gbqt", qh, kh).astype(jnp.float32) * D ** -0.5
        a = jax.nn.softmax(jnp.where(keep, s, -1e30), axis=-1).astype(dtype)
        return jnp.einsum("gbqt,gbtk->gbqk", a, vh)

    G = HEADS_AT_ONCE if H % HEADS_AT_ONCE == 0 else 1
    grouped = lambda x: jnp.moveaxis(x, 2, 0).reshape(H // G, G, B, S, D)
    o = jnp.moveaxis(jax.lax.map(some_heads, (grouped(q), grouped(k), grouped(v))).reshape(H, B, S, D), 0, 2)  # (B, S, H, D)
    if gated:
        o = o * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(dtype)
    return jnp.einsum("bshk,hkd->bsd", o, w(p["o_proj"]["kernel"]))


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def _routed(p, h, dtype, first, held, top_k, low_state):
    w = lambda leaf: leaf.astype(dtype)
    x = h.reshape(-1, h.shape[-1])
    f32 = dtype if low_state else jnp.float32
    probs = jax.nn.softmax((x.astype(f32) @ p["gate"]["kernel"].astype(f32)).astype(f32), axis=-1).astype(jnp.float32)  # (N, all experts)
    chosen, idx = jax.lax.top_k(probs, top_k)
    weights = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    shared = _swiglu(x, w(p["shared_gate_proj"]["kernel"]), w(p["shared_up_proj"]["kernel"]), w(p["shared_down_proj"]["kernel"]))
    y = shared * jax.nn.sigmoid((x @ w(p["shared_expert_gate"]["kernel"])).astype(jnp.float32)).astype(dtype)
    one = jax.checkpoint(lambda w_e, *mats: w_e * _swiglu(x, *mats))  # differentiated: an expert keeps its weights and no more

    def add_expert(y, held_expert):  # what one expert held here adds; the absent ones' part is left out, as in the program
        e, *mats = held_expert
        w_e = jnp.sum(jnp.where(idx == first + e, weights, 0.0), axis=-1, keepdims=True).astype(dtype)
        return y + one(w_e, *mats), None

    y, _ = jax.lax.scan(add_expert, y, (jnp.arange(held), w(p["experts_wg"]), w(p["experts_wi"]), w(p["experts_wo"])))  # one after another
    return y.reshape(h.shape)


@functools.partial(jax.jit, static_argnames=("full", "m", "dtype"))
def _layer(p, x, full, m, dtype):
    eps, first, held, top_k, theta, rotary, low_state, decay, gated = m
    h = _rms(x, p["RMSNorm_0"]["scale"], eps)
    x = x + (_attention(p["attn"], h, eps, dtype, theta, rotary, gated) if full else _deltanet(p["gdn"], h, eps, dtype, decay, low_state))
    return x + _routed(p["routed"], _rms(x, p["RMSNorm_1"]["scale"], eps), dtype, first, held, top_k, low_state)


@functools.partial(jax.jit, static_argnames=("eps", "dtype"))
def _head(top, x, eps, dtype):
    return (_rms(x, top["RMSNorm_0"]["scale"], eps) @ top["lm_head"]["kernel"].astype(dtype)).astype(jnp.float32)


def logits(params, ids, published, ref_cfg, dtype):
    """(B, S, rows held) float32 logits of the plain forward pass over ``ids`` (B, S)."""
    eps = float(published["rms_norm_eps"])
    layers = int(published["num_hidden_layers"]) - int(ref_cfg.get("layers_short", 0))
    m = (eps, int(ref_cfg["held_first"]), int(published["num_experts"]), int(published["num_experts_per_tok"]),
         float(published["rope_theta"]), float(published["partial_rotary_factor"]),
         bool(ref_cfg.get("low_state")) and dtype != jnp.float32)
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["wte"], jnp.asarray(ids, jnp.int32), axis=0).astype(dtype)
        for i in range(layers):
            full = (i + 1) % int(published["full_attention_interval"]) == 0
            # (decay, gated): each where the layer has it, so that a control compiles anew only the kind of layer it changes
            layer = functools.partial(_layer, full=full, dtype=dtype,
                                      m=m + (full or ref_cfg.get("no_decay_layer") != i + 1, not (full and ref_cfg.get("no_output_gate"))))
            x = jax.checkpoint(layer)(params[f"layer_{i}"], x)  # differentiated: a layer keeps its input and no more
        top = {k: v for k, v in params.items() if not k.startswith("layer_")}
        return _head(top, x, eps=eps, dtype=dtype)
