"""Plain reference of ``nemotron3-nano-30b-l9e8``: a decoder whose every layer is ONE part, a Mamba-2 mixer, a routed FFN
of ungated relu^2 experts behind a biased sigmoid router, or grouped-query attention without positions, as the share of
it one chip holds. Straightforward ``jax.numpy``: the state-space recurrence TOKEN BY TOKEN (a ``lax.scan``, no chunks),
the convolution as a sum of shifted copies, a masked softmax over whole rows a few heads and a band of queries at a time,
the routed FFN as a loop over the held experts with a dense mask. It imports nothing of the program and shares with it only
the names of the parameter tree it is handed.

Published layer ``i`` (``layers_here[n]``) by ``hybrid_override_pattern[i]``; every layer is ``y = x + Part(RMSNorm(x))``,
RMSNorm: ``x / sqrt(mean(x^2) + 1e-5) * w`` in float32:

    "M" (Mamba-2):  [z, xBC, dt] = h W_in       (2,688 x (4,096 + 6,144 + 64), no bias; d_inner = mamba_num_heads 64 x
                                                  mamba_head_dim 64, xBC = 4,096 + 2 x n_groups 8 x ssm_state_size 128)
        xBC = silu(conv_4(xBC) + b_c)            (depthwise, causal, conv_kernel = 4 taps, use_conv_bias)
        x (64 heads of 64), B, C (8 groups of 128; head h reads group h // 8) = split(xBC)
        delta = softplus(dt + dt_bias)           (float32, one a head and token);  A = -exp(A_log), one a head
        S_t = exp(delta_t A) S_{t-1} + delta_t x_t B_t^T    (64 x 128 a head, float32, from zero)
        y_t = S_t C_t + D x_t
        y = GroupRMSNorm_512(y * silu(z)) * w    (the gate FIRST, then the norm over each group's 4,096 / 8 channels)
        Part = y W_out                           (4,096 x 2,688, no bias)
    "E" (routed FFN):  s = sigmoid(h W_r)       (128 scores, float32)
        E(t) = the 6 largest of s[t] + b         (b: the correction bias, for the CHOICE alone: no gradient, not in the weight;
                                                  n_group 1 and topk_group 1: the grouping is a no-op)
        p[t, e] = s[t, e] / (sum_{e' in E(t)} s[t, e'] + 1e-20) * routed_scaling_factor 2.5
        Part = sum_{e in E(t), e held here} p[t, e] W2_e relu(h W1_e)^2  +  Ws2 relu(h Ws1)^2
                                                 (experts 1,856 wide, NO gate; one shared expert 3,712 wide on every token)
    "*" (attention):  q, k, v = h W_q, h W_k, h W_v   (32 / 2 / 2 heads of 128, no biases, NO rotation)
        Part = softmax(q k^T / sqrt(128) under the causal mask) v W_o    (4,096 x 2,688)

then one more RMSNorm, the untied head over the rows held, and the mean next-token cross-entropy over all positions but
the last. The embedding is not scaled.

Departures from the published model, each listed under ``assumed`` in ``nemotron3-nano-30b-l9e8.json``: the experts
``held_first .. held_first + n_routed_experts`` alone add to ``y`` (what the absent ones would add is left out, here as in
the program; the shared expert adds ONCE); the vocabulary is the slice held; no rotation in the attention layers (the
family's modelling code applies none although the row carries ``rope_theta``); the correction bias is a buffer, whatever
it holds.

``dtype=float32`` is the truth (matmuls at the highest precision); ``dtype=bfloat16`` the plain low-precision path:
weights and activations in bf16, the recurrence's state and its step, the convolution, the gated norm, the softmax's, the
norms' and the router's statistics in float32.

Beside the harness's ``logits(params, ids, published, ref_cfg, dtype)``: ``loss(logits, ids)``, ``loss_and_grads`` and
``layer_part`` (one layer's result for one share of the experts, or for all of them, with or without the shared expert:
what the test that ties the share to the model adds up).

``ref_cfg`` (the configuration's ``reference`` block): ``held_first`` (the first expert held here; how many is
``published["n_routed_experts"]``, of ``published["routed_over"]``), and for the controls ``expert_act`` (``"silu_gated"``:
``silu(u) * u`` for ``relu(u)^2``, a gated SiLU on the one product there is; ``"relu"``: no square), ``decay`` (``"none"``:
``A = 0``, the state never forgets), ``norm`` (``"before_gate"``: the group norm first, then the gate), ``skip``
(``"none"``: no ``D x``), ``choice`` (``"scores"``: the top 6 of ``s`` alone, the bias ignored), ``layers`` (how many of the
held layers are run), ``no_final_norm`` and ``low_state`` (with ``dtype=bfloat16``: the state, the step, the convolution, the
gated norm and the softmax's and router's statistics in bf16 too, the precision below the one the description states).
"""

import functools

import jax
import jax.numpy as jnp

HEADS_AT_ONCE = 4  # attention: 4 heads x 2,048 queries x 8,192 keys of float32 scores are 0.27 GB
QUERIES_AT_ONCE = 2048
STRETCH = 64  # the recurrence, differentiated: tokens between two states that are kept
NEG = -1e30


def _rms(x, scale, eps):
    x32 = x.astype(jnp.float32)
    return (x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps) * scale.astype(jnp.float32)).astype(x.dtype)


def kinds(published: dict) -> tuple:
    """"M", "E" or "*" of each layer held, from ``hybrid_override_pattern`` at the layers' published indices."""
    return tuple(published["hybrid_override_pattern"][int(i)] for i in published["layers_here"])


def _sizes(published):
    H, P, G, N = (int(published[k]) for k in ("mamba_num_heads", "mamba_head_dim", "n_groups", "ssm_state_size"))
    return H, P, G, N


def _mamba(p, h, sizes, eps, dtype, stat, controls):
    decay, norm, skip = controls
    H, P, G, N = sizes
    inner = H * P
    w = lambda leaf: leaf.astype(dtype)
    Bt, S, _ = h.shape
    zxbcdt = h @ w(p["in_proj"]["kernel"])
    z, xbc, dt = zxbcdt[..., :inner], zxbcdt[..., inner:2 * inner + 2 * G * N], zxbcdt[..., 2 * inner + 2 * G * N:]
    taps = p["conv_kernel"].astype(stat)  # (K, channels): tap j meets the token K - 1 - j back
    K = taps.shape[0]
    conv = sum(jnp.pad(xbc.astype(stat), ((0, 0), (K - 1 - j, 0), (0, 0)))[:, :S] * taps[j] for j in range(K))
    xbc = jax.nn.silu(conv + p["conv_bias"].astype(stat)).astype(dtype)
    x = xbc[..., :inner].reshape(Bt, S, H, P)
    B, C = (xbc[..., inner + n * G * N:inner + (n + 1) * G * N].reshape(Bt, S, G, N) for n in (0, 1))
    delta = jax.nn.softplus(dt.astype(stat) + p["dt_bias"].astype(stat))  # (Bt, S, H)
    A = jnp.zeros((H,), stat) if decay == "none" else -jnp.exp(p["A_log"].astype(stat))

    def step(state, xs):  # state (Bt, H, P, N)
        x_t, dt_t, b_t, c_t = xs
        b_t, c_t = (jnp.repeat(v.astype(stat), H // G, axis=1) for v in (b_t, c_t))
        state = jnp.exp(dt_t * A)[..., None, None] * state + (dt_t[..., None] * x_t.astype(stat))[..., None] * b_t[:, :, None, :]
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t)

    xs = tuple(jnp.moveaxis(v, 1, 0) for v in (x, delta, B, C))
    zero = jnp.zeros((Bt, H, P, N), stat)
    if S % STRETCH:
        _, y = jax.lax.scan(step, zero, xs)
    else:  # differentiated: a stretch keeps its first state and makes the rest again
        stretch = jax.checkpoint(lambda state, part: jax.lax.scan(step, state, part))
        _, y = jax.lax.scan(stretch, zero, tuple(v.reshape(S // STRETCH, STRETCH, *v.shape[1:]) for v in xs))
        y = y.reshape(S, Bt, H, P)
    y = jnp.moveaxis(y, 0, 1)
    if skip != "none":
        y = y + p["D"].astype(stat)[:, None] * x.astype(stat)
    y, gate = y.reshape(Bt, S, G, inner // G).astype(stat), jax.nn.silu(z.astype(stat)).reshape(Bt, S, G, inner // G)
    group_norm = lambda v: v * jax.lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True) + eps)
    y = group_norm(y) * gate if norm == "before_gate" else group_norm(y * gate)
    y = (y.reshape(Bt, S, inner) * p["norm_scale"].astype(stat)).astype(dtype)
    return y @ w(p["out_proj"]["kernel"])


def _attention(p, h, dtype, stat):
    w = lambda leaf: leaf.astype(dtype)
    B, S, _ = h.shape
    q = jnp.einsum("bsd,dhk->bshk", h, w(p["q_proj"]["kernel"]))
    k = jnp.einsum("bsd,dhk->bshk", h, w(p["k_proj"]["kernel"]))
    v = jnp.einsum("bsd,dhk->bshk", h, w(p["v_proj"]["kernel"]))
    H, D = q.shape[2:]
    k, v = (jnp.repeat(x, H // x.shape[2], axis=2) for x in (k, v))
    G = HEADS_AT_ONCE if H % HEADS_AT_ONCE == 0 else 1
    Q = QUERIES_AT_ONCE if S % QUERIES_AT_ONCE == 0 else S
    mask = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]  # (query, key): key s visible to query t iff s <= t

    @jax.checkpoint
    def some(args):  # G heads, Q queries: (G, B, Q, D) against (G, B, S, D) under (Q, S) of the mask
        qh, kh, vh, rows = args
        s = (jnp.einsum("gbqk,gbtk->gbqt", qh, kh, preferred_element_type=stat) * D ** -0.5).astype(stat)
        a = jax.nn.softmax(jnp.where(rows, s, NEG), axis=-1)
        return jnp.einsum("gbqt,gbtk->gbqk", a.astype(dtype), vh)

    heads = lambda x: jnp.moveaxis(x, 2, 0).reshape(H // G, G, B, S, D)

    def some_heads(args):
        qh, kh, vh = args
        bands = jnp.moveaxis(qh.reshape(G, B, S // Q, Q, D), 2, 0)
        o = jax.lax.map(lambda band: some((band[0], kh, vh, band[1])), (bands, mask.reshape(S // Q, Q, S)))
        return jnp.moveaxis(o, 0, 2).reshape(G, B, S, D)

    o = jax.lax.map(some_heads, (heads(q), heads(k), heads(v)))
    o = jnp.moveaxis(o.reshape(H, B, S, D), 0, 2)
    return jnp.einsum("bshk,hkd->bsd", o, w(p["o_proj"]["kernel"]))


def routing(logits, bias, top_k: int, scale: float, choice: str = "biased"):
    """The published routing: sigmoid scores, the ``top_k`` largest of score + bias, the chosen SCORES over their sum plus
    1e-20, times ``scale``. (indices, weights)."""
    s = jax.nn.sigmoid(logits)
    ranked = s if choice == "scores" else s + jax.lax.stop_gradient(bias.astype(s.dtype))
    _, idx = jax.lax.top_k(ranked, top_k)
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    return idx, chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20) * scale


ACTS = {"relu2": lambda u: jnp.square(jax.nn.relu(u)), "relu": jax.nn.relu, "silu_gated": lambda u: jax.nn.silu(u) * u}


def _routed(p, h, dtype, stat, first, held, top_k, scale, choice, expert_act, shared):
    """What the experts ``first .. first + held`` add, and with ``shared`` the shared expert."""
    w = lambda leaf: leaf.astype(dtype)
    act = ACTS[expert_act]
    x = h.reshape(-1, h.shape[-1])
    logits = (x.astype(stat) @ p["gate"]["kernel"].astype(stat)).astype(stat)
    idx, weights = routing(logits, p["select_bias"], top_k, scale, choice)
    one = jax.checkpoint(lambda w_e, wi, wo: w_e * (act(x @ wi) @ wo))  # differentiated: an expert keeps its weights and no more

    def add_expert(y, held_expert):  # what one expert held here adds; the absent ones' part is left out, as in the program
        e, *mats = held_expert
        w_e = jnp.sum(jnp.where(idx == first + e, weights, 0.0), axis=-1, keepdims=True).astype(dtype)
        return y + one(w_e, *mats), None

    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(x), (jnp.arange(held), w(p["experts_wi"]), w(p["experts_wo"])))
    if shared:
        y = y + act(x @ w(p["shared_up_proj"]["kernel"])) @ w(p["shared_down_proj"]["kernel"])
    return y.reshape(h.shape)


@functools.partial(jax.jit, static_argnames=("m", "dtype"))
def _layer(p, x, m, dtype):
    eps, sizes, kind, first, held, top_k, scale, controls, low, shared = m
    decay, norm, skip, choice, expert_act = controls
    stat = dtype if low else jnp.float32  # the state's, the step's, the convolution's, the gated norm's, the softmax's and the router's type
    h = _rms(x, p["RMSNorm_0"]["scale"], eps)
    if kind == "M":
        return x + _mamba(p["ssd"], h, sizes, eps, dtype, stat, (decay, norm, skip))
    if kind == "*":
        return x + _attention(p["attn"], h, dtype, stat)
    return x + _routed(p["routed"], h, dtype, stat, first, held, top_k, scale, choice, expert_act, shared)


@functools.partial(jax.jit, static_argnames=("eps", "dtype", "normed"))
def _head(top, x, eps, dtype, normed=True):
    x = _rms(x, top["RMSNorm_0"]["scale"], eps) if normed else x
    return (x @ top["lm_head"]["kernel"].astype(dtype)).astype(jnp.float32)


def _statics(published, ref_cfg, dtype, first=None, held=None, shared=True):
    """A layer's static arguments but for its kind: (eps, sizes), (first, held, top_k, scale, controls, low, shared)."""
    first = int(ref_cfg["held_first"]) if first is None else first
    held = int(published["n_routed_experts"]) if held is None else held
    low = bool(ref_cfg.get("low_state")) and dtype != jnp.float32
    controls = tuple(str(ref_cfg.get(key, usual)) for key, usual in (("decay", "published"), ("norm", "after_gate"), ("skip", "published"),
                                                                     ("choice", "biased"), ("expert_act", "relu2")))
    return ((float(published["layer_norm_epsilon"]), _sizes(published)),
            (first, held, int(published["num_experts_per_tok"]), float(published["routed_scaling_factor"]), controls, low, shared))


def logits(params, ids, published, ref_cfg, dtype):
    """(B, S, rows held) float32 logits of the plain forward pass over ``ids`` (B, S)."""
    ids = jnp.asarray(ids, jnp.int32)
    head, tail = _statics(published, ref_cfg, dtype)
    layers = kinds(published)[:int(ref_cfg.get("layers", len(published["layers_here"])))]
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["wte"], ids, axis=0).astype(dtype)
        for i, kind in enumerate(layers):
            # differentiated: a layer keeps its input and no more
            x = jax.checkpoint(functools.partial(_layer, m=head + (kind,) + tail, dtype=dtype))(params[f"layer_{i}"], x)
        top = {k: v for k, v in params.items() if not k.startswith("layer_")}
        return _head(top, x, eps=head[0], dtype=dtype, normed=not ref_cfg.get("no_final_norm"))


def layer_part(p, x, published, ref_cfg, dtype, n: int, first: int, held: int, shared: bool = True):
    """What layer ``n`` of the held ones gives for the experts ``first .. first + held`` of ``p["routed"]`` (whose expert
    leaves hold exactly those), with the shared expert or without: ``x + their part``. All of ``routed_over`` experts
    with the shared one: the uncut layer."""
    head, tail = _statics(published, ref_cfg, dtype, first, held, shared)
    with jax.default_matmul_precision("highest"):
        return _layer(p, x, m=head + (kinds(published)[n],) + tail, dtype=dtype)


def loss(logits_, ids):
    """Mean next-token cross-entropy over all positions but the last."""
    logp = jax.nn.log_softmax(logits_[:, :-1].astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, jnp.asarray(ids, jnp.int32)[:, 1:, None], axis=-1))


def loss_and_grads(params, ids, published, ref_cfg, dtype):
    """((the loss, the logits), its gradient in every leaf)."""

    def total(p):
        out = logits(p, ids, published, ref_cfg, dtype)
        return loss(out, ids), out

    return jax.value_and_grad(total, has_aux=True)(params)
