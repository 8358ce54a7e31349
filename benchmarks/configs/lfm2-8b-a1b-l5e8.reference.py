"""Plain reference of ``lfm2-8b-a1b-l5e8``: a decoder whose layers mix tokens by a gated short convolution three times in
four and by grouped-query attention once, over a dense SwiGLU in its leading layer and a routed FFN chosen by a biased
sigmoid router in the others, as the share of it one chip holds. Straightforward ``jax.numpy``: the filter as a sum of
shifted copies, a masked softmax over whole rows a few heads and a band of queries at a time (so that 16,384 rows fit on
the chip), the routed FFN as a loop over the held experts with a dense mask. It imports nothing of the program and shares
with it only the names of the parameter tree it is handed.

One layer, published index ``i`` (``layers_here[n]``), by the row's ``layer_types[i]`` and ``num_dense_layers``:

    block:  h = x + Op(RMSNorm_op(x));  y = h + FFN(RMSNorm_ffn(h));  RMSNorm: x / sqrt(mean(x^2) + 1e-5) * w, float32
    layer_types[i] == "conv":
        [B, C, u] = z W_in            (2048 x 6144, no bias, three chunks of 2,048 in that order)
        g = B * u;   c_t = sum_{j=0..2} w_j g_{t-2+j}    (depthwise, causal, conv_L_cache = 3 taps, one filter a channel,
                                                          conv_bias false, NO activation)
        Op = (C * c) W_out            (2048 x 2048, no bias)
    layer_types[i] == "full_attention":
        q, k, v = z W_q, z W_k, z W_v     (32 / 8 / 8 heads of 64, no biases)
        q, k = RMSNorm_64(q), RMSNorm_64(k)   (q_layernorm, k_layernorm, a head at a time, eps 1e-5) BEFORE the rotation
        q, k rotated: the whole head, half-split pairs, theta 1e6
        Op = softmax(q k^T / sqrt(64) under the causal mask) v W_o    (no bias)
    i < num_dense_layers:  FFN = W_down (silu(z W_gate) * (z W_up)),  7,168 wide, no biases
    else:  s = sigmoid(z W_r)         (32 scores, float32)
           E(t) = the 4 largest of s[t] + b      (b: expert_bias, used for the CHOICE alone: no gradient, not in the weight)
           p[t, e] = s[t, e] / (sum_{e' in E(t)} s[t, e'] + 1e-6),  times routed_scaling_factor = 1
           FFN = sum_{e in E(t), e held here} p[t, e] * W2_e (silu(z W1_e) * (z W3_e)),   1,792 wide, no shared expert

then one more RMSNorm (``embedding_norm``), the head tied to the embedding over the rows held, and the mean next-token
cross-entropy over all positions but the last.

Departures from the published model, each listed under ``assumed`` in ``lfm2-8b-a1b-l5e8.json``: the experts
``held_first .. held_first + num_experts`` alone add to ``y`` (what the absent ones would add is left out, here as in the
program); the vocabulary is the slice held; the embedding is tied to the head (the family's default, the catalog's row
dropped the key); ``expert_bias`` is a buffer, whatever it holds.

``dtype=float32`` is the truth (matmuls at the highest precision); ``dtype=bfloat16`` the plain low-precision path:
weights and activations in bf16, the gates and the filter, the softmax's, the norms' and the router's statistics in
float32.

Beside the harness's ``logits(params, ids, published, ref_cfg, dtype)``: ``loss(logits, ids)``, ``loss_and_grads`` and
``layer_part`` (one layer's result for one share of the experts, or for all of them: what the test that ties the share
to the model adds up).

``ref_cfg`` (the configuration's ``reference`` block): ``held_first`` (the first expert held here; how many is
``published["num_experts"]``, of ``published["routed_over"]``), and for the controls ``filter_act`` (``"silu"``: an
activation after the filter, as the other convolutions here have), ``chunks`` (``"cbu"``: W_in's first two chunks the other way round; B and u may change places, their product commutes),
``choice`` (``"scores"``: the top 4 of ``s`` alone, the bias ignored), ``qk_norm`` (``"none"``: q and k as projected),
``renorm`` (``"none"``: the chosen scores as they are), ``layers`` (how many of the held layers are run: a layer short),
``no_final_norm`` and
``low_state`` (with ``dtype=bfloat16``: the gates, the filter, the softmax's and the router's statistics in bf16 too,
the precision below the one the description states).
"""

import functools

import jax
import jax.numpy as jnp

HEADS_AT_ONCE = 4  # attention: 4 heads x 2,048 queries x 16,384 keys of float32 scores are 0.5 GB
QUERIES_AT_ONCE = 2048
NEG = -1e30


def _rms(x, scale, eps):
    x32 = x.astype(jnp.float32)
    return (x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps) * scale.astype(jnp.float32)).astype(x.dtype)


def _rope(x, pos, theta):
    """(B, S, heads, d): rotate-half over all d dims at positions ``pos`` (S,)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :d // 2].astype(jnp.float32), x[..., d // 2:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def kinds(published: dict) -> tuple:
    """(mixes by convolution, its FFN is dense) of each layer held, from ``layer_types`` and ``num_dense_layers`` at the
    layers' published indices."""
    return tuple((published["layer_types"][i] == "conv", i < int(published["num_dense_layers"])) for i in published["layers_here"])


def _conv(p, z, dtype, stat, filter_act, chunks):
    w = lambda leaf: leaf.astype(dtype)
    D, S = z.shape[-1], z.shape[1]
    bcu = z @ w(p["in_proj"]["kernel"])
    order = {"bcu": (0, 1, 2), "cbu": (1, 0, 2)}[chunks]
    B, C, u = (bcu[..., n * D:(n + 1) * D].astype(stat) for n in order)
    g = B * u
    taps = p["conv_kernel"].astype(stat)  # (K, D): tap j meets the token K - 1 - j back
    K = taps.shape[0]
    c = sum(jnp.pad(g, ((0, 0), (K - 1 - j, 0), (0, 0)))[:, :S] * taps[j] for j in range(K))
    c = jax.nn.silu(c) if filter_act == "silu" else c
    return (C * c).astype(dtype) @ w(p["out_proj"]["kernel"])


def _attention(p, z, eps, theta, dtype, stat, qk_norm):
    w = lambda leaf: leaf.astype(dtype)
    B, S, _ = z.shape
    q = jnp.einsum("bsd,dhk->bshk", z, w(p["q_proj"]["kernel"]))
    k = jnp.einsum("bsd,dhk->bshk", z, w(p["k_proj"]["kernel"]))
    v = jnp.einsum("bsd,dhk->bshk", z, w(p["v_proj"]["kernel"]))
    if qk_norm != "none":
        q, k = _rms(q, p["q_norm"]["scale"], eps), _rms(k, p["k_norm"]["scale"], eps)
    q, k = _rope(q, jnp.arange(S), theta), _rope(k, jnp.arange(S), theta)
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


def routing(logits, bias, top_k: int, choice: str = "biased", renorm: str = "sum", eps: float = 1e-6):
    """The published routing: sigmoid scores, the ``top_k`` largest of score + bias, the chosen SCORES over their sum
    plus ``eps``. (indices, weights)."""
    s = jax.nn.sigmoid(logits)
    ranked = s if choice == "scores" else s + jax.lax.stop_gradient(bias.astype(s.dtype))
    _, idx = jax.lax.top_k(ranked, top_k)
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    return idx, chosen if renorm == "none" else chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + eps)


def _routed(p, z, dtype, first, held, top_k, choice, renorm, stat):
    """What the experts ``first .. first + held`` add."""
    w = lambda leaf: leaf.astype(dtype)
    x = z.reshape(-1, z.shape[-1])
    logits = (x.astype(stat) @ p["gate"]["kernel"].astype(stat)).astype(stat)
    idx, weights = routing(logits, p["select_bias"], top_k, choice, renorm)
    one = jax.checkpoint(lambda w_e, wg, wi, wo: w_e * ((jax.nn.silu(x @ wg) * (x @ wi)) @ wo))  # differentiated: an expert keeps its weights and no more

    def add_expert(y, held_expert):  # what one expert held here adds; the absent ones' part is left out, as in the program
        e, *mats = held_expert
        w_e = jnp.sum(jnp.where(idx == first + e, weights, 0.0), axis=-1, keepdims=True).astype(dtype)
        return y + one(w_e, *mats), None

    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(x), (jnp.arange(held), w(p["experts_wg"]), w(p["experts_wi"]), w(p["experts_wo"])))
    return y.reshape(z.shape)


def _dense(p, z, dtype):
    w = lambda leaf: leaf.astype(dtype)
    return (jax.nn.silu(z @ w(p["gate_proj"]["kernel"])) * (z @ w(p["up_proj"]["kernel"]))) @ w(p["down_proj"]["kernel"])


@functools.partial(jax.jit, static_argnames=("m", "dtype"))
def _layer(p, x, m, dtype):
    eps, theta, conv, dense, first, held, top_k, controls, low = m
    filter_act, chunks, choice, qk_norm, renorm = controls
    stat = dtype if low else jnp.float32  # the gates', the filter's, the softmax's and the router's type
    z = _rms(x, p["RMSNorm_0"]["scale"], eps)
    h = x + (_conv(p["conv"], z, dtype, stat, filter_act, chunks) if conv else _attention(p["attn"], z, eps, theta, dtype, stat, qk_norm))
    z = _rms(h, p["RMSNorm_1"]["scale"], eps)
    return h + (_dense(p["mlp"], z, dtype) if dense else _routed(p["routed"], z, dtype, first, held, top_k, choice, renorm, stat))


@functools.partial(jax.jit, static_argnames=("eps", "dtype", "normed"))
def _head(top, x, eps, dtype, normed=True):
    x = _rms(x, top["RMSNorm_0"]["scale"], eps) if normed else x
    return (x @ top["wte"].astype(dtype).T).astype(jnp.float32)


def _statics(published, ref_cfg, dtype, first=None, held=None):
    """A layer's static arguments but for its kind: (eps, theta), (first, held, top_k, controls, low)."""
    first = int(ref_cfg["held_first"]) if first is None else first
    held = int(published["num_experts"]) if held is None else held
    low = bool(ref_cfg.get("low_state")) and dtype != jnp.float32
    controls = tuple(str(ref_cfg.get(key, usual)) for key, usual in (("filter_act", "none"), ("chunks", "bcu"), ("choice", "biased"),
                                                                     ("qk_norm", "early"), ("renorm", "sum")))
    return (float(published["norm_eps"]), float(published["rope_theta"])), (first, held, int(published["num_experts_per_tok"]), controls, low)


def logits(params, ids, published, ref_cfg, dtype):
    """(B, S, rows held) float32 logits of the plain forward pass over ``ids`` (B, S)."""
    ids = jnp.asarray(ids, jnp.int32)
    head, tail = _statics(published, ref_cfg, dtype)
    layers = kinds(published)[:int(ref_cfg.get("layers", len(published["layers_here"])))]
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["wte"], ids, axis=0).astype(dtype)
        for i, kind in enumerate(layers):
            # differentiated: a layer keeps its input and no more
            x = jax.checkpoint(functools.partial(_layer, m=head + kind + tail, dtype=dtype))(params[f"layer_{i}"], x)
        top = {k: v for k, v in params.items() if not k.startswith("layer_")}
        return _head(top, x, eps=head[0], dtype=dtype, normed=not ref_cfg.get("no_final_norm"))


def layer_part(p, x, published, ref_cfg, dtype, n: int, first: int, held: int):
    """What layer ``n`` of the held ones gives for the experts ``first .. first + held`` of ``p["routed"]`` (whose
    expert leaves hold exactly those): ``h + their part``. All of ``routed_over`` experts: the uncut layer."""
    head, tail = _statics(published, ref_cfg, dtype, first, held)
    with jax.default_matmul_precision("highest"):
        return _layer(p, x, m=head + kinds(published)[n] + tail, dtype=dtype)


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
