"""Plain reference of ``smallthinker-21b-l4e8``: a decoder whose layers are of two kinds that differ in BOTH mask and
rotation, over a routed ReGLU FFN whose router is placed ahead of the attention, as the share of it one chip holds.
Straightforward ``jax.numpy``: masks built from their definitions, a masked softmax over whole rows a few heads and a
band of queries at a time (so that 16,384 rows fit on the chip), the routed FFN as a loop over the held experts with a
dense mask. It imports nothing of the program and shares with it only the names of the parameter tree it is handed.

One layer, published index ``i`` (``layers_here[n]``), by the row's ``rope_layout[i]`` and ``sliding_window_layout[i]``:

    u  = RMSNorm_1(x)                                    # the attention's input
    r  = u W_r                        (64 logits, float32)          # the ROUTER reads u: "router placed before attention"
    q, k, v = u W_q, u W_k, u W_v     (28 / 4 / 4 heads of 128, no biases, no q/k norm)
    rope_layout[i] == 1:  q, k rotated (rotate-half over all 128 dims, theta 1.5e6);  else q, k as they are (no positions)
    sliding_window_layout[i] == 1:  key s visible to query t  iff  t - 4096 < s <= t;  else  iff  s <= t
    h  = x + softmax(q k^T / sqrt(128) under the layer's mask) v W_o
    E(t) = the 6 largest of r[t];  p[t, e] = exp(r[t, e]) / sum_{e' in E(t)} exp(r[t, e'])
    y  = h + sum_{e in E(t), e held here} p[t, e] * W_down_e ( relu(z W_gate_e) * (z W_up_e) ),   z = RMSNorm_2(h)

then the final RMSNorm, the untied head over the rows held, and the mean next-token cross-entropy over all positions but
the last. Every RMSNorm is ``x / sqrt(mean(x^2) + eps) * w`` in float32.

Departures from the published model, each listed under ``assumed`` in ``smallthinker-21b-l4e8.json``: the experts
``held_first .. held_first + moe_num_primary_experts`` alone add to ``y`` (what the absent ones would add is left out,
here as in the program); the vocabulary is the slice held; no secondary experts; the routing weights are written the
published way (top 6 of the logits, a softmax over those six), which equals a softmax over all 64 with its top 6
rescaled to sum to one.

``dtype=float32`` is the truth (matmuls at the highest precision); ``dtype=bfloat16`` the plain low-precision path:
weights and activations in bf16, the softmaxes', the norms' and the router's statistics in float32.

Beside the harness's ``logits(params, ids, published, ref_cfg, dtype)``: ``loss(logits, ids)``, ``loss_and_grads`` and
``layer_part`` (one layer's result for one share of the experts, or for all of them: what the test that ties the share
to the model adds up).

``ref_cfg`` (the configuration's ``reference`` block): ``held_first`` (the first expert held here; how many is
``published["moe_num_primary_experts"]``, of ``published["routed_over"]``), and for the controls ``windows`` (``"none"``:
window layers attend every earlier key), ``rotation`` (``"all"``: the full layer rotates too; ``"none"``: no layer does),
``router`` (``"late"``: the router scores ``RMSNorm_2(h)``, the experts' own input), ``gate`` (``"silu"``), ``layers``
(how many of the held layers are run: a layer short), ``no_final_norm`` and ``low_state`` (with ``dtype=bfloat16``: the
softmaxes' and the router's statistics in bf16 too, the precision below the one the description states).
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
    """(rotates, window or 0) of each layer held, from the two published layouts at the layers' published indices."""
    return tuple((bool(published["rope_layout"][i]), int(published["sliding_window_size"]) if published["sliding_window_layout"][i] else 0)
                 for i in published["layers_here"])


def keep(S: int, window: int):
    """The (S, S) boolean mask, query-major, from the definition: key s, query t: s <= t and, windowed, s > t - window."""
    t, s = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    return (s <= t) & (s > t - window) if window else s <= t


def _attention(p, u, rotates, window, theta, dtype, stat):
    w = lambda leaf: leaf.astype(dtype)
    B, S, _ = u.shape
    q = jnp.einsum("bsd,dhk->bshk", u, w(p["q_proj"]["kernel"]))
    k = jnp.einsum("bsd,dhk->bshk", u, w(p["k_proj"]["kernel"]))
    v = jnp.einsum("bsd,dhk->bshk", u, w(p["v_proj"]["kernel"]))
    if rotates:
        q, k = _rope(q, jnp.arange(S), theta), _rope(k, jnp.arange(S), theta)
    H, D = q.shape[2:]
    k, v = (jnp.repeat(x, H // x.shape[2], axis=2) for x in (k, v))
    G = HEADS_AT_ONCE if H % HEADS_AT_ONCE == 0 else 1
    Q = QUERIES_AT_ONCE if S % QUERIES_AT_ONCE == 0 else S
    mask = keep(S, window)

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


def routing(logits, top_k: int):
    """The published routing: the ``top_k`` largest of a token's logits, a softmax over those. (indices, weights)."""
    chosen, idx = jax.lax.top_k(logits.astype(jnp.float32), top_k)
    return idx, jax.nn.softmax(chosen, axis=-1)


def _routed(p, scored, z, dtype, first, held, top_k, gate, stat):
    """What the experts ``first .. first + held`` add: routed by ``scored`` (the router's input), computed on ``z``."""
    w = lambda leaf: leaf.astype(dtype)
    x = z.reshape(-1, z.shape[-1])
    logits = (scored.reshape(x.shape).astype(stat) @ p["gate"]["kernel"].astype(stat)).astype(stat)
    idx, weights = routing(logits, top_k)
    act = jax.nn.silu if gate == "silu" else jax.nn.relu
    one = jax.checkpoint(lambda w_e, wg, wi, wo: w_e * ((act(x @ wg) * (x @ wi)) @ wo))  # differentiated: an expert keeps its weights and no more

    def add_expert(y, held_expert):  # what one expert held here adds; the absent ones' part is left out, as in the program
        e, *mats = held_expert
        w_e = jnp.sum(jnp.where(idx == first + e, weights, 0.0), axis=-1, keepdims=True).astype(dtype)
        return y + one(w_e, *mats), None

    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(x), (jnp.arange(held), w(p["experts_wg"]), w(p["experts_wi"]), w(p["experts_wo"])))
    return y.reshape(z.shape)


@functools.partial(jax.jit, static_argnames=("m", "dtype"))
def _layer(p, x, m, dtype):
    eps, theta, rotates, window, first, held, top_k, router, gate, low = m
    stat = dtype if low else jnp.float32  # the softmaxes' and the router's type
    u = _rms(x, p["RMSNorm_0"]["scale"], eps)
    h = x + _attention(p["attn"], u, rotates, window, theta, dtype, stat)
    z = _rms(h, p["RMSNorm_1"]["scale"], eps)
    return h + _routed(p["routed"], z if router == "late" else u, z, dtype, first, held, top_k, gate, stat)


@functools.partial(jax.jit, static_argnames=("eps", "dtype", "normed"))
def _head(top, x, eps, dtype, normed=True):
    x = _rms(x, top["RMSNorm_0"]["scale"], eps) if normed else x
    return (x @ top["lm_head"]["kernel"].astype(dtype)).astype(jnp.float32)


def _statics(published, ref_cfg, dtype, first=None, held=None):
    """A layer's static arguments but for its kind: (eps, theta), (first, held, top_k, router, gate, low)."""
    first = int(ref_cfg["held_first"]) if first is None else first
    held = int(published["moe_num_primary_experts"]) if held is None else held
    low = bool(ref_cfg.get("low_state")) and dtype != jnp.float32
    return ((float(published["rms_norm_eps"]), float(published["rope_theta"])),
            (first, held, int(published["moe_num_active_primary_experts"]), str(ref_cfg.get("router", "early")), str(ref_cfg.get("gate", "relu")), low))


def _kinds(published, ref_cfg):
    """``kinds(published)`` as a control changes them."""
    windows, rotation = ref_cfg.get("windows"), ref_cfg.get("rotation")
    return tuple((True if rotation == "all" else False if rotation == "none" else rotates, 0 if windows == "none" else window)
                 for rotates, window in kinds(published))


def logits(params, ids, published, ref_cfg, dtype):
    """(B, S, rows held) float32 logits of the plain forward pass over ``ids`` (B, S)."""
    ids = jnp.asarray(ids, jnp.int32)
    head, tail = _statics(published, ref_cfg, dtype)
    layers = _kinds(published, ref_cfg)[:int(ref_cfg.get("layers", len(published["layers_here"])))]
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
        return _layer(p, x, m=head + _kinds(published, ref_cfg)[n] + tail, dtype=dtype)


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
