"""Plain reference of ``k-exaone-236b-l5e8``: published layers 0-4 of a window/global MoE decoder whose norms sit on each
sublayer's OUTPUT, as the share of it one four-chip host holds (experts 0-7 of 128, 19,200 of 153,600 vocabulary rows).
Straightforward ``jax.numpy``: no kernels, no mesh, no remat; masks built from their definitions, a masked softmax over
whole rows a few heads and a band of queries at a time (so that 8,192 rows fit on a chip beside the float32 tree), the
routed FFN as a loop over the held experts with a dense mask. It imports nothing of the program and shares with it only
the names of the parameter tree it is handed.

One layer, published index ``i`` (``layers_here[n]``), by the row's ``layer_types[i]``, ``sliding_windows[i]`` and
``mlp_layer_types[i]``:

    q, k, v = x W_q, x W_k, x W_v       (64 / 8 / 8 heads of 128, no biases; NO norm on the sublayer's input)
    q, k = RMSNorm_q(q), RMSNorm_k(k)   (over each head's 128, before the rotation)
    sliding_attention:  q, k rotated (rotate-half over all 128 dims, theta 1e6); key s visible to query t iff t - 128 < s <= t
    full_attention:     q, k as they are (no positions);                         key s visible to query t iff s <= t
    h  = x + RMSNorm_0( softmax(q k^T / sqrt(128) under the layer's mask) v W_o )        # the norm on the OUTPUT
    dense:   y = h + RMSNorm_1( W_down (silu(h W_gate) * (h W_up)) )                       (18,432 wide)
    sparse:  s = sigmoid(h W_r) (128 scores, float32);  E(t) = the 8 largest of s[t] + bias;
             p[t, e] = 2.5 * s[t, e] / sum_{e' in E(t)} s[t, e']
             y = h + RMSNorm_1( sum_{e in E(t), e held here} p[t, e] * W_down_e (silu(h W_gate_e) * (h W_up_e))
                                + W_down_s (silu(h W_gate_s) * (h W_up_s)) )             (experts and the shared one 2,048 wide)

then the final RMSNorm, the untied head over the rows held, and the mean next-token cross-entropy over all positions but
the last. Every RMSNorm is ``x / sqrt(mean(x^2) + 1e-5) * w`` in float32.

Departures from the published model, each listed under ``assumed`` in ``k-exaone-236b-l5e8.json``: the experts
``held_first .. held_first + num_experts`` alone add to the routed sum (what the absent ones would add is left out, here
as in the program; the shared expert is added once); the vocabulary is the slice held; no multi-token-prediction module.

``dtype=float32`` is the truth (matmuls at the highest precision); ``dtype=bfloat16`` the plain low-precision path:
weights and activations in bf16, the softmaxes', the norms' and the router's statistics in float32.

Beside the harness's ``logits(params, ids, published, ref_cfg, dtype)``: ``loss(logits, ids)``, ``loss_and_grads`` and
``layer_part`` (one layer's result for one share of the experts, with or without the shared expert, BEFORE the output's
norm, which is not linear: what the test that ties the sixteen hosts' shares to the uncut layer adds up).

``ref_cfg`` (the configuration's ``reference`` block): ``held_first`` (the first expert held here; how many is
``published["num_experts"]``, of ``published["routed_over"]``), and for the controls ``windows`` (``"none"``: window layers
attend every earlier key), ``rotation`` (``"all"``: the full layer rotates too), ``norms`` (``"pre"``: the norms on each
sublayer's input, the usual placement), ``no_qk_norm``, ``no_final_norm``, ``layers`` (how many of the held layers are
run) and ``low_state`` (with ``dtype=bfloat16``: the softmaxes', the norms' and the router's statistics in bf16 too, the
precision below the one the description states).
"""

import functools

import jax
import jax.numpy as jnp

HEADS_AT_ONCE = 4  # attention: 4 heads x 2,048 queries x 8,192 keys of float32 scores are 0.27 GB
QUERIES_AT_ONCE = 2048
NEG = -1e30


def _rms(x, scale, eps, stat=jnp.float32):
    xs = x.astype(stat)
    return (xs * jax.lax.rsqrt(jnp.mean(xs * xs, axis=-1, keepdims=True) + jnp.asarray(eps, stat)) * scale.astype(stat)).astype(x.dtype)


def _rope(x, pos, theta):
    """(B, S, heads, d): rotate-half over all d dims at positions ``pos`` (S,)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :d // 2].astype(jnp.float32), x[..., d // 2:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def kinds(published: dict) -> tuple:
    """(rotates, window or 0, routed) of each layer held, from the three published lists at the layers' published indices:
    a window layer rotates and a full one does not; ``sliding_windows`` gives the width, 0 for a full layer."""
    return tuple((published["layer_types"][i] == "sliding_attention", int(published["sliding_windows"][i]), published["mlp_layer_types"][i] == "sparse")
                 for i in published["layers_here"])


def keep(S: int, window: int):
    """The (S, S) boolean mask, query-major, from the definition: key s, query t: s <= t and, windowed, s > t - window."""
    t, s = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    return (s <= t) & (s > t - window) if window else s <= t


def _attention(p, u, rotates, window, eps, theta, dtype, stat, qk_norm):
    w = lambda leaf: leaf.astype(dtype)
    B, S, _ = u.shape
    q = jnp.einsum("bsd,dhk->bshk", u, w(p["q_proj"]["kernel"]))
    k = jnp.einsum("bsd,dhk->bshk", u, w(p["k_proj"]["kernel"]))
    v = jnp.einsum("bsd,dhk->bshk", u, w(p["v_proj"]["kernel"]))
    if qk_norm:
        q, k = _rms(q, p["q_norm"]["scale"], eps, stat), _rms(k, p["k_norm"]["scale"], eps, stat)
    if rotates:
        q, k = _rope(q, jnp.arange(S), theta), _rope(k, jnp.arange(S), theta)
    H, D = q.shape[2:]
    k, v = (jnp.repeat(x, H // x.shape[2], axis=2) for x in (k, v))
    G = HEADS_AT_ONCE if H % HEADS_AT_ONCE == 0 else 1
    Q = QUERIES_AT_ONCE if S % QUERIES_AT_ONCE == 0 else S
    mask = keep(S, window)

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


def routing(logits, bias, top_k: int, scale: float):
    """The published routing (the keys are DeepSeek-V3's, one group): sigmoid scores, the ``top_k`` largest of score + bias,
    weights the chosen SCORES over their sum, times ``scale``. (indices, weights), float32."""
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    _, idx = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    return idx, scale * chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)


def _swiglu(x, wg, wi, wo):
    return (jax.nn.silu(x @ wg) * (x @ wi)) @ wo


def _routed(p, z, dtype, first, held, top_k, scale, stat, shared=True):
    """What the experts ``first .. first + held`` add for the tokens ``z``, plus the shared expert once (``shared``)."""
    w = lambda leaf: leaf.astype(dtype)
    x = z.reshape(-1, z.shape[-1])
    logits = x.astype(stat) @ p["gate"]["kernel"].astype(stat)
    idx, weights = routing(logits, p["select_bias"], top_k, scale)

    def add_expert(y, held_expert):  # what one expert held here adds; the absent ones' part is left out, as in the program
        e, *mats = held_expert
        w_e = jnp.sum(jnp.where(idx == first + e, weights, 0.0), axis=-1, keepdims=True).astype(dtype)
        return y + w_e * _swiglu(x, *mats), None

    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(x), (jnp.arange(held), w(p["experts_wg"])[:held], w(p["experts_wi"])[:held], w(p["experts_wo"])[:held]))
    if shared:
        y = y + _swiglu(x, w(p["shared_gate_proj"]["kernel"]), w(p["shared_up_proj"]["kernel"]), w(p["shared_down_proj"]["kernel"]))
    return y.reshape(z.shape)


def _ffn(p, h, m, dtype, stat, shared=True):
    routed, first, held, top_k, scale = m
    if routed:
        return _routed(p["routed"], h, dtype, first, held, top_k, scale, stat, shared)
    w = lambda name: p["mlp"][name]["kernel"].astype(dtype)
    return _swiglu(h, w("gate_proj"), w("up_proj"), w("down_proj"))


@functools.partial(jax.jit, static_argnames=("m", "dtype"))
def _layer(p, x, m, dtype):
    eps, theta, rotates, window, routed, first, held, top_k, scale, norms, qk_norm, low = m
    stat = dtype if low else jnp.float32  # the softmaxes', the norms' and the router's type
    n0, n1 = (lambda t: _rms(t, p["RMSNorm_0"]["scale"], eps, stat)), (lambda t: _rms(t, p["RMSNorm_1"]["scale"], eps, stat))
    ffn = lambda t: _ffn(p, t, (routed, first, held, top_k, scale), dtype, stat)
    attn = lambda t: _attention(p["attn"], t, rotates, window, eps, theta, dtype, stat, qk_norm)
    if norms == "pre":  # the control: the usual placement, on each sublayer's input
        h = x + attn(n0(x))
        return h + ffn(n1(h))
    h = x + n0(attn(x))
    return h + n1(ffn(h))


@functools.partial(jax.jit, static_argnames=("eps", "dtype", "normed", "low"))
def _head(top, x, eps, dtype, normed=True, low=False):
    x = _rms(x, top["RMSNorm_0"]["scale"], eps, dtype if low else jnp.float32) if normed else x
    return (x @ top["lm_head"]["kernel"].astype(dtype)).astype(jnp.float32)


def _statics(published, ref_cfg, dtype, first=None, held=None):
    """A layer's static arguments but for its kind: (eps, theta), (first, held, top_k, scale, norms, qk_norm, low)."""
    first = int(ref_cfg["held_first"]) if first is None else first
    held = int(published["num_experts"]) if held is None else held
    low = bool(ref_cfg.get("low_state")) and dtype != jnp.float32
    return ((float(published["rms_norm_eps"]), float(published["rope_parameters"]["rope_theta"])),
            (first, held, int(published["num_experts_per_tok"]), float(published["routed_scaling_factor"]), str(ref_cfg.get("norms", "output")),
             not ref_cfg.get("no_qk_norm"), low))


def _kinds(published, ref_cfg):
    """``kinds(published)`` as a control changes them."""
    windows, rotation = ref_cfg.get("windows"), ref_cfg.get("rotation")
    return tuple((True if rotation == "all" else rotates, 0 if windows == "none" else window, routed) for rotates, window, routed in kinds(published))


def logits(params, ids, published, ref_cfg, dtype):
    """(B, S, rows held) float32 logits of the plain forward pass over ``ids`` (B, S): a layer at a time, each one jitted
    call, so that a layer's temporaries never outlive it."""
    ids = jnp.asarray(ids, jnp.int32)
    head, tail = _statics(published, ref_cfg, dtype)
    layers = _kinds(published, ref_cfg)[:int(ref_cfg.get("layers", len(published["layers_here"])))]
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["wte"], ids, axis=0).astype(dtype)
        for i, kind in enumerate(layers):
            x = _layer(params[f"layer_{i}"], x, m=head + kind + tail, dtype=dtype)
        top = {k: v for k, v in params.items() if not k.startswith("layer_")}
        return _head(top, x, eps=head[0], dtype=dtype, normed=not ref_cfg.get("no_final_norm"), low=tail[-1])


def layer_part(p, h, published, ref_cfg, dtype, first: int, held: int, shared: bool):
    """What a routed layer's FFN gives the tokens ``h`` for the experts ``first .. first + held`` of ``p["routed"]`` (whose
    expert leaves hold exactly those), with the shared expert or without, BEFORE the output's norm: the sixteen hosts'
    parts, the shared expert counted once, sum to the uncut layer's ``first = 0, held = routed_over``."""
    _, (_, _, top_k, scale, _, _, _) = _statics(published, ref_cfg, dtype, first, held)
    with jax.default_matmul_precision("highest"):
        return _ffn(p, h, (True, first, held, top_k, scale), dtype, jnp.float32, shared)


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
