"""Plain reference of ``keye-vl2-30b-l4e16``: pre-norm blocks of grouped-query
attention whose keys a learned indexer chooses for each query, and a
softmax-routed FFN as the share of it this chip holds; a final RMSNorm and an
untied head over the rows held. Straightforward ``jax.numpy``: the full (S, S)
matrix of indexer scores, ``lax.top_k`` a row, a masked softmax over whole rows
a few heads at a time, the routed FFN as a loop over the held experts with a
dense mask. It imports nothing of the program and shares with it only the names
of the parameter tree it is handed.

With ``h`` a block's normed input, ``t`` a query and ``s <= t`` a key:

- ``q, k, v`` projections, RMSNorm a head on q and k, rotate-half over all of a
  head's dims;
- indexer on ``stop_gradient(h)``: ``qI[t, j] = rope(h[t] Wq_j)``, ``kI[s] =
  rope(LayerNorm(h[s] Wk))``, ``w[t] = h[t] Ww / sqrt(heads * head_dim)``,
  ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])`` in float32;
- ``S_t`` = the ``min(topk, t + 1)`` visible keys of largest ``I[t, s]``, ties
  to the lower index; head i attends ``S_t`` alone;
- ``L_I = mean_t KL(p[t, S_t] || softmax_{S_t} I[t, .])``, ``p`` the heads'
  probabilities summed, renormalised over ``S_t``, under ``stop_gradient``.

``dtype=float32`` is the truth (matmuls at the highest precision);
``dtype=bfloat16`` the plain low-precision path: weights and activations in
bf16, the softmaxes, the indexer's scores and the router's in float32.

Beside the harness's ``logits(params, ids, published, ref_cfg, dtype)``:
``forward`` (logits, ``L_I`` by layer, the choice by layer), ``index_losses``
and ``loss_and_grads`` (the gradients of ``CE + sum L_I``). Each takes
``choice``: a list with a layer's (B, S, S) boolean mask, query-major, to use
in place of the reference's own, so that a comparison in bf16 can tell "chose
other keys near the threshold" from "computed something else".

``ref_cfg`` (the configuration's ``reference`` block): ``held_first`` (the first
expert held here; how many is ``published["num_experts"]``), and for the
controls ``layers_short`` (leave out the last n layers), ``no_indexer`` (every
visible key: dense causal attention), ``topk`` (another count of keys a query),
``no_index_loss`` (``loss_and_grads`` without ``L_I``), ``no_final_norm`` (the head on the stream as it
stands: the one thing wrong that moves a mean loss over random targets by more than its sampling noise) and ``low_state`` (with
``dtype=bfloat16``: the softmaxes' statistics, the indexer's scores and the
router's in bf16 too, the precision below the one the description states).
"""

import functools

import jax
import jax.numpy as jnp

HEADS_AT_ONCE = 4  # attention: 4 x S x S float32 scores are 1 GB at S = 8192
NEG = -1e30


def _rms(x, scale, eps):
    x32 = x.astype(jnp.float32)
    return (x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps) * scale.astype(jnp.float32)).astype(x.dtype)


def _layernorm(x, scale, bias, eps):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean((x32 - mu) ** 2, axis=-1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + eps) * scale + bias).astype(x.dtype)


def _rope(x, theta):
    """(B, S, heads, d): rotate-half over all d dims at positions 0 .. S - 1."""
    S, d = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :d // 2].astype(jnp.float32), x[..., d // 2:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def _index_scores(p, h, eps, theta, dtype, stat):
    """I (B, S, S), query-major, ``NEG`` where a key is ahead of its query."""
    w = lambda leaf: leaf.astype(dtype)
    q = _rope(jnp.einsum("bsd,djk->bsjk", h, w(p["index_q_proj"]["kernel"])), theta)  # (B, S, J, Di)
    k = h @ w(p["index_k_proj"]["kernel"])
    k = _rope(_layernorm(k, p["index_k_norm"]["scale"], p["index_k_norm"]["bias"], eps)[:, :, None, :], theta)[:, :, 0]
    J, Di = q.shape[2:]
    weight = (h @ w(p["index_w_proj"]["kernel"])).astype(jnp.float32) * (J ** -0.5 * Di ** -0.5)  # (B, S, J)

    @jax.checkpoint
    def add_head(acc, head):  # one head's (S, S) scores at a time
        q_j, w_j = head
        s = jnp.einsum("btd,bsd->bts", q_j, k, preferred_element_type=stat).astype(stat)
        return acc + (jax.nn.relu(s) * w_j[..., None].astype(stat)).astype(jnp.float32), None

    B, S = h.shape[:2]
    scores, _ = jax.lax.scan(add_head, jnp.zeros((B, S, S), jnp.float32), (jnp.moveaxis(q, 2, 0), jnp.moveaxis(weight, 2, 0)))
    return jnp.where(jnp.arange(S)[None, :] <= jnp.arange(S)[:, None], scores, NEG)


def _choose(scores, topk):
    """A row's ``min(topk, t + 1)`` largest visible scores as a boolean mask; ``lax.top_k`` puts the lower index first."""
    B, S, _ = scores.shape
    _, idx = jax.lax.top_k(jnp.where(scores == 0.0, 0.0, scores), min(topk, S))
    mask = jnp.zeros((B, S, S), bool).at[jnp.arange(B)[:, None, None], jnp.arange(S)[None, :, None], idx].set(True)
    return mask & (jnp.arange(S)[None, :] <= jnp.arange(S)[:, None])


def _attention(p, h, mask, eps, theta, dtype, stat):
    """-> (the mixer's output, the heads' probabilities summed (B, S, S) float32)."""
    w = lambda leaf: leaf.astype(dtype)
    B, S, _ = h.shape
    q = jnp.einsum("bsd,dhk->bshk", h, w(p["q_proj"]["kernel"]))
    k = jnp.einsum("bsd,dhk->bshk", h, w(p["k_proj"]["kernel"]))
    v = jnp.einsum("bsd,dhk->bshk", h, w(p["v_proj"]["kernel"]))
    q = _rope(_rms(q, p["q_norm"]["scale"], eps), theta)
    k = _rope(_rms(k, p["k_norm"]["scale"], eps), theta)
    H, D = q.shape[2:]
    k, v = (jnp.repeat(x, H // x.shape[2], axis=2) for x in (k, v))

    @jax.checkpoint
    def some_heads(qkv):  # (G, B, S, D) each
        qh, kh, vh = qkv
        s = (jnp.einsum("gbqk,gbtk->gbqt", qh, kh, preferred_element_type=stat) * D ** -0.5).astype(stat)
        a = jax.nn.softmax(jnp.where(mask, s, NEG), axis=-1)
        return jnp.einsum("gbqt,gbtk->gbqk", a.astype(dtype), vh), jnp.sum(a.astype(jnp.float32), axis=0)

    G = HEADS_AT_ONCE if H % HEADS_AT_ONCE == 0 else 1
    grouped = lambda x: jnp.moveaxis(x, 2, 0).reshape(H // G, G, B, S, D)
    o, probs = jax.lax.map(some_heads, (grouped(q), grouped(k), grouped(v)))
    o = jnp.moveaxis(o.reshape(H, B, S, D), 0, 2)
    return jnp.einsum("bshk,hkd->bsd", o, w(p["o_proj"]["kernel"])), jnp.sum(probs, axis=0)


def _index_loss(scores, probs, mask):
    probs = jax.lax.stop_gradient(probs)
    p = probs / jnp.sum(jnp.where(mask, probs, 0.0), axis=-1, keepdims=True)
    log_q = jax.nn.log_softmax(jnp.where(mask, scores, NEG), axis=-1)
    keep = mask & (p > 0)
    return jnp.mean(jnp.sum(jnp.where(keep, p * (jnp.log(jnp.where(keep, p, 1.0)) - log_q), 0.0), axis=-1))


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def _routed(p, h, dtype, first, held, top_k, stat):
    w = lambda leaf: leaf.astype(dtype)
    x = h.reshape(-1, h.shape[-1])
    probs = jax.nn.softmax((x.astype(stat) @ p["gate"]["kernel"].astype(stat)).astype(stat), axis=-1).astype(jnp.float32)
    chosen, idx = jax.lax.top_k(probs, top_k)
    weights = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    one = jax.checkpoint(lambda w_e, *mats: w_e * _swiglu(x, *mats))  # differentiated: an expert keeps its weights and no more

    def add_expert(y, held_expert):  # what one expert held here adds; the absent ones' part is left out, as in the program
        e, *mats = held_expert
        w_e = jnp.sum(jnp.where(idx == first + e, weights, 0.0), axis=-1, keepdims=True).astype(dtype)
        return y + one(w_e, *mats), None

    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(x), (jnp.arange(held), w(p["experts_wg"]), w(p["experts_wi"]), w(p["experts_wo"])))
    return y.reshape(h.shape)


@functools.partial(jax.jit, static_argnames=("m", "dtype"))
def _layer(p, x, given, m, dtype):
    eps, theta, topk, first, held, top_k, low, no_indexer = m
    stat = dtype if low else jnp.float32  # the softmaxes' and the scores' type
    h = _rms(x, p["RMSNorm_0"]["scale"], eps)
    S = h.shape[1]
    visible = jnp.broadcast_to(jnp.arange(S)[None, :] <= jnp.arange(S)[:, None], (h.shape[0], S, S))
    if no_indexer:
        scores, mask = None, visible
    else:
        scores = _index_scores(p["sparse"], jax.lax.stop_gradient(h), eps, theta, dtype, stat)
        mask = _choose(jax.lax.stop_gradient(scores), topk) if given is None else given
    a, probs = _attention(p["sparse"], h, mask, eps, theta, dtype, stat)
    loss = jnp.zeros((), jnp.float32) if no_indexer else _index_loss(scores, probs, mask)
    x = x + a
    return x + _routed(p["routed"], _rms(x, p["RMSNorm_1"]["scale"], eps), dtype, first, held, top_k, stat), loss, mask


@functools.partial(jax.jit, static_argnames=("eps", "dtype", "normed"))
def _head(top, x, eps, dtype, normed=True):
    x = _rms(x, top["RMSNorm_0"]["scale"], eps) if normed else x
    return (x @ top["lm_head"]["kernel"].astype(dtype)).astype(jnp.float32)


def forward(params, ids, published, ref_cfg, dtype, choice=None):
    """-> (float32 logits (B, S, rows held), ``L_I`` by layer, the choice by layer as (B, S, S) boolean masks)."""
    eps = float(published["rms_norm_eps"])
    layers = int(published["num_hidden_layers"]) - int(ref_cfg.get("layers_short", 0))
    m = (eps, float(published["rope_theta"]), int(ref_cfg.get("topk", published["sa_config"]["topk"])),
         int(ref_cfg["held_first"]), int(published["num_experts"]), int(published["num_experts_per_tok"]),
         bool(ref_cfg.get("low_state")) and dtype != jnp.float32, bool(ref_cfg.get("no_indexer")))
    losses, masks = [], []
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["wte"], jnp.asarray(ids, jnp.int32), axis=0).astype(dtype)
        for i in range(layers):
            layer = functools.partial(_layer, m=m, dtype=dtype)
            # differentiated: a layer keeps its input and no more
            x, loss, mask = jax.checkpoint(layer)(params[f"layer_{i}"], x, None if choice is None else choice[i])
            losses.append(loss)
            masks.append(mask)
        top = {k: v for k, v in params.items() if not k.startswith("layer_")}
        return _head(top, x, eps=eps, dtype=dtype, normed=not ref_cfg.get("no_final_norm")), losses, masks


def logits(params, ids, published, ref_cfg, dtype):
    """(B, S, rows held) float32 logits of the plain forward pass over ``ids`` (B, S): the harness's interface."""
    return forward(params, ids, published, ref_cfg, dtype)[0]


def index_losses(params, ids, published, ref_cfg, dtype, choice=None):
    return forward(params, ids, published, ref_cfg, dtype, choice)[1]


def cross_entropy(logits_, ids):
    logp = jax.nn.log_softmax(logits_[:, :-1].astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, jnp.asarray(ids)[:, 1:, None], axis=-1))


def loss_and_grads(params, ids, published, ref_cfg, dtype, choice=None):
    """((CE + sum L_I, (CE, L_I by layer, the logits)), its gradient in every leaf). The stop_gradients above make it
    the two separate learners' gradients side by side: the main leaves' from CE, the indexer's from ``L_I``."""
    def total(p):
        out, losses, _ = forward(p, ids, published, ref_cfg, dtype, choice)
        ce = cross_entropy(out, ids)
        return ce + (0.0 if ref_cfg.get("no_index_loss") else sum(losses)), (ce, losses, out)

    return jax.value_and_grad(total, has_aux=True)(params)
