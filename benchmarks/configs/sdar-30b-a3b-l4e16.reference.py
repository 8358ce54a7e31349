"""Plain reference of ``sdar-30b-a3b-l4e16``: block-diffusion TRAINING of a routed decoder (BD3-LM's vectorised form,
which SDAR's training follows), as the share of it one chip holds. Straightforward ``jax.numpy``: the mask built from
its definition over the whole doubled row, a masked softmax over whole rows a few heads and a band of queries at a time,
the routed FFN as a loop over the held experts with a dense mask, a loss of its own. It imports nothing of the program
and shares with it only the names of the parameter tree it is handed.

A clean row ``x0`` of L tokens is cut into K = L / B blocks of B tokens. The model's input is one row of 2 L ids,
``[xt ; x0]``: the noised copy, then the clean one. Index p in [0, 2 L): ``clean(p) = p >= L``, ``pos(p) = p mod L``,
``blk(p) = pos(p) // B``.

- Embedding: ``h[p] = E[ids[p]]``; the mask token is an ordinary row of ``E``.
- Every layer, pre-norm: ``h = h + Attn(RMSNorm(h)); h = h + MoE(RMSNorm(h))``.
- ``Attn``: q, k, v projections (no bias), RMSNorm a head on q and k, rotate-half RoPE over all of a head's dims at
  ``pos(p)``, scale ``head_dim^-0.5``, softmax over the keys that ``keep`` allows, ``o_proj``. ``keep(q, k)``: noised q,
  noised k: ``blk(k) == blk(q)``; noised q, clean k: ``blk(k) < blk(q)``; clean q, clean k: ``blk(k) <= blk(q)``; clean
  q, noised k: never. ``L^2 + L B`` pairs.
- ``MoE``: a softmax over all ``routed_over`` experts, the top ``num_experts_per_tok`` renormalised, SwiGLU experts; the
  held experts' part of the sum, nothing for the absent ones; over all 2 L positions.
- Head: final RMSNorm and the untied head over the NOISED half only; no shift: position i of the noised half predicts
  ``x0[i]``.
- Loss: ``(1 / L) sum over masked i of (B / m_blk(i)) CE(logits[i], x0[i])``, ``m`` the masked positions of a block,
  counted from ``xt`` itself (``xt[i] == MASK``); the mean over the batch's rows. Every number in it is read from the
  2 L ids.

Each choice the source does not settle is listed under ``assumed`` in ``sdar-30b-a3b-l4e16.json``.

``dtype=float32`` is the truth (matmuls at the highest precision); ``dtype=bfloat16`` the plain low-precision path:
weights and activations in bf16, the softmaxes' and the router's statistics in float32.

Beside the harness's ``logits(params, ids, published, ref_cfg, dtype)`` (the noised half's, (B, L, rows held)) and
``loss(logits, ids)``: ``masked_loss`` (the loss with its block length and mask id given) and ``loss_and_grads``.

``ref_cfg`` (the configuration's ``reference`` block): ``held_first`` (the first expert held here; how many is
``published["num_experts"]``), ``block_length`` and ``mask_token_id`` where they are not the file's, and for the
controls ``mask`` (``"causal"``: plain causal attention over the 2 L row; ``"blind"``: a noised query sees its own
noised block and nothing of the clean half), ``uniform_weights`` (every masked position weighs one), ``shift`` (position
i predicts ``x0[i + 1]``, an autoregressive head's target), ``no_final_norm`` (the head on the stream as it stands) and
``low_state`` (with ``dtype=bfloat16``: the softmaxes' and the router's statistics in bf16 too, the precision below the
one the description states).
"""

import functools
import json
import os

import jax
import jax.numpy as jnp

HEADS_AT_ONCE = 4  # attention: 4 heads x 2,048 queries x 16,384 keys of float32 scores are 0.5 GB
QUERIES_AT_ONCE = 2048
NEG = -1e30


@functools.lru_cache(maxsize=None)
def _stated() -> dict:
    """What the configuration file beside this one states for ``loss(logits, ids)``, which is handed no configuration."""
    with open(os.path.splitext(os.path.splitext(__file__)[0])[0] + ".json") as f:
        cfg = json.load(f)
    return {"block_length": int(cfg["block_length"])}


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


def keep(L: int, block: int, kind: str = "blockdiff"):
    """The (2 L, 2 L) boolean mask, query-major, from the definition."""
    p = jnp.arange(2 * L)
    clean, blk = p >= L, (p % L) // block
    q_clean, k_clean, qb, kb = clean[:, None], clean[None, :], blk[:, None], blk[None, :]
    own = ~q_clean & ~k_clean & (kb == qb)
    if kind == "causal":
        return p[None, :] <= p[:, None]
    if kind == "blind":  # the noised half sees nothing of the clean one
        return own | (q_clean & k_clean & (kb <= qb))
    return own | (~q_clean & k_clean & (kb < qb)) | (q_clean & k_clean & (kb <= qb))


def _attention(p, h, mask, pos, eps, theta, dtype, stat):
    w = lambda leaf: leaf.astype(dtype)
    B, S, _ = h.shape
    q = jnp.einsum("bsd,dhk->bshk", h, w(p["q_proj"]["kernel"]))
    k = jnp.einsum("bsd,dhk->bshk", h, w(p["k_proj"]["kernel"]))
    v = jnp.einsum("bsd,dhk->bshk", h, w(p["v_proj"]["kernel"]))
    q = _rope(_rms(q, p["q_norm"]["scale"], eps), pos, theta)
    k = _rope(_rms(k, p["k_norm"]["scale"], eps), pos, theta)
    H, D = q.shape[2:]
    k, v = (jnp.repeat(x, H // x.shape[2], axis=2) for x in (k, v))
    G = HEADS_AT_ONCE if H % HEADS_AT_ONCE == 0 else 1
    Q = QUERIES_AT_ONCE if S % QUERIES_AT_ONCE == 0 else S

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
def _layer(p, x, m, dtype):
    eps, theta, block, kind, first, held, top_k, low = m
    stat = dtype if low else jnp.float32  # the softmaxes' and the router's type
    L = x.shape[1] // 2
    h = _rms(x, p["RMSNorm_0"]["scale"], eps)
    x = x + _attention(p["blockdiff"], h, keep(L, block, kind), jnp.arange(2 * L) % L, eps, theta, dtype, stat)
    return x + _routed(p["routed"], _rms(x, p["RMSNorm_1"]["scale"], eps), dtype, first, held, top_k, stat)


@functools.partial(jax.jit, static_argnames=("eps", "dtype", "normed"))
def _head(top, x, eps, dtype, normed=True):
    x = _rms(x, top["RMSNorm_0"]["scale"], eps) if normed else x
    return (x @ top["lm_head"]["kernel"].astype(dtype)).astype(jnp.float32)


def block_length(published, ref_cfg) -> int:
    return int(ref_cfg.get("block_length", published.get("block_length", 0)) or _stated()["block_length"])


def logits(params, ids, published, ref_cfg, dtype):
    """(B, L, rows held) float32 logits of the NOISED half of the plain forward pass over ``ids`` (B, 2 L)."""
    ids = jnp.asarray(ids, jnp.int32)
    L = ids.shape[1] // 2
    block = block_length(published, ref_cfg)
    if ids.shape[1] % 2 or L % block:
        raise ValueError(f"a row is [noised ; clean], each half whole blocks of {block}: got {ids.shape[1]} ids")
    eps = float(published["rms_norm_eps"])
    m = (eps, float(published["rope_theta"]), block, str(ref_cfg.get("mask", "blockdiff")), int(ref_cfg["held_first"]),
         int(published["num_experts"]), int(published["num_experts_per_tok"]), bool(ref_cfg.get("low_state")) and dtype != jnp.float32)
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["wte"], ids, axis=0).astype(dtype)
        for i in range(int(published["num_hidden_layers"])):
            # differentiated: a layer keeps its input and no more
            x = jax.checkpoint(functools.partial(_layer, m=m, dtype=dtype))(params[f"layer_{i}"], x)
        top = {k: v for k, v in params.items() if not k.startswith("layer_")}
        return _head(top, x[:, :L], eps=eps, dtype=dtype, normed=not ref_cfg.get("no_final_norm"))


def masked_loss(logits_, ids, block: int, mask_id: int, uniform_weights: bool = False, shift: bool = False):
    """``(1 / L) sum_{i masked} (block / m_blk(i)) CE(logits[i], x0[i])``, the mean over rows; ``m`` from ``xt`` itself."""
    ids = jnp.asarray(ids, jnp.int32)
    B, L = ids.shape[0], ids.shape[1] // 2
    xt, x0 = ids[:, :L], ids[:, L:]
    masked = xt == mask_id
    m = jnp.sum(masked.reshape(B, L // block, block), axis=-1)  # (B, K)
    weight = jnp.where(masked, 1.0 if uniform_weights else block / jnp.maximum(jnp.repeat(m, block, axis=1), 1), 0.0)
    target = jnp.roll(x0, -1, axis=1) if shift else x0
    logp = jax.nn.log_softmax(logits_.astype(jnp.float32), axis=-1)
    ce = -jnp.take_along_axis(logp, target[..., None], axis=-1)[..., 0]
    return jnp.sum(weight * ce) / (B * L)


def loss(logits_, ids):
    """The harness's interface: the block length is the file's, the mask token the last row held."""
    return masked_loss(logits_, ids, _stated()["block_length"], logits_.shape[-1] - 1)


def loss_and_grads(params, ids, published, ref_cfg, dtype):
    """((the loss, the noised half's logits), its gradient in every leaf)."""
    block = block_length(published, ref_cfg)

    def total(p):
        out = logits(p, ids, published, ref_cfg, dtype)
        mask_id = int(ref_cfg.get("mask_token_id", out.shape[-1] - 1))
        return masked_loss(out, ids, block, mask_id, bool(ref_cfg.get("uniform_weights")), bool(ref_cfg.get("shift"))), out

    return jax.value_and_grad(total, has_aux=True)(params)
