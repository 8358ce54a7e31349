"""Plain reference of ``ouro-2.6b-l8``: a looped decoder. ONE stack of layers is run ``total_ut_steps`` = T times on the
same weights, every sublayer between two norms, and after every pass one head gives that pass's logits and one gate says
how much of what is left exits there; the training loss is the expected loss over the T exits less an entropy bonus.
Straightforward ``jax.numpy``: the loop over passes a Python ``for``, a masked softmax over whole rows a few heads and a band
of queries at a time, the head a block of positions at a time. It imports nothing of the program and shares with it only
the names of the parameter tree it is handed.

    x(0) = E[ids]                                            (not scaled)
    for t = 1 .. T, THE SAME parameters every t:
        h = x(t-1)
        for l = 0 .. L-1:
            h = h + N2_l(Attn_l(N1_l(h)))                    (layer_l/RMSNorm_0, RMSNorm_1: before and AFTER the sublayer,
            h = h + N4_l(FFN_l(N3_l(h)))                      RMSNorm_2, RMSNorm_3   the second inside the residual branch)
        x(t) = Nf(h)                                         (RMSNorm_0 of the tree's top: INSIDE the loop, so pass t + 1
                                                              starts from the normed state)
        logits(t) = x(t) W_head                              (lm_head/kernel, untied, one for all passes)
        lambda_t = sigmoid(x(t) . w_g + b_g)                 (exit_gate/kernel (d, 1), exit_gate/bias (1,), float32)
    p_1 = lambda_1,  p_t = lambda_t prod_{j<t}(1 - lambda_j) (1 < t < T),  p_T = prod_{j<T}(1 - lambda_j)
    nll_t = the next-token cross-entropy of logits(t), a position
    loss = mean over the S - 1 targets of [ sum_t p_t nll_t - beta H(p) ],  H(p) = -sum_t p_t log p_t

RMSNorm: ``x / sqrt(mean(x^2) + rms_norm_eps) * w`` in float32. Attn: 16 heads of ``head_dim`` 128 for q, k and v
(``num_key_value_heads`` 16), no biases, rotate-half over the whole head at ``rope_theta`` 1e6 and positions ``0 .. S-1`` in
every pass, causal softmax at 128^-0.5, ``o_proj``. FFN: ``W_down (silu(h W_gate) * h W_up)``, 5,632 wide. ``beta`` is
``ref_cfg["beta"]`` (not a key of the source; ``assumed`` in the configuration's file, as is every line above that the
source's keys do not settle).

``dtype=float32`` is the truth (matmuls at the highest precision); ``dtype=bfloat16`` the plain low-precision path: weights
and activations in bf16, the norms' and the softmax's statistics, the gate, the exit distribution and the loss in float32.

The harness hands whatever ``logits(params, ids, published, ref_cfg, dtype)`` returns to ``loss(out, ids)`` untouched
(``lib/reference.py::first_loss``): four passes' float32 logits are 6.4 GB at one row of 8,192 x 49,152, so ``logits``
returns a dict, every pass reduced to what the loss needs a position: ``nll`` (T, B, S-1), ``lam`` (T, B, S-1: lambda_t;
the last is computed and unused), ``p`` (T, B, S-1) and ``beta``, beside ``last`` (B, S, V), the last pass's logits. ``pass_logits``
gives every pass's logits at chosen positions, ``loss_and_grads`` the loss's gradient in every leaf.

``ref_cfg`` (the configuration's ``reference`` block) also carries the controls, each a defect: ``steps_short`` (T - 1
passes), ``norm_outside_loop`` (pass t + 1 starts from h, not from Nf(h); the head and the gate still read Nf(h)),
``no_sandwich`` (N2 and N4 left out: pre-norm only), ``uniform_exit`` (p_t = 1 / T), ``no_entropy`` (beta = 0),
``layers_short`` (L - 1 layers) and ``low_state`` (with ``dtype=bfloat16``: the norms' and the softmax's statistics, the
gate, the exit distribution and the cross-entropy's log-sum in bf16 too, the precision below the one stated).
"""

import functools

import jax
import jax.numpy as jnp

HEADS_AT_ONCE = 4  # attention: 4 heads x 2,048 queries x 8,192 keys of float32 scores are 0.27 GB
QUERIES_AT_ONCE = 2048
HEAD_ROWS = 2048  # the head: 2,048 positions x 49,152 float32 logits are 0.4 GB
NEG = -1e30


def _rms(x, scale, eps, stat):
    xs = x.astype(stat)
    return (xs * jax.lax.rsqrt(jnp.mean(xs * xs, axis=-1, keepdims=True) + jnp.asarray(eps, stat)) * scale.astype(stat)).astype(x.dtype)


def _rope(x, theta, stat):
    """Rotate-half over the whole head, positions 0 .. S-1: x (B, S, H, D)."""
    S, D = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv  # (S, D/2)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : D // 2].astype(jnp.float32), x[..., D // 2:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def _attention(p, h, theta, dtype, stat):
    w = lambda leaf: leaf.astype(dtype)
    B, S, _ = h.shape
    q, k, v = (jnp.einsum("bsd,dhk->bshk", h, w(p[f"{name}_proj"]["kernel"])) for name in "qkv")
    q, k = _rope(q, theta, stat), _rope(k, theta, stat)
    H, D = q.shape[2:]
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


def _ffn(p, h, dtype):
    w = lambda leaf: leaf.astype(dtype)
    return (jax.nn.silu(h @ w(p["gate_proj"]["kernel"])) * (h @ w(p["up_proj"]["kernel"]))) @ w(p["down_proj"]["kernel"])


@functools.partial(jax.jit, static_argnames=("m", "dtype"))
def _layer(p, h, m, dtype):
    eps, theta, sandwich, low = m
    stat = dtype if low else jnp.float32
    norm = lambda x, n: _rms(x, p[f"RMSNorm_{n}"]["scale"], eps, stat)
    after = norm if sandwich else (lambda x, n: x)
    h = h + after(_attention(p["attn"], norm(h, 0), theta, dtype, stat), 1)
    return h + after(_ffn(p["mlp"], norm(h, 2), dtype), 3)


def _statics(published, ref_cfg, dtype):
    low = bool(ref_cfg.get("low_state")) and dtype != jnp.float32
    return (float(published["rms_norm_eps"]), float(published["rope_theta"]), not ref_cfg.get("no_sandwich"), low)


def states(params, ids, published, ref_cfg, dtype):
    """The passes' normed states ``x(1) .. x(T)``, each (B, S, d)."""
    m = _statics(published, ref_cfg, dtype)
    stat = dtype if m[-1] else jnp.float32
    T = int(published["total_ut_steps"]) - (1 if ref_cfg.get("steps_short") else 0)
    L = int(published["num_hidden_layers"]) - (1 if ref_cfg.get("layers_short") else 0)
    final = lambda h: _rms(h, params["RMSNorm_0"]["scale"], m[0], stat)
    x = jnp.take(params["wte"], jnp.asarray(ids, jnp.int32), axis=0).astype(dtype)
    out = []
    for _ in range(T):
        for i in range(L):  # differentiated: an application of a layer keeps its input and no more
            x = jax.checkpoint(functools.partial(_layer, m=m, dtype=dtype))(params[f"layer_{i}"], x)
        out.append(final(x))
        if not ref_cfg.get("norm_outside_loop"):
            x = out[-1]
    return out


def _head(params, x, dtype):
    return (x @ params["lm_head"]["kernel"].astype(dtype)).astype(jnp.float32)


def _nll(params, x, targets, dtype, stat):
    """(B, S-1): the cross-entropy of position s's logits against ``targets[s]``, ``HEAD_ROWS`` positions at a time."""
    B, S, d = x.shape
    R = HEAD_ROWS if S % HEAD_ROWS == 0 else S
    padded = jnp.concatenate([targets, jnp.zeros((B, 1), targets.dtype)], axis=1)  # the last position has no target

    @jax.checkpoint
    def some(args):
        xs, ts = args
        lg = _head(params, xs, dtype).astype(stat)
        return (jax.nn.logsumexp(lg, axis=-1) - jnp.take_along_axis(lg, ts[..., None], axis=-1)[..., 0]).astype(jnp.float32)

    blocks = lambda a: jnp.moveaxis(a.reshape(B, S // R, R, *a.shape[2:]), 1, 0)
    return jnp.moveaxis(jax.lax.map(some, (blocks(x), blocks(padded))), 0, 1).reshape(B, S)[:, :-1]


def exit_distribution(lam):
    """``p`` (T, ...) from ``lambda`` (T, ...): the last pass takes what is left, so ``lambda_T`` is unused."""
    left = jnp.cumprod(1.0 - lam[:-1], axis=0)  # prod_{j<=t}(1 - lambda_j)
    before = jnp.concatenate([jnp.ones_like(left[:1]), left[:-1]], axis=0)
    return jnp.concatenate([lam[:-1] * before, left[-1:]], axis=0)


def reduced(params, ids, published, ref_cfg, dtype):
    """(what ``loss`` needs of the plain forward pass over ``ids`` (B, S): ``nll``, ``lam``, ``p``, ``beta``; the passes' states)."""
    ids = jnp.asarray(ids, jnp.int32)
    low = bool(ref_cfg.get("low_state")) and dtype != jnp.float32
    stat = dtype if low else jnp.float32
    with jax.default_matmul_precision("highest"):
        xs = states(params, ids, published, ref_cfg, dtype)
        nll = jnp.stack([_nll(params, x, ids[:, 1:], dtype, stat) for x in xs])
        w_g, b_g = params["exit_gate"]["kernel"][:, 0].astype(stat), params["exit_gate"]["bias"].astype(stat)
        lam = jnp.stack([jax.nn.sigmoid(jnp.einsum("bsd,d->bs", x.astype(stat), w_g) + b_g)[:, :-1] for x in xs])
        p = jnp.full_like(lam, 1.0 / len(xs)) if ref_cfg.get("uniform_exit") else exit_distribution(lam)
        return {"nll": nll, "lam": lam.astype(jnp.float32), "p": p.astype(jnp.float32),
                "beta": 0.0 if ref_cfg.get("no_entropy") else float(ref_cfg["beta"])}, xs


def logits(params, ids, published, ref_cfg, dtype):
    """What ``loss`` needs of the plain forward pass over ``ids`` (B, S), and ``last``, the last pass's logits: see the module's text."""
    out, xs = reduced(params, ids, published, ref_cfg, dtype)
    with jax.default_matmul_precision("highest"):
        return dict(out, last=_head(params, xs[-1], dtype))


def loss(out, ids=None):
    """The mean over the S - 1 targets of ``sum_t p_t nll_t - beta H(p)``, float32."""
    p, nll = out["p"], out["nll"]
    entropy = -jnp.sum(p * jnp.log(jnp.maximum(p, 1e-30)), axis=0)
    return jnp.mean(jnp.sum(p * nll, axis=0) - out["beta"] * entropy)


def pass_logits(params, ids, published, ref_cfg, dtype, positions):
    """(T, B, len(positions), V) float32: every pass's logits at ``positions`` of the row."""
    with jax.default_matmul_precision("highest"):
        at = jnp.asarray(positions, jnp.int32)
        return jnp.stack([_head(params, x[:, at], dtype) for x in states(params, jnp.asarray(ids, jnp.int32), published, ref_cfg, dtype)])


def loss_and_grads(params, ids, published, ref_cfg, dtype):
    """((the loss, ``nll``, ``lam`` and ``p`` a pass and position), the loss's gradient in every leaf)."""

    def total(tree):
        out = reduced(tree, ids, published, ref_cfg, dtype)[0]
        return loss(out, ids), {k: v for k, v in out.items() if k != "beta"}

    return jax.value_and_grad(total, has_aux=True)(params)
