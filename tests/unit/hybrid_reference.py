"""Plain ``jax.numpy`` forms of the hybrid layers, for the unit tests: the
same mathematics as the benchmark's reference of its hybrid configuration
(``benchmarks/configs/kimi-linear-48b-l5e8.reference.py``; the package and its
unit tests may not import ``benchmarks/``), on the program's parameter tree.
Float32 throughout; callers set the matmul precision."""

import jax
import jax.numpy as jnp

L2_EPS = 1e-6


def rms(x, scale, eps=1e-5):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def conv_silu(x, w):
    K, S = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0), (0, 0)))
    return jax.nn.silu(sum(padded[:, j:j + S] * w[j] for j in range(K)))


def l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def delta_rule(q, k, v, alpha, beta):
    """S_t = (I - b k k^T) Diag(a) S_{t-1} + b k v^T, o_t = S_t^T q_t, token by token."""
    def step(state, xs):
        q_t, k_t, v_t, a_t, b_t = xs
        state = state * a_t[..., None]
        state = state + k_t[..., None] * (b_t[..., None] * (v_t - jnp.einsum("bhk,bhkv->bhv", k_t, state)))[..., None, :]
        return state, jnp.einsum("bhk,bhkv->bhv", q_t, state)

    B, S, H, D = q.shape
    _, o = jax.lax.scan(step, jnp.zeros((B, H, D, v.shape[-1]), jnp.float32),
                        tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, alpha, beta)))
    return jnp.moveaxis(o, 0, 1)


def scan_kernel_results(q, k, v, g, beta, heads_a_step=None):
    """What the scan kernel (interpreted) gives on a batch of one as ``kda_chunked`` / ``gdn_chunked`` would hand it over
    (``g`` a number a channel, or a number a head and token: the per-head form, q and k with their own fewer heads):
    ``scan_fwd``'s outputs, saved states and inverses, then ``scan_bwd``'s five gradients for a fixed cotangent, and the
    heads a grid step the rule gave the two calls. ``heads_a_step``: a number that takes the rule's place."""
    from unittest import mock

    from deepspeed_tpu.ops.pallas import kda as K

    q, k, v, g, beta = (x[0] for x in (q, k, v, g, beta))
    pad = [(0, 0), (0, -q.shape[1] % K.CHUNK)]
    q, k, v, g, beta = (jnp.pad(x, pad + [(0, 0)] * (x.ndim - 2)) for x in (q, k, v, g, beta))
    kb, vb = beta[..., None] * jnp.repeat(k, v.shape[0] // k.shape[0], axis=0), beta[..., None] * v
    g = g if g.ndim == 3 else g.reshape(-1, 1, K.CHUNK)
    do = jax.random.normal(jax.random.PRNGKey(9), vb.shape, vb.dtype)
    chosen = [K.heads_a_step(q, vb, g, backward) for backward in (False, True)]
    with mock.patch.object(K, "heads_a_step", K.heads_a_step if heads_a_step is None else lambda *a: heads_a_step):
        o, states, inverses = K.scan_fwd(q, k, kb, vb, g, interpret=True)
        return (o, states, inverses, *K.scan_bwd(q, k, kb, vb, g, states, inverses, do, interpret=True)), chosen


def kernel_of_one_head_a_step(args, heads_a_step):
    """The rule gave ``heads_a_step`` to forward and backward on ``args`` (q, k, v, g, beta of a batch of one), and every
    result of the kernel (outputs, saved states, inverses, the five gradients) is, bit for bit, what one head a grid step
    gives on the same operands. (As the suite compiles for the CPU: with ``DS_TEST_XLA_OPT=1`` XLA fuses the interpreted
    bodies of two heads otherwise than one's and the last bit of dk moves; on the chip all eight are equal, PERF.md, PR 47.)"""
    got, chosen = scan_kernel_results(*args)
    assert chosen == [heads_a_step, heads_a_step]
    for a, b in zip(got, scan_kernel_results(*args, heads_a_step=1)[0]):
        assert a.shape == b.shape and a.dtype == b.dtype and bool(jnp.array_equal(a, b))


def kda(p, h, eps=1e-5):
    heads = lambda name: conv_silu(jnp.einsum("bsd,dhk->bshk", h, p[f"{name}_proj"]["kernel"]), p[f"{name}_conv"])
    D = p["q_conv"].shape[-1]
    q, k, v = l2(heads("q")) * D ** -0.5, l2(heads("k")), heads("v")
    g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(
        jnp.einsum("bsr,rhk->bshk", h @ p["f_a"]["kernel"], p["f_b"]["kernel"]) + p["dt_bias"])
    o = delta_rule(q, k, v, jnp.exp(g), jax.nn.sigmoid(h @ p["b_proj"]["kernel"]))
    gate = jax.nn.sigmoid(jnp.einsum("bsr,rhk->bshk", h @ p["g_a"]["kernel"], p["g_b"]["kernel"]))
    return jnp.einsum("bshk,hkd->bsd", rms(o, p["o_norm"]["scale"], eps) * gate, p["o_proj"]["kernel"])


def rotate_pairs(x, theta):
    """x (B, S, heads, D) at positions 0 .. S-1: the pair (2i, 2i + 1) of token t turned by t * theta^(-2i / D)."""
    S, D = x.shape[1], x.shape[-1]
    angle = (jnp.arange(S, dtype=jnp.float32)[:, None] * theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D))[None, :, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * jnp.cos(angle) - odd * jnp.sin(angle), odd * jnp.cos(angle) + even * jnp.sin(angle)], axis=-1).reshape(x.shape)


def mla(p, h, eps=1e-5, theta=None):
    """``theta``: rotate the second part of every head's query and the one shared key part; None: no positions."""
    B, S, _ = h.shape
    q = jnp.einsum("bsd,dhk->bshk", h, p["q_proj"]["kernel"])
    latent = h @ p["kv_a_proj"]["kernel"]
    rank = p["kv_a_norm"]["scale"].shape[0]
    rope = latent.shape[-1] - rank
    nope = q.shape[-1] - rope
    kv = jnp.einsum("bsr,rhk->bshk", rms(latent[..., :rank], p["kv_a_norm"]["scale"], eps), p["kv_b_proj"]["kernel"])
    shared = latent[:, :, None, rank:]
    if theta is not None:
        q, shared = jnp.concatenate([q[..., :nope], rotate_pairs(q[..., nope:], theta)], axis=-1), rotate_pairs(shared, theta)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(shared, (B, S, q.shape[2], rope))], axis=-1)
    s = jnp.einsum("bqhk,bthk->bhqt", q, k) * q.shape[-1] ** -0.5
    keep = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    a = jax.nn.softmax(jnp.where(keep, s, -1e30), axis=-1)
    return jnp.einsum("bhqt,bthk,hkd->bqd", a, kv[..., nope:], p["o_proj"]["kernel"])


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def routed(p, h, first, top_k, scale, shared=1):
    """The part of the routed FFN the experts ``first ..`` (as many as the
    tree holds) add, as a loop over them with a dense mask, plus ``shared``
    shared experts, each a SwiGLU of its own on its columns of the tree's one."""
    x = h.reshape(-1, h.shape[-1])
    scores = jax.nn.sigmoid(x @ p["gate"]["kernel"])
    _, idx = jax.lax.top_k(scores + p["select_bias"], top_k)
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    weights = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20) * scale
    y = jnp.zeros_like(x)
    for i in range(int(shared)):
        f = p["shared_gate_proj"]["kernel"].shape[1] // int(shared)
        cols = slice(i * f, (i + 1) * f)
        y = y + swiglu(x, p["shared_gate_proj"]["kernel"][:, cols], p["shared_up_proj"]["kernel"][:, cols], p["shared_down_proj"]["kernel"][cols])
    for e in range(p["experts_wg"].shape[0]):
        w_e = jnp.sum(jnp.where(idx == first + e, weights, 0.0), axis=-1, keepdims=True)
        y = y + w_e * swiglu(x, p["experts_wg"][e], p["experts_wi"][e], p["experts_wo"][e])
    return y.reshape(h.shape)


# ---- the fourth configuration's layers: Gated DeltaNet, output-gated grouped-query attention, softmax routing ----
def rms_offset(x, scale, eps=1e-6):
    """x / rms * (1 + w): the zero-centered weight."""
    return rms(x, 1.0 + scale, eps)


def gdn(p, h, eps=1e-6, decay=True):
    """One decay a value head and token; a key head's q and k for each of its value heads."""
    heads = lambda name: conv_silu(jnp.einsum("bsd,dhk->bshk", h, p[f"{name}_proj"]["kernel"]), p[f"{name}_conv"])
    D = p["q_conv"].shape[-1]
    q, k, v = l2(heads("q")) * D ** -0.5, l2(heads("k")), heads("v")
    Hv = v.shape[2]
    q, k = (jnp.repeat(x, Hv // x.shape[2], axis=2) for x in (q, k))
    ba = h @ p["ba_proj"]["kernel"]
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[..., Hv:] + p["dt_bias"])
    alpha = jnp.broadcast_to((jnp.exp(g) if decay else jnp.ones_like(g))[..., None], q.shape)
    o = delta_rule(q, k, v, alpha, jax.nn.sigmoid(ba[..., :Hv]))
    z = jnp.einsum("bsd,dhk->bshk", h, p["z_proj"]["kernel"])
    return jnp.einsum("bshk,hkd->bsd", rms(o, p["o_norm"]["scale"], eps) * jax.nn.silu(z), p["o_proj"]["kernel"])


def rotate_half_leading(x, theta, rotated):
    """The first ``rotated`` dims of each head, halves (i, i + rotated / 2) turned by t * theta^(-2i / rotated)."""
    S = x.shape[1]
    angle = (jnp.arange(S, dtype=jnp.float32)[:, None] * theta ** (-jnp.arange(0, rotated, 2, dtype=jnp.float32) / rotated))[None, :, None, :]
    a, b = x[..., :rotated // 2], x[..., rotated // 2:rotated]
    return jnp.concatenate([a * jnp.cos(angle) - b * jnp.sin(angle), b * jnp.cos(angle) + a * jnp.sin(angle), x[..., rotated:]], axis=-1)


def gated_attention(p, h, theta, rotary, eps=1e-6, gated=True):
    """q_proj's columns a head: the query, then the gate; q/k norms (1 + w); grouped keys and values."""
    S = h.shape[1]
    qg = jnp.einsum("bsd,dhk->bshk", h, p["q_proj"]["kernel"])
    D = qg.shape[-1] // 2
    q, gate = qg[..., :D], qg[..., D:]
    k, v = (jnp.einsum("bsd,dhk->bshk", h, p[f"{n}_proj"]["kernel"]) for n in ("k", "v"))
    q = rotate_half_leading(rms_offset(q, p["q_norm"]["scale"], eps), theta, int(D * rotary))
    k = rotate_half_leading(rms_offset(k, p["k_norm"]["scale"], eps), theta, int(D * rotary))
    k, v = (jnp.repeat(x, q.shape[2] // x.shape[2], axis=2) for x in (k, v))
    s = jnp.einsum("bqhk,bthk->bhqt", q, k) * D ** -0.5
    a = jax.nn.softmax(jnp.where(jnp.arange(S)[:, None] >= jnp.arange(S)[None, :], s, -1e30), axis=-1)
    o = jnp.einsum("bhqt,bthk->bqhk", a, v)
    return jnp.einsum("bqhk,hkd->bqd", o * jax.nn.sigmoid(gate) if gated else o, p["o_proj"]["kernel"])


def routed_softmax(p, h, first, top_k):
    """Softmax over all experts, the top k renormalised; the held experts' part plus the shared expert times its sigmoid gate."""
    x = h.reshape(-1, h.shape[-1])
    chosen, idx = jax.lax.top_k(jax.nn.softmax(x @ p["gate"]["kernel"], axis=-1), top_k)
    weights = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    y = swiglu(x, *(p[f"shared_{n}_proj"]["kernel"] for n in ("gate", "up", "down"))) * jax.nn.sigmoid(x @ p["shared_expert_gate"]["kernel"])
    for e in range(p["experts_wg"].shape[0]):
        w_e = jnp.sum(jnp.where(idx == first + e, weights, 0.0), axis=-1, keepdims=True)
        y = y + w_e * swiglu(x, p["experts_wg"][e], p["experts_wi"][e], p["experts_wo"][e])
    return y.reshape(h.shape)


def deltanet_model_loss(params, ids, kinds, first, top_k, theta, rotary, eps=1e-6):
    """Mean next-token loss of pre-norm blocks of the layers above, every norm (1 + w), an untied head."""
    x = params["wte"][ids]
    for i, (mixer, _) in enumerate(kinds):
        p = params[f"layer_{i}"]
        h = rms_offset(x, p["RMSNorm_0"]["scale"], eps)
        x = x + (gdn(p["gdn"], h, eps) if mixer == "gdn" else gated_attention(p["attn"], h, theta, rotary, eps))
        x = x + routed_softmax(p["routed"], rms_offset(x, p["RMSNorm_1"]["scale"], eps), first, top_k)
    logits = rms_offset(x, params["RMSNorm_0"]["scale"], eps) @ params["lm_head"]["kernel"]
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1))
