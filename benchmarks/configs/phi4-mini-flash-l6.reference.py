"""Plain reference of ``phi4-mini-flash-l6``: the decoder-hybrid-decoder stack of Phi-4-mini-flash-reasoning
(SambaY) as the layers the file holds (``layers_here``, published indices). Every layer is ``h = x + Mixer(LN(x));
y = h + MLP(LN(h))`` with LayerNorm (scale and bias), a SwiGLU FFN without biases, a final LayerNorm and a tied head
over the rows held; no positional encoding. With ``n`` the published depth, the mixer of published layer ``i``:

- ``i`` even, ``i <= n / 2``: Mamba-1. ``[u, z] = x W_in``; ``u = silu(causal_conv(u) + b_c)``; ``[r, B, C] = u W_x``;
  ``delta = softplus(r W_dt + b_dt)``; ``A = -exp(A_log)``; ``h_t = exp(delta_t A) h_{t-1} + (delta_t u_t) (x) B_t``;
  ``y_t = h_t C_t + D u_t``; ``out = (y * silu(z)) W_out``. Its ``y`` is the memory ``M`` the gated units read: the
  LAST such layer's (published layer ``n / 2``).
- ``i`` odd, ``i < n / 2``: differential attention over a window of ``sliding_window``; ``i = n / 2 + 1``: the same,
  full causal, and its keys and values are what the cross layers read. Queries in pairs (the first half of the heads
  with the second), keys likewise, values half as many heads twice as wide: ``A_j = softmax_masked(q_j k_j^T /
  sqrt(D)) v``; ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + l0(i)``, ``l0(i) = 0.8 - 0.6 exp(-0.3 i)``; ``O =
  RMSNorm(A_1 - lambda A_2) (1 - l0(i))``; ``out = concat(O) W_o``.
- ``i`` even, ``i >= n / 2 + 2``: a gated memory unit, ``out = (M * silu(x W_g)) W_out``.
- ``i`` odd, ``i >= n / 2 + 3``: differential cross-attention: its own ``W_q``, lambdas, sub-norm and ``W_o``; the keys
  and values of layer ``n / 2 + 1``; full causal.

Straightforward ``jax.numpy``: the scan token by token (a ``lax.scan``, no chunks, no kernel), attention as masked
softmax over whole rows a few heads at a time. It imports nothing of the program and shares with it only the names of
the parameter tree it is handed; the scan's sizes are read off that tree's shapes.

``dtype=float32`` is the truth (matmuls at the highest precision); ``dtype=bfloat16`` the plain low-precision path:
weights and activations in bf16; the scan's state, its step ``delta`` and its decay, the softmaxes, the norms'
statistics and the difference with its sub-norm in float32, as the program states them.

``ref_cfg`` (the configuration's ``reference`` block) carries the controls, each one thing wrong: ``no_window`` (the
window layers attend every earlier key), ``no_lambda`` (lambda = 0: the second map is left out), ``gated_memory``
(``M`` taken after the ``z`` gate), ``own_keys`` (a cross layer's keys and values are layer ``n / 2 + 1``'s
projections of the cross layer's OWN input) and ``low_state`` (with ``dtype=bfloat16``: the scan's state, step and
decay in bf16 too, the precision below the one stated).

So that a gradient of it fits a chip at 8192 tokens, the token scan is cut into stretches of ``STRETCH`` tokens whose
steps the backward makes again (``jax.checkpoint``), and so are a group of heads' softmax and every layer as a whole:
the same arithmetic, less of it kept.
"""

import functools
import math

import jax
import jax.numpy as jnp

HEADS_AT_ONCE = 4  # attention: 4 x S x S float32 scores are 1 GB at S = 8192
STRETCH = 64       # the scan: tokens between two kept states when it is differentiated


def _layer_norm(x, p, eps):
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
    return ((x32 - mean) * jax.lax.rsqrt(var + eps) * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)).astype(x.dtype)


def _mamba(p, h, dtype, low_state):
    """-> (the mixer's output, the scan's output y with the D term and before the z gate, y after the gate)."""
    w = lambda leaf: leaf.astype(dtype)
    f32 = dtype if low_state else jnp.float32  # the state's, the step's and the decay's type
    inner, N = p["A_log"].shape
    K, rank = p["conv_kernel"].shape[0], p["dt_proj"]["kernel"].shape[0]
    uz = h @ w(p["in_proj"]["kernel"])
    u, z = uz[..., :inner], uz[..., inner:]
    S = u.shape[1]
    padded = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))
    u = jax.nn.silu(sum(padded[:, j:j + S] * w(p["conv_kernel"])[j] for j in range(K)) + w(p["conv_bias"]))
    r_b_c = u @ w(p["x_proj"]["kernel"])
    r, B, C = r_b_c[..., :rank], r_b_c[..., rank:rank + N], r_b_c[..., rank + N:]
    delta = jax.nn.softplus((r @ w(p["dt_proj"]["kernel"])).astype(f32) + p["dt_bias"].astype(f32))
    A = -jnp.exp(p["A_log"].astype(f32))

    def step(state, xs):  # state (B, inner, N)
        u_t, dt_t, b_t, c_t = xs
        state = (jnp.exp(dt_t[..., None] * A) * state + (dt_t * u_t)[..., None] * b_t[:, None, :]).astype(f32)
        return state, jnp.sum(state * c_t[:, None, :], axis=-1)

    xs = tuple(jnp.moveaxis(x.astype(f32), 1, 0) for x in (u, delta, B, C))
    state0 = jnp.zeros((u.shape[0], inner, N), f32)
    if S % STRETCH:
        _, y = jax.lax.scan(step, state0, xs)
    else:  # the same steps, a stretch at a time
        stretch = jax.checkpoint(lambda state, part: jax.lax.scan(step, state, part))
        _, y = jax.lax.scan(stretch, state0, tuple(x.reshape(S // STRETCH, STRETCH, *x.shape[1:]) for x in xs))
        y = y.reshape(S, *y.shape[2:])
    y = (jnp.moveaxis(y, 0, 1).astype(jnp.float32) + p["D"].astype(jnp.float32) * u.astype(jnp.float32)).astype(dtype)
    gated = y * jax.nn.silu(z)
    return gated @ w(p["out_proj"]["kernel"]), y, gated


def _maps(q, k, v, window, dtype):
    """softmax_masked(q k^T / sqrt(D)) v for q (B, S, H, D), k (B, S, G, D), v (B, S, G, Dv), H a multiple of G."""
    B, S, H, D = q.shape
    k, v = (jnp.repeat(x, H // x.shape[2], axis=2) for x in (k, v))
    rows, cols = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    keep = (rows >= cols) if window is None else (rows >= cols) & (cols > rows - window)

    @jax.checkpoint
    def some_heads(qkv):  # (G, B, S, .) each
        qh, kh, vh = qkv
        s = jnp.einsum("gbqk,gbtk->gbqt", qh, kh).astype(jnp.float32) * D ** -0.5
        a = jax.nn.softmax(jnp.where(keep, s, -1e30), axis=-1).astype(dtype)
        return jnp.einsum("gbqt,gbtk->gbqk", a, vh)

    G = HEADS_AT_ONCE if H % HEADS_AT_ONCE == 0 else 1
    grouped = lambda x: jnp.moveaxis(x, 2, 0).reshape(H // G, G, B, S, x.shape[-1])
    o = jax.lax.map(some_heads, (grouped(q), grouped(k), grouped(v)))
    return jnp.moveaxis(o.reshape(H, B, S, v.shape[-1]), 0, 2)  # (B, S, H, Dv)


def _differential(p, q, k, v, number, window, eps, dtype, with_lambda):
    w = lambda leaf: leaf.astype(dtype)
    f32 = jnp.float32
    H, G = q.shape[2], k.shape[2]
    a1 = _maps(q[:, :, :H // 2], k[:, :, :G // 2], v, window, dtype).astype(f32)
    a2 = _maps(q[:, :, H // 2:], k[:, :, G // 2:], v, window, dtype).astype(f32)
    l0 = 0.8 - 0.6 * math.exp(-0.3 * number)
    lam = jnp.exp(jnp.sum(p["lambda_q1"].astype(f32) * p["lambda_k1"].astype(f32))) \
        - jnp.exp(jnp.sum(p["lambda_q2"].astype(f32) * p["lambda_k2"].astype(f32))) + l0
    o = a1 - lam * a2 if with_lambda else a1
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) * p["subln"]["scale"].astype(f32) * (1.0 - l0)
    return jnp.einsum("bshk,hkd->bsd", o.astype(dtype), w(p["o_proj"]["kernel"]))


def _keys_values(p, h, dtype):
    w = lambda leaf: leaf.astype(dtype)
    return jnp.einsum("bsd,dhk->bshk", h, w(p["k_proj"]["kernel"])), jnp.einsum("bsd,dhk->bshk", h, w(p["v_proj"]["kernel"]))


@functools.partial(jax.jit, static_argnames=("kind", "m", "dtype"))
def _layer(p, x, memory, keys, values, kv_weights, kind, m, dtype):
    """One layer -> (activations, the memory, keys and values handed on). ``kv_weights``: the full layer's key and value
    projections, for the control in which a cross layer projects its own input with them."""
    eps, number, window, low_state, with_lambda, gated_memory, own_keys = m
    w = lambda leaf: leaf.astype(dtype)
    h = _layer_norm(x, p["LayerNorm_0"], eps)
    query = lambda part: jnp.einsum("bsd,dhk->bshk", h, w(part["q_proj"]["kernel"]))
    if kind == "ssm":
        mixed, y, gated = _mamba(p["ssm"], h, dtype, low_state)
        memory = gated if gated_memory else y
    elif kind in ("diff", "diff_window"):
        part = p[kind]
        keys, values = _keys_values(part, h, dtype)
        mixed = _differential(part, query(part), keys, values, number, window if kind == "diff_window" else None, eps, dtype, with_lambda)
    elif kind == "gmu":
        part = p["gmu"]
        mixed = (memory * jax.nn.silu(h @ w(part["in_proj"]["kernel"]))) @ w(part["out_proj"]["kernel"])
    else:  # diff_cross
        part = p["diff_cross"]
        if own_keys:
            keys, values = _keys_values(kv_weights, h, dtype)
        mixed = _differential(part, query(part), keys, values, number, None, eps, dtype, with_lambda)
    x = x + mixed
    h = _layer_norm(x, p["LayerNorm_1"], eps)
    mlp = p["mlp"]
    x = x + (jax.nn.silu(h @ w(mlp["gate_proj"]["kernel"])) * (h @ w(mlp["up_proj"]["kernel"]))) @ w(mlp["down_proj"]["kernel"])
    return x, memory, keys, values


def kind_of(number: int, depth: int) -> str:
    """The mixer of published layer ``number`` of a stack of ``depth`` layers."""
    half = depth // 2
    if number % 2 == 0:
        return "ssm" if number <= half else "gmu"
    return "diff_window" if number < half else "diff" if number == half + 1 else "diff_cross"


def logits(params, ids, published, ref_cfg, dtype):
    """(B, S, rows held) float32 logits of the plain forward pass over ``ids`` (B, S)."""
    eps = float(published["layer_norm_eps"])
    numbers = [int(n) for n in published["layers_here"]]
    kinds = [kind_of(n, int(published["published_layers"])) for n in numbers]
    low_state = bool(ref_cfg.get("low_state")) and dtype != jnp.float32
    window = None if ref_cfg.get("no_window") else int(published["sliding_window"])
    kv_weights = next(({name: params[f"layer_{i}"]["diff"][name] for name in ("k_proj", "v_proj")} for i, kind in enumerate(kinds) if kind == "diff"), None)
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["wte"], jnp.asarray(ids, jnp.int32), axis=0).astype(dtype)
        memory = keys = values = None
        for i, (number, kind) in enumerate(zip(numbers, kinds)):
            m = (eps, number, window, low_state, not ref_cfg.get("no_lambda"), bool(ref_cfg.get("gated_memory")), bool(ref_cfg.get("own_keys")))
            layer = functools.partial(_layer, kind=kind, m=m, dtype=dtype)
            # differentiated: a layer keeps its inputs and no more
            x, memory, keys, values = jax.checkpoint(layer)(params[f"layer_{i}"], x, memory, keys, values, kv_weights if kind == "diff_cross" else None)
        h = _layer_norm(x, params["LayerNorm_0"], eps)
        return (h @ params["wte"].astype(dtype).T).astype(jnp.float32)
