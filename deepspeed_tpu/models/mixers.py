"""Token mixers beside softmax attention over one head size: gated
delta-rule linear-attention layers with a decay a channel (KDA) or a head
(Gated DeltaNet), latent attention (MLA), without positions or with its
shared key part rotated, and grouped-query attention over the keys a learned
indexer chooses for each query (``SparseMixer``); and the four of a
decoder-hybrid-decoder stack (SambaY): a Mamba-1 selective scan (``SSMMixer``),
differential attention (``DiffAttention``), and the second half's two, which
read what the first half made instead of making their own: a gate on the scan's
output (``GatedMemory``) and differential attention over the first half's keys
and values (``DiffCrossAttention``); and grouped-query attention under the
block-diffusion mask over a doubled row, whose objective is a masked-token loss
(``BlockDiffMixer``); and a gated short convolution, whose time-mixing is neither a softmax, a scan nor a delta rule
(``ShortConvMixer``); and a Mamba-2 layer, whose scan has one decay a head and runs as products of chunks on the MXU
(``SSDMixer``). All are training-side
modules: a block built from them takes no KV cache (``LayerKind.no_cache``) and
``inference/v2`` refuses these kinds (``LayerKind.stackable``). Each class
carries its kind's record (``../layer_kind.py::LayerKind``).
"""

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from ..ops import indexed_attention as sparse
from ..ops import masks
from ..ops import placement
from ..ops.attention import attention
from ..ops.kda import HEADS_A_STEP, SAVED as SCAN_SAVED, gdn, gdn_scan, kda, kda_scan
from ..ops.pallas import conv_silu, scan_operands, short_conv
from ..ops.ssd import SAVED as SSD_SAVED, ssd
from ..ops.ssm import SAVED as SSM_SAVED, selective_scan
from ..telemetry import device_counts
from ..telemetry.registry import get_registry
from ..telemetry.tracing import region
from .config import TransformerFields
from .layers import (FLASH_SAVED, ROPE_FORM, SAVED, TILES_A_TRIP, LayerKind, LayerNorm, RMSNorm, apply_rope, rope_region,
                     scaled_rope_frequencies)


def _uniform(low, high):
    return lambda key, shape, dtype=jnp.float32: jax.random.uniform(key, shape, dtype, low, high)


def _a_log_init(key, shape, dtype=jnp.float32):
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """softplus^-1 of a step drawn log-uniformly from [1e-3, 1e-1]."""
    dt = jnp.exp(jax.random.uniform(key, shape, dtype, np.log(1e-3), np.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))


def causal_conv(x, w, axis: int = 1):
    """Depthwise causal convolution over the sequence (``axis`` of x): w
    (K, ...) one filter a channel, broadcast against x without its leading
    dimension; y_t = sum_j w_j x_{t - (K - 1) + j}."""
    K, S = w.shape[0], x.shape[axis]
    padded = jnp.pad(x, [(K - 1, 0) if d == axis else (0, 0) for d in range(x.ndim)])
    return sum(jax.lax.slice_in_dim(padded, j, j + S, axis=axis) * w[j] for j in range(K))


def gated_conv(x, w):
    """A ``conv`` layer between its two products: ``x = [B, C, u]`` (Bt, S, 3 D), ``w`` (K, D) -> ``C * causal_conv(B * u)``
    (Bt, S, D) in x's type, float32 inside: XLA's fusions, and the oracle of ``ops/pallas/short_conv.py``."""
    D = w.shape[1]
    B, C, u = (x[..., n * D:(n + 1) * D].astype(jnp.float32) for n in range(3))
    return (C * causal_conv(B * u, w.astype(jnp.float32))).astype(x.dtype)


def l2_normalize(x, eps: float = 1e-6):
    x32 = x.astype(jnp.float32)
    return x32 * jax.lax.rsqrt(jnp.sum(x32 * x32, axis=-1, keepdims=True) + eps)


# both Mamba kinds count how their convolution, bias and SiLU ran under this key of the trainer's first-call line
CONV_SILU = {"conv_silu_path": ("mixer/conv", {"op": "conv_silu", "pass": "fwd"})}

# both delta-rule kinds count what makes their scan's operands under these keys of the trainer's first-call line (the
# backward's only where that is a call of its own: XLA differentiates the plain lines)
SCAN_OPERANDS = {f"scan_operands_{pass_}": ("mixer/proj", {"op": "scan_operands", "pass": pass_}) for pass_ in ("fwd", "bwd")}


class KDAMixer(LayerKind, nn.Module):
    """Kimi Delta Attention: per head, ``S_t = (I - beta_t k_t k_t^T)
    Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T``, ``o_t = S_t^T q_t``, with q, k
    L2-normalised after a depthwise causal convolution and SiLU, a
    per-channel decay ``alpha_t = exp(-exp(A_log) softplus(W_f2 W_f1 x +
    dt_bias))`` and an output gate; state and gates in float32
    (``ops/kda.py``)."""

    cfg: TransformerFields
    keeps, hybrid = (SCAN_SAVED, SAVED), True
    paths, joined = {"kda_path": ("mixer/kernel", {"op": "kda", "pass": "fwd"}), **SCAN_OPERANDS}, {"kda_heads_a_step": HEADS_A_STEP}

    @nn.compact
    def __call__(self, x, positions=None, kv_cache=None, segment_ids=None):
        self.no_cache(kv_cache, segment_ids)
        cfg = self.cfg
        H, D, K, rank = cfg.kda_heads, cfg.kda_head_dim, cfg.kda_conv_size, cfg.kda_gate_rank
        f32 = jnp.float32
        # named (``SAVED``: what a checkpointed block keeps) are the results of the products over the model width, each in
        # the layout its consumer reads; the convolutions, SiLUs, l2 norms and the gates' second products (over ``rank``)
        # follow from them by elementwise work
        heads = lambda name: nn.DenseGeneral((H, D), use_bias=False, name=name, dtype=cfg.dtype, param_dtype=f32)
        exact = lambda dtype: jax.lax.Precision.HIGHEST if dtype == f32 else None  # the float32 gates take no bf16 pass

        def low_rank(name, dtype):
            a = nn.Dense(rank, use_bias=False, name=f"{name}_a", dtype=dtype, param_dtype=f32, precision=exact(dtype))(x.astype(dtype))
            return nn.DenseGeneral((H, D), use_bias=False, name=f"{name}_b", dtype=dtype, param_dtype=f32, precision=exact(dtype))(
                checkpoint_name(a, SAVED))

        heads_first = lambda t: jnp.swapaxes(t, 1, 2)  # (B, S, H, .) -> (B, H, S, .): the scan's layout, beside the projection
        kept = lambda name: checkpoint_name(heads_first(heads(f"{name}_proj")(x)), SAVED)
        filt = lambda name: self.param(f"{name}_conv", _uniform(-K**-0.5, K**-0.5), (K, H, D), f32)

        def conv_silu(name):
            w = filt(name)
            return nn.silu(causal_conv(kept(name), w.astype(cfg.dtype)[:, :, None, :], axis=2))

        decay = lambda: (self.param("A_log", _a_log_init, (H,), f32), self.param("dt_bias", _dt_bias_init, (H, D), f32))
        beta_of = lambda: checkpoint_name(
            nn.Dense(H, use_bias=False, name="b_proj", dtype=f32, param_dtype=f32, precision=exact(f32))(x.astype(f32)), SAVED)
        # what lies between the kept projections and the scan: one Pallas pass each way on one TPU chip
        # (``ops/pallas/scan_operands.py``), the lines below it elsewhere, which are also that kernel's oracle
        path = scan_operands.path_for(x.shape[1], D, K)
        with placement.counted("scan_operands", path, name="mixer/proj"):  # the projections, their convolutions and the gates
            if path == "kernel":
                projections, filters = zip(*[(kept(name), filt(name)) for name in "qkv"])
                a_log, dt_bias = decay()
                operands = scan_operands.scan_operands(*projections, beta_of(), *filters, heads_first(low_rank("f", f32)), a_log, dt_bias,
                                                        interpret=placement.interpret())
            else:
                q = (l2_normalize(conv_silu("q")) * D**-0.5).astype(cfg.dtype)
                k = l2_normalize(conv_silu("k")).astype(cfg.dtype)
                v = conv_silu("v")
                a_log, dt_bias = decay()
                g = -jnp.exp(a_log)[:, None, None] * jax.nn.softplus(heads_first(low_rank("f", f32)) + dt_bias[:, None, :])  # (B, H, S, D)
                beta = jax.nn.sigmoid(beta_of())
        if path == "kernel":
            with region("mixer/kernel"):
                o = kda_scan(*operands, interpret=placement.interpret())  # (B, H, S, D)
        else:
            o = kda(q, k, v, g, jnp.swapaxes(beta, 1, 2))
        with region("mixer/proj"):
            o = RMSNorm(eps=cfg.norm_eps, dtype=cfg.dtype, name="o_norm")(o) * jax.nn.sigmoid(heads_first(low_rank("g", cfg.dtype)))
            o = heads_first(o)
            return nn.DenseGeneral(cfg.d_model, axis=(-2, -1), use_bias=False, name="o_proj", dtype=cfg.dtype,
                                   param_dtype=f32)(o)


class GDNMixer(LayerKind, nn.Module):
    """Gated DeltaNet: KDA's rule with ONE decay a head and token, ``S_t = (I -
    beta_t k_t k_t^T) exp(g_t) S_{t-1} + beta_t k_t v_t^T``, ``o_t = S_t^T
    q_t``. ``gdn_key_heads`` heads of q and k, each serving ``gdn_value_heads /
    gdn_key_heads`` consecutive value heads; q, k, v go through a depthwise
    causal convolution and SiLU, q and k are L2-normalised (q also divided by
    sqrt(d)); per value head ``beta = sigmoid(x w_b)`` and ``g = -exp(A_log)
    softplus(x w_a + dt_bias)`` in float32; the output is RMS-normalised a
    head (a plain weight) and multiplied by ``silu(z)``, z a projection of its
    own (``ops/kda.py::gdn``)."""

    cfg: TransformerFields
    keeps, hybrid = (SCAN_SAVED, SAVED), True
    paths, joined = {"gdn_path": ("mixer/kernel", {"op": "gdn", "pass": "fwd"}), **SCAN_OPERANDS}, {"gdn_heads_a_step": HEADS_A_STEP}

    @nn.compact
    def __call__(self, x, positions=None, kv_cache=None, segment_ids=None):
        self.no_cache(kv_cache, segment_ids)
        cfg = self.cfg
        Hk, Hv, D, K = cfg.gdn_key_heads, cfg.gdn_value_heads, cfg.gdn_head_dim, cfg.gdn_conv_size
        f32 = jnp.float32
        heads_first = lambda t: jnp.swapaxes(t, 1, 2)  # (B, S, H, .) -> (B, H, S, .): the scan's layout, beside the projection
        # named (``SAVED``: what a checkpointed block keeps): the four projections in the scan's layout and the gates' one
        heads = lambda name, H: checkpoint_name(heads_first(nn.DenseGeneral((H, D), use_bias=False, name=f"{name}_proj", dtype=cfg.dtype,
                                                                            param_dtype=f32)(x)), SAVED)

        filt = lambda name, H: self.param(f"{name}_conv", _uniform(-K**-0.5, K**-0.5), (K, H, D), f32)

        def conv_silu(name, H):
            w = filt(name, H)
            return nn.silu(causal_conv(heads(name, H), w.astype(cfg.dtype)[:, :, None, :], axis=2))

        def gates():  # -> (beta's pre-activation, the log-decay when asked), each a number a value head and token (B, S, Hv), float32
            a_log = self.param("A_log", _a_log_init, (Hv,), f32)
            dt_bias = self.param("dt_bias", _dt_bias_init, (Hv,), f32)
            ba = checkpoint_name(nn.Dense(2 * Hv, use_bias=False, name="ba_proj", dtype=f32, param_dtype=f32,
                                          precision=jax.lax.Precision.HIGHEST)(x.astype(f32)), SAVED)  # the float32 gates take no bf16 pass
            return ba[..., :Hv], lambda: -jnp.exp(a_log) * jax.nn.softplus(ba[..., Hv:] + dt_bias)

        # as in ``KDAMixer``: the scan's q, k, beta k, beta v by one Pallas pass each way, or the lines below it
        path = scan_operands.path_for(x.shape[1], D, K)
        with placement.counted("scan_operands", path, name="mixer/proj"):  # the projections, their convolutions and the gates
            if path == "kernel":
                projections, filters = zip(*[(heads(name, H), filt(name, H)) for name, H in (("q", Hk), ("k", Hk), ("v", Hv))])
                b, decay = gates()
                operands = scan_operands.scan_operands(*projections, b, *filters, interpret=placement.interpret())
                g = decay()
            else:
                q = (l2_normalize(conv_silu("q", Hk)) * D**-0.5).astype(cfg.dtype)
                k = l2_normalize(conv_silu("k", Hk)).astype(cfg.dtype)
                v = conv_silu("v", Hv)
                b, decay = gates()
                beta = jax.nn.sigmoid(b)
                g = decay()  # (B, S, Hv)
        if path == "kernel":
            with region("mixer/kernel"):
                o = gdn_scan(*operands, jnp.swapaxes(g, 1, 2), interpret=placement.interpret())  # (B, Hv, S, D)
        else:
            o = gdn(q, k, v, jnp.swapaxes(g, 1, 2), jnp.swapaxes(beta, 1, 2))
        with region("mixer/proj"):
            o = RMSNorm(eps=cfg.norm_eps, dtype=cfg.dtype, name="o_norm")(o) * nn.silu(heads("z", Hv))
            return nn.DenseGeneral(cfg.d_model, axis=(-2, -1), use_bias=False, name="o_proj", dtype=cfg.dtype,
                                   param_dtype=f32)(heads_first(o))


class MLAMixer(LayerKind, nn.Module):
    """Latent attention: keys and values are expanded from a latent of
    ``mla_kv_rank`` (no absorption: this is the training form); a head's
    query and key are ``mla_qk_nope_dim + mla_qk_rope_dim`` wide, the second
    part of the key ONE head shared by all; values are ``mla_v_dim`` wide.
    The model's ``pos_emb`` says which of two forms this is. ``"rope"``: the
    second part of every head's query and the shared key part are rotated by
    position (``rope_theta``, ``rope_style``, the table ``mla_qk_rope_dim``
    wide), the first parts are not. Anything else: no positions, nothing
    rotates, and the shared part is simply more key. Either way the shared
    part is broadcast into every head's key, so the attention kernel sees one
    product of 192 beside values of 128, unpadded (``ops/pallas/flash_attention.py``)."""

    cfg: TransformerFields
    keeps, hybrid = (FLASH_SAVED, SAVED), True
    # ``mla_rope``: the rotation of the shared key part (no key where the model has no positions)
    paths, joined = {"mla_path": ("mixer/kernel", {"op": "mla", "pass": "fwd"}), "mla_rope": ("mixer/rope", {"op": "mla"})}, TILES_A_TRIP

    @nn.compact
    def __call__(self, x, positions=None, kv_cache=None, segment_ids=None):
        self.no_cache(kv_cache, segment_ids)
        cfg = self.cfg
        B, S, _ = x.shape
        H, dn, dr, dv = cfg.n_heads, cfg.mla_qk_nope_dim, cfg.mla_qk_rope_dim, cfg.mla_v_dim
        f32 = jnp.float32
        with region("mixer/proj"):
            q = nn.DenseGeneral((H, dn + dr), use_bias=False, name="q_proj", dtype=cfg.dtype, param_dtype=f32)(x)
            # named (``SAVED``: what a checkpointed block keeps): the latent, from which the norm and ``kv_b_proj``'s backward follow
            latent = checkpoint_name(
                nn.Dense(cfg.mla_kv_rank + dr, use_bias=False, name="kv_a_proj", dtype=cfg.dtype, param_dtype=f32)(x), SAVED)
            c, k_shared = latent[..., :cfg.mla_kv_rank], latent[..., cfg.mla_kv_rank:]
            c = RMSNorm(eps=cfg.norm_eps, dtype=cfg.dtype, name="kv_a_norm")(c)
            kv = nn.DenseGeneral((H, dn + dv), use_bias=False, name="kv_b_proj", dtype=cfg.dtype, param_dtype=f32)(c)
        # the rotation, where the model has positions (``path``: the one form ``apply_rope`` traces, ahead of the attention
        # call), and the shared key part's way into every head
        with rope_region("mla") if cfg.pos_emb == "rope" else region("mixer/rope"):
            if cfg.pos_emb == "rope":
                if positions is None:
                    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
                cos, sin = scaled_rope_frequencies(cfg, dr)
                q = apply_rope(q, cos, sin, positions, rotary_dim=dr, style=cfg.rope_style, offset=dn)  # a head's second part, in place
                k_shared = apply_rope(k_shared[:, :, None, :], cos, sin, positions, style=cfg.rope_style)[:, :, 0, :]  # once, as one head
            k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_shared[:, :, None, :], (B, S, H, dr))], axis=-1)
            # named too: the attention call's own operands, which its backward reads. Everything between the three products
            # and them (the slices, the rotation, the shared part's way into every head) is linear, so a checkpointed
            # block that keeps these makes neither ``q_proj`` nor ``kv_b_proj`` nor the rotation a second time
            q, k, v = checkpoint_name(q, SAVED), checkpoint_name(k, SAVED), checkpoint_name(kv[..., dn:], SAVED)
        # a call of unequal head sizes is latent attention's: the form that takes it counts it so (``op="mla"``)
        o = attention(q, k, v, causal=True, scale=(dn + dr)**-0.5)
        with region("mixer/proj"):
            return nn.DenseGeneral(cfg.d_model, axis=(-2, -1), use_bias=False, name="o_proj", dtype=cfg.dtype,
                                   param_dtype=f32)(o)


def _sown(intermediates, name):
    return [leaf for path, leaf in jax.tree_util.tree_leaves_with_path(intermediates)
            if any(getattr(k, "key", None) == name for k in path)]


def _count_sparse(counts):
    reg = get_registry()
    reg.counter("sparse_keys_chosen_total").inc(float(counts[:, 0].sum()))
    reg.counter("sparse_keys_visible_total").inc(float(counts[:, 1].sum()))
    reg.gauge("sparse_index_loss").set(float(counts[:, 2].mean()))


class SparseMixer(LayerKind, nn.Module):
    """Grouped-query attention whose keys a learned indexer chooses, a query at a time (the DeepSeek-Sparse-Attention
    family). With ``h`` the block's normed input, ``t`` a query and ``s <= t`` a key position:

    - main heads as ``Attention`` has them: q, k, v projections, the per-head q/k norms under ``qk_norm``, the rotation;
    - indexer, on ``stop_gradient(h)``: ``qI[t, j] = rope(h[t] Wq_j)`` for ``index_heads`` heads of ``index_head_dim``,
      ``kI[s] = rope(LayerNorm(h[s] Wk))`` (one head), ``w[t] = h[t] Ww * index_heads^-0.5 * index_head_dim^-0.5``;
      ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])``, accumulated in float32;
    - choice: ``S_t`` = the ``min(index_topk, t + 1)`` visible positions of largest ``I[t, s]``, ties to the lower
      index: discrete, no gradient, one choice a position for every head;
    - attention: head i's output at t is ``softmax_{s in S_t}(q_i[t] . k_g(i)[s] / sqrt(D))`` over ``v_g(i)[s]``;
    - the indexer's own loss, sown as ``index_loss`` (``CausalLM.loss_fn`` adds its gradient and not its value):
      ``mean_t KL(p[t, S_t] || softmax_{S_t} I[t, .])`` with ``p`` the heads' probabilities summed and renormalised
      over ``S_t``, under ``stop_gradient``. So the main leaves learn from the model's loss alone and the indexer's
      (``index_*``) from this alone.

    A sequence no longer than ``index_topk`` leaves nothing to choose: the program is then dense causal attention
    through the registry, the indexer is not traced (its leaves take a zero gradient) and nothing is sown. Sown
    beside the loss: ``sparse_keys``, (chosen, visible) pairs of the call (``ops/indexed_attention.py`` has the forms a
    backend takes), and ``choice``, the key-major mask."""

    cfg: TransformerFields
    # kept: the choice, its attention call's output and row statistics, the index loss's cotangent, which the loss's one
    # call writes beside the loss (the flash call's where every visible key is chosen); a model has this mixer in every
    # layer or in none, so its key is given though ``alone``
    sows, keeps, hybrid = ("intermediates",), (sparse.SAVED, FLASH_SAVED, SAVED), True
    paths, joined, alone = {"sparse_path": ("mixer/kernel", {"op": "sparse", "pass": "fwd"})}, {**ROPE_FORM, **sparse.INDEX_STRIP}, True

    @staticmethod
    def report(intermediates):
        """The sum over the sparse layers of their indexer's loss (None where none chose: a sequence no longer than
        ``index_topk``); a layer's (chosen pairs, visible pairs, loss) leave the step as an output for the registry."""
        losses, keys = _sown(intermediates, "index_loss"), _sown(intermediates, "sparse_keys")
        if not losses:
            return None
        device_counts.report("sparse_keys", jnp.stack([jnp.concatenate([k, l[None]]) for k, l in zip(keys, losses)]), _count_sparse)
        return sum(losses)

    @nn.compact
    def __call__(self, x, positions, kv_cache=None, segment_ids=None):
        self.no_cache(kv_cache, segment_ids)
        cfg = self.cfg
        B, S, _ = x.shape
        H, KVH, D = cfg.n_heads, cfg.kv_heads, cfg.head_dim
        J, Di, f32 = cfg.index_heads, cfg.index_head_dim, jnp.float32
        dense = lambda feats, name, of: checkpoint_name(
            nn.DenseGeneral(feats, axis=-1, use_bias=False, name=name, dtype=cfg.dtype, param_dtype=f32)(of), SAVED)
        with region("mixer/proj"):
            q, k, v = dense((H, D), "q_proj", x), dense((KVH, D), "k_proj", x), dense((KVH, D), "v_proj", x)
            if cfg.qk_norm:
                q = RMSNorm(eps=cfg.norm_eps, dtype=cfg.dtype, offset=cfg.rms_offset, name="q_norm")(q)
                k = RMSNorm(eps=cfg.norm_eps, dtype=cfg.dtype, offset=cfg.rms_offset, name="k_norm")(k)
        rotate = lambda t, width: apply_rope(t, *scaled_rope_frequencies(cfg, width), positions, style=cfg.rope_style)
        if cfg.pos_emb == "rope":
            with rope_region():
                q, k = rotate(q, D), rotate(k, D)
        scale = cfg.attn_scale or D**-0.5
        if S <= cfg.index_topk and not self.is_initializing():
            out = attention(q, k, v, causal=True, scale=scale)  # every visible key is chosen: the dense mixer's program
        else:
            path = sparse.path_for(S, cfg.index_topk)
            with region("mixer/index"):  # the indexer's projections and scores, on an input cut from the graph
                h = jax.lax.stop_gradient(x)
                q_i, k_i = dense((J, Di), "index_q_proj", h), dense(Di, "index_k_proj", h)
                k_i = LayerNorm(eps=cfg.norm_eps, dtype=cfg.dtype, name="index_k_norm")(k_i)[:, :, None, :]
                w = dense(J, "index_w_proj", h).astype(f32) * (J**-0.5 * Di**-0.5)
                if cfg.pos_emb == "rope":
                    q_i, k_i = rotate(q_i, Di), rotate(k_i, Di)
                q_i, k_i, w = jnp.swapaxes(q_i, 1, 2), k_i[:, :, 0, :], jnp.swapaxes(w, 1, 2)  # heads first: (B, J, S, .)
                scores_t = sparse.index_scores(q_i, k_i, w, path=path)
            mask_t = sparse.select_keys(scores_t, cfg.index_topk, path=path)
            with region("mixer/kernel"):
                out, lse = sparse.sparse_attention(q, k, v, mask_t, scale=scale, path=path)
            with region("mixer/index_loss"):
                loss = sparse.index_loss(q_i, k_i, w, scores_t, q, k, lse, mask_t, scale=scale, dtype=cfg.dtype, path=path)
                chosen = jnp.sum(mask_t.astype(f32))
            self.sow("intermediates", "index_loss", loss)
            self.sow("intermediates", "choice", mask_t)  # key-major (B, Sk, Sq) int8: for a caller that asks; a step drops it
            self.sow("intermediates", "sparse_keys", jnp.stack([chosen, jnp.asarray(B * S * (S + 1) / 2, f32)]))
        with region("mixer/proj"):
            init = nn.initializers.variance_scaling(cfg.sparse_out_init_scale**2, "fan_in", "truncated_normal")  # 1: flax's own
            return nn.DenseGeneral(cfg.d_model, axis=(-2, -1), use_bias=False, name="o_proj", dtype=cfg.dtype,
                                   param_dtype=f32, kernel_init=init)(out)


def _s4d_init(key, shape, dtype=jnp.float32):
    """log(1 .. N) for every channel (S4D-real): the state's columns decay at rates 1 to N times the step."""
    return jnp.broadcast_to(jnp.log(jnp.arange(1, shape[1] + 1, dtype=dtype)), shape)


class SSMMixer(LayerKind, nn.Module):
    """A Mamba-1 layer. ``[u, z] = x W_in``; ``u = silu(causal_conv(u) + b_c)``; ``[r, B, C] = u W_x`` (``ssm_dt_rank`` +
    2 ``ssm_state`` wide); ``delta = softplus(r W_dt + b_dt)`` in float32; ``A = -exp(A_log)``; per channel ``d``, from a
    zero state, ``h_t = exp(delta_t[d] A[d]) h_{t-1} + delta_t[d] u_t[d] B_t`` and ``y_t[d] = h_t . C_t + D[d] u_t[d]``
    (``ops/ssm.py``); ``out = (y * silu(z)) W_out``. It hands on ``y`` (the scan's output with the ``D`` term, before the
    ``z`` gate) as ``scan_out``: a later ``gmu`` layer gates it instead of scanning. The convolution, its bias and the SiLU
    are one Pallas call each way on one TPU chip, from the first half of the kept ``W_in`` product in place
    (``ops/pallas/conv_silu.py``), and the plain line, XLA's fusions, elsewhere."""

    cfg: TransformerFields
    keeps, hybrid = (SSM_SAVED, SAVED), True
    paths = {"ssm_path": ("mixer/kernel", {"op": "ssm", "pass": "fwd"}), **CONV_SILU}
    gives = ("scan_out",)

    @nn.compact
    def __call__(self, x, positions=None, kv_cache=None, segment_ids=None):
        self.no_cache(kv_cache, segment_ids)
        cfg = self.cfg
        inner, N, K, rank, f32 = cfg.ssm_inner, cfg.ssm_state, cfg.ssm_conv, cfg.ssm_dt_rank, jnp.float32
        # named (``SAVED``: what a checkpointed block keeps): the three products' results; the convolution, the SiLUs and
        # the softplus follow from them by elementwise work
        dense = lambda feats, name, of: checkpoint_name(
            nn.Dense(feats, use_bias=False, name=name, dtype=cfg.dtype, param_dtype=f32)(of), SAVED)
        with region("mixer/proj"):
            uz = dense(2 * inner, "in_proj", x)
            u, z = uz[..., :inner], uz[..., inner:]
        path = conv_silu.path_for(x.shape[1], 0, (inner,), K)
        with placement.counted("conv_silu", path, name="mixer/conv"):
            w = self.param("conv_kernel", _uniform(-K**-0.5, K**-0.5), (K, inner), f32)
            bias = self.param("conv_bias", nn.initializers.zeros, (inner,), f32)
            if path == "kernel":  # from the kept product's first half in place, float32 inside
                u, = conv_silu.conv_silu(uz, w, bias, 0, (inner,), placement.interpret())
            else:
                u = nn.silu(causal_conv(u, w.astype(cfg.dtype)) + bias.astype(cfg.dtype))
        with region("mixer/proj"):
            r_b_c = dense(rank + 2 * N, "x_proj", u)
            r, B, C = r_b_c[..., :rank], r_b_c[..., rank:rank + N], r_b_c[..., rank + N:]
            dt_bias = self.param("dt_bias", _dt_bias_init, (inner,), f32)
            delta = jax.nn.softplus(dense(inner, "dt_proj", r).astype(f32) + dt_bias)
            A = -jnp.exp(self.param("A_log", _s4d_init, (inner, N), f32))
            D = self.param("D", nn.initializers.ones, (inner,), f32)
        y = selective_scan(u, delta, A, B, C, D)
        with region("mixer/proj"):
            out = nn.Dense(cfg.d_model, use_bias=False, name="out_proj", dtype=cfg.dtype, param_dtype=f32)(y * nn.silu(z))
        return out, {"scan_out": y}


# both kinds of differential attention count their layer under one key of the trainer's first-call line
_DIFF_PATHS = {"diff_path": ("mixer/kernel", {"op": "diff", "pass": "fwd"})}


def _differential(mod, q, k, v, layer, window):
    """Differential attention's two maps and what follows them, for ``mod`` (whose parameters the lambdas, the sub-norm and
    ``o_proj`` are): q (B, S, H, D) is H / 2 pairs (the first half of the heads with the second), k (B, S, KVH, D) KVH / 2
    pairs likewise, v (B, S, KVH / 2, 2 D). ``A_j = softmax(q_j k_j^T / sqrt(D)) v`` under the causal mask (and the
    ``window``); ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + l0``, ``l0 = 0.8 - 0.6 exp(-0.3 layer)`` with ``layer`` the
    layer's published index; ``O = RMSNorm_2D(A_1 - lambda A_2) (1 - l0)``; ``out = concat(O) W_o``. Two calls of the one
    attention op (on a TPU the flash kernel at D beside 2 D, grouped): the difference, its norm and the lambdas are
    elementwise work after them, in float32."""
    cfg = mod.cfg
    H, KVH, D, f32 = cfg.n_heads, cfg.kv_heads, cfg.head_dim, jnp.float32
    # the two calls are counted as the layer's (``op="diff"``) by the form that takes them (``ops/attention.py``)
    maps = [attention(q[:, :, half], k[:, :, kv_half], v, causal=True, window=window, scale=D**-0.5, count_as={"op": "diff"})
            for half, kv_half in ((slice(0, H // 2), slice(0, KVH // 2)), (slice(H // 2, H), slice(KVH // 2, KVH)))]
    with region("mixer/diff"):
        lq1, lk1, lq2, lk2 = (mod.param(name, nn.initializers.normal(0.1), (D,), f32)
                              for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"))
        l0 = 0.8 - 0.6 * jnp.exp(-0.3 * layer.astype(f32))
        lam = jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) + l0
        o = maps[0].astype(f32) - lam * maps[1].astype(f32)
        o = (RMSNorm(eps=cfg.norm_eps, dtype=f32, name="subln")(o) * (1.0 - l0)).astype(cfg.dtype)
    with region("mixer/proj"):
        return nn.DenseGeneral(cfg.d_model, axis=(-2, -1), use_bias=False, name="o_proj", dtype=cfg.dtype, param_dtype=f32)(o)


class DiffAttention(LayerKind, nn.Module):
    """Differential attention (``_differential``) over the layer's own keys and values, under ``sliding_window`` (the kind
    ``diff_window``) or none (``diff``): ``n_heads`` query heads and ``n_kv_heads`` key heads of ``head_dim``, values as
    ``n_kv_heads / 2`` heads twice as wide; no positions, no biases. It hands its keys and values on (``shared_k``,
    ``shared_v``: a later ``diff_cross`` layer attends the nearest earlier giver's) and takes its own published index."""

    cfg: TransformerFields
    window: Optional[int] = None
    keeps, hybrid = (FLASH_SAVED, SAVED), True
    paths, joined = _DIFF_PATHS, TILES_A_TRIP
    gives, takes = ("shared_k", "shared_v"), ("layer",)

    @classmethod
    def from_config(cls, cfg, kind):
        return cls(cfg, window=cfg.sliding_window if kind == "diff_window" else None, name=kind)

    @nn.compact
    def __call__(self, x, positions=None, kv_cache=None, segment_ids=None, layer=None):
        self.no_cache(kv_cache, segment_ids)
        cfg = self.cfg
        H, KVH, D = cfg.n_heads, cfg.kv_heads, cfg.head_dim
        heads = lambda feats, name: checkpoint_name(
            nn.DenseGeneral(feats, axis=-1, use_bias=False, name=name, dtype=cfg.dtype, param_dtype=jnp.float32)(x), SAVED)
        with region("mixer/proj"):
            q, k, v = heads((H, D), "q_proj"), heads((KVH, D), "k_proj"), heads((KVH // 2, 2 * D), "v_proj")
        return _differential(self, q, k, v, layer, self.window), {"shared_k": k, "shared_v": v}


class DiffCrossAttention(LayerKind, nn.Module):
    """Differential attention whose keys and values are an earlier layer's (``shared_k``, ``shared_v``): its own query
    projection, lambdas, sub-norm and ``o_proj``; full causal."""

    cfg: TransformerFields
    keeps, hybrid = (FLASH_SAVED, SAVED), True
    paths, joined = _DIFF_PATHS, TILES_A_TRIP
    takes = ("shared_k", "shared_v", "layer")

    @nn.compact
    def __call__(self, x, positions=None, kv_cache=None, segment_ids=None, shared_k=None, shared_v=None, layer=None):
        self.no_cache(kv_cache, segment_ids)
        cfg = self.cfg
        with region("mixer/proj"):
            q = checkpoint_name(nn.DenseGeneral((cfg.n_heads, cfg.head_dim), axis=-1, use_bias=False, name="q_proj", dtype=cfg.dtype,
                                                param_dtype=jnp.float32)(x), SAVED)
        return _differential(self, q, shared_k, shared_v, layer, None)


class GatedMemory(LayerKind, nn.Module):
    """A gated memory unit: ``out = (M * silu(x W_g)) W_out`` with ``M`` an earlier layer's scan output (``scan_out``): no
    scan, no convolution and no state of its own."""

    cfg: TransformerFields
    keeps, hybrid = (SAVED,), True
    takes = ("scan_out",)

    @nn.compact
    def __call__(self, x, positions=None, kv_cache=None, segment_ids=None, scan_out=None):
        self.no_cache(kv_cache, segment_ids)
        cfg = self.cfg
        dense = lambda feats, name: nn.Dense(feats, use_bias=False, name=name, dtype=cfg.dtype, param_dtype=jnp.float32)
        with region("mixer/proj"):
            gate = checkpoint_name(dense(cfg.ssm_inner, "in_proj")(x), SAVED)
        with region("mixer/memory"):
            gated = scan_out * nn.silu(gate)
        with region("mixer/proj"):
            return dense(cfg.d_model, "out_proj")(gated)


class ShortConvMixer(LayerKind, nn.Module):
    """A gated short convolution (the LFM2 family's ``conv`` operator): ``[B, C, u] = x W_in`` (three chunks of
    ``d_model`` in that order, no bias); ``g = B * u``; ``c_t = sum_j w_j g_{t - (K - 1) + j}`` (depthwise, causal,
    ``K = conv_kernel`` taps, one filter a channel, no bias, NO activation); ``out = (C * c) W_out``. Two gates around a
    filter between two products: bandwidth-bound. What lies between the products is one Pallas call each way on one TPU
    chip (``ops/pallas/short_conv.py``) and ``gated_conv``, XLA's fusions over ``causal_conv``, elsewhere; float32 inside either way."""

    cfg: TransformerFields
    keeps, hybrid = (short_conv.SAVED, SAVED), True
    paths = {"conv_path": ("mixer/conv", {"op": "short_conv", "pass": "fwd"})}

    @nn.compact
    def __call__(self, x, positions=None, kv_cache=None, segment_ids=None):
        self.no_cache(kv_cache, segment_ids)
        cfg = self.cfg
        D, K, f32 = cfg.d_model, cfg.conv_kernel, jnp.float32
        dense = lambda feats, name: nn.Dense(feats, use_bias=False, name=name, dtype=cfg.dtype, param_dtype=f32)
        with region("mixer/proj"):  # named (``SAVED``: what a checkpointed block keeps): the one product over the model width
            bcu = checkpoint_name(dense(3 * D, "in_proj")(x), SAVED)
        w = self.param("conv_kernel", _uniform(-K**-0.5, K**-0.5), (K, D), f32)
        path = short_conv.path_for(x.shape[1], D, K)
        with region("mixer/conv", op="short_conv", path=path, **{"pass": "fwd"}):  # the gates and the filter
            gated = short_conv.short_conv(bcu, w) if path == "kernel" else gated_conv(bcu, w)
        with region("mixer/proj"):
            return dense(D, "out_proj")(gated)


class SSDMixer(LayerKind, nn.Module):
    """A Mamba-2 layer (state-space duality). ``[z, xBC, dt] = h W_in`` (``inner`` + ``inner + 2 G N`` + ``H`` columns,
    ``inner = ssd_heads * ssd_head_dim``, no bias); ``xBC = silu(causal_conv(xBC) + b_c)``, depthwise over ``ssd_conv`` tokens;
    split x (``H`` heads of ``P``), B, C (``G = ssd_groups`` vectors of ``N = ssd_state``; head ``h`` reads group ``h // (H /
    G)``); ``delta = softplus(dt + dt_bias)`` in float32, one a head and token; ``A = -exp(A_log)``, one a head; per head,
    from a zero state, ``S_t = exp(delta_t A) S_{t-1} + delta_t x_t B_t^T`` (``P x N``, float32) and ``y_t = S_t C_t + D
    x_t`` (``ops/ssd.py``); ``y = GroupRMSNorm(y * silu(z)) * w``: the gate FIRST, then the norm over each group's ``inner /
    G`` channels; ``out = y W_out``. The scan is the chunked Pallas kernel on one TPU chip (``ops/pallas/ssd.py``) and the
    token-by-token recurrence elsewhere; the convolution, its bias and the SiLU are one Pallas call each way there, which
    reads xBC's columns of the kept ``W_in`` product in place and writes x, B and C as the scan takes them
    (``ops/pallas/conv_silu.py``), and the plain lines, XLA's fusions, elsewhere; the softplus and the gated norm are XLA's."""

    cfg: TransformerFields
    keeps, hybrid = (SSD_SAVED, SAVED), True
    paths = {"ssd_path": ("mixer/kernel", {"op": "ssd", "pass": "fwd"}), **CONV_SILU}

    @nn.compact
    def __call__(self, x, positions=None, kv_cache=None, segment_ids=None):
        self.no_cache(kv_cache, segment_ids)
        cfg = self.cfg
        H, P, N, G, K, f32 = cfg.ssd_heads, cfg.ssd_head_dim, cfg.ssd_state, cfg.ssd_groups, cfg.ssd_conv, jnp.float32
        inner, Bt, S = H * P, *x.shape[:2]
        dense = lambda feats, name: nn.Dense(feats, use_bias=False, name=name, dtype=cfg.dtype, param_dtype=f32)
        with region("mixer/proj"):  # named (``SAVED``: what a checkpointed block keeps): the one product over the model width
            zxbcdt = checkpoint_name(dense(2 * inner + 2 * G * N + H, "in_proj")(x), SAVED)
            z, xbc, dt = zxbcdt[..., :inner], zxbcdt[..., inner:2 * inner + 2 * G * N], zxbcdt[..., 2 * inner + 2 * G * N:]
        widths = (inner, G * N, G * N)
        path = conv_silu.path_for(S, inner, widths, K)
        with placement.counted("conv_silu", path, name="mixer/conv"):
            w = self.param("conv_kernel", _uniform(-K**-0.5, K**-0.5), (K, inner + 2 * G * N), f32)
            bias = self.param("conv_bias", nn.initializers.zeros, (inner + 2 * G * N,), f32)
            if path == "kernel":
                # x, B and C from the kept product's middle columns in place, float32 inside; kept under the scan's name,
                # whose residuals they are: the block's backward runs no second forward call (``PERF.md`` section 6, PR 60)
                xs, B, C = (checkpoint_name(v, SSD_SAVED) for v in conv_silu.conv_silu(zxbcdt, w, bias, inner, widths, placement.interpret()))
            else:
                xbc = nn.silu(causal_conv(xbc, w.astype(cfg.dtype)) + bias.astype(cfg.dtype))
                xs, B, C = xbc[..., :inner], xbc[..., inner:inner + G * N], xbc[..., inner + G * N:]
        with region("mixer/proj"):
            delta = jax.nn.softplus(dt.astype(f32) + self.param("dt_bias", _dt_bias_init, (H,), f32))
            A = -jnp.exp(self.param("A_log", _a_log_init, (H,), f32))
            D = self.param("D", nn.initializers.ones, (H,), f32)
        y = ssd(xs.reshape(Bt, S, H, P), delta, A, B.reshape(Bt, S, G, N), C.reshape(Bt, S, G, N), D)
        with region("mixer/proj"):  # the gate, then the norm a group (elementwise on what the block keeps), and the product out
            gated = (y.reshape(Bt, S, G, inner // G).astype(f32) * nn.silu(z.astype(f32)).reshape(Bt, S, G, inner // G))
            normed = gated * jax.lax.rsqrt(jnp.mean(gated * gated, axis=-1, keepdims=True) + cfg.norm_eps)
            scale = self.param("norm_scale", nn.initializers.ones, (inner,), f32)
            return dense(cfg.d_model, "out_proj")((normed.reshape(Bt, S, inner) * scale).astype(cfg.dtype))


def _count_diffusion(counts):
    reg = get_registry()
    reg.counter("diffusion_masked_positions_total").inc(float(counts[0]))
    reg.counter("diffusion_positions_total").inc(float(counts[1]))
    reg.gauge("diffusion_weight_sum").set(float(counts[2]))


class BlockDiffMixer(LayerKind, nn.Module):
    """Grouped-query attention of a block-diffusion model in TRAINING (BD3-LM's vectorised form, which SDAR's follows).
    A row of the batch is ``[xt ; x0]``: ``L`` noised ids, then the ``L`` clean ones, each half ``L / block_length``
    whole blocks. Index ``p`` of the ``2 L``: ``pos(p) = p mod L`` (the rotation's position, whatever ``positions`` the
    model hands in: they count the row), ``blk(p) = pos(p) // block_length``. The main heads are ``Attention``'s (q, k,
    v projections, the per-head q/k norms under ``qk_norm``, the rotation at ``pos``); a query keeps, of the keys
    (``ops/masks.py::BlockDiffusion``): noised -> noised of its OWN block; noised -> clean of EARLIER blocks; clean ->
    clean of its own and earlier blocks; clean -> noised never. ``L^2 + L block_length`` pairs of ``4 L^2``.

    The kind's objective is not next-token prediction (``targets``): the head runs over the noised half alone, position
    i predicts ``x0[i]`` (no shift), and a masked position weighs ``block_length / m`` with ``m`` the masked positions
    of its block, all read from the ids: ``loss = (1 / L) sum_{i masked} (block_length / m_blk(i)) CE_i``, the mean over
    the batch's rows.

    No KV cache and no packed segments (generation denoises a block in several steps against the clean prefix's cache:
    ``inference/v2`` has no such step); ``blockdiff_qk_init_scale`` is where the q/k norms' weights start (``config.py``
    says why a routed model wants them above one)."""

    cfg: TransformerFields
    keeps, hybrid = (FLASH_SAVED, SAVED), True
    paths, alone = {"blockdiff_path": ("mixer/kernel", {"op": "blockdiff", "pass": "fwd"})}, True
    # static at trace time, counted where the kernel's walk is chosen: tiles visited / tiles of the square, pairs kept
    joined = {"blockdiff_tiles": ("mixer/kernel", None, "tiles"), "blockdiff_pairs": ("mixer/kernel", None, "pairs"), **TILES_A_TRIP,
              **ROPE_FORM}

    @staticmethod
    def halves(cfg, S: int) -> int:
        """L of a row of ``S`` ids, or in words what a row must be."""
        if cfg.block_length < 1 or S % 2 or (S // 2) % cfg.block_length:
            raise ValueError(f"a block-diffusion row is [noised ; clean]: an even count of ids, each half a whole number of "
                             f"blocks of block_length={cfg.block_length}; got {S}")
        return S // 2

    @staticmethod
    def targets(cfg, input_ids):
        """-> (the hidden states' positions the head runs over, their targets, a weight a target), all from the ids:
        compare with the mask token, count a block, divide. The counts leave the step for the registry
        (``diffusion_masked_positions_total`` / ``diffusion_positions_total``, and the weights' sum a row, which is L
        whenever every block masks a position)."""
        B, S = input_ids.shape
        L, Bl = BlockDiffMixer.halves(cfg, S), cfg.block_length
        masked = (input_ids[:, :L] == cfg.mask_token_id).reshape(B, L // Bl, Bl)
        m = jnp.sum(masked, axis=-1, keepdims=True).astype(jnp.float32)  # masked positions a block
        weights = jnp.where(masked, Bl / jnp.maximum(m, 1.0), 0.0).reshape(B, L)
        device_counts.report("diffusion", jnp.stack([jnp.sum(m), jnp.asarray(B * L, jnp.float32), jnp.sum(weights) / B]),
                             _count_diffusion)
        return slice(0, L), input_ids[:, L:], weights, B * L

    @nn.compact
    def __call__(self, x, positions=None, kv_cache=None, segment_ids=None):
        if kv_cache is not None or segment_ids is not None:
            raise NotImplementedError("a blockdiff layer is block-diffusion TRAINING over a doubled row: it takes no KV cache "
                                      "(generation denoises a block in several steps, which inference/v2 does not run) and no "
                                      "packed segments")
        cfg = self.cfg
        B, S, _ = x.shape
        L = self.halves(cfg, S)
        H, KVH, D = cfg.n_heads, cfg.kv_heads, cfg.head_dim
        dense = lambda feats, name: checkpoint_name(
            nn.DenseGeneral(feats, axis=-1, use_bias=False, name=name, dtype=cfg.dtype, param_dtype=jnp.float32)(x), SAVED)
        with region("mixer/proj"):
            q, k, v = dense((H, D), "q_proj"), dense((KVH, D), "k_proj"), dense((KVH, D), "v_proj")
            if cfg.qk_norm:
                norm = lambda name: RMSNorm(eps=cfg.norm_eps, dtype=cfg.dtype, offset=cfg.rms_offset,
                                            init_scale=cfg.blockdiff_qk_init_scale, name=name)
                q, k = norm("q_norm")(q), norm("k_norm")(k)
        if cfg.pos_emb == "rope":
            with rope_region():
                pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32) % L, (B, S))
                cos, sin = scaled_rope_frequencies(cfg, D)
                q, k = (apply_rope(t, cos, sin, pos, style=cfg.rope_style) for t in (q, k))
        # the form that takes the call counts it under the mask's own name (``op="blockdiff"``)
        out = attention(q, k, v, mask=masks.BlockDiffusion(cfg.block_length, L), scale=cfg.attn_scale or D**-0.5)
        with region("mixer/proj"):
            return nn.DenseGeneral(cfg.d_model, axis=(-2, -1), use_bias=False, name="o_proj", dtype=cfg.dtype,
                                   param_dtype=jnp.float32)(out)
