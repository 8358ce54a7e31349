"""Fused (chunked) cross-entropy over a large vocabulary.

The TPU analogue of the reference's fused logits/softmax inference kernels
(``csrc/transformer/inference/csrc/pt_binding.cpp:1945+``) applied to the
*training* loss: never materialize the fp32 ``(B, S, V)`` logits tensor.
At GPT-2 scale (B=8, S=1024, V=50257) the naive loss costs ~1.6 GB of
fp32 HBM writes in forward plus the same again for ``d_logits`` in
backward; this op chunks the sequence dimension and recomputes each
chunk's logits in the backward pass, so peak extra memory is one
``(B, C, V)`` block and the only residuals are the hidden states and a
per-token logsumexp.

Chunking is along the sequence dim (not tokens, not vocab) so that under
SPMD the batch dimension stays sharded over ``data``/``fsdp`` and each
device processes its local rows of every chunk; XLA inserts the psum for
the weight gradient as usual.

All matmuls run in the input dtype (bf16 on TPU) with fp32 accumulation
(``preferred_element_type``) — MXU-friendly. The weight cotangent is
accumulated in fp32 across chunks and cast to ``w.dtype`` once at the end.

A target's part in the sum is a weight: ``valid`` below is either the
boolean "not ``ignore_index``" (weights one and zero, the older callers'
program, equation for equation) or a float32 weight a target (a masked-token
diffusion loss weighs a target by its block's noise); the forward multiplies
the token's loss by it and the hand-written backward the token's
``dlogits``. Such a weight is a CONSTANT of the objective and takes no
gradient (``fused_cross_entropy_sums`` stops it).

A weight that is itself differentiated (a looped model's exit distribution:
``sum_t p_t nll_t`` with a gradient into ``p_t``) is the caller's own, outside
this op: ``fused_cross_entropy_tokens`` hands back every token's loss, the
caller multiplies and sums, and the cotangent that comes back a token is the
weight the backward multiplies that token's ``dlogits`` by. The weight's own
gradient, ``nll_i``, is then plain autodiff of the caller's product. Same
chunks, same residuals (the hidden states and a logsumexp a token).
"""

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..analysis import knobs

_CHUNK_TARGET = knobs.get_int("DS_TPU_CE_CHUNK")  # 0 = auto (memory-budgeted)
_BUDGET_MB = knobs.get_int("DS_TPU_CE_BUDGET_MB")


def _auto_target(S: int, B: int, V: int) -> int:
    """Largest chunk whose fp32 logits block fits the budget.

    Hardware A/B (round 3, v5e, GPT-2-125M bs=16): chunk=S beat chunk=512
    by 2.2% (119.3k vs 116.8k tok/s) — the lax.scan carry costs more than
    the larger logits block saves, so prefer the biggest chunk memory
    allows and only chunk when the block would blow the budget.
    """
    rows = max(1, (_BUDGET_MB << 20) // max(1, B * V * 4))
    return S if rows >= S else max(64, rows)


def _pick_chunk(S: int, target: Optional[int] = None, B: int = 8, V: int = 50257) -> int:
    target = target or _CHUNK_TARGET or _auto_target(S, B, V)
    if target <= 0:
        target = 512
    # fall back only DOWNWARD: a chunk above the requested target would
    # exceed the (B, C, V) logits-block memory the caller tuned for
    for c in (target, 512, 256, 128, 64, 32):
        if c <= target and S % c == 0 and c <= S:
            return c
    # no power-of-two-ish candidate divides S (prime/odd S): take the
    # largest divisor of S that still respects the target
    best = 1
    d = 1
    while d * d <= S:
        if S % d == 0:
            for c in (d, S // d):
                if best < c <= target:
                    best = c
        d += 1
    if best >= min(32, S):
        return best
    # only tiny divisors exist (prime-ish S): chunk=1..31 would serialize the
    # projection into S near-scalar matmuls — worse than the memory blowup.
    # Take the full block and say so instead of silently cliffing either way.
    import warnings
    warnings.warn(
        f"fused CE: seq len {S} has no divisor in [32, {target}]; using a single "
        f"(B, {S}, V) logits block — set DS_TPU_CE_CHUNK or pad S to a multiple "
        "of a power of two to restore chunking", stacklevel=2)
    return S


def _project(xs: jnp.ndarray, w: jnp.ndarray, vd_layout: bool) -> jnp.ndarray:
    """(B,C,D) x w -> (B,C,V) fp32 logits. w is (V,D) when vd_layout (tied
    embedding) else (D,V)."""
    if vd_layout:
        return jax.lax.dot_general(xs, w, (((2,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    return jax.lax.dot_general(xs, w, (((2,), (0,)), ((), ())), preferred_element_type=jnp.float32)


def _weighed(valid, x):
    """``x`` a target, times its weight: ``valid`` boolean (kept or dropped) or a float32 weight."""
    return jnp.where(valid, x, 0.0) if valid.dtype == jnp.bool_ else valid * x


def _own_labels(labels, n_own: int, vocab_axis: str):
    """Under ``vocab_axis``, where this device holds ``n_own`` consecutive
    entries of the vocabulary: the labels counted from its first entry, and
    which of them fall into its slice."""
    own = labels - jax.lax.axis_index(vocab_axis) * n_own
    return own, (own >= 0) & (own < n_own)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _fused_ce_sum(x, w, b, labels, valid, vd_layout: bool, chunk: int, has_bias: bool, vocab_axis: Optional[str]):
    total, _ = _ce_fwd_scan(x, w, b, labels, valid, vd_layout, chunk, has_bias, vocab_axis)
    return total


def _ce_fwd_scan(x, w, b, labels, valid, vd_layout, chunk, has_bias, vocab_axis, per_token=False):
    """(the weighed sum, a logsumexp a token (nb, B, C)); ``per_token``: and every token's weighed loss (nb, B, C)."""
    B, S, D = x.shape
    nb = S // chunk
    xs = x.reshape(B, nb, chunk, D).transpose(1, 0, 2, 3)  # (nb, B, C, D)
    ls = labels.reshape(B, nb, chunk).transpose(1, 0, 2)
    vs = valid.reshape(B, nb, chunk).transpose(1, 0, 2)

    def body(acc, inp):
        xc, lc, vc = inp  # (B,C,D), (B,C), (B,C)
        logits = _project(xc, w, vd_layout)  # (B,C,V) fp32
        if has_bias:
            logits = logits + b
        lse = jax.nn.logsumexp(logits, axis=-1)  # (B,C)
        if vocab_axis is None:
            gold = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        else:  # this device's slice of the vocabulary: the sum of exponentials and the gold logit are summed over devices
            top = jax.lax.pmax(lse, vocab_axis)
            lse = top + jnp.log(jax.lax.psum(jnp.exp(lse - top), vocab_axis))
            own, mine = _own_labels(lc, logits.shape[-1], vocab_axis)
            gold = jnp.take_along_axis(logits, jnp.where(mine, own, 0)[..., None], axis=-1)[..., 0]
            gold = jax.lax.psum(jnp.where(mine, gold, 0.0), vocab_axis)
        nll = _weighed(vc, lse - gold)
        return acc + jnp.sum(nll), ((lse, nll) if per_token else lse)

    total, lses = jax.lax.scan(body, jnp.zeros((), jnp.float32), (xs, ls, vs))
    return total, lses  # lses: (nb, B, C)


def _ce_vjp_fwd(x, w, b, labels, valid, vd_layout, chunk, has_bias, vocab_axis):
    total, lses = _ce_fwd_scan(x, w, b, labels, valid, vd_layout, chunk, has_bias, vocab_axis)
    return total, (x, w, b, labels, valid, lses)


def _ce_vjp_bwd(vd_layout, chunk, has_bias, vocab_axis, res, g):
    """``g``: the sum's cotangent, a scalar, or (``fused_cross_entropy_tokens``) a cotangent a token, (B, S)."""
    x, w, b, labels, valid, lses = res
    if vocab_axis is not None:
        # every device holds the whole sum, so each is handed a share of its cotangent; ``dx`` comes out as this
        # slice's part of the sum over the vocabulary, for the caller to sum over devices
        g = jax.lax.psum(g, vocab_axis)
        own, mine = _own_labels(labels, w.shape[0] if vd_layout else w.shape[1], vocab_axis)
        labels = jnp.where(mine, own, -1)  # -1: no entry of this slice, a row of zeros in the one-hot
    B, S, D = x.shape
    V = w.shape[0] if vd_layout else w.shape[1]
    nb = S // chunk
    xs = x.reshape(B, nb, chunk, D).transpose(1, 0, 2, 3)
    ls = labels.reshape(B, nb, chunk).transpose(1, 0, 2)
    vs = valid.reshape(B, nb, chunk).transpose(1, 0, 2)
    gs = (g.reshape(B, nb, chunk).transpose(1, 0, 2),) if jnp.ndim(g) else ()  # a cotangent a token rides the scan with its chunk

    def body(carry, inp):
        dw_acc, db_acc = carry
        xc, lc, vc, lse, *gc = inp
        logits = _project(xc, w, vd_layout)
        if has_bias:
            logits = logits + b
        p = jnp.exp(logits - lse[..., None])  # softmax, (B,C,V) fp32
        onehot = jax.nn.one_hot(lc, V, dtype=jnp.float32)
        dlogits = (p - onehot) * _weighed(vc, gc[0] if gc else g)[..., None]  # (B,C,V) fp32
        dlogits_c = dlogits.astype(xc.dtype)
        if vd_layout:
            # w: (V,D); dxc = dlogits @ w ; dw += dlogits^T @ xc
            dxc = jax.lax.dot_general(dlogits_c, w, (((2,), (0,)), ((), ())))
            dwc = jax.lax.dot_general(dlogits_c, xc, (((0, 1), (0, 1)), ((), ())),
                                      preferred_element_type=jnp.float32)  # (V,D)
        else:
            # w: (D,V); dxc = dlogits @ w^T ; dw += xc^T @ dlogits
            dxc = jax.lax.dot_general(dlogits_c, w, (((2,), (1,)), ((), ())))
            dwc = jax.lax.dot_general(xc, dlogits_c, (((0, 1), (0, 1)), ((), ())),
                                      preferred_element_type=jnp.float32)  # (D,V)
        if has_bias:
            db_acc = db_acc + jnp.sum(dlogits, axis=(0, 1))
        return (dw_acc + dwc, db_acc), dxc.astype(xc.dtype)

    dw0 = jnp.zeros(w.shape, jnp.float32)
    db0 = jnp.zeros((V,), jnp.float32)
    (dw, db), dxs = jax.lax.scan(body, (dw0, db0), (xs, ls, vs, lses) + gs)
    dx = dxs.transpose(1, 0, 2, 3).reshape(B, S, D).astype(x.dtype)
    return dx, dw.astype(w.dtype), db.astype(b.dtype), None, None


_fused_ce_sum.defvjp(_ce_vjp_fwd, _ce_vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _fused_ce_tokens(x, w, b, labels, valid, vd_layout: bool, chunk: int, has_bias: bool):
    return _ce_tokens_fwd(x, w, b, labels, valid, vd_layout, chunk, has_bias)[0]


def _ce_tokens_fwd(x, w, b, labels, valid, vd_layout, chunk, has_bias):
    _, (lses, nll) = _ce_fwd_scan(x, w, b, labels, valid, vd_layout, chunk, has_bias, None, per_token=True)
    return nll.transpose(1, 0, 2).reshape(labels.shape), (x, w, b, labels, valid, lses)


_fused_ce_tokens.defvjp(_ce_tokens_fwd, lambda vd_layout, chunk, has_bias, res, g: _ce_vjp_bwd(vd_layout, chunk, has_bias, None, res, g))


def fused_cross_entropy_tokens(x: jnp.ndarray,
                               w: jnp.ndarray,
                               labels: jnp.ndarray,
                               ignore_index: int = -100,
                               vd_layout: bool = False,
                               chunk: Optional[int] = None,
                               bias: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """(B, S) float32: every token's cross-entropy of ``x @ w (+ bias)`` against ``labels`` (0 where the label is
    ``ignore_index``), without the logits in HBM: for a caller whose objective weighs a token by something it
    differentiates. Its sum is ``fused_cross_entropy_sums``'s; the cotangent a token is that token's weight in the
    backward, which is the sums' own."""
    chunk, valid, safe_labels, has_bias, b = _operands(x, w, labels, ignore_index, vd_layout, chunk, bias)
    return _fused_ce_tokens(x, w, b, safe_labels, valid, bool(vd_layout), chunk, has_bias)


def _operands(x, w, labels, ignore_index, vd_layout, chunk, bias):
    """(chunk, which targets count, the labels with the ignored ones at 0, whether there is a bias, the bias or zeros)."""
    B, S, D = x.shape
    V = w.shape[0] if vd_layout else w.shape[1]
    chunk = chunk or _pick_chunk(S, B=B, V=V)
    valid = labels != ignore_index
    safe_labels = jnp.where(valid, labels, 0).astype(jnp.int32)
    has_bias = bias is not None
    b = bias.astype(jnp.float32) if has_bias else jnp.zeros((V,), jnp.float32)
    return int(chunk), valid, safe_labels, has_bias, b


def fused_cross_entropy_sums(x: jnp.ndarray,
                             w: jnp.ndarray,
                             labels: jnp.ndarray,
                             ignore_index: int = -100,
                             vd_layout: bool = False,
                             chunk: Optional[int] = None,
                             bias: Optional[jnp.ndarray] = None,
                             vocab_axis: Optional[str] = None,
                             weights: Optional[jnp.ndarray] = None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(summed token CE, count of positions not ignored) of
    ``fused_cross_entropy``, for a caller that divides after summing.

    ``weights``: (B, S) float32, a weight a target: the sum is ``sum w_i
    CE_i`` (``ignore_index`` is the weight-0 case of it) and the count the
    targets of non-zero weight; the caller divides by what its loss says.

    ``vocab_axis``: the call is every device's own program under
    ``shard_map`` over that mesh axis, ``w`` and ``bias`` are this device's
    consecutive slice of the vocabulary, ``x`` and ``labels`` the same on
    every device: the softmax's sum and the gold logit are summed over the
    axis, every device returns the whole sums, ``dw`` is this slice's, and
    ``dx`` is this slice's part of a sum that the caller takes over the
    axis."""
    chunk, valid, safe_labels, has_bias, b = _operands(x, w, labels, ignore_index, vd_layout, chunk, bias)
    if weights is not None:
        valid = jax.lax.stop_gradient(jnp.where(valid, weights.astype(jnp.float32), 0.0))
    total = _fused_ce_sum(x, w, b, safe_labels, valid, bool(vd_layout), chunk, has_bias, vocab_axis)
    return total, jnp.sum(valid) if weights is None else jnp.sum(valid != 0)


def fused_cross_entropy(x: jnp.ndarray,
                        w: jnp.ndarray,
                        labels: jnp.ndarray,
                        ignore_index: int = -100,
                        vd_layout: bool = False,
                        chunk: Optional[int] = None,
                        bias: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Mean token CE of ``x @ w (+ bias)`` against ``labels`` without
    materializing full logits.

    x: (B, S, D) final hidden states (compute dtype).
    w: (D, V) projection kernel, or (V, D) with ``vd_layout=True`` (tied
       input embedding).
    labels: (B, S) int; positions equal to ``ignore_index`` are masked out.
    bias: optional (V,) head bias (phi/gpt-j untied heads).
    Matches ``models.transformer.cross_entropy_loss`` numerics (fp32
    logits, mean over valid positions).
    """
    total, count = fused_cross_entropy_sums(x, w, labels, ignore_index, vd_layout, chunk, bias)
    return total / jnp.maximum(count, 1)
