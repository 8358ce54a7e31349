"""Attention over keys the model chooses: the parts of ``models/mixers.py::
SparseMixer`` that are square in the sequence, each in one form a backend.

On a TPU, where the sequence is whole 128-lane tiles, every part is a Pallas
call of ``ops/pallas/indexed_attention.py``; elsewhere (the CPU tests, a
sequence the kernels do not take) it is the plain XLA form in this file, which
holds the whole square and is the kernels' oracle. Which one a call site took
is counted where it is chosen (``program_regions_traced_total{region=
"mixer/kernel", op="sparse", pass, path}``, ``{region="mixer/select", path}``;
the kernels' choice also says, as ``counted``, the share of a band's rows its
count passes walk, and the two kernels with a loop over heads, as
``index_strip``, the strip of a tile their sums over heads are made in).

Square arrays are key-major, ``(B, Sk, Sq)``, as the kernels keep them
(``scores_t``, ``mask_t``, the index loss's gradient in the scores; XLA's
form has the loss's target ``probs_t`` too, which the kernels never write):
nothing outside this file and the mixer reads them.
"""

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..telemetry.tracing import region
from . import placement
from .pallas import indexed_attention as kernel

NEG_INF = kernel.NEG_INF
# The name the choice, the attention call's output and row statistics and the index loss's cotangent (the loss's
# gradient in the scores, an output of the ``index_loss`` call) carry for a checkpoint policy (``models/transformer.py::
# remat_keeps``): a checkpointed block that keeps them runs neither the indexer's scores, nor the choice, nor a
# forward kernel, nor the index loss's call a second time
SAVED = "sparse_attention"
# for a kind's record (``LayerKind.joined``): the strip in which the kernels with a loop over heads (``index_scores``,
# ``index_loss``) walk a tile (``ops/pallas/indexed_attention.py::strip_for``), as their call sites counted it
INDEX_STRIP = {"index_strip": ("mixer/kernel", None, "index_strip")}


def _strip(path: str, seq: int) -> dict:
    """The label ``index_strip="<rows>x<lanes>"`` of a site that took a kernel with a loop over heads at this sequence
    (static: the rule's word for the call's block); nothing on XLA's path."""
    return {"index_strip": "{}x{}".format(*kernel.strip_for(kernel.block_for(seq)))} if path == "kernel" else {}


def path_for(seq: int, topk: int) -> str:
    """The rule's word (``placement.kernel_path``) for the four parts of a layer at this sequence: the kernels sit in no
    ``shard_map`` yet (ROADMAP.md, Reach)."""
    return placement.kernel_path(kernel.kernels_take(seq, topk), has_specs=False)


# ----------------------------------------------------------------------
# the indexer's scores: I^T[s, t] = sum_j w[t, j] relu(kI[s] . qI[t, j])
# ----------------------------------------------------------------------
def index_scores_xla(q_i, k_i, w):
    """q_i (B, J, S, Di), k_i (B, S, Di), w (B, J, S) float32 -> (B, Sk, Sq) float32, ``NEG_INF`` where a key is ahead
    of its query."""
    s = jnp.einsum("bsd,bjtd->bjst", k_i, q_i, preferred_element_type=jnp.float32)
    scores = jnp.sum(jax.nn.relu(s) * w[:, :, None, :], axis=1)
    S = scores.shape[-1]
    return jnp.where(jnp.arange(S)[:, None] <= jnp.arange(S)[None, :], scores, NEG_INF)


def index_scores(q_i, k_i, w, *, path: str):
    """The scores the choice and ``index_loss`` read. The kernel's take no gradient: ``index_loss`` carries the
    indexer's gradient itself, from the one array its backward keeps."""
    placement.count("sparse", path, "index", **_strip(path, q_i.shape[2]))
    if path != "kernel":
        return index_scores_xla(q_i, k_i, w)
    return kernel.index_scores(*(jax.lax.stop_gradient(x) for x in (q_i, k_i, w)), interpret=placement.interpret())


# ----------------------------------------------------------------------
# the choice
# ----------------------------------------------------------------------
def select_xla(scores_t, topk: int):
    """A query's ``min(topk, t + 1)`` largest visible scores as an int8 mask (B, Sk, Sq); ties to the lower index
    (``lax.top_k`` puts the lower index first)."""
    B, S, _ = scores_t.shape
    scores = jnp.swapaxes(scores_t, 1, 2)  # a query's scores a row
    scores = jnp.where(scores == 0.0, 0.0, scores)  # -0.0 is 0.0
    _, idx = jax.lax.top_k(scores, min(int(topk), S))
    mask = jnp.zeros((B, S, S), jnp.int8).at[jnp.arange(B)[:, None, None], jnp.arange(S)[None, :, None], idx].set(1)
    visible = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]  # a query short of topk keys took keys ahead of it
    return jnp.swapaxes(mask * visible.astype(jnp.int8), 1, 2)


def select_keys(scores_t, topk: int, *, path: str):
    """The choice: discrete, no gradient. Named (``SAVED``). The kernel's count passes walk the rows at or below a
    band's diagonal, in the bands that search: which share of all rows that is follows the shapes and is the label
    ``counted``, to three digits."""
    walked = {"counted": f"{kernel.share_walked(scores_t.shape[1], topk):.3f}"} if path == "kernel" else {}
    with region("mixer/select", path=path, **walked):
        scores_t = jax.lax.stop_gradient(scores_t)
        mask_t = select_xla(scores_t, topk) if path != "kernel" else kernel.index_select(scores_t, topk, interpret=placement.interpret())
        return checkpoint_name(mask_t, SAVED)


# ----------------------------------------------------------------------
# attention over the chosen keys
# ----------------------------------------------------------------------
def _expand(k, H):
    return jnp.repeat(k, H // k.shape[2], axis=2)


def _masked_scores(q, k, mask_t, scale):
    """(B, H, Sq, Sk) float32 scores of (B, S, H, D) operands, ``NEG_INF`` off the chosen pairs."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, _expand(k, q.shape[2]), preferred_element_type=jnp.float32) * scale
    return jnp.where(jnp.swapaxes(mask_t, 1, 2)[:, None] != 0, s, NEG_INF)


def sparse_attention_xla(q, k, v, mask_t, scale: float):
    """(B, S, H, D) softmax attention over the pairs ``mask_t`` (B, Sk, Sq) keeps -> o, and lse (B, H, S)."""
    s = _masked_scores(q, k, mask_t, scale)
    lse = jax.nn.logsumexp(s, axis=-1)
    p = jnp.exp(s - lse[..., None]).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, _expand(v, q.shape[2])).astype(q.dtype), lse


def head_probs_xla(q, k, lse, mask_t, scale: float):
    """The chosen pairs' probabilities summed over the heads, key-major (B, Sk, Sq) float32."""
    return jnp.swapaxes(jnp.sum(jnp.exp(_masked_scores(q, k, mask_t, scale) - lse[..., None]), axis=1), 1, 2)


def _to_bh(x):
    B, S, H, D = x.shape
    return jnp.transpose(x, (0, 2, 1, 3)).reshape(B * H, S, D)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _sparse_kernel(q, k, v, mask_t, scale, interpret):
    return _sparse_fwd(q, k, v, mask_t, scale, interpret)[0]


def _sparse_fwd(q, k, v, mask_t, scale, interpret):
    B, S, H, D = q.shape
    tiles = kernel.tiled(mask_t, kernel.block_for(S))
    o, lse = kernel.sparse_fwd(_to_bh(q), _to_bh(k), _to_bh(v), tiles, scale, H, k.shape[2], interpret=interpret)
    # named, as the flash call's are: a checkpointed block that keeps them runs no second forward kernel
    o = checkpoint_name(o.reshape(B, H, S, D).transpose(0, 2, 1, 3), SAVED)
    lse = checkpoint_name(lse.reshape(B, H, S), SAVED)
    return (o, lse), (q, k, v, mask_t, o, lse)


def _sparse_bwd(scale, interpret, res, cotangents):
    q, k, v, mask_t, o, lse = res
    do, _ = cotangents  # the row statistics feed the index loss's target alone, which takes no gradient
    B, S, H, D = q.shape
    KVH = k.shape[2]
    placement.count("sparse", "kernel", "bwd")
    tiles = kernel.tiled(mask_t, kernel.block_for(S))
    dq, dk, dv = kernel.sparse_bwd(_to_bh(q), _to_bh(k), _to_bh(v), _to_bh(o), lse.reshape(B * H, S), _to_bh(do), tiles,
                                   scale, H, KVH, interpret=interpret)
    back = lambda x, heads: x.reshape(B, heads, S, D).transpose(0, 2, 1, 3)
    return back(dq, H), back(dk, KVH), back(dv, KVH), None


_sparse_kernel.defvjp(_sparse_fwd, _sparse_bwd)


def sparse_attention(q, k, v, mask_t, *, scale: float, path: str):
    """Softmax attention of (B, S, H, D) queries over the keys ``mask_t`` gives each -> (o, lse (B, H, S))."""
    placement.count("sparse", path)
    if path != "kernel":
        return sparse_attention_xla(q, k, v, mask_t, scale)
    return _sparse_kernel(q, k, v, mask_t, scale, placement.interpret())


# ----------------------------------------------------------------------
# the indexer's loss
# ----------------------------------------------------------------------
def _index_loss_and_grad(scores_t, probs_t, mask_t):
    """mean_t KL(p[t, S_t] || softmax_{S_t} I[t, .]) and its gradient in ``scores_t``, (softmax - p) / queries."""
    chosen = mask_t != 0
    logits = jnp.where(chosen, scores_t, NEG_INF)
    log_q = logits - jax.nn.logsumexp(logits, axis=1, keepdims=True)
    p = probs_t / jnp.sum(probs_t, axis=1, keepdims=True)  # zero off the chosen pairs already
    kl = jnp.sum(jnp.where(p > 0, p * (jnp.log(jnp.where(p > 0, p, 1.0)) - log_q), 0.0), axis=1)
    return jnp.mean(kl), (jnp.where(chosen, jnp.exp(log_q), 0.0) - p) / kl.size


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10))
def _index_loss_kernel(q_i, k_i, w, scores_t, q, k, lse, mask_t, scale, dtype, interpret):
    return _index_loss_fwd(q_i, k_i, w, scores_t, q, k, lse, mask_t, scale, dtype, interpret)[0]


def _index_loss_fwd(q_i, k_i, w, scores_t, q, k, lse, mask_t, scale, dtype, interpret):
    S, H = q.shape[1:3]
    tiles = kernel.tiled(mask_t, kernel.block_for(S))
    kl, grad = kernel.index_loss(_to_bh(q), _to_bh(k), lse.reshape(-1, S), tiles, scores_t, scale, H, k.shape[2], dtype, interpret=interpret)
    return jnp.mean(kl), (q_i, k_i, w, checkpoint_name(grad, SAVED))


def _index_loss_bwd(scale, dtype, interpret, res, g):
    q_i, k_i, w, grad = res
    placement.count("sparse", "kernel", "index_bwd")
    dq, dk, dw = kernel.index_scores_bwd(grad, q_i, k_i, w, interpret=interpret)
    return (g * dq).astype(q_i.dtype), (g * dk).astype(k_i.dtype), g * dw, None, None, None, None, None


_index_loss_kernel.defvjp(_index_loss_fwd, _index_loss_bwd)


def index_loss(q_i, k_i, w, scores_t, q, k, lse, mask_t, *, scale: float, dtype, path: str):
    """The indexer's own loss, ``mean_t KL(p[t, S_t] || softmax_{S_t} I[t, .])``: ``p`` is the heads' probabilities
    over the chosen pairs, summed and renormalised, from the main heads' (B, S, H, D) ``q`` and ``k`` and the forward's
    ``lse`` (the target: no gradient reaches them), ``I`` the key-major scores. In XLA's form the target is a square
    array and the gradient reaches the indexer through ``scores_t``. The kernels' form is ONE call that makes the
    target a tile at a time and finishes the loss there: it keeps ONE square array for its backward, the loss's
    gradient in the scores in ``dtype`` (the model's: a cotangent like any other) under the name ``SAVED``, and
    ``index_scores_bwd`` carries it to ``q_i``, ``k_i`` and ``w``."""
    placement.count("sparse", path, "loss", **_strip(path, q.shape[1]))
    q, k, lse = (jax.lax.stop_gradient(x) for x in (q, k, lse))
    if path != "kernel":
        return _index_loss_and_grad(scores_t, head_probs_xla(q, k, lse, mask_t, scale), mask_t)[0]
    return _index_loss_kernel(q_i, k_i, w, jax.lax.stop_gradient(scores_t), q, k, lse, mask_t, scale, dtype, placement.interpret())
