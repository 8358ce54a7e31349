"""Top-k gating + expert dispatch.

Parity: reference ``deepspeed/moe/sharded_moe.py`` (``TopKGate`` :372 with
capacity/jitter, ``MOELayer`` :455: gate → dispatch einsum → all-to-all →
experts → all-to-all → combine). The TPU-native formulation is the GShard
einsum dispatch: one-hot dispatch/combine tensors contracted with the
token batch, with the expert dimension sharded over the ``expert`` mesh
axis so XLA lowers the dispatch/return into all-to-alls over ICI — no
explicit ``all_to_all_single`` calls needed under GSPMD (the shard_map
path in ``layer.py`` shows the explicit-collective equivalent).
"""

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..ops import placement
from ..telemetry.tracing import region

uniform_map = {}
# The name a routed layer's values carry for a checkpoint policy: the router's scores' product and its choice
# (``sigmoid_topk`` / ``softmax_topk``), the order, the sorted rows and the grouped products (``held_experts``), the shared
# expert's gate and up products (``moe/layer.py``). A checkpointed hybrid block keeps them
# (``models/transformer.py::remat_keeps``), so its backward runs no product over the model width, no top-k, no sort and
# no grouped product a second time; the activations between them are elementwise and are made again
SAVED = "routed_ffn"


def multiplicative_jitter(x: jnp.ndarray, rng, epsilon: float = 1e-2) -> jnp.ndarray:
    """Reference ``sharded_moe.py`` jitter: multiply by U(1-eps, 1+eps)."""
    if epsilon == 0 or rng is None:
        return x
    noise = jax.random.uniform(rng, x.shape, x.dtype, 1.0 - epsilon, 1.0 + epsilon)
    return x * noise


def _capacity(num_tokens: int, num_experts: int, capacity_factor: float, min_capacity: int, k: int) -> int:
    cap = int(math.ceil(k * num_tokens / num_experts * capacity_factor))
    return max(cap, min_capacity)


def _one_hot(idx, n, dtype=jnp.float32):
    return jax.nn.one_hot(idx, n, dtype=dtype)


def top1gating(logits: jnp.ndarray, capacity_factor: float, min_capacity: int, rng=None,
               noisy_gate_policy: Optional[str] = None, drop_tokens: bool = True,
               used_token_mask: Optional[jnp.ndarray] = None):
    """Top-1 (Switch) gating. logits: (N, E). Returns (l_aux, combine (N,E,C), dispatch (N,E,C), exp_counts)."""
    N, E = logits.shape
    # drop_tokens=False must hold the worst case (all tokens to one expert):
    # C < N would silently zero overflow rows via the out-of-range one_hot
    C = _capacity(N, E, capacity_factor, min_capacity, k=1) if drop_tokens else N
    if noisy_gate_policy == "RSample" and rng is not None:
        logits_w_noise = logits + jax.random.normal(rng, logits.shape, logits.dtype)
    else:
        logits_w_noise = logits
    gates = jax.nn.softmax(logits, axis=-1)
    expert_idx = jnp.argmax(logits_w_noise, axis=-1)  # (N,)
    mask1 = _one_hot(expert_idx, E)  # (N, E)
    if used_token_mask is not None:
        mask1 = mask1 * used_token_mask[:, None]

    # load-balancing loss (Switch): E * sum_e mean_prob_e * frac_tokens_e
    me = jnp.mean(gates, axis=0)
    ce = jnp.mean(mask1, axis=0)
    l_aux = jnp.sum(me * ce) * E

    # position of each token within its expert's capacity
    positions = jnp.cumsum(mask1, axis=0) - mask1  # (N, E), rank among tokens routed to e
    pos_in_expert = jnp.sum(positions * mask1, axis=-1)  # (N,)
    if drop_tokens:
        keep = pos_in_expert < C
        mask1 = mask1 * keep[:, None]
    exp_counts = jnp.sum(mask1, axis=0)

    gate_val = jnp.sum(gates * mask1, axis=-1)  # (N,)
    pos_oh = _one_hot(pos_in_expert.astype(jnp.int32), C)  # (N, C)
    dispatch = (mask1[:, :, None] * pos_oh[:, None, :])  # (N, E, C)
    combine = dispatch * gate_val[:, None, None]
    return l_aux, combine, dispatch.astype(bool), exp_counts


def topkgating(logits: jnp.ndarray, k: int, capacity_factor: float, min_capacity: int, rng=None,
               drop_tokens: bool = True, normalize_weights: bool = True):
    """General top-k gating (k=2 reproduces GShard top-2). logits: (N, E)."""
    N, E = logits.shape
    # see top1gating: no-drop mode needs room for every token per expert,
    # or the clip at C-1 sums overflow tokens into one corrupted slot
    C = _capacity(N, E, capacity_factor, min_capacity, k) if drop_tokens else N
    gates = jax.nn.softmax(logits, axis=-1)

    topk_vals, topk_idx = jax.lax.top_k(gates, k)  # (N, k)
    if normalize_weights:
        topk_vals = topk_vals / jnp.maximum(jnp.sum(topk_vals, axis=-1, keepdims=True), 1e-9)

    # aux loss over the top-1 assignment (reference uses mask of first choice)
    mask1 = _one_hot(topk_idx[:, 0], E)
    me = jnp.mean(gates, axis=0)
    ce = jnp.mean(mask1, axis=0)
    l_aux = jnp.sum(me * ce) * E

    combine = jnp.zeros((N, E, C), gates.dtype)
    dispatch = jnp.zeros((N, E, C), bool)
    # fill choices in priority order so earlier choices win capacity slots
    occupancy = jnp.zeros((E,), jnp.int32)
    for j in range(k):
        idx_j = topk_idx[:, j]  # (N,)
        mask_j = _one_hot(idx_j, E)  # (N, E)
        pos_j = occupancy[None, :] + jnp.cumsum(mask_j, axis=0) - mask_j  # (N, E)
        pos_in_expert = jnp.sum(pos_j * mask_j, axis=-1)
        keep = (pos_in_expert < C) if drop_tokens else jnp.ones((N,), bool)
        mask_j = mask_j * keep[:, None]
        pos_oh = _one_hot(jnp.clip(pos_in_expert, 0, C - 1).astype(jnp.int32), C)
        disp_j = mask_j[:, :, None] * pos_oh[:, None, :]
        dispatch = dispatch | disp_j.astype(bool)
        combine = combine + disp_j * topk_vals[:, j][:, None, None]
        occupancy = occupancy + jnp.sum(mask_j, axis=0).astype(jnp.int32)
    exp_counts = occupancy
    return l_aux, combine, dispatch, exp_counts


def gate_and_dispatch(x: jnp.ndarray, gate_logits: jnp.ndarray, k: int, capacity_factor: float,
                      min_capacity: int, rng=None, noisy_gate_policy=None, drop_tokens=True):
    """x: (N, d), gate_logits: (N, E) -> (l_aux, dispatched (E, C, d), combine (N, E, C), exp_counts)."""
    if k == 1:
        l_aux, combine, dispatch, exp_counts = top1gating(gate_logits, capacity_factor, min_capacity, rng,
                                                          noisy_gate_policy, drop_tokens)
    else:
        l_aux, combine, dispatch, exp_counts = topkgating(gate_logits, k, capacity_factor, min_capacity, rng,
                                                          drop_tokens)
    dispatched = jnp.einsum("nec,nd->ecd", dispatch.astype(x.dtype), x)
    return l_aux, dispatched, combine, exp_counts


def combine_output(expert_out: jnp.ndarray, combine: jnp.ndarray) -> jnp.ndarray:
    """expert_out: (E, C, d), combine: (N, E, C) -> (N, d)."""
    return jnp.einsum("nec,ecd->nd", combine.astype(expert_out.dtype), expert_out)


# ----------------------------------------------------------------------
# routing without a capacity: scores over ALL experts, the part of the ones held here
# ----------------------------------------------------------------------
def _renormalised(chosen, scale: float, eps: float = 1e-20):
    """A token's chosen scores (N, k) rescaled to sum to one (``eps`` added to the sum), times ``scale``."""
    return chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + eps) * scale


def _chosen(scores, ranked, k: int):
    """The columns of the ``k`` largest of ``ranked`` in each row, and ``scores`` there: (N, E) -> ((N, k) int32, (N, k)).
    The scores are taken by comparison, not by a gather: each choice's column against an iota over the expert axis, the
    row's scores where they meet and zero elsewhere, summed over that axis: one term and zeros, so the value is the
    gathered one to the last bit, and what JAX derives for the backward is a select by the same mask summed over ``k``
    (a token's ``k`` experts are distinct, so again one term an entry) where a gather's transpose is a scatter-add,
    which the chip runs a scalar at a time. Both results are named, so a checkpointed block keeps them and its backward
    runs neither the top-k nor the sum again; the mask's only residual is ``idx``.
    The values ``lax.top_k`` itself returns are left unused: its own rule reads the indices of the call it
    differentiates, which no name reaches, and a backward through them would make the top-k a second time."""
    _, idx = jax.lax.top_k(ranked, k)
    idx = checkpoint_name(idx, SAVED)
    met = idx[..., None] == jnp.arange(scores.shape[-1], dtype=idx.dtype)  # (N, k, E), fused into the sum
    return idx, checkpoint_name(jnp.sum(jnp.where(met, scores[..., None, :], 0), axis=-1), SAVED)


def sigmoid_topk(scores_logits: jnp.ndarray, select_bias: jnp.ndarray, k: int, scale: float, eps: float = 1e-20):
    """Sigmoid scores, the top ``k`` of ``score + select_bias`` (the bias only
    chooses: it takes no gradient and does not enter the weight), the chosen
    scores rescaled to sum to one (their sum plus ``eps``: the families
    differ, 1e-20 and 1e-6) and multiplied by ``scale``.
    logits (N, E) float32 -> (indices (N, k) int32, weights (N, k) float32)."""
    scores = jax.nn.sigmoid(scores_logits.astype(jnp.float32))
    idx, chosen = _chosen(scores, scores + jax.lax.stop_gradient(select_bias.astype(jnp.float32)), k)
    return idx, _renormalised(chosen, scale, eps)


def softmax_topk(logits: jnp.ndarray, k: int, scale: float):
    """Softmax over ALL experts in float32, its ``k`` largest, rescaled to sum
    to one and multiplied by ``scale``. logits (N, E) -> (indices (N, k)
    int32, weights (N, k) float32)."""
    scores = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    idx, chosen = _chosen(scores, scores, k)
    return idx, _renormalised(chosen, scale)


def _rows_a_group(key, n: int):
    """How many of the pairs ``key`` (P,), each the held expert of a pair or ``n`` for one not held here, each of the
    ``n`` groups has: (n,) int32, ``bincount(key)[:n]``. By comparison, as ``_chosen`` takes its scores and
    ``moe_sum_rows.spans`` counts a tile's rows: each key against an iota over the groups, summed over the pairs (a pair
    not held meets no column); ``bincount`` is a scatter-add of one scalar a pair, which the chip adds one by one."""
    return jnp.sum(key[:, None] == jnp.arange(n, dtype=key.dtype), axis=0, dtype=jnp.int32)


def _grouped(xs, w, group_sizes, kernel: bool, row_tile: int = 256, **choice):
    """Rows sorted by group times that group's matrix: (M, a) x (G, a, b) ->
    (M, b); rows past the groups' total are not defined. ``row_tile``: the
    rows of a kernel's tile (``routed_part`` says when 512). ``choice``: what
    else the caller chose for these products, counted with their path."""
    def tile(n, want, rows=False):
        """The widest listed tile that divides ``n``; a width that only 128 divides (1,408 = 11 x 128) is its own tile
        while it is small enough to stay in VMEM: eleven times fewer grid steps of eleven times the work. 896 = 7 x 128
        is listed for 1,792 = 2 x 896, whose next divisor down is 256: a product of 2,048 onto 1,792 over 8 groups of
        2,048 rows, forward and backward, 5.61 ms at (256, 512, 256) and 3.90 at (256, 512, 896); 1,792 onto 2,048 3.95
        at (256, 256, 1024) and 3.35 at (256, 896, 1024) (TPU v5e; ``PERF.md``, PR 55). It divides no other cell's width.
        A weight's width over 1,024 that NO listed tile divides (1,856 = 29 x 64) goes in tiles of 1,024, the last part
        empty: the kernel masks a contraction's remainder and drops a result's. Two products of 2,688 x 1,856 over 8 groups
        of some 384 rows, forward and backward: 3.29 ms so, 3.34 in tiles of 384 (five, less padding), 3.36 of 512, 5.04 of
        128, and 12.46 as XLA's ragged product, which is what such a width fell to (TPU v5e; ``PERF.md``, PR 59). The rows
        have no such tile: the kernel wants them whole."""
        t = max((t for t in (1024, 896, 768, 512, 384, 256, 128) if t <= want and n % t == 0), default=0)
        if t == 0 and n > 1024 and not rows:
            return 1024
        return n if t == 128 and n <= 1536 else t

    tiling = (tile(xs.shape[0], row_tile, rows=True), tile(w.shape[1], 896), tile(w.shape[2], 1024))
    kernel = kernel and all(tiling)  # off the TPU, or a width no tile of the kernel divides: XLA's ragged product
    with region("ffn/experts", path="kernel" if kernel else "xla", **choice):  # the choice, counted where it is made
        if not kernel:
            return jax.lax.ragged_dot(xs, w, group_sizes)
        from jax.experimental.pallas.ops.tpu.megablox import gmm

        return gmm(xs, w, group_sizes, preferred_element_type=xs.dtype, tiling=tiling, interpret=placement.interpret())


def _sum_rows(rows, tok_of_row, w_row, spans, n_tokens: int):
    """Each token's weighted sum of its own rows in the sorted buffer, by the
    kernel that walks token tiles (``ops/pallas/moe_sum_rows.py``)."""
    from ..ops.pallas.moe_sum_rows import sum_rows

    # the forward's calls see no abstract mesh and the backward's an empty one: named here, both are one tracing
    # context, and ``jax.jit`` traces and lowers the kernel once a shape, not once a context (0.5 s each on the chip's host)
    with jax.sharding.use_abstract_mesh(jax.sharding.get_abstract_mesh()):
        return sum_rows(rows, tok_of_row, w_row, spans, n_tokens, interpret=placement.interpret())


@jax.custom_vjp
def _rows_of(tokens, tok_of_row, row_ok, pos, take, spans):
    """The sorted rows' tokens: ``tokens[tok_of_row]``, zero past the routed
    rows. Its backward is no scatter-add (XLA's transpose of a gather, which
    the chip runs row by row): each token sums the rows of its own pairs that
    were taken. With ``spans`` (``held_experts``' packed buffer, or the slabs
    of ``exchanged_experts``, a group every ``slab`` rows:
    ``moe_sum_rows.spans``) that sum is read off the sorted buffer a token
    tile at a time (``_sum_rows``); without, every pair's row is gathered by
    ``pos`` into (N, k, d), masked by ``take`` and summed over k."""
    return jnp.where(row_ok, tokens[tok_of_row], 0)


def _rows_of_fwd(tokens, tok_of_row, row_ok, pos, take, spans):
    return _rows_of(tokens, tok_of_row, row_ok, pos, take, spans), (tok_of_row, pos, take, spans)


def _rows_of_bwd(res, dxs):
    tok_of_row, pos, take, spans = res
    if spans is not None:
        return (_sum_rows(dxs, tok_of_row, jnp.ones(tok_of_row.shape, jnp.float32), spans, pos.shape[0]), None, None, None, None, None)
    picked = dxs[jnp.minimum(pos, dxs.shape[0] - 1)]  # (N, k, d)
    return (jnp.sum(jnp.where(take[..., None], picked, 0), axis=1), None, None, None, None, None)


_rows_of.defvjp(_rows_of_fwd, _rows_of_bwd)


@jax.custom_vjp
def _back_to_tokens(ys, weights, tok_of_row, pair_of_row, row_ok, pos, take, spans):
    """Each token's weighted rows: ``sum_j weights[n, j] ys[pos[n, j]]`` over
    the pairs that were taken. With ``spans`` (either layout, as ``_rows_of``)
    the same sum is read off the sorted buffer a token tile at a time, each
    row times its own pair's weight (in the rows' type, as the backward below
    applies it: the product is exact in float32) and summed in float32
    (``_sum_rows``); without, every pair's row is gathered into (N, k, d),
    weighted and summed in the rows' type. Backward, by gathers: a row's
    gradient is its own pair's weight times its token's, a weight's the
    product of its row with its token's gradient."""
    if spans is not None:
        return _sum_rows(ys, tok_of_row, weights.reshape(-1)[pair_of_row], spans, pos.shape[0])
    picked = ys[jnp.minimum(pos, ys.shape[0] - 1)]  # (N, k, d)
    return jnp.sum(picked * jnp.where(take, weights, 0.0)[..., None].astype(picked.dtype), axis=1)


def _back_to_tokens_fwd(ys, weights, tok_of_row, pair_of_row, row_ok, pos, take, spans):
    return _back_to_tokens(ys, weights, tok_of_row, pair_of_row, row_ok, pos, take, spans), (ys, weights, tok_of_row, pair_of_row, row_ok, pos, take)


def _back_to_tokens_bwd(res, dout):
    ys, weights, tok_of_row, pair_of_row, row_ok, pos, take = res
    d_rows = dout[tok_of_row]  # (rows, d): the gradient of the token each row belongs to
    w_rows = weights.reshape(-1)[pair_of_row][:, None].astype(ys.dtype)
    d_ys = jnp.where(row_ok, d_rows * w_rows, 0).astype(ys.dtype)
    dw_rows = jnp.sum(d_rows.astype(jnp.float32) * ys.astype(jnp.float32), axis=-1)  # (rows,)
    d_weights = jnp.where(take, dw_rows[jnp.minimum(pos, ys.shape[0] - 1)], 0.0).astype(weights.dtype)
    return (d_ys, d_weights, None, None, None, None, None, None)


_back_to_tokens.defvjp(_back_to_tokens_fwd, _back_to_tokens_bwd)


GATES = {"silu": jax.nn.silu, "relu": jax.nn.relu}  # a gated expert's gate, by ``act``: SwiGLU's, ReGLU's
UNGATED = {"relu2": lambda x: jnp.square(jax.nn.relu(x))}  # an expert of TWO matrices, ``wo act(x wi)``: its activation, by ``act``


def held_experts(tokens, idx, weights, wg, wi, wo, first, rows: int, kernel: bool, named: bool = True, act: str = "silu", row_tile: int = 256):
    """The part of a routed FFN that the experts ``first .. first + n`` add
    (``wg, wi`` (n, d, f), ``wo`` (n, f, d): ``wo (act(x wg) * x wi)``, ``act``
    the configuration's: ``silu``, or ``relu`` for ReGLU experts; ``wg`` None:
    an expert of two matrices, ``wo act(x wi)``, ``act`` one of ``UNGATED``,
    through the same sort, rows and sums with two grouped products where a
    gated expert has three; the one line
    below where it is applied is differentiated by autodiff), for
    tokens (N, d) routed by ``idx`` / ``weights`` (N, k) over ALL experts, a
    token's ``k`` experts distinct (``lax.top_k``'s).

    The (token, choice) pairs are sorted by expert, the ones for experts held
    here first; the first ``rows`` of that order are gathered and go through
    three grouped products whose cost follows the groups' sizes, and each
    token sums its weighted rows. The sort is stable and a pair's index is
    ``token * k + choice``, so inside an expert's group the rows' tokens
    ascend strictly: a tile of consecutive tokens has, an expert, ONE
    contiguous span of rows. On the kernel's path, where the shapes fit it
    (``ops/pallas/moe_sum_rows.py``), a token's sum, and in the backward the
    sum of its rows' gradients, is read off those spans a tile at a time, and
    only the rows routed here are read; elsewhere every pair's row is
    gathered by the inverse order into (N, k, d), masked and summed over k.
    ``row_tile``: the rows of the grouped kernels' tile (``_grouped``).
    ``rows`` bounds the buffer, not the routing, and every pass between the
    grouped products runs over all of it: the caller gives the smallest of a
    ladder that holds every pair routed here (``routed_part``). ``named``: whether the
    order, the rows and the products carry their names for a checkpoint
    policy; without them a block under ``jax.checkpoint`` keeps nothing of
    this call and makes it again in its backward. Returns ((N, d), pairs
    routed here, those of them the buffer did not hold and so were not
    computed, the largest and smallest group)."""
    from ..ops.pallas import moe_sum_rows

    N, k = idx.shape
    n = wo.shape[0]
    keep = lambda x: checkpoint_name(x, SAVED) if named and x is not None else x
    tiled = kernel and moe_sum_rows.fits(N, rows, tokens.shape[1], n, tokens.dtype)  # off the TPU, or a shape its tiles do not take: the gathers
    # the bookkeeping: the two sorts of every pair, the counts, the spans. ``compare_sum``: the expert axis is indexed by
    # comparison against an iota and a sum (here the counts, in ``_chosen`` the scores), never by a gather or a scatter
    with region("ffn/router", path="compare_sum"):
        local = idx - first
        here = (local >= 0) & (local < n)
        key = jnp.where(here, local, n).reshape(-1)
        order = jnp.argsort(key)  # stable: held experts first, by expert, tokens in order
        group_sizes = _rows_a_group(key, n)
        routed = jnp.sum(group_sizes)
        row_ok = (jnp.arange(rows) < routed)[:, None]
        pos = jnp.argsort(order).reshape(N, k)  # where each pair went
        take = here & (pos < rows)
        pair_of_row = order[:rows]
        tok_of_row = pair_of_row // k
        spans = moe_sum_rows.spans(key, n, k, rows) if tiled else None
        # named: a block under jax.checkpoint keeps the order and the rows (a few tens of MB a layer) and does not sort,
        # gather and multiply a second time in its backward (models/transformer.py::block_fn)
        pos, take, pair_of_row, tok_of_row, row_ok, group_sizes, spans = (keep(x) for x in (pos, take, pair_of_row, tok_of_row, row_ok, group_sizes, spans))
    with region("ffn/rows", path="kernel" if tiled else "xla"):  # how a token sums its rows: the choice, counted where it is made
        xs = keep(_rows_of(tokens, tok_of_row, row_ok, pos, take, spans))  # (rows, d)
    said = {} if act == "silu" else {"act": act}  # every older configuration's series are what they were
    with region("ffn/experts"):
        if wg is None:
            hidden = UNGATED[act](keep(_grouped(xs, wi, group_sizes, kernel, row_tile, **said))).astype(xs.dtype)
        else:
            gate, up = keep(_grouped(xs, wg, group_sizes, kernel, row_tile, **said)), keep(_grouped(xs, wi, group_sizes, kernel, row_tile, **said))
            hidden = (GATES[act](gate) * up).astype(xs.dtype)
        product = _grouped(hidden, wo, group_sizes, kernel, row_tile, **said)
    with region("ffn/rows"):
        ys = keep(jnp.where(row_ok, product, 0))
        out = _back_to_tokens(ys, weights, tok_of_row, pair_of_row, row_ok, pos, take, spans)
    with region("ffn/router"):
        return out, routed, routed - jnp.sum(take), jnp.max(group_sizes), jnp.min(group_sizes)


def _rows_to_each(idx, first, n: int, axis):
    """For the chips of ``axis``, chip ``c`` holding the experts ``first + c * n .. first + (c + 1) * n``: each pair's chip
    ((P,) int32, the axis' size for a pair whose expert none of them holds) and how many pairs go to each chip."""
    chips = jax.lax.axis_size(axis)
    local = (idx - first).reshape(-1)
    dest = jnp.where((local >= 0) & (local < chips * n), local // n, chips).astype(jnp.int32)
    return dest, _rows_a_group(dest, chips)


def exchanged_experts(tokens, idx, weights, wg, wi, wo, first, rows: int, kernel: bool, named: bool = True, act: str = "silu", row_tile: int = 256,
                      axis="fsdp", slab: int = None):
    """``held_experts`` where the experts are split over the chips of ``axis`` AND the tokens are (a deployment's layout:
    the expert-parallel group is a slice of the data-parallel one): chip ``c`` holds ``first + c * n .. first + (c + 1) * n``
    (``wg, wi, wo`` are its own ``n``) and has its own ``tokens`` (N, d), routed by ``idx`` / ``weights`` over ALL experts.

    A chip sorts its pairs by the chip that holds the expert (stable: a chip's rows keep their tokens' order) into a
    buffer of ``slab`` slots a chip, (chips, slab, d): group ``c`` begins at ``c * slab`` whatever the others hold, inside
    it the rows' tokens ascend (not strictly: a token may send one chip ``min(k, n)`` rows, side by side), and the slots
    past a chip's rows are empty. So a tile of consecutive tokens has, a destination chip, ONE contiguous span of rows, as
    it has an expert in ``held_experts``' packed buffer, and on the kernel's path, where the shapes fit it, a token's sum
    over its own rows of the slabs (the combine, and the backward of the rows' gather) is read off those spans a tile at
    a time by the same kernel (``ops/pallas/moe_sum_rows.py``: the groups are the chips; ``spans`` is told the slab);
    elsewhere every pair's slot is gathered into (N, k, d), 15 of 16 of them for absent experts where a host holds a
    sixteenth of them. ``lax.all_to_all`` hands slab ``c`` to chip ``c`` with each row's
    expert; ``held_experts`` runs there on what arrived, one pair a row with weight one, in a buffer of ``rows`` rows
    that the arrivals of all the chips are packed into (its sort puts the empty slots last); the results return by the
    reverse exchange into the slots they left from, and each token sums its own, by weight. The backward is the same two
    exchanges the other way (``all_to_all``'s transpose). Two sizes, because two loads spread differently
    (``exchange_rungs``): ``slab`` bounds what one chip sends ONE other, the popularity of a few experts among one chip's
    tokens, and is wide; ``rows`` bounds what a chip RECEIVES from all, which the senders' skews mostly average out in,
    and is what every pass between the grouped products costs. A slab travels whole, its empty slots too (tried: its second half
    under a ``lax.cond`` on whether any pair fills the first, a linear branch that keeps nothing; XLA:CPU's four virtual
    devices then gave a wrong input gradient or aborted, one run in two, so tier-1 could not hold it, and one form on the
    CPU and the chip is the design). Both sizes are the same on every chip of the axis: the caller takes a rung that
    holds the fullest pair's and the fullest chip's load (``routed_part``). ``slab`` None: ``rows`` over the chips, a
    buffer that holds every slot. Rows that stay on their chip pass through the same buffer and are no traffic.
    Returns (the tokens' sums, the rows that arrived here, those of a chip's pairs for held experts that the slab or the
    buffer here did not hold, the largest and smallest group here, the rows this chip sent to ANOTHER chip)."""
    from ..ops.pallas import moe_sum_rows

    N, k = idx.shape
    n, d = wo.shape[0], tokens.shape[1]
    chips = jax.lax.axis_size(axis)
    slab = rows // chips if slab is None else slab
    keep = lambda x: checkpoint_name(x, SAVED) if named and x is not None else x
    tiled = kernel and moe_sum_rows.fits(N, chips * slab, d, chips, tokens.dtype)  # as ``held_experts``: the groups are the chips, the buffer the slabs
    with region("ffn/router", path="compare_sum"):
        me = jax.lax.axis_index(axis)
        dest, counts = _rows_to_each(idx, first, n, axis)
        order = jnp.argsort(dest)  # stable: by chip, tokens in order
        starts = jnp.cumsum(counts) - counts
        at = dest[:, None] == jnp.arange(chips, dtype=dest.dtype)  # (P, chips): by comparison, as ``_rows_a_group`` counts
        place = jnp.argsort(order) - jnp.sum(jnp.where(at, starts, 0), axis=1)  # a pair's place among its chip's rows
        take = ((dest < chips) & (place < slab)).reshape(N, k)
        pos = (jnp.minimum(dest, chips - 1) * slab + place).reshape(N, k)  # the slot each pair went to
        slot = jnp.arange(slab, dtype=jnp.int32)
        row_ok = (slot[None, :] < counts[:, None]).reshape(-1, 1)
        pair_of_row = order[jnp.minimum(starts[:, None] + slot[None, :], N * k - 1).reshape(-1)]
        tok_of_row = pair_of_row // k
        # a row's expert among its chip's own, ``n`` for an empty slot: it travels with the row
        expert_of_row = jnp.where(row_ok[:, 0], (idx.reshape(-1)[pair_of_row] - first) % n, n).astype(jnp.int32)
        sent = jnp.sum(jnp.where(jnp.arange(chips) == me, 0, jnp.minimum(counts, slab)))
        spans = moe_sum_rows.spans(dest, chips, k, chips * slab, slab) if tiled else None
        pos, take, pair_of_row, tok_of_row, row_ok, spans = (keep(x) for x in (pos, take, pair_of_row, tok_of_row, row_ok, spans))
    with region("ffn/rows", path="kernel" if tiled else "xla"):  # how a token sums its rows of the slabs, counted as ``held_experts`` counts its own
        xs = _rows_of(tokens, tok_of_row, row_ok, pos, take, spans)  # (chips * slab, d)
    swap = lambda x: jax.lax.all_to_all(x.reshape(chips, slab, *x.shape[1:]), axis, 0, 0).reshape(x.shape)
    with region("ffn/exchange", path="dispatch", form="rows"):
        arrived, expert_arrived = swap(xs), swap(expert_of_row)
    mine = first + me * n
    ys, routed, unheld, largest, smallest = held_experts(arrived, (mine + expert_arrived)[:, None], jnp.ones((chips * slab, 1), weights.dtype), wg, wi, wo,
                                                        mine, rows, kernel, named, act, row_tile)
    with region("ffn/exchange", path="return", form="rows"):
        back = swap(ys)
    with region("ffn/rows"):
        out = _back_to_tokens(back, weights, tok_of_row, pair_of_row, row_ok, pos, take, spans)  # (an empty slot comes back zero: no pair of the other chip's took it)
    with region("ffn/router"):
        return out, routed, jnp.sum(counts) - jnp.sum(take) + unheld, largest, smallest, sent


def _above_first(tokens, idx, weights, wg, wi, wo, first, rungs, kernel, act, axis=None):
    """``routed_part``'s fallback, which sizes itself: ``held_experts`` at
    the smallest of ``rungs`` (buffer sizes, ascending, the last one every
    pair) that holds the pairs routed here, chosen on the device. As
    ``_every_pair`` it keeps NOTHING for its backward but its operands, which
    the conditional's caller holds anyway: if the branch is ever taken, its
    backward picks the same rung from the same count, makes that rung's call
    again and differentiates it there (``_unkept_back``: a ``jax.vjp`` inside
    each arm of the choice, so no conditional is differentiated and no rung
    writes zeros for another's residuals). A ``lax.cond`` under
    differentiation hands on the residuals of BOTH its branches, so whatever
    this one kept (its sorted rows and grouped products, each of a larger
    rung's row count), the first rung wrote zeros for, a layer and a step."""
    return _at_rung(idx, first, wo.shape[0], rungs,
                    lambda rows: _unkept(tokens, idx, weights, wg, wi, wo, first, rows=rows, kernel=kernel, act=act, axis=axis))


def _at_rung(idx, first, n: int, rungs, run):
    """``run(rows)`` at the smaller of the one or two ``rungs`` that holds the
    pairs ``idx`` routes to the experts ``first .. first + n`` (an exchange
    has ONE rung above its first, which holds any load: ``_in_chunks``)."""
    # one tracing context for the jitted arms, whoever calls (``_sum_rows`` says why)
    with region("branch/every_pair"), jax.sharding.use_abstract_mesh(jax.sharding.get_abstract_mesh()):
        if len(rungs) == 1:
            return run(rungs[0])
        return jax.lax.cond(_load(idx, first, n) <= rungs[0], lambda: run(rungs[0]), lambda: run(rungs[1]))


def _routed_here(idx, first, n: int):
    """How many of the pairs ``idx`` go to the experts ``first .. first + n``."""
    local = idx - first
    return jnp.sum((local >= 0) & (local < n))


def _load(idx, first, n: int, axis=None):
    """What a rung has to hold: the pairs routed here; with the rows exchanged over ``axis`` (``exchanged_experts``), the
    most that any chip of the axis sends any one chip and the most that any chip receives from all, each the same number
    on every chip, so that all take one rung."""
    if axis is None:
        return _routed_here(idx, first, n)
    counts = _rows_to_each(idx, first, n, axis)[1]
    return jax.lax.pmax(jnp.max(counts), axis), jnp.max(jax.lax.psum(counts, axis))


def _one_rung(axis):
    """A rung's call: ``held_experts``, or ``exchanged_experts`` over ``axis``."""
    return held_experts if axis is None else functools.partial(exchanged_experts, axis=axis)


# The fallback's arms are jitted for the trace's sake, not the program's (the compiler inlines them): a rung's
# forward is wanted by the fallback's value, by its rule's forward and by every kind of block that has a routed layer, its
# backward by each of those kinds, and a trace of ``held_experts`` with its kernels is 0.3-0.5 s on the chip's host. Traced
# and lowered once a shape and a rung for the whole model, whatever the rungs above the first cost a step that takes them
def _in_chunks(N: int, most: int, rows: int, chips: int):
    """An exchange's LAST rung, ``(rows, chunks)``: the chip's ``N`` tokens in the fewest equal chunks whose every pair fits
    a slab of ``rows // chips`` slots (a token sends one chip ``most`` rows at most), so that the first rung's buffer of
    ``rows`` holds whatever arrives, one chunk after another through that one buffer, forward and backward (``_unkept``,
    ``_unkept_back``). A buffer for every pair the chips might send one of them would be ``chips * N * most`` rows,
    65,536 of 6,144 where the first rung has 8,192: memory a step that never takes the rung would still be compiled to hold."""
    return rows, next(c for c in range(1, N + 1) if N % c == 0 and N // c * most <= rows // chips)


def _stacked(chunk):
    """A chunk's results on their way into the loop's stacked outputs, behind a barrier. A chunk's sums come straight out
    of ``moe_sum_rows``; XLA:TPU fuses a custom call whose result is written into a slice of a loop's buffer with that
    write, and the fusion runs under the default 16 MiB of scoped VMEM whatever limit the kernel asked for: at four
    groups of 6,144 (18 MiB of windows and accumulator) the step did not compile for the described chips (``PERF.md``
    section 6, PR 67). Behind the barrier the kernel stands alone, with its own limit, and the write is a copy."""
    return jax.lax.optimization_barrier(chunk)


@functools.partial(jax.jit, static_argnames=("rows", "kernel", "act", "axis"))
def _unkept(tokens, idx, weights, wg, wi, wo, first, rows, kernel, act, axis=None):
    """``held_experts`` (``exchanged_experts`` over ``axis``) with nothing named for a checkpoint policy. ``rows`` a pair
    (``_in_chunks``: the buffer and how many chunks): the tokens a chunk at a time; the counts are the chunks' sums, the groups' the chunks' extremes."""
    one = functools.partial(_one_rung(axis), kernel=kernel, named=False, act=act)
    if not isinstance(rows, tuple):
        return one(tokens, idx, weights, wg, wi, wo, first, rows)
    split = lambda x: x.reshape(rows[1], x.shape[0] // rows[1], *x.shape[1:])
    out, routed, dropped, largest, smallest, *sent = jax.lax.map(lambda c: _stacked(one(*c, wg, wi, wo, first, rows[0])), (split(tokens), split(idx), split(weights)))
    return out.reshape(tokens.shape), jnp.sum(routed), jnp.sum(dropped), jnp.max(largest), jnp.min(smallest), *(jnp.sum(x) for x in sent)


@functools.partial(jax.jit, static_argnames=("rows", "kernel", "act", "axis"))
def _unkept_back(tokens, idx, weights, wg, wi, wo, first, cotangent, rows, kernel, act, axis=None):
    """``_unkept``'s forward again and its five gradients for the output's ``cotangent`` (its counts take none). In
    chunks (``rows`` a pair): each chunk's forward and backward in its own turn, the experts' gradients summed in float32."""
    one = functools.partial(_one_rung(axis), kernel=kernel, named=False, act=act)
    per = rows[0] if isinstance(rows, tuple) else rows
    back = lambda t, ix, w, ct: jax.vjp(lambda t, w, g, i, o: one(t, ix, w, g, i, o, first, per)[0], t, w, wg, wi, wo)[1](ct)
    if not isinstance(rows, tuple):
        return back(tokens, idx, weights, cotangent)
    split = lambda x: x.reshape(rows[1], x.shape[0] // rows[1], *x.shape[1:])
    mats = tuple(m for m in (wg, wi, wo) if m is not None)

    def chunk(sums, c):
        d_t, d_w, *d_mats = _stacked(back(*c))
        return tuple(a + d.astype(a.dtype) for a, d in zip(sums, (d for d in d_mats if d is not None))), (d_t, d_w)

    sums, (d_tokens, d_weights) = jax.lax.scan(chunk, tuple(jnp.zeros(m.shape, jnp.float32) for m in mats), tuple(split(x) for x in (tokens, idx, weights, cotangent)))
    d_mats = iter(a.astype(m.dtype) for a, m in zip(sums, mats))
    return d_tokens.reshape(tokens.shape), d_weights.reshape(weights.shape), *(None if m is None else next(d_mats) for m in (wg, wi, wo))


_every_pair = jax.custom_vjp(_above_first, nondiff_argnums=(7, 8, 9, 10))


def _every_pair_fwd(tokens, idx, weights, wg, wi, wo, first, rungs, kernel, act, axis):
    return _above_first(tokens, idx, weights, wg, wi, wo, first, rungs, kernel, act, axis), (tokens, idx, weights, wg, wi, wo, first)


def _every_pair_bwd(rungs, kernel, act, axis, res, cotangents):
    tokens, idx, weights, wg, wi, wo, first = res
    back = lambda rows: _unkept_back(tokens, idx, weights, wg, wi, wo, first, cotangents[0], rows=rows, kernel=kernel, act=act, axis=axis)
    d_tokens, d_weights, d_wg, d_wi, d_wo = _at_rung(idx, first, wo.shape[0], rungs, back)
    return d_tokens, None, d_weights, d_wg, d_wi, d_wo, None


_every_pair.defvjp(_every_pair_fwd, _every_pair_bwd)


def buffer_rungs(every: int, n: int, num_experts: int):
    """The sizes ``routed_part``'s buffer takes, ascending: twice and four
    times the pairs a uniform router sends to ``n`` of ``num_experts``
    experts (``every * n / num_experts``), each rounded up to 512 rows and
    capped at ``every``, the count of all pairs, which is the last."""
    up = lambda times: min(every, -(-times * every * n // num_experts // 512) * 512)
    return up(2), up(4), every


RUNGS = ("first", "four", "every")  # the rung a layer took in a step, by ``routed_part``'s sixth value


def exchange_rungs(N: int, k: int, n: int, num_experts: int, chips: int):
    """The first rung where the rows are exchanged (``exchanged_experts``), ``(slab, rows)``, from ``buffer_rungs``' two
    multiples of what a uniform router sends from a chip's ``N`` tokens to the ``n`` experts another holds, each rounded
    up to 512 rows and capped at every pair that can go one way (a token's ``k`` experts are distinct: ``min(k, n)`` of
    them at most on one chip). The SLAB one chip sends another holds FOUR times that: a pair of chips' load is the
    popularity of ``n`` experts among ONE chip's tokens (one sequence, at 8k), which a router that is not uniform takes
    past twice the uniform load in a tenth to a fifth of a model's (layer, step) pairs and past four times in none of 160
    (K-EXAONE's layers 0-4 at a random start, my chip runs, PR 66). The BUFFER a chip computes in holds TWICE what a
    uniform router sends it from all ``chips``, PR 54's first rung of the host's load: every pass between the grouped
    products runs over the buffer's rows, so it is what a sound router's step pays for. Where the senders' skews are
    their own rows' they average out at the receiver (the fullest chip of four received 0.9 to 1.3 times its uniform
    load there); after a full-attention layer they are not (its normed output is all but the same vector for every row
    of every chip, so all the routers lean one way): one chip received over twice its uniform load in one (layer, step)
    pair in a hundred, which then goes in token chunks (``_exchanged_part``; ``PERF.md`` section 6, PR 66)."""
    up = lambda times: min(N * min(k, n), -(-times * N * k * n // num_experts // 512) * 512)
    return up(4), chips * up(2)


def routed_part(tokens, idx, weights, wg, wi, wo, first, num_experts: int, kernel: bool, act: str = "silu", axis=None):
    """``held_experts`` with a buffer that follows the load, chosen on the
    device from a ladder (``buffer_rungs``) by the count of pairs routed
    here: twice the pairs a uniform router sends to ``n`` of ``num_experts``
    experts, then four times, then every pair there is. No pair routed to a
    held expert is dropped at any imbalance. Every XLA pass between the
    grouped products runs over the buffer's static rows, so the first rung
    is what a sound router's step pays for.

    A ``lax.cond`` under differentiation hands on the residuals of BOTH its
    branches (zeros for the one not taken). So there is ONE conditional that
    is differentiated, the first rung alone names what a checkpointed block
    keeps, and the branch above it keeps nothing at all and chooses between
    the two larger buffers inside itself, forward and backward
    (``_every_pair``): the conditional's residuals are the first rung's and
    the fallback's operands, and nothing of a larger row count is written
    for a branch that a sound router takes in a step out of hundreds, if
    ever. What the fallback costs when it does run: its forward a second
    time inside its backward. Under ``jax.checkpoint`` (the block's) that is
    what an unnamed branch costs anyway; without, it is a slower backward in
    a step whose router sent more than twice the uniform load to the held
    experts. ``moe_fallback_layers_total`` counts such (layer, step) pairs,
    ``moe_buffer_rung_layers_total{rung}`` each rung's.

    The first rung's grouped kernels take rows in tiles of 512 where a
    uniform router fills an expert's group with 2,048 rows or more (``N * k /
    num_experts``: what this call can see of the load), else 256: at full
    groups a wider tile crosses fewer group boundaries a row (three products,
    forward and backward, 8 groups of 2,048 rows at 2,048 x 1,792: 11.14 ms at
    256, 9.91 at 512; TPU v5e, ``PERF.md``, PR 55), at groups of a few hundred
    rows it multiplies padding. The rungs above keep 256: they run in a step
    out of hundreds and their shapes are every layer's to compile.

    ``axis``: the experts are split over the chips of that mesh axis and the
    tokens are too (``exchanged_experts``: ``wg, wi, wo`` are this chip's own
    ``n``, ``first`` the axis' first expert): one rung that keeps, sized for a
    pair of chips' skew and for what a chip receives (``exchange_rungs``), and
    every pair in token chunks above it, taken by every chip of the axis
    together (``_exchanged_part``).

    Returns ``held_experts``'s five values (``exchanged_experts``'s six) and
    the rung taken (0, 1 or 2: ``RUNGS``; 1 where four times the uniform load
    is every pair)."""
    N, k = idx.shape
    n = wo.shape[0]
    if axis is not None:
        return _exchanged_part(tokens, idx, weights, wg, wi, wo, first, num_experts, kernel, act, axis)
    usual, four, every = buffer_rungs(N * k, n, num_experts)
    row_tile = 512 if N * k >= 2048 * num_experts else 256

    def held():
        with region("branch/usual"):
            return held_experts(tokens, idx, weights, wg, wi, wo, first, usual, kernel, act=act, row_tile=row_tile)

    if usual == every:
        return *held(), jnp.zeros((), jnp.int32)
    above = (four, every) if usual < four < every else (every,)
    # what the conditional itself adds (whatever one branch writes for the other's residuals) has no inner region and so
    # falls to ``ffn/cond``; the branches' scopes tell their ``ffn/rows`` and ``ffn/experts`` apart
    with region("ffn/cond", path="fallback_keeps_nothing"):
        routed = _load(idx, first, n)
        rung = (routed > usual).astype(jnp.int32) + (routed > four).astype(jnp.int32)
        return *jax.lax.cond(routed <= usual, held, lambda: _every_pair(tokens, idx, weights, wg, wi, wo, first, above, kernel, act, None)), rung


def _exchanged_part(tokens, idx, weights, wg, wi, wo, first, num_experts: int, kernel: bool, act: str, axis):
    """``routed_part`` where the rows are exchanged over ``axis``: ONE rung that keeps what a checkpointed block keeps
    (``exchange_rungs``: a slab of four times the uniform load a pair of chips, a buffer of twice what a chip receives),
    and above it every pair, the chip's tokens a chunk at a time through the same buffer (``_in_chunks``), which keeps
    nothing. The rung taken is 0 or 2 (``RUNGS``: ``first``, ``every``)."""
    N, k = idx.shape
    n, chips = wo.shape[0], jax.lax.axis_size(axis)
    slab, rows = exchange_rungs(N, k, n, num_experts, chips)
    row_tile = 512 if chips * N * k >= 2048 * num_experts else 256

    def held():
        with region("branch/usual"):
            return exchanged_experts(tokens, idx, weights, wg, wi, wo, first, rows, kernel, act=act, row_tile=row_tile, axis=axis, slab=slab)

    most = N * min(k, n)  # every pair a chip can send one other
    if slab == most and rows == chips * most:
        return *held(), jnp.zeros((), jnp.int32)
    above = (_in_chunks(N, min(k, n), rows, chips),)
    with region("ffn/cond", path="fallback_keeps_nothing"):
        pair, chip = _load(idx, first, n, axis)
        holds = (pair <= slab) & (chip <= rows)
        return *jax.lax.cond(holds, held, lambda: _every_pair(tokens, idx, weights, wg, wi, wo, first, above, kernel, act, axis)), 2 * (1 - holds.astype(jnp.int32))
