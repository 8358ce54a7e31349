"""Attention over the keys a learned indexer chooses (``models/mixers.py::SparseMixer``, ``ops/indexed_attention.py``,
``ops/pallas/indexed_attention.py``): each Pallas kernel in interpret mode against the XLA form, forward and backward;
the mixer's two learners kept apart; exactly ``min(k, t + 1)`` keys a query and none ahead of it; a sequence no longer
than ``k`` is the dense mixer's program; what it counts; the share of eight; and the flash calls the older cells make,
pinned. Tiny widths, float32, CPU."""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import CausalLM, TransformerConfig
from deepspeed_tpu.models.transformer import cross_entropy_loss
from deepspeed_tpu.ops import indexed_attention as ops, placement
from deepspeed_tpu.ops.pallas import indexed_attention as kernel
from deepspeed_tpu.telemetry import device_counts, get_registry
from deepspeed_tpu.telemetry.tracing import regions_traced

B, S, H, KVH, D, J, DI, TOPK, BLK = 2, 256, 4, 2, 32, 3, 16, 40, 128
SCALE = D ** -0.5


@pytest.fixture(scope="module")
def highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def operands(highest):
    rng = np.random.default_rng(0)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    t = dict(q=f(B, S, H, D), k=f(B, S, KVH, D), v=f(B, S, KVH, D), do=f(B, S, H, D), q_i=f(B, J, S, DI), k_i=f(B, S, DI), w=f(B, J, S) * 0.2)
    t["scores"] = ops.index_scores_xla(t["q_i"], t["k_i"], t["w"])
    t["mask"] = ops.select_xla(t["scores"], TOPK)
    t["tiles"] = kernel.tiled(t["mask"], BLK)
    (t["o"], t["lse"]), t["vjp"] = jax.vjp(lambda q, k, v: ops.sparse_attention_xla(q, k, v, t["mask"], SCALE), t["q"], t["k"], t["v"])
    t["probs"] = ops.head_probs_xla(t["q"], t["k"], t["lse"], t["mask"], SCALE)
    return t


def _close(a, b, tol=2e-5):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert np.max(np.abs(a - b)) <= tol * (1.0 + np.max(np.abs(b))), (np.max(np.abs(a - b)), np.max(np.abs(b)))


def chosen_pairs(seq, topk, batch=1):
    return batch * sum(min(topk, t + 1) for t in range(seq))


# (positions, the tile) of the cases in which the loops over heads have something to walk: ``strips_and_tiles`` has four
# strips a tile (``strip_for(512)`` is (512, 128)), two tiles a row (the diagonal's, one below, one above), 16 indexer
# heads (two trips of eight) and 32 query heads on 4 KV heads (four trips; a trip's heads share one KV head's keys);
# ``a_strip_a_tile`` is the block that IS one strip, three indexer heads (trips of one head) and 4 query heads (one trip)
WALKS = {"a_strip_a_tile": (S, BLK), "strips_and_tiles": (1024, 512)}


@functools.lru_cache(maxsize=None)
def _walk_case(case):
    """The three kernels' operands of a walk's case with XLA's forms of everything: the oracle."""
    seq, blk = WALKS[case]
    b, heads, kv_heads, index_heads, topk = (B, H, KVH, J, TOPK) if case == "a_strip_a_tile" else (1, 32, 4, 16, 96)
    rng = np.random.default_rng(sorted(WALKS).index(case) + 100)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    with jax.default_matmul_precision("highest"):
        t = dict(q=f(b, seq, heads, D), k=f(b, seq, kv_heads, D), v=f(b, seq, kv_heads, D), q_i=f(b, index_heads, seq, DI), k_i=f(b, seq, DI),
                 w=f(b, index_heads, seq) * 0.1, heads=(heads, kv_heads))
        t["scores"] = ops.index_scores_xla(t["q_i"], t["k_i"], t["w"])
        t["mask"] = ops.select_xla(t["scores"], topk)
        _, t["lse"] = ops.sparse_attention_xla(t["q"], t["k"], t["v"], t["mask"], SCALE)
        t["loss"], t["grad"] = ops._index_loss_and_grad(t["scores"], ops.head_probs_xla(t["q"], t["k"], t["lse"], t["mask"], SCALE), t["mask"])
        t["vjp"] = jax.vjp(ops.index_scores_xla, t["q_i"], t["k_i"], t["w"])[1](t["grad"])
    return t


def _parents_index_scores(q_i, k_i, w, blk):
    """``index_scores`` as it stood before PR 62, interpreted: a tile's sum over heads carried whole through one loop."""
    from jax.experimental import pallas as pl

    b, heads, seq, di = q_i.shape
    n = seq // blk

    def body(q_ref, k_ref, w_ref, o_ref):
        i, j = pl.program_id(1), pl.program_id(2)

        @pl.when(j > i)
        def _above():
            o_ref[0] = jnp.full((blk, blk), kernel.NEG_INF, jnp.float32)

        @pl.when(j <= i)
        def _scores():
            k = k_ref[0]

            def head(h, acc):
                s = jax.lax.dot_general(k, q_ref[0, h], (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
                return acc + jnp.maximum(s, 0.0) * w_ref[0, h]

            acc = jax.lax.fori_loop(0, heads, head, jnp.zeros((blk, blk), jnp.float32))
            keys = j * blk + jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 0)
            queries = i * blk + jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 1)
            o_ref[0] = jnp.where(keys <= queries, acc, kernel.NEG_INF)

    return pl.pallas_call(body, grid=(b, n, n), interpret=True, out_shape=jax.ShapeDtypeStruct((b, seq, seq), jnp.float32),
                          in_specs=[pl.BlockSpec((1, heads, blk, di), lambda b, i, j: (b, 0, i, 0)), pl.BlockSpec((1, blk, di), lambda b, i, j: (b, j, 0)),
                                    pl.BlockSpec((1, heads, 1, blk), lambda b, i, j: (b, 0, 0, i))],
                          out_specs=pl.BlockSpec((1, blk, blk), lambda b, i, j: (b, j, i)))(q_i, k_i, w.reshape(b, heads, 1, seq))


@pytest.mark.parametrize("case", list(WALKS))
def test_the_score_kernel_is_the_xla_form(highest, case):
    """... and, bit for bit, what it gave before its sum over heads was made a strip at a time, eight heads a trip: the
    float32 sums over the heads keep their order."""
    (seq, blk), t = WALKS[case], _walk_case(case)
    got = kernel.index_scores(t["q_i"], t["k_i"], t["w"], interpret=True, blk=blk)
    _close(got, t["scores"])
    assert float(jnp.max(jnp.where(jnp.arange(seq)[:, None] > jnp.arange(seq)[None, :], got, kernel.NEG_INF))) <= -9e29  # a key ahead of its query scores NEG_INF
    assert int(jnp.sum(got != _parents_index_scores(t["q_i"], t["k_i"], t["w"], blk))) == 0


@pytest.mark.parametrize("case", list(WALKS))
def test_the_scores_backward_kernel_is_the_xla_forms_vjp(highest, case):
    (seq, blk), t = WALKS[case], _walk_case(case)
    for got, want in zip(kernel.index_scores_bwd(t["grad"], t["q_i"], t["k_i"], t["w"], interpret=True, blk=blk), t["vjp"]):
        _close(got, want)


# (positions, keys a query takes, rows a chunk; 0: the kernel's own) at bands of 128 queries. The first has the band in which
# ``t + 1`` passes ``topk`` and one past it; the others add a band wholly under ``topk``, which runs no search, and the
# last walks chunks of 256 rows, which divide the visible rows of no odd band: its last chunk reaches past the diagonal
CHOICES = {"two_bands": (S, TOPK, 128), "every_kind_of_band": (512, 160, 0), "chunks_past_the_diagonal": (512, 160, 256)}


@functools.lru_cache(maxsize=None)
def _scores(seq):
    rng = np.random.default_rng(seq)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    return ops.index_scores_xla(f(B, J, seq, DI), f(B, seq, DI), f(B, J, seq) * 0.2)


@pytest.mark.parametrize("what", ["as_scored", "many_ties", "all_equal", "negative_zero", "ties_across_a_chunks_edge"])
@pytest.mark.parametrize("shape", list(CHOICES))
def test_the_choice_kernel_is_top_k_with_ties_to_the_lower_index(highest, shape, what):
    seq, topk, rows = CHOICES[shape]
    scores = _scores(seq)
    seen = scores > -1e29
    key = jnp.arange(seq)[:, None]
    edge = -(-(topk + 10) // 128) * 128  # the first row past the keys above the threshold that starts a chunk of 128 or of 256 rows
    if what == "many_ties":  # half-integers: dozens of keys share a query's threshold
        scores = jnp.where(seen, jnp.round(scores * 2) / 2, scores)
    elif what == "all_equal":  # every key ties: the choice is the first ``topk`` positions
        scores = jnp.where(seen, 1.0, scores)
    elif what == "negative_zero":  # -0.0 and 0.0 are one score
        scores = jnp.where(seen, jnp.where(key % 2 == 0, -0.0, 0.0), scores)
    elif what == "ties_across_a_chunks_edge":  # ``topk - 14`` keys above the threshold, twenty ties around the edge, 14 needed
        scores = jnp.where(seen, jnp.where(key < topk - 14, 3.0 + key, jnp.where(abs(key - edge + 0.5) < 10, 1.0, -1.0 - abs(scores))), scores)
    want, got = ops.select_xla(scores, topk), kernel.index_select(scores, topk, interpret=True, band=128, rows=rows)
    assert got.dtype == jnp.int8 and int(jnp.sum(want != got)) == 0
    assert int(jnp.sum(got)) == chosen_pairs(seq, topk, B)
    per_query = jnp.sum(got.astype(jnp.int32), axis=1)  # key-major: a query's keys lie along axis 1
    assert (np.asarray(per_query) == np.minimum(np.arange(seq) + 1, topk)[None, :]).all()
    assert int(jnp.sum(jnp.where(key > jnp.arange(seq)[None, :], got, 0))) == 0  # none ahead of its query
    last = np.asarray(got[0, :, seq - 1])  # the last query's keys
    if what in ("all_equal", "negative_zero"):
        assert (last[:topk] == 1).all()  # the lowest indices
    if what == "ties_across_a_chunks_edge":
        assert (np.flatnonzero(last) == np.r_[0:topk - 14, edge - 10:edge + 4]).all()  # four of the ties lie in the next chunk


@pytest.mark.parametrize("rows,chunks,share", [(128, 1944, "0.475"), (256, 984, "0.480"), (512, 504, "0.492")])
def test_the_choice_walks_under_half_of_the_rows_at_the_keye_cells_shape(rows, chunks, share):
    """8,192 positions, 2,048 keys a query, bands of 128: bands 0-15 search nothing, band ``i`` of the others walks the
    chunks that hold one of its ``128 (i + 1)`` visible keys; 4,096 band-chunks of 128 rows in all."""
    walked = kernel.chunks_walked(8192, 2048, 128, rows)
    assert walked[:16] == [0] * 16 and walked[16:] == [-(-128 * (i + 1) // rows) for i in range(16, 64)] and sum(walked) == chunks
    assert f"{kernel.share_walked(8192, 2048, rows=rows):.3f}" == share and kernel.share_walked(8192, 2048, rows=rows) <= 0.5
    assert kernel.share_walked(8192, 8192, rows=rows) == 0.0  # every query takes every visible key: no band searches


def test_the_forward_kernel_is_masked_softmax_attention(operands):
    t = operands
    o, lse = kernel.sparse_fwd(ops._to_bh(t["q"]), ops._to_bh(t["k"]), ops._to_bh(t["v"]), t["tiles"], SCALE, H, KVH, interpret=True)
    _close(o.reshape(B, H, S, D).transpose(0, 2, 1, 3), t["o"])
    _close(lse.reshape(B, H, S), t["lse"])


@pytest.mark.parametrize("kv_heads", [KVH, H])
def test_the_backward_kernel_is_the_xla_forms_vjp(operands, kv_heads):
    """A group of two query heads a KV head (dk and dv added up in the kernel's scratch), and one."""
    t = operands
    k, v = (jnp.repeat(t[x], kv_heads // KVH, axis=2) for x in ("k", "v"))
    (o, lse), vjp = jax.vjp(lambda q, k, v: ops.sparse_attention_xla(q, k, v, t["mask"], SCALE), t["q"], k, v)
    want = vjp((t["do"], jnp.zeros_like(lse)))
    got = kernel.sparse_bwd(ops._to_bh(t["q"]), ops._to_bh(k), ops._to_bh(v), ops._to_bh(o), lse.reshape(B * H, S), ops._to_bh(t["do"]),
                            t["tiles"], SCALE, H, kv_heads, interpret=True)
    for a, b, heads in zip(got, want, (H, kv_heads, kv_heads)):
        _close(a.reshape(B, heads, S, D).transpose(0, 2, 1, 3), b)


# (B, S, H, KVH, keys a query takes, the tile) and what is done to the operands. The tile of 128 gives a query block two to
# four key blocks to walk, twice; ``every_visible_key`` has a band whose queries take every key they see (the first 160)
LOSSES = {
    "batch_of_two": (2, 256, 4, 2, 40, 128),
    "four_query_blocks": (1, 512, 4, 2, 40, 128),
    "one_block": (1, 256, 4, 2, 40, 256),
    "a_head_a_kv_head": (1, 256, 4, 4, 40, 128),
    "four_heads_a_kv_head": (2, 256, 4, 1, 40, 128),
    "every_visible_key": (1, 256, 4, 2, 160, 128),
    "a_chosen_pair_underflows": (2, 256, 4, 2, 40, 128),
    "equal_scores_at_the_threshold": (1, 256, 4, 2, 40, 128),
    # (PR 62) four strips a tile and two tiles a row, 32 heads on 4 KV heads: four trips of eight heads a strip
    "strips_and_tiles_32_heads_on_4": (1, 1024, 32, 4, 96, 512),
}
ONE_ULP_SHARE = 1e-3  # of the nonzero pairs, after both gradients are rounded to bf16: 0 to 1.5e-4 in these cases (4 of 28,239 at most)


@functools.lru_cache(maxsize=None)
def _loss_case(case):
    """The loss kernel's operands of a case, with XLA's target, loss and gradient: the oracle."""
    b, seq, h, kvh, topk, blk = LOSSES[case]
    rng = np.random.default_rng(sorted(LOSSES).index(case))
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    with jax.default_matmul_precision("highest"):
        q, k, v = f(b, seq, h, D), f(b, seq, kvh, D), f(b, seq, kvh, D)
        scores = ops.index_scores_xla(f(b, J, seq, DI), f(b, seq, DI), f(b, J, seq) * 0.2)
        if case == "equal_scores_at_the_threshold":  # half-integers: dozens of keys share a query's threshold and its softmax's terms
            scores = jnp.where(scores > -1e29, jnp.round(scores * 2) / 2, scores)
        mask = ops.select_xla(scores, topk)
        pair = None
        if case == "a_chosen_pair_underflows":  # every head of query 200 scores its first chosen key at -141: exp(-141 - lse) is zero in float32
            pair = (0, int(np.flatnonzero(np.asarray(mask[0, :, 200]))[0]), 200)
            q, k = q.at[0, 200].set(5.0), k.at[0, pair[1]].set(-5.0)
        _, lse = ops.sparse_attention_xla(q, k, v, mask, SCALE)
        probs = ops.head_probs_xla(q, k, lse, mask, SCALE)
        loss, grad = ops._index_loss_and_grad(scores, probs, mask)
    return dict(q=q, k=k, lse=lse, scores=scores, mask=mask, probs=probs, loss=loss, grad=grad, pair=pair, shape=LOSSES[case])


@pytest.mark.parametrize("case", list(LOSSES))
def test_the_loss_kernel_is_the_xla_forms_loss_and_gradient(highest, case):
    """ONE call from q, k, the forward's lse, the mask's tiles and the scores: every query's KL (their mean the loss, to
    1e-6 relative) and the mean's gradient in the scores, which rounded to bf16 is the XLA form's rounded to bf16 but for
    one ulp on at most ``ONE_ULP_SHARE`` of the nonzero pairs (the rows' sums are added up a tile at a time, and ``P / Z``
    is ``P * (1 / Z)``); zero off the chosen pairs."""
    t = _loss_case(case)
    b, seq, h, kvh, topk, blk = t["shape"]
    call = lambda dtype: kernel.index_loss(ops._to_bh(t["q"]), ops._to_bh(t["k"]), t["lse"].reshape(b * h, seq), kernel.tiled(t["mask"], blk),
                                           t["scores"], SCALE, h, kvh, dtype, interpret=True)
    kl, grad = call(jnp.float32)
    assert kl.shape == (b, seq) and grad.shape == (b, seq, seq) and float(jnp.min(kl)) > -1e-6
    assert abs(float(jnp.mean(kl)) / float(t["loss"]) - 1.0) <= 1e-6
    _close(grad, t["grad"], 1e-6)
    assert float(jnp.max(jnp.abs(jnp.where(t["mask"] == 0, grad, 0.0)))) == 0.0
    _close(jnp.sum(grad, axis=1), jnp.zeros((b, seq)), 1e-8)  # softmax - p: a query's row sums to nothing
    kl16, got = call(jnp.bfloat16)
    want = t["grad"].astype(jnp.bfloat16)
    assert got.dtype == jnp.bfloat16 and float(jnp.max(jnp.abs(kl16 - kl))) == 0.0
    differ = np.asarray(got != want)
    assert differ.sum() <= ONE_ULP_SHARE * int(jnp.sum(want != 0)), (int(differ.sum()), int(jnp.sum(want != 0)))
    bits = lambda x: np.asarray(jax.lax.bitcast_convert_type(x, jnp.int16), np.int32)
    assert np.abs(bits(got) - bits(want))[differ].max(initial=0) <= 1  # neighbours in bf16
    if case == "every_visible_key":
        assert int(jnp.sum(t["mask"][0, :, :topk])) == topk * (topk + 1) // 2  # the first 160 queries take every key they see
    if t["pair"]:
        at = t["pair"]
        assert int(t["mask"][at]) == 1 and float(t["probs"][at]) == 0.0 and float(grad[at]) > 0.0  # the softmax's term alone
        assert np.isfinite(np.asarray(kl)).all()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_index_loss_carries_the_indexers_gradient_through_the_backward_kernel(operands, dtype):
    """The kernels' form keeps the loss's gradient in the scores (in the model's dtype) and ``index_scores_bwd`` carries it
    to qI, kI and w: against autodiff of the XLA forms."""
    t = operands
    args = (t["q_i"], t["k_i"], t["w"])
    want_loss, want = jax.value_and_grad(lambda *a: ops._index_loss_and_grad(ops.index_scores_xla(*a), t["probs"], t["mask"])[0], argnums=(0, 1, 2))(*args)
    got_loss, got = jax.value_and_grad(lambda *a: ops._index_loss_kernel(*a, t["scores"], t["q"], t["k"], t["lse"], t["mask"], SCALE, dtype, True), argnums=(0, 1, 2))(*args)
    _close(got_loss, want_loss, 1e-6)
    for a, b in zip(got, want):
        _close(a, b, 2e-5 if dtype == jnp.float32 else 2e-2)
    grad = ops._index_loss_and_grad(t["scores"], t["probs"], t["mask"])[1]
    by_blocks = kernel.index_scores_bwd(grad, *args, interpret=True, blk=BLK)  # four blocks a batch: the walk along the keys
    for a, b in zip(by_blocks, (want[0], want[1], want[2])):
        _close(a, b)


def test_the_kernels_are_named_apart_from_the_calls_other_readers_match(operands):
    """The benchmark's readers match custom calls by name: ``flash_fwd``, ``flash_bwd``, ``kda_scan_*``, ``gdn_scan_*``,
    ``gmm``, ``tgmm``, ``moe_sum_rows`` must not see these six, and these readers' patterns must see their own alone."""
    t = operands
    bh = ops._to_bh
    q, k, v, o, do, lse = bh(t["q"]), bh(t["k"]), bh(t["v"]), bh(t["o"]), bh(t["do"]), t["lse"].reshape(B * H, S)
    calls = (lambda: kernel.index_scores(t["q_i"], t["k_i"], t["w"], interpret=True),
             lambda: kernel.index_select(t["scores"], TOPK, interpret=True),
             lambda: kernel.sparse_fwd(q, k, v, t["tiles"], SCALE, H, KVH, interpret=True),
             lambda: kernel.sparse_bwd(q, k, v, o, lse, do, t["tiles"], SCALE, H, KVH, interpret=True),
             lambda: kernel.index_loss(q, k, lse, t["tiles"], t["scores"], SCALE, H, KVH, jnp.bfloat16, interpret=True),
             lambda: kernel.index_scores_bwd(t["probs"], t["q_i"], t["k_i"], t["w"], interpret=True))
    names = {name for call in calls for name in re.findall(r"name=(\w+)", str(jax.make_jaxpr(call)()))}
    assert {"index_scores", "index_select", "sparse_fwd", "sparse_bwd", "index_loss", "index_scores_bwd"} <= names
    # the two readers of these calls: ``index_loss`` is in neither's pattern, as ``sparse_probs`` was in neither
    assert not re.search(r"\bsparse_(fwd|bwd)\b|\bindex_(scores|select|scores_bwd)\b", "index_loss")
    others = r"flash_(fwd|bwd|dq|dkv)|kda_scan|gdn_scan|\bt?gmm\b|moe_sum_rows"
    assert not [n for n in names if re.search(others, n)]


# ---------------------------------------------------------------------- what a start pays for the loops over heads
def _kernel_dots(call, *shapes):
    """The ``dot_general`` equations in the body of the one Pallas call ``call`` traces, loops' bodies entered."""
    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn
            for value in eqn.params.values():
                for sub in (value if isinstance(value, (tuple, list)) else (value,)):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        yield from walk(sub)

    bodies = [eqn.params["jaxpr"] for eqn in walk(jax.make_jaxpr(call)(*shapes).jaxpr) if eqn.primitive.name == "pallas_call"]
    assert len(bodies) == 1
    return sum(eqn.primitive.name == "dot_general" for eqn in walk(bodies[0]))


@pytest.mark.parametrize("call,dots", [("index_scores", kernel.HEADS_A_TRIP), ("index_loss", kernel.HEADS_A_TRIP), ("index_scores_bwd", 3)])
def test_a_kernels_code_does_not_grow_with_its_strips_nor_its_heads(monkeypatch, call, dots):
    """The strips of a tile are ONE rolled loop and the heads another, of ``HEADS_A_TRIP`` heads a trip written out (the
    backward's, one head a trip of whole-tile products): the body of a kernel holds as many ``dot_general`` at 2 strips
    a tile as at 16, and at 16 heads as at 32. Copies of a body are what a start pays for: traced, lowered and compiled
    every time (sixteen and thirty-two copies of a head cost the Keye cell 5 s of every warm start: PERF.md, PR 61, 62)."""
    monkeypatch.setattr(kernel, "vmem_budget", lambda: 1 << 40)  # a trace: a tile of sixteen strips holds in no VMEM
    seq, sds = 2048, jax.ShapeDtypeStruct

    def traced(blk, heads):
        if call == "index_loss":
            shapes = (sds((heads, seq, D), jnp.float32), sds((heads // 4, seq, D), jnp.float32), sds((heads, seq), jnp.float32),
                      sds((1, seq // blk, seq // blk, blk, blk), jnp.int8), sds((1, seq, seq), jnp.float32))
            return _kernel_dots(lambda *a: kernel.index_loss(*a, SCALE, heads, heads // 4, jnp.float32, interpret=True), *shapes)
        shapes = (sds((1, heads, seq, DI), jnp.float32), sds((1, seq, DI), jnp.float32), sds((1, heads, seq), jnp.float32))
        if call == "index_scores":
            return _kernel_dots(lambda *a: kernel.index_scores(*a, interpret=True, blk=blk), *shapes)
        return _kernel_dots(lambda *a: kernel.index_scores_bwd(*a, interpret=True, blk=blk), sds((1, seq, seq), jnp.float32), *shapes)

    assert [seq // kernel.strip_for(blk)[1] * blk // seq for blk in (256, 2048)] == [2, 16]  # strips a tile
    assert {traced(blk, heads) for blk in (256, 2048) for heads in (16, 32)} == {dots}


def test_the_six_calls_are_traced_once_a_shape_whatever_the_layers(highest, monkeypatch):
    """``model.init`` runs its four sparse layers in a plain loop and the step traces a checkpointed block for its value
    and its backward: the six entry points are jitted, so each one's Python body (and with it its kernel's) is entered
    ONCE for the process at a shape, where the four that ``init`` reaches were entered four times there and once more in
    the step. (A jitted call inside a ``custom_vjp`` is traced anew under ``jax.checkpoint``'s gradient: the two forwards
    that have a backward are entered a second time there, once, whatever the layers.)"""
    entered = []
    call = kernel.pl.pallas_call
    monkeypatch.setattr(kernel.pl, "pallas_call", lambda body, *a, **kw: entered.append(kw.get("name")) or call(body, *a, **kw))
    monkeypatch.setattr(placement, "kernel_path", lambda fits=True, has_specs=True: "kernel")
    # widths of this test's own: a shape some other test of the process has traced would be entered no more
    m = CausalLM(tiny(n_layers=4, layer_kinds=(("sparse", "dense"),) * 4, max_seq_len=384, index_topk=72, remat=True, head_dims=24, d_model=48,
                      index_heads=5, index_head_dim=24))
    ids = np.zeros((1, 384), np.int32)
    ours = lambda: {name: entered.count(name) for name in entered if name and re.match(r"index_|sparse_", name)}
    params = jax.eval_shape(lambda: m.init(jax.random.PRNGKey(0), {"input_ids": ids}))
    assert ours() == {"index_scores": 1, "index_select": 1, "sparse_fwd": 1, "index_loss": 1}  # four layers' forwards, one entry each
    jax.make_jaxpr(jax.value_and_grad(lambda p: m.loss_fn(p, {"input_ids": ids})))(params)
    assert ours() == {"index_scores": 1, "index_select": 1, "sparse_fwd": 2, "index_loss": 2, "sparse_bwd": 1, "index_scores_bwd": 1}
    jax.make_jaxpr(jax.value_and_grad(lambda p: m.loss_fn(p, {"input_ids": ids})))(params)  # the step again, as a second program of the shape would
    assert sum(ours().values()) == 8


def test_the_kernels_call_sites_say_the_strip_they_walk(operands, monkeypatch):
    """``program_regions_traced_total{region="mixer/kernel", op="sparse", pass, path="kernel", index_strip="RxL"}`` at the
    two call sites with a loop over heads; the backward's whole-tile products and XLA's path carry no such label."""
    t = operands
    assert kernel.strip_for(kernel.block_for(8192)) == (512, 128) and kernel.strip_for(128) == (128, 128) and kernel.strip_for(64) == (64, 64)
    series = lambda pass_, **labels: get_registry().peek("program_regions_traced_total", region="mixer/kernel", op="sparse", **{"pass": pass_}, **labels) or 0.0
    word = "{}x{}".format(*kernel.strip_for(kernel.block_for(S)))
    assert word == "256x128"
    before = {p: series(p, path="kernel", index_strip=word) for p in ("index", "loss")}
    xla = {p: (regions_traced("mixer/kernel", op="sparse", path="xla", **{"pass": p}), series(p, path="xla")) for p in ("index", "loss")}
    monkeypatch.setattr(placement, "interpret", lambda: True)
    scores = ops.index_scores(t["q_i"], t["k_i"], t["w"], path="kernel")
    loss = lambda *a: ops.index_loss(*a, scores, t["q"], t["k"], t["lse"], t["mask"], scale=SCALE, dtype=jnp.float32, path="kernel")
    jax.grad(loss, argnums=(0, 1, 2))(t["q_i"], t["k_i"], t["w"])
    assert {p: series(p, path="kernel", index_strip=word) - n for p, n in before.items()} == {"index": 1.0, "loss": 1.0}
    assert not get_registry().by_label("program_regions_traced_total", "index_strip", region="mixer/kernel", **{"pass": "index_bwd"})
    ops.index_scores(t["q_i"], t["k_i"], t["w"], path="xla")
    ops.index_loss(t["q_i"], t["k_i"], t["w"], scores, t["q"], t["k"], t["lse"], t["mask"], scale=SCALE, dtype=jnp.float32, path="xla")
    for p, (every, unlabelled) in xla.items():  # the series without the label is the whole of XLA's path
        assert regions_traced("mixer/kernel", op="sparse", path="xla", **{"pass": p}) == every + 1 == series(p, path="xla") and every == unlabelled


# ---------------------------------------------------------------------- the mixer
def tiny(**over):
    base = dict(vocab_size=97, n_layers=2, n_heads=4, n_kv_heads=2, head_dims=16, d_model=32, max_seq_len=64, norm="rmsnorm",
                activation="swiglu", pos_emb="rope", rope_theta=1e7, qk_norm=True, tie_embeddings=False, norm_eps=1e-6,
                layer_kinds=(("sparse", "dense"),) * 2, index_heads=3, index_head_dim=8, index_topk=16)
    return TransformerConfig(**dict(base, **over))


@pytest.fixture(scope="module")
def model(highest):
    m = CausalLM(tiny())
    ids = np.random.default_rng(1).integers(0, 97, (2, 64)).astype(np.int32)
    params = m.init(jax.random.PRNGKey(0), {"input_ids": ids})
    leaves, tree = jax.tree_util.tree_flatten(params)  # the norms' weights start at one and the biases at zero: moved
    return m, jax.tree_util.tree_unflatten(tree, [x + 0.05 * jax.random.normal(jax.random.PRNGKey(7 + i), x.shape) for i, x in enumerate(leaves)]), ids


def _is_indexer(path):
    return any(str(getattr(k, "key", "")).startswith("index_") for k in path)


def _sown(m, params, ids):
    logits, mods = m.module.apply({"params": params}, ids, mutable=("intermediates", "losses"))
    return logits, [mods["intermediates"][f"layer_{i}"]["sparse"] for i in range(m.cfg.n_layers)]


def test_the_two_learners_are_kept_apart(model):
    """``d L_I / d(main leaves) = 0`` and ``d CE / d(indexer leaves) = 0``; the step's loss is the cross-entropy's value
    and its gradient the two side by side."""
    m, params, ids = model
    labels = jnp.concatenate([ids[:, 1:], jnp.full((2, 1), -100, ids.dtype)], axis=1)
    ce = lambda p: cross_entropy_loss(m.apply(p, ids), labels)
    index = lambda p: sum(layer["index_loss"][0] for layer in _sown(m, p, ids)[1])
    (ce_value, g_ce), (index_value, g_index) = jax.value_and_grad(ce)(params), jax.value_and_grad(index)(params)
    step_value, g_step = jax.value_and_grad(lambda p: m.loss_fn(p, {"input_ids": ids}))(params)
    _close(step_value, ce_value, 1e-6)
    assert float(index_value) > 0.01
    for (path, a), b, both in zip(jax.tree_util.tree_leaves_with_path(g_ce), jax.tree_util.tree_leaves(g_index), jax.tree_util.tree_leaves(g_step)):
        if _is_indexer(path):
            assert float(jnp.max(jnp.abs(a))) == 0.0 and float(jnp.max(jnp.abs(b))) > 0.0, path
        else:
            assert float(jnp.max(jnp.abs(b))) == 0.0 and float(jnp.max(jnp.abs(a))) > 0.0, path
        _close(both, a + b, 1e-5)
    assert sum(_is_indexer(path) for path, _ in jax.tree_util.tree_leaves_with_path(params)) == 2 * 5  # qI, kI, its norm's two, w


def test_a_query_takes_exactly_its_keys_and_none_ahead_of_it(model):
    m, params, ids = model
    for layer in _sown(m, params, ids)[1]:
        choice = np.asarray(layer["choice"][0])  # key-major (B, Sk, Sq)
        assert (choice.sum(axis=1) == np.minimum(np.arange(64) + 1, 16)[None, :]).all()
        assert np.triu(choice.transpose(0, 2, 1), k=1).sum() == 0
        assert tuple(np.asarray(layer["sparse_keys"][0])) == (chosen_pairs(64, 16, 2), 2 * 64 * 65 / 2)


def test_a_sequence_no_longer_than_the_keys_a_query_takes_is_the_dense_mixers_program(model):
    """Values equal the ``full`` mixer's on the same weights; no mask, no sort, no indexer traced; the indexer's leaves
    take a zero gradient and nothing is sown."""
    m, params, ids = model
    short = CausalLM(dataclasses.replace(m.cfg, index_topk=64))
    dense = CausalLM(dataclasses.replace(m.cfg, layer_kinds=(("full", "dense"),) * 2))
    as_dense = {k: ({"attn": {n: x for n, x in v["sparse"].items() if not n.startswith("index_")}, **{n: x for n, x in v.items() if n != "sparse"}}
                    if k.startswith("layer_") else v) for k, v in params.items()}
    assert float(jnp.max(jnp.abs(short.apply(params, ids) - dense.apply(as_dense, ids)))) == 0.0
    text = str(jax.make_jaxpr(lambda p: short.loss_fn(p, {"input_ids": ids}))(params))
    traced = lambda text: [word for word in ("top_k", " sort", "scatter") if word in text]  # the choice and its mask
    assert traced(text) == [] and "top_k" in traced(str(jax.make_jaxpr(lambda p: m.loss_fn(p, {"input_ids": ids}))(params)))
    grads = jax.grad(lambda p: short.loss_fn(p, {"input_ids": ids}))(params)
    assert all(float(jnp.max(jnp.abs(g))) == 0.0 for path, g in jax.tree_util.tree_leaves_with_path(grads) if _is_indexer(path))
    assert "intermediates" not in short.module.apply({"params": params}, ids, mutable=("intermediates", "losses"))[1]


def test_the_kernels_path_is_the_xla_path_through_the_whole_model_under_remat(highest, monkeypatch):
    """The model at 256 positions with every square part a Pallas call (interpreted), blocks checkpointed with the named
    saves: loss and every leaf's gradient equal the XLA forms'."""
    m = CausalLM(tiny(max_seq_len=256, index_topk=48, remat=True))
    ids = np.random.default_rng(2).integers(0, 97, (1, 256)).astype(np.int32)
    params = m.init(jax.random.PRNGKey(3), {"input_ids": ids})
    f = lambda p: m.loss_fn(p, {"input_ids": ids})
    want_loss, want = jax.value_and_grad(f)(params)
    monkeypatch.setattr(placement, "kernel_path", lambda fits=True, has_specs=True: "kernel")  # the one rule, steered: its kernels are interpreted here
    before = regions_traced("mixer/kernel", op="sparse", path="kernel")
    got_loss, got = jax.value_and_grad(f)(params)
    assert regions_traced("mixer/kernel", op="sparse", path="kernel") - before == 5  # index, fwd, loss, bwd, index_bwd: one block trace
    _close(got_loss, want_loss, 1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        _close(a, b, 1e-5)


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold (a call a use); a Pallas call's body is not entered."""
    for eqn in jaxpr.eqns:
        inner = [] if eqn.primitive.name == "pallas_call" else [
            getattr(x, "jaxpr", x) for value in eqn.params.values() for x in (value if isinstance(value, (tuple, list)) else (value,))
            if hasattr(getattr(x, "jaxpr", x), "eqns")]
        if not inner:
            yield eqn
        for sub in inner:
            yield from _equations(sub)


def test_no_square_array_of_the_index_loss_is_made_outside_a_kernel(highest, monkeypatch):
    """THE MECHANISM: in the traced training step of the model on the kernels' path, blocks checkpointed, every value as
    large as the square of the sequence comes out of a Pallas call, but for what reads the int8 choice alone: its tiles
    (a reshape and a transpose) and the count of chosen pairs. So no XLA pass makes or walks the target, the KL term or
    the gradient in the scores. A layer makes ONE ``index_loss`` call, in the forward (its gradient is kept, so the
    backward makes no second), and the call site is counted as ``pass="loss"`` once a block trace."""
    layers, seq = 2, 256
    m = CausalLM(tiny(max_seq_len=seq, index_topk=48, remat=True, n_layers=layers))
    ids = np.random.default_rng(2).integers(0, 97, (1, seq)).astype(np.int32)
    params = m.init(jax.random.PRNGKey(3), {"input_ids": ids})
    monkeypatch.setattr(placement, "kernel_path", lambda fits=True, has_specs=True: "kernel")
    counted = lambda: regions_traced("mixer/kernel", op="sparse", path="kernel", **{"pass": "loss"})
    before, probs = counted(), regions_traced("mixer/kernel", op="sparse", **{"pass": "probs"})
    eqns = list(_equations(jax.make_jaxpr(jax.value_and_grad(lambda p: m.loss_fn(p, {"input_ids": ids})))(params).jaxpr))
    assert counted() == before + 1 and regions_traced("mixer/kernel", op="sparse", **{"pass": "probs"}) == probs == 0
    calls = [eqn.params["name"] for eqn in eqns if eqn.primitive.name == "pallas_call"]
    for name, n in {"index_scores": 1, "index_select": 1, "sparse_fwd": 1, "index_loss": 1, "sparse_bwd": 1, "index_scores_bwd": 1, "sparse_probs": 0}.items():
        assert calls.count(name) == n * layers, (name, calls)
    square = {(eqn.primitive.name, str(v.aval.dtype), tuple(str(x.aval.dtype) for x in eqn.invars if hasattr(x.aval, "shape") and x.aval.size == seq * seq))
              for eqn in eqns if eqn.primitive.name not in ("pallas_call", "name", "stop_gradient")  # the two last lower to nothing
              for v in eqn.outvars if getattr(v.aval, "size", 0) == seq * seq}
    assert square == {("reshape", "int8", ("int8",)), ("transpose", "int8", ("int8",)), ("convert_element_type", "float32", ("int8",))}, square
    monkeypatch.setattr(placement, "kernel_path", lambda fits=True, has_specs=True: "xla")  # the test of the test: XLA's form makes its squares in the open
    eqns = list(_equations(jax.make_jaxpr(lambda p: m.loss_fn(p, {"input_ids": ids}))(params).jaxpr))
    assert len({eqn.primitive.name for eqn in eqns for v in eqn.outvars if getattr(v.aval, "shape", ())[-2:] == (seq, seq) and v.aval.dtype == jnp.float32}) > 5


def test_a_checkpointed_sparse_block_keeps_its_names():
    from deepspeed_tpu.models.transformer import remat_keeps

    assert remat_keeps(("sparse", "dense")) == ("sparse_attention", "flash_attention", "projection")
    assert remat_keeps(("gdn", "routed")) == ("kda_scan", "projection", "routed_ffn") and remat_keeps(("full", "routed")) == ("flash_attention", "projection", "routed_ffn")
    assert remat_keeps(("full", "dense")) == ("flash_attention",)  # a plain block: its kernel's outputs, no projection


def test_what_the_mixer_counts(model):
    """Regions ``mixer/index``, ``mixer/select``, ``mixer/index_loss`` and the attention under ``mixer/kernel``; the one
    counter's ``op="sparse"`` series; the three device counts, an output of the traced loss."""
    m, params, ids = model
    reg = get_registry()
    before = {p: regions_traced("mixer/kernel", op="sparse", path="xla", **{"pass": p}) for p in ("index", "fwd", "loss")}
    select = regions_traced("mixer/select", path="xla")
    with device_counts.collecting() as reported:
        text = jax.jit(lambda p: m.loss_fn(p, {"input_ids": ids})).lower(params).as_text(debug_info=True)
        assert set(reported) == {"sparse_keys"}
    for name in ("mixer/index", "mixer/select", "mixer/index_loss", "mixer/kernel"):
        assert name in text  # the scope reaches the lowered operations' names
    assert all(regions_traced("mixer/kernel", op="sparse", path="xla", **{"pass": p}) == n + 1 for p, n in before.items())  # one block trace
    assert regions_traced("mixer/select", path="xla") == select + 1
    with device_counts.collecting() as reported:
        m.loss_fn(params, {"input_ids": ids})
    was = [reg.peek(name) or 0.0 for name in ("sparse_keys_chosen_total", "sparse_keys_visible_total")]
    device_counts.count(reported)
    chosen, visible = (reg.peek(name) - before for name, before in zip(("sparse_keys_chosen_total", "sparse_keys_visible_total"), was))
    assert (chosen, visible) == (2 * chosen_pairs(64, 16, 2), 2 * 2 * 64 * 65 / 2) and chosen / visible == pytest.approx(904 / 2080)
    assert 0.01 < reg.peek("sparse_index_loss") < 5.0


def test_the_kernels_choice_says_which_share_of_the_rows_it_counts(highest, monkeypatch):
    """``mixer/select`` on the kernels' path carries ``counted``: at 512 positions, 160 keys a query and bands of 128 the
    three bands past the first search, over the 2, 3 and 4 chunks of 128 rows (or the one of 512) at or below their
    diagonals, of the 4 x 4 (4 x 1) the bands hold (9 of 16; 3 of 4). XLA's path has no such label and counts as before."""
    scores = _scores(512)
    rows = kernel.chunk_for(512)
    by_hand = f"{sum(-(-128 * (i + 1) // rows) for i in (1, 2, 3)) * rows / (4 * 512):.3f}"
    assert by_hand == {128: "0.562", 256: "0.625", 512: "0.750"}[rows]
    reg = get_registry()
    series = lambda **labels: reg.peek("program_regions_traced_total", region="mixer/select", **labels) or 0.0
    kernels, xla, counted = regions_traced("mixer/select", path="kernel"), regions_traced("mixer/select", path="xla"), series(path="kernel", counted=by_hand)
    got = ops.select_keys(scores, 160, path="kernel")
    assert series(path="kernel", counted=by_hand) == counted + 1 and regions_traced("mixer/select", path="kernel") == kernels + 1
    assert regions_traced("mixer/select", path="xla") == xla == series(path="xla")
    assert int(jnp.sum(got != ops.select_keys(scores, 160, path="xla"))) == 0
    assert regions_traced("mixer/select", path="xla") == xla + 1 == series(path="xla") and regions_traced("mixer/select", path="kernel") == kernels + 1
    assert regions_traced("mixer/select") == kernels + xla + 2  # the sum over the series, whatever their labels


def test_the_first_call_line_says_which_path_the_attention_took(tmp_path):
    """A sparse model has one kind of layer: its trainer's line still says ``layer_kinds`` and ``sparse_path``, and
    ``index_strip`` where its kernels ran (the strip their loops over heads walk a tile in, as the call sites counted it)."""
    import deepspeed_tpu
    from deepspeed_tpu.parallel.mesh import initialize_mesh, reset_mesh
    from deepspeed_tpu.runtime.config import MeshConfig

    reset_mesh()
    topo = initialize_mesh(MeshConfig.from_dict({"data": 1}), devices=jax.devices()[:1], force=True)
    m = CausalLM(tiny(remat=True))
    ids = np.random.default_rng(3).integers(0, 97, (1, 64)).astype(np.int32)
    params = m.init(jax.random.PRNGKey(0), {"input_ids": ids})
    engine, _, _, _ = deepspeed_tpu.initialize(model=m, model_parameters=params, mesh=topo, config={
        "train_micro_batch_size_per_gpu": 1, "optimizer": {"type": "adam", "params": {"lr": 1e-3}}, "zero_optimization": {"stage": 0},
        "mesh": {"data": 1}, "steps_per_print": 10**9})
    from deepspeed_tpu.runtime.engine import _paths_traced

    blocks, paths_before = regions_traced("block", site="train"), _paths_traced()
    loss = engine.forward({"input_ids": ids})
    engine.backward(loss)
    engine.step()
    assert regions_traced("block", site="train") == blocks + 1  # one block trace for both layers
    notes = engine._layer_kind_notes(paths_before)
    assert notes["layer_kinds"] == "sparse+dense:2" and notes["sparse_path"] == "xla" and "sparse_attention" in notes["remat_keeps"]
    assert "index_strip" not in notes  # XLA's forms walk nothing
    before = _paths_traced()  # ... and where the call sites took the kernels, the strip they counted since: ``index_strip=512x128``
    for pass_ in ("index", "loss"):
        placement.count("sparse", "kernel", pass_, index_strip="512x128")
    assert engine._layer_kind_notes(before)["index_strip"] == "512x128"
    reset_mesh()


# ---------------------------------------------------------------------- the start of the output projection
@pytest.mark.parametrize("scale", [1.0, 0.25, 0.02])
def test_the_output_projection_starts_at_the_scale_asked_for(scale):
    """``sparse_out_init_scale`` times flax's own start for ``o_proj`` (1: the same numbers), and no other leaf moves."""
    cfg = TransformerConfig(vocab_size=64, n_layers=1, n_heads=4, n_kv_heads=2, head_dims=8, d_model=32, max_seq_len=16, pos_emb="rope",
                            norm="rmsnorm", activation="swiglu", layer_kinds=(("sparse", "dense"),), index_heads=2, index_head_dim=8,
                            index_topk=8, tie_embeddings=False)
    start = lambda cfg: CausalLM(cfg).init(jax.random.PRNGKey(3), {"input_ids": np.zeros((1, 16), np.int32)})
    usual, asked = start(cfg), start(dataclasses.replace(cfg, sparse_out_init_scale=scale))
    flat = lambda tree: {jax.tree_util.keystr(path): leaf for path, leaf in jax.tree_util.tree_leaves_with_path(tree)}
    for name, leaf in flat(usual).items():
        np.testing.assert_allclose(flat(asked)[name], leaf * (scale if "o_proj" in name else 1.0), rtol=1e-6, atol=0)
    import flax.linen as nn

    own = nn.DenseGeneral(32, axis=(-2, -1), use_bias=False, param_dtype=jnp.float32).init(jax.random.PRNGKey(0), jnp.zeros((1, 4, 8)))
    ours = nn.DenseGeneral(32, axis=(-2, -1), use_bias=False, param_dtype=jnp.float32,
                           kernel_init=nn.initializers.variance_scaling(1.0, "fan_in", "truncated_normal")).init(jax.random.PRNGKey(0), jnp.zeros((1, 4, 8)))
    np.testing.assert_array_equal(own["params"]["kernel"], ours["params"]["kernel"])


@pytest.mark.parametrize("scale,even", [(1.0, False), (0.02, True)])
def test_a_small_start_of_the_output_projection_keeps_the_routers_load_even(scale, even):
    """Why the Keye-VL cell starts ``o_proj`` small: at the usual random start attention averages its keys, every
    position gets nearly the same vector, the stream collapses onto it layer by layer and a random router sends a share
    here that the seed decides (0.004 to 2.7 times the uniform 512 pairs over these layers); with the attention's part
    small the stream stays the tokens' own and every layer of every seed stays within 0.35 of uniform."""
    seq = 512
    cfg = TransformerConfig(vocab_size=1024, n_layers=4, n_heads=4, n_kv_heads=2, head_dims=32, d_model=256, max_seq_len=seq, pos_emb="rope",
                            norm="rmsnorm", activation="swiglu", qk_norm=True, layer_kinds=(("sparse", "routed"),) * 4, index_heads=2,
                            index_head_dim=16, index_topk=seq, tie_embeddings=False, moe_num_experts=32, moe_top_k=4, moe_d_ff=64,
                            moe_held=(0, 8), moe_scoring="softmax", moe_aux_loss_coef=0.0, sparse_out_init_scale=scale)
    model, furthest = CausalLM(cfg), 0.0
    for seed in range(3):
        params = model.init(jax.random.PRNGKey(seed), {"input_ids": np.zeros((1, seq), np.int32)})
        ids = np.random.default_rng(seed).integers(0, 1024, (1, seq)).astype(np.int32)
        _, mods = model.module.apply({"params": params}, ids, return_hidden=True, mutable=("losses", "intermediates"))
        here = [int(np.asarray(leaf).reshape(-1)[0]) for path, leaf in jax.tree_util.tree_leaves_with_path(mods["intermediates"])
                if any(getattr(k, "key", None) == "rows" for k in path)]
        assert len(here) == 4
        furthest = max(furthest, max(abs(n / (seq * 4 * 8 / 32) - 1.0) for n in here))
    assert (furthest <= 0.35) if even else (furthest >= 0.9), furthest


# ---------------------------------------------------------------------- the share, and the older cells' calls
def test_eight_shares_add_up_to_the_whole_routed_layer(highest):
    """THE SHARE TEST: 32 experts, 8 a token by softmax, renormalised, no shared expert: eight chips each hold 4 and
    give their experts' part; the parts add up to the uncut layer. What every chip of the group computes alike (the
    attention over the indexer's choice, the norms, the router) is one program on the same weights, counted once."""
    from deepspeed_tpu.moe.layer import RoutedMoE

    layer = lambda held: RoutedMoE(hidden_size=48, num_experts=32, k=8, d_ff=24, held=held, shared_ff=0, scale=1.0, scoring="softmax", dtype=jnp.float32)
    h = jax.random.normal(jax.random.PRNGKey(4), (2, 24, 48))
    whole = layer(None).init(jax.random.PRNGKey(5), h)["params"]
    whole = {k: v + 0.05 * jax.random.normal(jax.random.PRNGKey(i), jnp.shape(v)) if not isinstance(v, dict) else v for i, (k, v) in enumerate(whole.items())}
    share = lambda first: {k: (v[first:first + 4] if k.startswith("experts_") else v) for k, v in whole.items()}
    parts = sum(layer((first, 4)).apply({"params": share(first)}, h) for first in range(0, 32, 4))
    _close(parts, layer(None).apply({"params": whole}, h), 1e-5)


@pytest.mark.parametrize("heads,kv_heads,head_dim,seq,calls", [
    (16, 16, 128, 2048, ["flash_fwd", "flash_bwd"]),        # OLMo-1B's attention: the fused backward
    (16, 2, 256, 8192, ["flash_fwd", "flash_bwd"]),         # Qwen3-Next's: a head at a time on copies of its KV head, one call still
])
def test_the_older_cells_attention_lowers_to_the_calls_it_lowered_to(heads, kv_heads, head_dim, seq, calls):
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    q = jax.ShapeDtypeStruct((1, seq, heads, head_dim), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, seq, kv_heads, head_dim), jnp.bfloat16)

    def fwd_bwd(q, k, v, do):
        o, vjp = jax.vjp(lambda q, k, v: flash_attention(q, k, v, causal=True, interpret=True), q, k, v)
        return (o,) + vjp(do)

    text = str(jax.make_jaxpr(fwd_bwd)(q, kv, kv, q))
    assert re.findall(r"name=(flash_(?:fwd|bwd|dq|dkv)|sparse_\w+|index_\w+)", text) == calls
    # the forward a (head, q block) grid; the backward (KV head, q heads a KV head, kv block): Qwen3-Next's group of 8 does
    # not fit VMEM at 8,192 positions, so every q head gets a copy of its KV head and the grid is a head at a time
    assert re.findall(r"grid=\(([0-9, ]+)\)", text) == [f"{heads}, {seq // 512}", f"{heads}, 1, {seq // 512}"]
