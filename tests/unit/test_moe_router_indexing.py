"""The router indexes the EXPERT axis by comparison (``moe/sharded_moe.py::_chosen``, ``_rows_a_group``): the chosen
scores and the rows-a-group counts are a compare against an iota and a sum, so neither the router's gradient nor
``held_experts``' bookkeeping holds a ``gather``, a ``scatter`` or a ``scatter-add``, which the chip runs a scalar at a
time. What stays, and is allowed by name below: the sorts of the pairs (``ffn/router``), the gathers whose operand
or result is ``rows`` long (``ffn/rows``: a row's token, a row's weight, a pair's row) and the Pallas grouped matmul's
own group metadata (``ffn/experts``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from deepspeed_tpu.moe.sharded_moe import _renormalised, _rows_a_group, held_experts, routed_part, sigmoid_topk, softmax_topk
from tests.unit.test_moe_sum_rows import FIRST, HELD, ROUTINGS, E, N, _operands
from tests.unit.test_remat_keeps import _equations

INDEXING = {"gather", "scatter", "scatter-add"}
K = 6


def _indexing(jaxpr, scope=""):
    """The (primitive, name stack) of every indexing equation of ``jaxpr`` whose name stack holds ``scope``."""
    return [(eqn.primitive.name, stack) for eqn, stack in _equations(jaxpr) if eqn.primitive.name in INDEXING and scope in stack]


@pytest.mark.parametrize("scoring", ["softmax", "sigmoid"])
def test_a_routers_value_and_gradient_hold_no_gather_and_no_scatter(scoring):
    logits, w = jax.random.normal(jax.random.PRNGKey(0), (N, E)), jax.random.normal(jax.random.PRNGKey(1), (N, K))
    bias = 0.3 * jax.random.normal(jax.random.PRNGKey(2), (E,))
    ours = (lambda x: softmax_topk(x, K, 1.0)[1]) if scoring == "softmax" else (lambda x: sigmoid_topk(x, bias, K, 2.446)[1])
    grad = jax.make_jaxpr(jax.value_and_grad(lambda x: jnp.sum(ours(x) * w)))(logits)
    assert not _indexing(grad.jaxpr) and "top_k" in {eqn.primitive.name for eqn, _ in _equations(grad.jaxpr)}
    # what the check looks for is there to be found: the gather this replaced, and its transpose
    score = (lambda x: jax.nn.softmax(x, axis=-1)) if scoring == "softmax" else jax.nn.sigmoid
    gathered = lambda x: _renormalised(jnp.take_along_axis(score(x), jax.lax.top_k(score(x), K)[1], axis=-1), 1.0)
    found = {name for name, _ in _indexing(jax.make_jaxpr(jax.grad(lambda x: jnp.sum(gathered(x) * w)))(logits).jaxpr)}
    assert {"gather", "scatter-add"} <= found, found


@pytest.mark.parametrize("part", ["held_experts, gathered rows", "held_experts, tiled rows", "routed_part, both branches"])
def test_the_routed_parts_bookkeeping_indexes_by_comparison(part):
    """Forward and backward of the routed part: nothing under ``ffn/router`` indexes; the sorts are there; every
    indexing equation of the whole lies under ``ffn/rows`` or, with the kernels (interpreted off the TPU), in the
    grouped matmul's own tile bookkeeping under ``ffn/experts`` (``megablox.gmm``'s group metadata)."""
    idx, (operands, _) = ROUTINGS["uniform"](K), _operands(K)
    if part.startswith("held_experts"):
        call = lambda *a: held_experts(a[0], idx, *a[1:], FIRST, N * K, part.endswith("tiled rows"))[0]
    else:
        call = lambda *a: routed_part(a[0], idx, *a[1:], FIRST, 4 * E, False)[0]
    jaxpr = jax.make_jaxpr(jax.value_and_grad(lambda *a: jnp.sum(call(*a) ** 2), argnums=(0, 1, 2, 3, 4)))(*operands).jaxpr
    router = [eqn.primitive.name for eqn, stack in _equations(jaxpr) if "ffn/router" in stack]
    assert router.count("sort") >= 2 and not _indexing(jaxpr, "ffn/router"), _indexing(jaxpr, "ffn/router")
    outside = [(name, stack) for name, stack in _indexing(jaxpr) if "ffn/rows" not in stack and "ffn/experts" not in stack]
    assert _indexing(jaxpr, "ffn/rows") and not outside, outside
    # the counts this replaced are what the check finds
    assert "scatter-add" in {name for name, _ in _indexing(jax.make_jaxpr(lambda k: jnp.bincount(k, length=HELD + 1))(jnp.zeros(N * K, jnp.int32)).jaxpr)}


def _some_held(seed):
    return jax.random.randint(jax.random.PRNGKey(seed), (N * K,), 0, HELD + 1)  # HELD: a pair not held here


KEYS = {
    "some pairs held, some not": lambda: _some_held(0),
    "an expert with no rows": lambda: jnp.where(_some_held(1) == 2, HELD, _some_held(1)),
    "no pair held at all": lambda: jnp.full((N * K,), HELD),
    "every pair on one expert": lambda: jnp.full((N * K,), 3),
    "one pair": lambda: jnp.full((N * K,), HELD).at[77].set(1),
}


@pytest.mark.parametrize("case", list(KEYS))
def test_the_rows_a_group_are_bincounts(case):
    key = KEYS[case]().astype(jnp.int32)
    want = np.asarray(jnp.bincount(key, length=HELD + 1)[:HELD])
    for got in (_rows_a_group(key, HELD), jax.jit(_rows_a_group, static_argnums=1)(key, HELD)):
        assert got.dtype == jnp.int32 and got.shape == (HELD,) and np.array_equal(np.asarray(got), want)
    assert {"no pair held at all": want.sum() == 0, "an expert with no rows": want[2] == 0 and want.sum() > 0}.get(case, True)


@pytest.mark.parametrize("routing", list(ROUTINGS))
def test_held_experts_reports_the_bincounts_sum_largest_and_smallest(routing):
    """Through ``held_experts`` itself, where the counts size the grouped products: pairs routed here, the largest and
    the smallest group are ``bincount``'s over the held experts, and none is dropped by a buffer of every pair."""
    idx, (operands, _) = ROUTINGS[routing](K), _operands(K)
    _, routed, dropped, largest, smallest = held_experts(operands[0], idx, *operands[1:], FIRST, N * K, False)
    want = np.bincount(np.asarray(idx).reshape(-1), minlength=E)[FIRST:FIRST + HELD]
    assert (int(routed), int(dropped), int(largest), int(smallest)) == (want.sum(), 0, want.max(), want.min())


@pytest.mark.parametrize("axis", [2, 4])
def test_the_rows_a_group_under_an_expert_axis_are_each_chips_own_experts(axis):
    """On ``axis`` virtual devices, each holding ``E / axis`` experts and seeing every pair (``moe/layer.py::
    _over_expert_axis``): a chip's counts are the bincount's entries of its own experts."""
    idx = jax.lax.top_k(jax.random.uniform(jax.random.PRNGKey(5), (N, E)), K)[1]
    n = E // axis

    def local(idx):
        mine = idx - jax.lax.axis_index("expert") * n
        return _rows_a_group(jnp.where((mine >= 0) & (mine < n), mine, n).reshape(-1), n)

    mesh = Mesh(np.array(jax.devices()[:axis]), ("expert",))
    got = jax.jit(jax.shard_map(local, mesh=mesh, in_specs=P(), out_specs=P("expert")))(idx)
    assert np.array_equal(np.asarray(got), np.bincount(np.asarray(idx).reshape(-1), minlength=E))


def test_the_form_is_counted_once_a_traced_held_experts_and_is_a_word_of_the_first_call_line():
    """``program_regions_traced_total{region="ffn/router", path="compare_sum"}``: one a traced ``held_experts``, two a
    traced ``routed_part`` with a conditional (its usual branch and the one that holds every pair); the trainer's
    first-call line joins it to the scoring as ``moe_router`` (``runtime/engine.py::_ROUTER_WORDS``)."""
    from deepspeed_tpu.runtime import engine

    rose = lambda before: engine._paths_traced()["moe_router"][engine._ROUTER_WORDS.index("compare_sum")] - before
    idx, (operands, _) = ROUTINGS["uniform"](K), _operands(K)
    shapes = [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in operands]
    before = rose(0)
    jax.eval_shape(lambda *a: held_experts(a[0], idx, *a[1:], FIRST, N * K, False), *shapes)
    assert rose(before) == 1
    jax.eval_shape(lambda *a: routed_part(a[0], idx, *a[1:], FIRST, 4 * E, False), *shapes)
    assert rose(before) == 3
