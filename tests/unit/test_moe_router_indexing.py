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
from tests.unit.test_moe_sum_rows import FIRST, HELD, K_LADDER, LADDER, ON_RUNG, ROUTINGS, E, N, _operands
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


@pytest.mark.parametrize("part", ["held_experts, gathered rows", "held_experts, tiled rows", "routed_part, both branches", "routed_part, three rungs"])
def test_the_routed_parts_bookkeeping_indexes_by_comparison(part):
    """Forward and backward of the routed part: nothing under ``ffn/router`` indexes; the sorts are there; every
    indexing equation of the whole lies under ``ffn/rows`` or, with the kernels (interpreted off the TPU), in the
    grouped matmul's own tile bookkeeping under ``ffn/experts`` (``megablox.gmm``'s group metadata)."""
    idx, (operands, _) = ROUTINGS["uniform"](K), _operands(K)
    if part.startswith("held_experts"):
        call = lambda *a: held_experts(a[0], idx, *a[1:], FIRST, N * K, part.endswith("tiled rows"))[0]
    else:
        # 4 of 16: four times the uniform load is every pair, the fallback has one buffer; 4 of 64: it chooses between two
        call = lambda *a: routed_part(a[0], idx, *a[1:], FIRST, 4 * E if part.endswith("three rungs") else E, False)[0]
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
    """``program_regions_traced_total{region="ffn/router", path="compare_sum"}``: one a traced ``held_experts``; a traced
    ``routed_part`` with a conditional counts its first rung each time and the rungs above it once a shape for the
    process (the fallback's arms are jitted: a second kind of block with a routed layer of the same shapes traces the
    first rung alone); the trainer's first-call line joins it to the scoring as ``moe_router``
    (``runtime/engine.py::_ROUTER_WORDS``)."""
    from deepspeed_tpu.runtime import engine

    rose = lambda before: engine._paths_traced()["moe_router"][engine._ROUTER_WORDS.index("compare_sum")] - before
    idx = ROUTINGS["uniform"](K)
    d = 3 * 128  # a width no other test of the process traces the fallback's arms at
    shapes = [jax.ShapeDtypeStruct(s, jnp.float32) for s in ((N, d), (N, K), (HELD, d, 128), (HELD, d, 128), (HELD, 128, d))]
    before = rose(0)
    jax.eval_shape(lambda *a: held_experts(a[0], idx, *a[1:], FIRST, N * K, False), *shapes)
    assert rose(before) == 1
    jax.eval_shape(lambda *a: routed_part(a[0], idx, *a[1:], FIRST, 4 * E, False), *shapes)
    assert rose(before) == 4  # 4 of 64: the first rung, and the arms at four times the uniform load and at every pair
    jax.eval_shape(lambda *a: routed_part(a[0], idx, *a[1:], FIRST, 4 * E, False), *shapes)
    assert rose(before) == 5  # the first rung again; the arms are traced already
    jax.eval_shape(lambda *a: routed_part(a[0], idx, *a[1:], FIRST, E, False), *shapes)
    assert rose(before) == 6  # 4 of 16: four times the uniform load is every pair, whose arm is traced already


@pytest.mark.parametrize("axis", [1, 2], ids=["one_device", "expert_axis_of_2"])
@pytest.mark.parametrize("rung", list(ON_RUNG))
def test_the_rung_a_layer_took_and_its_load_reach_the_registry(rung, axis):
    """What a routed layer sows as ``rows`` for a crafted routing a rung (``tests/unit/test_moe_sum_rows.py::ON_RUNG``),
    handed out of a jitted program and counted on the host: ``moe_buffer_rung_layers_total{rung}`` rises by one for the
    rung that was crafted and by none for the others, ``moe_fallback_layers_total`` by one above the first rung
    whichever it was, ``moe_rows_over_uniform_max`` is the pairs routed here over the 341.3 of a uniform router, and no
    row is dropped. Under an ``expert`` axis each chip holds two of the four experts and has its own ladder (512, 1,024,
    4,096 rows against a uniform 170.7): the pair counts once, at the rung and the load of its fullest chip."""
    from deepspeed_tpu.moe.layer import _over_expert_axis, report_rows
    from deepspeed_tpu.moe.sharded_moe import RUNGS
    from deepspeed_tpu.parallel.mesh import initialize_mesh, reset_mesh
    from deepspeed_tpu.runtime.config import MeshConfig
    from deepspeed_tpu.telemetry import device_counts
    from deepspeed_tpu.telemetry.registry import get_registry

    idx, _, pairs, taken = ON_RUNG[rung]
    operands, _ = _operands(K_LADDER, seed=5)
    # a chip's experts 2, 3 or 4, 5: the fuller one has every token's pair for each of its experts that the routing names
    fullest = 512 if rung == "under_first" else 1024
    taken, load = (taken, pairs * 3 / 1024) if axis == 1 else (int(fullest > 512), fullest * 3 / 512)

    def counted(*a):
        with device_counts.collecting() as reported:
            out, *counts = _over_expert_axis(a[0], idx, *a[1:], FIRST, LADDER, False)
            report_rows({"layer": {"rows": (jnp.stack(counts).astype(jnp.int32),)}})
        return out, reported

    reg = get_registry()
    read = lambda: [reg.peek("moe_buffer_rung_layers_total", rung=r) or 0.0 for r in RUNGS] + \
        [reg.peek(n) or 0.0 for n in ("moe_fallback_layers_total", "moe_rows_dropped_total", "moe_rows_routed_here_total")]
    before = read()
    reset_mesh()
    try:
        if axis > 1:
            topo = initialize_mesh(MeshConfig.from_dict({"expert": axis}), devices=jax.devices()[:axis], force=True)
            with topo.mesh:
                out, reported = jax.jit(counted)(*operands)
        else:
            out, reported = jax.jit(counted)(*operands)
    finally:
        reset_mesh()
    device_counts.count(reported)
    rose = [now - was for now, was in zip(read(), before)]
    # left as found: a benchmark reader that is handed no counter falls back to the process's totals (``tests/unit/test_hybrid_layers.py``)
    for name, found in zip(("moe_fallback_layers_total", "moe_rows_dropped_total", "moe_rows_routed_here_total"), before[3:]):
        reg.counter(name).value = found
    assert rose == [float(taken == r) for r in range(3)] + [float(taken > 0), 0.0, float(pairs)]
    assert reg.peek("moe_rows_over_uniform_max") == pytest.approx(load, abs=1e-3)
    want = held_experts(operands[0], idx, *operands[1:], FIRST, N * K_LADDER, False)[0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5 * float(jnp.max(jnp.abs(want))))
