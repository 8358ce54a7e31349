"""A routed layer's tokens sum only the rows routed here (``ops/pallas/moe_sum_rows.py``): the kernel, interpreted on the
CPU, against the gather form it replaces, through ``held_experts`` (forward value and every gradient)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.moe.sharded_moe import SAVED, held_experts, routed_part
from deepspeed_tpu.ops.pallas import moe_sum_rows
from deepspeed_tpu.telemetry.registry import get_registry

N, D, F, E, HELD, FIRST = 512, 256, 128, 16, 4, 2  # two token tiles; experts 2..5 of 16 held


def _uniform(k):
    return jax.lax.top_k(jax.random.uniform(jax.random.PRNGKey(k), (N, E)), k)[1]


def _one_held_expert(k):
    """Every token picks held expert 3 first and no other held one: a tile's span is all its 256 rows, so it passes its
    first window of 128 and the rest is taken a window at a time."""
    return jnp.concatenate([jnp.full((N, 1), 3), FIRST + HELD + jnp.broadcast_to(jnp.arange(k - 1), (N, k - 1))], axis=1)


def _none_here(k):
    return jnp.broadcast_to(FIRST + HELD + jnp.arange(k), (N, k))


def _unequal(k):
    """Expert 2 from every token, expert 3 from every seventh, expert 4 from the last token alone, expert 5 from none."""
    t = jnp.arange(N)[:, None]
    rest = FIRST + HELD + jnp.broadcast_to(jnp.arange(k), (N, k))
    picks = jnp.concatenate([jnp.full((N, 1), 2), jnp.where(t % 7 == 0, 3, 0), jnp.where(t == N - 1, 4, 1)], axis=1)
    return jnp.concatenate([picks, rest[:, 3:]], axis=1)


ROUTINGS = {"uniform": _uniform, "one_held_expert": _one_held_expert, "none_here": _none_here, "unequal": _unequal}


def _operands(k, seed=0):
    key = jax.random.PRNGKey(seed)
    tokens = jax.random.normal(key, (N, D))
    weights = jax.random.uniform(jax.random.fold_in(key, 1), (N, k))
    wg, wi, wo = (0.1 * jax.random.normal(jax.random.fold_in(key, 2 + i), s) for i, s in enumerate(((HELD, D, F), (HELD, D, F), (HELD, F, D))))
    return (tokens, weights, wg, wi, wo), jax.random.normal(jax.random.fold_in(key, 9), (N, D))


def _value_and_grads(idx, rows, kernel, operands, cot, part=held_experts):
    call = (lambda *a: held_experts(a[0], idx, *a[1:], FIRST, rows, kernel)) if part is held_experts else \
        (lambda *a: routed_part(a[0], idx, *a[1:], FIRST, 4 * E, kernel))
    out, routed, dropped, *_ = call(*operands)
    grads = jax.grad(lambda *a: jnp.sum(call(*a)[0] * cot), argnums=(0, 1, 2, 3, 4))(*operands)
    return (out,) + grads, int(routed), int(dropped)


def _same(got, want):
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5 * float(jnp.max(jnp.abs(b)) + 1e-6))


@pytest.mark.parametrize("k", [6, 8])
@pytest.mark.parametrize("rows", ["usual", "every"])
@pytest.mark.parametrize("routing", list(ROUTINGS))
def test_the_tiled_sum_is_the_gathered_sum(routing, rows, k):
    """Forward value and the gradients to tokens, weights and the three expert matrices, the kernel's path against the
    gathers', at a buffer that just holds the usual load and at the one that holds every pair (the fallback branch's)."""
    idx = ROUTINGS[routing](k).astype(jnp.int32)
    rows = N * k if rows == "every" else N * 3  # 1,536 hold the 1,024 or so that 8 a token send to 4 of 16
    operands, cot = _operands(k)
    got, routed, dropped = _value_and_grads(idx, rows, True, operands, cot)
    want, routed_xla, _ = _value_and_grads(idx, rows, False, operands, cot)
    assert (routed, dropped) == (routed_xla, 0) and (routed == 0) == (routing == "none_here")
    _same(got, want)
    if routing == "none_here":
        assert not any(np.asarray(x).any() for x in got[:2])  # no row read: zeros out, zeros back


def test_a_buffer_that_drops_rows_sums_the_taken_ones_only():
    """``rows`` smaller than the pairs routed here (never ``routed_part``'s doing): both paths leave the same pairs out.
    Value and the gradients to tokens and weights: a grouped product whose groups pass its rows is no one's contract."""
    idx = _one_held_expert(6).astype(jnp.int32)
    operands, cot = _operands(6, seed=1)
    got, routed, dropped = _value_and_grads(idx, 384, True, operands, cot)
    want, *_ = _value_and_grads(idx, 384, False, operands, cot)
    assert (routed, dropped) == (512, 128)
    _same(got[:3], want[:3])
    assert not np.asarray(got[0][384:]).any() and np.asarray(got[0][:384]).any()


def test_both_branches_of_the_cond_take_the_kernel():
    """``routed_part``: 4 of 64 held at 6 a token gives a usual buffer of 1,024 rows; every token picking one held
    expert is 512 pairs (the usual branch), every token picking all four is 2,048 (the branch that holds every pair).
    Same numbers as the gathers' either way."""
    for idx, want_routed in ((_one_held_expert(6), 512), (jnp.broadcast_to(jnp.array([2, 3, 4, 5, 9, 10]), (N, 6)), 2048)):
        operands, cot = _operands(6, seed=2)
        got, routed, dropped = _value_and_grads(idx.astype(jnp.int32), None, True, operands, cot, part=routed_part)
        want, *_ = _value_and_grads(idx.astype(jnp.int32), None, False, operands, cot, part=routed_part)
        assert (routed, dropped) == (want_routed, 0)
        _same(got, want)


ONE_HELD, ALL_HELD = _one_held_expert(6).astype(jnp.int32), jnp.broadcast_to(jnp.array([2, 3, 4, 5, 9, 10], jnp.int32), (N, 6))
EVERY, USUAL = N * 6, 1024  # 4 of 64 held at 6 a token: 3,072 pairs, a usual buffer of 1,024 rows


def _both_branches_plain(tokens, idx, weights, wg, wi, wo, first, num_experts, kernel):
    """``routed_part`` as it was while its fallback was a plain branch (unnamed, so a checkpointed block kept none of
    it, but differentiated by the ``lax.cond`` like the other): what the pin below must tell from today's."""
    n, every = wg.shape[0], idx.size
    usual = min(every, -(-4 * every * n // num_experts // 512) * 512)
    run = lambda rows, named: lambda: held_experts(tokens, idx, weights, wg, wi, wo, first, rows, kernel, named)
    local = idx - first
    return jax.lax.cond(jnp.sum((local >= 0) & (local < n)) <= usual, run(usual, True), run(every, False))


def _rows_out_of_conds(jaxpr, seen):
    """The leading dimension of every output of every ``cond`` equation, at any depth."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "cond":
            seen.update(v.aval.shape[0] for v in eqn.outvars if getattr(v.aval, "shape", ()))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _rows_out_of_conds(sub, seen)
    return seen


def _checkpointed(part, idx, kernel, cot):
    """The loss of ``part`` under the block's policy: what is named is kept, the rest made again in the backward."""
    return jax.checkpoint(lambda *a: jnp.sum(part(a[0], idx, *a[1:], FIRST, 4 * E, kernel)[0] * cot),
                          policy=jax.checkpoint_policies.save_only_these_names(SAVED))


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "kernel"])
def test_the_conditional_hands_on_nothing_of_every_pairs_row_count(kernel):
    """A ``lax.cond`` under differentiation returns the residuals of BOTH branches, the branch not taken writing zeros
    for the other's. The fallback keeps nothing but its operands (``_every_pair``), so in the gradient of a
    checkpointed ``routed_part`` no conditional returns an array of every pair's 3,072 rows: the usual branch has none
    to write zeros for. With both branches plain the recomputed conditional returns the fallback's sorted rows and
    products at that size, which is what this test would see again if the rule were lost."""
    operands, cot = _operands(6)
    rows = {part: _rows_out_of_conds(jax.make_jaxpr(jax.grad(_checkpointed(part, ONE_HELD, kernel, cot), argnums=(0, 1, 2, 3, 4)))(*operands).jaxpr, set())
            for part in (routed_part, _both_branches_plain)}
    assert USUAL in rows[routed_part] and EVERY not in rows[routed_part]
    assert {USUAL, EVERY} <= rows[_both_branches_plain]


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "kernel"])
@pytest.mark.parametrize("idx,rows,pairs,fallback", [(ALL_HELD, EVERY, 2048, 1), (ONE_HELD, USUAL, 512, 0)], ids=["every_pair", "usual"])
def test_either_branch_is_held_experts_at_its_buffer_differentiated_directly(idx, rows, pairs, fallback, kernel):
    """Every token picking all four held experts is 2,048 pairs, past the usual buffer: output and the five gradients
    are those of ``held_experts(..., rows=every)`` differentiated with no conditional and no rule around it, though
    the fallback kept nothing and made its forward again in its backward; one held expert a token is 512 pairs, and
    they are the usual buffer's. Plainly and under the block's checkpoint policy; no pair dropped either way."""
    operands, cot = _operands(6, seed=3)
    want, routed, _ = _value_and_grads(idx, rows, kernel, operands, cot)
    got, routed_here, dropped = _value_and_grads(idx, None, kernel, operands, cot, part=routed_part)
    assert (routed, routed_here, dropped) == (pairs, pairs, 0)
    assert int(routed_part(operands[0], idx, *operands[1:], FIRST, 4 * E, kernel)[5]) == fallback
    _same(got, want)
    _same(jax.grad(_checkpointed(routed_part, idx, kernel, cot), argnums=(0, 1, 2, 3, 4))(*operands), want[1:])


def _shapes(jaxpr, seen):
    for eqn in jaxpr.eqns:
        seen.update(tuple(v.aval.shape) for v in eqn.outvars if hasattr(v.aval, "shape"))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _shapes(sub, seen)
    return seen


def test_the_kernels_path_builds_no_tokens_by_k_by_d_array():
    """The usual branch, forward and backward: with the kernel no equation's result is (N, k, d); with the gathers two are."""
    k, idx = 6, _uniform(6).astype(jnp.int32)
    operands, cot = _operands(k)
    loss = lambda kernel: lambda *a: jnp.sum(held_experts(a[0], idx, *a[1:], FIRST, 1024, kernel)[0] * cot)
    shapes = {kernel: _shapes(jax.make_jaxpr(jax.grad(loss(kernel), argnums=(0, 1)))(*operands).jaxpr, set()) for kernel in (True, False)}
    assert (N, k, D) in shapes[False] and (N, k, D) not in shapes[True]
    assert (moe_sum_rows.TOKENS, D) in shapes[True]  # the kernel's own accumulator, inside its ``pallas_call``


@pytest.mark.parametrize("n_tokens,d,kernel,path", [(N, D, True, "kernel"), (N, D, False, "xla"), (N - 128, D, True, "xla"), (N, D - 64, True, "xla")])
def test_the_choice_is_counted_where_it_is_made(n_tokens, d, kernel, path):
    """``program_regions_traced_total{region="ffn/rows", path}``: the kernel where it is asked for and the shapes fit it
    (whole tiles of 256 tokens, whole lanes), else the gathers; one count a traced ``held_experts``."""
    reg = get_registry()
    before = {p: reg.peek("program_regions_traced_total", region="ffn/rows", path=p) or 0 for p in ("kernel", "xla")}
    shapes = [jax.ShapeDtypeStruct(s, jnp.float32) for s in ((n_tokens, d), (n_tokens, 6), (HELD, d, 128), (HELD, d, 128), (HELD, 128, d))]
    idx = jnp.zeros((n_tokens, 6), jnp.int32)
    jax.eval_shape(lambda *a: held_experts(a[0], idx, *a[1:], FIRST, 1024, kernel), *shapes)
    rose = {p: (reg.peek("program_regions_traced_total", region="ffn/rows", path=p) or 0) - before[p] for p in before}
    assert rose == {"kernel": float(path == "kernel"), "xla": float(path == "xla")}


def test_the_conditionals_form_is_counted_once_a_trace_and_has_its_word_for_the_first_call_line():
    """``program_regions_traced_total{region="ffn/cond", path="fallback_keeps_nothing"}``: one a traced ``routed_part``
    that has a conditional (none where the usual buffer holds every pair), and the trainer's first-call line reads it
    as ``moe_cond``."""
    from deepspeed_tpu.runtime import engine

    shapes = [jax.ShapeDtypeStruct(s, jnp.float32) for s in ((N, D), (N, 6), (HELD, D, F), (HELD, D, F), (HELD, F, D))]
    rose = []
    for experts in (4 * E, E):  # 4 of 64: a buffer of 1,024 under 3,072 pairs; 4 of 16: the usual buffer is every pair
        before = engine._paths_traced()["moe_cond"]
        jax.eval_shape(lambda *a: routed_part(a[0], ONE_HELD, *a[1:], FIRST, experts, False), *shapes)
        rose.append(tuple(now - was for now, was in zip(engine._paths_traced()["moe_cond"], before)))
    assert rose == [(1, 0), (0, 0)] and engine._PATH_WORDS["moe_cond"] == "fallback_keeps_nothing"


def test_spans_are_where_each_tile_and_expert_lies_in_the_sorted_buffer():
    k, idx = 6, _unequal(6)
    local = idx - FIRST
    key = jnp.where((local >= 0) & (local < HELD), local, HELD).reshape(-1)
    order = np.argsort(np.asarray(key), kind="stable")
    lo, hi = np.asarray(moe_sum_rows.spans(key, HELD, k, 600)).reshape(2, N // moe_sum_rows.TOKENS, HELD)
    for tile in range(N // moe_sum_rows.TOKENS):
        for e in range(HELD):
            mine = [r for r in range(600) if key[order[r]] == e and order[r] // k // moe_sum_rows.TOKENS == tile]
            assert (hi[tile, e] - lo[tile, e] == len(mine)) and (not mine or (lo[tile, e], hi[tile, e]) == (mine[0], mine[-1] + 1))
    assert hi.max() == 587 and moe_sum_rows.spans(key, HELD, k, 300).max() == 300  # cut at the buffer
