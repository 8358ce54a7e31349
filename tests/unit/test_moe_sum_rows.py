"""A routed layer's tokens sum only the rows routed here (``ops/pallas/moe_sum_rows.py``): the kernel, interpreted on the
CPU, against the gather form it replaces, through ``held_experts`` (forward value and every gradient), and over an
exchange's slabs (a group every ``slab`` rows: ``exchanged_experts``' sender), laid out by hand and through the call."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.moe.sharded_moe import SAVED, buffer_rungs, exchanged_experts, held_experts, routed_part
from deepspeed_tpu.ops.pallas import moe_sum_rows
from deepspeed_tpu.telemetry.registry import get_registry

N, D, F, E, HELD, FIRST = 512, 256, 128, 16, 4, 2  # two token tiles; experts 2..5 of 16 held


@pytest.fixture(scope="module", autouse=True)
def _compiled_programs_dropped():
    """These tests compile some hundred large CPU programs (conditionals whose branches hold interpreted kernels). A
    process that had run ``test_moe_router_indexing.py`` and ``test_regions.py`` before them died inside XLA's CPU
    compiler (a segmentation fault in ``backend_compile_and_load``, at whichever test came next once enough was
    compiled; not with what the process held dropped first), so it is dropped before this module and after it."""
    jax.clear_caches()
    yield
    jax.clear_caches()


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


def _value_and_grads(idx, rows, kernel, operands, cot, part=held_experts, experts=4 * E):
    """Output and the five gradients, pairs routed here, pairs dropped and whatever else ``part`` counts: ONE compiled
    program a call (run op by op, a conditional whose branches hold interpreted kernels is a large program of its own
    for its value and another for its gradient)."""
    call = (lambda *a: held_experts(a[0], idx, *a[1:], FIRST, rows, kernel)) if part is held_experts else \
        (lambda *a: part(a[0], idx, *a[1:], FIRST, experts, kernel))

    @jax.jit
    def both(*a):
        out, back, counts = jax.vjp(lambda *b: (lambda o, *c: (o, c))(*call(*b)), *a, has_aux=True)
        return (out,) + back(cot), counts

    got, (routed, dropped, *rest) = both(*operands)
    return got, int(routed), int(dropped), *(int(x) for x in rest[2:])


def _same(got, want):
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5 * float(jnp.max(jnp.abs(b)) + 1e-6))


CHIPS, PER, SLAB = 3, 2, 384  # an exchange's sender by hand: experts 2..7 lie two a chip on three chips, a slab of 384 slots each


def _slabs(idx, k):
    """What ``exchanged_experts`` lays out before it sends: the pairs sorted by the chip that holds the expert (stable),
    chip ``c``'s from slot ``c * SLAB`` on and cut at its slab. Every slot holds numbers, its token's index too, the
    empty ones whatever they like (here: random rows, weights and tokens of the tiles the kernel walks).
    Returns (each pair's chip, the rows, their tokens, their weights, which slots are filled, every token's sum)."""
    rng = np.random.default_rng(k)
    local = np.asarray(idx) - FIRST
    dest = np.where((local >= 0) & (local < CHIPS * PER), local // PER, CHIPS).reshape(-1).astype(np.int32)
    order = np.argsort(dest, kind="stable")
    rows, w_row = rng.standard_normal((CHIPS * SLAB, D)).astype(np.float32), rng.uniform(size=CHIPS * SLAB).astype(np.float32)
    tok_of_row, filled = rng.integers(0, N, CHIPS * SLAB).astype(np.int32), np.zeros(CHIPS * SLAB, bool)
    for c in range(CHIPS):
        before, count = int(np.sum(dest < c)), int(np.sum(dest == c))
        kept = slice(c * SLAB, c * SLAB + min(count, SLAB))
        tok_of_row[kept], filled[kept] = order[before:before + min(count, SLAB)] // k, True
    want = np.zeros((N, D), np.float32)
    np.add.at(want, tok_of_row[filled], w_row[filled, None] * rows[filled])
    return dest, rows, tok_of_row, w_row, filled, want


@pytest.mark.parametrize("k", [6, 8])
@pytest.mark.parametrize("rows", ["usual", "every", "slabs"])
@pytest.mark.parametrize("routing", list(ROUTINGS))
def test_the_tiled_sum_is_the_gathered_sum(routing, rows, k):
    """Forward value and the gradients to tokens, weights and the three expert matrices, the kernel's path against the
    gathers', at a buffer that just holds the usual load and at the one that holds every pair (the fallback branch's).
    ``slabs``: the kernel over groups that begin at multiples of a slab (``_slabs``) against each token's sum of its
    filled slots. The four routings there: three chips near their slab, some cut at it (``uniform``); the first chip's
    512 rows cut at 384, a tile's 256 past its first window of 128, and two rows a token for the third chip, cut too
    (``one_held_expert``, ``none_here``, whose experts 6 and 7 the third chip holds); 586 rows cut at 384 with every
    seventh token twice in the first chip's, ONE row in the second's and an empty third (``unequal``)."""
    idx = ROUTINGS[routing](k).astype(jnp.int32)
    if rows == "slabs":
        dest, slots, tok_of_row, w_row, filled, want = _slabs(idx, k)
        assert (routing != "unequal" or not filled[2 * SLAB:].any()) and (routing == "unequal" or filled[2 * SLAB:].sum() > 256)
        spans = moe_sum_rows.spans(jnp.asarray(dest), CHIPS, k, CHIPS * SLAB, SLAB)
        return _same([moe_sum_rows.sum_rows(jnp.asarray(slots), jnp.asarray(tok_of_row), jnp.asarray(w_row), spans, N, interpret=True)], [want])
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


K_LADDER = 8


def _held_by(held, every=1, k=K_LADDER):
    """``k`` experts a token: the first ``held`` of the held experts 2..5, then experts held elsewhere; with ``every``
    above 1 only every ``every``-th token takes its last held one."""
    picks = jnp.broadcast_to(jnp.array([2, 3, 4, 5][:held] + list(range(9, 9 + k - held)), jnp.int32), (N, k))
    return picks.at[:, held - 1].set(jnp.where(jnp.arange(N) % every == 0, picks[:, held - 1], 9 + k))


ONE_HELD, ALL_HELD = _one_held_expert(6).astype(jnp.int32), _held_by(4, k=6)
# 4 of 48 held at 8 a token: 4,096 pairs of which a uniform router sends 341 here. The ladder (``buffer_rungs``): twice
# that rounded up to 512 rows, four times, every pair; none of them N, so a leading dimension says which buffer an array is of
LADDER, FIRST_RUNG, FOUR, EVERY = 3 * E, 1024, 1536, N * K_LADDER
# a crafted routing a rung: (idx, the buffer that holds it, pairs routed here, the rung)
ON_RUNG = {"under_first": (_held_by(1), FIRST_RUNG, 512, 0), "first_to_its_last_row": (_held_by(2), FIRST_RUNG, 1024, 0),
           "between_first_and_four": (_held_by(3, every=2), FOUR, 1280, 1), "four_to_its_last_row": (_held_by(3), FOUR, 1536, 1),
           "above_four": (_held_by(4), EVERY, 2048, 2)}


def test_the_ladder_is_twice_and_four_times_the_uniform_load_in_rows_of_512_under_every_pair():
    """``buffer_rungs``: where a rung rounds past every pair it IS every pair (the small shapes of every rehearsal), and
    the two lower rungs may round to the same buffer (a share of 1/32 at 1,024 pairs)."""
    assert buffer_rungs(N * K_LADDER, HELD, LADDER) == (FIRST_RUNG, FOUR, EVERY)
    assert buffer_rungs(N * 6, HELD, 4 * E) == (512, 1024, N * 6) and buffer_rungs(N * 6, HELD, E) == (1536, N * 6, N * 6)
    assert buffer_rungs(N * 6, HELD, 2 * E) == (1024, 1536, N * 6)
    assert buffer_rungs(1024, 2, 64) == (512, 512, 1024) and buffer_rungs(96 * 4, 4, 16) == (96 * 4,) * 3
    # the benchmark's cells: SDAR's 16 of 128 at 2 x 8,192 x 8 pairs, SmallThinker's and Kimi-VL's 8 of 64, Keye's 16 of 128,
    # Qwen3-Next's 32 of 512, Kimi-Linear's 8 of 256
    assert buffer_rungs(2 * 8192 * 8, 16, 128) == (32768, 65536, 131072) and buffer_rungs(16384 * 6, 8, 64) == (24576, 49152, 98304)
    assert buffer_rungs(8192 * 6, 8, 64) == (12288, 24576, 49152) and buffer_rungs(8192 * 8, 16, 128) == (16384, 32768, 65536)
    assert buffer_rungs(8192 * 10, 32, 512) == (10240, 20480, 81920) and buffer_rungs(8192 * 8, 8, 256) == (4096, 8192, 65536)


@pytest.mark.parametrize("rung", list(ON_RUNG))
def test_every_rung_of_the_ladder_takes_the_kernel(rung):
    """``routed_part`` on each rung (``ON_RUNG``): same numbers as the gathers', no pair dropped."""
    idx, _, want_routed, _ = ON_RUNG[rung]
    operands, cot = _operands(K_LADDER, seed=2)
    got, routed, dropped, _ = _value_and_grads(idx, None, True, operands, cot, part=routed_part, experts=LADDER)
    want, *_ = _value_and_grads(idx, None, False, operands, cot, part=routed_part, experts=LADDER)
    assert (routed, dropped) == (want_routed, 0)
    _same(got, want)


def _plain_branches(ladder):
    """``routed_part`` with every branch differentiated by its ``lax.cond`` (unnamed above the first rung, so a
    checkpointed block keeps none of it): as it was before its fallback kept nothing (``ladder`` false: the first rung,
    else every pair), and the ladder as one would write it first (a conditional a rung). What the pin below must tell
    from today's."""
    def part(tokens, idx, weights, wg, wi, wo, first, num_experts, kernel):
        usual, four, every = buffer_rungs(idx.size, wg.shape[0], num_experts)
        run = lambda rows, named: lambda: held_experts(tokens, idx, weights, wg, wi, wo, first, rows, kernel, named)
        local = idx - first
        routed = jnp.sum((local >= 0) & (local < wg.shape[0]))
        above = (lambda: jax.lax.cond(routed <= four, run(four, False), run(every, False))) if ladder else run(every, False)
        return jax.lax.cond(routed <= usual, run(usual, True), above)

    return part


_TWO_PLAIN, _LADDER_PLAIN = _plain_branches(False), _plain_branches(True)


def _rows_out_of_conds(jaxpr, seen):
    """The leading dimension of every output of every ``cond`` equation, at any depth."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "cond":
            seen.update(v.aval.shape[0] for v in eqn.outvars if getattr(v.aval, "shape", ()))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _rows_out_of_conds(sub, seen)
    return seen


def _checkpointed(part, idx, kernel, cot, experts=LADDER):
    """The loss of ``part`` under the block's policy: what is named is kept, the rest made again in the backward."""
    return jax.checkpoint(lambda *a: jnp.sum(part(a[0], idx, *a[1:], FIRST, experts, kernel)[0] * cot),
                          policy=jax.checkpoint_policies.save_only_these_names(SAVED))


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "kernel"])
def test_the_conditional_hands_on_nothing_of_a_larger_rungs_row_count(kernel):
    """A ``lax.cond`` under differentiation returns the residuals of BOTH branches, the branch not taken writing zeros
    for the other's. The fallback keeps nothing but its operands (``_every_pair``) and chooses its own buffer inside
    its rule, forward and backward, so in the gradient of a checkpointed ``routed_part`` no conditional returns an
    array of the 1,536 rows of four times the uniform load or of every pair's 3,072: the first rung, whose 1,024 rows
    are kept, has none to write zeros for. With the branches plain (the old two, or a conditional a rung) the
    recomputed conditional returns the larger rungs' sorted rows and products at those sizes, which is what this test
    would see again if the rule were lost."""
    operands, cot = _operands(K_LADDER)
    rows = {part: _rows_out_of_conds(jax.make_jaxpr(jax.grad(_checkpointed(part, ON_RUNG["under_first"][0], kernel, cot), argnums=(0, 1, 2, 3, 4)))(*operands).jaxpr, set())
            for part in (routed_part, _TWO_PLAIN, _LADDER_PLAIN)}
    assert FIRST_RUNG in rows[routed_part] and not {FOUR, EVERY} & rows[routed_part]
    assert {FIRST_RUNG, EVERY} <= rows[_TWO_PLAIN] and {FIRST_RUNG, FOUR, EVERY} <= rows[_LADDER_PLAIN]


def _kept(jaxpr, seen):
    """The shape of every value a ``jax.checkpoint`` names for keeping (``name`` equations), at any depth."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "name":
            seen.update(tuple(v.aval.shape) for v in eqn.outvars)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _kept(sub, seen)
    return seen


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "kernel"])
def test_a_checkpointed_block_keeps_arrays_of_the_first_rungs_rows_alone(kernel):
    """What carries the block's name in a traced ``routed_part``: the first rung's sorted rows, its three products and
    its order, each of 1,024 rows; nothing of the rungs above it, which are traced (their ``held_experts`` unnamed)
    but keep nothing."""
    operands, cot = _operands(K_LADDER)
    jaxpr = jax.make_jaxpr(jax.grad(_checkpointed(routed_part, ON_RUNG["under_first"][0], kernel, cot), argnums=(0, 1, 2, 3, 4)))(*operands).jaxpr
    leading = {shape[0] for shape in _kept(jaxpr, set()) if shape}
    assert {(FIRST_RUNG, D), (FIRST_RUNG, F)} <= _kept(jaxpr, set()) and not {FOUR, EVERY} & leading
    assert {FOUR, EVERY} <= {shape[0] for shape in _shapes(jaxpr, set()) if shape}  # they are in the program all the same


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "kernel"])
@pytest.mark.parametrize("rung", list(ON_RUNG))
def test_every_rung_is_held_experts_at_its_buffer_differentiated_directly(rung, kernel):
    """A crafted routing a rung (``ON_RUNG``: under the first, the first full to its last row, between the first and
    four times, that one full to its last row, above it): output and the five gradients are those of
    ``held_experts(..., rows=the rung's)`` differentiated with no conditional and no rule around it, and so those of
    ``held_experts`` with every pair, though above the first rung the fallback kept nothing and made its forward again
    in its backward. Plainly and under the block's checkpoint policy; no pair dropped on any rung, and the sixth value
    says which was taken."""
    idx, rows, pairs, taken = ON_RUNG[rung]
    operands, cot = _operands(K_LADDER, seed=3)
    want, routed, _ = _value_and_grads(idx, rows, kernel, operands, cot)
    got, routed_here, dropped, rung_taken = _value_and_grads(idx, None, kernel, operands, cot, part=routed_part, experts=LADDER)
    assert (routed, routed_here, dropped, rung_taken) == (pairs, pairs, 0, taken)
    _same(got, want)
    if rows != EVERY:
        _same(got, _value_and_grads(idx, EVERY, kernel, operands, cot)[0])
    _same(jax.jit(jax.grad(_checkpointed(routed_part, idx, kernel, cot), argnums=(0, 1, 2, 3, 4)))(*operands), want[1:])


@pytest.mark.parametrize("experts,idx,rows,taken", [(4 * E, ALL_HELD, N * 6, 2), (4 * E, _held_by(2, k=6), 1024, 1), (4 * E, ONE_HELD, 512, 0),
                                                    (2 * E, _held_by(3, every=2, k=6), 1536, 1), (2 * E, _held_by(2, k=6), 1024, 0),
                                                    (E, ALL_HELD, N * 6, 1), (E, ONE_HELD, 1536, 0)],
                         ids=["1/16_every", "1/16_four", "1/16_first", "1/8_four", "1/8_first", "1/4_four_is_every", "1/4_first"])
def test_the_ladder_at_other_shares_of_the_experts(experts, idx, rows, taken):
    """Six a token. 4 of 64 held (what these tests pinned while the buffer was four times the uniform load: 1,024 rows,
    then every pair; now 512 before them); 4 of 32, the benchmark's share of an eighth (1,024, 1,536 and 3,072 rows);
    4 of 16, where four times the uniform load is every pair and the fallback has one buffer. The rung taken and
    ``held_experts`` at its buffer, plainly and checkpointed."""
    operands, cot = _operands(6, seed=4)
    want, routed, _ = _value_and_grads(idx, rows, False, operands, cot)
    got, routed_here, dropped, rung_taken = _value_and_grads(idx, None, False, operands, cot, part=routed_part, experts=experts)
    assert (routed_here, dropped, rung_taken) == (routed, 0, taken)
    _same(got, want)
    _same(jax.jit(jax.grad(_checkpointed(routed_part, idx, False, cot, experts), argnums=(0, 1, 2, 3, 4)))(*operands), want[1:])


def _shapes(jaxpr, seen):
    for eqn in jaxpr.eqns:
        seen.update(tuple(v.aval.shape) for v in eqn.outvars if hasattr(v.aval, "shape"))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _shapes(sub, seen)
    return seen


@pytest.mark.parametrize("part", ["held_experts", "exchanged_experts"])
def test_the_kernels_path_builds_no_tokens_by_k_by_d_array(part):
    """The usual branch, forward and backward: with the kernel no equation's result is (N, k, d); with the gathers two are.
    ``exchanged_experts``: each of four virtual devices its own N tokens, one held expert a chip and slabs of 256 slots
    (the receiving side's ``held_experts`` has 1,024 tokens of ONE pair each: no shape of the sender's)."""
    k, idx = 6, _uniform(6).astype(jnp.int32)
    operands, cot = _operands(k)
    if part == "held_experts":
        loss = lambda kernel: lambda *a: jnp.sum(held_experts(a[0], idx, *a[1:], FIRST, 1024, kernel)[0] * cot)
    else:
        from jax.sharding import PartitionSpec as P

        mesh = jax.sharding.Mesh(np.array(jax.devices()[:4]), ("fsdp",))
        own, whole = P(), P("fsdp")  # every chip the same tokens and its own expert of the four
        each = lambda kernel: jax.shard_map(lambda t, w, *held: exchanged_experts(t, idx, w, *held, FIRST, 1024, kernel, slab=256)[0], mesh=mesh,
                                            in_specs=(own, own, whole, whole, whole), out_specs=own, check_vma=False)
        loss = lambda kernel: lambda *a: jnp.sum(each(kernel)(*a) * cot)
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
    that has a conditional (one whatever the rungs above the first; none where the first rung holds every pair), and the trainer's first-call line reads it
    as ``moe_cond``."""
    from deepspeed_tpu.runtime import engine

    shapes = [jax.ShapeDtypeStruct(s, jnp.float32) for s in ((N, D), (N, 6), (HELD, D, F), (HELD, D, F), (HELD, F, D))]
    rose = []
    for experts in (4 * E, E, HELD):  # 4 of 64: a first rung of 512 under 3,072 pairs; 4 of 16: 1,536; 4 of 4: it is every pair
        before = engine._paths_traced()["moe_cond"]
        jax.eval_shape(lambda *a: routed_part(a[0], ONE_HELD, *a[1:], FIRST, experts, False), *shapes)
        rose.append(tuple(now - was for now, was in zip(engine._paths_traced()["moe_cond"], before)))
    assert rose == [(1, 0), (1, 0), (0, 0)] and engine._PATH_WORDS["moe_cond"] == "fallback_keeps_nothing"


@pytest.mark.parametrize("routing", list(ROUTINGS))
def test_spans_of_slabs_are_where_each_tile_and_chip_lies_from_its_slabs_first_slot(routing):
    """``spans`` told a slab: a (tile, chip)'s rows are the filled slots of that chip's slab whose tokens are the
    tile's, one run, whatever the other chips hold; an empty chip's spans are empty AT its slab, a full one's end with it."""
    k, idx = 6, ROUTINGS[routing](6)
    dest, _, tok_of_row, _, filled, _ = _slabs(idx, k)
    lo, hi = np.asarray(moe_sum_rows.spans(jnp.asarray(dest), CHIPS, k, CHIPS * SLAB, SLAB)).reshape(2, N // moe_sum_rows.TOKENS, CHIPS)
    for tile in range(N // moe_sum_rows.TOKENS):
        for c in range(CHIPS):
            mine = [r for r in range(c * SLAB, (c + 1) * SLAB) if filled[r] and tok_of_row[r] // moe_sum_rows.TOKENS == tile]
            assert hi[tile, c] - lo[tile, c] == len(mine) and c * SLAB <= lo[tile, c] <= hi[tile, c] <= (c + 1) * SLAB
            assert not mine or (lo[tile, c], hi[tile, c]) == (mine[0], mine[-1] + 1)
    if routing == "unequal":  # the first chip's 586 rows cut at its slab, the second's one row (the last token's), the third's none
        assert (lo[:, 0].tolist(), hi[:, 0].tolist(), lo[1, 1], hi[1, 1], lo[:, 2].tolist(), hi[:, 2].tolist()) == ([0, 293], [293, 384], 384, 385, [768, 768], [768, 768])


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
