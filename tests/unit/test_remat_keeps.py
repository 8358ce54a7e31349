"""What a checkpointed block keeps (``models/transformer.py::remat_keeps``, ``block_fn``'s policy): a hybrid block's
backward makes no product over or onto the model width, no top-k, no sort and no sum of the router's chosen scores a
second time, and gives the gradients of the same block without a checkpoint; a ``full``/``dense`` block under plain ``jax.checkpoint`` keeps its inputs alone. Tiny
widths, float32, CPU; ``d_model`` (48) is the width of nothing else in these configurations."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.transformer import SAVED, Block, block_fn, remat_keeps
from tests.unit.test_deltanet_layers import tiny as tiny_next
from tests.unit.test_hybrid_layers import tiny, tiny_vl

# (the kind of block, its configuration): Kimi-Linear's three kinds, Qwen3-Next's two (the attention with its output
# gate, q/k norms and a quarter of each head rotated; a softmax router, a gated shared expert), Kimi-VL's two with the
# rotation and the same without
CASES = {
    "kda+dense": (("kda", "dense"), tiny),
    "kda+routed": (("kda", "routed"), tiny),
    "gdn+routed": (("gdn", "routed"), tiny_next),
    "full+routed, output gate": (("full", "routed"), tiny_next),
    "mla+dense": (("mla", "dense"), tiny),
    "mla+routed": (("mla", "routed"), tiny),
    "mla+dense, rotated": (("mla", "dense"), tiny_vl),
    "mla+routed, rotated": (("mla", "routed"), tiny_vl),
}
B, S = 2, 64


def _block(kind, cfg, remat):
    """(loss of (parameters, activations), parameters, activations) of one block of ``kind`` through ``block_fn``."""
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, cfg.d_model))
    params = Block(cfg, kind).init(jax.random.PRNGKey(2), x, positions)["params"]
    leaves, tree = jax.tree_util.tree_flatten(params)  # off their start: a norm weight of one or a bias of zero hides its gradient's path
    params = jax.tree_util.tree_unflatten(tree, [p + 0.05 * jax.random.normal(jax.random.PRNGKey(7 + i), p.shape) for i, p in enumerate(leaves)])
    w = jax.random.normal(jax.random.PRNGKey(3), x.shape)
    fn = block_fn(cfg, kind, True, remat)
    return (lambda p, x: jnp.sum(fn(p, x, positions, None, None, {})[0][0] * w)), params, x


def _equations(jaxpr, stack=""):
    """(equation, the name stack under its enclosing equations') of every equation, sub-jaxprs too."""
    for eqn in jaxpr.eqns:
        here = f"{stack}/{eqn.source_info.name_stack}"
        yield eqn, here
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (tuple, list)) else (value,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _equations(sub, here)


def _reads_or_writes_the_width(eqn, d_model):
    """A product over the model width (a projection of the block's activations) or onto it (an output projection)."""
    (lhs_c, rhs_c), (_, rhs_b) = eqn.params["dimension_numbers"]
    lhs, rhs = (v.aval.shape for v in eqn.invars)
    return int(np.prod([lhs[i] for i in lhs_c])) == d_model or d_model in [n for i, n in enumerate(rhs) if i not in (*rhs_c, *rhs_b)]


def _sums_chosen_scores(eqn, cfg):
    """The router's chosen scores as ``moe/sharded_moe.py::_chosen`` makes them: a sum over the expert axis of a
    (tokens, chosen a token, experts) array."""
    return eqn.primitive.name == "reduce_sum" and eqn.invars[0].aval.shape[1:] == (cfg.moe_top_k, cfg.moe_num_experts)


def _made_again(loss, params, x, cfg):
    """Of the gradient's equations that lie in a rematted computation: (products over or onto the model width; top-ks,
    sorts and sums of the chosen scores; all of them)."""
    made = [eqn for eqn, stack in _equations(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(params, x).jaxpr) if "rematted_computation" in stack]
    wide = [eqn for eqn in made if eqn.primitive.name == "dot_general" and _reads_or_writes_the_width(eqn, cfg.d_model)]
    return wide, [eqn for eqn in made if eqn.primitive.name in ("top_k", "sort") or _sums_chosen_scores(eqn, cfg)], made


@pytest.mark.parametrize("case", list(CASES))
def test_a_hybrid_blocks_backward_makes_no_product_over_the_model_width_again(case):
    kind, make = CASES[case]
    cfg = make()
    assert cfg.d_model == 48 and SAVED in remat_keeps(kind)
    loss, params, x = _block(kind, cfg, remat=True)
    wide, chosen, made = _made_again(loss, params, x, cfg)
    assert made and not wide and not chosen, ([str(e) for e in wide + chosen], len(made))
    if kind[1] == "routed":  # what the check looks for is there to be found: the first forward sums the chosen scores, once
        assert sum(_sums_chosen_scores(eqn, cfg) for eqn, _ in _equations(jax.make_jaxpr(loss)(params, x).jaxpr)) == 1
    # the counter-example: the same block under a policy without the projections' name makes them again
    plain, _, _ = _block(kind, cfg, remat=False)
    without = lambda name: jax.checkpoint(plain, policy=jax.checkpoint_policies.save_only_these_names(*(set(remat_keeps(kind)) - {name})))
    wide, _, _ = _made_again(without(SAVED), params, x, cfg)
    assert len(wide) >= 2
    if kind[1] == "routed":  # and without the routed layer's name: the top-k and the sum of the chosen scores
        from deepspeed_tpu.moe.sharded_moe import SAVED as ROUTED

        _, chosen, _ = _made_again(without(ROUTED), params, x, cfg)
        assert {"top_k", "sort"} <= {eqn.primitive.name for eqn in chosen} and any(_sums_chosen_scores(eqn, cfg) for eqn in chosen)
    # the same operations on the same values, kept where they were made again: loss and gradients to the last bit. (A
    # block with a delta-rule scan: to float32's rounding. Its kernel runs interpreted here, and XLA's CPU compiler
    # fuses the interpreter's arithmetic differently in the two programs: the LOSS, which no checkpoint touches, differs
    # in its last bit already)
    got, want = (jax.jit(jax.value_and_grad(f, argnums=(0, 1)))(params, x) for f in (loss, plain))
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        a, b = np.asarray(a), np.asarray(b)
        assert np.isfinite(a).all() and (np.max(np.abs(a - b)) <= 2e-6 * np.max(np.abs(b)) if kind[0] in ("kda", "gdn") else np.array_equal(a, b))
    assert all(float(jnp.max(jnp.abs(g))) > 0 for g in jax.tree_util.tree_leaves(got[1]) if g.shape != (cfg.moe_num_experts,))  # select_bias takes none


def test_a_full_dense_block_under_plain_checkpoint_keeps_what_it_kept():
    """The names act only under ``save_only_these_names``: a ``full``/``dense`` block has no policy, keeps its inputs
    and nothing made inside it, and makes its projections again."""
    from jax._src.ad_checkpoint import saved_residuals  # what print_saved_residuals prints, as a list

    kind, cfg = ("full", "dense"), tiny_next(n_layers=1, layer_kinds=(("full", "dense"),), moe_num_experts=0, d_ff=64)
    assert remat_keeps(kind) == () and remat_keeps(("window", "moe")) == ()
    loss, params, x = _block(kind, cfg, remat=True)
    kept = saved_residuals(loss, params, x)
    assert kept and all(why.startswith(("from the argument", "from a constant")) for _, why in kept), kept
    wide, _, _ = _made_again(loss, params, x, cfg)
    assert len(wide) == 6  # q, k, v, o (the FFN half starts from its sum with the input), gate, up
    plain, _, _ = _block(kind, cfg, remat=False)
    assert not _made_again(plain, params, x, cfg)[2]


# (scoring, experts, chosen a token, whether ``select_bias`` is non-zero): the two small cases this test began with, then
# the four routed cells' own: Qwen3-Next 512/10 and Keye-VL 128/8 (softmax), Kimi-Linear 256/8 and Kimi-VL 64/6 (sigmoid)
ROUTERS = [("sigmoid", 16, 4, False), ("softmax", 16, 4, False), ("softmax", 512, 10, False), ("softmax", 128, 8, False),
           ("sigmoid", 256, 8, True), ("sigmoid", 64, 6, True)]


@pytest.mark.parametrize("scoring,E,k,biased", ROUTERS, ids=[f"{s}-{E}-{k}" + ("-biased" if b else "") for s, E, k, b in ROUTERS])
def test_a_routers_choice_has_lax_top_ks_values_and_gradient(scoring, E, k, biased):
    """Both routers take their chosen scores through ``moe/sharded_moe.py::_chosen``: the top-k's indices, and the
    scores there as a compare against an iota over the expert axis and a sum (both named; no gather, so no scatter-add
    in the backward). To the last bit, the weights and their gradient are those of the gather it replaces
    (``take_along_axis`` and its transpose) and, where no ``select_bias`` moves the ranking off the scores, of
    ``lax.top_k``'s own values."""
    from deepspeed_tpu.moe.sharded_moe import _renormalised, sigmoid_topk, softmax_topk

    logits = jax.random.normal(jax.random.PRNGKey(0), (96, E))
    w = jax.random.normal(jax.random.PRNGKey(1), (96, k))
    bias = 0.3 * jax.random.normal(jax.random.PRNGKey(2), (E,)) if biased else jnp.zeros(E)
    score = jax.nn.sigmoid if scoring == "sigmoid" else (lambda x: jax.nn.softmax(x, axis=-1))
    ours = (lambda x: sigmoid_topk(x, bias, k, 2.5)) if scoring == "sigmoid" else (lambda x: softmax_topk(x, k, 2.5))
    ranked = lambda x: jax.lax.top_k(score(x) + bias, k)
    gathered = lambda x: _renormalised(jnp.take_along_axis(score(x), ranked(x)[1], axis=-1), 2.5)
    plains = [gathered] + ([] if biased else [lambda x: _renormalised(ranked(x)[0], 2.5)])
    idx, weights = ours(logits)
    assert np.array_equal(np.asarray(idx), np.asarray(ranked(logits)[1]))
    assert not biased or not np.array_equal(np.asarray(idx), np.asarray(jax.lax.top_k(logits, k)[1]))  # the bias did choose
    got = jax.grad(lambda x: jnp.sum(ours(x)[1] * w))(logits)
    assert float(jnp.max(jnp.abs(got))) > 0
    for plain in plains:
        assert np.array_equal(np.asarray(weights), np.asarray(plain(logits)))
        assert np.array_equal(np.asarray(got), np.asarray(jax.grad(lambda x: jnp.sum(plain(x) * w))(logits)))


def test_the_first_call_line_says_inputs_where_the_layers_are_scanned():
    """``remat_keeps`` on the trainer's first-call line is the policy the program's blocks had: scanned layers go through
    ``nn.remat(Block)``, which has no policy and keeps a block's inputs alone, whatever the kind."""
    import types

    from deepspeed_tpu.runtime.engine import DeepSpeedEngine, _paths_traced

    notes = lambda **over: DeepSpeedEngine._layer_kind_notes(
        types.SimpleNamespace(module=types.SimpleNamespace(cfg=tiny(n_layers=2, layer_kinds=(("mla", "dense"),) * 2, **over))), _paths_traced())
    assert notes(remat=True) == {"remat_keeps": "flash_attention+projection"}
    assert notes(remat=True, scan_layers=True) == {"remat_keeps": "inputs"}
    assert notes() == {}
