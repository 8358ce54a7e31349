"""What a checkpointed block keeps (``models/transformer.py::remat_keeps``, ``remat_policy``): a hybrid block's
backward makes no product over or onto the model width, no top-k, no sort and no sum of the router's chosen scores a
second time, and gives the gradients of the same block without a checkpoint; a plain block (``full``, ``window`` or
``nope`` beside ``dense`` or ``moe``) keeps its inputs and the flash call's output and row statistics, runs ONE forward
kernel call and makes its products again, unrolled, looped or stacked. Tiny widths, float32, CPU; ``d_model`` (48) is
the width of nothing else in these configurations."""

import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import CausalLM, TransformerConfig, layers
from deepspeed_tpu.models.transformer import SAVED, Block, block_fn, remat_keeps
from deepspeed_tpu.ops.pallas.flash_attention import SAVED as FLASH_SAVED, flash_attention
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


PLAIN = [("full", "dense"), ("window", "dense"), ("nope", "dense"), ("full", "moe")]  # neither part is hybrid


def _on_the_flash_kernels(monkeypatch):
    """Softmax attention's layers call the flash kernels, interpreted: the path the chip takes."""
    monkeypatch.setattr(layers, "attention", lambda q, k, v, **kw: flash_attention(q, k, v, interpret=True, **kw))


def _kernel_calls(jaxpr):
    """How often a jaxpr holds each Pallas call, by the call's name, sub-jaxprs too."""
    return collections.Counter(eqn.params["name"] for eqn, _ in _equations(jaxpr) if eqn.primitive.name == "pallas_call")


@pytest.mark.parametrize("kind", PLAIN, ids="+".join)
def test_a_plain_block_keeps_its_kernels_outputs_and_nothing_else_it_made(kind, monkeypatch):
    """Every checkpointed block keeps its kernels' outputs; the projections' name is a hybrid block's. On XLA's attention
    no value carries the flash call's name and the block keeps its inputs alone. On the flash kernels it keeps ``o`` and
    ``lse`` and nothing else made inside it, its gradient holds ONE forward call (under plain ``jax.checkpoint``, the rule
    before PR 64: two), the products over the model width are made again as they were, and values and gradients are the
    unchecked block's to the last bit."""
    from jax._src.ad_checkpoint import saved_residuals  # what print_saved_residuals prints, as a list

    cfg = tiny_next(n_layers=1, layer_kinds=(kind,), moe_num_experts=4 if kind[1] == "moe" else 0, d_ff=64, sliding_window=16)
    assert remat_keeps(kind) == (FLASH_SAVED,)
    made_inside = lambda loss: [(aval.shape, why.split(" from ")[0]) for aval, why in saved_residuals(loss, params, x)
                                if not why.startswith(("from the argument", "from a constant"))]
    loss, params, x = _block(kind, cfg, remat=True)
    assert saved_residuals(loss, params, x) and not made_inside(loss)
    _on_the_flash_kernels(monkeypatch)
    loss, params, x = _block(kind, cfg, remat=True)
    # (a kept value is listed by the last thing done to it: ``o`` by the rounding ``jax.checkpoint`` gives a residual)
    assert sorted(made_inside(loss)) == [((B, S, cfg.n_heads, cfg.head_dim), "output of reduce_precision"), ((B * cfg.n_heads, S), f"named '{FLASH_SAVED}'")]
    plain, _, _ = _block(kind, cfg, remat=False)
    grad = lambda f: jax.make_jaxpr(jax.grad(f, argnums=(0, 1)))(params, x).jaxpr
    assert _kernel_calls(grad(loss)) == _kernel_calls(grad(plain)) == {"flash_fwd": 1, "flash_bwd": 1}
    assert _kernel_calls(grad(jax.checkpoint(plain))) == {"flash_fwd": 2, "flash_bwd": 1}
    wide, _, _ = _made_again(loss, params, x, cfg)
    assert len(wide) == len(_made_again(jax.checkpoint(plain), params, x, cfg)[0]) == (6 if kind[1] == "dense" else 9)  # q, k, v, o, gate, up
    assert not _made_again(plain, params, x, cfg)[2]
    got, want = (jax.jit(jax.value_and_grad(f, argnums=(0, 1)))(params, x) for f in (loss, plain))
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        assert np.isfinite(np.asarray(a)).all() and np.array_equal(np.asarray(a), np.asarray(b))
    assert all(float(jnp.max(jnp.abs(g))) > 0 for g in jax.tree_util.tree_leaves(got[1]))


def _stack(**over):
    """(the model, its parameters off their start, what makes a model's loss of its parameters) of a two-layer plain
    decoder under ``remat``."""
    cfg = TransformerConfig(**dict(dict(vocab_size=97, n_layers=2, n_heads=4, n_kv_heads=2, head_dims=16, d_model=48, d_ff=64, max_seq_len=S,
                                        norm="rmsnorm", activation="swiglu", pos_emb="rope", tie_embeddings=False, remat=True), **over))
    ids = np.random.default_rng(0).integers(0, 97, (B, S)).astype(np.int32)
    params = CausalLM(cfg).init(jax.random.PRNGKey(0), {"input_ids": ids})
    leaves, tree = jax.tree_util.tree_flatten(params)
    params = jax.tree_util.tree_unflatten(tree, [p + 0.05 * jax.random.normal(jax.random.PRNGKey(7 + i), p.shape) for i, p in enumerate(leaves)])
    return CausalLM(cfg), params, lambda model: (lambda p: model.loss_fn(p, {"input_ids": ids}))


def test_a_looped_stack_runs_one_forward_call_an_application(monkeypatch):
    """``loop_steps=4`` over two layers: the scan over passes holds the two blocks' calls once, forward and backward, and
    stacks what they keep; loss and gradients are the unchecked stack's to the last bit."""
    _on_the_flash_kernels(monkeypatch)
    model, params, loss = _stack(loop_steps=4, norm_scheme="sandwich", exit_gate=True, exit_entropy_coef=0.05)
    assert _kernel_calls(jax.make_jaxpr(jax.grad(loss(model)))(params).jaxpr) == {"flash_fwd": 2, "flash_bwd": 2}
    unchecked = CausalLM(TransformerConfig(**dict(model.cfg.__dict__, remat=False)))
    got, want = (jax.jit(jax.value_and_grad(loss(m)))(params) for m in (model, unchecked))
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        assert np.isfinite(np.asarray(a)).all() and np.array_equal(np.asarray(a), np.asarray(b))


def test_the_stacked_form_has_the_unrolled_forms_policy(monkeypatch):
    """``scan_layers`` wraps ``Block`` in ``nn.remat`` with the policy of the stack's one kind: the scanned body holds one
    forward call (a layer), where the unrolled form holds one a layer, and the gradients are the unrolled form's."""
    _on_the_flash_kernels(monkeypatch)
    unrolled, params, loss = _stack()
    stacked = CausalLM(TransformerConfig(**dict(unrolled.cfg.__dict__, scan_layers=True)))
    # a tree of the unrolled form as the stacked form holds it: the two layers' leaves stacked under ``layers/block``
    together = lambda tree: {**{name: leaf for name, leaf in tree.items() if not name.startswith("layer_")},
                             "layers": {"block": jax.tree_util.tree_map(lambda *leaves: jnp.stack(leaves), tree["layer_0"], tree["layer_1"])}}
    shapes = jax.eval_shape(lambda: stacked.init(jax.random.PRNGKey(0), {"input_ids": np.zeros((B, S), np.int32)}))
    assert jax.tree_util.tree_structure(together(params)) == jax.tree_util.tree_structure(shapes)
    assert _kernel_calls(jax.make_jaxpr(jax.grad(loss(unrolled)))(params).jaxpr) == {"flash_fwd": 2, "flash_bwd": 2}
    assert _kernel_calls(jax.make_jaxpr(jax.grad(loss(stacked)))(together(params)).jaxpr) == {"flash_fwd": 1, "flash_bwd": 1}  # the scan's body, a layer
    (got_loss, got), (want_loss, want) = jax.jit(jax.value_and_grad(loss(stacked)))(together(params)), jax.jit(jax.value_and_grad(loss(unrolled)))(params)
    np.testing.assert_allclose(got_loss, want_loss, rtol=1e-6)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree_util.tree_leaves(together(want))):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6 * float(jnp.max(jnp.abs(b))), err_msg=jax.tree_util.keystr(path))


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


def test_the_first_call_line_says_one_thing_for_one_rule():
    """``remat_keeps`` on the trainer's first-call line is the policy the program's blocks had: unrolled through
    ``block_fn`` or scanned through ``nn.remat(Block)``, the same names; ``inputs`` where a block's parts declare none."""
    import types

    from deepspeed_tpu.runtime.engine import DeepSpeedEngine, _paths_traced

    notes = lambda kind=("mla", "dense"), **over: DeepSpeedEngine._layer_kind_notes(
        types.SimpleNamespace(module=types.SimpleNamespace(cfg=tiny(n_layers=2, layer_kinds=(kind,) * 2, **over))), _paths_traced())
    assert notes(remat=True) == {"remat_keeps": "flash_attention+projection"}
    assert notes(("full", "dense"), remat=True) == notes(("full", "dense"), remat=True, scan_layers=True) == {"remat_keeps": "flash_attention"}
    assert notes() == {}
