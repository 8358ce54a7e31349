"""The hybrid layers (``models/mixers.py``, ``moe/layer.py::RoutedMoE``, the
KDA scan kernel) against plain references, the per-layer specification, and
the share: at tiny widths, float32, on the CPU."""

import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import hybrid_reference as ref
from deepspeed_tpu.models import CausalLM, TransformerConfig, transformer as T
from deepspeed_tpu.telemetry import get_registry

KINDS = (("kda", "dense"), ("kda", "routed"), ("kda", "routed"), ("mla", "routed"), ("kda", "routed"))


def tiny(**over):
    base = dict(vocab_size=211, n_layers=5, n_heads=4, d_model=48, d_ff=64, max_seq_len=64, norm="rmsnorm", activation="swiglu",
                pos_emb="none", tie_embeddings=False, layer_kinds=KINDS, kda_heads=2, kda_head_dim=16, kda_gate_rank=8,
                mla_kv_rank=24, mla_qk_nope_dim=24, mla_qk_rope_dim=8, mla_v_dim=16, moe_num_experts=16, moe_top_k=4,
                moe_d_ff=32, moe_shared_d_ff=32, moe_route_scale=2.446, moe_held=(4, 8), moe_aux_loss_coef=0.0)
    return TransformerConfig(**dict(base, **over))


VL_KINDS = (("mla", "dense"),) + (("mla", "routed"),) * 5


def tiny_vl(**over):
    """The other pattern: latent attention with its shared key part rotated in every layer (theta 800,000, pairs
    (2i, 2i + 1)), a dense layer and then routed ones over 16 experts, 3 a token, two shared experts as one of twice
    the width."""
    return tiny(**dict(dict(n_layers=6, layer_kinds=VL_KINDS, pos_emb="rope", rope_theta=800000.0, rope_style="gptj", moe_top_k=3,
                            moe_shared_d_ff=64), **over))


@pytest.fixture(scope="module", autouse=True)
def _routed_rows_counters_left_as_found():
    """The routed layers' counters are the process's, and a benchmark reader that is handed no counter falls back to the
    process's totals (``benchmarks/lib/program.py::counter``): on a worker that ran this file first,
    ``tests/benchmarks/test_benchmark_hybrid.py`` would read these tests' rows where it expects none."""
    names = ("moe_rows_routed_here_total", "moe_rows_dropped_total", "moe_fallback_layers_total")
    found = {name: get_registry().peek(name) or 0.0 for name in names}
    yield
    for name in names:
        get_registry().counter(name).value = found[name]


@pytest.fixture(scope="module")
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def _close(a, b, tol=2e-5):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert np.max(np.abs(a - b)) <= tol * (1.0 + np.max(np.abs(b))), (np.max(np.abs(a - b)), np.max(np.abs(b)))


def _module(kind, cfg):
    from deepspeed_tpu.models.mixers import KDAMixer, MLAMixer
    from deepspeed_tpu.moe.layer import RoutedMoE

    if kind == "kda":
        return KDAMixer(cfg), lambda p, h: ref.kda(p, h)
    if kind == "mla":
        return MLAMixer(cfg), lambda p, h: ref.mla(p, h)
    if kind == "mla_rope":
        return MLAMixer(tiny_vl()), lambda p, h: ref.mla(p, h, theta=800000.0)
    if kind == "routed_two_shared":  # 6 of 64 with 8 held, two shared experts of 32 held as one of 64
        return (RoutedMoE(cfg.d_model, 64, 6, cfg.moe_d_ff, (8, 8), 64, cfg.moe_route_scale),
                lambda p, h: ref.routed(p, h, 8, 6, cfg.moe_route_scale, shared=2))
    return (RoutedMoE(cfg.d_model, cfg.moe_num_experts, cfg.moe_top_k, cfg.moe_d_ff, cfg.moe_held, cfg.moe_shared_d_ff,
                      cfg.moe_route_scale), lambda p, h: ref.routed(p, h, cfg.moe_held[0], cfg.moe_top_k, cfg.moe_route_scale))


@pytest.mark.parametrize("kind", ["kda", "mla", "routed", "mla_rope", "routed_two_shared"])
def test_a_layer_matches_its_plain_reference_forward_and_gradients(kind, highest):
    """(a) q/k of 24 + 8 beside v of 16 for MLA, without positions and with the 8-part rotated pair by pair; KDA heads
    of 16; a share of 8 of 16 experts with one shared expert, and of 8 of 64 at 6 a token with two."""
    cfg = tiny()
    module, plain = _module(kind, cfg)
    h = jax.random.normal(jax.random.PRNGKey(1), (2, 40, cfg.d_model))
    params = module.init(jax.random.PRNGKey(2), h)["params"]
    w = jax.random.normal(jax.random.PRNGKey(3), h.shape)
    ours = jax.value_and_grad(lambda p, h: jnp.sum(module.apply({"params": p}, h) * w), argnums=(0, 1))
    theirs = jax.value_and_grad(lambda p, h: jnp.sum(plain(p, h) * w), argnums=(0, 1))
    (lo, go), (lt, gt) = ours(params, h), theirs(params, h)
    _close(module.apply({"params": params}, h), plain(params, h))
    _close(lo, lt)
    flat_o, flat_t = jax.tree_util.tree_leaves_with_path(go), dict(jax.tree_util.tree_leaves_with_path(gt))
    for path, leaf in flat_o:
        _close(leaf, flat_t[path], 5e-5)
    if kind.startswith("routed"):  # the selection bias chooses and takes no gradient
        assert float(jnp.max(jnp.abs(go[0]["select_bias"]))) == 0.0


def _scan_inputs(S, decay, seed=0, B=1, H=2, dk=16, dv=16):
    """Heads before the sequence, as ``ops/kda.py`` takes them."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = ref.l2(jax.random.normal(ks[0], (B, H, S, dk))) * dk ** -0.5
    k = ref.l2(jax.random.normal(ks[1], (B, H, S, dk)))
    v = jax.random.normal(ks[2], (B, H, S, dv))
    alpha = jnp.clip(decay + 0.01 * jax.random.uniform(ks[3], (B, H, S, dk), minval=-1.0), 1e-12, 1.0)
    return q, k, v, jnp.log(alpha), jax.nn.sigmoid(jax.random.normal(ks[4], (B, H, S)))


def _plain_delta_rule(q, k, v, g, beta):
    """The test-side reference, which keeps the sequence before the heads."""
    sw = lambda x: jnp.swapaxes(x, 1, 2)
    return sw(ref.delta_rule(sw(q), sw(k), sw(v), jnp.exp(sw(g)), sw(beta)))


@pytest.mark.parametrize("S,decay,heads,heads_a_step", [
    (256, 0.9, 2, 2), (100, 0.9, 2, 2), (128, 0.999999, 2, 2), (70, 1e-9, 2, 2), (260, 0.02, 2, 2),
    (1000, 0.9, 4, 4), (256, 0.9, 6, 2), (100, 0.9, 3, 1), (260, 0.02, 4, 4)])
def test_the_kda_kernel_matches_the_token_recurrence(S, decay, heads, heads_a_step, highest):
    """(b) interpret mode: lengths that are and are not whole chunks, decays near 1 and near 0 (where exp(-G) overflows),
    and 2, 4, 6 and 3 heads, which the rule walks 2, 4, 2 and 1 a grid step (an odd count is the kernel of one head)."""
    from deepspeed_tpu.ops.kda import kda_chunked, kda_recurrence

    args = _scan_inputs(S, decay, H=heads)
    w = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
    run = lambda fn: jax.value_and_grad(lambda *a: jnp.sum(fn(*a) * w), argnums=(0, 1, 2, 3, 4))(*args)
    (lo, go), (lt, gt) = run(lambda *a: kda_chunked(*a, interpret=True)), run(kda_recurrence)
    _close(kda_chunked(*args, interpret=True), kda_recurrence(*args))
    _close(kda_recurrence(*args), _plain_delta_rule(*args))  # the oracle's oracle
    assert np.isfinite(float(lo))
    for a, b in zip(go, gt):
        _close(a, b, 1e-4)
    ref.kernel_of_one_head_a_step(args, heads_a_step)


@pytest.mark.parametrize("form,heads,word", [("kda", 4, "4"), ("kda", 6, "2"), ("kda", 3, "1"), ("gdn", 4, "4")])
def test_the_heads_a_grid_step_are_counted_where_the_kernel_is_traced(form, heads, word):
    """``program_regions_traced_total{region="mixer/kernel", op, pass, path="kernel", heads_a_step}`` rises once a traced
    call site, forward and backward, and the trainer's first-call key (the mixers' ``joined`` entry, whose third word
    names the label) reads the same series."""
    from deepspeed_tpu.models.mixers import GDNMixer, KDAMixer
    from deepspeed_tpu.ops.kda import gdn_chunked, kda_chunked
    from deepspeed_tpu.runtime import engine as trainer

    q, k, v, g, beta = _scan_inputs(128, 0.9, H=heads)
    chunked, g, record = (kda_chunked, g, KDAMixer) if form == "kda" else (gdn_chunked, g[..., 0], GDNMixer)
    key, (region, words, label) = next(iter(record.joined.items()))
    assert (key, region, label) == (f"{form}_heads_a_step", "mixer/kernel", "heads_a_step") and word in words
    series = lambda pass_: get_registry().total("program_regions_traced_total", region=region, op=form, path="kernel",
                                                heads_a_step=word, **{"pass": pass_})
    before, said = [series("fwd"), series("bwd")], trainer._paths_traced([record])[key]
    jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(chunked(*a, interpret=True)), argnums=(0, 1, 2, 3, 4)))(q, k, v, g, beta)
    assert [series("fwd") - before[0], series("bwd") - before[1]] == [1, 1]
    rose = [w for w, now, was in zip(words, trainer._paths_traced([record])[key], said) if now > was]
    assert rose == [word]


@pytest.mark.parametrize("heads", [2, 4, 3])
def test_the_kda_kernel_under_bf16_operands_is_bf16_close(heads, highest):
    """bf16 q, k, v: the large products take bf16 operands and the triangular inverse three bf16 passes a product;
    several heads a grid step give one head's bits under them too."""
    from deepspeed_tpu.ops.kda import kda_chunked, kda_recurrence

    q, k, v, g, beta = _scan_inputs(256, 0.9, H=heads)
    low = tuple(x.astype(jnp.bfloat16) for x in (q, k, v))
    ref.kernel_of_one_head_a_step((*low, g, beta.astype(jnp.bfloat16)), {2: 2, 4: 4, 3: 1}[heads])
    w = jax.random.normal(jax.random.PRNGKey(9), v.shape)
    run = lambda fn, *qkv: jax.grad(lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * w), argnums=(0, 1, 2, 3, 4))(*qkv, g, beta)
    got, want = run(lambda *a: kda_chunked(*a, interpret=True), *low), run(kda_recurrence, *(x.astype(jnp.float32) for x in low))
    for a, b in zip(got, want):
        assert float(jnp.linalg.norm(a.astype(jnp.float32) - b) / jnp.linalg.norm(b)) < 2e-2


@pytest.mark.parametrize("noise,beta,decay", [(0.0, 0.99, 1.0), (0.3, 0.9, 0.99)])
def test_the_kda_kernel_stays_exact_where_keys_are_alike(noise, beta, decay, highest):
    """Identical and nearly identical keys with beta near 1 and hardly any decay: the triangular system at its worst
    (squaring a whole chunk's matrix gave 1e31 here)."""
    from deepspeed_tpu.ops.kda import kda_chunked, kda_recurrence

    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    k = ref.l2(jax.random.normal(ks[0], (1, 1, 1, 32)) + noise * jax.random.normal(ks[1], (1, 1, 256, 32)))
    v = jax.random.normal(ks[2], (1, 1, 256, 32))
    args = (k * 32 ** -0.5, k, v, jnp.full(k.shape, np.log(decay), jnp.float32), jnp.full((1, 1, 256), beta))
    _close(kda_chunked(*args, interpret=True), kda_recurrence(*args), 2e-3)
    # the gradients too, through the saved inverse and the solve's own adjoint. 5e-4: three times what this reads (1.7e-4
    # at most over the five operands for identical keys, 5e-5 for the noisy ones; differentiating through the inverse's
    # twelve products, as the kernel did before, read 3.4e-4 and 7e-5)
    w = jax.random.normal(jax.random.PRNGKey(9), v.shape)
    run = lambda fn: jax.grad(lambda *a: jnp.sum(fn(*a) * w), argnums=(0, 1, 2, 3, 4))(*args)
    for a, b in zip(run(lambda *a: kda_chunked(*a, interpret=True)), run(kda_recurrence)):
        _close(a, b, 5e-4)


def _strictly_lower(case, n):
    """A chunk's ``A``: random, or ``beta k_t . k_s decay^(t - s)`` of keys that are alike (as the test above has them)."""
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    t, s = jnp.arange(n)[:, None], jnp.arange(n)[None, :]
    if case == "random":
        return jnp.where(t > s, 0.3 * jax.random.normal(ks[0], (n, n)), 0.0)
    noise, beta, decay = case
    k = ref.l2(jax.random.normal(ks[0], (1, 32)) + noise * jax.random.normal(ks[1], (n, 32)))
    return jnp.where(t > s, beta * (k @ k.T) * decay ** jnp.maximum(t - s, 0).astype(jnp.float32), 0.0)


@pytest.mark.parametrize("given", [False, True], ids=["made", "given"])
@pytest.mark.parametrize("case", ["random", (0.0, 0.99, 1.0), (0.3, 0.9, 0.99)], ids=["random", "identical", "alike"])
def test_the_triangular_solve_has_the_adjoint_of_a_solve(case, given, highest):
    """``solve_unit_lower``'s written-out adjoint alone, float32, with the inverse made in the call and handed in,
    against ``jax.grad`` of ``jnp.linalg.solve(I + A, r)``. Tolerance 7e-4: keys that are alike read 2.3e-4 (all of it
    the inverse by blocks, as the forward has it: against float64 the library's solve is exact to 3e-7 there), a random
    ``A``, whose gradient is 3,000 large, 5e-5 (all of it the library's: ours is exact to 4e-7)."""
    from deepspeed_tpu.ops.pallas.kda import CHUNK, solve_unit_lower

    A = _strictly_lower(case, CHUNK)
    r, w = (jax.random.normal(jax.random.PRNGKey(i), (CHUNK, 16)) for i in (5, 6))
    T = solve_unit_lower(A, r, None, jnp.float32)[1] if given else None
    ours = jax.grad(lambda A, r: jnp.sum(solve_unit_lower(A, r, T, jnp.float32)[0] * w), argnums=(0, 1))(A, r)
    theirs = jax.grad(lambda A, r: jnp.sum(jnp.linalg.solve(jnp.eye(CHUNK) + A, r) * w), argnums=(0, 1))(A, r)
    _close(solve_unit_lower(A, r, T, jnp.float32)[0], jnp.linalg.solve(jnp.eye(CHUNK) + A, r), 7e-4)
    _close(ours[0], jnp.tril(theirs[0], -1), 7e-4)  # A has no entry on or above its diagonal, so no gradient there
    _close(ours[1], theirs[1], 7e-4)
    assert float(jnp.max(jnp.abs(jnp.triu(ours[0])))) == 0.0


def _count(jaxpr, primitive):
    found = 0
    for eqn in jaxpr.eqns:
        found += eqn.primitive.name == primitive
        for value in eqn.params.values():
            for inner in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(inner, "jaxpr", inner)
                found += _count(inner, primitive) if hasattr(inner, "eqns") else 0
    return found


@pytest.mark.parametrize("mm,most,forward", [(jnp.bfloat16, 41, 50), (jnp.float32, 35, 24)], ids=["bf16", "f32"])
def test_the_kda_backward_does_not_differentiate_the_triangular_inverse(mm, most, forward):
    """The backward kernel's body as a jaxpr: 14 products of the chunk forward with ``u = T r`` (three passes under bf16
    operands, one under float32), 21 cotangent products of the 11 that JAX differentiates, and the solve's adjoint, two
    more of three passes or of one. With the inverse's construction inside (12 products, 24 cotangent ones) it was 149
    and 71: an edit that lets it back in fails here and not on the chip. The forward kernel's body keeps its 50 / 24."""
    from deepspeed_tpu.ops.pallas import kda as K

    x, g = jnp.zeros((K.CHUNK, 128), mm), jnp.zeros((K.CHUNK, 128), jnp.float32)
    state, inverse = jnp.zeros((128, 128), jnp.float32), jnp.zeros((K.CHUNK, K.CHUNK), jnp.float32)
    body = jax.make_jaxpr(lambda *a: K.chunk_bwd(*a, mm))(x, x, x, x, g, state, inverse, g, state)
    assert 0 < _count(body.jaxpr, "dot_general") <= most
    assert _count(jax.make_jaxpr(lambda *a: K.chunk_fn(*a, mm))(x, x, x, x, g, state).jaxpr, "dot_general") == forward


def test_the_kda_forward_saves_the_inverse_of_each_chunk(highest):
    """``scan_fwd``'s third output times ``I + A``, ``A`` from its definition, is the identity, on two chunks of two heads."""
    from deepspeed_tpu.ops.pallas.kda import CHUNK, scan_fwd

    q, k, v, g, beta = (x[0] for x in _scan_inputs(2 * CHUNK, 0.9))  # (H, S, d), (H, S)
    _, states, inverses = scan_fwd(q, k, beta[..., None] * k, beta[..., None] * v, g, interpret=True)
    assert states.shape == (2, 2, 16, 16) and inverses.shape == (2, 2, CHUNK, CHUNK) and inverses.dtype == jnp.float32
    chunks = lambda x: x.reshape(2, 2, CHUNK, *x.shape[2:])
    k, g, beta = chunks(k), chunks(g), chunks(beta)
    G = jnp.cumsum(g, axis=2)  # from the chunk's start
    decay = jnp.exp(jnp.minimum(G[:, :, :, None] - G[:, :, None, :], 0.0))  # (H, chunk, t, s, d): only s < t is kept
    A = jnp.tril(beta[..., None] * jnp.einsum("hctd,hcsd,hctsd->hcts", k, k, decay), -1)
    _close(jnp.einsum("hcts,hcsr->hctr", inverses, jnp.eye(CHUNK) + A), jnp.broadcast_to(jnp.eye(CHUNK), A.shape), 1e-5)


def _routed_layer(held, cfg=None, shared=32):
    from deepspeed_tpu.moe.layer import RoutedMoE

    cfg = cfg or tiny()
    return RoutedMoE(cfg.d_model, cfg.moe_num_experts, cfg.moe_top_k, cfg.moe_d_ff, held, shared, cfg.moe_route_scale)


def test_two_shared_experts_are_one_of_twice_the_width(highest):
    """Two SwiGLUs of 32 added are one of 64 with the columns side by side: the layer with ``shared_ff`` = 64 gives what
    the routed part alone gives plus each half's SwiGLU."""
    h = jax.random.normal(jax.random.PRNGKey(4), (2, 24, 48))
    params = _routed_layer((4, 8), shared=64).init(jax.random.PRNGKey(5), h)["params"]
    routed_alone = _routed_layer((4, 8), shared=0).apply({"params": {k: v for k, v in params.items() if not k.startswith("shared_")}}, h)
    gate, up, down = (params[f"shared_{n}_proj"]["kernel"] for n in ("gate", "up", "down"))
    halves = sum(ref.swiglu(h, gate[:, c], up[:, c], down[c]) for c in (slice(0, 32), slice(32, 64)))
    _close(_routed_layer((4, 8), shared=64).apply({"params": params}, h), routed_alone + halves)


def _share_of(whole, first, count):
    """The parameters of the layer holding experts first .. first + count, cut from the one holding all."""
    return {k: (v[first:first + count] if k.startswith("experts_") else v) for k, v in whole.items()}


@pytest.mark.parametrize("experts,k,count,shared", [(16, 4, 8, 1), (16, 4, 4, 1), (16, 4, 2, 1), (64, 6, 8, 2)])
def test_the_shares_add_up_to_the_whole_layer(experts, k, count, shared, highest):
    """(d) at 16 experts, 4 a token, and at 64, 6 a token, eight shares of 8 and two shared experts: the parts that
    shares of ``count`` experts give add up to the uncut layer's output, what every chip computes alike (the shared
    experts) counted once."""
    h = jax.random.normal(jax.random.PRNGKey(4), (2, 24, 48))
    cfg = tiny(moe_num_experts=experts, moe_top_k=k)
    whole_layer = _routed_layer(None, cfg, 32 * shared)
    whole = whole_layer.init(jax.random.PRNGKey(5), h)["params"]
    want = whole_layer.apply({"params": whole}, h)
    shared_once = _routed_layer((0, count), cfg, 32 * shared).apply({"params": _share_of(whole, 0, count)}, h)
    no_shared = {k: v for k, v in whole.items() if not k.startswith("shared_")}
    rest = sum(_routed_layer((f, count), cfg, 0).apply({"params": _share_of(no_shared, f, count)}, h) for f in range(count, experts, count))
    _close(shared_once + rest, want)
    _close(want, ref.routed(whole, h, 0, k, 2.446, shared=shared))


@pytest.mark.parametrize("axis", [2, 4])
def test_an_expert_axis_gives_the_one_device_layers_output(axis, highest):
    """(d) with an ``expert`` mesh axis of 2 and of 4 virtual devices the layer's output equals the one-device layer holding all."""
    from deepspeed_tpu.parallel.mesh import initialize_mesh, reset_mesh
    from deepspeed_tpu.runtime.config import MeshConfig

    h = jax.random.normal(jax.random.PRNGKey(6), (2, 24, 48))
    layer = _routed_layer(None)
    params = layer.init(jax.random.PRNGKey(7), h)["params"]
    reset_mesh()
    want, grads_want = jax.value_and_grad(lambda p: jnp.sum(layer.apply({"params": p}, h) ** 2))(params)
    try:
        topo = initialize_mesh(MeshConfig.from_dict({"expert": axis}), devices=jax.devices()[:axis], force=True)
        with topo.mesh:
            got, grads = jax.jit(jax.value_and_grad(lambda p: jnp.sum(layer.apply({"params": p}, h) ** 2)))(params)
    finally:
        reset_mesh()
    _close(got, want)
    for a, b in zip(jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(grads_want)):
        _close(a, b, 1e-4)


def test_no_row_is_dropped_when_every_token_picks_one_held_expert(highest):
    """(e) a router biased so that every token's first choice is expert 5 of the held 4..7: every row comes back."""
    cfg = tiny(moe_held=(4, 4))
    layer = _routed_layer(cfg.moe_held, cfg)
    h = jax.random.normal(jax.random.PRNGKey(8), (3, 32, 48))
    params = layer.init(jax.random.PRNGKey(9), h)["params"]
    params = dict(params, select_bias=params["select_bias"].at[5].set(10.0))
    out, sown = jax.jit(lambda p: layer.apply({"params": p}, h, mutable=["intermediates"]))(params)
    routed, dropped, largest, _, rung, over_uniform = (int(v) for v in sown["intermediates"]["rows"][0])
    assert routed >= 3 * 32 and dropped == 0  # every token's pair for expert 5, and whatever else fell on 4..7
    assert largest == 3 * 32 and rung == 0  # all 96 tokens in one group; every pair is 384, and so is the first rung: no conditional
    assert over_uniform == round(1000 * routed / 96)  # a uniform router sends 384 x 4 / 16 pairs here
    _close(out, ref.routed(params, h, 4, cfg.moe_top_k, cfg.moe_route_scale))


@pytest.mark.parametrize("biased", [False, True], ids=["usual", "every"])
def test_a_checkpointed_routed_layer_keeps_the_usual_branchs_rows_only(biased, highest):
    """Under the block's policy (``save_only_these_names``) a routed layer of 2 of 16 held at 2 a token, where the usual
    buffer (512 rows) is smaller than every pair (1,024): what the layer keeps for its backward has the usual buffer's
    rows and nothing of the branch that holds every pair (a ``lax.cond`` hands on both branches' residuals), and the
    gradients are the plain reference's whichever branch runs (biased: every token picks held expert 5 first)."""
    from jax._src.ad_checkpoint import saved_residuals  # what print_saved_residuals prints, as a list

    from deepspeed_tpu.moe.sharded_moe import SAVED

    cfg = tiny(moe_held=(4, 2), moe_top_k=2)
    layer = _routed_layer(cfg.moe_held, cfg)
    h = jax.random.normal(jax.random.PRNGKey(8), (4, 128, 48))
    params = layer.init(jax.random.PRNGKey(9), h)["params"]
    if biased:
        params = dict(params, select_bias=params["select_bias"].at[5].set(10.0))
    w = jax.random.normal(jax.random.PRNGKey(3), h.shape)
    kept = jax.checkpoint(lambda p, h: jnp.sum(layer.apply({"params": p}, h) * w), policy=jax.checkpoint_policies.save_only_these_names(SAVED))
    shapes = [tuple(aval.shape) for aval, _ in saved_residuals(kept, params, h)]
    assert any(s and s[0] == 512 for s in shapes) and not any(s and s[0] == 1024 for s in shapes), shapes
    got = jax.grad(kept, argnums=(0, 1))(params, h)
    want = jax.grad(lambda p, h: jnp.sum(ref.routed(p, h, 4, 2, cfg.moe_route_scale) * w), argnums=(0, 1))(params, h)
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        _close(a, b, 5e-5)


def test_five_layers_trace_three_blocks(highest):
    """(f) ``block_fn``'s key is the kind: KDA+dense, KDA+routed, MLA+routed."""
    model = CausalLM(tiny())
    ids = np.zeros((1, 32), np.int32)
    params = model.init(jax.random.PRNGKey(0), {"input_ids": ids})
    from deepspeed_tpu.telemetry import device_counts

    reg = get_registry()
    before = reg.peek("program_regions_traced_total", region="block", site="train") or 0
    rows = [reg.peek(n) or 0.0 for n in ("moe_rows_routed_here_total", "moe_rows_dropped_total")]

    def loss_and_counts(p):
        with device_counts.collecting() as reported:
            return model.loss_fn(p, {"input_ids": ids}), reported

    compiled = jax.jit(loss_and_counts).lower(params).compile()
    assert "callback" not in compiled.as_text()  # nothing the persistent compile cache would refuse to keep
    _, reported = compiled(params)
    assert reg.peek("program_regions_traced_total", region="block", site="train") - before == 3
    assert reported["moe_rows"].shape == (4, 6) and reg.peek("moe_rows_routed_here_total") in (None, rows[0])  # not yet counted
    device_counts.count(reported)
    # the four routed layers' rows leave the program as an output: 32 tokens x 4 choices, 8 of 16 held
    assert 4 * 32 <= reg.peek("moe_rows_routed_here_total") - rows[0] <= 4 * 32 * 4 and reg.peek("moe_rows_dropped_total") == rows[1]
    jax.block_until_ready(jax.jit(lambda p: model.loss_fn(p, {"input_ids": ids}))(params))  # nobody collects: nothing is reported
    assert model.cfg.kinds == KINDS and [model.cfg.moe_for(i) for i in range(5)] == [False, True, True, True, True]


def test_six_layers_of_rotated_latent_attention_trace_two_blocks_and_build_one_table(highest):
    """``block_fn``'s key is the kind: MLA+dense, MLA+routed. Both traces take the rotary table (8 wide, theta 800,000)
    that is worked out once a configuration, and each counts its rotation where it is traced."""
    T._rope_table.cache_clear()
    model = CausalLM(tiny_vl())
    ids = np.zeros((1, 32), np.int32)
    params = model.init(jax.random.PRNGKey(0), {"input_ids": ids})
    reg = get_registry()
    before = [reg.peek("program_regions_traced_total", region="block", site="train") or 0, reg.peek("program_regions_traced_total", region="mixer/rope", path="xla", op="mla") or 0]
    built = T._rope_table.cache_info().misses
    jax.block_until_ready(jax.jit(lambda p: model.loss_fn(p, {"input_ids": ids}))(params))
    assert reg.peek("program_regions_traced_total", region="block", site="train") - before[0] == 2
    assert reg.peek("program_regions_traced_total", region="mixer/rope", path="xla", op="mla") - before[1] == 2
    info = T._rope_table.cache_info()
    assert (info.misses - built, info.currsize) == (0, 1) and info.hits >= 2  # ``init`` built it; the traces found it
    cos, sin = T.scaled_rope_frequencies(model.cfg, 8)
    t = np.arange(64, dtype=np.float64)[:, None] * 800000.0 ** (-np.arange(0, 8, 2) / 8)
    np.testing.assert_allclose(np.asarray(cos), np.cos(t), atol=1e-6)
    np.testing.assert_allclose(np.asarray(sin), np.sin(t), atol=1e-6)
    assert model.cfg.kinds == VL_KINDS and tiny().pos_emb == "none"


def test_the_unrotated_latent_mixer_is_the_program_it_was(monkeypatch):
    """The no-positions form, equation for equation: the mixer's jaxpr at this size is the text the parent commit's
    mixer gave (sha256 of ``str(jaxpr)``, made from the parent by the same lines), and positions handed to it change nothing.
    What a checkpointed block keeps of it (PR 40) is four ``name`` equations, which compile to nothing: the text is
    taken without them."""
    from deepspeed_tpu.models import mixers
    from deepspeed_tpu.models.mixers import MLAMixer

    mixer = MLAMixer(tiny(n_layers=1, layer_kinds=(("mla", "dense"),)))
    x = jnp.zeros((2, 40, 48))
    params = jax.eval_shape(lambda: mixer.init(jax.random.PRNGKey(0), x))
    assert str(jax.make_jaxpr(lambda p, x: mixer.apply(p, x))(params, x)).count("name[name=projection]") == 4
    monkeypatch.setattr(mixers, "checkpoint_name", lambda value, name: value)
    positions = jnp.broadcast_to(jnp.arange(40, dtype=jnp.int32), (2, 40))
    with jax.default_matmul_precision("highest"):  # said here: the module's fixture may or may not be live on this worker
        text = str(jax.make_jaxpr(lambda p, x: mixer.apply(p, x))(params, x))
        assert str(jax.make_jaxpr(lambda p, x: mixer.apply(p, x, positions))(params, x)) == text
    # under the suite's ``DS_ACCELERATOR=tpu`` (the flash kernel, interpreted); 110 lines, 5b03615aea16b402 without
    assert (len(text.splitlines()), hashlib.sha256(text.encode()).hexdigest()[:16]) == (122, "9211f4e1a113ea17")


# the parameter trees the presets and the benchmark's OLMo ``program`` block built BEFORE the per-layer field
# (leaves, sha256 of the sorted "path shape dtype" lines; made from the parent commit by the same code as below)
GOLDEN = {
    "gpt2_tiny": (36, "a3f17807e10a3b20"), "gpt2_125m": (196, "5dbc31949410fefb"), "gpt2_1_3b": (388, "1630c0070f04b940"),
    "llama_tiny": (21, "57591dcb7215d7fc"), "llama2_7b": (291, "40b0b6b4cfaf01ca"), "llama3_8b": (291, "ed8c2104724c6448"),
    "olmo-1b.program": (113, "275453e661e320ac"), "llama_tiny.moe": (22, "46ce87439e34811e"),
    "gpt2_tiny.windows": (36, "a3f17807e10a3b20"),
    "kimi-linear-48b-l5e8.program": (113, "2bae1106a81e9319"),  # made from PR 33's commit: latent attention without positions
}
KIMI_LINEAR_PROGRAM = dict(
    vocab_size=20480, n_layers=5, n_heads=32, d_model=2304, d_ff=9216, max_seq_len=8192, norm="rmsnorm", activation="swiglu",
    pos_emb="none", tie_embeddings=False, norm_eps=1e-05, remat=True,
    layer_kinds=(("kda", "dense"), ("kda", "routed"), ("kda", "routed"), ("mla", "routed"), ("kda", "routed")),
    kda_heads=32, kda_head_dim=128, kda_conv_size=4, kda_gate_rank=128, mla_kv_rank=512, mla_qk_nope_dim=128, mla_qk_rope_dim=64,
    mla_v_dim=128, moe_num_experts=256, moe_top_k=8, moe_d_ff=1024, moe_shared_d_ff=1024, moe_route_scale=2.446, moe_held=(0, 8),
    moe_aux_loss_coef=0.0)
OLMO_PROGRAM = dict(vocab_size=50304, n_layers=16, n_heads=16, n_kv_heads=16, d_model=2048, d_ff=8192, max_seq_len=2048,
                    norm="layernorm_np", activation="swiglu", pos_emb="rope", rope_theta=10000.0, tie_embeddings=True,
                    norm_eps=1e-05, remat=False)


def _golden_case(name):
    if name == "olmo-1b.program":
        return TransformerConfig(**OLMO_PROGRAM)
    if name == "kimi-linear-48b-l5e8.program":
        return TransformerConfig(**KIMI_LINEAR_PROGRAM)
    if name == "llama_tiny.moe":
        return T.llama_tiny(moe_num_experts=4, moe_top_k=2)
    if name == "gpt2_tiny.windows":
        return T.gpt2_tiny(sliding_window=8, window_layers=(1,))
    return getattr(T, name)()


@pytest.mark.parametrize("name", sorted(GOLDEN))
def test_the_older_fields_build_the_same_parameter_tree(name):
    """(g) every preset and OLMo's ``program`` block: the same paths and shapes as before ``layer_kinds``."""
    cfg = _golden_case(name)
    shapes = jax.eval_shape(lambda: CausalLM(cfg).init(jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 8), np.int32)}))
    lines = sorted(f"{jax.tree_util.keystr(p)} {tuple(l.shape)} {l.dtype}" for p, l in jax.tree_util.tree_flatten_with_path(shapes)[0])
    assert (len(lines), hashlib.sha256("\n".join(lines).encode()).hexdigest()[:16]) == GOLDEN[name]


@pytest.mark.parametrize("cfg,kinds", [
    (T.gpt2_tiny(), (("full", "dense"),) * 2),
    (T.gpt2_tiny(sliding_window=8), (("window", "dense"),) * 2),
    (T.gpt2_tiny(sliding_window=8, window_layers=(1,)), (("full", "dense"), ("window", "dense"))),
    (T.llama_tiny(moe_num_experts=4), (("full", "dense"), ("full", "moe"))),
    (T.llama_tiny(moe_num_experts=4, moe_layer_freq=1), (("full", "moe"),) * 2),
])
def test_window_for_and_moe_for_are_readings_of_the_kinds(cfg, kinds):
    assert cfg.kinds == kinds
    assert [cfg.window_for(i) for i in range(2)] == [cfg.sliding_window if k[0] == "window" else None for k in kinds]
    assert [cfg.moe_for(i) for i in range(2)] == [k[1] != "dense" for k in kinds]
    same = dataclasses.replace(cfg, layer_kinds=kinds)  # the same model, said the new way
    assert same.kinds == kinds and same.uniform_window == cfg.uniform_window and hash(same) != hash(cfg)


def test_layer_kinds_of_the_wrong_length_or_name_are_refused():
    with pytest.raises(ValueError, match="layer_kinds"):
        tiny(n_layers=4).kinds
    with pytest.raises(ValueError, match="layer_kinds"):
        tiny(layer_kinds=(("kda", "dense"),) * 4 + (("mamba", "dense"),)).kinds


def test_the_old_gate_keeps_its_renormalisation():
    """The capacity-gated layer's k chosen gates still sum to one; without, they sum to less (``topkgating``'s argument,
    which no layer kind sets: the routed kind has scores of its own, ``sigmoid_topk``)."""
    from deepspeed_tpu.moe.sharded_moe import gate_and_dispatch, topkgating

    x, logits = jnp.ones((8, 4)), jax.random.normal(jax.random.PRNGKey(0), (8, 4))
    on = gate_and_dispatch(x, logits, 2, 4.0, 4, drop_tokens=False)[2]
    off = topkgating(logits, 2, 4.0, 4, drop_tokens=False, normalize_weights=False)[1]
    np.testing.assert_allclose(np.asarray(jnp.sum(on, axis=(1, 2))), 1.0, rtol=1e-5)
    assert float(jnp.max(jnp.sum(off, axis=(1, 2)))) < 1.0


def test_a_buffer_too_small_shows_as_dropped_rows():
    """``moe_rows_dropped_total`` is computed, not a constant: pairs routed here less pairs taken. ``routed_part`` always
    gives ``held_experts`` a buffer that holds every pair; one that does not is seen."""
    from deepspeed_tpu.moe.sharded_moe import held_experts, routed_part

    key = jax.random.PRNGKey(3)
    tokens = jax.random.normal(key, (512, 16))
    idx = jnp.tile(jnp.array([[0, 1]], jnp.int32), (512, 1))  # every token picks the two held experts: 1,024 pairs
    weights = jnp.full((512, 2), 0.5)
    wg, wi, wo = (jax.random.normal(jax.random.fold_in(key, i), shape) for i, shape in enumerate(((2, 16, 8), (2, 16, 8), (2, 8, 16))))
    _, routed, dropped, largest, smallest = held_experts(tokens, idx, weights, wg, wi, wo, 0, 768, False)
    assert (int(routed), int(dropped), int(largest), int(smallest)) == (1024, 256, 512, 512)
    _, routed, dropped, _, _, fallback = jax.jit(lambda *a: routed_part(*a, 0, 64, False))(tokens, idx, weights, wg, wi, wo)
    assert (int(routed), int(dropped), int(fallback)) == (1024, 0, 2)  # 32 x the uniform load, past the 512 rows that are the first rung and four times the load alike: the rung that holds every pair


@pytest.mark.parametrize("held_biased,fallbacks", [((4, 5), 1), ((), 0)], ids=["every_pair", "usual"])
def test_a_step_that_takes_the_fallback_is_counted_a_layer(held_biased, fallbacks, highest):
    """``moe_fallback_layers_total``: 2 of 16 held at 2 a token, a first rung of 512 rows (twice the uniform load and
    four times it round to the same buffer) under 1,024 pairs. A router biased to both held experts sends every pair
    here and the rung that holds every pair runs: one more (layer, step), read off the ``rows`` the layer sows as the
    other counts are, and one more of ``moe_buffer_rung_layers_total{rung="every"}``; an unbiased one stays on the first
    rung, counts no fallback and one more of ``{rung="first"}``. No row dropped either way, and
    ``moe_rows_over_uniform_max`` is the pairs that arrived over the 128 of a uniform router."""
    from deepspeed_tpu.moe.layer import report_rows
    from deepspeed_tpu.telemetry import device_counts

    cfg = tiny(moe_held=(4, 2), moe_top_k=2)
    layer = _routed_layer(cfg.moe_held, cfg)
    h = jax.random.normal(jax.random.PRNGKey(8), (4, 128, 48))
    params = layer.init(jax.random.PRNGKey(9), h)["params"]
    for e in held_biased:
        params = dict(params, select_bias=params["select_bias"].at[e].set(10.0))

    def layer_and_counts(p):
        with device_counts.collecting() as reported:
            out, sown = layer.apply({"params": p}, h, mutable=["intermediates"])
            report_rows(sown["intermediates"])
        return out, reported

    reg, names = get_registry(), ("moe_fallback_layers_total", "moe_rows_dropped_total", "moe_rows_routed_here_total")
    before = [reg.peek(n) or 0.0 for n in names]
    rungs = lambda: [reg.peek("moe_buffer_rung_layers_total", rung=r) or 0.0 for r in ("first", "four", "every")]
    rungs_before = rungs()
    out, reported = jax.jit(layer_and_counts)(params)
    device_counts.count(reported)
    rose = [(reg.peek(n) or 0.0) - b for n, b in zip(names, before)]
    assert rose[:2] == [fallbacks, 0] and (rose[2] == 1024 if fallbacks else 0 < rose[2] <= 512)
    assert [now - was for now, was in zip(rungs(), rungs_before)] == [1 - fallbacks, 0, fallbacks]
    assert reg.peek("moe_rows_over_uniform_max") == pytest.approx(rose[2] / 128, abs=1e-3)
    _close(out, ref.routed(params, h, 4, 2, cfg.moe_route_scale))


@pytest.mark.parametrize("pattern,stage,mesh,n", [("linear", 0, {"data": 1}, 1), ("linear", 3, {"fsdp": 4}, 4), ("vl", 0, {"data": 1}, 1)])
def test_the_five_layer_pattern_trains_through_initialize(pattern, stage, mesh, n):
    """(c) stage 0 on one device and stage 3 on four virtual devices: the same first loss and the same loss after 3
    steps, within 2e-3 of the recorded float32 values (the two differ in the order of every reduction). And the other
    pattern, six layers of rotated latent attention over a dense and five routed FFNs, on one device."""
    import deepspeed_tpu
    from deepspeed_tpu.parallel.mesh import initialize_mesh, reset_mesh
    from deepspeed_tpu.runtime.config import MeshConfig

    from deepspeed_tpu.telemetry import get_tracer

    model = CausalLM(tiny(max_seq_len=32) if pattern == "linear" else tiny_vl(max_seq_len=32))
    routed, k = (4, 4) if pattern == "linear" else (5, 3)
    ids = np.random.default_rng(0).integers(0, 211, (4, 32)).astype(np.int32)
    params = model.init(jax.random.PRNGKey(0), {"input_ids": ids[:1]})
    reg = get_registry()
    rows = [reg.peek(n) or 0.0 for n in ("moe_rows_routed_here_total", "moe_rows_dropped_total")]
    reset_mesh()
    try:
        topo = initialize_mesh(MeshConfig.from_dict(mesh), devices=jax.devices()[:n], force=True)
        engine, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params, mesh=topo, config={
            "train_micro_batch_size_per_gpu": 4 // n, "gradient_accumulation_steps": 1, "steps_per_print": 10**9,
            "optimizer": {"type": "adam", "params": {"lr": 1e-3}}, "zero_optimization": {"stage": stage}})
        losses = []
        for _ in range(4):
            loss = engine.forward({"input_ids": ids})
            engine.backward(loss)
            engine.step()
            losses.append(float(loss))
    finally:
        reset_mesh()
    # the routed layers' rows are an output of the step, counted once the step has ended: three or all four of the
    # steps' by now, each 128 tokens x 4 choices x 4 layers with 8 of 16 experts held; and the first-call span says
    # what was traced
    counted = reg.peek("moe_rows_routed_here_total") - rows[0]
    assert 3 * routed * 128 <= counted <= 4 * routed * 128 * k and reg.peek("moe_rows_dropped_total") == rows[1]
    said = [s["attrs"] for s in get_tracer().spans() if s["name"] == "program/first_call" and s["attrs"].get("family") == "train"][-1]
    if pattern == "linear":
        assert said["layer_kinds"] == "kda+dense:1,kda+routed:3,mla+routed:1" and "mla_rope" not in said  # no positions: no key
        assert (said["kda_path"], said["mla_path"], said["moe_path"], said["moe_combine"]) == ("xla",) * 4  # off the TPU: the counters' word
    else:
        assert said["layer_kinds"] == "mla+dense:1,mla+routed:5" and said["block_traces"] == 2 and "kda_path" not in said
        assert (said["mla_path"], said["mla_rope"], said["moe_path"], said["moe_combine"]) == ("xla",) * 4
    _TRAINED.setdefault(pattern, losses)
    assert np.isfinite(losses).all() and losses[3] < losses[0]
    np.testing.assert_allclose([losses[0], losses[3]], [_TRAINED[pattern][0], _TRAINED[pattern][3]], atol=2e-3)


_TRAINED = {}


def test_serving_refuses_the_new_layer_kinds_by_name():
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2

    model = CausalLM(tiny())
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 8), np.int32)}))
    with pytest.raises(NotImplementedError, match="kda"):
        InferenceEngineV2(model, params)
    with pytest.raises(NotImplementedError, match="pipeline"):
        model.to_pipeline(1, params=params)
