"""``ops/pallas/scan_operands.py``: what a delta-rule layer's scan reads, made from the kept projections by one Pallas call
each way. Interpreted on the CPU, both forms (a decay a channel: KDA; a decay a head with value heads that share a key
head: Gated DeltaNet) against the lines they replace (``KDAMixer``'s and ``GDNMixer``'s plain form and ``kda_chunked``'s
``beta k``, ``beta v``), in every output and every gradient; the mixers steered onto the kernels against themselves on
XLA's lines; the rule's word, the count of the choice, and what a checkpointed block keeps.

The interpreter gives the reciprocal's estimate (``pl.reciprocal(approx=True)``) bf16's precision, so after the one
Newton step the kernels' sigmoid is good to 2e-5 here where the chip's is good to float32's rounding (``PERF.md``
section 6, PR 58): the float32 cases hold to 1e-4, which a wrong tap, halo or head misses by four orders."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import TransformerConfig
from deepspeed_tpu.models.mixers import GDNMixer, KDAMixer, causal_conv, l2_normalize
from deepspeed_tpu.models.transformer import Block, block_fn, remat_keeps
from deepspeed_tpu.ops import placement
from deepspeed_tpu.ops.kda import _to_value_heads
from deepspeed_tpu.ops.pallas import scan_operands as so
from deepspeed_tpu.runtime import engine as trainer
from deepspeed_tpu.telemetry.tracing import regions_traced

F32, BF16 = jnp.float32, jnp.bfloat16
OUTPUTS, INPUTS = ("q", "k", "kb", "vb", "g"), ("x_q", "x_k", "x_v", "b", "w_q", "w_k", "w_v", "f", "A_log", "dt_bias")


def plain(x_q, x_k, x_v, b, w_q, w_k, w_v, f=None, a_log=None, dt_bias=None):
    """The lines the kernels replace, as the mixers and ``kda_chunked`` / ``gdn_chunked`` have them."""
    dtype, D, Hv = x_q.dtype, x_q.shape[-1], x_v.shape[1]
    conv_silu = lambda x, w: nn.silu(causal_conv(x, w.astype(dtype)[:, :, None, :], axis=2))
    q = (l2_normalize(conv_silu(x_q, w_q)) * D**-0.5).astype(dtype)
    k = l2_normalize(conv_silu(x_k, w_k)).astype(dtype)
    v = conv_silu(x_v, w_v)
    beta = jnp.swapaxes(jax.nn.sigmoid(b), 1, 2).astype(F32)[..., None]
    kb = (beta * _to_value_heads(k, Hv).astype(F32)).astype(dtype)
    vb = (beta * v.astype(F32)).astype(dtype)
    if f is None:
        return q, k, kb, vb
    return q, k, kb, vb, -jnp.exp(a_log)[:, None, None] * jax.nn.softplus(f + dt_bias[:, None, :])


def operands(B, Hk, Hv, S, D, K, dtype, decay):
    """-> (the call's arguments, a cotangent for each output); the filters are bf16's numbers, which the plain lines round them to."""
    keys = jax.random.split(jax.random.PRNGKey(0), 15)
    n = lambda i, shape, dt=F32: jax.random.normal(keys[i], shape, F32).astype(dt)
    filt = lambda i, H: (0.5 * n(i, (K, H, D))).astype(BF16).astype(F32)
    args = [n(0, (B, Hk, S, D), dtype), n(1, (B, Hk, S, D), dtype), n(2, (B, Hv, S, D), dtype), n(3, (B, S, Hv)), filt(4, Hk), filt(5, Hk), filt(6, Hv)]
    cts = [n(10, (B, Hk, S, D), dtype), n(11, (B, Hk, S, D), dtype), n(12, (B, Hv, S, D), dtype), n(13, (B, Hv, S, D), dtype)]
    if decay:
        args += [n(7, (B, Hk, S, D)), 0.3 * n(8, (Hk,)), n(9, (Hk, D))]
        cts.append(n(14, (B, Hk, S, D)))
    return args, tuple(cts)


def close(a, b, tol, what):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert a.shape == b.shape and np.max(np.abs(a - b)) <= tol * (1.0 + np.max(np.abs(b))), (what, np.max(np.abs(a - b)), np.max(np.abs(b)))


CASES = {
    "kda, one tile: zeros before the sequence": (2, 2, 2, 256, 128, 4, F32, True),
    "kda, three tiles of 128, two taps": (1, 2, 2, 384, 128, 2, F32, True),
    "gdn, a key head for two value heads, three tiles": (1, 1, 2, 384, 128, 4, F32, False),
    "gdn, two key heads for four, two tiles of 512, three taps": (1, 2, 4, 1024, 128, 3, F32, False),
    "kda, bf16, three tiles of 256": (1, 2, 2, 768, 128, 4, BF16, True),
    "gdn, bf16, heads of 256": (1, 1, 2, 256, 256, 4, BF16, False),
}


@pytest.mark.parametrize("case", list(CASES))
def test_the_kernels_are_the_lines_they_replace_in_every_output_and_gradient(case):
    """Every output and the cotangent of every input (the three projections, ``beta``'s pre-activation, the three
    filters, and with a decay a channel its pre-activation, ``A_log`` and ``dt_bias``); and by themselves the rows either
    side of every tile's edge, where a forward tile reads the halo before it and a backward tile the one after it."""
    B, Hk, Hv, S, D, K, dtype, decay = CASES[case]
    args, cts = operands(*CASES[case])
    want, pull = jax.vjp(plain, *args)
    got, pull_kernel = jax.vjp(lambda *a: so.scan_operands(*a, interpret=True), *args)
    tol = 1e-4 if dtype == F32 else 2e-2
    T = so.rows_a_tile(S)
    edges = np.concatenate([np.arange(max(e - 8, 0), min(e + 8, S)) for e in range(0, S + 1, T)])
    for names, mine, theirs in ((OUTPUTS, got, want), (INPUTS, pull_kernel(cts), pull(cts))):
        assert len(mine) == len(theirs)
        for name, a, b in zip(names, mine, theirs):
            assert a.dtype == b.dtype, name
            close(a, b, tol, name)
            if a.ndim == 4:
                close(a[:, :, edges], b[:, :, edges], tol, f"{name} beside a tile's edge")


def test_kb_is_beta_times_the_rounded_k():
    """As ``kda_chunked`` makes it: the scan's ``k`` and ``kb`` are one bf16 number and that number times beta."""
    args, _ = operands(1, 1, 2, 256, 128, 4, BF16, False)
    _, k, kb, _ = so.scan_operands(*args, interpret=True)
    beta = jnp.swapaxes(jax.nn.sigmoid(args[3]), 1, 2)[..., None]
    assert np.array_equal(np.asarray(kb, np.float32), np.asarray((beta * k.astype(F32)).astype(BF16), np.float32))


def test_the_kernels_take_whole_tiles_of_128_rows_or_more_and_the_chooser_says_xla_off_the_tpu(monkeypatch):
    assert [so.rows_a_tile(n) for n in (8192, 768, 384, 8256, 100)] == [512, 256, 128, 0, 0]
    assert so.fits(8192, 128, 4) and so.fits(384, 256, 2) and so.fits(1024, 128, 8)
    assert not so.fits(8256, 128, 4)  # whole tiles of 64 rows and no more: beta's cotangent leaves with a tile's rows along the lanes
    assert not so.fits(100, 128, 4) and not so.fits(8192, 64, 4) and not so.fits(8192, 128, 1) and not so.fits(8192, 128, 9)
    assert so.path_for(8192, 128, 4) == "xla"  # no TPU here
    monkeypatch.setattr(placement, "pallas_available", lambda: True)
    assert so.path_for(8192, 128, 4) == "kernel" and so.path_for(8256, 128, 4) == "xla"


def tiny(kind, **over):
    base = dict(vocab_size=211, n_layers=1, n_heads=4, d_model=48, d_ff=64, max_seq_len=256, norm="rmsnorm", activation="swiglu",
                pos_emb="none", tie_embeddings=False, layer_kinds=((kind, "dense"),), kda_heads=2, kda_head_dim=128, kda_gate_rank=8,
                gdn_key_heads=1, gdn_value_heads=2, gdn_head_dim=128)
    return TransformerConfig(**dict(base, **over))


def _mixer(kind, S=256, **over):
    mixer = (KDAMixer if kind == "kda" else GDNMixer)(tiny(kind, **over))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, S, 48))
    params = mixer.init(jax.random.PRNGKey(2), x)["params"]
    leaves, tree = jax.tree_util.tree_flatten(params)  # off their start: a norm weight of one hides its gradient's path
    params = jax.tree_util.tree_unflatten(tree, [p + 0.05 * jax.random.normal(jax.random.PRNGKey(7 + i), p.shape) for i, p in enumerate(leaves)])
    w = jax.random.normal(jax.random.PRNGKey(3), x.shape)
    return (lambda p, x: jnp.sum(mixer.apply({"params": p}, x) * w)), params, x


def _steer(monkeypatch, word="kernel"):
    monkeypatch.setattr(placement, "kernel_path", lambda fits=True, has_specs=True: word if fits else "xla")


def _counts():
    return {(path, pass_): regions_traced("mixer/proj", op="scan_operands", path=path, **{"pass": pass_}) for path in ("kernel", "xla") for pass_ in ("fwd", "bwd")}


def _rose(before):
    return {key: now - before[key] for key, now in _counts().items() if now != before[key]}


@pytest.mark.parametrize("kind", ["kda", "gdn"])
def test_a_mixer_on_the_kernels_is_the_mixer_on_xlas_lines_and_each_counts_its_choice_once(kind, monkeypatch):
    """Loss and every leaf's gradient, the scan's kernel interpreted behind the operands' on one side and the token
    recurrence behind the plain lines on the other; ``program_regions_traced_total{region="mixer/proj",
    op="scan_operands", pass, path}`` rises once a call site a trace: forward and backward on the kernels, forward alone
    on XLA's lines (which XLA differentiates), and the trainer's first-call keys read those series."""
    loss, params, x = _mixer(kind)
    record = KDAMixer if kind == "kda" else GDNMixer
    assert {key: record.paths[key] for key in ("scan_operands_fwd", "scan_operands_bwd")} == {
        f"scan_operands_{p}": ("mixer/proj", {"op": "scan_operands", "pass": p}) for p in ("fwd", "bwd")}
    with jax.default_matmul_precision("highest"):
        before, said = _counts(), trainer._paths_traced([record])
        want = jax.value_and_grad(loss, argnums=(0, 1))(params, x)
        assert _rose(before) == {("xla", "fwd"): 1}
        now = trainer._paths_traced([record])
        assert [tuple(n - s for n, s in zip(now[key], said[key])) for key in ("scan_operands_fwd", "scan_operands_bwd")] == [(0, 1), (0, 0)]
        _steer(monkeypatch)
        before, said = _counts(), now
        got = jax.value_and_grad(loss, argnums=(0, 1))(params, x)
        assert _rose(before) == {("kernel", "fwd"): 1, ("kernel", "bwd"): 1}
        now = trainer._paths_traced([record])
        assert [tuple(n - s for n, s in zip(now[key], said[key])) for key in ("scan_operands_fwd", "scan_operands_bwd")] == [(1, 0), (1, 0)]
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree_util.tree_leaves(want)):
        close(a, b, 2e-4, jax.tree_util.keystr(path))
    assert all(float(jnp.max(jnp.abs(g))) > 0 for g in jax.tree_util.tree_leaves(got[1]))


@pytest.mark.parametrize("kind,over", [("kda", dict(max_seq_len=64)), ("gdn", dict(gdn_head_dim=64)), ("kda", dict(kda_conv_size=9))],
                         ids=["rows", "lanes", "taps"])
def test_a_shape_that_does_not_fit_takes_the_plain_lines_and_counts_xla(kind, over, monkeypatch):
    """Where the backend compiles Mosaic and the shapes do not fit (64 rows; heads of 64; nine taps) the operands are XLA's
    lines, counted so, though the scan behind them is its kernel; traced, not run: the scan's kernel is not interpreted here."""
    loss, params, x = _mixer(kind, S=over.get("max_seq_len", 256), **over)
    monkeypatch.setattr(placement, "pallas_available", lambda: True)
    before = _counts()
    text = str(jax.make_jaxpr(jax.grad(loss))(params, x))
    assert _rose(before) == {("xla", "fwd"): 1}
    assert "scan_operands" not in text and f"{kind}_scan_fwd" in text and f"{kind}_scan_bwd" in text


@pytest.mark.parametrize("kind", ["kda", "gdn"])
def test_a_checkpointed_block_keeps_what_it_kept_and_makes_the_operands_again_by_the_forward_call(kind, monkeypatch):
    """Under the block's policy the kernels' outputs carry no name and their residuals are their inputs: what the block
    keeps (name, shape and type of every kept value) is what it keeps with XLA's lines ahead of the same scan kernel, the
    parent's program (nothing new is kept, by name or without); its backward runs the operands' forward call a second time and the backward call once; and the
    gradients are the unchecked block's on XLA's lines."""
    from jax._src.ad_checkpoint import saved_residuals  # what print_saved_residuals prints, as a list

    cfg, pair = tiny(kind), (kind, "dense")
    positions = jnp.broadcast_to(jnp.arange(256, dtype=jnp.int32), (2, 256))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 256, 48))
    params = Block(cfg, pair).init(jax.random.PRNGKey(2), x, positions)["params"]
    w = jax.random.normal(jax.random.PRNGKey(3), x.shape)
    loss = lambda remat: (lambda p, x: jnp.sum(block_fn(cfg, pair, True, remat)(p, x, positions, None, None, {})[0][0] * w))
    with jax.default_matmul_precision("highest"):
        want = jax.grad(loss(False), argnums=(0, 1))(params, x)  # XLA's lines, the token recurrence, no checkpoint
        _steer(monkeypatch)
        # (a kept value's name where the list says it, its shape and type): a kept projection is listed by the last thing
        # done to it (``reduce_precision``, or the plain lines' ``_pad``), the scan's saves by their name
        kept = lambda: sorted((why.split(" from ")[0] if why.startswith("named") else "", tuple(aval.shape), str(aval.dtype))
                              for aval, why in saved_residuals(loss(True), params, x))
        mine = kept()
        monkeypatch.setattr(so, "path_for", lambda *a: "xla")  # the parent: the plain lines ahead of the scan's kernel
        parents = kept()
        monkeypatch.undo()
        _steer(monkeypatch)
        assert mine == parents and {why for why, _, _ in mine if why} == {"named 'kda_scan'"} and "kda_scan" in remat_keeps(pair)
        text = str(jax.make_jaxpr(jax.grad(loss(True)))(params, x))
        assert (text.count("name=scan_operands_fwd"), text.count("name=scan_operands_bwd")) == (2, 1)
        got = jax.grad(loss(True), argnums=(0, 1))(params, x)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree_util.tree_leaves(want)):
        close(a, b, 2e-4, jax.tree_util.keystr(path))
