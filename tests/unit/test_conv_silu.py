"""``ops/pallas/conv_silu.py``: a Mamba layer's convolution, bias and SiLU over a column range of its kept product, by
one Pallas call each way. Interpreted on the CPU, both call sites' forms (three outputs from the middle of a wider
product: Mamba-2's x, B and C; one output from column 0: Mamba-1's u) against the definition, ``silu(causal_conv(x, w) +
b)`` under ``jax.vjp``, in every output and every gradient; the mixers steered onto the kernels against themselves on
XLA's lines; the rule's word, the count of the choice, and what a checkpointed block keeps.

The kernels make ``silu(c)`` as ``h tanh(h) + h`` at ``h = c / 2`` from HALF the filter and the bias (``_pack``), float32
inside: the float32 cases hold to 1e-5, which a wrong tap, halo, column or half misses by five orders."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import TransformerConfig
from deepspeed_tpu.models.mixers import SSDMixer, SSMMixer, causal_conv
from deepspeed_tpu.models.transformer import Block, block_fn, remat_keeps
from deepspeed_tpu.ops import placement
from deepspeed_tpu.ops.pallas import conv_silu as cs
from deepspeed_tpu.runtime import engine as trainer
from deepspeed_tpu.telemetry.tracing import regions_traced

F32, BF16 = jnp.float32, jnp.bfloat16


def plain(x, w, b, start, widths):
    """The definition over the product's columns [start, start + sum(widths)), in float32, split and rounded once."""
    y = nn.silu(causal_conv(x[..., start:start + sum(widths)].astype(F32), w) + b)
    return tuple(y[..., at - width:at].astype(x.dtype) for at, width in zip(np.cumsum(widths), widths))


def operands(Bt, S, columns, start, widths, K, dtype):
    """-> ((the product, the filter, the bias), a cotangent for each output)."""
    keys = jax.random.split(jax.random.PRNGKey(0), 3 + len(widths))
    x = jax.random.normal(keys[0], (Bt, S, columns), F32).astype(dtype)
    w, b = 0.5 * jax.random.normal(keys[1], (K, sum(widths))), 0.3 * jax.random.normal(keys[2], (sum(widths),))
    return (x, w, b), tuple(jax.random.normal(key, (Bt, S, width), F32).astype(dtype) for key, width in zip(keys[3:], widths))


def close(a, b, tol, what):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert a.shape == b.shape and np.max(np.abs(a - b)) <= tol * (1.0 + np.max(np.abs(b))), (what, np.max(np.abs(a - b)), np.max(np.abs(b)))


# (Bt, S, the product's columns, start, widths, K, dtype)
CASES = {
    "mamba2, two sequences, one tile: zeros before each sequence": (2, 128, 832, 256, (256, 128, 128), 4, F32),
    "mamba2, three tiles of 128": (1, 384, 832, 256, (256, 128, 128), 4, F32),
    "mamba2, bf16, three tiles of 256, two sequences": (2, 768, 832, 256, (256, 128, 128), 4, BF16),
    "mamba2, two column steps: 3,072 lanes in blocks of 1,024, 256 and 256": (1, 256, 5184, 2048, (2048, 512, 512), 4, F32),
    "mamba1, one output from column 0, two tiles of 512": (1, 1024, 1280, 0, (640,), 4, F32),
    "mamba1, bf16, two sequences, two column steps of 1,280 lanes, three taps": (2, 256, 5120, 0, (2560,), 3, BF16),
}


@pytest.mark.parametrize("case", list(CASES))
def test_the_kernels_are_the_definition_in_every_output_and_gradient(case):
    """Every output and the cotangent of every input (the product, whose columns outside the range take zeros; the
    filter; the bias); and by themselves the rows either side of every tile's edge, where a forward tile reads the halo
    before it and a backward tile the one after it. bf16: the definition's float32 arithmetic rounded once, as the
    kernels'; a cotangent sums its taps in another order."""
    Bt, S, columns, start, widths, K, dtype = CASES[case]
    args, cts = operands(*CASES[case])
    want, pull = jax.vjp(lambda x, w, b: plain(x, w, b, start, widths), *args)
    got, pull_kernel = jax.vjp(lambda x, w, b: cs.conv_silu(x, w, b, start, widths, True), *args)
    tol = 1e-5 if dtype == F32 else 1e-2
    T = cs.rows_a_tile(S)
    edges = np.concatenate([np.arange(max(e - 8, 0), min(e + 8, S)) for e in range(0, S + 1, T)])
    names = [f"y{i}" for i in range(len(widths))], ["dx", "dw", "db"]
    for names, mine, theirs in zip(names, (got, pull_kernel(cts)), (want, pull(cts))):
        assert len(mine) == len(theirs)
        for name, a, b in zip(names, mine, theirs):
            assert a.dtype == b.dtype, name
            close(a, b, tol, name)
            if a.ndim == 3:
                close(a[:, edges], b[:, edges], tol, f"{name} beside a tile's edge")
    dx = pull_kernel(cts)[0]
    assert not np.any(np.asarray(dx[..., :start], np.float32)) and not np.any(np.asarray(dx[..., start + sum(widths):], np.float32))


def test_the_kernels_take_whole_tiles_and_columns_an_index_map_reaches_and_the_chooser_says_xla_off_the_tpu(monkeypatch):
    nemotron, phi4 = (8192, 4096, (4096, 1024, 1024), 4), (8192, 0, (5120,), 4)
    assert cs.fits(*nemotron) and cs.fits(*phi4) and cs.fits(384, 256, (256, 128, 128), 2) and cs.fits(128, 0, (128,), 8)
    # a step's blocks: whole vregs of lanes, LANES_A_STEP at most together, each where a multiple of its width reaches it
    assert [cs.column_steps(*at) for at in ((4096, (4096, 1024, 1024)), (0, (5120,)), (2048, (2048, 512, 512)), (0, (2560,)), (256, (256, 128, 128)))] == [4, 4, 2, 2, 1]
    assert not cs.fits(8192, 128, (256, 128, 128), 4)  # x's columns start at no multiple of their width
    assert not cs.fits(8192, 96, (96,), 4) and not cs.fits(8192, 0, (192,), 4)  # no whole vregs of lanes
    assert not cs.fits(100, 0, (128,), 4) and not cs.fits(8256, 0, (128,), 4)  # no whole tiles of 128 rows or more
    assert not cs.fits(8192, 0, (128,), 1) and not cs.fits(8192, 0, (128,), 9)
    assert cs.path_for(*nemotron) == "xla"  # no TPU here
    monkeypatch.setattr(placement, "pallas_available", lambda: True)
    assert cs.path_for(*nemotron) == cs.path_for(*phi4) == "kernel" and cs.path_for(8192, 128, (256, 128, 128), 4) == "xla"


def tiny(kind, **over):
    base = dict(vocab_size=211, n_layers=1, n_heads=4, d_model=48, d_ff=64, max_seq_len=256, norm="rmsnorm", activation="swiglu", pos_emb="none",
                tie_embeddings=False, layer_kinds=((kind, "dense"),), ssd_heads=16, ssd_head_dim=8, ssd_state=16, ssd_groups=8, ssd_conv=4,
                ssm_inner=128, ssm_state=16, ssm_conv=4, ssm_dt_rank=4)
    return TransformerConfig(**dict(base, **over))


MIXERS = {"ssd": SSDMixer, "ssm": SSMMixer}


def _mixer(kind, S=256, **over):
    mixer = MIXERS[kind](tiny(kind, **over))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, S, 48))
    params = mixer.init(jax.random.PRNGKey(2), x)["params"]
    leaves, tree = jax.tree_util.tree_flatten(params)  # off their start: a bias of zero hides its gradient's path
    params = jax.tree_util.tree_unflatten(tree, [p + 0.05 * jax.random.normal(jax.random.PRNGKey(7 + i), p.shape) for i, p in enumerate(leaves)])
    w = jax.random.normal(jax.random.PRNGKey(3), x.shape)
    out = lambda y: y[0] if isinstance(y, tuple) else y  # (a Mamba-1 layer hands its scan's output on beside its own)
    return (lambda p, x: jnp.sum(out(mixer.apply({"params": p}, x)) * w)), params, x


def _steer(monkeypatch):
    """The convolution's chooser alone says a TPU is there: the scans behind it stay the recurrences."""
    monkeypatch.setattr(cs, "path_for", lambda S, start, widths, K: "kernel" if cs.fits(S, start, widths, K) else "xla")


def _counts():
    return {(path, pass_): regions_traced("mixer/conv", op="conv_silu", path=path, **{"pass": pass_}) for path in ("kernel", "xla") for pass_ in ("fwd", "bwd")}


def _rose(before):
    return {key: now - before[key] for key, now in _counts().items() if now != before[key]}


@pytest.mark.parametrize("kind", list(MIXERS))
def test_a_mixer_on_the_kernels_is_the_mixer_on_xlas_lines_and_each_counts_its_choice_once(kind, monkeypatch):
    """Loss and every leaf's gradient, the kernels interpreted on one side and the plain lines on the other, the token
    recurrence behind both; ``program_regions_traced_total{region="mixer/conv", op="conv_silu", pass, path}`` rises once a
    call site a trace: forward and backward on the kernels, forward alone on XLA's lines (which XLA differentiates), and
    the trainer's first-call key reads the forward's series."""
    loss, params, x = _mixer(kind)
    record = MIXERS[kind]
    assert record.paths["conv_silu_path"] == ("mixer/conv", {"op": "conv_silu", "pass": "fwd"})
    with jax.default_matmul_precision("highest"):
        before, said = _counts(), trainer._paths_traced([record])["conv_silu_path"]
        want = jax.value_and_grad(loss, argnums=(0, 1))(params, x)
        assert _rose(before) == {("xla", "fwd"): 1}
        now = trainer._paths_traced([record])["conv_silu_path"]
        assert tuple(n - s for n, s in zip(now, said)) == (0, 1)
        _steer(monkeypatch)
        before, said = _counts(), now
        got = jax.value_and_grad(loss, argnums=(0, 1))(params, x)
        assert _rose(before) == {("kernel", "fwd"): 1, ("kernel", "bwd"): 1}
        assert tuple(n - s for n, s in zip(trainer._paths_traced([record])["conv_silu_path"], said)) == (1, 0)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree_util.tree_leaves(want)):
        close(a, b, 2e-5, jax.tree_util.keystr(path))
    assert all(float(jnp.max(jnp.abs(g))) > 0 for g in jax.tree_util.tree_leaves(got[1]))


@pytest.mark.parametrize("kind,over", [("ssd", dict(max_seq_len=64)), ("ssd", dict(ssd_groups=4)), ("ssm", dict(ssm_inner=96)), ("ssm", dict(ssm_conv=9))],
                         ids=["rows", "columns", "lanes", "taps"])
def test_a_shape_that_does_not_fit_takes_the_plain_lines_and_counts_xla(kind, over, monkeypatch):
    """Where the backend compiles Mosaic and the shapes do not fit (64 rows; B and C of 64 lanes; 96 lanes; nine taps) the
    layer is XLA's lines, counted so."""
    loss, params, x = _mixer(kind, S=over.get("max_seq_len", 256), **over)
    _steer(monkeypatch)
    before = _counts()
    text = str(jax.make_jaxpr(jax.grad(loss))(params, x))
    assert _rose(before) == {("xla", "fwd"): 1} and "conv_silu" not in text


@pytest.mark.parametrize("kind", list(MIXERS))
def test_a_checkpointed_block_keeps_the_scans_operands_under_the_scans_name_or_makes_them_again_by_the_forward_call(kind, monkeypatch):
    """The kernels' outputs carry no name of their own and their residuals are their inputs. A Mamba-2 layer names x, B
    and C by the scan's name, whose residuals they are (the chip read the step 0.4% faster for 403 MB: ``PERF.md`` section
    6, PR 60): beside what the block keeps with XLA's lines it keeps those three and no other value, ``remat_keeps`` says
    what it said, and its backward runs the backward call and no second forward call. A Mamba-1 layer keeps what it kept
    (the list names its kept product twice) and runs the forward call a second time. Either way the gradients are the unchecked block's on XLA's lines."""
    from jax._src.ad_checkpoint import saved_residuals  # what print_saved_residuals prints, as a list

    cfg, pair = tiny(kind), (kind, "dense")
    positions = jnp.broadcast_to(jnp.arange(256, dtype=jnp.int32), (2, 256))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 256, 48))
    params = Block(cfg, pair).init(jax.random.PRNGKey(2), x, positions)["params"]
    w = jax.random.normal(jax.random.PRNGKey(3), x.shape)
    loss = lambda remat: (lambda p, x: jnp.sum(block_fn(cfg, pair, True, remat)(p, x, positions, None, None, {})[0][0] * w))
    with jax.default_matmul_precision("highest"):
        want = jax.grad(loss(False), argnums=(0, 1))(params, x)  # XLA's lines, no checkpoint
        kept = lambda: sorted((why.split(" from ")[0] if why.startswith("named") else "", tuple(aval.shape), str(aval.dtype))
                              for aval, why in saved_residuals(loss(True), params, x))
        parents, names = kept(), remat_keeps(pair)
        _steer(monkeypatch)
        more = kept()
        for entry in parents:
            more.remove(entry)
        # (a kept value is listed by the last thing done to it: x, B and C by the reshapes to the scan's heads and groups.
        # Mamba-1's one more line is no new value: the kept product ``[u, z]`` again, which the jitted forward call, whose
        # trace the layers share and the backward runs a second time, hands on as an output of its own: one array)
        assert more == [("", (2, 256, 128 if kind == "ssd" else 256), "float32")] * (3 if kind == "ssd" else 1) and remat_keeps(pair) == names
        text = str(jax.make_jaxpr(jax.grad(loss(True)))(params, x))
        assert (text.count("name=conv_silu_fwd"), text.count("name=conv_silu_bwd")) == ((1, 1) if kind == "ssd" else (2, 1))
        got = jax.grad(loss(True), argnums=(0, 1))(params, x)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree_util.tree_leaves(want)):
        close(a, b, 2e-5, jax.tree_util.keystr(path))
