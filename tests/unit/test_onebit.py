"""1-bit optimizers + compressed collectives.

Mirrors reference ``tests/onebit/`` + ``tests/unit/runtime/comm/
test_coalesced_collectives.py``: compression round-trip error bounds,
error-feedback accumulation, cross-worker agreement inside shard_map,
convergence of the compressed optimizers on a toy problem vs plain Adam.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

from deepspeed_tpu.runtime.comm.compressed import (all_to_all_quant_reduce, compress_1bit, compressed_allreduce)
from deepspeed_tpu.runtime.fp16.onebit import onebit_adam, onebit_lamb, zero_one_adam


def _mesh():
    return Mesh(np.array(jax.devices()[:8]), ("data",))


def test_compress_1bit_error_feedback():
    x = jnp.asarray([1.0, -2.0, 3.0, -4.0])
    err = jnp.zeros(4)
    sign, scale, new_err = compress_1bit(x, err)
    np.testing.assert_array_equal(np.asarray(sign), [1, -1, 1, -1])
    assert scale.shape == (1,) and np.isclose(float(scale[0]), 2.5)  # one scale per row
    # error = residual; feeding it back reduces long-run bias
    np.testing.assert_allclose(np.asarray(new_err), np.asarray(x) - 2.5 * np.asarray(sign), rtol=1e-6)
    # second round with feedback: compensated = x + err
    sign2, scale2, _ = compress_1bit(x, new_err)
    assert float(scale2[0]) != float(scale[0])
    # 2-D input: independent scale per row
    x2 = jnp.stack([x, 10 * x])
    _, scales, _ = compress_1bit(x2, jnp.zeros_like(x2))
    assert scales.shape == (2, 1) and np.isclose(float(scales[1, 0]), 25.0)


def test_compressed_allreduce_agrees_across_workers():
    mesh = _mesh()
    n = 64
    rng = np.random.RandomState(0)
    per_worker = rng.randn(8, n).astype(np.float32)  # distinct vector per worker

    @partial(shard_map, mesh=mesh, in_specs=(P("data"), P("data"), P("data")),
             out_specs=(P("data"), P("data"), P("data")))
    def run(x, werr, serr):
        out, ne, nse = compressed_allreduce(x[0], werr[0], serr[0], "data")
        return out[None], ne[None], nse[None]

    werr = np.zeros((8, n), np.float32)
    serr = np.zeros((8, n // 8), np.float32)
    out, new_werr, new_serr = run(per_worker, werr, serr)
    out = np.asarray(out)
    # every worker ends with the same averaged vector
    for w in range(1, 8):
        np.testing.assert_allclose(out[0], out[w], rtol=1e-6)
    # and it's a reasonable approximation of the true mean (1-bit: coarse,
    # but correlated — check sign agreement dominates)
    true_mean = per_worker.mean(axis=0)
    agree = np.mean(np.sign(out[0]) == np.sign(true_mean))
    assert agree > 0.7


@pytest.mark.nightly  # ~7 min on a 1-core box: the long error-feedback convergence run
def test_compressed_allreduce_error_feedback_converges():
    """Repeatedly reducing the SAME vectors with error feedback must drive
    the accumulated estimate toward the true mean (the 1-bit Adam claim)."""
    mesh = _mesh()
    n = 32
    rng = np.random.RandomState(1)
    per_worker = rng.randn(8, n).astype(np.float32)
    true_mean = per_worker.mean(axis=0)

    @partial(shard_map, mesh=mesh, in_specs=(P("data"), P("data"), P("data")),
             out_specs=(P("data"), P("data"), P("data")))
    def run(x, werr, serr):
        out, ne, nse = compressed_allreduce(x[0], werr[0], serr[0], "data")
        return out[None], ne[None], nse[None]

    werr = np.zeros((8, n), np.float32)
    serr = np.zeros((8, n // 8), np.float32)
    acc = np.zeros(n, np.float64)
    for t in range(1, 41):
        out, werr, serr = run(per_worker, np.asarray(werr), np.asarray(serr))
        acc += np.asarray(out)[0]
    # time-averaged estimate approaches the true mean
    np.testing.assert_allclose(acc / 40, true_mean, atol=0.2)


def test_all_to_all_quant_reduce():
    mesh = _mesh()
    n = 64
    rng = np.random.RandomState(2)
    per_worker = rng.randn(8, n).astype(np.float32)

    @partial(shard_map, mesh=mesh, in_specs=(P("data"),), out_specs=P("data"))
    def run(x):
        return all_to_all_quant_reduce(x[0], "data")[None]

    out = np.asarray(run(per_worker))
    true_mean = per_worker.mean(axis=0)
    for w in range(8):
        np.testing.assert_allclose(out[w], true_mean, atol=0.05)  # int8: tight


def test_size_must_divide():
    mesh = _mesh()

    @partial(shard_map, mesh=mesh, in_specs=(P("data"),), out_specs=P("data"))
    def run(x):
        return all_to_all_quant_reduce(x[0], "data")[None]

    with pytest.raises(ValueError):
        run(np.zeros((8, 9), np.float32))


def test_reduce_scatter_coalesced():
    from deepspeed_tpu.runtime.comm.compressed import reduce_scatter_coalesced

    mesh = _mesh()
    rng = np.random.RandomState(3)
    a = rng.randn(8, 6).astype(np.float32)  # per-worker tensor pair, 6+3=9 -> pads to 16
    b = rng.randn(8, 3).astype(np.float32)

    @partial(shard_map, mesh=mesh, in_specs=(P("data"), P("data")), out_specs=P("data"))
    def run(x, y):
        return reduce_scatter_coalesced([x[0], y[0]], "data")[None]

    out = np.asarray(run(a, b))  # (8, 2): each worker's shard of the padded mean
    full_mean = np.concatenate([a, b], axis=1).mean(axis=0)
    padded = np.pad(full_mean, (0, 16 - 9))
    np.testing.assert_allclose(out.reshape(-1), padded, rtol=1e-5)


def test_onebit_adam_warmup_syncs_across_workers():
    """During warmup every worker must apply the SAME (allreduced) update —
    regression for unsynced local warmup steps."""
    mesh = _mesh()
    opt = onebit_adam(learning_rate=0.1, freeze_step=1000, axis_name="data", world=8)
    rng = np.random.RandomState(4)
    per_worker_grads = rng.randn(8, 16).astype(np.float32)
    params = {"w": jnp.zeros((16,), jnp.float32)}
    state = opt.init(params)

    @partial(shard_map, mesh=mesh, in_specs=(P(), P(), P("data")), out_specs=P("data"),
             check_vma=False)
    def one_step(p, s, g):
        updates, _ = opt.update({"w": g[0]}, s, p)
        return updates["w"][None]

    ups = np.asarray(one_step(params, state, per_worker_grads))
    for w in range(1, 8):
        np.testing.assert_allclose(ups[0], ups[w], rtol=1e-6)


# -------------------- optimizers --------------------
def _train_quadratic(opt, steps=200, seed=0):
    """Minimize ||Aw - b||^2; returns final loss."""
    rng = np.random.RandomState(seed)
    A = jnp.asarray(rng.randn(16, 8).astype(np.float32))
    b = jnp.asarray(rng.randn(16).astype(np.float32))
    params = {"w": jnp.zeros((8,), jnp.float32)}
    state = opt.init(params)

    @jax.jit
    def step(params, state):
        loss, grads = jax.value_and_grad(lambda p: jnp.mean((A @ p["w"] - b)**2))(params)
        updates, state = opt.update(grads, state, params)
        return optax.apply_updates(params, updates), state, loss

    for _ in range(steps):
        params, state, loss = step(params, state)
    return float(loss)


def test_onebit_adam_converges():
    loss = _train_quadratic(onebit_adam(learning_rate=0.05, freeze_step=50))
    baseline = _train_quadratic(optax.adam(0.05))
    assert loss < baseline * 3 + 0.05  # compressed phase still converges


def test_zero_one_adam_converges():
    # 0/1 Adam skips bias correction (like the reference), so it wants a
    # gentler lr on a cold start
    start = _train_quadratic(zero_one_adam(learning_rate=0.01, var_freeze_step=400), steps=1)
    loss = _train_quadratic(zero_one_adam(learning_rate=0.01, var_freeze_step=400), steps=400)
    assert loss < start


def test_onebit_lamb_converges():
    loss = _train_quadratic(onebit_lamb(learning_rate=0.05, freeze_step=50))
    assert loss < 0.5


def test_onebit_adam_warmup_matches_adam():
    """During warmup the update rule is exactly Adam (no compression).

    The reference applies no bias correction (onebit/adam.py:194) — our
    default matches it; ``bias_correction=True`` recovers textbook Adam,
    which is what optax.adam implements."""
    opt_1bit = onebit_adam(learning_rate=0.01, freeze_step=10**9, bias_correction=True)
    opt_ref = optax.adam(0.01)
    l1 = _train_quadratic(opt_1bit, steps=50)
    l2 = _train_quadratic(opt_ref, steps=50)
    np.testing.assert_allclose(l1, l2, rtol=1e-4)


def test_onebit_adam_default_is_uncorrected():
    """Default update is exp_avg/(sqrt(exp_avg_sq)+eps) — reference parity."""
    import jax.numpy as jnp

    opt = onebit_adam(learning_rate=0.1, freeze_step=10**9)
    p = {"w": jnp.asarray([1.0, 2.0])}
    g = {"w": jnp.asarray([0.5, -0.25])}
    state = opt.init(p)
    upd, _ = opt.update(g, state, p)
    b1, b2, eps = 0.9, 0.999, 1e-8
    m = (1 - b1) * np.asarray(g["w"])
    v = (1 - b2) * np.asarray(g["w"]) ** 2
    expect = -0.1 * m / (np.sqrt(v) + eps)
    np.testing.assert_allclose(np.asarray(upd["w"]), expect, rtol=1e-6)


def test_engine_with_onebit_adam():
    import deepspeed_tpu
    from deepspeed_tpu.models import CausalLM, gpt2_tiny
    from deepspeed_tpu.runtime.dataloader import RepeatingLoader

    cfg = {
        "train_micro_batch_size_per_gpu": 1,
        # uncorrected updates (reference parity) have ~1/sqrt(1-b2) larger
        # magnitude on cold start; keep the lr gentle
        "optimizer": {"type": "OneBitAdam", "params": {"lr": 1e-4, "freeze_step": 2}},
        "zero_optimization": {"stage": 1},
        "steps_per_print": 100,
    }
    model = CausalLM(gpt2_tiny())
    params = model.init(jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 16), np.int32)})
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params, config=cfg)
    rng = np.random.RandomState(0)
    data = [{"input_ids": rng.randint(0, 1024, size=(16,)).astype(np.int32)} for _ in range(16)]
    it = RepeatingLoader(engine.deepspeed_io(data))
    losses = [float(engine.train_batch(it)) for _ in range(6)]  # crosses freeze_step
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0]
