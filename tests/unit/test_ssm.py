"""The selective scan (``ops/ssm.py``): the Pallas kernel in interpret mode and the plain recurrence against a float64
loop written out here, forward and every operand's gradient; chunk edges; a sequence that is not whole chunks; the
state's type as a control; and what a trace counts."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import ssm
from deepspeed_tpu.ops.pallas import ssm as kernel
from deepspeed_tpu.telemetry.tracing import regions_traced

NAMES = ("u", "delta", "A", "B", "C", "D")


def operands(seed, Bt, S, channels, N=16, dtype=np.float32):
    rng = np.random.default_rng(seed)
    u = rng.standard_normal((Bt, S, channels))
    delta = np.log1p(np.exp(rng.standard_normal((Bt, S, channels)) - 2.0))  # a softplus: steps of 0.05 to 0.5
    A = -np.tile(np.arange(1, N + 1, dtype=np.float64), (channels, 1)) * rng.uniform(0.5, 1.5, (channels, 1))
    B, C = rng.standard_normal((Bt, S, N)), rng.standard_normal((Bt, S, N))
    D = rng.standard_normal(channels)
    return tuple(np.asarray(x, dtype) for x in (u, delta, A, B, C, D))


def loop64(u, delta, A, B, C, D, dy):
    """The definition and its adjoint, token by token in float64: (y, gradients in the operands' order)."""
    u, delta, A, B, C, D, dy = (np.asarray(x, np.float64) for x in (u, delta, A, B, C, D, dy))
    Bt, S, channels = u.shape
    h = np.zeros((Bt, channels, A.shape[1]))
    hs, y = [h], np.zeros_like(u)
    for t in range(S):
        h = np.exp(delta[:, t, :, None] * A) * h + (delta[:, t] * u[:, t])[..., None] * B[:, t, None, :]
        hs.append(h)
        y[:, t] = (h * C[:, t, None, :]).sum(-1) + D * u[:, t]
    du, dd, dA, dB, dC = np.zeros_like(u), np.zeros_like(delta), np.zeros_like(A), np.zeros_like(B), np.zeros_like(C)
    dh = np.zeros_like(h)
    for t in reversed(range(S)):
        dh = dh + dy[:, t, :, None] * C[:, t, None, :]
        dC[:, t] = (dy[:, t, :, None] * hs[t + 1]).sum(1)
        decay = np.exp(delta[:, t, :, None] * A)
        slope = dh * hs[t] * decay
        dA += (slope * delta[:, t, :, None]).sum(0)
        moved = (dh * B[:, t, None, :]).sum(-1)
        dB[:, t] = (dh * (delta[:, t] * u[:, t])[..., None]).sum(1)
        dd[:, t] = (slope * A).sum(-1) + moved * u[:, t]
        du[:, t] = moved * delta[:, t] + dy[:, t] * D
        dh = dh * decay
    return y, (du, dd, dA, dB, dC, (dy * u).sum((0, 1)))


def close(got, want, tol, what):
    err = float(np.max(np.abs(np.asarray(got, np.float64) - want)) / max(np.max(np.abs(want)), 1e-30))
    assert err <= tol, f"{what}: {err:.3e} of its largest entry, over {tol:.0e}"


PATHS = {"kernel": lambda *ops: ssm.ssm_chunked(*ops, interpret=True), "recurrence": ssm.ssm_recurrence}


# S = 320: two whole chunks and half of a third (the kernel pads; the recurrence takes its stretches); 200 is no whole
# stretch of the recurrence either. channels = 384: tiles of 384 forward and 128 backward (the widths that divide it)
@pytest.mark.parametrize("path", sorted(PATHS))
@pytest.mark.parametrize("Bt,S,channels", [(2, 320, 256), (1, 200, 384)])
def test_forward_and_every_gradient_against_the_float64_loop(path, Bt, S, channels):
    """float32 operands: 1e-5 of the largest entry (float32 sums of up to 320 terms; a float32 ``exp``)."""
    ops = operands(S + channels, Bt, S, channels)
    dy = np.random.default_rng(1).standard_normal((Bt, S, channels)).astype(np.float32)
    y64, grads64 = loop64(*ops, dy)
    y, vjp = jax.vjp(PATHS[path], *(jnp.asarray(x) for x in ops))
    close(y, y64, 1e-5, "y")
    for name, got, want in zip(NAMES, vjp(jnp.asarray(dy)), grads64):
        close(got, want, 2e-5, f"d{name}")


def test_the_state_crosses_a_chunk_edge_and_padding_leaves_it_alone():
    """A pulse in the first chunk is read out in the third (slow channels), and the outputs of a sequence do not depend on
    what follows it: the first 130 tokens of a longer sequence give the first 130 outputs of the shorter."""
    u, delta, A, B, C, D = operands(3, 1, 384, 128)
    A = np.full_like(A, -0.01)
    u[:, 1:], D[:] = 0.0, 0.0
    y = np.asarray(PATHS["kernel"](*(jnp.asarray(x) for x in (u, delta, A, B, C, D))))
    assert np.abs(y[0, 300:]).max() > 1e-3  # the pulse at token 0 is still there 2.3 chunks on
    whole = operands(4, 1, 256, 128)
    short = tuple(x[:, :130] if x.ndim == 3 else x for x in whole)
    y_whole, y_short = (np.asarray(PATHS["kernel"](*(jnp.asarray(x) for x in ops))) for ops in (whole, short))
    np.testing.assert_allclose(y_short, y_whole[:, :130], rtol=1e-6, atol=1e-6)


def test_bf16_operands_keep_a_float32_state():
    """bf16 u, B, C (the model's type) with a float32 delta: the kernel agrees with the recurrence on the same rounded
    operands to float32's own error, and a state rounded to bf16 after every token (the control) does not: its error
    grows with the tokens it is carried over."""
    ops = operands(5, 1, 256, 128)
    rounded = tuple(jnp.asarray(x, jnp.bfloat16 if name in ("u", "B", "C") else jnp.float32) for name, x in zip(NAMES, ops))
    y64, _ = loop64(*(np.asarray(x, np.float32) for x in rounded), np.zeros((1, 256, 128)))
    y = PATHS["kernel"](*rounded)
    assert y.dtype == jnp.bfloat16
    close(y, y64, 4e-3, "y in bf16")  # the output's own rounding: 2^-9 of an entry
    u, delta, A, B, C, D = rounded
    wide = lambda x: jnp.swapaxes(x.astype(jnp.float32), 1, 2)
    exact, low = (kernel.scan_fwd(u.astype(jnp.float32), delta, A.T, wide(B), wide(C), D[None, :], True, state_dtype=t)[0]
                  for t in (jnp.float32, jnp.bfloat16))
    close(exact, y64, 1e-5, "float32 state")
    with pytest.raises(AssertionError):
        close(low, y64, 1e-3, "bf16 state")


def test_a_trace_counts_the_path_it_took():
    ops = tuple(jnp.asarray(x) for x in operands(6, 1, 128, 128))
    count = lambda **labels: regions_traced("mixer/kernel", op="ssm", **labels)
    before = count(path="xla"), count(path="kernel", **{"pass": "fwd"}), count(path="kernel", **{"pass": "bwd"})
    jax.make_jaxpr(lambda *o: ssm.selective_scan(*o))(*ops)  # off the TPU: the recurrence
    jax.make_jaxpr(jax.grad(lambda *o: jnp.sum(ssm.ssm_chunked(*o, interpret=True))))(*ops)
    after = count(path="xla"), count(path="kernel", **{"pass": "fwd"}), count(path="kernel", **{"pass": "bwd"})
    assert tuple(b - a for a, b in zip(before, after)) == (1, 1, 1)


def test_channels_come_in_whole_vregs():
    with pytest.raises(ValueError, match="whole vregs"):
        PATHS["kernel"](*(jnp.asarray(x) for x in operands(7, 1, 128, 96)))
