"""The selective scan of a Mamba-1 layer: a diagonal state-space recurrence whose step, input and read-out depend on
the token.

``selective_scan(u, delta, A, B, C, D)``: u, delta (Bt, S, channels), ``A`` (channels, N) float32 (< 0), B, C (Bt, S, N),
``D`` (channels,); returns y (Bt, S, channels) in u's type. For each sequence and channel ``d``, from a zero state::

    h_t[d, :] = exp(delta_t[d] A[d, :]) h_{t-1}[d, :] + delta_t[d] u_t[d] B_t,    y_t[d] = h_t[d, :] . C_t + D[d] u_t[d]

State and accumulation are float32 whatever the operands' type. On one TPU chip it is the Pallas kernel
(``ops/pallas/ssm.py``: forward, and a backward from the chunk-boundary states the forward saves); on the CPU and on a
mesh of several chips ``ssm_recurrence``, a ``lax.scan`` over tokens in stretches whose steps the backward makes again,
which is also the kernel's oracle. The choice is counted where it is made, while a program is traced
(``program_regions_traced_total{region="mixer/kernel", op="ssm", pass, path}``).
"""

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..telemetry.tracing import region
from . import placement

# The name the scan kernel's outputs carry for a checkpoint policy: y and every chunk's incoming state. A checkpointed
# hybrid block keeps them (``models/transformer.py::remat_keeps``), so its backward runs no second forward scan
SAVED = "ssm_scan"
STRETCH = 64  # the recurrence: tokens between two states the backward keeps


def ssm_recurrence(u, delta, A, B, C, D, state_dtype=jnp.float32):
    """Token by token, state in float32 (``state_dtype``: rounded to it after every token; a control's): the definition."""
    f32 = jnp.float32
    Bt, S, channels = u.shape
    a = A.astype(f32)

    def step(h, xs):  # h (Bt, channels, N)
        u_t, dt_t, b_t, c_t = xs
        h = jnp.exp(dt_t[..., None] * a) * h + (dt_t * u_t)[..., None] * b_t[:, None, :]
        h = h.astype(state_dtype).astype(f32)
        return h, jnp.sum(h * c_t[:, None, :], axis=-1)

    xs = tuple(jnp.moveaxis(x.astype(f32), 1, 0) for x in (u, delta, B, C))
    h0 = jnp.zeros((Bt, channels, a.shape[1]), f32)
    if S % STRETCH:
        _, y = jax.lax.scan(step, h0, xs)
    else:  # the same steps, a stretch at a time: differentiated, a stretch keeps its first state and makes the rest again
        stretch = jax.checkpoint(lambda h, part: jax.lax.scan(step, h, part))
        _, y = jax.lax.scan(stretch, h0, tuple(x.reshape(S // STRETCH, STRETCH, *x.shape[1:]) for x in xs))
        y = y.reshape(S, Bt, channels)
    return (jnp.moveaxis(y, 0, 1) + D.astype(f32) * u.astype(f32)).astype(u.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _scan(u, delta, a_t, b_t, c_t, d, interpret):
    from .pallas import ssm as kernel

    return kernel.scan_fwd(u, delta, a_t, b_t, c_t, d, interpret)[0]


def _scan_fwd(u, delta, a_t, b_t, c_t, d, interpret):
    from .pallas import ssm as kernel

    # named, both (the outputs and every chunk's incoming state), so that a block under jax.checkpoint keeps them
    # (models/transformer.py::block_fn) and its backward does not run the forward scan a second time to get them back
    with placement.counted("ssm", "kernel"):
        y, states = (checkpoint_name(x, SAVED) for x in kernel.scan_fwd(u, delta, a_t, b_t, c_t, d, interpret))
    return y, (u, delta, a_t, b_t, c_t, d, states)


def _scan_bwd(interpret, res, dy):
    from .pallas import ssm as kernel

    u, delta, a_t, b_t, c_t, d, states = res
    with placement.counted("ssm", "kernel", "bwd"):
        du, ddelta, da, dbp, dcp, dd = kernel.scan_bwd(u, delta, a_t, b_t, c_t, d, states, dy, interpret)
        # B's and C's gradients left the kernel as partial sums along the lanes, a token a row: (Bt, S, N, 128) -> (Bt, N, S)
        cols = lambda partial, like: jnp.swapaxes(jnp.sum(partial, axis=-1), 1, 2).astype(like.dtype)
        return du, ddelta, jnp.sum(da, axis=0), cols(dbp, b_t), cols(dcp, c_t), jnp.sum(dd, axis=0)


_scan.defvjp(_scan_fwd, _scan_bwd)


def ssm_chunked(u, delta, A, B, C, D, interpret: bool = False):
    """The kernel path: pad the sequence to whole chunks (a padded token has delta = 0, so it leaves the state as it was),
    hand the kernel ``A`` transposed and B and C with time along the lanes, which XLA differentiates."""
    from .pallas.ssm import CHUNK

    f32 = jnp.float32
    S = u.shape[1]
    pad = -S % CHUNK
    rows = lambda x: jnp.pad(x, ((0, 0), (0, pad), (0, 0))) if pad else x
    cols = lambda x: jnp.swapaxes(rows(x).astype(f32), 1, 2)
    y = _scan(rows(u), rows(delta.astype(f32)), A.astype(f32).T, cols(B), cols(C), D.astype(f32)[None, :], interpret)
    return y[:, :S]


def selective_scan(u, delta, A, B, C, D):
    if placement.kernel_path(has_specs=False) == "xla":  # the kernels sit in no ``shard_map`` yet
        with placement.counted("ssm", "xla"):
            return ssm_recurrence(u, delta, A, B, C, D)
    with region("mixer/kernel"):  # the call with the padding and transposes around it; ``_scan_fwd`` / ``_scan_bwd`` count the path
        return ssm_chunked(u, delta, A, B, C, D)
