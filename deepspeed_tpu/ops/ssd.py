"""The scan of a Mamba-2 layer (state-space duality): a state-space recurrence with ONE decay a head and token, whose
input and read-out vectors are shared by the heads of a group.

``ssd(x, delta, A, B, C, D)``: x (Bt, S, H, P); ``delta`` (Bt, S, H) float32 (>= 0); ``A`` (H,) float32 (< 0); B, C (Bt,
S, G, N) with ``H`` a multiple of ``G`` (head ``h`` reads group ``h // (H / G)``); ``D`` (H,); returns y (Bt, S, H, P) in
x's type. For each sequence and head, from a zero state ``S`` in R^{P x N}::

    S_t = exp(delta_t A) S_{t-1} + delta_t x_t B_t^T,    y_t = S_t C_t + D x_t

State and accumulation are float32 whatever the operands' type. On one TPU chip it is the chunked Pallas kernel
(``ops/pallas/ssd.py``: forward, and a backward from the chunk-boundary states the forward saves); on the CPU, on a mesh
of several chips (the kernels sit in no ``shard_map`` yet) and at sizes the kernel's tiles do not take,
``ssd_recurrence``, a ``lax.scan`` over tokens in stretches whose steps the backward makes again, which is also the
kernel's oracle. The choice is counted where it is made, while a program is traced
(``program_regions_traced_total{region="mixer/kernel", op="ssd", pass, path}``).
"""

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..telemetry.tracing import region
from . import placement

# The name the scan kernel's outputs carry for a checkpoint policy: y and every chunk's incoming state. A checkpointed
# hybrid block keeps them (``models/transformer.py::remat_keeps``), so its backward runs no second forward scan
SAVED = "ssd_scan"
STRETCH = 64  # the recurrence: tokens between two states the backward keeps


def ssd_recurrence(x, delta, A, B, C, D, state_dtype=jnp.float32):
    """Token by token, state in float32 (``state_dtype``: rounded to it after every token; a control's): the definition."""
    f32 = jnp.float32
    Bt, S, H, P = x.shape
    rep = H // B.shape[2]
    a = A.astype(f32)

    def step(state, xs):  # state (Bt, H, P, N)
        x_t, dt_t, b_t, c_t = xs
        b_t, c_t = (jnp.repeat(v, rep, axis=1) for v in (b_t, c_t))  # a group's vector for each of its heads
        state = jnp.exp(dt_t * a)[..., None, None] * state + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        state = state.astype(state_dtype).astype(f32)
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t)

    xs = tuple(jnp.moveaxis(v.astype(f32), 1, 0) for v in (x, delta, B, C))
    zero = jnp.zeros((Bt, H, P, B.shape[-1]), f32)
    if S % STRETCH:
        _, y = jax.lax.scan(step, zero, xs)
    else:  # the same steps, a stretch at a time: differentiated, a stretch keeps its first state and makes the rest again
        stretch = jax.checkpoint(lambda state, part: jax.lax.scan(step, state, part))
        _, y = jax.lax.scan(stretch, zero, tuple(v.reshape(S // STRETCH, STRETCH, *v.shape[1:]) for v in xs))
        y = y.reshape(S, Bt, H, P)
    return (jnp.moveaxis(y, 0, 1) + D.astype(f32)[:, None] * x.astype(f32)).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _scan(x, Bm, Cm, cols, lanes, d, P, interpret):
    from .pallas import ssd as kernel

    return kernel.scan_fwd(x, Bm, Cm, cols, lanes, d, P, interpret)[0]


def _scan_fwd(x, Bm, Cm, cols, lanes, d, P, interpret):
    from .pallas import ssd as kernel

    # named, both (the outputs and every chunk's incoming state), so that a block under jax.checkpoint keeps them
    # (models/transformer.py::block_fn) and its backward does not run the forward scan a second time to get them back
    with placement.counted("ssd", "kernel"):
        y, states = (checkpoint_name(v, SAVED) for v in kernel.scan_fwd(x, Bm, Cm, cols, lanes, d, P, interpret))
    return y, (x, Bm, Cm, cols, lanes, d, states)


def _scan_bwd(P, interpret, res, dy):
    from .pallas import ssd as kernel

    with placement.counted("ssd", "kernel", "bwd"):
        *grads, dd = kernel.scan_bwd(*res, dy, P, interpret)
        return (*grads, jnp.sum(dd, axis=(0, 2, 3)).reshape(res[5].shape))  # D's gradient left the kernel a chunk: (Bt, G, chunks, 1, W)


_scan.defvjp(_scan_fwd, _scan_bwd)


def ssd_chunked(x, delta, A, B, C, D, interpret: bool = False):
    """The kernel path: pad the sequence to whole chunks (a padded token has delta = 0, so it leaves the state as it was),
    and hand the kernel what a head and token carry both ways round, a token a row and a token a lane: the cumulative
    log-decay from the chunk's start ``G``, ``exp(G_last - G) delta`` and ``delta``. A few floats a token, made here so
    that XLA differentiates the cumulative sums and the kernel transposes nothing."""
    from .pallas.ssd import CHUNK

    f32 = jnp.float32
    Bt, S, H, P = x.shape
    G = B.shape[2]
    pad = -S % CHUNK
    rows = lambda v: jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2)) if pad else v
    x, delta, B, C = rows(x), rows(delta.astype(f32)), rows(B), rows(C)
    chunks = delta.reshape(Bt, -1, CHUNK, H)
    cum = jnp.cumsum(chunks * A.astype(f32), axis=2)  # G: (Bt, chunks, CHUNK, H)
    by_group = lambda *parts: jnp.concatenate([v.reshape(Bt, S + pad, G, H // G) for v in parts], axis=-1)  # (Bt, S, G, 2 H/G)
    cols = jnp.moveaxis(by_group(cum, jnp.exp(cum[:, :, -1:] - cum) * chunks), 2, 1)  # (Bt, G, S, 2 H/G)
    lanes = jnp.transpose(by_group(cum, chunks), (0, 2, 3, 1))  # (Bt, G, 2 H/G, S)
    d = jnp.repeat(D.astype(f32), P)[None, :]
    flat = lambda v: v.reshape(Bt, S + pad, -1)
    y = _scan(flat(x), flat(B), flat(C), cols, lanes, d, P, interpret)
    return y.reshape(Bt, S + pad, H, P)[:, :S]


def ssd(x, delta, A, B, C, D):
    from .pallas.ssd import fits

    H, P, G, N = x.shape[2], x.shape[3], B.shape[2], B.shape[3]
    if placement.kernel_path(fits=fits(H, P, G, N), has_specs=False) == "xla":  # the kernels sit in no ``shard_map`` yet
        with placement.counted("ssd", "xla"):
            return ssd_recurrence(x, delta, A, B, C, D)
    with region("mixer/kernel"):  # the call with the padding and the per-token floats around it; ``_scan_fwd`` / ``_scan_bwd`` count the path
        return ssd_chunked(x, delta, A, B, C, D)
