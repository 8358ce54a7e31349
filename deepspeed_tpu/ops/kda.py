"""The gated delta rule with a per-channel decay (Kimi Delta Attention's scan).

``kda(q, k, v, g, beta)``, heads before the sequence: q, k (B, H, S, d_k), v
(B, H, S, d_v), g (B, H, S, d_k) float32 log-decay (<= 0), beta (B, H, S)
float32 in (0, 1); returns (B, H, S, d_v). That is the layout the kernel
walks, so a caller that projects straight into it (``models/mixers.py``)
leaves XLA no transpose to run beside the kernel: with (B, S, H, d) operands
the transposes around the scan took 10 ms of a layer's 36 (my chip run, PR 32).
For each head, from a zero state::

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T,    o_t = S_t^T q_t

On a TPU it is the chunked Pallas kernel (``ops/pallas/kda.py``, forward and
backward); elsewhere ``kda_recurrence``, a ``lax.scan`` over tokens, which is
also the kernel's oracle. The choice is counted where it is made, while a
program is traced (``program_regions_traced_total{region="mixer/kernel",
op="kda", pass, path}``).
"""

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ..telemetry.tracing import region
from .registry import pallas_available

SAVED = "kda_scan"  # the name the kernel's outputs carry for a checkpoint policy


def _traced(pass_: str, path: str):
    """The region of a scan that was traced as ``path``, counted."""
    return region("mixer/kernel", op="kda", path=path, **{"pass": pass_})


def kda_recurrence(q, k, v, g, beta):
    """Token by token, state in float32: the definition."""
    f32 = jnp.float32
    B, H, S, dk = q.shape
    dv = v.shape[-1]

    def step(state, xs):  # state (B, H, d_k, d_v)
        q_t, k_t, v_t, g_t, b_t = xs
        state = state * jnp.exp(g_t)[..., None]
        u = b_t[..., None] * (v_t - jnp.einsum("bhk,bhkv->bhv", k_t, state))
        state = state + k_t[..., None] * u[..., None, :]
        return state, jnp.einsum("bhk,bhkv->bhv", q_t, state)

    time_major = lambda x: jnp.moveaxis(x.astype(f32), 2, 0)
    _, o = jax.lax.scan(step, jnp.zeros((B, H, dk, dv), f32), tuple(time_major(x) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 2).astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _scan(q, k, kb, vb, g, interpret):
    from .pallas import kda as kernel

    return kernel.scan_fwd(q, k, kb, vb, g, interpret)[0]


def _scan_fwd(q, k, kb, vb, g, interpret):
    from .pallas import kda as kernel

    # named, all three (outputs, every chunk's incoming state and its (I + A)^-1), so that a block under jax.checkpoint
    # keeps them (models/transformer.py::block_fn) and its backward does not run the scan a second time to get them back
    with _traced("fwd", "kernel"):
        o, states, inverses = (checkpoint_name(x, SAVED) for x in kernel.scan_fwd(q, k, kb, vb, g, interpret))
    return o, (q, k, kb, vb, g, states, inverses)


def _scan_bwd(interpret, res, do):
    from .pallas import kda as kernel

    with _traced("bwd", "kernel"):
        return tuple(kernel.scan_bwd(*res, do, interpret))


_scan.defvjp(_scan_fwd, _scan_bwd)


def kda_chunked(q, k, v, g, beta, interpret: bool = False):
    """The kernel path on whole (or shard-local) operands: pad the sequence
    to whole chunks (a padded token has k = 0 and g = 0, so it leaves the
    state as it was), hand the kernel beta*k and beta*v, which XLA
    differentiates, and fold the heads into the batch."""
    from .pallas.kda import CHUNK

    B, H, S, _ = q.shape
    pad = -S % CHUNK
    beta = beta.astype(jnp.float32)[..., None]
    kb, vb = (beta * k.astype(jnp.float32)).astype(k.dtype), (beta * v.astype(jnp.float32)).astype(v.dtype)

    def rows(x):
        x = jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0))) if pad else x
        return x.reshape(B * H, S + pad, x.shape[-1])

    o = _scan(rows(q), rows(k), rows(kb), rows(vb), rows(g.astype(jnp.float32)), interpret)
    return o.reshape(B, H, S + pad, -1)[:, :, :S]


def kda(q, k, v, g, beta):
    if not pallas_available():
        with _traced("fwd", "xla"):
            return kda_recurrence(q, k, v, g, beta)
    from ..parallel.mesh import get_mesh_topology
    from ..runtime.zero.partition import fit_spec, prune_spec
    from .pallas._utils import on_mesh

    # several chips: the kernel sits in a shard_map over the batch axes and, where it divides the heads, the tensor axis
    topo = get_mesh_topology(required=False)
    spec = P() if topo is None else fit_spec(prune_spec(P(topo.batch_axes, "tensor", None, None), topo), q.shape, topo)
    with region("mixer/kernel"):  # the call with the padding and reshapes around it; ``_scan_fwd`` / ``_scan_bwd`` count the path
        return on_mesh(kda_chunked, (spec, spec, spec, spec, P(*spec[:3])), spec)(q, k, v, g, beta)
