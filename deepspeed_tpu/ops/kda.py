"""The gated delta rule with a per-channel decay (Kimi Delta Attention's scan),
and with one decay a head (Gated DeltaNet's: ``gdn``, at the end).

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
op="kda", pass, path}``), and with the kernel how many heads a grid step of
it works on (``heads_a_step``: the kernel's own rule on the shard-local
operands, ``ops/pallas/kda.py::heads_a_step``).
"""

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ..telemetry.tracing import region
from . import placement

# The name the scan kernel's outputs carry for a checkpoint policy: the scan's result, every chunk's incoming state and its
# (I + A)^-1. A checkpointed hybrid block keeps them (``models/transformer.py::remat_keeps``), so its backward runs no
# second scan; the kernel's operands carry no name and follow, elementwise, from the projections the block keeps
SAVED = "kda_scan"
# for a kind's record (``LayerKind.joined``): the series that say how many heads a grid step of the kernel works on (the
# values ``ops/pallas/kda.py::heads_a_step`` can give)
HEADS_A_STEP = ("mixer/kernel", ("1", "2", "4"), "heads_a_step")


def _kernel_traced(pass_: str, op: str, q, vb, g):
    """The region of a scan (``op``: "kda", or "gdn" for one decay a head) that was traced as the kernel, counted with
    the heads a grid step of this call works on."""
    from .pallas.kda import heads_a_step

    return placement.counted(op, "kernel", pass_, heads_a_step=str(heads_a_step(q, vb, g, pass_ == "bwd")))


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


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _scan(q, k, kb, vb, g, op, interpret):
    from .pallas import kda as kernel

    return kernel.scan_fwd(q, k, kb, vb, g, interpret)[0]


def _scan_fwd(q, k, kb, vb, g, op, interpret):
    from .pallas import kda as kernel

    # named, all three (outputs, every chunk's incoming state and its (I + A)^-1), so that a block under jax.checkpoint
    # keeps them (models/transformer.py::block_fn) and its backward does not run the scan a second time to get them back
    with _kernel_traced("fwd", op, q, vb, g):
        o, states, inverses = (checkpoint_name(x, SAVED) for x in kernel.scan_fwd(q, k, kb, vb, g, interpret))
    return o, (q, k, kb, vb, g, states, inverses)


def _scan_bwd(op, interpret, res, do):
    from .pallas import kda as kernel

    q, _, _, vb, g = res[:5]
    with _kernel_traced("bwd", op, q, vb, g):
        dq, dk, *rest = kernel.scan_bwd(*res, do, interpret)
        if dq.shape != q.shape:  # value heads that share a key head: each gave its own dq and dk
            dq, dk = (x.reshape(q.shape[0], -1, *q.shape[1:]).astype(jnp.float32).sum(1).astype(q.dtype) for x in (dq, dk))
        return (dq, dk, *rest)


_scan.defvjp(_scan_fwd, _scan_bwd)


def kda_scan(q, k, kb, vb, g, interpret: bool = False):
    """The kernel on operands made already (``kb``, ``vb``: beta*k, beta*v), whole or shard-local: pad the sequence
    to whole chunks (a padded token has k = 0 and g = 0, so it leaves the
    state as it was) and fold the heads into the batch."""
    from .pallas.kda import CHUNK

    B, H, S, _ = q.shape
    pad = -S % CHUNK

    def rows(x):
        x = jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0))) if pad else x
        return x.reshape(B * H, S + pad, x.shape[-1])

    o = _scan(rows(q), rows(k), rows(kb), rows(vb), rows(g.astype(jnp.float32)), "kda", interpret)
    return o.reshape(B, H, S + pad, -1)[:, :, :S]


def kda_chunked(q, k, v, g, beta, interpret: bool = False):
    """The kernel path on whole (or shard-local) operands: hand ``kda_scan`` beta*k and beta*v, which XLA
    differentiates."""
    beta = beta.astype(jnp.float32)[..., None]
    kb, vb = (beta * k.astype(jnp.float32)).astype(k.dtype), (beta * v.astype(jnp.float32)).astype(v.dtype)
    return kda_scan(q, k, kb, vb, g, interpret)


def kda(q, k, v, g, beta):
    if placement.kernel_path() == "xla":
        with placement.counted("kda", "xla"):
            return kda_recurrence(q, k, v, g, beta)
    # several chips: the kernel sits in a shard_map over the batch axes and, where it divides the heads, the tensor axis
    spec = placement.batch_spec(q.shape, "tensor", None, None)
    with region("mixer/kernel"):  # the call with the padding and reshapes around it; ``_scan_fwd`` / ``_scan_bwd`` count the path
        return placement.on_mesh(kda_chunked, (spec, spec, spec, spec, P(*spec[:3])), spec)(q, k, v, g, beta)


# ---------------------------------------------------------------------------
# One decay a head (Gated DeltaNet): ``Diag(exp(g_t))`` is ``exp(g_t) I``
# ---------------------------------------------------------------------------
def _to_value_heads(x, heads: int):
    return x if x.shape[1] == heads else jnp.repeat(x, heads // x.shape[1], axis=1)


def gdn_recurrence(q, k, v, g, beta):
    """Token by token: ``kda_recurrence`` with the one decay for every channel
    and a key head's q and k for each of its value heads."""
    H = v.shape[1]
    return kda_recurrence(_to_value_heads(q, H), _to_value_heads(k, H), v, g[..., None], beta)


def gdn_scan(q, k, kb, vb, g, interpret: bool = False):
    """``kda_scan`` for one decay a head and token (g (B, H_v, S)): q and k keep their own
    (fewer) heads, the kernel reads a key head's blocks for each of its value
    heads, and the log-decay goes in along the lanes, a chunk a row."""
    from .pallas.kda import CHUNK

    B, H, S, _ = vb.shape
    pad = -S % CHUNK

    def rows(x):
        x = jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0))) if pad else x
        return x.reshape(-1, S + pad, x.shape[-1])

    g = jnp.pad(g.astype(jnp.float32), ((0, 0), (0, 0), (0, pad))) if pad else g.astype(jnp.float32)
    o = _scan(rows(q), rows(k), rows(kb), rows(vb), g.reshape(-1, 1, CHUNK), "gdn", interpret)
    return o.reshape(B, H, S + pad, -1)[:, :, :S]


def gdn_chunked(q, k, v, g, beta, interpret: bool = False):
    """``kda_chunked`` for one decay a head and token: beta*k a VALUE head (its key head's k) and beta*v."""
    H = v.shape[1]
    beta = beta.astype(jnp.float32)[..., None]
    kb = (beta * _to_value_heads(k, H).astype(jnp.float32)).astype(k.dtype)
    vb = (beta * v.astype(jnp.float32)).astype(v.dtype)
    return gdn_scan(q, k, kb, vb, g, interpret)


def gdn(q, k, v, g, beta):
    """The gated delta rule with ONE decay a head and token: q, k (B, H_k, S,
    d_k), v (B, H_v, S, d_v) with ``H_v`` a multiple of ``H_k`` (value head h
    reads key head ``h // (H_v / H_k)``), g and beta (B, H_v, S) float32;
    returns (B, H_v, S, d_v). For each value head, from a zero state::

        S_t = (I - beta_t k_t k_t^T) exp(g_t) S_{t-1} + beta_t k_t v_t^T,    o_t = S_t^T q_t

    On a TPU the chunked kernel's per-head form (``ops/pallas/kda.py``:
    ``gdn_scan_fwd`` / ``gdn_scan_bwd``), elsewhere the recurrence; the choice
    is counted as ``kda``'s is, under ``op="gdn"``."""
    if placement.kernel_path() == "xla":
        with placement.counted("gdn", "xla"):
            return gdn_recurrence(q, k, v, g, beta)
    spec = placement.batch_spec(q.shape, None, None, None)  # several chips: a shard_map over the batch axes
    with region("mixer/kernel"):
        return placement.on_mesh(gdn_chunked, (spec, spec, spec, P(*spec[:3]), P(*spec[:3])), spec)(q, k, v, g, beta)
