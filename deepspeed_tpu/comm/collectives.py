"""In-jit collectives over named mesh axes.

These are the compute-path primitives: torch.distributed-shaped functions
(parity with reference ``deepspeed/comm/comm.py``: ``all_reduce`` :483,
``all_gather_into_tensor`` :297, ``reduce_scatter_tensor`` :280,
``all_to_all_single`` :331) expressed as ``jax.lax`` collectives. They must
be called from inside ``shard_map`` (or a ``pmap``-like context) where the
named axis is bound; XLA lowers them onto ICI/DCN. There are no group
handles — a "group" is a mesh axis name or tuple of names.
"""

from typing import Optional, Sequence, Union

import jax.numpy as jnp
from jax import lax

from ..analysis import comm_audit
from .reduce_op import ReduceOp

AxisName = Union[str, Sequence[str]]


def _audit(op: str, tensor, axis: AxisName) -> None:
    """Trace-time choreography recording (DS_TPU_COMM_AUDIT): runs once per
    trace, never in the compiled program, so the serving path stays free."""
    aud = comm_audit.get_auditor()
    if aud is not None:
        aud.record(op, str(getattr(tensor, "dtype", "")),
                   tuple(getattr(tensor, "shape", ()) or ()), axis=str(axis))


def _psum_like(tensor, axis_name: AxisName, op: ReduceOp):
    if op in (ReduceOp.SUM, ReduceOp.AVG):
        out = lax.psum(tensor, axis_name)
        if op == ReduceOp.AVG:
            out = out / lax.psum(jnp.ones((), dtype=tensor.dtype), axis_name)
        return out
    if op == ReduceOp.MAX:
        return lax.pmax(tensor, axis_name)
    if op == ReduceOp.MIN:
        return lax.pmin(tensor, axis_name)
    if op == ReduceOp.PRODUCT:
        return jnp.exp(lax.psum(jnp.log(tensor), axis_name))
    raise NotImplementedError(f"ReduceOp {op} not supported on TPU collectives")


def all_reduce(tensor, op: ReduceOp = ReduceOp.SUM, group: AxisName = "data"):
    """Reference ``comm.py:483``. Sum (or max/min/avg) across the axis."""
    _audit("all_reduce", tensor, group)
    return _psum_like(tensor, group, op)


def inference_all_reduce(tensor, op: ReduceOp = ReduceOp.SUM, group: AxisName = "tensor"):
    """Reference ``comm.py:500`` — the TP-inference row-parallel reduce."""
    return _psum_like(tensor, group, op)


def _tp_reduce_chunk(x, group: AxisName, bits: int):
    if bits <= 0:
        return lax.psum(x, group)
    # EQuARX-style quantized allreduce: shards agree on a shared per-row
    # scale (pmax of local amax), psum the integer codes exactly, then
    # rescale. Integer summation is associative, so the result is
    # bit-identical regardless of reduction order, and the per-element
    # error is bounded by tp * scale / 2 (each shard's rounding error is
    # at most scale/2). A real TPU build would fuse this into the XLA
    # allreduce; here we emulate the semantics and account bytes at
    # bits/8 per element.
    qmax = (1 << (bits - 1)) - 1
    xf = x.astype(jnp.float32)
    amax = lax.pmax(jnp.max(jnp.abs(xf), axis=-1, keepdims=True), group)
    scale = jnp.maximum(amax, 1e-30) / qmax
    codes = jnp.clip(jnp.round(xf / scale), -qmax, qmax).astype(jnp.int32)
    return (lax.psum(codes, group).astype(jnp.float32) * scale).astype(x.dtype)


def tp_all_reduce(tensor, group: AxisName = "tensor", bits: int = 0, interleave: int = 1):
    """Row-parallel activation allreduce for TP serving (o_proj / down_proj).

    ``bits > 0`` selects the EQuARX-style quantized reduce (shared scale +
    exact integer-code psum). ``interleave > 1`` splits the hidden dim into
    that many independently-reduced chunks, issuing one collective per
    chunk — the T3-style overlap seam: each chunk's psum is independent of
    the others, so a scheduler that overlaps collectives with the next
    matmul's shards can start it as soon as its slice of the producing
    matmul finishes (XLA only partially exploits this on CPU, but the
    program structure is the one T3 wants). Chunking never changes the
    result: each element is reduced exactly once either way.
    """
    _audit("tp_all_reduce", tensor, group)
    if interleave > 1 and tensor.shape[-1] % interleave == 0:
        chunks = jnp.split(tensor, interleave, axis=-1)
        return jnp.concatenate([_tp_reduce_chunk(c, group, bits) for c in chunks], axis=-1)
    return _tp_reduce_chunk(tensor, group, bits)


def all_gather_into_tensor(tensor, group: AxisName = "data", axis: int = 0, tiled: bool = True):
    """Gather shards along ``axis`` from every member; result is the
    concatenation (``tiled=True``, torch semantics) or stacked (False)."""
    _audit("all_gather_into_tensor", tensor, group)
    return lax.all_gather(tensor, group, axis=axis, tiled=tiled)


def all_gather(tensor, group: AxisName = "data", axis: int = 0):
    _audit("all_gather", tensor, group)
    return lax.all_gather(tensor, group, axis=axis, tiled=True)


def reduce_scatter_tensor(tensor, op: ReduceOp = ReduceOp.SUM, group: AxisName = "data", axis: int = 0):
    """Reference ``comm.py:280``. Sum across members, scatter along ``axis``."""
    if op not in (ReduceOp.SUM, ReduceOp.AVG):
        raise NotImplementedError("reduce_scatter supports SUM/AVG")
    _audit("reduce_scatter_tensor", tensor, group)
    out = lax.psum_scatter(tensor, group, scatter_dimension=axis, tiled=True)
    if op == ReduceOp.AVG:
        out = out / lax.psum(jnp.ones((), dtype=out.dtype), group)
    return out


def all_to_all_single(tensor, group: AxisName = "seq", split_axis: int = 0, concat_axis: int = 0):
    """Reference ``comm.py:331``. Split ``split_axis`` into group-size chunks,
    exchange chunk i with member i, concatenate received chunks on
    ``concat_axis``."""
    _audit("all_to_all_single", tensor, group)
    return lax.all_to_all(tensor, group, split_axis=split_axis, concat_axis=concat_axis, tiled=True)


def all_to_all(output_unused, tensor, group: AxisName = "seq"):
    return all_to_all_single(tensor, group)


def broadcast(tensor, src: int = 0, group: AxisName = "data"):
    """Broadcast the value held by member ``src`` of the axis to all members."""
    idx = lax.axis_index(group)
    masked = jnp.where(idx == src, tensor, jnp.zeros_like(tensor))
    return lax.psum(masked, group)


def reduce(tensor, dst: int = 0, op: ReduceOp = ReduceOp.SUM, group: AxisName = "data"):
    """All members get the reduction; non-dst members keep their input
    (matches torch.reduce observable state on dst)."""
    reduced = _psum_like(tensor, group, op)
    idx = lax.axis_index(group)
    return jnp.where(idx == dst, reduced, tensor)


def ppermute(tensor, perm, group: AxisName = "pipe"):
    _audit("ppermute", tensor, group)
    return lax.ppermute(tensor, group, perm)


def send_recv_ring(tensor, group: AxisName = "pipe", shift: int = 1):
    """Ring shift: member i's tensor goes to member (i+shift) % n."""
    # static size needed: the perm list is built at trace time
    size = axis_size(group)
    perm = [(i, (i + shift) % size) for i in range(size)]
    return lax.ppermute(tensor, group, perm)


def axis_rank(group: AxisName):
    return lax.axis_index(group)


def axis_size(group: AxisName) -> int:
    """Static size of a bound mesh axis."""
    return lax.axis_size(group)


def barrier(group: Optional[AxisName] = None):
    """In-jit barrier is meaningless (XLA orders ops); no-op for parity."""
    return None
