"""Compressed collectives: error-compensated 1-bit allreduce + quantized
all-to-all gradient reduction (ZeRO++ qgZ analogue).

Parity: reference ``runtime/comm/nccl.py:51 compressed_allreduce`` (1-bit
Adam/LAMB transport) and ``runtime/comm/coalesced_collectives.py:81
all_to_all_quant_reduce``. The reference moves int8 sign bytes over NCCL
in two phases (reduce-scatter of compressed chunks, then allgather of the
server-side recompression); the TPU-native versions run *inside*
``shard_map`` over a mesh axis, moving int8 over ICI via
``lax.all_to_all`` / ``lax.all_gather`` — same wire format, compiler-
scheduled. All functions are pure: error feedback state is carried by the
caller (the 1-bit optimizers).
"""

from typing import Tuple

import jax
import jax.numpy as jnp


def compress_1bit(x: jnp.ndarray, error: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Error-compensated sign compression, one scale per last-axis row.

    Returns (sign int8 in {-1,+1}, scale f32 (..., 1), new_error).
    scale = ||compensated||_1 / n per row minimizes L2 error for sign codes
    (the reference's per-chunk server scales, ``nccl.py:95``).
    """
    compensated = x + error
    scale = jnp.mean(jnp.abs(compensated), axis=-1, keepdims=True)
    sign = jnp.where(compensated >= 0, jnp.int8(1), jnp.int8(-1))
    decoded = scale * sign.astype(jnp.float32)
    new_error = compensated - decoded
    return sign, scale, new_error


def compressed_allreduce(x: jnp.ndarray, worker_error: jnp.ndarray, server_error: jnp.ndarray,
                         axis_name: str) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Two-phase error-compensated 1-bit allreduce (mean) over ``axis_name``.

    Must be called inside ``shard_map``/``pjit`` with ``axis_name`` bound.
    ``x``: this worker's full vector (replicated shape). ``worker_error``:
    same shape. ``server_error``: shape of one chunk (n // world).
    Returns (averaged vector, new_worker_error, new_server_error).
    """
    world = jax.lax.axis_size(axis_name)
    n = x.size
    if n % world != 0:
        raise ValueError(f"compressed_allreduce needs size {n} divisible by axis size {world} (pad first)")
    flat = x.reshape(world, n // world)

    # phase 1: worker compression (per-chunk scales), all-to-all so each
    # worker gets one chunk of every peer's sign vector (int8 on the wire)
    sign_w, scale_w, new_worker_error = compress_1bit(flat, worker_error.reshape(world, n // world))
    chunks = jax.lax.all_to_all(sign_w[:, None, :], axis_name, split_axis=0, concat_axis=1)[0]  # (world, chunk)
    peer_scales = jax.lax.all_to_all(scale_w[:, None, :], axis_name, split_axis=0, concat_axis=1)[0]  # (world, 1)
    # server-side mean of decoded chunks
    server_chunk = jnp.mean(chunks.astype(jnp.float32) * peer_scales, axis=0)

    # phase 2: server recompression (own error feedback), allgather int8
    sign_s, scale_s, new_server_error = compress_1bit(server_chunk, server_error)
    gathered = jax.lax.all_gather(sign_s, axis_name)  # (world, chunk) int8
    scales_s = jax.lax.all_gather(scale_s, axis_name)  # (world, 1)
    out = (gathered.astype(jnp.float32) * scales_s).reshape(x.shape)
    return out, new_worker_error.reshape(worker_error.shape), new_server_error


def _quantize_int8(x: jnp.ndarray, axis: int = -1) -> Tuple[jnp.ndarray, jnp.ndarray]:
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def all_to_all_quant_reduce(x: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """qgZ-style quantized gradient reduction: int8-quantize, all-to-all so
    each worker owns a chunk, dequant+mean, requantize, allgather. Returns
    the mean over ``axis_name`` (full shape), with int8 wire traffic.

    Reference: ``coalesced_collectives.py:81`` (+ swizzled_quantize.cu /
    quant_reduce.cu kernels, here jnp — XLA fuses the (de)quant math).
    """
    world = jax.lax.axis_size(axis_name)
    n = x.size
    if n % world != 0:
        raise ValueError(f"all_to_all_quant_reduce needs size {n} divisible by axis size {world} (pad first)")
    flat = x.reshape(world, n // world)
    q, scale = _quantize_int8(flat, axis=1)  # per-chunk scale
    chunks = jax.lax.all_to_all(q[:, None, :], axis_name, split_axis=0, concat_axis=1)[0]  # (world, chunk)
    chunk_scales = jax.lax.all_to_all(scale[:, None, :], axis_name, split_axis=0, concat_axis=1)[0]
    owned = jnp.mean(chunks.astype(jnp.float32) * chunk_scales, axis=0)  # (chunk,)
    q2, scale2 = _quantize_int8(owned[None, :], axis=1)
    gathered = jax.lax.all_gather(q2[0], axis_name).astype(jnp.float32)
    scales2 = jax.lax.all_gather(scale2[0], axis_name)
    return (gathered * scales2).reshape(x.shape)


def reduce_scatter_coalesced(tensors, axis_name: str):
    """Flatten a list of tensors, reduce-scatter the concatenation, return
    this worker's shard (reference ``coalesced_collectives.py:31``)."""
    world = jax.lax.axis_size(axis_name)
    flat = jnp.concatenate([t.reshape(-1) for t in tensors])
    pad = (-flat.size) % world
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return jax.lax.psum_scatter(flat.reshape(world, -1), axis_name, scatter_dimension=0, tiled=False) / world
