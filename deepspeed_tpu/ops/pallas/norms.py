"""Pallas fused RMSNorm / LayerNorm.

Capability parity: reference ``csrc/transformer/normalize_kernels.cu`` and
``inference/csrc/{layer_norm,rms_norm}.cu``. Row-blocked single-pass
kernels; backward via recompute (jax.checkpoint-style custom_vjp) — the
stats are cheap relative to HBM traffic on TPU.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..registry import REGISTRY, pallas_available
from ..placement import replicated_on_mesh
from ._utils import block_that_divides


def _rms_kernel(x_ref, w_ref, o_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    o_ref[...] = (y * w).astype(o_ref.dtype)


def _ln_kernel(x_ref, w_ref, b_ref, o_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    y = (x - mean) * jax.lax.rsqrt(var + eps)
    o_ref[...] = (y * w + b).astype(o_ref.dtype)


def _rows_block(n_rows: int, want: int = 256) -> int:
    return block_that_divides(n_rows, want)


def _rms_fwd_pallas(x, weight, eps, interpret):
    shape = x.shape
    d = shape[-1]
    x2 = x.reshape(-1, d)
    rows = _rows_block(x2.shape[0])
    out = pl.pallas_call(
        functools.partial(_rms_kernel, eps=eps),
        grid=(x2.shape[0] // rows,),
        in_specs=[pl.BlockSpec((rows, d), lambda i: (i, 0)), pl.BlockSpec((d,), lambda i: (0,))],
        out_specs=pl.BlockSpec((rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x2.shape, x.dtype),
        interpret=interpret,
    )(x2, weight)
    return out.reshape(shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _rms(x, weight, eps, interpret):
    return _rms_fwd_pallas(x, weight, eps, interpret)


def _rms_vjp_fwd(x, weight, eps, interpret):
    return _rms_fwd_pallas(x, weight, eps, interpret), (x, weight)


def _rms_vjp_bwd(eps, interpret, res, g):
    # recompute stats from saved x (cheap vs HBM traffic of saving them)
    x, weight = res
    x32 = x.astype(jnp.float32)
    g32 = g.astype(jnp.float32)
    w32 = weight.astype(jnp.float32)
    r = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    gu = g32 * w32
    s = jnp.mean(gu * x32, axis=-1, keepdims=True)
    dx = r * gu - (r**3) * x32 * s
    dw = jnp.sum((g32 * x32 * r).reshape(-1, x.shape[-1]), axis=0)
    return dx.astype(x.dtype), dw.astype(weight.dtype)


_rms.defvjp(_rms_vjp_fwd, _rms_vjp_bwd)


def rms_norm(x, weight, eps: float = 1e-5, interpret: bool = False):
    return replicated_on_mesh(lambda x, w: _rms(x, w, eps, interpret))(x, weight)


def _ln_fwd_pallas(x, weight, bias, eps, interpret):
    shape = x.shape
    d = shape[-1]
    x2 = x.reshape(-1, d)
    rows = _rows_block(x2.shape[0])
    out = pl.pallas_call(
        functools.partial(_ln_kernel, eps=eps),
        grid=(x2.shape[0] // rows,),
        in_specs=[pl.BlockSpec((rows, d), lambda i: (i, 0)), pl.BlockSpec((d,), lambda i: (0,)),
                  pl.BlockSpec((d,), lambda i: (0,))],
        out_specs=pl.BlockSpec((rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x2.shape, x.dtype),
        interpret=interpret,
    )(x2, weight, bias)
    return out.reshape(shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _ln(x, weight, bias, eps, interpret):
    return _ln_fwd_pallas(x, weight, bias, eps, interpret)


def _ln_vjp_fwd(x, weight, bias, eps, interpret):
    return _ln_fwd_pallas(x, weight, bias, eps, interpret), (x, weight, bias)


def _ln_vjp_bwd(eps, interpret, res, g):
    x, weight, bias = res
    d = x.shape[-1]
    x32 = x.astype(jnp.float32)
    g32 = g.astype(jnp.float32)
    w32 = weight.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = (x32 - mean) * rstd
    gx = g32 * w32
    dx = rstd * (gx - jnp.mean(gx, axis=-1, keepdims=True) - xhat * jnp.mean(gx * xhat, axis=-1, keepdims=True))
    dw = jnp.sum((g32 * xhat).reshape(-1, d), axis=0)
    db = jnp.sum(g32.reshape(-1, d), axis=0)
    return dx.astype(x.dtype), dw.astype(weight.dtype), db.astype(bias.dtype)


_ln.defvjp(_ln_vjp_fwd, _ln_vjp_bwd)


def layer_norm(x, weight, bias, eps: float = 1e-5, interpret: bool = False):
    return replicated_on_mesh(lambda x, w, b: _ln(x, w, b, eps, interpret))(x, weight, bias)


REGISTRY.register("rms_norm", "pallas", rms_norm, is_available=pallas_available, priority=10)
REGISTRY.register("layer_norm", "pallas", layer_norm, is_available=pallas_available, priority=10)


def rms_norm_xla(x, weight, eps: float = 1e-5, **_):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * weight.astype(jnp.float32)).astype(x.dtype)


def layer_norm_xla(x, weight, bias, eps: float = 1e-5, **_):
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
    y = (x32 - mean) * jax.lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)


REGISTRY.register("rms_norm", "xla", rms_norm_xla, priority=0)
REGISTRY.register("layer_norm", "xla", layer_norm_xla, priority=0)
