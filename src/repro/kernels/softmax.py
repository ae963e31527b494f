"""Fused row-softmax Pallas kernel (reduce -> broadcast -> expensive-ew ->
reduce -> broadcast chain stitched in VMEM; paper §2.1's canonical
middle-reduction case)."""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import kernels


def _softmax_kernel(x_ref, y_ref):
    x = x_ref[...].astype(jnp.float32)
    m = jnp.max(x, axis=-1, keepdims=True)     # reduction mid-kernel
    e = jnp.exp(x - m)                          # expensive-ew mid-kernel
    s = jnp.sum(e, axis=-1, keepdims=True)      # second reduction
    y_ref[...] = (e / s).astype(y_ref.dtype)


def softmax_fwd(x, *, block_rows: int = 64):
    orig_shape = x.shape
    C = x.shape[-1]
    R = x.size // C
    x2 = x.reshape(R, C)
    br = max(1, min(block_rows, R))
    Rp = math.ceil(R / br) * br
    if Rp != R:
        x2 = jnp.pad(x2, ((0, Rp - R), (0, 0)))

    y = pl.pallas_call(
        _softmax_kernel,
        grid=(Rp // br,),
        in_specs=[pl.BlockSpec((br, C), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((br, C), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Rp, C), x.dtype),
        name="softmax",
        interpret=kernels.interpret_mode(),
    )(x2)
    return y[:R].reshape(orig_shape)


def _softmax_bwd_kernel(y_ref, dy_ref, dx_ref):
    """Stitched softmax backward: dx = y * (dy - sum(dy*y)) with the row
    reduction staged in VMEM (same block composition as the forward)."""
    yf = y_ref[...].astype(jnp.float32)
    dyf = dy_ref[...].astype(jnp.float32)
    s = jnp.sum(dyf * yf, axis=-1, keepdims=True)
    dx_ref[...] = (yf * (dyf - s)).astype(dx_ref.dtype)


def softmax_bwd(y, dy, *, block_rows: int = 64):
    orig_shape = y.shape
    C = y.shape[-1]
    R = y.size // C
    y2 = y.reshape(R, C)
    dy2 = dy.reshape(R, C)
    br = max(1, min(block_rows, R))
    Rp = math.ceil(R / br) * br
    if Rp != R:
        y2 = jnp.pad(y2, ((0, Rp - R), (0, 0)))
        dy2 = jnp.pad(dy2, ((0, Rp - R), (0, 0)))
    dx = pl.pallas_call(
        _softmax_bwd_kernel,
        grid=(Rp // br,),
        in_specs=[pl.BlockSpec((br, C), lambda i: (i, 0)),
                  pl.BlockSpec((br, C), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((br, C), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Rp, C), y.dtype),
        name="softmax_bwd",
        interpret=kernels.interpret_mode(),
    )(y2, dy2)
    return dx[:R].reshape(orig_shape)


@jax.custom_vjp
def softmax(x):
    return softmax_fwd(x)


def _fwd(x):
    y = softmax_fwd(x)
    return y, (y,)


def _bwd(res, dy):
    (y,) = res
    return (softmax_bwd(y, dy),)


softmax.defvjp(_fwd, _bwd)
