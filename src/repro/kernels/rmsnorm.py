"""Fused RMSNorm Pallas kernel (block composition; see layernorm.py)."""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import kernels


def _rms_kernel(x_ref, g_ref, y_ref, rstd_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(ms + eps)
    y_ref[...] = (x * rstd * g_ref[...].astype(jnp.float32)).astype(y_ref.dtype)
    rstd_ref[...] = rstd.astype(rstd_ref.dtype)


#: VMEM bytes the row blocks may take: the input and output blocks, each
#: double-buffered, stay well inside the chip's 16 MiB scoped limit
BLOCK_VMEM_BYTES = 8 << 20


def rmsnorm_fwd(x, gamma, *, eps: float = 1e-6, block_rows: int = 128):
    orig_shape = x.shape
    C = x.shape[-1]
    R = x.size // C
    x2 = x.reshape(R, C)
    while block_rows > 8 and \
            4 * block_rows * C * x.dtype.itemsize > BLOCK_VMEM_BYTES:
        block_rows //= 2        # wide rows (Granite's 8192): fewer per block
    br = max(1, min(block_rows, R))
    Rp = math.ceil(R / br) * br
    if Rp != R:
        x2 = jnp.pad(x2, ((0, Rp - R), (0, 0)))

    y, rstd = pl.pallas_call(
        functools.partial(_rms_kernel, eps=eps),
        grid=(Rp // br,),
        in_specs=[
            pl.BlockSpec((br, C), lambda i: (i, 0)),
            pl.BlockSpec((1, C), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, C), lambda i: (i, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Rp, C), x.dtype),
            jax.ShapeDtypeStruct((Rp, 1), jnp.float32),
        ],
        name="rmsnorm",
        interpret=kernels.interpret_mode(),
    )(x2, gamma.reshape(1, C))
    return y[:R].reshape(orig_shape), rstd[:R]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def rmsnorm(x, gamma, eps: float = 1e-6):
    y, _ = rmsnorm_fwd(x, gamma, eps=eps)
    return y


def _fwd(x, gamma, eps):
    y, rstd = rmsnorm_fwd(x, gamma, eps=eps)
    return y, (x, gamma, rstd)


def _bwd(eps, res, dy):
    x, gamma, rstd = res
    C = x.shape[-1]
    R = x.size // C
    xf = x.reshape(R, C).astype(jnp.float32)
    dyf = dy.reshape(R, C).astype(jnp.float32)
    gf = gamma.astype(jnp.float32)
    xhat = xf * rstd
    gdy = dyf * gf
    m = jnp.mean(gdy * xhat, axis=-1, keepdims=True)
    dx = rstd * (gdy - xhat * m)
    dgamma = jnp.sum(dyf * xhat, axis=0)
    return dx.reshape(x.shape).astype(x.dtype), dgamma.astype(gamma.dtype)


rmsnorm.defvjp(_fwd, _bwd)
