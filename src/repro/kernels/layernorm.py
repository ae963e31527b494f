"""Fused LayerNorm Pallas kernel — the paper's Fig. 1 flagship pattern.

One kernel computes mean, variance, normalization and the affine epilogue
with every intermediate staged in VMEM (*block composition*): the two
reductions live mid-kernel, which XLA's thread-local fusion refuses to do
(paper §2.1).  BlockSpec tiles rows; the feature axis stays whole in VMEM
(d_model <= 8192 for every assigned arch -> <= 4 MiB per 128-row block).

Forward returns (y, mean, rstd); the analytic backward consumes the saved
statistics (standard recompute-free LN VJP).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import kernels


def _ln_kernel(x_ref, g_ref, b_ref, y_ref, mean_ref, rstd_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)            # [br, C]
    mean = jnp.mean(x, axis=-1, keepdims=True)    # staged in VMEM
    xc = x - mean
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    y = xc * rstd * g_ref[...].astype(jnp.float32) + b_ref[...].astype(jnp.float32)
    y_ref[...] = y.astype(y_ref.dtype)
    mean_ref[...] = mean.astype(mean_ref.dtype)
    rstd_ref[...] = rstd.astype(rstd_ref.dtype)


def layernorm_fwd(x, gamma, beta, *, eps: float = 1e-6, block_rows: int = 128):
    orig_shape = x.shape
    C = x.shape[-1]
    R = x.size // C
    x2 = x.reshape(R, C)
    br = max(1, min(block_rows, R))
    Rp = math.ceil(R / br) * br
    if Rp != R:
        x2 = jnp.pad(x2, ((0, Rp - R), (0, 0)))

    y, mean, rstd = pl.pallas_call(
        functools.partial(_ln_kernel, eps=eps),
        grid=(Rp // br,),
        in_specs=[
            pl.BlockSpec((br, C), lambda i: (i, 0)),
            pl.BlockSpec((1, C), lambda i: (0, 0)),
            pl.BlockSpec((1, C), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, C), lambda i: (i, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Rp, C), x.dtype),
            jax.ShapeDtypeStruct((Rp, 1), jnp.float32),
            jax.ShapeDtypeStruct((Rp, 1), jnp.float32),
        ],
        name="layernorm",
        interpret=kernels.interpret_mode(),
    )(x2, gamma.reshape(1, C), beta.reshape(1, C))
    y = y[:R].reshape(orig_shape)
    return y, (mean[:R], rstd[:R])


def _ln_bwd_kernel(x_ref, g_ref, mean_ref, rstd_ref, dy_ref,
                   dx_ref, dgp_ref, dbp_ref):
    """Stitched LN backward: dx plus per-block dgamma/dbeta partials.

    Same block-composition shape as the forward: the two row reductions
    (m1, m2) stay in VMEM mid-kernel.  Cross-row dgamma/dbeta reductions
    emit one [C]-wide partial per grid step, accumulated in VMEM scratch
    semantics via the sequential grid (finalized outside by a cheap sum
    over n_blocks rows).
    """
    xf = x_ref[...].astype(jnp.float32)
    dyf = dy_ref[...].astype(jnp.float32)
    mean = mean_ref[...]
    rstd = rstd_ref[...]
    xhat = (xf - mean) * rstd
    gdy = dyf * g_ref[...].astype(jnp.float32)
    m1 = jnp.mean(gdy, axis=-1, keepdims=True)        # reduction mid-kernel
    m2 = jnp.mean(gdy * xhat, axis=-1, keepdims=True)
    dx_ref[...] = (rstd * (gdy - m1 - xhat * m2)).astype(dx_ref.dtype)
    dgp_ref[...] = jnp.sum(dyf * xhat, axis=0, keepdims=True)
    dbp_ref[...] = jnp.sum(dyf, axis=0, keepdims=True)


def _ln_bwd(x2, gamma, mean, rstd, dy2, *, block_rows: int = 128,
            use_pallas: bool = True):
    """Analytic LN backward; Pallas kernel with jnp fallback."""
    if not use_pallas:
        xf = x2.astype(jnp.float32)
        dyf = dy2.astype(jnp.float32)
        xhat = (xf - mean) * rstd
        gdy = dyf * gamma.astype(jnp.float32)
        m1 = jnp.mean(gdy, axis=-1, keepdims=True)
        m2 = jnp.mean(gdy * xhat, axis=-1, keepdims=True)
        dx = rstd * (gdy - m1 - xhat * m2)
        return (dx.astype(x2.dtype), jnp.sum(dyf * xhat, axis=0),
                jnp.sum(dyf, axis=0))

    R, C = x2.shape
    br = max(1, min(block_rows, R))
    Rp = math.ceil(R / br) * br
    if Rp != R:  # pad with zero dy so partials are unaffected
        x2 = jnp.pad(x2, ((0, Rp - R), (0, 0)))
        dy2 = jnp.pad(dy2, ((0, Rp - R), (0, 0)))
        mean = jnp.pad(mean, ((0, Rp - R), (0, 0)))
        rstd = jnp.pad(rstd, ((0, Rp - R), (0, 0)), constant_values=1.0)
    nb = Rp // br
    dx, dgp, dbp = pl.pallas_call(
        _ln_bwd_kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((br, C), lambda i: (i, 0)),
            pl.BlockSpec((1, C), lambda i: (0, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
            pl.BlockSpec((br, C), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, C), lambda i: (i, 0)),
            pl.BlockSpec((1, C), lambda i: (i, 0)),
            pl.BlockSpec((1, C), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Rp, C), x2.dtype),
            jax.ShapeDtypeStruct((nb, C), jnp.float32),
            jax.ShapeDtypeStruct((nb, C), jnp.float32),
        ],
        name="layernorm_bwd",
        interpret=kernels.interpret_mode(),
    )(x2, gamma.reshape(1, C).astype(jnp.float32), mean, rstd, dy2)
    return dx[:R], jnp.sum(dgp, axis=0), jnp.sum(dbp, axis=0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def layernorm(x, gamma, beta, eps: float = 1e-6):
    y, _ = layernorm_fwd(x, gamma, beta, eps=eps)
    return y


def _fwd(x, gamma, beta, eps):
    y, (mean, rstd) = layernorm_fwd(x, gamma, beta, eps=eps)
    return y, (x, gamma, mean, rstd)


def _bwd(eps, res, dy):
    x, gamma, mean, rstd = res
    C = x.shape[-1]
    R = x.size // C
    dx, dg, db = _ln_bwd(x.reshape(R, C), gamma, mean, rstd,
                         dy.reshape(R, C))
    return (dx.reshape(x.shape), dg.astype(gamma.dtype),
            db.astype(gamma.dtype))


layernorm.defvjp(_fwd, _bwd)
