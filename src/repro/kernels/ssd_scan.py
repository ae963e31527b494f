"""Mamba-2 SSD chunked scan as a Pallas kernel.

The SSD (state-space duality) scan is the archetypal *memory-intensive
recurrence*: per chunk it is a chain of cumsum/exp/segment-sum elementwise
+ reduction ops around two small matmuls.  Stitching the whole chunk into
one kernel keeps the decay matrices, segment sums and the running state in
VMEM across the chunk loop — the paper's block composition applied to a
recurrence (the running state is the cross-step staged intermediate).

Grid: (batch, heads, n_chunks); the chunk axis is sequential and carries
the [P, N] state in VMEM scratch.  B/C projections are shared across
heads (single SSM group), so their index maps ignore the head index.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels


def _ssd_kernel(x_ref, dtc_ref, dtr_ref, a_ref, b_ref, c_ref, y_ref, st_ref,
                h_ref, *, chunk: int):
    z = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(z == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[...].reshape(chunk, -1).astype(jnp.float32)    # [c, P]
    # dt arrives in both orientations: a (c,1) column and a (1,c) row
    # (no in-kernel transpose of a vector)
    dt = dtc_ref[...].reshape(chunk, 1).astype(jnp.float32)  # [c, 1]
    dt_row = dtr_ref[...].reshape(1, chunk).astype(jnp.float32)
    A = a_ref[...].reshape(1, 1)                             # head decay
    B = b_ref[...].reshape(chunk, -1).astype(jnp.float32)    # [c, N]
    C = c_ref[...].reshape(chunk, -1).astype(jnp.float32)    # [c, N]

    a = dt * A                                               # [c,1] log-decay
    a_row = dt_row * A                                       # [1,c]
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = row >= col
    # inclusive cumsum of the log-decay as masked reductions, once per
    # orientation: cum[i] = sum_{j<=i} a[j]
    cum = jnp.sum(jnp.where(causal, a_row, 0.0), axis=1, keepdims=True)
    cum_row = jnp.sum(jnp.where(row <= col, a, 0.0), axis=0, keepdims=True)
    total = jnp.sum(a_row, axis=1, keepdims=True)            # [1,1]

    # intra-chunk quadratic part: Y_intra = (CB^T ⊙ L ⊙ dt) @ X
    seg = cum - cum_row                                      # [c,c] cum_i - cum_j
    L = jnp.where(causal, jnp.exp(seg), 0.0)
    cb = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)   # [c,c]
    w = cb * L * dt_row                                       # weight[i,j]
    y_intra = jax.lax.dot_general(w, x, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    # inter-chunk: Y_inter = (C ⊙ exp(cum)) @ h_prev^T
    h_prev = h_ref[...]                                       # [P, N]
    c_scaled = C * jnp.exp(cum)                               # [c, N]
    y_inter = jax.lax.dot_general(c_scaled, h_prev,
                                  (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)  # [c,P]

    y_ref[...] = (y_intra + y_inter).reshape(y_ref.shape).astype(y_ref.dtype)

    # state update: h = h * exp(cum[-1]) + X^T @ (B ⊙ decay ⊙ dt)
    decay_states = jnp.exp(total - cum)                       # [c,1]
    bw = B * decay_states * dt                                # [c, N]
    h_new = h_prev * jnp.exp(total) + jax.lax.dot_general(
        x, bw, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    h_ref[...] = h_new

    @pl.when(z == nc - 1)
    def _final():
        st_ref[...] = h_new.reshape(st_ref.shape).astype(st_ref.dtype)


def ssd_scan(x, dt, A, B, C, *, chunk: int = 64):
    """Chunked SSD scan (semantics of ``ref.ssd_scan``).

    x: [b, L, H, P]; dt: [b, L, H]; A: [H]; B, C: [b, L, N].
    Returns (y [b, L, H, P], state [b, H, P, N]).
    """
    b, L, H, P = x.shape
    N = B.shape[-1]
    assert L % chunk == 0, "pad sequence to a chunk multiple first"
    nc = L // chunk

    # every block's trailing two dims are whole or tile-aligned (the
    # chip's compiler refuses anything else): dt as [.., c, 1] columns
    # and [.., 1, c] rows, A as [H, 1, 1]
    xc = x.reshape(b, nc, chunk, H, P).transpose(0, 3, 1, 2, 4)   # [b,H,nc,c,P]
    dth = dt.reshape(b, nc, chunk, H).transpose(0, 3, 1, 2)       # [b,H,nc,c]
    dt_col = dth[..., None]                                       # [b,H,nc,c,1]
    dt_row = dth[..., None, :]                                    # [b,H,nc,1,c]
    Bc = B.reshape(b, nc, chunk, N)                               # [b,nc,c,N]
    Cc = C.reshape(b, nc, chunk, N)
    Ah = A.reshape(H, 1, 1).astype(jnp.float32)

    y, state = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=chunk),
        grid=(b, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, 1, chunk, P), lambda i, h, z: (i, h, z, 0, 0)),
            pl.BlockSpec((1, 1, 1, chunk, 1), lambda i, h, z: (i, h, z, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, chunk), lambda i, h, z: (i, h, z, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda i, h, z: (h, 0, 0)),
            pl.BlockSpec((1, 1, chunk, N), lambda i, h, z: (i, z, 0, 0)),
            pl.BlockSpec((1, 1, chunk, N), lambda i, h, z: (i, z, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, chunk, P), lambda i, h, z: (i, h, z, 0, 0)),
            pl.BlockSpec((1, 1, P, N), lambda i, h, z: (i, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, H, nc, chunk, P), x.dtype),
            jax.ShapeDtypeStruct((b, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        name="ssd_scan",
        interpret=kernels.interpret_mode(),
    )(xc, dt_col, dt_row, Ah, Bc, Cc)

    y = y.transpose(0, 2, 3, 1, 4).reshape(b, L, H, P)
    return y, state
