"""Tiled matmul with fused prologue/epilogue (compute-anchored stitching).

The generic stitched emitter stops at the memory/compute divide: a
``dot_general`` is an anchor the planner may open a group *around*, not a
pattern member.  This kernel is the matmul side of that scheme -- the
elementwise/norm chain feeding the contraction runs on the lhs tile
before it hits the MXU, and the residual/norm/activation chain consuming
it runs on the f32 accumulator before the HBM store, so neither chain's
interface tensor ever round-trips HBM.

Grid: one axis over M tiles.  The rhs (K, N) weight panel is resident
per step (the anchored cost model's VMEM feasibility gate guarantees it
fits); the contraction is not split over K, so f32 results are bit-equal
to XLA's single dot.
"""
from __future__ import annotations

import math
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import kernels

#: Block-role strings shared with the emitter (kept as plain strings so
#: this kernel does not import the core planner): how an operand folds
#: into the kernel's 2D row view.
ROLE_FULL, ROLE_ROW, ROLE_COL, ROLE_SCALAR = "full", "row", "col", "scalar"

DEFAULT_BLOCK_M = 128


def _spec_for(role: str, bm: int, C: int):
    if role == ROLE_FULL:
        return pl.BlockSpec((bm, C), lambda i: (i, 0))
    if role == ROLE_ROW:
        return pl.BlockSpec((bm, 1), lambda i: (i, 0))
    if role == ROLE_COL:
        return pl.BlockSpec((1, C), lambda i: (0, 0))
    return pl.BlockSpec((1, 1), lambda i: (0, 0))


def _to_block(v, role: str, bm: int, C: int):
    if role == ROLE_FULL:
        return v.reshape(bm, C)
    if role == ROLE_ROW:
        return v.reshape(bm, 1)
    if role == ROLE_COL:
        return v.reshape(1, C)
    return v.reshape(())


def matmul_fused(pro_args: Sequence, rhs, epi_args: Sequence, *,
                 M: int, K: int, N: int,
                 pro_roles: Sequence[str], epi_roles: Sequence[str],
                 out_roles: Sequence[str], out_dtypes: Sequence,
                 acc_dtype=jnp.float32, anchor_dtype=None,
                 prologue: Callable | None = None,
                 epilogue: Callable | None = None,
                 block_m: int = DEFAULT_BLOCK_M,
                 name: str = "matmul_fused"):
    """Run ``epilogue(prologue(pro_blocks) @ rhs, epi_blocks)`` tiled over M.

    ``prologue`` maps the prologue operands' blocks to the (bm, K) lhs
    tile (None: ``pro_args[0]`` *is* the lhs).  ``epilogue`` maps the
    anchor's (bm, N) result block plus the epilogue operands' blocks to
    the tuple of output blocks (None: the anchor result is the single
    output).  Roles describe how each operand folds into the kernel's
    2D view: prologue operands against (M, K), epilogue operands and
    outputs against (M, N).  ``name`` names the kernel in the compiled
    program and the profiler's trace.
    """
    bm = max(1, min(block_m, M))
    Mp = math.ceil(M / bm) * bm
    n_pro, n_epi = len(pro_args), len(epi_args)

    def kernel(*refs):
        pro_refs = refs[:n_pro]
        rhs_ref = refs[n_pro]
        epi_refs = refs[n_pro + 1: n_pro + 1 + n_epi]
        out_refs = refs[n_pro + 1 + n_epi:]
        pro_blocks = tuple(_to_block(r[...], role, bm, K)
                           for r, role in zip(pro_refs, pro_roles))
        lhs = prologue(*pro_blocks) if prologue is not None else pro_blocks[0]
        acc = jax.lax.dot_general(
            lhs, rhs_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=acc_dtype)
        if anchor_dtype is not None:
            acc = acc.astype(anchor_dtype)
        epi_blocks = tuple(_to_block(r[...], role, bm, N)
                           for r, role in zip(epi_refs, epi_roles))
        outs = epilogue(acc, *epi_blocks) if epilogue is not None else (acc,)
        for ref, o in zip(out_refs, outs):
            ref[...] = jnp.broadcast_to(o, ref.shape).astype(ref.dtype)

    in_specs = [_spec_for(role, bm, K) for role in pro_roles]
    in_specs.append(pl.BlockSpec((K, N), lambda i: (0, 0)))
    in_specs += [_spec_for(role, bm, N) for role in epi_roles]

    out_specs, out_shapes = [], []
    for role, dt in zip(out_roles, out_dtypes):
        width = N if role in (ROLE_FULL, ROLE_COL) else 1
        out_specs.append(pl.BlockSpec((bm, width), lambda i: (i, 0)))
        out_shapes.append(jax.ShapeDtypeStruct((Mp, width), dt))

    call = pl.pallas_call(
        kernel,
        grid=(Mp // bm,),
        in_specs=in_specs,
        out_specs=out_specs if len(out_specs) > 1 else out_specs[0],
        out_shape=out_shapes if len(out_shapes) > 1 else out_shapes[0],
        name=name,
        interpret=kernels.interpret_mode(),
    )

    def pad2d(v, role: str, C: int):
        if role == ROLE_FULL:
            v2 = v.reshape(M, C)
            return jnp.pad(v2, ((0, Mp - M), (0, 0))) if Mp != M else v2
        if role == ROLE_ROW:
            v2 = v.reshape(M, 1)
            return jnp.pad(v2, ((0, Mp - M), (0, 0))) if Mp != M else v2
        if role == ROLE_COL:
            return v.reshape(1, C)
        return jnp.asarray(v).reshape(1, 1)

    ops = [pad2d(v, role, K) for v, role in zip(pro_args, pro_roles)]
    ops.append(rhs.reshape(K, N))
    ops += [pad2d(v, role, N) for v, role in zip(epi_args, epi_roles)]
    res = call(*ops)
    if not isinstance(res, (tuple, list)):
        res = (res,)
    outs = []
    for r, role in zip(res, out_roles):
        if role == ROLE_COL:
            outs.append(r[:1])
        elif role == ROLE_SCALAR:
            outs.append(r[:1, :1])
        else:
            outs.append(r[:M])
    return tuple(outs)
