"""Flash attention as *block composition* (beyond-paper stitched kernel).

The paper's block-composition scheme stages a producer's intermediate in
on-chip memory so non-homogeneous consumers can reuse it (§4.1).  Online-
softmax attention is exactly that scheme applied to ``matmul -> softmax ->
matmul``: the running max/denominator/accumulator are VMEM-staged
intermediates shared across the K-block loop, so the O(Sq*Skv) score
matrix never touches HBM.  This is the streaming (two-accumulator)
schedule the generic emitter does not synthesize — the hand-written
flagship for long rows (32k-500k).

Grid: (batch, q_heads, q_blocks, k_blocks); the last axis iterates
sequentially on TPU, carrying (m, l, acc) scratch.  GQA is handled in the
K/V index maps (kv_head = q_head // group) — no materialized repeat.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


def _attn_kernel(*refs, scale: float, causal: bool, sq: int, skv: int,
                 blk_q: int, blk_k: int, score_mod=None, n_score: int = 0):
    q_ref, k_ref, v_ref = refs[:3]
    score_refs = refs[3: 3 + n_score]
    o_ref = refs[3 + n_score]
    m_ref, l_ref, acc_ref = refs[3 + n_score + 1:]
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...].reshape(blk_q, -1).astype(jnp.float32)   # [bq, D]
    k = k_ref[...].reshape(blk_k, -1).astype(jnp.float32)   # [bk, D]
    v = v_ref[...].reshape(blk_k, -1).astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    if score_mod is not None:
        # anchored stitching: the graph's own pre-softmax chain (scale /
        # bias / mask) folded into the inner loop -- applied before the
        # kv-padding and causal masks so a folded mask cannot resurrect
        # padded columns.
        blocks = tuple(r[...].reshape(r.shape[-2], r.shape[-1])
                       for r in score_refs)
        s = score_mod(s, *blocks).astype(jnp.float32)

    q_idx = iq * blk_q + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0)
    k_idx = ik * blk_k + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1)
    mask = k_idx < skv                       # KV padding mask
    if causal:
        mask &= q_idx + (skv - sq) >= k_idx  # causal offset for Sq != Skv
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                       # [bq, 1]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)                    # [bq, bk]
    alpha = jnp.exp(m_prev - m_new)           # rescale factor

    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / denom).reshape(o_ref.shape).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, scale: float | None = None,
                    block_q: int = DEFAULT_BLOCK_Q, block_k: int = DEFAULT_BLOCK_K,
                    score_mod=None, score_args=(),
                    name: str = "flash_attention"):
    """q: [B, Hq, Sq, D]; k, v: [B, Hkv, Skv, D]; returns [B, Hq, Sq, D].

    ``score_mod`` (anchored stitching) rewrites the scaled score block
    inside the inner loop: called as ``score_mod(s, *blocks)`` with ``s``
    the f32 [blk_q, blk_k] tile and one 2D block per entry of
    ``score_args``.  Each score arg must be 4D with every dim either 1
    or the matching full extent of (B, Hq, Sq, Skv); size-1 dims are
    pinned, full dims tile with the grid.  ``name`` names the kernel in
    the compiled program and the profiler's trace.
    """
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    assert Hq % Hkv == 0, "GQA requires Hq % Hkv == 0"
    group = Hq // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(D)

    blk_q = max(1, min(block_q, Sq))
    blk_k = max(1, min(block_k, Skv))
    Sqp = math.ceil(Sq / blk_q) * blk_q
    Skp = math.ceil(Skv / blk_k) * blk_k
    if Sqp != Sq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, Sqp - Sq), (0, 0)))
    if Skp != Skv:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, Skp - Skv), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, Skp - Skv), (0, 0)))

    score_specs = []
    padded_scores = []
    for a in score_args:
        d0, d1, d2, d3 = a.shape
        if d2 == Sq and Sqp != Sq:
            a = jnp.pad(a, ((0, 0), (0, 0), (0, Sqp - Sq), (0, 0)))
        if d3 == Skv and Skp != Skv:
            a = jnp.pad(a, ((0, 0), (0, 0), (0, 0), (0, Skp - Skv)))
        padded_scores.append(a)
        bq2 = blk_q if d2 == Sq else 1
        bk3 = blk_k if d3 == Skv else 1
        score_specs.append(pl.BlockSpec(
            (1, 1, bq2, bk3),
            lambda b, h, iq, ik, d0=d0, d1=d1, d2=d2, d3=d3: (
                b if d0 == B else 0, h if d1 == Hq else 0,
                iq if d2 == Sq else 0, ik if d3 == Skv else 0)))

    grid = (B, Hq, Sqp // blk_q, Skp // blk_k)

    out = pl.pallas_call(
        functools.partial(_attn_kernel, scale=scale, causal=causal,
                          sq=Sq, skv=Skv, blk_q=blk_q, blk_k=blk_k,
                          score_mod=score_mod, n_score=len(score_args)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, blk_q, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, blk_k, D),
                         lambda b, h, iq, ik: (b, h // group, ik, 0)),
            pl.BlockSpec((1, 1, blk_k, D),
                         lambda b, h, iq, ik: (b, h // group, ik, 0)),
            *score_specs,
        ],
        out_specs=pl.BlockSpec((1, 1, blk_q, D),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sqp, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((blk_q, 1), jnp.float32),   # running max
            pltpu.VMEM((blk_q, 1), jnp.float32),   # running denom
            pltpu.VMEM((blk_q, D), jnp.float32),   # output accumulator
        ],
        name=name,
        interpret=kernels.interpret_mode(),
    )(q, k, v, *padded_scores)
    return out[:, :, :Sq, :]


def flash_decode(q, k_cache, v_cache, *, kv_len: int | None = None, scale=None,
                 block_k: int = 512):
    """Decode-shape attention: q [B, Hq, D] against caches [B, Hkv, S, D].

    Uses the same streaming kernel with a single q row per block; the
    K-block axis does the long-context streaming (the 500k case).
    ``kv_len`` (static) masks cache positions >= kv_len — the serve loop
    passes the current decode position so a pre-allocated cache works.
    """
    B, Hq, D = q.shape
    S = k_cache.shape[2]
    eff = S if kv_len is None else int(kv_len)
    if eff < S:  # restrict streaming to the live prefix
        k_cache = k_cache[:, :, :eff, :]
        v_cache = v_cache[:, :, :eff, :]
    out = flash_attention(q[:, :, None, :], k_cache, v_cache, causal=False,
                          scale=scale, block_q=1, block_k=min(block_k, eff),
                          name="flash_decode")
    return out[:, :, 0, :]
