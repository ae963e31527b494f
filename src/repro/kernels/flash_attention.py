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


def _pad_seq(x, n: int):
    """x [B, H, S, D] zero-padded along S to ``n`` positions."""
    S = x.shape[2]
    return x if S == n else jnp.pad(x, ((0, 0), (0, 0), (0, n - S), (0, 0)))


def _scratch(blk_q: int, d: int):
    return [pltpu.VMEM((blk_q, 1), jnp.float32),   # running max
            pltpu.VMEM((blk_q, 1), jnp.float32),   # running denom
            pltpu.VMEM((blk_q, d), jnp.float32)]   # output accumulator


def _init_scratch(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _online_softmax_step(s, v, m_ref, l_ref, acc_ref):
    """Fold one key block's masked scores ``s`` [..., bq, bk] and values
    ``v`` [..., bk, D] into the running max, denominator and accumulator
    (leading dims, if any, are a batch)."""
    batch = tuple(range(s.ndim - 2))
    m_prev = m_ref[...]                       # [..., bq, 1]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)                    # [bq, bk]
    alpha = jnp.exp(m_prev - m_new)           # rescale factor

    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((len(batch) + 1,), (len(batch),)), (batch, batch)),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def _write_out(o_ref, l_ref, acc_ref):
    denom = jnp.maximum(l_ref[...], 1e-30)
    o_ref[...] = (acc_ref[...] / denom).reshape(o_ref.shape).astype(o_ref.dtype)


def _attn_kernel(*refs, scale: float, causal: bool, sq: int, skv: int,
                 blk_q: int, blk_k: int, score_mod=None, n_score: int = 0):
    q_ref, k_ref, v_ref = refs[:3]
    score_refs = refs[3: 3 + n_score]
    o_ref = refs[3 + n_score]
    m_ref, l_ref, acc_ref = refs[3 + n_score + 1:]
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    pl.when(ik == 0)(lambda: _init_scratch(m_ref, l_ref, acc_ref))

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
    _online_softmax_step(s, v, m_ref, l_ref, acc_ref)
    pl.when(ik == nk - 1)(lambda: _write_out(o_ref, l_ref, acc_ref))


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
    q, k, v = _pad_seq(q, Sqp), _pad_seq(k, Skp), _pad_seq(v, Skp)

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
        scratch_shapes=_scratch(blk_q, D),
        name=name,
        interpret=kernels.interpret_mode(),
    )(q, k, v, *padded_scores)
    return out[:, :, :Sq, :]


def _window_blocks(iq, *, blk_q: int, blk_k: int, off: int, window: int,
                   nk: int):
    """First and last key block that query block ``iq`` sees: its
    queries ``iq*blk_q + off ...`` look back over ``window`` keys."""
    lo = jnp.maximum(iq * blk_q + off - window + 1, 0) // blk_k
    hi = jnp.minimum((iq * blk_q + blk_q - 1 + off) // blk_k, nk - 1)
    return lo, hi


def _window_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                   scale: float, sq: int, skv: int, blk_q: int, blk_k: int,
                   window: int, nk: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    off = skv - sq
    lo, hi = _window_blocks(iq, blk_q=blk_q, blk_k=blk_k, off=off,
                            window=window, nk=nk)
    kb = lo + ik                              # the key block this step sees

    pl.when(ik == 0)(lambda: _init_scratch(m_ref, l_ref, acc_ref))

    @pl.when(kb <= hi)                        # past it: nothing in window
    def _step():
        q = q_ref[...].reshape(blk_q, -1).astype(jnp.float32)
        k = k_ref[...].reshape(blk_k, -1).astype(jnp.float32)
        v = v_ref[...].reshape(blk_k, -1).astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        q_idx = iq * blk_q + off + jax.lax.broadcasted_iota(
            jnp.int32, (blk_q, blk_k), 0)
        k_idx = kb * blk_k + jax.lax.broadcasted_iota(
            jnp.int32, (blk_q, blk_k), 1)
        back = q_idx - k_idx                  # 0 <= back < window is seen
        mask = (k_idx < skv) & (back >= 0) & (back < window)
        _online_softmax_step(jnp.where(mask, s, NEG_INF), v,
                             m_ref, l_ref, acc_ref)

    pl.when(ik == pl.num_programs(3) - 1)(
        lambda: _write_out(o_ref, l_ref, acc_ref))


def flash_attention_window(q, k, v, *, window: int,
                           scale: float | None = None,
                           block_q: int = DEFAULT_BLOCK_Q,
                           block_k: int = DEFAULT_BLOCK_K,
                           name: str = "flash_attention_window"):
    """Causal attention over a sliding window: query ``i`` (at position
    ``i + Skv - Sq``) sees key ``j`` iff ``0 <= i + Skv - Sq - j <
    window``.  q: [B, Hq, Sq, D]; k, v: [B, Hkv, Skv, D].

    The key-block axis of the grid spans only the blocks one query
    block's window can touch, offset per query block in the K/V index
    maps, so key blocks wholly outside ``(i - window, i]`` are neither
    read nor computed; blocks on the window's edges are masked.  A step
    past a query block's last key block repeats that block's index (no
    copy) and computes nothing.
    """
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    assert Hq % Hkv == 0, "GQA requires Hq % Hkv == 0"
    assert window > 0, "a window of at least one position"
    group = Hq // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(D)

    blk_q = max(1, min(block_q, Sq))
    blk_k = max(1, min(block_k, Skv))
    Sqp = math.ceil(Sq / blk_q) * blk_q
    Skp = math.ceil(Skv / blk_k) * blk_k
    q, k, v = _pad_seq(q, Sqp), _pad_seq(k, Skp), _pad_seq(v, Skp)
    nk = Skp // blk_k
    # key blocks a span of blk_q + window - 1 positions can touch
    nkw = min(nk, (blk_q + window - 2) // blk_k + 2)
    bounds = functools.partial(_window_blocks, blk_q=blk_q, blk_k=blk_k,
                               off=Skv - Sq, window=window, nk=nk)

    def kv_map(b, h, iq, ik):
        lo, hi = bounds(iq)
        return b, h // group, jnp.minimum(lo + ik, hi), 0

    out = pl.pallas_call(
        functools.partial(_window_kernel, scale=scale, sq=Sq, skv=Skv,
                          blk_q=blk_q, blk_k=blk_k, window=window, nk=nk),
        grid=(B, Hq, Sqp // blk_q, nkw),
        in_specs=[
            pl.BlockSpec((1, 1, blk_q, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, blk_k, D), kv_map),
            pl.BlockSpec((1, 1, blk_k, D), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, blk_q, D),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sqp, D), q.dtype),
        scratch_shapes=_scratch(blk_q, D),
        name=name,
        interpret=kernels.interpret_mode(),
    )(q, k, v)
    return out[:, :, :Sq, :]


#: K and V bytes a decode step aims at: enough that the fixed cost of a
#: grid step is small beside its copy, few enough that a row's last,
#: partly live block wastes little.  A step may hold twice this (many
#: heads at the shortest block), double-buffered still well inside the
#: default scoped VMEM
DECODE_STEP_BYTES = 2 << 20
#: the shortest key block the decode kernel picks for itself
DECODE_MIN_BLOCK = 128


def _decode_blocks(hkv: int, s: int, d: int, itemsize: int,
                   block_k: int | None) -> tuple[int, int]:
    """(key block, kv heads per step) for a cache of ``hkv`` heads of
    ``s`` positions: the block spans what a step's byte aim leaves each
    head, and a step takes as many heads as fit in twice the aim."""
    cap = max(1, DECODE_STEP_BYTES // (2 * d * itemsize))  # head-positions
    if block_k is None:
        block_k = max(DECODE_MIN_BLOCK, cap // hkv)
    blk_k = max(1, min(block_k, -(-s // 8) * 8))
    hb = max(n for n in range(1, hkv + 1)
             if hkv % n == 0 and n * blk_k <= max(2 * cap, blk_k))
    return blk_k, hb


def _decode_live(n, *, blk_k: int, nk: int):
    """The first step of a row's key-block run that reads a live block:
    its ``cdiv(n, blk_k)`` live blocks take the run's last steps."""
    return nk - (jnp.maximum(n, 1) + blk_k - 1) // blk_k


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                   acc_ref, *, scale: float, blk_k: int, nk: int):
    n = len_ref[pl.program_id(0)]
    ik = pl.program_id(2)
    first = _decode_live(n, blk_k=blk_k, nk=nk)

    pl.when(ik == 0)(lambda: _init_scratch(m_ref, l_ref, acc_ref))

    @pl.when(ik >= first)                     # before the live blocks: skip
    def _step():
        q = q_ref[0].astype(jnp.float32)      # [hb, group, D]
        k = k_ref[0].astype(jnp.float32)      # [hb, blk_k, D]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32) * scale
        k_idx = (ik - first) * blk_k + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 2)
        _online_softmax_step(jnp.where(k_idx < n, s, NEG_INF), v,
                             m_ref, l_ref, acc_ref)

    pl.when(ik == nk - 1)(lambda: _write_out(o_ref, l_ref, acc_ref))


def flash_decode(q, k_cache, v_cache, lengths, *, scale=None,
                 block_k: int | None = None):
    """Decode-shape attention: q [B, Hq, D] against caches [B, Hkv, S, D],
    row ``b`` over its first ``lengths[b]`` cache positions (``lengths``
    [B] int32, each at least 1; an int is every row's length).

    The lengths reach the kernel as scalar prefetch.  The key-block axis
    of the grid spans the whole cache, and a row's live blocks take the
    last steps of its run: the steps before them repeat the index of its
    first block (no copy) and compute nothing, so a row reads only its
    live blocks, the last one masked.  Skipping at the start of a run,
    not the end, lets the pipeline fetch a row's first block while the
    row before computes its last.  One step holds a group of kv
    heads with all their query heads (``[hb, Hq // Hkv, D]``), so under
    GQA each K/V byte is read once; the block and the group are sized
    from the shapes (``_decode_blocks``) unless ``block_k`` is given.
    """
    B, Hq, D = q.shape
    _, Hkv, S, _ = k_cache.shape
    assert Hq % Hkv == 0, "GQA requires Hq % Hkv == 0"
    group = Hq // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (B,))
    blk_k, hb = _decode_blocks(Hkv, S, D, k_cache.dtype.itemsize, block_k)
    nk = math.ceil(S / blk_k)
    k_cache = _pad_seq(k_cache, nk * blk_k)
    v_cache = _pad_seq(v_cache, nk * blk_k)

    def kv_map(b, h, ik, lens):
        return b, h, jnp.maximum(ik - _decode_live(lens[b], blk_k=blk_k,
                                                   nk=nk), 0), 0

    def q_map(b, h, ik, lens):
        return b, h, 0, 0

    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, blk_k=blk_k, nk=nk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, Hkv // hb, nk),
            in_specs=[pl.BlockSpec((1, hb, group, D), q_map),
                      pl.BlockSpec((1, hb, blk_k, D), kv_map),
                      pl.BlockSpec((1, hb, blk_k, D), kv_map)],
            out_specs=pl.BlockSpec((1, hb, group, D), q_map),
            scratch_shapes=[pltpu.VMEM((hb, group, 1), jnp.float32),
                            pltpu.VMEM((hb, group, 1), jnp.float32),
                            pltpu.VMEM((hb, group, D), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, group, D), q.dtype),
        name="flash_decode",
        interpret=kernels.interpret_mode(),
    )(lengths, q.reshape(B, Hkv, group, D), k_cache, v_cache)
    return out.reshape(B, Hq, D)
