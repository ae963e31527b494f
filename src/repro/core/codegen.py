"""Stitched-kernel code generation (paper §4).

``emit_pattern`` compiles one fusion pattern into a single Pallas TPU
kernel implementing the *block composition* scheme: the whole reduce row
plus every intermediate lives in VMEM for one grid cell, consumers read
staged values instead of recomputing them (paper §4.1).  Grouping +
schedule enumeration (§4.2) is realized by the latency-evaluator sweep
over block-row launch dims in ``cost_model.best_estimate`` plus the
stage-vs-recompute choice for expensive sub-roots below.

Patterns without a consistent row view fall back to *kernel packing*:
the subgraph runs as one fused XLA computation (single launch), which is
the paper's packing scheme realized with the native compiler.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl

from repro import kernels

from .cost_model import Hardware, KernelEstimate, V5E, best_estimate, \
    legal_block_rows, row_tile
from .ir import Graph, OpKind
from .memory_planner import plan_scratch
from .rowspec import Role, RowInfo, analyze
from .tracer import bind_node

# --------------------------------------------------------------------------
# in-kernel op table: prim name -> block-level implementation
# --------------------------------------------------------------------------
def _select_n(which, *cases):
    if len(cases) == 2:
        return jnp.where(which, cases[1], cases[0])
    out = cases[0]
    for i, c in enumerate(cases[1:], start=1):
        out = jnp.where(which == i, c, out)
    return out


_OPS: dict[str, Callable] = {
    "add": lax.add, "sub": lax.sub, "mul": lax.mul, "div": lax.div,
    "max": lax.max, "min": lax.min, "neg": lax.neg, "abs": lax.abs,
    "sign": lax.sign, "floor": lax.floor, "ceil": lax.ceil,
    "round": lambda x: lax.round(x, lax.RoundingMethod.TO_NEAREST_EVEN),
    "exp": lax.exp, "exp2": lax.exp2, "expm1": lax.expm1,
    "log": lax.log, "log1p": lax.log1p,
    "tanh": lax.tanh, "sin": lax.sin, "cos": lax.cos,
    "logistic": lax.logistic, "erf": lax.erf, "erfc": lax.erfc,
    "rsqrt": lax.rsqrt, "sqrt": lax.sqrt, "cbrt": lax.cbrt,
    "pow": lax.pow, "square": lax.square,
    "eq": lax.eq, "ne": lax.ne, "ge": lax.ge, "gt": lax.gt,
    "le": lax.le, "lt": lax.lt,
    "and": lax.bitwise_and, "or": lax.bitwise_or,
    "xor": lax.bitwise_xor, "not": lax.bitwise_not,
    "is_finite": lax.is_finite,
    "select_n": _select_n,
    "clamp": lax.clamp,
    "nextafter": lax.nextafter,
    "atan2": lax.atan2,
    "rem": lax.rem,
}

_REDUCES = {
    "reduce_sum": lambda x: jnp.sum(x, axis=-1, keepdims=True),
    "reduce_max": lambda x: jnp.max(x, axis=-1, keepdims=True),
    "reduce_min": lambda x: jnp.min(x, axis=-1, keepdims=True),
    "reduce_prod": lambda x: jnp.prod(x, axis=-1, keepdims=True),
    "reduce_and": lambda x: jnp.all(x, axis=-1, keepdims=True),
    "reduce_or": lambda x: jnp.any(x, axis=-1, keepdims=True),
}

EMITTABLE_PRIMS = (set(_OPS) | set(_REDUCES)
                   | {"broadcast_in_dim", "reshape", "squeeze", "expand_dims",
                      "convert_element_type", "integer_pow", "copy",
                      "stop_gradient", "const"})


def pattern_emittable(graph: Graph, pattern: frozenset[int],
                      info: "RowInfo | None" = ...) -> bool:
    """Can the Pallas emitter stitch this pattern?  Pass a precomputed
    ``analyze`` result via ``info`` to skip re-running the inference."""
    if info is ...:
        info = analyze(graph, pattern)
    if info is None:
        return False
    return all(graph.node(n).prim in EMITTABLE_PRIMS for n in pattern)


def check_shard_emittable(graph: Graph, union: frozenset[int], shard,
                          group_index: int) -> None:
    """Sanity-check one stitch group for sharded (shard_map) emission.

    The group's member shapes are already *per-shard* (the sharded build
    traces on local shapes), so the existing emitters apply unchanged --
    what can still go wrong is the shard layout itself: a collective
    leaking into the union, or a spec whose divisibility repair left a
    degenerate (zero-extent) local dim.  Raises ``guard.EmitError`` so
    ``stitch._finalize``'s existing ladder degrades exactly this group
    to the per-pattern rung while sibling groups stay stitched.

    ``shard_spec_fail`` is this seam's fault point: firing it simulates
    a bad/non-divisible PartitionSpec reaching emission.
    """
    from repro.runtime.guard import EmitError
    from repro.testing import faults as _faults

    if _faults.fire("shard_spec_fail", group=group_index) is not None:
        raise EmitError(
            f"group {group_index}: injected shard_spec_fail "
            "(simulated non-divisible PartitionSpec)")
    for nid in union:
        node = graph.node(nid)
        if node.kind is OpKind.COLLECTIVE:
            raise EmitError(
                f"group {group_index}: collective {node.prim} (%{nid}) "
                "inside a stitch group -- collectives are hard group "
                "boundaries")
        if any(d <= 0 for d in node.spec.shape):
            raise EmitError(
                f"group {group_index}: %{nid} has degenerate per-shard "
                f"shape {node.spec.shape} under mesh "
                f"{dict(shard.mesh.shape)}")


# --------------------------------------------------------------------------
# compute-anchored groups: structural matchers
# --------------------------------------------------------------------------
class AnchorEmitError(RuntimeError):
    """Anchored emission found an unsupported structure at emit time;
    the dispatch ladder degrades the group to its unanchored parts."""


#: Shape-plumbing prims the softmax-tail matcher walks through (they are
#: elided along with the tail itself -- the flash kernel's online softmax
#: replaces the whole chain).
_PASSTHROUGH = {"reshape", "squeeze", "expand_dims", "convert_element_type",
                "copy", "stop_gradient", "broadcast_in_dim"}


def _raw_params(node) -> dict:
    return node.params.get("_raw_params") or {}


def _match_matmul_anchor(graph: Graph, union: frozenset[int],
                         a: int) -> dict | None:
    """Match a single-anchor group: prologue -> dot_general -> epilogue.

    Requires an unbatched contraction ``(..., K) @ (K, N)`` with the rhs
    external to the group, a prologue whose row view is (M, K) and whose
    every escaping value feeds only the anchor, and an epilogue with row
    view (M, N) that solely consumes the anchor's result.
    """
    node = graph.node(a)
    if node.prim != "dot_general" or len(node.inputs) < 2:
        return None
    dn = _raw_params(node).get("dimension_numbers")
    if dn is None:
        return None
    (cl, cr), (bl, br_) = dn
    if tuple(bl) or tuple(br_):
        return None
    lhs_id, rhs_id = node.inputs[0], node.inputs[1]
    lhs_spec = graph.node(lhs_id).spec
    rhs_spec = graph.node(rhs_id).spec
    if len(rhs_spec.shape) != 2 or rhs_id in union:
        return None
    if tuple(cl) != (len(lhs_spec.shape) - 1,) or tuple(cr) != (0,):
        return None
    K, N = rhs_spec.shape
    if not lhs_spec.shape or lhs_spec.shape[-1] != K:
        return None
    M = lhs_spec.size // K
    if node.spec.size != M * N or not node.spec.shape \
            or node.spec.shape[-1] != N:
        return None

    mem = union - {a}
    if not mem or any(graph.node(m).prim not in EMITTABLE_PRIMS
                      for m in mem):
        return None
    _, anc = graph.reachability()
    pro = frozenset(m for m in mem if (anc[a] >> m) & 1)
    epi = mem - pro
    outset = set(graph.outputs)

    pro_info = None
    if pro:
        if lhs_id not in pro:
            return None
        for m in pro:
            if m in outset or any(c not in pro and c != a
                                  for c in graph.consumers(m)):
                return None
        pro_info = analyze(graph, pro)
        if pro_info is None or pro_info.R != M or pro_info.C != K:
            return None
    elif lhs_id in union:
        return None

    epi_info = None
    if epi:
        if a in outset or any(c not in epi for c in graph.consumers(a)):
            return None
        epi_info = analyze(graph, epi)
        if epi_info is None or epi_info.R != M or epi_info.C != N:
            return None
    return {"kind": "matmul", "a": a, "lhs": lhs_id, "rhs": rhs_id,
            "M": M, "K": K, "N": N, "pro": pro, "epi": epi,
            "pro_info": pro_info, "epi_info": epi_info}


def _match_softmax_tail(graph: Graph, chain: frozenset[int],
                        root: int) -> tuple[int, frozenset[int]] | None:
    """Match ``div(exp(sub(s, max(s))), sum(exp(...)))`` ending at ``root``
    (walking through shape-plumbing wrappers); returns (s_pre, elided
    members) where ``s_pre`` is the pre-softmax score value the flash
    kernel's ``score_mod`` must reproduce.
    """
    elided: set[int] = set()

    def back(nid: int) -> int:
        while nid in chain and graph.node(nid).prim in _PASSTHROUGH:
            elided.add(nid)
            nid = graph.node(nid).inputs[0]
        return nid

    div_id = back(root)
    if div_id not in chain or graph.node(div_id).prim != "div":
        return None
    elided.add(div_id)
    num_id = back(graph.node(div_id).inputs[0])
    den_id = back(graph.node(div_id).inputs[1])
    if den_id not in chain or graph.node(den_id).prim != "reduce_sum":
        return None
    elided.add(den_id)
    if back(graph.node(den_id).inputs[0]) != num_id:
        return None
    if num_id not in chain or graph.node(num_id).prim != "exp":
        return None
    elided.add(num_id)
    sub_id = back(graph.node(num_id).inputs[0])
    if sub_id not in chain or graph.node(sub_id).prim != "sub":
        return None
    elided.add(sub_id)
    s_pre = back(graph.node(sub_id).inputs[0])
    mx_id = back(graph.node(sub_id).inputs[1])
    if mx_id in chain and graph.node(mx_id).prim == "max":
        # jax.nn.softmax clamps the row max against a -inf initial value
        # (``max(-inf, reduce_max(s))``): semantically the identity, and
        # the flash kernel's own running max handles the all-masked row,
        # so the clamp is elided.
        ins = graph.node(mx_id).inputs
        guard = [i for i in ins
                 if graph.node(i).kind is OpKind.CONST
                 and graph.node(i).spec.size == 1
                 and graph.node(i).value is not None
                 and np.isneginf(np.asarray(graph.node(i).value))]
        rest = [i for i in ins if i not in guard]
        if len(guard) == 1 and len(rest) == 1:
            elided.add(mx_id)
            mx_id = back(rest[0])
    if mx_id not in chain or graph.node(mx_id).prim != "reduce_max":
        return None
    elided.add(mx_id)
    if back(graph.node(mx_id).inputs[0]) != s_pre:
        return None
    for r in (den_id, mx_id):
        rnode = graph.node(r)
        op_shape = graph.node(rnode.inputs[0]).spec.shape
        if tuple(rnode.params.get("axes", ())) != (len(op_shape) - 1,):
            return None
    return s_pre, frozenset(elided)


def _pad4(shape: tuple[int, ...]) -> tuple[int, int, int, int]:
    return (1,) * (4 - len(shape)) + tuple(shape)


def _score_shape_ok(shape: tuple[int, ...],
                    extent: tuple[int, int, int, int]) -> bool:
    if len(shape) > 4:
        return False
    return all(d == 1 or d == e for d, e in zip(_pad4(shape), extent))


def _match_attention_anchors(graph: Graph, union: frozenset[int],
                             anchors: tuple[int, ...]) -> dict | None:
    """Match a two-anchor group: QK dot -> score chain -> softmax -> PV dot.

    q/k/v must be external 4D operands with flash-compatible dimension
    numbers; the chain between the anchors must end in a softmax tail,
    and everything upstream of it (scale / bias / mask) must evaluate on
    (blk_q, blk_k) score tiles -- each value's shape, padded to 4D, has
    every dim either 1 or the full (B, H, Sq, Skv) extent.
    """
    qk, pv = anchors
    nqk, npv = graph.node(qk), graph.node(pv)
    if nqk.prim != "dot_general" or npv.prim != "dot_general":
        return None
    dn_qk = _raw_params(nqk).get("dimension_numbers")
    dn_pv = _raw_params(npv).get("dimension_numbers")
    if dn_qk is None or dn_pv is None:
        return None
    if (tuple(map(tuple, dn_qk[0])), tuple(map(tuple, dn_qk[1]))) \
            != (((3,), (3,)), ((0, 1), (0, 1))):
        return None
    if (tuple(map(tuple, dn_pv[0])), tuple(map(tuple, dn_pv[1]))) \
            != (((3,), (2,)), ((0, 1), (0, 1))):
        return None
    q_id, k_id = nqk.inputs[0], nqk.inputs[1]
    p_id, v_id = npv.inputs[0], npv.inputs[1]
    if any(x in union for x in (q_id, k_id, v_id)):
        return None
    q_spec, k_spec = graph.node(q_id).spec, graph.node(k_id).spec
    v_spec = graph.node(v_id).spec
    if len(q_spec.shape) != 4 or len(k_spec.shape) != 4 \
            or len(v_spec.shape) != 4:
        return None
    B, H, Sq, D = q_spec.shape
    _, _, Sk, _ = k_spec.shape
    if k_spec.shape != (B, H, Sk, D) or v_spec.shape != (B, H, Sk, D):
        return None
    extent = (B, H, Sq, Sk)

    chain = union - {qk, pv}
    outset = set(graph.outputs)
    if qk in outset or p_id not in chain:
        return None
    for m in chain:
        if m in outset or any(c not in chain and c != pv
                              for c in graph.consumers(m)):
            return None
    if any(c not in chain for c in graph.consumers(qk)):
        return None

    tail = _match_softmax_tail(graph, chain, p_id)
    if tail is None:
        return None
    s_pre, elided = tail
    score = chain - elided
    if s_pre == qk:
        if score:
            return None
    elif s_pre not in score:
        return None

    score_ext: list[int] = []
    _, anc = graph.reachability()
    for m in sorted(score):
        node = graph.node(m)
        if node.prim not in EMITTABLE_PRIMS or node.kind is OpKind.REDUCE:
            return None
        if m != s_pre and not ((anc[s_pre] >> m) & 1):
            return None  # a score member the pre-softmax value never reads
        if not _score_shape_ok(node.spec.shape, extent):
            return None
        if node.prim == "broadcast_in_dim":
            bd = tuple(node.params.get("broadcast_dimensions", ()))
            in_nd = len(graph.node(node.inputs[0]).spec.shape)
            out_nd = len(node.spec.shape)
            if bd != tuple(range(out_nd - in_nd, out_nd)):
                return None  # not suffix-aligned: 4D padding would misread it
        for i in node.inputs:
            if i in score or i == qk:
                continue
            ispec = graph.node(i).spec
            if not _score_shape_ok(ispec.shape, extent):
                return None
            if i not in score_ext:
                score_ext.append(i)
    return {"kind": "attention", "qk": qk, "pv": pv,
            "q": q_id, "k": k_id, "v": v_id,
            "extent": extent, "D": D, "s_pre": s_pre,
            "score": score, "score_ext": score_ext}


def anchor_emittable(graph: Graph, parts, anchors, ctx=None) -> bool:
    """Can ``_emit_anchored`` compile this anchored group?  Structural
    test only (dimension numbers, row views, softmax tail) -- pricing is
    the stitcher's job."""
    try:
        union = frozenset(n for p in parts for n in p)
        anchors = tuple(sorted(anchors))
        if len(anchors) == 1:
            return _match_matmul_anchor(graph, union, anchors[0]) is not None
        if len(anchors) == 2:
            return _match_attention_anchors(graph, union, anchors) is not None
    except Exception:
        return False
    return False


# --------------------------------------------------------------------------
# emission
# --------------------------------------------------------------------------
def _canon2d(role: Role, C: int) -> tuple[int, ...]:
    """Canonical per-block trailing shape for a role (rows prepended later)."""
    return {"full": (C,), "row": (1,), "col": (C,), "scalar": ()}[role.value]


def _to_block(val, role: Role, br: int, C: int):
    """Reshape a block-level value to its canonical broadcastable 2D form."""
    if role is Role.FULL:
        return val.reshape(br, C)
    if role is Role.ROW:
        return val.reshape(br, 1)
    if role is Role.COL:
        return val.reshape(1, C)
    return val.reshape(())


def kernel_name(scheme: str, group: int) -> str:
    """A stitch group's kernel name in the compiled program and in the
    profiler's trace: ``stitch_<scheme>_<group index>``."""
    return f"stitch_{scheme}_{group}"


@dataclass
class Emitted:
    """A compiled pattern or stitch group: callable + benchmark metadata."""
    fn: Callable                 # (*ext_arrays) -> tuple(outputs)
    kind: str                    # "pallas" | "packed"
    estimate: KernelEstimate
    ext_ids: list[int]           # runtime external inputs (non-const)
    out_ids: list[int]
    scratch_bytes: int
    scratch_naive_bytes: int
    parts: tuple = ()            # member patterns (sorted id tuples); one
                                 # entry per part, >1 for stitched groups
    hbm_saved: int = 0           # inter-pattern HBM bytes the group avoids
    staged_slots: int = 0        # explicit VMEM scratch buffers allocated
    io_aliases: dict = None      # ext pos -> out pos donated into the kernel
    n_recomputed: int = 0        # values inlined per consumer (not staged)
    recompute_bytes_freed: int = 0  # VMEM scratch bytes those flips elide
    name: str = ""               # the kernel's name (``kernel_name``)
    group: int = 0               # index of the stitch group it runs as


def _override_estimate(graph: Graph, pattern: frozenset[int], info,
                       override: dict, hw: Hardware,
                       ctx=None) -> KernelEstimate | None:
    """Re-price a cached/tuned schedule choice; None if it doesn't apply."""
    from .cost_model import estimate_onepass, estimate_packed, \
        estimate_streaming

    sched = override.get("schedule")
    if sched == "packed":
        return estimate_packed(graph, pattern, hw, ctx=ctx)
    if info is None:
        return None
    if sched == "onepass":
        rec = frozenset(int(x) for x in override.get("recompute", ())
                        if isinstance(x, int)
                        and not isinstance(x, bool)) & pattern
        if rec:
            # a corrupt / hand-edited pin naming an output (or a value
            # nothing inside reads) must degrade, not miscompile: the
            # emitter never materializes recomputed values, so an
            # unmaterialized output would crash the kernel's HBM write.
            outs = set(graph.pattern_outputs(pattern))
            rec = frozenset(
                r for r in rec
                if r not in outs
                and any(c in pattern for c in graph.consumers(r)))
        est = estimate_onepass(graph, pattern, info,
                               int(override.get("block_rows", 8)), hw,
                               ctx=ctx, recompute=rec or None)
        return est if est.feasible else None
    if sched == "streaming":
        est = estimate_streaming(graph, pattern, info,
                                 int(override.get("block_rows", 8)),
                                 int(override.get("block_cols", 2048)), hw,
                                 ctx=ctx)
        return est if est.feasible else None
    return None


def _alias_map(graph: Graph, info: RowInfo, ext_ids: list[int],
               out_ids: list[int],
               donate_into: "frozenset[int] | None") -> dict[int, int] | None:
    """Donate eligible inputs into the kernel's output buffers.

    ``donate_into`` holds graph inputs whose only consumers live inside
    this kernel (the caller's schedule-position analysis); each is
    aliased to the first unclaimed output of identical padded shape and
    dtype (FULL->FULL / ROW->ROW), so the one-pass grid can write output
    block i over the input block it just consumed.
    """
    if not donate_into:
        return None
    aliases: dict[int, int] = {}
    used: set[int] = set()
    for i, e in enumerate(ext_ids):
        if e not in donate_into:
            continue
        role = info.roles.get(e)
        if role not in (Role.FULL, Role.ROW):
            continue  # COL/scalar operands pad to a different leading dim
        for j, o in enumerate(out_ids):
            if j in used:
                continue
            if (info.roles[o] is role
                    and graph.node(o).spec.dtype == graph.node(e).spec.dtype):
                aliases[i] = j
                used.add(j)
                break
    return aliases or None


def _alias_map_streaming(graph: Graph, info: RowInfo, ext_ids: list[int],
                         out_ids: list[int],
                         donate_into: "frozenset[int] | None",
                         block_cols: int, phases: int
                         ) -> dict[int, int] | None:
    """Phase-aware alias legality for the streaming schedule.

    The streaming grid is ``(row_blocks, phases, col_tiles)`` with the
    trailing axes sequential and the column axis fastest.  The hazard
    is not only the kernel's own final-phase store: Pallas flushes an
    output window back to HBM whenever its block index *changes*
    between grid cells, including after cells where the kernel never
    stored to the ref (the ``pl.when(p == phases - 1)`` gate).  With
    ``input_output_aliases`` such a flush lands on the aliased input's
    block, which later phases re-read.  Donation is therefore legal
    only when every read of the aliased input's block precedes the
    first possible write-back of the aliased output's block:

      * FULL -> FULL with ``phases == 1``: each ``(i, j)`` tile is
        visited exactly once; the read precedes the same cell's write.
      * FULL -> FULL or ROW -> ROW with one column tile: the output
        block index is pinned across the whole phase axis of row block
        ``i``, so its write-back is deferred until the grid advances
        to row ``i + 1`` -- after every phase has re-read the input.
      * FULL -> FULL with ``phases > 1`` *and* several column tiles is
        refused: the out block index changes every cell, so phase 0's
        unwritten-window flush would clobber input tiles that phase 1
        still reads.  Likewise ROW -> ROW across several column tiles
        (the pinned ``(i, 0)`` block is re-read at ``j >= 1`` after
        the final phase's first write).
      * COL / scalar operands pad to a different leading dim entirely.
    """
    if not donate_into:
        return None
    n_col_tiles = math.ceil(info.C / max(1, min(block_cols, info.C)))
    aliases: dict[int, int] = {}
    used: set[int] = set()
    for i, e in enumerate(ext_ids):
        if e not in donate_into:
            continue
        role = info.roles.get(e)
        if role is Role.FULL:
            if phases > 1 and n_col_tiles > 1:
                continue  # unwritten-window flush precedes later reads
        elif role is Role.ROW:
            if n_col_tiles > 1:
                continue  # pinned block re-read after the first write
        else:
            continue
        for j, o in enumerate(out_ids):
            if j in used:
                continue
            if (info.roles[o] is role
                    and graph.node(o).spec.dtype == graph.node(e).spec.dtype):
                aliases[i] = j
                used.add(j)
                break
    return aliases or None


def emit_pattern(graph: Graph, pattern: frozenset[int], *,
                 hw: Hardware = V5E,
                 force_packed: bool = False, ctx=None,
                 schedule_override: dict | None = None,
                 donate_into: "frozenset[int] | None" = None,
                 group: int = 0) -> Emitted:
    """Compile one pattern.  ``schedule_override`` (from the persistent
    plan cache or the measured autotuner) pins {schedule, block_rows,
    block_cols} instead of re-running the analytic sweep.
    ``donate_into`` names graph inputs this kernel may overwrite with
    its outputs (one-pass schedule only; see ``_alias_map``).  ``group``
    is the stitch group's index, which names the kernel
    (``kernel_name``)."""
    info = ctx.info(pattern) if ctx is not None else analyze(graph, pattern)
    est = None
    if schedule_override is not None:
        est = _override_estimate(graph, pattern, info, schedule_override,
                                 hw, ctx=ctx)
    if est is None:
        est = (ctx.best(pattern) if ctx is not None
               else best_estimate(graph, pattern, hw))
    if ctx is not None:
        b = ctx.bounds(pattern)
        ext_all, out_ids = list(b.inputs), list(b.outputs)
    else:
        ext_all = graph.pattern_inputs(pattern)
        out_ids = graph.pattern_outputs(pattern)
    ext_ids = [i for i in ext_all if graph.node(i).kind is not OpKind.CONST]

    if not force_packed and pattern_emittable(graph, pattern, info=info):
        rec = frozenset(est.recompute_ids) if est.schedule == "onepass" \
            else frozenset()
        scratch = (ctx.scratch(pattern, info, recompute=rec)
                   if ctx is not None
                   else plan_scratch(graph, pattern, info, recompute=rec))
        rec_freed = 0
        if rec:
            # the all-staged baseline was already priced (and memoized)
            # during the schedule sweep
            base = (ctx.scratch(pattern, info) if ctx is not None
                    else plan_scratch(graph, pattern, info))
            rec_freed = (base.total_bytes - scratch.total_bytes) \
                * max(1, min(est.block_rows or 1, info.R))
        if est.schedule == "onepass":
            aliases = _alias_map(graph, info, ext_ids, out_ids, donate_into)
            fn = _emit_pallas(graph, pattern, info, est.block_rows, ext_ids,
                              out_ids, io_aliases=aliases, recompute=rec,
                              name=kernel_name("onepass", group))
            return Emitted(fn, "pallas", est, ext_ids, out_ids,
                           scratch.total_bytes, scratch.naive_bytes,
                           parts=(tuple(sorted(pattern)),),
                           io_aliases=aliases, n_recomputed=len(rec),
                           recompute_bytes_freed=rec_freed,
                           name=kernel_name("onepass", group), group=group)
        if est.schedule == "streaming":
            # the estimate carries the column tile (analytic sweep, tuned
            # override or plan-cache entry alike -- no side-channel)
            from .cost_model import reduce_levels
            phases = max(reduce_levels(graph, pattern).values(),
                         default=0) + 1
            aliases = _alias_map_streaming(graph, info, ext_ids, out_ids,
                                           donate_into,
                                           est.block_cols or 2048, phases)
            fn = _emit_pallas_streaming(graph, pattern, info,
                                        est.block_rows, ext_ids, out_ids,
                                        block_cols=est.block_cols or 2048,
                                        io_aliases=aliases,
                                        name=kernel_name("streaming", group))
            return Emitted(fn, "pallas", est, ext_ids, out_ids,
                           scratch.total_bytes, scratch.naive_bytes,
                           parts=(tuple(sorted(pattern)),),
                           io_aliases=aliases,
                           name=kernel_name("streaming", group), group=group)

    fn = _emit_packed(graph, pattern, ext_ids, out_ids)
    if est.schedule in ("onepass", "streaming"):  # emitter gap: packed
        from .cost_model import estimate_packed
        est = estimate_packed(graph, pattern, hw, ctx=ctx)
    return Emitted(fn, "packed", est, ext_ids, out_ids, 0, 0,
                   parts=(tuple(sorted(pattern)),),
                   name=kernel_name("packed", group), group=group)


def emit_group(graph: Graph, parts, *, hw: Hardware = V5E,
               ctx=None,
               schedule_override: dict | None = None,
               donate_into: "frozenset[int] | None" = None,
               anchors: tuple = (), group: int = 0) -> Emitted:
    """Compile one stitch group into a single Pallas megakernel (paper §4).

    ``parts`` are the group's member patterns in topological order.  A
    single-part group degenerates to ``emit_pattern``.  Otherwise the
    union is emitted as ONE ``pallas_call`` whose body executes the
    member patterns back-to-back inside each grid cell: inter-pattern
    values are staged in VMEM (``plan_group_scratch`` prices the
    spanning liveness) instead of materialized to HBM, and the per-call
    pad/reshape wrappers collapse to one boundary per group.  Mixed
    onepass/streaming members share one grid: the union's streaming
    schedule phases over the *cumulative* reduce levels (the max phase
    count across the chain -- the paper's non-homogeneous-parallelism
    case), while a union that fits VMEM residency runs all members in a
    single one-pass cell.  ``group`` is the group's index, which names
    its kernel (``kernel_name``).
    """
    parts = tuple(tuple(sorted(p)) for p in parts)
    union = frozenset(n for p in parts for n in p)
    if anchors:
        return _emit_anchored(graph, parts, tuple(sorted(anchors)),
                              hw=hw, ctx=ctx, group=group)
    if len(parts) == 1:
        return emit_pattern(graph, union, hw=hw, ctx=ctx,
                            schedule_override=schedule_override,
                            donate_into=donate_into, group=group)

    info = ctx.info(union) if ctx is not None else analyze(graph, union)
    est = None
    if schedule_override is not None:
        est = _override_estimate(graph, union, info, schedule_override,
                                 hw, ctx=ctx)
    if est is None:
        est = (ctx.best(union) if ctx is not None
               else best_estimate(graph, union, hw))
    parts_fs = tuple(frozenset(p) for p in parts)
    if ctx is not None:
        b = ctx.bounds(union)
        ext_all, out_ids = list(b.inputs), list(b.outputs)
        hbm_saved = ctx.stitch_gain(parts_fs).hbm_bytes_saved
    else:
        from .cost_model import stitch_gain
        ext_all = graph.pattern_inputs(union)
        out_ids = graph.pattern_outputs(union)
        hbm_saved = stitch_gain(graph, parts_fs, hw).hbm_bytes_saved
    ext_ids = [i for i in ext_all if graph.node(i).kind is not OpKind.CONST]

    if pattern_emittable(graph, union, info=info) and \
            est.schedule in ("onepass", "streaming"):
        from .memory_planner import group_order, plan_group_scratch

        rec = frozenset(est.recompute_ids) if est.schedule == "onepass" \
            else frozenset()
        scratch = plan_group_scratch(graph, parts_fs, info, recompute=rec)
        order = group_order(graph, parts_fs)
        aliases = None
        n_staged = 0
        rec_freed = 0
        if est.schedule == "onepass":
            from .memory_planner import plan_staged_buffers

            aliases = _alias_map(graph, info, ext_ids, out_ids, donate_into)
            br = max(1, min(est.block_rows or 1, info.R))  # emitter clamp
            if rec:
                # both sides of the subtraction must use the group's
                # back-to-back emission order (the ctx memo plans in
                # sorted order, which would skew the delta)
                base = plan_group_scratch(graph, parts_fs, info)
                rec_freed = (base.total_bytes - scratch.total_bytes) * br
            staged = plan_staged_buffers(graph, info.roles, scratch, br,
                                         info.C)
            n_staged = len(staged[1])
            fn = _emit_pallas(graph, union, info, est.block_rows, ext_ids,
                              out_ids, order=order,
                              staged=staged, io_aliases=aliases,
                              recompute=rec,
                              name=kernel_name("onepass", group))
        else:
            from .cost_model import reduce_levels
            phases = max(reduce_levels(graph, union).values(),
                         default=0) + 1
            aliases = _alias_map_streaming(graph, info, ext_ids, out_ids,
                                           donate_into,
                                           est.block_cols or 2048, phases)
            fn = _emit_pallas_streaming(graph, union, info, est.block_rows,
                                        ext_ids, out_ids,
                                        block_cols=est.block_cols or 2048,
                                        order=order, io_aliases=aliases,
                                        name=kernel_name("streaming", group))
        return Emitted(fn, "pallas", est, ext_ids, out_ids,
                       scratch.total_bytes, scratch.naive_bytes,
                       parts=parts, hbm_saved=hbm_saved,
                       staged_slots=n_staged, io_aliases=aliases,
                       n_recomputed=len(rec),
                       recompute_bytes_freed=rec_freed,
                       name=kernel_name(est.schedule, group), group=group)

    # defensive fallback (stale cached group / emitter gap): the union
    # still runs as one launch via kernel packing.
    fn = _emit_packed(graph, union, ext_ids, out_ids)
    from .cost_model import estimate_packed
    est = estimate_packed(graph, union, hw, ctx=ctx)
    return Emitted(fn, "packed", est, ext_ids, out_ids, 0, 0,
                   parts=parts, hbm_saved=hbm_saved,
                   name=kernel_name("packed", group), group=group)


_REDUCE_IDENTITY = {
    "reduce_sum": 0.0, "reduce_max": -1e30, "reduce_min": 1e30,
    "reduce_prod": 1.0, "reduce_and": True, "reduce_or": False,
}
_REDUCE_COMBINE = {
    "reduce_sum": lax.add, "reduce_max": lax.max, "reduce_min": lax.min,
    "reduce_prod": lax.mul,
    "reduce_and": lax.bitwise_and, "reduce_or": lax.bitwise_or,
}


def _emit_pallas_streaming(graph: Graph, pattern: frozenset[int],
                           info: RowInfo, block_rows: int,
                           ext_ids: list[int], out_ids: list[int], *,
                           block_cols: int = 2048,
                           order: list[int] | None = None,
                           io_aliases: dict[int, int] | None = None,
                           name: str = kernel_name("streaming", 0)
                           ) -> Callable:
    """Streaming multi-phase kernel (warp-composition analogue, §4.1).

    Grid (row_blocks, phases, col_tiles); the two trailing axes iterate
    sequentially, carrying one VMEM scratch accumulator per reduction
    (the staged intermediate consumers reuse).  In phase p, nodes with
    reduce-level <= p are (re)computed per column tile -- the explicit
    recompute-vs-reuse trade the delta-evaluator prices; level-(p)
    reductions accumulate masked partials; the final phase writes
    outputs.  Handles arbitrarily long rows in O(block) VMEM.
    """
    from .cost_model import reduce_levels

    R, C = info.R, info.C
    br = legal_block_rows(block_rows, R, row_tile(graph, pattern))
    bc = min(block_cols, C)
    Rp = math.ceil(R / br) * br
    NC = math.ceil(C / bc)
    Cp = NC * bc
    roles = info.roles
    members = order if order is not None else sorted(pattern)
    lvl = reduce_levels(graph, pattern)
    reduces = [n for n in members if graph.node(n).kind is OpKind.REDUCE]
    phases = max(lvl.values(), default=0) + 1
    acc_slot = {r: i for i, r in enumerate(reduces)}
    ext_roles = [roles[i] for i in ext_ids]
    out_roles = [roles[o] for o in out_ids]

    def kernel(*refs):
        in_refs = refs[: len(ext_ids)]
        out_refs = refs[len(ext_ids): len(ext_ids) + len(out_ids)]
        accs = refs[len(ext_ids) + len(out_ids):]
        p = pl.program_id(1)
        j = pl.program_id(2)

        @pl.when((p == 0) & (j == 0))
        def _init():
            for r in reduces:
                accs[acc_slot[r]][...] = jnp.full(
                    (br, 1), _REDUCE_IDENTITY[graph.node(r).prim],
                    jnp.float32)

        col = j * bc + jax.lax.broadcasted_iota(jnp.int32, (br, bc), 1)
        col_ok = col < C  # mask the padded tail tile

        env: dict[int, Any] = {}
        for nid, role, ref in zip(ext_ids, ext_roles, in_refs):
            v = ref[...]
            env[nid] = (v.reshape(br, bc) if role is Role.FULL else
                        v.reshape(br, 1) if role is Role.ROW else
                        v.reshape(1, bc) if role is Role.COL else
                        v.reshape(()))

        def val(i):
            if i in env:
                return env[i]
            cnode = graph.node(i)
            v = jnp.asarray(cnode.value)
            if cnode.spec.size > 1:
                role = roles[i]
                return (v.reshape(1, bc) if role is Role.COL else
                        v.reshape(br, 1) if role is Role.ROW else v)
            return v

        for nid in members:
            node = graph.node(nid)
            if node.kind is OpKind.REDUCE:
                # consumers read the finished accumulator (staged reuse)
                env[nid] = accs[acc_slot[nid]][...]
                # accumulate masked partials during this node's phase
                operand = val(node.inputs[0])
                ident = _REDUCE_IDENTITY[node.prim]
                masked = jnp.where(col_ok, operand.astype(jnp.float32),
                                   ident)
                part = _REDUCES[node.prim](masked)

                @pl.when(p == lvl[nid] - 1)
                def _acc(part=part, slot=acc_slot[nid], prim=node.prim):
                    accs[slot][...] = _REDUCE_COMBINE[prim](
                        accs[slot][...], part.astype(jnp.float32))
                continue
            prim = node.prim
            if prim == "broadcast_in_dim":
                role = roles[nid]
                env[nid] = jnp.broadcast_to(
                    val(node.inputs[0]),
                    (br, bc) if role is Role.FULL else
                    (br, 1) if role is Role.ROW else
                    (1, bc) if role is Role.COL else ())
            elif prim in ("reshape", "squeeze", "expand_dims", "copy",
                          "stop_gradient"):
                env[nid] = val(node.inputs[0])
            elif prim == "convert_element_type":
                env[nid] = val(node.inputs[0]).astype(node.spec.dtype)
            elif prim == "integer_pow":
                env[nid] = val(node.inputs[0]) ** node.params.get("y", 2)
            elif node.kind is OpKind.CONST:
                env[nid] = val(nid) if node.spec.size > 1 \
                    else jnp.asarray(node.value)
            else:
                env[nid] = _OPS[prim](*(val(i) for i in node.inputs))

        @pl.when(p == phases - 1)
        def _write():
            for ref, oid in zip(out_refs, out_ids):
                ref[...] = jnp.broadcast_to(env[oid], ref.shape).astype(
                    ref.dtype)

    in_specs = []
    for role in ext_roles:
        if role is Role.FULL:
            in_specs.append(pl.BlockSpec((br, bc), lambda i, p, j: (i, j)))
        elif role is Role.ROW:
            in_specs.append(pl.BlockSpec((br, 1), lambda i, p, j: (i, 0)))
        elif role is Role.COL:
            in_specs.append(pl.BlockSpec((1, bc), lambda i, p, j: (0, j)))
        else:
            in_specs.append(pl.BlockSpec((1, 1), lambda i, p, j: (0, 0)))

    out_specs, out_shapes = [], []
    for oid, role in zip(out_ids, out_roles):
        node = graph.node(oid)
        if role is Role.FULL:
            out_specs.append(pl.BlockSpec((br, bc), lambda i, p, j: (i, j)))
            out_shapes.append(jax.ShapeDtypeStruct((Rp, Cp), node.spec.dtype))
        elif role is Role.COL:
            # per-column values: every row block writes the same block
            out_specs.append(pl.BlockSpec((1, bc), lambda i, p, j: (0, j)))
            out_shapes.append(jax.ShapeDtypeStruct((1, Cp), node.spec.dtype))
        else:
            out_specs.append(pl.BlockSpec((br, 1), lambda i, p, j: (i, 0)))
            out_shapes.append(jax.ShapeDtypeStruct((Rp, 1), node.spec.dtype))

    from jax.experimental.pallas import tpu as pltpu
    call = pl.pallas_call(
        kernel,
        grid=(Rp // br, phases, NC),
        in_specs=in_specs,
        out_specs=out_specs if len(out_specs) > 1 else out_specs[0],
        out_shape=out_shapes if len(out_shapes) > 1 else out_shapes[0],
        scratch_shapes=[pltpu.VMEM((br, 1), jnp.float32) for _ in reduces],
        input_output_aliases=dict(io_aliases or {}),
        name=name,
        interpret=kernels.interpret_mode(),
    )

    out_orig = {o: graph.node(o).spec.shape for o in out_ids}

    def wrapper(*ext_vals):
        ops_in = []
        for nid, role, v in zip(ext_ids, ext_roles, ext_vals):
            if role is Role.FULL:
                v2 = v.reshape(R, C)
                v2 = jnp.pad(v2, ((0, Rp - R), (0, Cp - C)))
            elif role is Role.ROW:
                v2 = jnp.pad(v.reshape(R, 1), ((0, Rp - R), (0, 0)))
            elif role is Role.COL:
                v2 = jnp.pad(v.reshape(1, C), ((0, 0), (0, Cp - C)))
            else:
                v2 = jnp.asarray(v).reshape(1, 1)
            ops_in.append(v2)
        res = call(*ops_in)
        if not isinstance(res, (tuple, list)):
            res = (res,)
        outs = []
        for o, r in zip(out_ids, res):
            role = roles[o]
            if role is Role.FULL:
                r = r[:R, :C]
            elif role is Role.COL:
                r = r[:1, :C]
            elif role is Role.SCALAR:
                r = r[:1, :1]
            else:
                r = r[:R]
            outs.append(r.reshape(out_orig[o]))
        return tuple(outs)

    return wrapper


def _emit_packed(graph: Graph, pattern: frozenset[int],
                 ext_ids: list[int], out_ids: list[int]) -> Callable:
    """Kernel packing: run the whole subgraph as one fused XLA computation."""
    members = sorted(pattern)

    def packed_fn(*ext_vals):
        env: dict[int, Any] = dict(zip(ext_ids, ext_vals))
        for nid in members:
            node = graph.node(nid)
            if node.kind is OpKind.CONST:
                env[nid] = node.value
                continue
            ins = []
            for i in node.inputs:
                if i in env:
                    ins.append(env[i])
                else:  # external const
                    ins.append(graph.node(i).value)
            env[nid] = bind_node(node, ins)
        return tuple(env[o] for o in out_ids)

    return packed_fn


def _emit_pallas(graph: Graph, pattern: frozenset[int], info: RowInfo,
                 block_rows: int, ext_ids: list[int], out_ids: list[int],
                 *, order: list[int] | None = None,
                 staged: tuple | None = None,
                 io_aliases: dict[int, int] | None = None,
                 recompute: frozenset[int] = frozenset(),
                 name: str = kernel_name("onepass", 0)) -> Callable:
    R, C = info.R, info.C
    br = legal_block_rows(block_rows, R, row_tile(graph, pattern))
    Rp = math.ceil(R / br) * br
    members = order if order is not None else sorted(pattern)
    roles = info.roles

    # stage-vs-recompute: block composition stages by default; members in
    # ``recompute`` realize the paper's thread-composition alternative --
    # they are never materialized (no env entry, no scratch slot), each
    # consumer inlines the producer expression instead.  The decision is
    # made upstream (``memory_planner.plan_reuse`` via the latency
    # sweep); it wins exactly when VMEM is tight and recompute FLOPs are
    # free.

    ext_roles = [roles[i] for i in ext_ids]
    out_roles = [roles[o] for o in out_ids]
    out_specs_shapes = []
    for o, role in zip(out_ids, out_roles):
        node = graph.node(o)
        width = C if role in (Role.FULL, Role.COL) else 1
        out_specs_shapes.append((width, node.spec.dtype))

    # group emission: inter-pattern values ride in *explicit* VMEM scratch
    # (the memory planner's slot assignment, precomputed by emit_group),
    # not implicit env allocation.
    staged_slot, scratch_buffers = staged if staged is not None else ({}, [])

    def kernel(*refs):
        in_refs = refs[: len(ext_ids)]
        out_refs = refs[len(ext_ids): len(ext_ids) + len(out_ids)]
        scratch_refs = refs[len(ext_ids) + len(out_ids):]
        env: dict[int, Any] = {}
        for nid, role, ref in zip(ext_ids, ext_roles, in_refs):
            env[nid] = _to_block(ref[...], role, br, C)

        def val(i):
            if i in env:
                return env[i]
            if i in recompute:
                # thread composition: re-evaluate the producer inline
                # (a fresh copy of the expression per use -- no staged
                # value, no scratch slot).
                return compute(i)
            cnode = graph.node(i)  # embedded external const
            v = jnp.asarray(cnode.value)
            return (_to_block(v, roles[i], br, C)
                    if cnode.spec.size > 1 else v)

        def compute(nid):
            node = graph.node(nid)
            role = roles[nid]
            prim = node.prim
            if prim in _REDUCES:
                return _REDUCES[prim](val(node.inputs[0]))
            if prim == "broadcast_in_dim":
                return _to_block(jnp.broadcast_to(
                    val(node.inputs[0]),
                    (br, C) if role is Role.FULL else
                    (br, 1) if role is Role.ROW else
                    (1, C) if role is Role.COL else ()), role, br, C)
            if prim in ("reshape", "squeeze", "expand_dims", "copy",
                        "stop_gradient"):
                return val(node.inputs[0])
            if prim == "convert_element_type":
                return val(node.inputs[0]).astype(node.spec.dtype)
            if prim == "integer_pow":
                return val(node.inputs[0]) ** node.params.get("y", 2)
            return _OPS[prim](*(val(i) for i in node.inputs))

        for nid in members:
            node = graph.node(nid)
            if node.kind is OpKind.CONST:
                env[nid] = _to_block(
                    jnp.asarray(node.value), roles[nid], br, C
                ) if node.spec.size > 1 else jnp.asarray(node.value)
                continue
            if nid in recompute:
                continue  # rematerialized inside each consumer via val()

            env[nid] = compute(nid)
            slot = staged_slot.get(nid)
            if slot is not None:  # stage into the assigned VMEM buffer
                sref = scratch_refs[slot]
                sref[...] = jnp.broadcast_to(env[nid],
                                             sref.shape).astype(sref.dtype)
                env[nid] = sref[...]

        for ref, oid in zip(out_refs, out_ids):
            role = roles[oid]
            v = env[oid]
            width = C if role in (Role.FULL, Role.COL) else 1
            ref[...] = jnp.broadcast_to(v, (br, width)).astype(ref.dtype)

    in_specs = []
    for role in ext_roles:
        if role in (Role.FULL,):
            in_specs.append(pl.BlockSpec((br, C), lambda i: (i, 0)))
        elif role is Role.ROW:
            in_specs.append(pl.BlockSpec((br, 1), lambda i: (i, 0)))
        elif role is Role.COL:
            in_specs.append(pl.BlockSpec((1, C), lambda i: (0, 0)))
        else:
            in_specs.append(pl.BlockSpec((1, 1), lambda i: (0, 0)))

    out_specs = []
    out_shapes = []
    for (width, dtype), role in zip(out_specs_shapes, out_roles):
        out_specs.append(pl.BlockSpec((br, width), lambda i: (i, 0)))
        out_shapes.append(jax.ShapeDtypeStruct((Rp, width), dtype))

    from jax.experimental.pallas import tpu as pltpu
    call = pl.pallas_call(
        kernel,
        grid=(Rp // br,),
        in_specs=in_specs,
        out_specs=out_specs if len(out_specs) > 1 else out_specs[0],
        out_shape=out_shapes if len(out_shapes) > 1 else out_shapes[0],
        scratch_shapes=[pltpu.VMEM(shape, dtype)
                        for shape, dtype in scratch_buffers],
        input_output_aliases=dict(io_aliases or {}),
        name=name,
        interpret=kernels.interpret_mode(),
    )

    ext_shapes = {i: graph.node(i).spec.shape for i in ext_ids}
    out_orig_shapes = {o: graph.node(o).spec.shape for o in out_ids}

    def wrapper(*ext_vals):
        ops = []
        for nid, role, v in zip(ext_ids, ext_roles, ext_vals):
            if role is Role.FULL:
                v2 = v.reshape(R, C)
                if Rp != R:
                    v2 = jnp.pad(v2, ((0, Rp - R), (0, 0)))
            elif role is Role.ROW:
                v2 = v.reshape(R, 1)
                if Rp != R:
                    v2 = jnp.pad(v2, ((0, Rp - R), (0, 0)))
            elif role is Role.COL:
                v2 = v.reshape(1, C)
            else:
                v2 = jnp.asarray(v).reshape(1, 1)
            ops.append(v2)
        res = call(*ops)
        if not isinstance(res, (tuple, list)):
            res = (res,)
        outs = []
        for o, r in zip(out_ids, res):
            role = roles[o]
            # COL/scalar outputs are written identically by every row
            # block (the kernel broadcasts them over the block): slice
            # one copy back out instead of R of them.
            if role is Role.COL:
                r = r[:1]
            elif role is Role.SCALAR:
                r = r[:1, :1]
            else:
                r = r[:R]
            outs.append(r.reshape(out_orig_shapes[o]))
        return tuple(outs)

    return wrapper


# --------------------------------------------------------------------------
# compute-anchored emission
# --------------------------------------------------------------------------
def _eval_rowview(graph: Graph, members, roles, br: int, C: int,
                  env: dict) -> dict:
    """Evaluate a row-view subgraph on canonical 2D blocks.

    ``env`` maps external (and already-computed) node ids to their block
    values; members are evaluated in order and written back into ``env``.
    The op semantics mirror ``_emit_pallas``'s in-kernel ``compute`` so
    the prologue/epilogue chains of an anchored kernel behave exactly
    like the generic one-pass emitter would.
    """
    def val(i):
        if i in env:
            return env[i]
        cnode = graph.node(i)  # embedded external const
        v = jnp.asarray(cnode.value)
        return (_to_block(v, roles[i], br, C)
                if cnode.spec.size > 1 else v)

    for nid in members:
        node = graph.node(nid)
        if node.kind is OpKind.CONST:
            env[nid] = _to_block(
                jnp.asarray(node.value), roles[nid], br, C
            ) if node.spec.size > 1 else jnp.asarray(node.value)
            continue
        role = roles[nid]
        prim = node.prim
        if prim in _REDUCES:
            env[nid] = _REDUCES[prim](val(node.inputs[0]))
        elif prim == "broadcast_in_dim":
            env[nid] = _to_block(jnp.broadcast_to(
                val(node.inputs[0]),
                (br, C) if role is Role.FULL else
                (br, 1) if role is Role.ROW else
                (1, C) if role is Role.COL else ()), role, br, C)
        elif prim in ("reshape", "squeeze", "expand_dims", "copy",
                      "stop_gradient"):
            env[nid] = val(node.inputs[0])
        elif prim == "convert_element_type":
            env[nid] = val(node.inputs[0]).astype(node.spec.dtype)
        elif prim == "integer_pow":
            env[nid] = val(node.inputs[0]) ** node.params.get("y", 2)
        else:
            env[nid] = _OPS[prim](*(val(i) for i in node.inputs))
    return env


def _anchored_estimate(graph: Graph, union: frozenset[int],
                       hw: Hardware, block_rows: int,
                       n_steps: int) -> KernelEstimate:
    hbm = graph.pattern_hbm_bytes(union)
    flops = sum(2 * graph.node(a).spec.size
                * graph.node(graph.node(a).inputs[0]).spec.shape[-1]
                for a in union if graph.node(a).kind is OpKind.ANCHOR)
    return KernelEstimate(
        schedule="anchored", block_rows=block_rows,
        latency_s=hbm / hw.hbm_bw + flops / hw.peak_bf16_flops
        + hw.launch_s + hw.hbm_latency_s,
        hbm_bytes=hbm, vpu_ops=0.0, scratch_bytes=0,
        n_steps=n_steps, feasible=True)


def _emit_anchored(graph: Graph, parts, anchors, *, hw: Hardware = V5E,
                   ctx=None, group: int = 0) -> Emitted:
    """Compile an anchored stitch group into ONE compute kernel whose
    grid also runs the folded prologue/epilogue chains.  Raises
    ``AnchorEmitError`` on any structural mismatch -- the dispatch
    ladder re-emits the group's unanchored composition."""
    union = frozenset(n for p in parts for n in p)
    anchor_set = set(anchors)
    if ctx is not None:
        b = ctx.bounds(union)
        ext_all, out_ids = list(b.inputs), list(b.outputs)
    else:
        ext_all = graph.pattern_inputs(union)
        out_ids = graph.pattern_outputs(union)
    ext_ids = [i for i in ext_all if graph.node(i).kind is not OpKind.CONST]
    from .cost_model import anchor_interface_bytes
    folded = tuple(frozenset(p) for p in parts
                   if not (len(p) == 1 and p[0] in anchor_set))
    hbm_saved = anchor_interface_bytes(graph, anchors, folded)

    if len(anchors) == 1:
        m = _match_matmul_anchor(graph, union, anchors[0])
        if m is None:
            raise AnchorEmitError("anchored matmul: structure mismatch")
        return _emit_anchored_matmul(graph, parts, m, ext_ids, out_ids,
                                     hbm_saved, hw=hw, group=group)
    if len(anchors) == 2:
        m = _match_attention_anchors(graph, union, anchors)
        if m is None:
            raise AnchorEmitError("anchored attention: structure mismatch")
        if list(out_ids) != [m["pv"]]:
            raise AnchorEmitError("anchored attention: escaping chain value")
        return _emit_anchored_attention(graph, parts, m, ext_ids,
                                        hbm_saved, hw=hw, group=group)
    raise AnchorEmitError(f"unsupported anchor count {len(anchors)}")


def _emit_anchored_matmul(graph: Graph, parts, m: dict, ext_ids, out_ids,
                          hbm_saved: int, *, hw: Hardware,
                          group: int) -> Emitted:
    from ..kernels.matmul import DEFAULT_BLOCK_M, matmul_fused

    a, lhs_id, rhs_id = m["a"], m["lhs"], m["rhs"]
    M, K, N = m["M"], m["K"], m["N"]
    pro, epi = m["pro"], m["epi"]
    pro_info, epi_info = m["pro_info"], m["epi_info"]
    bm = max(1, min(DEFAULT_BLOCK_M, M))
    anchor_dtype = graph.node(a).spec.dtype

    if pro:
        pro_ext = [i for i in graph.pattern_inputs(pro)
                   if graph.node(i).kind is not OpKind.CONST]
        pro_roles = [pro_info.roles[i].value for i in pro_ext]
        pro_order = sorted(pro)

        def prologue(*blocks):
            env = dict(zip(pro_ext, blocks))
            _eval_rowview(graph, pro_order, pro_info.roles, bm, K, env)
            return env[lhs_id]
    else:
        pro_ext = [lhs_id]
        pro_roles = ["full"]
        prologue = None

    if epi:
        epi_ext = [i for i in graph.pattern_inputs(epi)
                   if i != a and graph.node(i).kind is not OpKind.CONST]
        epi_roles = [epi_info.roles[i].value for i in epi_ext]
        out_roles = [epi_info.roles[o].value for o in out_ids]
        epi_order = sorted(epi)

        def epilogue(acc, *blocks):
            env = dict(zip(epi_ext, blocks))
            env[a] = acc
            _eval_rowview(graph, epi_order, epi_info.roles, bm, N, env)
            return tuple(env[o] for o in out_ids)
    else:
        epi_ext = []
        epi_roles = []
        out_roles = ["full"]
        epilogue = None

    out_dtypes = [graph.node(o).spec.dtype for o in out_ids]
    out_shapes = {o: graph.node(o).spec.shape for o in out_ids}

    def fn(*ext_vals):
        env = dict(zip(ext_ids, ext_vals))

        def get(i):
            return env[i] if i in env else graph.node(i).value

        outs = matmul_fused(
            [get(i) for i in pro_ext], get(rhs_id),
            [get(i) for i in epi_ext],
            M=M, K=K, N=N, pro_roles=pro_roles, epi_roles=epi_roles,
            out_roles=out_roles, out_dtypes=out_dtypes,
            anchor_dtype=anchor_dtype, prologue=prologue,
            epilogue=epilogue, block_m=bm,
            name=kernel_name("anchored", group))
        return tuple(o.reshape(out_shapes[oid])
                     for o, oid in zip(outs, out_ids))

    union = frozenset(n for p in parts for n in p)
    est = _anchored_estimate(graph, union, hw, bm, math.ceil(M / bm))
    vmem = bm * K * graph.node(lhs_id).spec.itemsize \
        + K * N * graph.node(rhs_id).spec.itemsize + bm * N * 4
    return Emitted(fn, "pallas", est, ext_ids, list(out_ids),
                   vmem, vmem, parts=parts, hbm_saved=hbm_saved,
                   name=kernel_name("anchored", group), group=group)


def _emit_anchored_attention(graph: Graph, parts, m: dict, ext_ids,
                             hbm_saved: int, *, hw: Hardware,
                             group: int) -> Emitted:
    from ..kernels.flash_attention import DEFAULT_BLOCK_K, DEFAULT_BLOCK_Q, \
        flash_attention

    qk, pv = m["qk"], m["pv"]
    q_id, k_id, v_id = m["q"], m["k"], m["v"]
    B, H, Sq, Sk = m["extent"]
    D = m["D"]
    s_pre, score, score_ext = m["s_pre"], m["score"], m["score_ext"]
    extent = m["extent"]
    score_order = sorted(score)
    out_spec = graph.node(pv).spec
    score_shapes = [_pad4(graph.node(i).spec.shape) for i in score_ext]

    def _blk_shape(nid, bq, bk):
        d = _pad4(graph.node(nid).spec.shape)
        return (bq if d[2] == Sq and Sq != 1 else 1,
                bk if d[3] == Sk and Sk != 1 else 1)

    def score_mod(s, *blocks):
        if not score:
            return s
        bq, bk = s.shape
        env = {qk: s}
        env.update(zip(score_ext, blocks))
        for nid in score_order:
            node = graph.node(nid)
            prim = node.prim

            def val(i):
                if i in env:
                    return env[i]
                v = jnp.asarray(graph.node(i).value)  # scalar const
                return v.reshape(()) if v.size == 1 \
                    else v.reshape(_blk_shape(i, bq, bk))

            if node.kind is OpKind.CONST:
                env[nid] = val(nid)
            elif prim == "broadcast_in_dim":
                env[nid] = jnp.broadcast_to(val(node.inputs[0]),
                                            _blk_shape(nid, bq, bk))
            elif prim in ("reshape", "squeeze", "expand_dims", "copy",
                          "stop_gradient"):
                env[nid] = val(node.inputs[0])
            elif prim == "convert_element_type":
                env[nid] = val(node.inputs[0]).astype(node.spec.dtype)
            elif prim == "integer_pow":
                env[nid] = val(node.inputs[0]) ** node.params.get("y", 2)
            else:
                env[nid] = _OPS[prim](*(val(i) for i in node.inputs))
        return env[s_pre]

    def fn(*ext_vals):
        env = dict(zip(ext_ids, ext_vals))

        def get(i):
            return env[i] if i in env else graph.node(i).value

        sargs = [jnp.asarray(get(i)).reshape(sh)
                 for i, sh in zip(score_ext, score_shapes)]
        out = flash_attention(
            get(q_id), get(k_id), get(v_id), causal=False, scale=1.0,
            score_mod=score_mod if score else None,
            score_args=sargs, name=kernel_name("anchored", group))
        return (out.astype(out_spec.dtype).reshape(out_spec.shape),)

    union = frozenset(n for p in parts for n in p)
    bq = max(1, min(DEFAULT_BLOCK_Q, Sq))
    bk = max(1, min(DEFAULT_BLOCK_K, Sk))
    n_steps = B * H * math.ceil(Sq / bq) * math.ceil(Sk / bk)
    est = _anchored_estimate(graph, union, hw, bq, n_steps)
    vmem = bq * D * 4 + bk * D * 8 + bq * bk * 4 + bq * (D + 2) * 4
    return Emitted(fn, "pallas", est, ext_ids, [pv],
                   vmem, vmem, parts=parts, hbm_saved=hbm_saved,
                   name=kernel_name("anchored", group), group=group)
