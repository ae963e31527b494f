"""Measured block-schedule autotuning (optional, accelerator-gated).

The analytic latency-evaluator picks ``BLOCK_ROWS`` / streaming tiles
from the roofline model; on real hardware the best launch dims can
deviate (padding effects, DMA granularity).  ``tune_pattern`` sweeps the
same candidate space the analytic model enumerates, but *measures* each
emitted kernel on dummy inputs and returns the fastest as a schedule
override; ``tune_group`` does the same for a whole stitch group's union
kernel (the megakernel's onepass/streaming phase split and tile choice)
-- both results land in the persistent plan cache, giving the paper's
tune-once-run-many behavior.

Sweeps are **batch-compiled**: every surviving candidate becomes one
branch of a single ``lax.switch``, so one ``jax.jit`` lowering +
compilation pass covers the whole sweep and all candidates share one
set of dummy inputs; per-candidate measurement then re-dispatches the
same compiled executable with a different branch index.  The previous
per-candidate compile-measure loop survives as ``batch_compile=False``
(the equivalence oracle for tests and the baseline the benchmark's
speedup is quoted against).

Gating: measuring wall time in Pallas interpret mode on CPU says nothing
about TPU latency, so the sweep runs only where kernels compile for the
chip (or ``REPRO_AUTOTUNE=force`` for tests / CI smoke).  Otherwise
the caller falls back to the analytic cost model.
"""
from __future__ import annotations

import os
import time
from dataclasses import dataclass, field

import numpy as np

from repro.runtime.guard import RaceTimeoutError, race_timeout_s, \
    watchdog_cancelled, watchdog_sleep, with_watchdog
from repro.testing import faults as _faults

from .codegen import _override_estimate, emit_group, emit_pattern, \
    pattern_emittable
from .cost_model import BLOCK_ROWS, STREAM_TILES, Hardware, V5E, \
    legal_block_rows, row_tile
from .ir import Graph, OpKind
from .plan_cache import override_fp

#: Env switch: "force" measures even without an accelerator (tests).
ENV_AUTOTUNE = "REPRO_AUTOTUNE"


def autotune_available() -> bool:
    """Measured tuning is meaningful only for compiled kernels: timing
    the Pallas interpreter says nothing about the chip."""
    if os.environ.get(ENV_AUTOTUNE, "").lower() == "force":
        return True
    from repro import kernels

    return not kernels.interpret_mode()


def _candidate_overrides(info, tile: int) -> list[dict]:
    """The analytic schedule space, rounded onto the sublane ``tile``."""
    cands: list[dict] = []
    for br in sorted({legal_block_rows(br, info.R, tile)
                      for br in BLOCK_ROWS}):
        cands.append({"schedule": "onepass", "block_rows": br})
    for br, bc in STREAM_TILES:
        cands.append({"schedule": "streaming",
                      "block_rows": legal_block_rows(br, info.R, tile),
                      "block_cols": bc})
    return cands


def _recompute_variants(graph, pattern, info, ctx, hw):
    """Yield (override, estimate) for every feasible thread-composition
    one-pass of ``pattern``: block sizes whose ``reuse_plan`` flips fit
    the VMEM budget.  The single source of the recompute override shape
    for both the measured sweep (``_recompute_overrides``) and the
    partition race's swap branches (``_recompute_swap_override``)."""
    from .cost_model import estimate_onepass, reuse_plan

    if info is None:
        return
    for br in BLOCK_ROWS:
        rp = (ctx.reuse(pattern, br) if ctx is not None
              else reuse_plan(graph, pattern, info, br, hw))
        if rp is not None and rp.feasible and rp.recompute:
            est = estimate_onepass(graph, pattern, info, br, hw, ctx=ctx,
                                   recompute=rp.recompute)
            if est.feasible:
                yield ({"schedule": "onepass",
                        "block_rows": est.block_rows,
                        "recompute": sorted(est.recompute_ids)}, est)
        if br >= info.R:
            break


def _recompute_overrides(graph, pattern, info, ctx, hw) -> list[dict]:
    """Thread-composition candidates for the measured sweep: one
    override per distinct (block_rows, flip set).  These race alongside
    the staged/streaming candidates so a tuned pin can itself be a
    recompute schedule."""
    out: list[dict] = []
    seen: set[tuple] = set()
    for over, _est in _recompute_variants(graph, pattern, info, ctx, hw):
        fp = override_fp(over)
        if fp not in seen:
            seen.add(fp)
            out.append(over)
    return out


def _dummy_inputs(graph: Graph, ext_ids, rng) -> list:
    import jax.numpy as jnp

    return [jnp.asarray(rng.standard_normal(graph.node(i).spec.shape),
                        dtype=graph.node(i).spec.dtype)
            for i in ext_ids]


def _sync_all(out) -> None:
    """Block on EVERY output leaf, not just the container.

    A timed sample that only synchronizes the last output (or trusts a
    tuple to be synchronized as a unit) measures dispatch-queue depth on
    asynchronous-dispatch backends, not kernel latency -- candidates
    with more outputs would look faster.  Flatten and block each leaf
    explicitly so every array the candidate produced has landed before
    the clock stops.
    """
    import jax

    for leaf in jax.tree_util.tree_leaves(out):
        block = getattr(leaf, "block_until_ready", None)
        if block is not None:
            block()


def _time_callable(fn, args, *, warmup: int = 1, iters: int = 3,
                   key=None) -> float:
    """Best-of-``iters`` wall time of ``fn(*args)`` after ``warmup``
    untimed calls (each fully synchronized, see ``_sync_all``).

    ``key`` identifies the candidate being measured (its override,
    hashable); it is unused here but lets tests monkeypatch this
    function with a deterministic fake so the batched and serial sweep
    paths can be compared exactly.
    """
    del key

    for _ in range(warmup):
        _sync_all(fn(*args))
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        _sync_all(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


#: Sentinel for seam detection: tests and the emulated-silicon benchmark
#: replace ``_time_callable`` with a deterministic fake keyed on the
#: candidate; the amortized single-dispatch screening path (which never
#: consults the seam) must stand down whenever the seam is patched so
#: those fakes keep deciding the sweep.
_TIME_CALLABLE_DEFAULT = _time_callable


def _emit_candidates(info, emit, tile: int,
                     extra: list[dict] | None = None
                     ) -> list[tuple[dict, object]]:
    """Emit every analytic-space candidate (plus ``extra`` recompute
    overrides); drop the ones the emitter refuses (infeasible override
    -> the emitter falls back to another schedule or to the recompute
    variant) or that fail to build at all.  A fallback kernel
    masquerading under the override's label would let the sweep race N
    identical kernels and persist a tuned pin whose parameters never
    actually ran, so the emitted estimate must match the override's
    schedule, its (clamped) block rows, and its stage-vs-recompute
    choice."""
    cands: list[tuple[dict, object]] = []
    for over in _candidate_overrides(info, tile) + list(extra or ()):
        try:
            em = emit(over)
        except Exception:  # noqa: BLE001 - a failing candidate just loses
            continue
        est = em.estimate
        if est.schedule != over["schedule"]:
            continue
        want_br = over.get("block_rows")
        if want_br and est.block_rows != max(1, min(want_br, info.R)):
            continue  # emitter fell back to a different launch dim
        if sorted(est.recompute_ids) != sorted(over.get("recompute", ())):
            continue  # stage-vs-recompute fallback masquerading
        cands.append((over, em))
    return cands


def _sane_timing(t) -> bool:
    """A usable sample: finite, non-negative, an actual number.  A
    branch that reports NaN/inf/negative wall time (a poisoned clock, a
    garbage test fake, an overflowed delta) is disqualified rather than
    allowed to win the race with a nonsense number."""
    try:
        t = float(t)
    except (TypeError, ValueError):
        return False
    return np.isfinite(t) and t >= 0.0


def _measure_serial(cands, graph: Graph, rng) -> dict | None:
    """Today's-baseline sweep: per-candidate dummy inputs + warmup +
    timing, one candidate at a time (no shared compilation)."""
    best_t, best_over = float("inf"), None
    for over, em in cands:
        try:
            args = _dummy_inputs(graph, em.ext_ids, rng)
            t = _time_callable(em.fn, args,
                               key=override_fp(over))
        except Exception:  # noqa: BLE001
            continue
        if not _sane_timing(t):
            continue  # garbage timing: disqualify, don't abort the race
        if t < best_t:
            best_t, best_over = t, over
    return best_over


#: The sweep executable is compiled at reduced XLA optimization: the
#: program is throwaway (run a handful of times each candidate) and the
#: kernels under measurement are Pallas/Mosaic-compiled either way, so
#: backend-level optimization only burns tune time on the glue code.
_SWEEP_COMPILER_OPTIONS = {"xla_backend_optimization_level": "0"}


def _screen_single_dispatch(fns, args, reps) -> dict[int, float] | None:
    """Amortized screening: ALL branches back-to-back in ONE device
    program, per-branch host timestamps, two dispatches total.

    The branches are chained into a single jitted program with an
    ordered ``io_callback`` timestamp between consecutive branches;
    data dependencies force strict sequencing (each timestamp consumes
    a scalar folded from every output leaf of the branch before it --
    so no branch is dead-code-eliminated or reordered -- and the next
    branch's first argument consumes a zero derived from that
    timestamp).  One warm run pays every branch's one-time costs, then
    one timed run yields all per-branch deltas -- amortizing the
    per-branch dispatch round-trips of the old screening loop into a
    single dispatch.  Returns {branch: seconds} or None (the caller
    falls back to per-branch screening dispatches).
    """
    import jax
    import jax.numpy as jnp

    try:
        from jax.experimental import io_callback
    except ImportError:  # pragma: no cover - ancient jax
        return None

    epoch = [time.perf_counter()]

    def clock(_dep):
        # seconds since the current run's epoch: the epoch is re-based
        # right before each dispatch (lowering + compiling the chained
        # program can take seconds-to-minutes, and a float32 timestamp
        # at minute magnitude has ~us ULP -- comparable to a branch's
        # runtime), so timed-run magnitudes stay small and quantization
        # far below any branch delta.
        return np.float32(time.perf_counter() - epoch[0])

    spec = jax.ShapeDtypeStruct((), jnp.float32)

    def chained(*a):
        stamps = [io_callback(clock, spec, jnp.float32(0.0), ordered=True)]
        for k in reps:
            ak = a
            if a:  # serialize: branch k starts after timestamp k-1
                gate = (stamps[-1] * 0).astype(a[0].dtype)
                ak = (a[0] + gate,) + tuple(a[1:])
            out = fns[k](*ak)
            dep = jnp.float32(0.0)
            for leaf in jax.tree_util.tree_leaves(out):
                dep = dep + jnp.ravel(leaf)[0].astype(jnp.float32) * 0
            stamps.append(io_callback(clock, spec, dep, ordered=True))
        return tuple(stamps)

    try:
        lowered = jax.jit(chained).lower(*args)
        try:
            prog = lowered.compile(compiler_options=_SWEEP_COMPILER_OPTIONS)
        except Exception:  # noqa: BLE001 - options unknown to this backend
            prog = lowered.compile()
        epoch[0] = time.perf_counter()
        _sync_all(prog(*args))              # warm every branch once
        epoch[0] = time.perf_counter()      # re-base for the timed run
        stamps = [float(s) for s in prog(*args)]
    except Exception:  # noqa: BLE001 - any bad branch: fall back
        return None
    return {k: max(b - a, 0.0)
            for k, a, b in zip(reps, stamps, stamps[1:])}


def _measure_switch_branches(fns, args, keys,
                             rep_of: dict[int, int] | None = None
                             ) -> list[float | None] | None:
    """The shared batched measurement pipeline: compile every callable
    as a branch of ONE jitted ``lax.switch``, then screen + refine.

    The branches are selected by a *traced* index, so the whole sweep
    is traced, lowered and compiled exactly once (every branch compiles
    inside that one XLA program) and the dummy inputs are shared.
    Screening prefers the amortized path
    (``_screen_single_dispatch``: all branches back-to-back inside one
    device program with per-branch timestamps -- a single dispatch
    instead of one per branch); when that path is unavailable, or when
    the ``_time_callable`` seam has been replaced by a deterministic
    test fake, screening falls back to one warmed timed dispatch per
    branch through the seam -- the executable is compiled either way,
    but branch k's first dispatch still pays one-time costs
    (branch-local constant uploads, allocator warm paths), so it is
    never timed cold.  Only the two front-runners get the full min-of-k
    refinement.  ``keys[k]`` is branch k's ``_time_callable`` seam key;
    ``rep_of`` (branch -> representative branch) lets structurally
    isomorphic branches share one measurement.  Returns per-branch best
    times (None: that branch failed to time), or None when the batch
    itself failed to compile/warm -- the caller falls back to its
    serial path.
    """
    import jax
    from jax import lax

    if rep_of is None:
        rep_of = {k: k for k in range(len(fns))}
    reps = sorted(set(rep_of.values()))

    def _compile(fn, *sample):
        lowered = jax.jit(fn).lower(*sample)
        try:
            return lowered.compile(compiler_options=_SWEEP_COMPILER_OPTIONS)
        except Exception:  # noqa: BLE001 - options unknown to this backend
            return lowered.compile()

    screened: dict[int, float] = {}
    branch_fn: dict[int, object] = {}   # branch -> timed dispatchable
    amortized = False
    if len(reps) > 1 and _time_callable is _TIME_CALLABLE_DEFAULT:
        screened = _screen_single_dispatch(fns, args, reps) or {}
        amortized = bool(screened)
    if not screened:
        # seam path: one switch executable, one warmed timed dispatch
        # per branch through ``_time_callable``.
        if len(fns) == 1:
            sweep_fn = (lambda i, *a: fns[0](*a))
        else:
            sweep_fn = (lambda i, *a: lax.switch(i, fns, *a))
        try:
            sweep = _compile(sweep_fn, 0, *args)  # the single lowering pass
            _sync_all(sweep(0, *args))
        except Exception:  # noqa: BLE001 - a bad branch poisons the batch
            return None
        for k in reps:
            branch_fn[k] = (lambda *a, _k=k: sweep(_k, *a))
        for k in reps:
            try:
                screened[k] = _time_callable(branch_fn[k], args,
                                             warmup=1, iters=1, key=keys[k])
            except Exception:  # noqa: BLE001
                continue
    # NaN/inf/negative samples disqualify their branch, never the race
    screened = {k: t for k, t in screened.items() if _sane_timing(t)}
    if not screened:
        return None
    refined: set[int] = set()

    def refine(k: int) -> None:
        fnk = branch_fn.get(k)
        if fnk is None:  # amortized screening: compile the finalist only
            fnk = branch_fn[k] = _compile(fns[k], *args)
        t = _time_callable(fnk, args, warmup=1, iters=2, key=keys[k])
        if not _sane_timing(t):
            raise ValueError(f"garbage refinement timing {t!r}")
        # the amortized timestamp delta is a different methodology
        # (callback spacing, clamped at 0): a spuriously low value must
        # be REPLACED by the refined standalone timing, not min-ed with
        # it -- min is only sound when both numbers come from the same
        # _time_callable pipeline.
        screened[k] = t if amortized else min(screened[k], t)

    def try_refine(k: int) -> None:
        try:
            refine(k)
        except Exception:  # noqa: BLE001
            # an amortized branch whose standalone refinement failed
            # must not keep competing on its raw timestamp delta (it
            # could decide the sweep on a clamped-at-0 number); on the
            # seam path the screening value is a real _time_callable
            # measurement and stays.
            if amortized:
                screened.pop(k, None)
        refined.add(k)

    for k in sorted(screened, key=screened.get)[:2]:  # top-2 refinement
        try_refine(k)
    while amortized:
        # the winner must be a refined timing: a raw timestamp delta
        # (possibly quantized/clamped toward 0) may rank branches but
        # never decide the sweep, so keep refining any branch that
        # still undercuts the refined front-runner.
        floor = min((screened[k] for k in refined if k in screened),
                    default=None)
        pending = [k for k in screened
                   if k not in refined and (floor is None
                                            or screened[k] < floor)]
        if not pending:
            break
        try_refine(min(pending, key=screened.get))
    if not screened:
        return None  # every refinement failed: poisoned batch, go serial
    return [screened.get(rep_of[k]) for k in range(len(fns))]


def _measure_batched(cands, graph: Graph, rng) -> dict | None:
    """Batched schedule sweep over one kernel's candidate overrides:
    ``_measure_switch_branches`` over the emitted candidates (shared
    dummy inputs; branch signatures agree by construction since every
    candidate takes the union's external inputs and returns its
    outputs), falling back to the serial loop on a poisoned batch."""
    args = _dummy_inputs(graph, cands[0][1].ext_ids, rng)
    keys = [override_fp(over) for over, _em in cands]
    times = _measure_switch_branches([em.fn for _, em in cands], args, keys)
    if times is None:
        return _measure_serial(cands, graph, rng)
    best_t, best_over = float("inf"), None
    for (over, _em), t in zip(cands, times):
        if t is not None and t < best_t:
            best_t, best_over = t, over
    return best_over


def _sweep(info, emit, graph: Graph, tile: int, *, batch_compile: bool,
           extra_overrides: list[dict] | None = None) -> dict | None:
    cands = _emit_candidates(info, emit, tile, extra=extra_overrides)
    if not cands:
        return None
    rng = np.random.default_rng(0)
    if batch_compile:
        return _measure_batched(cands, graph, rng)
    return _measure_serial(cands, graph, rng)


def tune_pattern(graph: Graph, pattern: frozenset[int], *,
                 hw: Hardware = V5E,
                 ctx=None, batch_compile: bool = True) -> dict | None:
    """Measure candidate schedules for one pattern; None -> keep analytic.

    Returns the winning ``{"schedule", "block_rows"[, "block_cols"]}``
    override, or None when the pattern has no row view / nothing beats
    running the sweep (e.g. every candidate failed to emit).
    """
    if ctx is not None:
        info = ctx.info(pattern)
    else:
        from .rowspec import analyze

        info = analyze(graph, pattern)
    if info is None or not pattern_emittable(graph, pattern, info=info):
        return None

    def emit(over):
        return emit_pattern(graph, pattern, hw=hw, ctx=ctx,
                            schedule_override=over)

    return _sweep(info, emit, graph, row_tile(graph, pattern, ctx),
                  batch_compile=batch_compile,
                  extra_overrides=_recompute_overrides(graph, pattern,
                                                       info, ctx, hw))


def tune_group(graph: Graph, parts, *, hw: Hardware = V5E,
               ctx=None,
               batch_compile: bool = True) -> dict | None:
    """Measure candidate schedules for a stitch group's union megakernel.

    ``parts`` are the group's member patterns (as for ``emit_group``).
    The candidate space is the analytic sweep over the *union*: onepass
    block rows vs. streaming phase splits x column tiles.  Returns the
    winning override, or None when the union has no row view or no
    candidate emitted.
    """
    parts = tuple(frozenset(p) for p in parts)
    union: frozenset[int] = frozenset()
    for p in parts:
        union |= p
    if ctx is not None:
        info = ctx.info(union)
    else:
        from .rowspec import analyze

        info = analyze(graph, union)
    if info is None or not pattern_emittable(graph, union, info=info):
        return None

    def emit(over):
        return emit_group(graph, parts, hw=hw, ctx=ctx,
                          schedule_override=over)

    return _sweep(info, emit, graph, row_tile(graph, union, ctx),
                  batch_compile=batch_compile,
                  extra_overrides=_recompute_overrides(graph, union,
                                                       info, ctx, hw))


# ---------------------------------------------------------------------------
# joint partition x schedule tuning (paper: tune the stitching *scheme*)
# ---------------------------------------------------------------------------
#: Hard cap on (partition, schedule-assignment) branches in one sweep:
#: every branch is a whole-partition program, so the switch's compile
#: time grows with each one.  All-analytic assignments are kept first;
#: excess per-group schedule swaps are dropped.
MAX_PARTITION_BRANCHES = 32


@dataclass
class PartitionTuneResult:
    """Outcome of racing candidate partitions on silicon."""

    index: int                   # winning candidate (rank in model order)
    overrides: list[dict]        # per-group schedule pin for the winner
                                 # ({} = the analytic pick)
    measured_s: list[float] = field(default_factory=list)
    # best measured wall time per candidate (inf: never timed)
    branches: int = 0            # (partition, assignment) pairs raced


def _alt_schedule_override(graph, union, info, ctx, hw) -> dict | None:
    """The best-priced feasible override from the schedule family the
    analytic model did NOT pick (onepass <-> streaming) -- the coarse
    schedule axis that can flip a partition comparison on silicon.  The
    fine tile sweep within the winning family stays ``tune_group``'s
    job after the partition is committed."""
    from .cost_model import best_estimate

    best = ctx.best(union) if ctx is not None \
        else best_estimate(graph, union, hw)
    alt = {"onepass": "streaming", "streaming": "onepass"}.get(best.schedule)
    if alt is None or info is None:
        return None
    pick: tuple[dict, float] | None = None
    for over in _candidate_overrides(info, row_tile(graph, union, ctx)):
        if over["schedule"] != alt:
            continue
        est = _override_estimate(graph, union, info, over, hw, ctx=ctx)
        if est is None:
            continue
        if pick is None or est.latency_s < pick[1]:
            pick = (over, est.latency_s)
    return pick[0] if pick else None


def _recompute_swap_override(graph, union, info, ctx, hw) -> dict | None:
    """The best-priced feasible *recompute one-pass* override for a
    union whose analytic best is something else -- the stage-vs-
    recompute axis of the race.  The model engages recompute only when
    staging is VMEM-infeasible, so when the best schedule is streaming
    (or packed), a feasible thread-composition one-pass is exactly the
    close call silicon should settle; it becomes one extra branch of
    the partition ``lax.switch``.  When the best already IS a recompute
    one-pass, ``_alt_schedule_override``'s family swap races streaming
    against it instead."""
    best = ctx.best(union)
    if best.schedule == "onepass":
        return None  # staged or recompute onepass won: nothing to swap in
    pick: tuple[dict, float] | None = None
    for over, est in _recompute_variants(graph, union, info, ctx, hw):
        if pick is None or est.latency_s < pick[1]:
            pick = (over, est.latency_s)
    return pick[0] if pick else None


def _region_schedule(graph: Graph, region: frozenset[int],
                     kernels: list) -> list[tuple[str, int]] | None:
    """Dependency-ordered execution plan of ``region`` for one candidate:
    group kernels plus the region nodes this candidate leaves bare
    (nodes another candidate absorbs into a kernel).  Returns None on a
    dependence cycle (defensive; convex groups cannot produce one)."""
    member_of: dict[int, int] = {}
    for k, (em, members) in enumerate(kernels):
        for nid in members:
            member_of[nid] = k
    sched: list[tuple[str, int]] = []
    done: set[int] = set()
    pending_nodes = [n for n in sorted(region) if n not in member_of]
    pending_kernels = list(range(len(kernels)))
    while pending_nodes or pending_kernels:
        progressed = False
        keep_n: list[int] = []
        for nid in pending_nodes:
            if all(i not in region or i in done
                   for i in graph.node(nid).inputs):
                sched.append(("node", nid))
                done.add(nid)
                progressed = True
            else:
                keep_n.append(nid)
        pending_nodes = keep_n
        keep_k: list[int] = []
        for k in pending_kernels:
            em, members = kernels[k]
            if all(e not in region or e in done for e in em.ext_ids):
                sched.append(("kernel", k))
                done.update(members)
                progressed = True
            else:
                keep_k.append(k)
        pending_kernels = keep_k
        if not progressed:
            return None
    return sched


def _partition_runner(graph: Graph, sched, kernels,
                      ext_ids: list[int], out_ids: list[int]):
    """Closure executing one candidate's region program: group kernels
    in dependency order, bare nodes via ``bind_node`` -- the same shape
    as ``stitch._Compiled._run_schedule`` restricted to the region, so
    every branch of the partition sweep maps the region's external
    inputs to the identical output tuple."""
    from .tracer import bind_node

    def runner(*ext_vals):
        env = dict(zip(ext_ids, ext_vals))
        for kind, item in sched:
            if kind == "node":
                node = graph.node(item)
                if node.kind is OpKind.CONST:
                    env[item] = node.value
                    continue
                ins = [env[i] if i in env else graph.node(i).value
                       for i in node.inputs]
                env[item] = bind_node(node, ins)
            else:
                em = kernels[item][0]
                outs = em.fn(*[env[i] for i in em.ext_ids])
                for oid, val in zip(em.out_ids, outs):
                    env[oid] = val
        return tuple(env[o] for o in out_ids)

    return runner


@dataclass
class _Branch:
    ci: int                      # candidate partition index
    assignment: dict             # group index -> schedule override
    runner: object               # region program for this assignment
    mkey: tuple                  # structural measurement key (iso dedup)
    tkey: tuple                  # _time_callable seam key


def _branch_tkey(ci: int, assignment: dict) -> tuple:
    return ("partition", ci,
            tuple(sorted((gi, override_fp(over))
                         for gi, over in assignment.items())))


def _candidate_branches(graph: Graph, ci: int, groups, region, ext_ids,
                        out_ids, ctx, hw,
                        emit_cache: dict) -> list[_Branch]:
    """All (this partition, schedule-assignment) branches: the
    all-analytic assignment first, then one swap per stitched group
    into the opposite schedule family's best-priced override, plus one
    stage-vs-recompute swap (``_recompute_swap_override``) for groups
    whose analytic best left a feasible thread-composition one-pass on
    the table."""
    def emitted_for(grp, over: dict | None):
        anchors = tuple(getattr(grp, "anchors", ()))
        key = (grp.members, anchors, override_fp(over))
        if key not in emit_cache:
            em = emit_group(graph, grp.parts, hw=hw, ctx=ctx,
                            schedule_override=over or None,
                            anchors=anchors)
            if anchors:
                pass  # anchored emission has one fixed scheme
            elif over and em.estimate.schedule != over.get("schedule"):
                em = None  # emitter fell back: not the asked-for schedule
            elif over and sorted(em.estimate.recompute_ids) != sorted(
                    over.get("recompute", ())):
                em = None  # stage-vs-recompute choice not honored
            emit_cache[key] = em
        return emit_cache[key]

    def build(assignment: dict) -> _Branch | None:
        kernels = []
        mkey_parts = []
        for gi, grp in enumerate(groups):
            over = assignment.get(gi)
            em = emitted_for(grp, over)
            if em is None:
                return None
            kernels.append((em, grp.members))
            mkey_parts.append((ctx.struct_key(grp.members),
                               override_fp(over)))
        sched = _region_schedule(graph, region, kernels)
        if sched is None:
            return None
        bare = tuple(sorted(n for n in region
                            if all(n not in m for _, m in kernels)))
        mkey = (tuple(mkey_parts),
                tuple(ctx.struct_key(frozenset({n})) for n in bare))
        runner = _partition_runner(graph, sched, kernels, ext_ids, out_ids)
        return _Branch(ci, assignment, runner, mkey,
                       _branch_tkey(ci, assignment))

    out: list[_Branch] = []
    try:
        base = build({})
    except Exception:  # noqa: BLE001 - unemittable candidate just loses
        return out
    if base is None:
        return out
    out.append(base)
    for gi, grp in enumerate(groups):
        if getattr(grp, "anchors", ()) or not grp.stitched:
            continue  # anchored groups race as-is: no schedule family swap
        for swap in (_alt_schedule_override, _recompute_swap_override):
            try:
                over = swap(graph, grp.members,
                            ctx.info(grp.members), ctx, hw)
                if over is None:
                    continue
                br = build({gi: over})
            except Exception:  # noqa: BLE001
                continue
            if br is not None:
                out.append(br)
    return out


def tune_partitions(graph: Graph, candidates, *, hw: Hardware = V5E,
                    ctx=None,
                    batch_compile: bool = True
                    ) -> PartitionTuneResult | None:
    """Race candidate partitions (each a list of ``StitchGroup``) on
    silicon; return the measured winner and its schedule assignment.

    The branch space is every (partition, candidate-schedule) pair:
    each candidate contributes its all-analytic assignment plus one
    swap per stitched group into the opposite schedule family.  All
    branches lower as ONE jitted ``lax.switch`` over a shared *region*
    program -- the union of every candidate's members, with nodes a
    candidate does not cover executed bare -- so every branch takes the
    same inputs and returns the same outputs and a single compile
    covers the whole sweep (``batch_compile=False`` keeps the serial
    loop as the equivalence oracle).  Screening (one warmed sample per
    branch) plus top-2 refinement picks the winner; structurally
    isomorphic branches (equal per-group ``struct_key`` + override
    sequences) are measured once.  Returns None when nothing could be
    measured -- the caller falls back to the cost-model ranking.
    """
    if ctx is None:
        from .costctx import CostContext

        ctx = CostContext(graph, hw)
    candidates = [list(c) for c in candidates]
    if not candidates or not candidates[0]:
        return None

    region: frozenset[int] = frozenset()
    for groups in candidates:
        for grp in groups:
            region |= grp.members
    b = ctx.bounds(region)
    ext_ids = [i for i in b.inputs
               if graph.node(i).kind is not OpKind.CONST]
    out_ids = list(b.outputs)

    emit_cache: dict = {}
    branches: list[_Branch] = []
    for ci, groups in enumerate(candidates):
        branches.extend(_candidate_branches(
            graph, ci, groups, region, ext_ids, out_ids, ctx, hw,
            emit_cache))
    if not branches:
        return None
    if len(branches) > MAX_PARTITION_BRANCHES:
        # keep every all-analytic assignment, then swaps in order
        # (logged via note_cap: no silent caps)
        ctx.note_cap("partition_branches",
                     len(branches) - MAX_PARTITION_BRANCHES)
        base = [br for br in branches if not br.assignment]
        swaps = [br for br in branches if br.assignment]
        branches = (base + swaps)[:MAX_PARTITION_BRANCHES]

    # -- fault containment ---------------------------------------------------
    # ``race_crash``: one branch's runner is replaced with a raiser; the
    # measurement layer must disqualify it (batch poisoning falls back
    # to the serial loop; the serial loop times the survivors) and the
    # race commits a winner from the healthy branches.
    crash = _faults.fire("race_crash")
    if crash is not None:
        try:
            idx = int(crash.params.get("branch", 0)) % len(branches)
        except (TypeError, ValueError):
            idx = 0

        def _crashed_runner(*_a):
            raise RuntimeError("injected race_crash branch failure")

        # unique mkey/tkey: the crashed branch must be its own
        # measurement representative, never shared with healthy
        # isomorphic siblings.
        branches[idx] = _Branch(branches[idx].ci, branches[idx].assignment,
                                _crashed_runner, ("injected_crash", idx),
                                ("injected_crash", idx))

    rng = np.random.default_rng(0)
    args = _dummy_inputs(graph, ext_ids, rng)

    def _measured():
        # ``tuner_hang``: a wedged measurement, contained by the watchdog
        hang = _faults.fire("tuner_hang")
        if hang is not None:
            watchdog_sleep(hang.sleep_s())
        if watchdog_cancelled():
            # the caller already timed out and moved on: do NOT start
            # device work from an abandoned thread (it would race live
            # traffic -- and interpreter shutdown).
            return None
        return _measure_partition_branches(branches, args,
                                           batch_compile=batch_compile)

    try:
        times = with_watchdog(_measured, race_timeout_s(),
                              label="partition race")
    except RaceTimeoutError:
        # a wedged race disqualifies itself: the caller serves the
        # model ranking; the timeout is recorded, never silent.
        ctx.note_cap("race_timeout", 1)
        return None
    if times is None:
        return None

    measured_s = [float("inf")] * len(candidates)
    best_k = -1
    for k, t in enumerate(times):
        if t is None:
            continue
        ci = branches[k].ci
        if t < measured_s[ci]:
            measured_s[ci] = t
        if best_k < 0 or t < times[best_k]:
            best_k = k
    if best_k < 0:
        return None
    win = branches[best_k]
    overrides = [dict(win.assignment.get(gi, {}))
                 for gi in range(len(candidates[win.ci]))]
    return PartitionTuneResult(index=win.ci, overrides=overrides,
                               measured_s=measured_s,
                               branches=len(branches))


def _measure_partition_branches(branches: list[_Branch], args, *,
                                batch_compile: bool
                                ) -> list[float | None] | None:
    """Per-branch best wall time (None: branch failed to measure).
    Isomorphic branches (equal ``mkey``) share one measurement."""
    rep_by_mkey: dict[tuple, int] = {}
    for k, br in enumerate(branches):
        rep_by_mkey.setdefault(br.mkey, k)
    rep_of = {k: rep_by_mkey[br.mkey] for k, br in enumerate(branches)}

    if batch_compile:
        times = _measure_switch_branches([br.runner for br in branches],
                                         args, [br.tkey for br in branches],
                                         rep_of=rep_of)
        if times is not None:
            return times
        # a poisoned batch falls through to the serial loop

    timed: dict[int, float | None] = {}
    for k in set(rep_of.values()):
        br = branches[k]
        try:
            timed[k] = _time_callable(br.runner, args, key=br.tkey)
        except Exception:  # noqa: BLE001
            timed[k] = None
    return [timed.get(rep_of[k]) for k in range(len(branches))]
