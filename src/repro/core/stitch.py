"""``stitched_jit`` -- the FusionStitching public API.

Usage::

    fused = stitched_jit(layer_norm)        # trace -> explore -> plan -> emit
    y = fused(x, gamma, beta)               # one jitted dispatch per call

The wrapper is a pure JAX-traceable function, so it composes with jit /
grad / vmap / pjit: stitched kernels appear as pallas_call ops inside a
larger program, exactly like the paper's fusions live inside an XLA
module.  Plans are cached per argument tree and leaf shapes/dtypes in-process
and, when ``$REPRO_PLAN_CACHE`` (or ``plan_cache=``) points at a
directory, persistently across processes (the paper's
tune-once-run-many model; dynamic shapes share its §7.5 limitation).

Pipeline: trace -> plan (``make_plan``: patterns bounded by the
explorer guardrail) -> **stitch** (``stitcher.search_groups``: adjacent
row-compatible patterns and sandwiched singletons merge into stitch
groups, priced by the latency evaluator; the top-k distinct candidate
partitions are retained and, with ``autotune=True`` on an accelerator,
*raced on silicon* by ``autotune.tune_partitions`` -- the committed
partition is the measured winner, not just the cost-model pick) ->
emit (ONE ``pallas_call`` per group, inter-pattern values staged in
VMEM -- the paper's §4 megakernel).  Structurally isomorphic groups
(repeated transformer layers) are emitted once and rebound per
instance.

Dispatch: the whole fusion schedule -- stitched group kernels, packed
subgraphs and leftover singleton ops -- is composed into **one**
``jax.jit``-compiled callable, so a stitched call costs a single Python
dispatch instead of one Python round-trip per schedule item (the
per-kernel context-switch overhead the paper eliminates, §2.2).  The
seed's per-item interpreter survives as ``dispatch="interpret"``: the
equivalence oracle for tests and a debugging aid.  With ``donate=True``
input buffers with no reader after the schedule are donated to XLA,
cutting HBM pressure at decode batch sizes.
"""
from __future__ import annotations

import collections
import contextlib
import functools
import hashlib
import heapq
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.runtime import spans
from repro.runtime.canary import CanaryController
from repro.runtime.guard import EmitError, GuardError, PoisonList, \
    RUNG_ANCHORED, RUNG_BASELINE, RUNG_PATTERNS, RUNG_STITCHED, RUNGS, \
    VerifyMismatchError, VerifyPolicy, outputs_mismatch
from repro.testing import faults as _faults

from .codegen import Emitted, emit_group, emit_pattern
from .costctx import CostContext
from .cost_model import Hardware, KernelEstimate, hardware
from .ir import FUSIBLE_KINDS, FusionPlan, Graph, OpKind, StitchGroup
from .plan_cache import PlanCache, entry_partition_source, \
    entry_to_groups, entry_to_plan, graph_signature, override_fp, \
    plan_to_entry
from .planner import PlanStats, make_plan, plan_stats
from .stitcher import break_cycles, search_groups
from .tracer import bind_node, trace, trace_with_shape


#: ``str(jnp.result_type(dtype))`` by ``(dtype, jax_enable_x64)``: the
#: name canonicalises (a float64 leaf keys as float32 with x64 off) and
#: costs microseconds to compute.  None marks an extended dtype (a PRNG
#: key), which ``jnp.result_type`` names only from a value.
_DTYPE_KEYS: dict[tuple, str | None] = {}


def _dtype_key(dtype, x64: bool) -> str | None:
    try:
        return _DTYPE_KEYS[dtype, x64]
    except KeyError:
        pass
    try:
        name = str(jnp.result_type(dtype))
    except TypeError:
        name = None
    _DTYPE_KEYS[dtype, x64] = name
    return name


@dataclass
class StitchReport:
    """Everything the benchmarks want to know about one stitched function."""
    stats: PlanStats
    n_pallas: int
    n_packed: int
    scratch_bytes: int
    scratch_naive_bytes: int
    plan_time_s: float
    patterns: list[frozenset] = field(default_factory=list)
    # -- plan phases (parts of plan_time_s, each under a span) ---------------
    trace_s: float = 0.0             # jaxpr trace (``stitch.trace``)
    search_s: float = 0.0            # plan-cache load or explore + stitch,
    #                                  and group tuning (``stitch.search``)
    emit_s: float = 0.0              # codegen (``stitch.emit``)
    plan_cache_hit: bool = False
    autotuned: bool = False
    signature: str = ""
    dispatch: str = "single"
    # -- stitch groups (paper §4 megakernels) --------------------------------
    groups: list = field(default_factory=list)  # per group: tuple of parts
    n_groups: int = 0                # macro-kernels emitted from patterns
    n_stitched: int = 0              # groups fusing >1 part
    n_anchored: int = 0              # groups folded into a compute anchor
    stitched_hbm_bytes_saved: int = 0  # inter-pattern HBM traffic removed
    emission_reused: int = 0         # isomorphic groups rebound, not re-emitted
    # -- beam-search partition + measured group tuning -----------------------
    beam_width: int = 0              # partition search width (0: search skipped)
    beam_states_explored: int = 0    # states priced by the partition search
    group_tuned: int = 0             # groups with a *measured* schedule
    group_tuned_wins: int = 0        # ...where measurement beat the analytic pick
    # -- measured top-k partition tuning -------------------------------------
    partition_source: str = "model"  # how the committed partition was chosen
    partition_candidates: int = 0    # distinct top-k partitions considered
    partition_index: int = 0         # winner's rank in the model ordering
    #                                  (> 0: silicon disagreed with the model)
    # -- stage-vs-recompute stitching scheme (paper §4 thread composition) ---
    n_recomputed: int = 0            # values inlined per consumer, not staged
    recompute_bytes_freed: int = 0   # VMEM scratch bytes those flips elide
    # -- no silent caps + cache observability --------------------------------
    caps_hit: dict = field(default_factory=dict)  # guardrail -> truncations
    #: OPAQUE primitive -> nodes of it the planner met (each is a hard
    #: group boundary: ``{"top_k": 10}`` splits a routing chain per layer)
    opaque_prims: dict = field(default_factory=dict)
    plan_cache_hits: int = 0         # this cache instance's load hits
    plan_cache_misses: int = 0       # ...and misses (absent/corrupt entries)
    # -- SPMD-aware stitching (one plan replayed per shard) ------------------
    sharded: bool = False            # a ShardCtx was active for this compile
    mesh_axes: tuple = ()            # ((axis, size), ...) of the mesh
    n_collective: int = 0            # collective nodes in the (local) graph
    collective_boundaries: int = 0   # segment splits forced by a collective
    # -- fail-safe compilation (fallback ladder + shadow verification) -------
    fallbacks: list = field(default_factory=list)
    #                                  (group_id, rung, reason) per
    #                                  degradation; group_id -1 = whole
    #                                  dispatch (exec failure / verify
    #                                  mismatch / poisoned signature)
    rung: str = RUNG_STITCHED        # coarsest active dispatch rung
    verified: int = 0                # executions shadow-verified vs XLA
    verify_failures: int = 0         # ...that mismatched (-> quarantine)
    quarantined: bool = False        # plan evicted + signature poisoned


class _Phases:
    """Seconds of one build's phases (``trace``, ``search``, ``emit``);
    ``with phases("trace"):`` times a block into its phase inside the
    span ``stitch.trace``."""

    def __init__(self):
        self.s = {"trace": 0.0, "search": 0.0, "emit": 0.0}

    @contextlib.contextmanager
    def __call__(self, phase: str):
        t = time.perf_counter()
        try:
            with spans.span(f"stitch.{phase}"):
                yield
        finally:
            self.s[phase] += time.perf_counter() - t


class _Compiled:
    """One traced+planned+emitted instance for a fixed shape signature.

    ``dispatch="single"``: the schedule executor is wrapped in one
    ``jax.jit``, so it runs in Python once (at trace time) and every
    later call is a single compiled dispatch.  ``exec_count`` counts
    Python-level executions of the schedule body -- tests use it to
    prove single-dispatch behavior.  ``donate_argnums`` lists the flat
    input positions donated to XLA (inputs no schedule item reads after
    the call returns, i.e. every input that is not itself an output).
    An explicit ``donate_argnums`` restricts donation to those flat
    positions (serving donates the KV/SSM cache but never the params);
    positions naming an input that is also an output are dropped.

    Fail-safe execution (the guard layer): every instance carries a
    lazily-jitted *baseline* -- the plain per-node XLA replay of the
    traced graph, no pallas, no donation.  ``REPRO_VERIFY`` shadow-runs
    it against the stitched dispatch; a mismatch (or a dispatch that
    raises) quarantines the instance: it pins itself to the baseline,
    records the degradation on its report and invokes ``on_quarantine``
    so the owner can evict + poison the plan-cache entry.  The call
    still returns a correct result -- degradation is recorded, never
    silent, and never an exception on the serving path.

    The compiled program is named ``name`` (its HLO module is
    ``jit_<name>``), and each stitch group's kernels and ops run under
    the name scope ``g<group index>``.
    """

    def __init__(self, graph: Graph, plan: FusionPlan,
                 emitted: list[Emitted], schedule: list[tuple[str, Any]],
                 report: StitchReport, out_tree, dispatch: str = "single",
                 name: str = "stitched",
                 donate: bool = False,
                 donate_argnums: tuple[int, ...] | None = None,
                 verify_policy: VerifyPolicy | None = None,
                 on_quarantine: Callable | None = None,
                 shard=None, canary=None,
                 on_readmit: Callable | None = None):
        self.graph = graph
        self.plan = plan
        self.emitted = emitted
        self.schedule = schedule  # [("pattern", Emitted) | ("node", nid)]
        self.report = report
        self.out_tree = out_tree
        self.dispatch = dispatch
        #: explicit ShardCtx: the schedule body is the *per-shard*
        #: program (traced on local shapes), so both the stitched
        #: dispatch and the XLA baseline wrap in ``shard_map`` -- one
        #: compiled plan replays on every shard, and the guard ladder /
        #: shadow verification compare global-view outputs per-shard.
        self.shard = shard if shard is not None and shard.explicit else None
        self.exec_count = 0
        self.call_count = 0           # __call__ invocations (verify sampling)
        self.verify_policy = verify_policy or VerifyPolicy("off")
        self.on_quarantine = on_quarantine
        #: production canary loop: when a ``CanaryController`` is
        #: attached, it governs dispatch per call (sampled shadow
        #: verification, quarantine/probation routing) and ``__call__``
        #: defers to it; ``on_readmit`` lets the owner lift the poison
        #: pin and re-persist the plan when probation passes.
        self.canary = canary
        self.on_readmit = on_readmit
        self._canary_prev_rung = None  # rung to restore on re-admission
        self._use_baseline = False    # quarantined / poisoned: baseline rung
        self._baseline_fn = None      # lazily jitted XLA reference
        self._race_ctx: "_RaceContext | None" = None
        self.donate_argnums: tuple[int, ...] = ()
        if dispatch == "single" and (donate or donate_argnums is not None):
            outset = set(graph.outputs)
            if donate_argnums is not None:
                self.donate_argnums = tuple(
                    i for i in donate_argnums
                    if 0 <= i < len(graph.inputs)
                    and graph.inputs[i] not in outset)
            else:
                self.donate_argnums = tuple(
                    i for i, nid in enumerate(graph.inputs)
                    if nid not in outset)
        body = (self.shard.wrap(self._run_schedule)
                if self.shard is not None else self._run_schedule)

        def program(*flat_args):
            return body(*flat_args)
        program.__name__ = program.__qualname__ = name
        self._jitted = jax.jit(program, donate_argnums=self.donate_argnums)

    def _run_schedule(self, *flat_args):
        """Execute the fusion schedule (traceable; jitted for dispatch)."""
        self.exec_count += 1
        graph = self.graph
        env: dict[int, Any] = dict(zip(graph.inputs, flat_args))
        for kind, item in self.schedule:
            if kind == "node":
                node = graph.node(item)
                if node.kind is OpKind.CONST:
                    env[item] = node.value
                    continue
                ins = [env[i] if i in env else graph.node(i).value
                       for i in node.inputs]
                env[item] = bind_node(node, ins)
            else:
                em: Emitted = item
                with jax.named_scope(f"g{em.group}"):
                    outs = em.fn(*[env[i] for i in em.ext_ids])
                for oid, val in zip(em.out_ids, outs):
                    env[oid] = val
        return tuple(env[o] for o in graph.outputs)

    def _run_baseline(self, *flat_args):
        """Plain XLA replay of the traced graph: no pallas kernels, no
        donation.  The ladder's last rung and the shadow-verification
        reference."""
        graph = self.graph
        env: dict[int, Any] = dict(zip(graph.inputs, flat_args))
        for nid in graph.topo_order():
            if nid in env:
                continue
            node = graph.node(nid)
            if node.kind is OpKind.CONST:
                env[nid] = node.value
                continue
            ins = [env[i] if i in env else graph.node(i).value
                   for i in node.inputs]
            env[nid] = bind_node(node, ins)
        return tuple(env[o] for o in graph.outputs)

    @property
    def _baseline(self):
        if self._baseline_fn is None:
            body = (self.shard.wrap(self._run_baseline)
                    if self.shard is not None else self._run_baseline)
            self._baseline_fn = jax.jit(body)
        return self._baseline_fn

    def _quarantine(self, reason: str) -> None:
        """Pin this instance to the baseline rung and tell the owner to
        evict + poison the persisted plan.  Never raises: quarantine is
        containment, not a second failure mode."""
        self._use_baseline = True
        self.report.quarantined = True
        self.report.rung = RUNG_BASELINE
        self.report.fallbacks.append((-1, RUNG_BASELINE, reason))
        if self.on_quarantine is not None:
            try:
                self.on_quarantine(reason)
            except Exception:  # noqa: BLE001 - eviction failure must not
                pass           # take down the already-degraded dispatch

    def pin_baseline(self, reason: str) -> None:
        """Pre-pin to the baseline rung (signature poisoned by an
        earlier quarantine): the stitched dispatch is never attempted."""
        self._use_baseline = True
        self.report.rung = RUNG_BASELINE
        self.report.fallbacks.append((-1, RUNG_BASELINE, reason))

    def __call__(self, flat_args):
        if self.dispatch != "single":
            flat_out = self._run_schedule(*flat_args)
            return jax.tree_util.tree_unflatten(self.out_tree,
                                                list(flat_out))
        if self._use_baseline:
            with spans.span("stitch.launch"):
                flat_out = self._baseline(*flat_args)
            return jax.tree_util.tree_unflatten(self.out_tree,
                                                list(flat_out))
        if self.canary is not None:
            with spans.span("stitch.guard"):
                flat_out = self.canary.guarded_call(self, flat_args)
            return jax.tree_util.tree_unflatten(self.out_tree,
                                                list(flat_out))
        policy = self.verify_policy
        verify = policy.enabled and policy.should_verify(self.call_count)
        self.call_count += 1
        ref = None
        if verify:
            # the stitched call may donate its inputs: the reference
            # must consume them first.
            with spans.span("stitch.guard"):
                ref = self._baseline(*flat_args)
        try:
            with spans.span("stitch.launch"):
                flat_out = self._jitted(*flat_args)
        except Exception as e:  # noqa: BLE001 - contained: baseline rung
            self._quarantine(f"dispatch failed: {type(e).__name__}: {e}")
            if ref is None:
                try:
                    ref = self._baseline(*flat_args)
                except Exception as e2:  # noqa: BLE001
                    raise GuardError(
                        "stitched dispatch failed and the baseline replay "
                        f"could not run (inputs donated?): {e2}") from e
            return jax.tree_util.tree_unflatten(self.out_tree, list(ref))
        if ref is not None:
            with spans.span("stitch.guard"):
                self.report.verified += 1
                reason = outputs_mismatch(
                    ref, flat_out, anchored=self.report.n_anchored > 0)
                if _faults.fire("numeric_mismatch") is not None:
                    reason = reason or "injected numeric_mismatch"
                if reason is not None:
                    self.report.verify_failures += 1
                    self._quarantine(
                        f"shadow verification mismatch: {reason}")
                    flat_out = ref  # serve the reference, not the mismatch
        return jax.tree_util.tree_unflatten(self.out_tree, list(flat_out))


def _build_schedule(graph: Graph, emitted: list[Emitted]) -> list[tuple[str, Any]]:
    """Topologically order macro-nodes (groups + leftover singletons).

    A group runs once every value it reads from outside exists, so it
    can land after a bare node with a smaller id; that node's consumers
    must wait for it in turn.  Kahn's algorithm over the macro-node DAG
    (acyclic because groups are convex) orders both, earliest original
    position first among the ready ones.
    """
    member_of: dict[int, int] = {}
    for idx, em in enumerate(emitted):
        for nid in em._members:  # type: ignore[attr-defined]
            member_of[nid] = idx
    inputs = set(graph.inputs)

    def macro(nid: int) -> tuple:
        idx = member_of.get(nid)
        return ("pattern", idx) if idx is not None else ("node", nid)

    first: dict[tuple, int] = {}
    deps: dict[tuple, set] = {}
    users: dict[tuple, list] = {}
    for nid in graph.topo_order():
        if nid in inputs:
            continue
        m = macro(nid)
        first.setdefault(m, nid)
        mdeps = deps.setdefault(m, set())
        for i in graph.node(nid).inputs:
            mi = macro(i)
            if i not in inputs and mi != m and mi not in mdeps:
                mdeps.add(mi)
                users.setdefault(mi, []).append(m)
    waiting = {m: len(d) for m, d in deps.items()}
    ready = [(first[m], m) for m, n in waiting.items() if n == 0]
    heapq.heapify(ready)
    schedule: list[tuple[str, Any]] = []
    while ready:
        _, m = heapq.heappop(ready)
        kind, ref = m
        schedule.append((kind, emitted[ref] if kind == "pattern" else ref))
        for u in users.get(m, ()):
            waiting[u] -= 1
            if waiting[u] == 0:
                heapq.heappush(ready, (first[u], u))
    if len(schedule) != len(first):  # unreachable for convex plans
        raise GuardError("fusion schedule has a dependence cycle")
    return schedule


# ---------------------------------------------------------------------------
# isomorphic-emission dedup (CostContext.struct_key)
# ---------------------------------------------------------------------------
def _ext_seen_order(graph: Graph, union: frozenset[int],
                    wanted: set[int]) -> list[int]:
    """External inputs in first-reference order over the sorted members.

    This order is *structural*: two unions with equal ``struct_key``
    reference their externals in corresponding positions, which is what
    lets one emitted kernel be rebound to another instance whose
    id-sorted external order differs.
    """
    order: list[int] = []
    seen: set[int] = set()
    for nid in sorted(union):
        for i in graph.node(nid).inputs:
            if i in wanted and i not in seen:
                seen.add(i)
                order.append(i)
    return order


#: Consts above this element count are fingerprinted by identity (node
#: id) instead of content: hashing a captured weight table per group per
#: compile would dwarf the emission work the dedup saves.  Identity is
#: conservative -- the *same* shared const node still dedups, distinct
#: but equal-valued large consts merely refuse reuse.
_CONST_HASH_MAX_ELEMS = 65536


def _hash_const(h, nid: int, value) -> None:
    v = np.asarray(value)
    h.update(repr((v.shape, str(v.dtype))).encode())
    if v.size <= _CONST_HASH_MAX_ELEMS:
        h.update(v.tobytes())
    else:
        h.update(repr(("by-identity", nid)).encode())


def _emit_signature(graph: Graph, ctx: CostContext, union: frozenset[int],
                    override: dict | None, anchors: tuple = ()) -> tuple:
    """Dedup key for emission: structural isomorphism + everything the
    emitted closure bakes in beyond the struct key (primitive params,
    constant *values* -- member and external -- the schedule pin and the
    anchors, positionally within the sorted members so isomorphic
    anchored layers still dedup)."""
    h = hashlib.sha1()
    params_fp = []
    for nid in sorted(union):
        n = graph.node(nid)
        params_fp.append(tuple(sorted(
            (k, repr(v)) for k, v in n.params.items()
            if not k.startswith("_"))))
        if n.kind is OpKind.CONST and n.value is not None:
            _hash_const(h, nid, n.value)
    seen: set[int] = set()
    for nid in sorted(union):
        for i in graph.node(nid).inputs:
            if i in union or i in seen:
                continue
            seen.add(i)
            cn = graph.node(i)
            if cn.kind is OpKind.CONST and cn.value is not None:
                _hash_const(h, i, cn.value)
    smem = sorted(union)
    apos = tuple(smem.index(a) for a in anchors)
    return (ctx.struct_key(union), tuple(params_fp), h.hexdigest(),
            override_fp(override), apos)


def _rebind_emitted(graph: Graph, ctx: CostContext, union: frozenset[int],
                    parts: tuple, template: Emitted,
                    template_seen: list[int], *,
                    group: int) -> Emitted | None:
    """Reuse a structurally identical compiled kernel for ``union``, run
    as the stitch group ``group``.  The kernel keeps the template's name:
    one emitted kernel, one name, so isomorphic groups (repeated layers)
    still lower once.

    The template callable takes its externals in *its* id-sorted order;
    this instance's id-sorted order can differ, so arguments are routed
    through the shared first-seen correspondence.  Outputs are pattern
    members in sorted order on both sides, hence positional.  Any shape
    mismatch (defensive: struct keys should preclude it) refuses reuse.
    """
    b = ctx.bounds(union)
    ext_ids = [i for i in b.inputs
               if graph.node(i).kind is not OpKind.CONST]
    out_ids = list(b.outputs)
    seen = _ext_seen_order(graph, union, set(ext_ids))
    if (len(seen) != len(template_seen)
            or len(ext_ids) != len(template.ext_ids)
            or len(out_ids) != len(template.out_ids)):
        return None
    t_slot = {e: s for s, e in enumerate(template_seen)}
    pos = {e: j for j, e in enumerate(ext_ids)}
    try:
        mapping = tuple(pos[seen[t_slot[e]]] for e in template.ext_ids)
    except (KeyError, IndexError):
        return None

    def rebound(*vals, _fn=template.fn, _m=mapping):
        return _fn(*(vals[i] for i in _m))

    return Emitted(rebound, template.kind, template.estimate, ext_ids,
                   out_ids, template.scratch_bytes,
                   template.scratch_naive_bytes, parts=parts,
                   hbm_saved=template.hbm_saved,
                   staged_slots=template.staged_slots,
                   n_recomputed=template.n_recomputed,
                   recompute_bytes_freed=template.recompute_bytes_freed,
                   name=template.name, group=group)


def _remap_override(over: dict, src_members: list[int],
                    dst_members: list[int]) -> dict:
    """Retarget a struct-shared schedule override to an isomorphic
    sibling.  Node-id-specific fields (the ``recompute`` flip set) map
    through the positional correspondence of the sorted member lists --
    equal ``struct_key``s imply equal id-offset sequences, so sorted
    members correspond index-by-index.  A broken correspondence drops
    the field (degrade to re-deciding at emission), never a foreign-id
    pin that would silently fall back yet persist as tuned."""
    out = dict(over)
    rec = out.get("recompute")
    if rec:
        pos = {nid: i for i, nid in enumerate(src_members)}
        try:
            out["recompute"] = sorted(dst_members[pos[int(r)]] for r in rec)
        except (KeyError, IndexError, ValueError):
            out.pop("recompute", None)
    return out


def _sched_of(est: KernelEstimate) -> dict:
    """Persistable schedule pin of an estimate (incl. streaming tile and
    the stage-vs-recompute flip set)."""
    d: dict = {"schedule": est.schedule}
    if est.block_rows > 0:
        d["block_rows"] = est.block_rows
    if est.schedule == "streaming" and est.block_cols > 0:
        d["block_cols"] = est.block_cols
    if est.schedule == "onepass" and est.recompute_ids:
        d["recompute"] = sorted(est.recompute_ids)
    return d


@dataclass
class _RaceContext:
    """Everything a deferred partition race needs to re-finalize a
    compiled instance in a background thread: the traced graph, the
    plan, the ranked candidate partitions and any schedule pins loaded
    from the plan cache.  Held on the served ``_Compiled`` until
    ``StitchedFunction.rerace`` consumes it."""
    graph: Graph
    ctx: CostContext
    sig: str
    plan: FusionPlan
    overrides: list          # per-pattern schedule overrides
    candidates: list         # ranked PartitionCandidates (model order)
    groups: list             # the served (model-ranked) partition
    loaded_over_by_parts: dict
    stitch_stats: Any
    out_tree: Any
    shard: Any = None        # ambient ShardCtx (explicit builds never race)


class StitchedFunction:
    def __init__(self, fn: Callable, *, hw: Hardware | None = None,
                 use_remote_fusion: bool = True,
                 dispatch: str = "single", plan_cache: str | None = None,
                 autotune: bool = False, stitch_groups: bool = True,
                 donate: bool = False,
                 donate_argnums: tuple[int, ...] | None = None,
                 background: Any = None,
                 mesh: Any = None, in_specs: Any = None,
                 out_specs: Any = None, canary: Any = None):
        if dispatch not in ("single", "interpret"):
            raise ValueError(
                f"dispatch must be 'single' or 'interpret', got {dispatch!r}")
        from .shard import ShardCtx

        if in_specs is not None or out_specs is not None:
            if mesh is None:
                raise ValueError("in_specs/out_specs require a mesh")
            if in_specs is None or out_specs is None:
                raise ValueError(
                    "explicit sharding needs BOTH in_specs and out_specs")
            if dispatch != "single":
                raise ValueError(
                    "dispatch='interpret' cannot run inside shard_map; "
                    "use dispatch='single' with a mesh")
        #: explicit: fn is the *per-shard* (shard_map-style) body, planned
        #: on local shapes and dispatched through shard_map.  Mesh-only:
        #: signature/cache keying (the GSPMD global-view serving path).
        self._shard = (ShardCtx.build(mesh, in_specs, out_specs)
                       if mesh is not None else None)
        self._fn = fn
        #: the compiled programs' name (``jit_<program>`` in a profile)
        self.program = f"stitched_{getattr(fn, '__name__', 'fn')}"
        #: the planning target: the default backend's chip unless a
        #: caller models another one (tests shrink VMEM this way)
        self._hw = hw if hw is not None else hardware()
        self._remote = use_remote_fusion
        self._dispatch = dispatch
        self._autotune = autotune
        self._stitch_groups = stitch_groups
        self._donate = donate
        self._donate_argnums = (tuple(donate_argnums)
                                if donate_argnums is not None else None)
        #: executor with ``submit(callable)`` (serving's BackgroundTuner).
        #: When set, a cold compile never blocks on measurement: the
        #: analytic plan is served immediately and the top-k partition
        #: race + group tile sweeps run via ``rerace`` on the executor,
        #: whose winner is hot-swapped into ``_cache`` under a lock.
        self._background = background
        self._plan_cache = (PlanCache(plan_cache) if plan_cache
                            else PlanCache.from_env())
        #: quarantine pins shared with the persistent cache (or process
        #: local when no cache dir is configured): a signature whose
        #: stitched dispatch ever failed verification stays on the
        #: baseline rung until the pin is lifted.
        self._poison = (self._plan_cache.poison
                        if self._plan_cache is not None else PoisonList())
        #: production canary loop: pass a ``CanaryController`` to share
        #: one (and its overhead budget) across dispatch paths, or let
        #: ``$REPRO_CANARY`` auto-create one rooted beside the plan
        #: cache; ``canary=False`` suppresses even the env auto-create
        #: (differentiable backward).  Off = dispatch byte-identical to
        #: the pre-canary path.
        self._canary = (None if canary is False
                        else canary if canary is not None
                        else CanaryController.from_env(self._plan_cache))
        self._cache: dict[tuple, _Compiled] = {}
        #: leaves keyed by the slow path of ``_signature`` so far
        self.key_fallback_leaves = 0
        self._compile_lock = threading.Lock()
        self._swap_lock = threading.Lock()

    def _shard_ctx(self):
        """The active shard context for the next compile: the explicit
        one this function was constructed with, else the ambient
        ``use_mesh`` context (signature-keying only)."""
        from .shard import ShardCtx

        if self._shard is not None:
            return self._shard
        return ShardCtx.ambient()

    def _signature(self, flat_args, in_tree) -> tuple:
        """The dispatch-table key: the argument tree, each leaf's shape
        and ``jnp.result_type`` name, and the mesh.  A strongly typed
        array leaf reads its shape and dtype as attributes and its name
        from ``_DTYPE_KEYS``; any other leaf (a Python scalar, a weakly
        typed array, a PRNG key) is keyed by ``np.shape`` and
        ``jnp.result_type`` on the value and counted in
        ``key_fallback_leaves``."""
        x64 = jax.config.jax_enable_x64
        key = [in_tree]
        for a in flat_args:
            dtype = getattr(a, "dtype", None)
            name = (None if dtype is None or getattr(a, "weak_type", False)
                    else _dtype_key(dtype, x64))
            if name is None:
                self.key_fallback_leaves += 1
                key.append((tuple(np.shape(a)), str(jnp.result_type(a))))
            else:
                key.append((a.shape, name))
        # the ambient mesh can change between calls (serving enters /
        # leaves ``use_mesh``): a sharded compile must never be served
        # to an unsharded call, so the mesh keys the dispatch table too.
        shard = self._shard_ctx()
        if shard is not None:
            key.append(shard.mesh_key())
        return tuple(key)

    def _load_cached_plan(self, graph: Graph, sig: str
                          ) -> tuple[FusionPlan, list[dict], dict] | None:
        if self._plan_cache is None:
            return None
        entry = self._plan_cache.load(sig)
        if entry is None:
            return None
        decoded = entry_to_plan(entry, graph)
        if decoded is None:
            return None
        plan, overrides = decoded
        return plan, overrides, entry

    def _compile(self, args, kwargs) -> tuple[_Compiled, Any]:
        flat, in_tree = jax.tree_util.tree_flatten((args, kwargs))
        key = self._signature(flat, in_tree)
        compiled = self._cache.get(key)
        if compiled is not None:
            return compiled, flat
        submit = False
        with self._compile_lock:
            compiled = self._cache.get(key)
            if compiled is None:
                with spans.span("stitch.build", program=self.program):
                    compiled = self._build(flat, in_tree)
                self._cache[key] = compiled
                submit = (compiled._race_ctx is not None
                          and self._background is not None)
        if submit:  # outside the lock: a synchronous executor must not
            #         re-enter _compile under _compile_lock
            job = functools.partial(self.rerace, key)
            try:
                # keyed submission lets the tuner's circuit breaker skip
                # a signature whose race keeps crashing
                self._background.submit(job, key=key)
            except TypeError:  # executor protocol: plain submit(job)
                self._background.submit(job)
        return compiled, flat

    def _build(self, flat, in_tree) -> _Compiled:
        t0 = time.perf_counter()
        phases = _Phases()

        def flat_fn(*fargs):
            a, k = jax.tree_util.tree_unflatten(in_tree, fargs)
            return self._fn(*a, **k)

        shard = self._shard_ctx()
        explicit_shard = shard is not None and shard.explicit
        with phases("trace"):
            if explicit_shard:
                # the per-shard program IS the plan's subject: trace on
                # local shapes with the mesh axes bound, so collectives
                # become COLLECTIVE nodes and every row count / VMEM / HBM
                # figure downstream is per-shard with no cost-formula
                # changes.  The output tree comes from the same trace:
                # eval_shape on the *global* args would re-trace the
                # per-shard body without its axis_env and fail on the
                # first collective.
                graph, out_tree, _ = trace_with_shape(
                    flat_fn, *shard.local_args(flat),
                    axis_env=shard.axis_env())
            else:
                graph = trace(flat_fn, *flat)
                # the output tree (also needed by a deferred race rebuild)
                out_shape = jax.eval_shape(flat_fn, *flat)
                _, out_tree = jax.tree_util.tree_flatten(out_shape)

        with phases("search"):
            ctx = CostContext(graph, self._hw, shard=shard)
            sig = graph_signature(graph, self._hw,
                                  remote_fusion=self._remote, shard=shard)
            # persistent cache: an identical graph signature in any process
            # reuses the stored patterns + group composition + tuned
            # schedules and skips exploration *and* stitching entirely.
            overrides: list[dict] = []
            entry: dict | None = None
            cached = self._load_cached_plan(graph, sig)
            autotuned = False
            if cached is not None:
                plan, overrides, entry = cached
            else:
                plan = make_plan(graph, self._hw,
                                 use_remote_fusion=self._remote, ctx=ctx)
                if self._autotune:
                    from .autotune import autotune_available, tune_pattern

                    if autotune_available():
                        # isomorphic patterns (repeated layers) share one
                        # measured sweep: timing depends on structure +
                        # shapes, not on which instance runs it.  Shared
                        # pins are remapped to each sibling's node ids
                        # (the recompute flip set is id-specific).
                        tuned_by_struct: dict[tuple, tuple] = {}
                        for pat in plan.patterns:
                            skey = ctx.struct_key(pat.members)
                            members = sorted(pat.members)
                            hit = tuned_by_struct.get(skey)
                            if hit is None:
                                over = tune_pattern(graph, pat.members,
                                                    hw=self._hw,
                                                    ctx=ctx) or {}
                                tuned_by_struct[skey] = (over, members)
                            else:
                                over = _remap_override(hit[0], hit[1], members)
                            overrides.append(over)
                        autotuned = True
                if not overrides:
                    overrides = [{} for _ in plan.patterns]

            # ---- stitch groups: compose patterns into megakernels ---------
            # The partition search ranks the top-k distinct candidate
            # partitions by modeled gain; with an accelerator available the
            # candidates are *raced on silicon* (``tune_partitions``) and
            # the measured winner is committed -- the paper's
            # model-validated-by-measurement tuning of the stitching scheme.
            # A cached entry whose partition was already measured is
            # trusted; a model-sourced one is re-raced when this process
            # can measure.
            groups: list[StitchGroup]
            group_overrides: list[dict]
            groups_from_cache = False
            stitch_stats = None
            race_ctx: _RaceContext | None = None
            partition_source = "model"
            partition_index = 0
            partition_candidates = 0
            if self._stitch_groups:
                from .autotune import autotune_available

                # explicit-shard compiles neither race nor measure: the
                # in-process tuner runs unsharded branches that would price
                # a different (global-shape) program.  Sharded racing is a
                # follow-on; the analytic sharded cost model decides.
                defer = self._background is not None and not explicit_shard
                can_tune = ((self._autotune or defer) and not explicit_shard
                            and autotune_available())
                loaded = (entry_to_groups(entry, plan, graph)
                          if entry is not None else None)
                cached_source = (entry_partition_source(entry)
                                 if entry is not None else "model")
                if loaded is not None and (cached_source == "measured"
                                           or not can_tune):
                    # trust the cached composition: its partition was raced
                    # already, or this process cannot measure anyway.
                    groups, group_overrides = loaded
                    groups_from_cache = True
                    partition_source = cached_source
                else:
                    # a model-sourced entry loaded by a process that can
                    # tune re-races the *partition*, but its group schedule
                    # pins (earlier measurements, keyed by composition) are
                    # reused for any winner group with the same parts
                    # instead of being re-swept from scratch.
                    loaded_over_by_parts: dict[tuple, dict] = {}
                    if loaded is not None:
                        for lgrp, lover in zip(*loaded):
                            if lover:
                                loaded_over_by_parts[lgrp.parts] = lover
                    result = search_groups(graph, plan, self._hw, ctx=ctx)
                    stitch_stats = result.stats
                    candidates = result.candidates
                    partition_candidates = len(candidates)
                    groups = result.groups
                    if can_tune and defer:
                        # cold-miss policy (paper §7 production regime):
                        # serve the analytic (cost-model) plan NOW; the
                        # top-k partition race and the per-group tile
                        # sweeps run via ``rerace`` on the background
                        # executor, whose winner is hot-swapped into the
                        # live dispatch table and persisted.
                        if len(candidates) > 1:
                            partition_source = "analytic"
                        if len(candidates) > 1 or any(g.stitched
                                                      for g in groups):
                            race_ctx = _RaceContext(
                                graph=graph, ctx=ctx, sig=sig, plan=plan,
                                overrides=overrides, candidates=candidates,
                                groups=groups,
                                loaded_over_by_parts=loaded_over_by_parts,
                                stitch_stats=stitch_stats, out_tree=None)
                    elif can_tune and len(candidates) > 1:
                        from .autotune import tune_partitions

                        res = tune_partitions(
                            graph, [c.groups for c in candidates],
                            hw=self._hw, ctx=ctx)
                        if res is not None:
                            # commit the raced winner; its schedule *pins*
                            # are left to the per-group measured sweep below
                            # (the race's family swaps screen partitions,
                            # they are not a substitute for the tile sweep).
                            groups = candidates[res.index].groups
                            partition_source = "measured"
                            partition_index = res.index
                            autotuned = True
                    # a lone candidate stays model-sourced: "measured" is
                    # never stamped without an actual race, so a later
                    # process with a wider REPRO_STITCH_TOPK still races.
                    group_overrides = [
                        dict(loaded_over_by_parts.get(grp.parts, {}))
                        for grp in groups]
            else:
                groups = [StitchGroup((p.members,)) for p in plan.patterns]
                group_overrides = [{} for _ in groups]

        if race_ctx is not None:
            race_ctx.out_tree = out_tree
            race_ctx.shard = shard

        # with a background executor, measurement never blocks the cold
        # path: group tile sweeps run in ``rerace`` alongside the race.
        tune_groups = self._autotune and self._background is None \
            and not explicit_shard
        return self._finalize(
            graph=graph, ctx=ctx, sig=sig, plan=plan, overrides=overrides,
            entry=entry, cached_hit=cached is not None, autotuned=autotuned,
            groups=groups, group_overrides=group_overrides,
            groups_from_cache=groups_from_cache, stitch_stats=stitch_stats,
            partition_source=partition_source,
            partition_index=partition_index,
            partition_candidates=partition_candidates,
            tune_groups=tune_groups, t0=t0, phases=phases,
            out_tree=out_tree, race_ctx=race_ctx, shard=shard)

    def _finalize(self, *, graph: Graph, ctx: CostContext, sig: str,
                  plan: FusionPlan, overrides: list[dict],
                  entry: dict | None, cached_hit: bool, autotuned: bool,
                  groups: list[StitchGroup], group_overrides: list[dict],
                  groups_from_cache: bool, stitch_stats,
                  partition_source: str, partition_index: int,
                  partition_candidates: int, tune_groups: bool, t0: float,
                  phases: "_Phases", out_tree,
                  race_ctx: "_RaceContext | None",
                  shard=None) -> _Compiled:
        """Group tuning + emission + plan-cache store + report: the part
        of compilation shared by the cold path and the background
        ``rerace`` rebuild."""
        explicit_shard = shard is not None and shard.explicit
        group_tuned = group_tuned_wins = 0
        tuned_fresh = False
        with phases("search"):
            # groups convex one by one can still deadlock as kernels (A
            # feeds B through one member, B feeds A through another):
            # split until the partition schedules
            legal = break_cycles(graph, groups, ctx)
            if legal != groups:
                over_of = {g.parts: o
                           for g, o in zip(groups, group_overrides)}
                group_overrides = [dict(over_of.get(g.parts, {}))
                                   for g in legal]
                groups = legal

            # ---- measured group tuning (paper: tune the stitching scheme)
            # Stitched unions get their onepass/streaming phase split + tile
            # measured (batch-compiled sweep); a cache hit that already holds
            # a measured pin (override carries ``tuned``) is trusted.
            if tune_groups and self._stitch_groups:
                from .autotune import autotune_available, tune_group

                if autotune_available():
                    # isomorphic groups share one measured sweep (same
                    # rationale as emission dedup: struct_key equality means
                    # identical kernels up to constant values).
                    group_tuned_by_struct: dict[tuple, tuple] = {}
                    for gi, grp in enumerate(groups):
                        if grp.anchors or not grp.stitched:
                            # anchored groups carry their own fixed scheme
                            # (the anchor kernel's grid); single patterns
                            # are tune_pattern's job.
                            continue
                        gover = group_overrides[gi]
                        analytic = _sched_of(ctx.best(grp.members))
                        if gover.get("tuned"):
                            group_tuned += 1
                            pin = {k: v for k, v in gover.items()
                                   if k != "tuned"}
                            group_tuned_wins += pin != analytic
                            continue
                        skey = ctx.struct_key(grp.members)
                        members = sorted(grp.members)
                        hit = group_tuned_by_struct.get(skey)
                        if hit is not None:
                            # shared measured pin, remapped to this
                            # sibling's node ids (recompute is id-specific)
                            over = (_remap_override(hit[0], hit[1], members)
                                    if hit[0] is not None else None)
                        else:
                            over = tune_group(graph, grp.parts, hw=self._hw,
                                              ctx=ctx)
                            group_tuned_by_struct[skey] = (over, members)
                        if over is None:
                            continue
                        group_tuned += 1
                        group_tuned_wins += over != analytic
                        group_overrides[gi] = dict(over, tuned=True)
                        tuned_fresh = True
                    autotuned = True

        pat_over = {pat.members: over
                    for pat, over in zip(plan.patterns, overrides)}

        # ---- finer donation: schedule-position analysis -------------------
        # The first schedule item's kernel may overwrite graph inputs whose
        # only consumers are its own members (they are dead the moment it
        # has read them): those inputs alias the kernel's output buffers
        # (``input_output_aliases`` on the pallas_call) on top of the
        # jit-level ``donate_argnums`` donation.
        donate_first: frozenset[int] = frozenset()
        first_idx = -1
        # under an explicit shard the jit-level donate_argnums (outside
        # the shard_map) still applies, but kernel-level aliasing inside
        # the mapped body is not: the pallas_call's operands are local
        # shards whose buffers shard_map manages.
        if (self._donate or self._donate_argnums is not None) \
                and self._dispatch == "single" and not explicit_shard:
            # with explicit donate_argnums only those flat positions may
            # alias (serving donates the cache, never the params).
            allowed = (None if self._donate_argnums is None else
                       {graph.inputs[i] for i in self._donate_argnums
                        if 0 <= i < len(graph.inputs)})
            member_of: dict[int, int] = {}
            for gi, grp in enumerate(groups):
                for nid in grp.members:
                    member_of[nid] = gi
            inset = set(graph.inputs)
            for nid in graph.topo_order():
                if nid in inset or graph.node(nid).kind is OpKind.CONST:
                    continue
                first_idx = member_of.get(nid, -1)
                break
            if first_idx >= 0:
                members = groups[first_idx].members
                ready = all(i in inset
                            or graph.node(i).kind is OpKind.CONST
                            for i in ctx.bounds(members).inputs)
                outset = set(graph.outputs)
                donate_first = frozenset(
                    i for i in graph.inputs
                    if ready and i not in outset and graph.consumers(i)
                    and (allowed is None or i in allowed)
                    and all(c in members for c in graph.consumers(i)))
                if not donate_first:
                    first_idx = -1

        with phases("emit"):
            # ---- emission (isomorphic groups emitted once, rebound after)
            # Each group descends the fallback ladder on emission failure:
            # stitched megakernel -> one fused kernel per member pattern ->
            # plain packed (XLA) lowering of the union -> bare per-node
            # schedule entries.  A degraded group never degrades its
            # neighbors, and every rung taken is recorded on the report.
            fallbacks: list[tuple[int, str, str]] = []

            def _emit_fallback(gi: int, grp,
                               exc: BaseException) -> list[Emitted]:
                reason = f"{type(exc).__name__}: {exc}"
                anchor_set = set(grp.anchors)
                if anchor_set:
                    # anchored -> unanchored stitched: re-emit the exact
                    # pre-absorption composition (``grp.unanchored``); the
                    # bare anchor nodes fall out of every emitted union and
                    # replay as plain XLA schedule entries.
                    try:
                        ems = [emit_group(graph, tuple(sub), hw=self._hw,
                                          ctx=ctx, group=gi)
                               for sub in grp.unanchored
                               if frozenset(x for p in sub for x in p)
                               - anchor_set]
                        fallbacks.append((gi, RUNG_STITCHED, reason))
                        return ems
                    except Exception:  # noqa: BLE001 - descend one more rung
                        pass
                parts = [p for p in grp.parts
                         if not (len(p) == 1 and next(iter(p)) in anchor_set)]
                if parts and (anchor_set or len(parts) > 1):
                    try:
                        ems = [emit_group(
                                   graph, (part,), hw=self._hw, ctx=ctx,
                                   schedule_override=(dict(pat_over.get(
                                       frozenset(part), {})) or None),
                                   group=gi)
                               for part in parts]
                        fallbacks.append((gi, RUNG_PATTERNS, reason))
                        return ems
                    except Exception:  # noqa: BLE001 - descend one more rung
                        pass
                try:
                    ems = [emit_pattern(graph, frozenset(grp.members),
                                        hw=self._hw, force_packed=True,
                                        ctx=ctx, group=gi)]
                    fallbacks.append((gi, RUNG_BASELINE, reason))
                    return ems
                except Exception as exc2:  # noqa: BLE001 - last rung: the
                    # members run as bare per-node schedule entries (the
                    # interpreter path _build_schedule keeps for uncovered
                    # nodes) -- slow, still correct.
                    fallbacks.append((gi, RUNG_BASELINE,
                                      f"{reason}; packed emission also failed "
                                      f"({type(exc2).__name__}: {exc2})"))
                    return []

            emit_cache: dict[tuple, tuple[Emitted, list[int]]] = {}
            emitted: list[Emitted] = []
            reused = 0
            for gi, (grp, gover) in enumerate(zip(groups, group_overrides)):
                union = grp.members
                over = gover or (pat_over.get(grp.parts[0], {})
                                 if len(grp.parts) == 1 else {})
                parts = tuple(tuple(sorted(p)) for p in grp.parts)
                donate_into = donate_first if gi == first_idx else None
                ekey = _emit_signature(graph, ctx, union, over,
                                       anchors=grp.anchors) + (
                    ("donate", tuple(sorted(donate_first)))
                    if donate_into else ())
                em = None
                hit = emit_cache.get(ekey)
                if hit is not None:
                    em = _rebind_emitted(graph, ctx, union, parts, *hit,
                                         group=gi)
                    if em is not None:
                        reused += 1
                if em is None:
                    try:
                        if explicit_shard:
                            from .codegen import check_shard_emittable

                            # spec-sanity seam (also the shard_spec_fail
                            # fault site): a bad layout degrades THIS group
                            # down the ladder, siblings stay stitched.
                            check_shard_emittable(graph, union, shard, gi)
                        flt = _faults.fire("emit_fail", group=gi)
                        if flt is not None:
                            raise EmitError(
                                f"injected emit_fail on group {gi}")
                        if grp.anchors:
                            flt = _faults.fire("anchor_emit_fail", group=gi)
                            if flt is not None:
                                raise EmitError(
                                    f"injected anchor_emit_fail on group {gi}")
                        em = emit_group(graph, grp.parts, hw=self._hw,
                                        ctx=ctx,
                                        schedule_override=over or None,
                                        donate_into=donate_into,
                                        anchors=grp.anchors, group=gi)
                    except Exception as exc:  # noqa: BLE001 - ladder below
                        for fem in _emit_fallback(gi, grp, exc):
                            fem._members = sorted(  # type: ignore[attr-defined]
                                n for p in fem.parts for n in p)
                            emitted.append(fem)
                        continue
                    ext_set = set(em.ext_ids)
                    emit_cache[ekey] = (em, _ext_seen_order(graph, union,
                                                            ext_set))
                em._members = sorted(union)  # type: ignore[attr-defined]
                emitted.append(em)
            schedule = _build_schedule(graph, emitted)
        rung = (RUNG_ANCHORED if any(g.anchors for g in groups)
                else RUNG_STITCHED)
        for _gi, r, _r in fallbacks:
            if RUNGS.index(r) > RUNGS.index(rung):
                rung = r

        # a degraded compile must not persist: the stored plan would
        # replay the very emission that just failed (and the schedules
        # below assume one emitted kernel per group).
        poisoned = self._poison.rung_for(sig) is not None
        store_fresh = (self._plan_cache is not None and not cached_hit
                       and not fallbacks and not poisoned)
        # a cache hit whose entry lacked a usable groups section (e.g.
        # first written by a stitch_groups=False baseline run) gets the
        # freshly stitched composition written back once, so later
        # processes skip the stitcher again.  Likewise an entry whose
        # groups were just measured for the first time is rewritten so
        # later processes skip the re-tune.
        store_groups_backfill = (self._plan_cache is not None
                                 and cached_hit
                                 and self._stitch_groups
                                 and not fallbacks and not poisoned
                                 and (not groups_from_cache or tuned_fresh))
        #: the clean entry payload, kept (not only stored) so canary
        #: re-admission can re-persist the plan after a quarantine
        #: evicted it -- including the restart case, where the compile
        #: itself saw a poisoned signature and the store was refused.
        entry_payload = None
        build_payload = store_fresh or store_groups_backfill or (
            self._canary is not None and poisoned
            and self._plan_cache is not None and self._stitch_groups
            and not fallbacks)
        if build_payload:
            em_of_pattern = {em.parts[0]: em for em in emitted
                             if len(em.parts) == 1}
            schedules = []
            for pat, over in zip(plan.patterns, overrides):
                em = em_of_pattern.get(tuple(sorted(pat.members)))
                if em is not None:
                    # emitted standalone: persist what actually ran (the
                    # estimate carries tuned/streaming block_cols now)
                    schedules.append(_sched_of(em.estimate))
                elif over:
                    schedules.append(dict(over))
                else:
                    schedules.append(_sched_of(ctx.best(pat.members)))
            # groups are persisted only when the stitcher actually ran: a
            # stitch_groups=False run (benchmark baseline, debugging) must
            # not poison the shared cache with its degenerate singleton
            # composition -- a later default-mode compile re-stitches.
            # measured group pins persist verbatim (with their ``tuned``
            # marker); analytic ones persist what actually emitted.
            groups_arg = groups if self._stitch_groups else None
            group_scheds = ([dict(gover) if gover.get("tuned")
                             else _sched_of(em.estimate)
                             for em, gover in zip(emitted, group_overrides)]
                            if self._stitch_groups else None)
            # "analytic" is a report-level state (race pending in the
            # background): the stored entry stays model-sourced so any
            # later process still races it.
            store_source = None
            if self._stitch_groups:
                store_source = ("model" if partition_source == "analytic"
                                else partition_source)
            entry_payload = plan_to_entry(plan, schedules, sig,
                                          groups=groups_arg,
                                          group_schedules=group_scheds,
                                          partition_source=store_source,
                                          shard=shard)
            if store_fresh or store_groups_backfill:
                self._plan_cache.store(sig, dict(entry_payload))
        if entry_payload is None and entry:
            entry_payload = {k: v for k, v in entry.items()
                             if k != "checksum"}
        plan_time = time.perf_counter() - t0

        stats = plan_stats(graph, plan, ctx=ctx, groups=groups)
        report = StitchReport(
            stats=stats,
            n_pallas=sum(1 for e in emitted if e.kind == "pallas"),
            n_packed=sum(1 for e in emitted if e.kind == "packed"),
            scratch_bytes=sum(e.scratch_bytes for e in emitted),
            scratch_naive_bytes=sum(e.scratch_naive_bytes for e in emitted),
            plan_time_s=plan_time,
            trace_s=phases.s["trace"],
            search_s=phases.s["search"],
            emit_s=phases.s["emit"],
            patterns=[p.members for p in plan.patterns],
            plan_cache_hit=cached_hit,
            autotuned=autotuned,
            signature=sig,
            dispatch=self._dispatch,
            groups=[g.parts for g in groups],
            n_groups=len(groups),
            n_stitched=sum(1 for g in groups if g.stitched),
            n_anchored=sum(1 for g in groups if g.anchors),
            stitched_hbm_bytes_saved=sum(e.hbm_saved for e in emitted),
            emission_reused=reused,
            beam_width=(stitch_stats.beam_width if stitch_stats else 0),
            beam_states_explored=(stitch_stats.states_explored
                                  if stitch_stats else 0),
            group_tuned=group_tuned,
            group_tuned_wins=group_tuned_wins,
            partition_source=partition_source,
            partition_candidates=partition_candidates,
            partition_index=partition_index,
            n_recomputed=sum(e.n_recomputed for e in emitted),
            recompute_bytes_freed=sum(e.recompute_bytes_freed
                                      for e in emitted),
            caps_hit=dict(ctx.caps),
            plan_cache_hits=(self._plan_cache.hits
                             if self._plan_cache is not None else 0),
            plan_cache_misses=(self._plan_cache.misses
                               if self._plan_cache is not None else 0),
            fallbacks=list(fallbacks),
            rung=rung,
            sharded=shard is not None,
            mesh_axes=(shard.mesh_key() if shard is not None else ()),
            n_collective=sum(1 for n in graph.nodes.values()
                             if n.kind is OpKind.COLLECTIVE),
            opaque_prims=dict(collections.Counter(
                n.prim for n in graph.nodes.values()
                if n.kind is OpKind.OPAQUE and n.prim != "tuple_get")),
            collective_boundaries=getattr(stitch_stats,
                                          "collective_boundaries", 0)
            if stitch_stats else 0,
        )

        def _on_quarantine(reason: str, _sig=sig) -> None:
            # a verified-bad (or crashing) plan must never be served or
            # re-persisted again: evict the live cache entry and pin the
            # signature so every later compile lands on the baseline.
            if self._plan_cache is not None:
                self._plan_cache.evict_entry(_sig)
            self._poison.pin(_sig, RUNG_BASELINE, reason)

        def _on_readmit(_sig=sig, _payload=entry_payload) -> None:
            # canary probation passed: lift the poison pin so the
            # signature serves stitched again and, when a clean plan
            # payload is in hand, re-persist it (the quarantine evicted
            # the on-disk entry).
            if self._plan_cache is not None:
                self._plan_cache.readmit(_sig)
                if _payload:
                    self._plan_cache.store(_sig, dict(_payload))
            else:
                self._poison.unpin(_sig)

        compiled = _Compiled(graph, plan, emitted, schedule, report,
                             out_tree, dispatch=self._dispatch,
                             name=self.program,
                             donate=self._donate,
                             donate_argnums=self._donate_argnums,
                             verify_policy=VerifyPolicy.from_env(),
                             on_quarantine=_on_quarantine,
                             shard=shard, canary=self._canary,
                             on_readmit=_on_readmit)
        if poisoned and self._canary is None:
            compiled.pin_baseline(
                "signature poisoned: "
                + (self._poison.reason_for(sig) or "unspecified"))
        elif not poisoned:
            compiled._race_ctx = race_ctx
        # with a canary attached a poisoned signature is NOT hard-pinned:
        # register() adopts it as quarantined and the per-call governor
        # serves the baseline until probation re-admits it.
        if self._canary is not None:
            self._canary.register(
                sig,
                poisoned_reason=((self._poison.reason_for(sig) or "poisoned")
                                 if poisoned else None),
                rung=report.rung)
        return compiled

    def rerace(self, key: tuple) -> str | None:
        """Run the deferred measurement for ``key`` and hot-swap the
        winner into the live dispatch table.

        Called on the background executor: races the top-k candidate
        partitions on silicon (when there is more than one), sweeps the
        winner's group schedules, re-emits, and swaps the new compiled
        instance in with a single dict assignment under ``_swap_lock``
        -- in-flight calls keep executing the old instance, which stays
        fully valid, so a wave never observes a half-built dispatch.
        The winner persists to the plan cache (``partition_source:
        measured``), so later processes replay it with no re-race.
        Returns the new partition source, or None when there was
        nothing to measure or the instance was already superseded."""
        compiled = self._cache.get(key)
        if compiled is None or compiled._race_ctx is None:
            return None
        rc = compiled._race_ctx
        if compiled._use_baseline \
                or self._poison.rung_for(rc.sig) is not None:
            return None  # quarantined/poisoned: nothing worth racing
        from .autotune import autotune_available, tune_partitions

        if not autotune_available():
            return None
        t0 = time.perf_counter()
        phases = _Phases()
        partition_source, partition_index, autotuned = "model", 0, False
        groups = rc.groups
        with spans.span("stitch.build", program=self.program):
            with phases("search"):
                if len(rc.candidates) > 1:
                    res = tune_partitions(rc.graph,
                                          [c.groups for c in rc.candidates],
                                          hw=self._hw, ctx=rc.ctx)
                    if res is not None:
                        groups = rc.candidates[res.index].groups
                        partition_source = "measured"
                        partition_index = res.index
                        autotuned = True
            group_overrides = [
                dict(rc.loaded_over_by_parts.get(grp.parts, {}))
                for grp in groups]
            new = self._finalize(
                graph=rc.graph, ctx=rc.ctx, sig=rc.sig, plan=rc.plan,
                overrides=rc.overrides, entry=None, cached_hit=False,
                autotuned=autotuned, groups=groups,
                group_overrides=group_overrides, groups_from_cache=False,
                stitch_stats=rc.stitch_stats,
                partition_source=partition_source,
                partition_index=partition_index,
                partition_candidates=len(rc.candidates),
                tune_groups=True, t0=t0, phases=phases,
                out_tree=rc.out_tree, race_ctx=None, shard=rc.shard)
        if _faults.fire("swap_crash", signature=rc.sig) is not None:
            raise GuardError("injected swap_crash: hot-swap commit failed")
        if self._canary is not None:
            # a measured rebuild must prove itself before it serves: N
            # verified calls on synthesized inputs.  Failure refuses the
            # swap and evicts the just-stored measured entry -- but does
            # NOT poison the signature: the live analytic plan is fine.
            ok, why = self._canary.burn_in(new)
            if not ok:
                if self._plan_cache is not None:
                    self._plan_cache.evict_entry(rc.sig)
                raise VerifyMismatchError(
                    f"measured plan failed canary burn-in: {why}")
        with self._swap_lock:
            if self._cache.get(key) is not compiled:
                return None  # superseded: a newer swap already won
            if compiled._use_baseline:
                return None  # quarantined mid-race: keep the baseline pin
            if self._poison.rung_for(rc.sig) is not None:
                return None  # canary quarantined mid-race: its _trip
                #              pinned the poison list synchronously, so
                #              this re-check closes the swap-vs-
                #              quarantine race
            self._cache[key] = new
        return partition_source

    @property
    def n_compiled(self) -> int:
        """Distinct shape signatures compiled so far (serving stats)."""
        return len(self._cache)

    def reports(self) -> list[StitchReport]:
        """Reports of every live compiled instance, in insertion order
        (the serving layer aggregates plan-cache hit/miss from these)."""
        return [c.report for c in self._cache.values()]

    def __call__(self, *args, **kwargs):
        with spans.span("stitch.call", program=self.program):
            with spans.span("stitch.lookup"):
                compiled, flat = self._compile(args, kwargs)
            return compiled(flat)

    def compiled(self, *args, **kwargs) -> _Compiled:
        """The compiled instance for these example args (tests/benchmarks)."""
        compiled, _ = self._compile(args, kwargs)
        return compiled

    def report(self, *args, **kwargs) -> StitchReport:
        compiled, _ = self._compile(args, kwargs)
        return compiled.report


def stitched_jit(fn: Callable, *, hw: Hardware | None = None,
                 use_remote_fusion: bool = True,
                 differentiable: bool = False,
                 dispatch: str = "single",
                 plan_cache: str | None = None,
                 autotune: bool = False,
                 stitch_groups: bool = True,
                 donate: bool = False,
                 donate_argnums: tuple[int, ...] | None = None,
                 background: Any = None,
                 mesh: Any = None,
                 in_specs: Any = None,
                 out_specs: Any = None,
                 canary: Any = None) -> Callable:
    """Wrap ``fn`` with the FusionStitching trace->plan->stitch->emit
    pipeline.

    ``dispatch="single"`` (default) lowers the whole plan into one jitted
    callable; ``dispatch="interpret"`` keeps the per-schedule-item Python
    interpreter.  ``stitch_groups=False`` disables the cross-pattern
    stitching pass (one kernel per plan pattern -- the baseline
    ``benchmarks/bench_stitch_groups.py`` measures against).
    ``donate=True`` donates input buffers the schedule never reads again
    (any input that is not also an output) to XLA; ``donate_argnums``
    instead donates only the named flat input positions (the serving
    scheduler donates the stacked KV/SSM cache across decode waves but
    never the params).  ``plan_cache`` points
    at a persistent plan-cache directory (defaults to
    ``$REPRO_PLAN_CACHE`` when set).  With ``autotune=True`` and an
    accelerator present, block schedules are measured instead of modeled
    (results land in the plan cache).  ``background`` takes an executor
    with ``submit(callable)`` (``repro.serving.BackgroundTuner``): cold
    compiles then serve the analytic plan immediately and the partition
    race + group sweeps run asynchronously, hot-swapping the measured
    winner into the dispatch table (the paper's production cold-miss
    policy).

    With ``differentiable=True`` the wrapper carries a ``custom_vjp`` whose
    forward runs the stitched kernels and whose backward re-traces the VJP
    of ``fn`` and stitches *it* too (recompute-style backward: residuals
    are the primal inputs, matching the paper's training support where the
    backward graph is just another fusion-planned graph).

    ``canary`` takes a ``repro.runtime.CanaryController`` (or
    ``$REPRO_CANARY=1`` auto-creates one rooted beside the plan cache):
    live dispatches are sampled through the shadow-verification
    reference under a hard overhead budget, and per-signature health
    (healthy -> quarantined -> probation -> re-admitted) persists
    beside the poison list.  The forward path only -- a differentiable
    wrapper's backward runs un-canaried.

    ``mesh`` + ``in_specs``/``out_specs`` plan one stitched schedule
    against the *per-shard* shapes of ``fn`` (treated as the per-shard
    body, shard_map-style) and replay it on every shard via
    ``shard_map`` -- collectives inside ``fn`` become hard stitch-group
    boundaries.  Sharded plans are not differentiable (the backward
    re-trace has no mesh context yet).
    """
    if differentiable and mesh is not None:
        raise ValueError(
            "stitched_jit: differentiable=True cannot be combined with "
            "an explicit mesh (the backward re-trace is mesh-free)")
    # differentiable wrappers keep the primal inputs as VJP residuals, so
    # the forward must not donate them out from under the backward pass.
    sf = StitchedFunction(fn, hw=hw, use_remote_fusion=use_remote_fusion,
                          dispatch=dispatch, plan_cache=plan_cache,
                          autotune=autotune, stitch_groups=stitch_groups,
                          donate=donate and not differentiable,
                          donate_argnums=(donate_argnums
                                          if not differentiable else None),
                          background=background,
                          mesh=mesh, in_specs=in_specs,
                          out_specs=out_specs, canary=canary)
    if not differentiable:
        return sf

    bwd_cache: dict[tuple, StitchedFunction] = {}

    @jax.custom_vjp
    def wrapped(*args):
        return sf(*args)

    def fwd(*args):
        return sf(*args), args

    def bwd(residuals, cts):
        args = residuals
        key = tuple((tuple(np.shape(a)), str(jnp.result_type(a)))
                    for a in jax.tree_util.tree_leaves(args))
        if key not in bwd_cache:
            def vjp_fn(ct, *primals):
                _, pullback = jax.vjp(fn, *primals)
                return pullback(ct)
            bwd_cache[key] = StitchedFunction(
                vjp_fn, hw=hw, use_remote_fusion=use_remote_fusion,
                dispatch=dispatch,
                plan_cache=plan_cache, autotune=autotune,
                stitch_groups=stitch_groups, canary=False)
        return bwd_cache[key](cts, *args)

    wrapped.defvjp(fwd, bwd)
    wrapped.report = sf.report  # type: ignore[attr-defined]
    return wrapped


def fusion_report(fn: Callable, *example_args, hw: Hardware | None = None,
                  **example_kwargs) -> StitchReport:
    """Plan ``fn`` on example inputs and return the plan statistics."""
    sf = stitched_jit(fn, hw=hw)
    return sf.report(*example_args, **example_kwargs)
