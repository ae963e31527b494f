"""ISSUE-2 tests: cross-pattern stitch groups (megakernel emission),
group-aware plan cache (+ LRU bound), emission dedup across isomorphic
patterns, block_cols on KernelEstimate, and input-buffer donation."""
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (StitchedFunction, StitchGroup, make_groups,
                        make_plan, stitch_gain, trace)
from repro.core.codegen import emit_group
from repro.core.cost_model import V5E, best_estimate, estimate_streaming
from repro.core.costctx import CostContext
from repro.core.ir import FusionPlan, Pattern
from repro.core.memory_planner import group_order, plan_group_scratch
from repro.core.plan_cache import (PlanCache, entry_to_groups,
                                   graph_signature, plan_to_entry)
from repro.core.rowspec import analyze

rng = np.random.default_rng(11)


def _ln(x, g, b):
    m = jnp.mean(x, axis=-1, keepdims=True)
    v = jnp.mean((x - m) ** 2, axis=-1, keepdims=True)
    return (x - m) * jax.lax.rsqrt(v + 1e-5) * g + b


def _softmax(x):
    e = jnp.exp(x - jnp.max(x, axis=-1, keepdims=True))
    return e / jnp.sum(e, axis=-1, keepdims=True)


def _rms(x, g):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * g


def _chain(x, g, b, g2):
    return _rms(_softmax(_ln(x, g, b)), g2)


def _deep(x, g, b):
    """Deep enough that MAX_PATTERN splits the plan into >= 3 patterns."""
    for _ in range(8):
        x = _ln(x, g, b)
        x = jax.nn.gelu(x, approximate=True) + x
    return x


def _deep_args(R=64, C=512):
    return (rng.standard_normal((R, C)).astype(np.float32),
            (np.abs(rng.standard_normal(C)) + 0.5).astype(np.float32),
            rng.standard_normal(C).astype(np.float32))


def _subjaxprs(v):
    if hasattr(v, "eqns"):          # Jaxpr
        yield v
    elif hasattr(v, "jaxpr"):       # ClosedJaxpr
        yield v.jaxpr
    elif isinstance(v, (list, tuple)):
        for x in v:
            yield from _subjaxprs(x)


def _count_pallas_calls(jaxpr) -> int:
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            n += 1
        for v in eqn.params.values():
            n += sum(_count_pallas_calls(j) for j in _subjaxprs(v))
    return n


# -- the stitcher pass --------------------------------------------------------
def test_three_pattern_chain_stitches_to_one_pallas_call():
    """Acceptance: a chain of >= 3 row-compatible patterns lowers to a
    single pallas_call."""
    args = _deep_args()
    graph = trace(_deep, *args)
    plan = make_plan(graph)
    assert len(plan.patterns) >= 3  # the guardrail split the chain

    sf = StitchedFunction(_deep)
    compiled = sf.compiled(*args)
    rep = compiled.report
    assert rep.n_groups == 1 and rep.n_stitched == 1
    assert rep.n_pallas == 1 and rep.n_packed == 0
    jaxpr = jax.make_jaxpr(compiled._run_schedule)(
        *[jnp.asarray(a) for a in args])
    assert _count_pallas_calls(jaxpr.jaxpr) == 1
    # and the per-pattern baseline really pays one launch per pattern
    base = StitchedFunction(_deep, stitch_groups=False).compiled(*args)
    base_jaxpr = jax.make_jaxpr(base._run_schedule)(
        *[jnp.asarray(a) for a in args])
    assert _count_pallas_calls(base_jaxpr.jaxpr) >= 2


def test_stitched_report_saves_interpattern_hbm():
    args = _deep_args()
    sf = StitchedFunction(_deep)
    rep = sf.report(*args)
    base = StitchedFunction(_deep, stitch_groups=False).report(*args)
    assert rep.stitched_hbm_bytes_saved > 0
    assert base.stitched_hbm_bytes_saved == 0
    assert rep.stats.n_kernels_stitched < base.stats.n_kernels_stitched
    assert rep.stats.hbm_bytes_stitched < base.stats.hbm_bytes_stitched


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_group_matches_interpreter_numerics(dtype):
    def fn(x, g, b):  # 4 stitched layers: still groups, fewer bf16 ulps
        for _ in range(4):
            x = _ln(x, g, b)
            x = jax.nn.gelu(x, approximate=True) + x
        return x

    args = [jnp.asarray(a, dtype) for a in _deep_args()]
    single = StitchedFunction(fn, dispatch="single")
    interp = StitchedFunction(fn, dispatch="interpret")
    assert single.report(*args).n_stitched >= 1
    y1 = np.asarray(single(*args), np.float32)
    y2 = np.asarray(interp(*args), np.float32)
    ref = np.asarray(fn(*args), np.float32)
    tol = 5e-2 if dtype == "bfloat16" else 1e-4
    np.testing.assert_allclose(y1, y2, rtol=tol, atol=tol)
    # vs the eager reference: bf16 cancellation makes isolated elements
    # noisy in *any* execution order, so bound the violation rate too
    close = np.isclose(y1, ref, rtol=tol, atol=tol)
    assert close.mean() > 0.999
    if dtype == "float32":
        np.testing.assert_allclose(y1, ref, rtol=tol, atol=tol)


def test_make_groups_on_hand_split_plan():
    """The stitcher merges a hand-split 3-pattern chain and emit_group
    compiles the union into one numerically faithful kernel."""
    x = rng.standard_normal((16, 128)).astype(np.float32)
    g = (np.abs(rng.standard_normal(128)) + 0.5).astype(np.float32)
    b = rng.standard_normal(128).astype(np.float32)
    g2 = (np.abs(rng.standard_normal(128)) + 0.5).astype(np.float32)
    graph = trace(_chain, x, g, b, g2)
    ctx = CostContext(graph)
    fusible = sorted(graph.fusible_nodes())
    thirds = [frozenset(fusible[:len(fusible) // 3]),
              frozenset(fusible[len(fusible) // 3: 2 * len(fusible) // 3]),
              frozenset(fusible[2 * len(fusible) // 3:])]
    plan = FusionPlan([Pattern(t, 0.0) for t in thirds])
    groups = make_groups(graph, plan, ctx=ctx)
    assert len(groups) == 1 and len(groups[0].parts) >= 3

    em = emit_group(graph, groups[0].parts, ctx=ctx)
    assert em.kind == "pallas" and len(em.parts) >= 3
    assert em.hbm_saved > 0
    vals = {nid: v for nid, v in zip(graph.inputs, [x, g, b, g2])}
    outs = em.fn(*[jnp.asarray(vals[i]) for i in em.ext_ids])
    ref = _chain(x, g, b, g2)
    got = np.asarray(outs[em.out_ids.index(graph.outputs[0])])
    np.testing.assert_allclose(got.reshape(ref.shape), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


def test_stitch_gain_prices_interface_bytes():
    args = _deep_args()
    graph = trace(_deep, *args)
    ctx = CostContext(graph)
    plan = make_plan(graph, ctx=ctx)
    parts = tuple(sorted((p.members for p in plan.patterns), key=min))
    gain = stitch_gain(graph, parts, ctx=ctx)
    assert gain.feasible
    assert gain.hbm_bytes_saved > 0
    assert gain.latency_gain_s > 0
    # structural interface accounting agrees in spirit: bytes flowing
    # between parts are a lower bound on what stitching saves
    assert graph.interface_bytes(parts) > 0


def test_group_scratch_spans_patterns():
    args = _deep_args(16, 256)
    graph = trace(_deep, *args)
    ctx = CostContext(graph)
    plan = make_plan(graph, ctx=ctx)
    groups = make_groups(graph, plan, ctx=ctx)
    grp = max(groups, key=len)
    if len(grp.parts) < 2:
        pytest.skip("planner produced a single pattern here")
    info = ctx.info(grp.members)
    assert info is not None
    plan_s = plan_group_scratch(graph, list(grp.parts), info)
    assert plan_s.staged_ids  # inter-part values are staged, not spilled
    assert plan_s.total_bytes <= plan_s.naive_bytes
    order = group_order(graph, list(grp.parts))
    assert sorted(order) == sorted(grp.members)
    seen = set()
    for nid in order:  # the back-to-back order respects dependences
        assert all(i in seen or i not in grp.members
                   for i in graph.node(nid).inputs)
        seen.add(nid)


# -- group-aware persistent cache ---------------------------------------------
def test_group_cache_roundtrip(tmp_path):
    args = _deep_args()
    sf1 = StitchedFunction(_deep, plan_cache=str(tmp_path))
    rep1 = sf1.report(*args)
    assert not rep1.plan_cache_hit and rep1.n_stitched >= 1

    sf2 = StitchedFunction(_deep, plan_cache=str(tmp_path))
    rep2 = sf2.report(*args)
    assert rep2.plan_cache_hit
    assert rep2.groups == rep1.groups          # same composition
    assert rep2.n_groups == rep1.n_groups
    y1 = np.asarray(sf1(*args))
    y2 = np.asarray(sf2(*args))
    np.testing.assert_allclose(y1, y2, rtol=1e-6, atol=1e-6)


def test_baseline_run_does_not_poison_group_cache(tmp_path):
    """A stitch_groups=False compile (benchmark baseline / debugging)
    must not persist its degenerate singleton composition: a later
    default-mode compile of the same signature re-runs the stitcher."""
    args = _deep_args()
    base = StitchedFunction(_deep, stitch_groups=False,
                            plan_cache=str(tmp_path))
    assert base.report(*args).n_stitched == 0
    stitched = StitchedFunction(_deep, plan_cache=str(tmp_path))
    rep = stitched.report(*args)
    assert rep.plan_cache_hit          # the plan itself is reused...
    assert rep.n_stitched >= 1         # ...but stitching still happens
    assert rep.stitched_hbm_bytes_saved > 0
    # and the freshly stitched composition is written back: the entry now
    # carries groups, so a third compile skips the stitcher too
    entry = PlanCache(str(tmp_path)).load(rep.signature)
    assert entry is not None and entry.get("groups")
    graph = trace(_deep, *args)
    from repro.core.plan_cache import entry_to_plan
    plan, _ = entry_to_plan(entry, graph)
    assert entry_to_groups(entry, plan, graph) is not None


def test_entry_to_groups_validates(tmp_path):
    args = _deep_args()
    graph = trace(_deep, *args)
    ctx = CostContext(graph)
    plan = make_plan(graph, ctx=ctx)
    groups = make_groups(graph, plan, ctx=ctx)
    sig = graph_signature(graph, V5E)
    entry = plan_to_entry(plan, [{} for _ in plan.patterns], sig,
                          groups=groups,
                          group_schedules=[{} for _ in groups])
    decoded = entry_to_groups(entry, plan, graph)
    assert decoded is not None
    got_groups, _ = decoded
    assert [g.parts for g in got_groups] == [g.parts for g in groups]
    # corrupt: pattern index out of range / duplicated -> stitcher re-runs
    bad = dict(entry)
    bad["groups"] = [{"parts": [0, 99], "extra": []}]
    assert entry_to_groups(bad, plan, graph) is None
    bad["groups"] = [{"parts": [0], "extra": []},
                     {"parts": [0], "extra": []}]
    assert entry_to_groups(bad, plan, graph) is None
    # duplicates *within* one record are corrupt too
    bad["groups"] = [{"parts": [0, 0], "extra": []}]
    assert entry_to_groups(bad, plan, graph) is None
    free = [n for n in graph.fusible_nodes()
            if n not in plan.covered()]
    if free:
        bad["groups"] = [{"parts": [0], "extra": [free[0], free[0]]}]
        assert entry_to_groups(bad, plan, graph) is None
    # extras inside a pattern are stale
    some_member = min(plan.patterns[0].members)
    bad["groups"] = [{"parts": [0], "extra": [some_member]}]
    assert entry_to_groups(bad, plan, graph) is None


def test_plan_cache_lru_eviction(tmp_path):
    # grace disabled: this test stores entries milliseconds apart and
    # asserts LRU behavior; the store-during-evict grace window has its
    # own two-instance test in test_topk_tune.py.
    cache = PlanCache(str(tmp_path), max_entries=2, evict_grace_s=0.0)
    entries = {}
    for name in ("aaa", "bbb", "ccc"):
        entries[name] = {"format": 2, "signature": name, "patterns": []}
        cache.store(name, entries[name])
        time.sleep(0.02)
    assert cache.load("aaa") is None          # oldest evicted
    assert cache.load("bbb") is not None
    assert cache.load("ccc") is not None
    # a load refreshes recency: bbb was just touched, so storing ddd
    # evicts ccc (stored before the bbb touch)
    time.sleep(0.02)
    assert cache.load("bbb") is not None
    time.sleep(0.02)
    cache.store("ddd", {"format": 2, "signature": "ddd", "patterns": []})
    assert cache.load("ccc") is None
    assert cache.load("bbb") is not None
    assert cache.load("ddd") is not None
    assert len([n for n in os.listdir(str(tmp_path))
                if n.endswith(".json")]) == 2


# -- block_cols on KernelEstimate --------------------------------------------
def test_kernel_estimate_carries_block_cols():
    x = np.zeros((8, 4096), np.float32)
    graph = trace(_softmax, x)
    pat = frozenset(graph.fusible_nodes())
    info = analyze(graph, pat)
    est = estimate_streaming(graph, pat, info, 8, 512)
    assert est.block_cols == 512
    assert best_estimate(graph, frozenset(graph.fusible_nodes())).block_cols \
        >= 0  # onepass/packed report 0, streaming a positive tile


def test_streaming_block_cols_roundtrips_cache_without_override(tmp_path):
    """Analytic streaming tiles persist via the estimate itself now."""
    import dataclasses

    from repro.core.cost_model import Hardware
    small = Hardware(vmem_bytes=256 * 1024)  # force streaming
    x = rng.standard_normal((16, 8192)).astype(np.float32)
    g = (np.abs(rng.standard_normal(8192)) + 0.5).astype(np.float32)
    b = rng.standard_normal(8192).astype(np.float32)
    sf = StitchedFunction(_ln, hw=small, plan_cache=str(tmp_path))
    rep = sf.report(x, g, b)
    entry = PlanCache(str(tmp_path)).load(rep.signature)
    assert entry is not None
    streaming = [rec for rec in entry["patterns"]
                 if rec.get("schedule") == "streaming"]
    streaming += [rec for rec in entry.get("groups", ())
                  if rec.get("schedule") == "streaming"]
    assert streaming and all(rec.get("block_cols", 0) > 0
                             for rec in streaming)
    y = np.asarray(sf(x, g, b))
    np.testing.assert_allclose(y, np.asarray(_ln(x, g, b)),
                               rtol=1e-4, atol=1e-4)


# -- emission dedup across isomorphic patterns --------------------------------
def test_isomorphic_layers_emit_once():
    """Repeated transformer-style layers separated by opaque sorts:
    identical layers compile one kernel, rebound per instance.

    A sort is opaque but not an anchor, so nothing folds across it
    (anchored dedup is covered in test_anchor.py)."""
    def stack(x, g, b):
        for _ in range(4):
            x = jnp.sort(_ln(x, g, b), axis=-1)  # keeps layers separate
        return x

    x = rng.standard_normal((16, 128)).astype(np.float32)
    g = (np.abs(rng.standard_normal(128)) + 0.5).astype(np.float32)
    b = rng.standard_normal(128).astype(np.float32)
    sf = StitchedFunction(stack)
    rep = sf.report(x, g, b)
    assert rep.n_groups >= 4
    # layer 1 reads a graph input (different structure); layers 2..4 are
    # isomorphic and rebind one compiled kernel
    assert rep.emission_reused >= 2
    y = np.asarray(sf(x, g, b))
    ref = np.asarray(stack(jnp.asarray(x), jnp.asarray(g), jnp.asarray(b)))
    np.testing.assert_allclose(y, ref, rtol=1e-4, atol=1e-4)


def test_dedup_respects_differing_constants():
    """Same structure, different embedded constants: no unsound reuse."""
    def two_eps(x):
        a = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-2)
        b = a * jax.lax.rsqrt(jnp.mean(a * a, -1, keepdims=True) + 1e-6)
        return b

    x = rng.standard_normal((8, 64)).astype(np.float32)
    sf = StitchedFunction(two_eps)
    y = np.asarray(sf(x))
    ref = np.asarray(two_eps(jnp.asarray(x)))
    np.testing.assert_allclose(y, ref, rtol=1e-5, atol=1e-6)


# -- input donation -----------------------------------------------------------
@pytest.mark.filterwarnings("ignore:Some donated buffers were not usable")
def test_donate_marks_nonoutput_inputs_and_stays_correct():
    args = _deep_args()
    sf = StitchedFunction(_deep, donate=True)
    compiled = sf.compiled(*args)
    assert compiled.donate_argnums == (0, 1, 2)
    y = np.asarray(sf(*args))
    ref = np.asarray(_deep(*[jnp.asarray(a) for a in args]))
    np.testing.assert_allclose(y, ref, rtol=1e-4, atol=1e-4)

    # passthrough outputs must never be donated
    def passthrough(x, g):
        return x, x * g
    x = rng.standard_normal((4, 32)).astype(np.float32)
    g = np.ones(32, np.float32)
    sfp = StitchedFunction(passthrough, donate=True)
    cp = sfp.compiled(x, g)
    assert 0 not in cp.donate_argnums and 1 in cp.donate_argnums

    # default: nothing is donated
    assert StitchedFunction(_deep).compiled(*args).donate_argnums == ()


def _crossed(x):
    a1 = x + 1.0
    b2 = x * 2.0
    b1 = a1 * 3.0
    a2 = b2 - 1.0
    return b1, a2


def test_break_cycles_splits_convex_groups_that_feed_each_other():
    """A = {a1, a2} and B = {b1, b2} are each convex, but A's a2 reads
    B's b2 while B's b1 reads A's a1: neither kernel can run first."""
    from repro.core.stitcher import break_cycles

    G = trace(_crossed, np.ones((8, 128), np.float32))
    by_prim = {}
    for nid in G.topo_order():
        by_prim.setdefault(G.node(nid).prim, []).append(nid)
    (a1,), (a2,) = by_prim["add"], by_prim["sub"]
    b2, b1 = by_prim["mul"]
    A, B = frozenset({a1, a2}), frozenset({b1, b2})
    assert G.is_convex(A) and G.is_convex(B)
    assert sorted(G.group_cycle([A, B])) == [0, 1]

    ctx = CostContext(G, V5E)
    legal = break_cycles(G, [StitchGroup((A,)), StitchGroup((B,))], ctx)
    assert G.group_cycle([g.members for g in legal]) is None
    # the widest group dissolves into its (single-node) components
    assert [g.members for g in legal] == [B]
    assert ctx.caps["cycle_split"] == 1
    # a partition without a cycle is left as it is
    assert break_cycles(G, legal, ctx) == legal
