"""ISSUE-4 tests: measured top-k partition tuning.

Covers: top-k partition distinctness/ranking from ``search_groups``,
the ``REPRO_STITCH_TOPK`` knob, batched-vs-serial ``tune_partitions``
equivalence via the ``_time_callable`` seam, the end-to-end
measured-vs-model disagreement path (a stubbed timer forces a runner-up
partition to win on "silicon"), the plan-cache round-trip (measured
partitions replay without re-measuring), ``partition_source``
reporting, COL-role interface outputs exposed by candidate boundaries,
the deterministic beam tie-break, the timer synchronization fix, and
the plan-cache eviction grace window.
"""
import os
import random
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (CostContext, Hardware, StitchedFunction, make_plan,
                        search_groups, trace)
from repro.core import autotune as autotune_mod
from repro.core import stitch as stitch_mod
from repro.core.autotune import tune_partitions
from repro.core.ir import FusionPlan, Pattern
from repro.core.plan_cache import FORMAT_VERSION, PlanCache, \
    entry_partition_source
from repro.core.stitcher import (DEFAULT_TOPK, TopKResult, _state_rank_key,
                                 _State, topk_from_env)

rng = np.random.default_rng(41)


def _ln(x, g, b):
    m = jnp.mean(x, axis=-1, keepdims=True)
    v = jnp.mean((x - m) ** 2, axis=-1, keepdims=True)
    return (x - m) * jax.lax.rsqrt(v + 1e-5) * g + b


def _deep(x, g, b):
    for _ in range(8):
        x = _ln(x, g, b)
        x = jax.nn.gelu(x, approximate=True) + x
    return x


def _deep_args(R=16, C=256):
    return (rng.standard_normal((R, C)).astype(np.float32),
            (np.abs(rng.standard_normal(C)) + 0.5).astype(np.float32),
            rng.standard_normal(C).astype(np.float32))


def _waist(x, g, b):
    t = x * g + b
    s = jnp.mean(jnp.tanh(t), -1, keepdims=True)
    s2 = jnp.mean(t * t, -1, keepdims=True)
    r = jax.lax.rsqrt(s2 + 1e-5) * (s + 1.0)
    u = jnp.tanh(x * r)
    v = jax.nn.gelu(x + r, approximate=True)
    w_ = jnp.exp(x * 0.1) * r
    c = u * v + w_
    c = c + u * w_
    return c * 0.5 + jnp.tanh(c)


def _waist_case():
    R, C = 512, 2048
    x = rng.standard_normal((R, C)).astype(np.float32)
    g = (np.abs(rng.standard_normal(C)) + 0.5).astype(np.float32)
    b = rng.standard_normal(C).astype(np.float32)
    graph = trace(_waist, x, g, b)
    fus = sorted(graph.fusible_nodes())
    stats = [n for n in fus
             if graph.node(n).spec.shape[0] == R
             and (len(graph.node(n).spec.shape) == 1
                  or graph.node(n).spec.shape[-1] == 1)]
    a_end = max(stats)
    tail = [n for n in fus if n > a_end]
    b_end = tail[2 * len(tail) // 3 - 1]
    plan = FusionPlan([Pattern(frozenset(s), 0.0) for s in (
        [n for n in fus if n <= a_end],
        [n for n in fus if a_end < n <= b_end],
        [n for n in fus if n > b_end]) if s])
    return graph, plan, Hardware(vmem_bytes=160 * 1024)


def _partition_fp(groups):
    return tuple(tuple(tuple(sorted(p)) for p in g.parts) for g in groups)


# -- top-k partition retention ------------------------------------------------
def test_topk_partitions_distinct_and_ranked():
    for graph, plan, hw in (_waist_case(),
                            (lambda a: (trace(_deep, *a),
                                        None, None))(_deep_args())):
        ctx = CostContext(graph, hw)
        if plan is None:
            plan = make_plan(graph, ctx=ctx)
        res = search_groups(graph, plan, hw or ctx.hw, ctx=ctx, topk=3)
        assert isinstance(res, TopKResult)
        assert res.stats.topk == 3
        assert 2 <= len(res.candidates) <= 3
        assert res.stats.candidates == len(res.candidates)
        # distinct partitions, each covering every plan pattern once
        fps = [_partition_fp(c.groups) for c in res.candidates]
        assert len(set(fps)) == len(fps)
        plan_members = {n for p in plan.patterns for n in p.members}
        for cand in res.candidates:
            covered = [n for grp in cand.groups for p in grp.parts for n in p]
            assert len(covered) == len(set(covered))
            assert plan_members <= set(covered)
        # ranked: the winner's modeled gain dominates every runner-up
        gains = [c.gain_s for c in res.candidates]
        assert all(gains[0] >= g - 1e-15 for g in gains[1:])
        # back-compat unpacking still yields (winner groups, stats)
        groups, stats = search_groups(graph, plan, hw or ctx.hw, ctx=ctx,
                                      topk=3)
        assert _partition_fp(groups) == fps[0]
        assert stats.beam_width == res.stats.beam_width


def test_topk_one_keeps_winner_only():
    graph, plan, hw = _waist_case()
    ctx = CostContext(graph, hw)
    res = search_groups(graph, plan, hw, ctx=ctx, topk=1)
    assert len(res.candidates) == 1
    full = search_groups(graph, plan, hw, ctx=ctx, topk=3)
    assert _partition_fp(res.groups) == _partition_fp(full.groups)


def test_topk_env_knob(monkeypatch):
    monkeypatch.delenv("REPRO_STITCH_TOPK", raising=False)
    assert topk_from_env() == DEFAULT_TOPK
    monkeypatch.setenv("REPRO_STITCH_TOPK", "5")
    assert topk_from_env() == 5
    monkeypatch.setenv("REPRO_STITCH_TOPK", "0")
    assert topk_from_env() == 1            # clamped to winner-only
    monkeypatch.setenv("REPRO_STITCH_TOPK", "bogus")
    assert topk_from_env() == DEFAULT_TOPK


# -- tune_partitions: batched vs serial, forced disagreement -------------------
def _force_partition_timer(want: int):
    """Deterministic ``_time_callable`` stand-in: partition branches of
    candidate ``want`` measure fast, everything else slow; group/pattern
    sweep keys (plain override tuples) get a deterministic constant."""
    def timer(fn, args, *, warmup=1, iters=3, key=None):
        assert key is not None
        if isinstance(key, tuple) and key and key[0] == "partition":
            return 0.001 if key[1] == want else 1.0
        return 1.0 + dict(key).get("block_rows", 0) * 1e-3
    return timer


def test_tune_partitions_batched_and_serial_agree(monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE", "force")
    args = _deep_args()
    graph = trace(_deep, *args)
    ctx = CostContext(graph)
    plan = make_plan(graph, ctx=ctx)
    res = search_groups(graph, plan, ctx=ctx)
    assert len(res.candidates) >= 2
    cands = [c.groups for c in res.candidates]
    for want in (0, 1):
        monkeypatch.setattr(autotune_mod, "_time_callable",
                            _force_partition_timer(want))
        out_b = tune_partitions(graph, cands, ctx=ctx, batch_compile=True)
        out_s = tune_partitions(graph, cands, ctx=ctx, batch_compile=False)
        assert out_b is not None and out_s is not None
        assert out_b.index == out_s.index == want
        assert out_b.overrides == out_s.overrides
        assert out_b.branches == out_s.branches >= len(cands)
        assert out_b.measured_s[want] <= min(
            t for i, t in enumerate(out_b.measured_s) if i != want)


def test_measured_partition_disagreement_end_to_end(monkeypatch, tmp_path):
    """Silicon (a stubbed timer) prefers a runner-up partition: stitch.py
    must commit it, mark the report measured, and persist a v4 entry
    that replays without re-measuring."""
    monkeypatch.setenv("REPRO_AUTOTUNE", "force")
    monkeypatch.setattr(autotune_mod, "_time_callable",
                        _force_partition_timer(1))
    args = _deep_args()
    sf1 = StitchedFunction(_deep, autotune=True, plan_cache=str(tmp_path))
    rep1 = sf1.report(*args)
    assert rep1.partition_source == "measured"
    assert rep1.partition_candidates >= 2
    assert rep1.partition_index == 1       # silicon disagreed with the model
    y = np.asarray(sf1(*args))
    ref = np.asarray(_deep(*(jnp.asarray(a) for a in args)))
    np.testing.assert_allclose(y, ref, rtol=1e-4, atol=1e-4)

    entry = PlanCache(str(tmp_path)).load(rep1.signature)
    assert entry["format"] == FORMAT_VERSION
    assert entry["partition_source"] == "measured"
    assert entry_partition_source(entry) == "measured"

    # second process: the measured partition is replayed, not re-raced
    calls = []
    monkeypatch.setattr(
        stitch_mod, "search_groups",
        lambda *a, **k: calls.append("search") or search_groups(*a, **k))
    monkeypatch.setattr(
        autotune_mod, "tune_partitions",
        lambda *a, **k: calls.append("tune") or tune_partitions(*a, **k))
    sf2 = StitchedFunction(_deep, autotune=True, plan_cache=str(tmp_path))
    rep2 = sf2.report(*args)
    assert rep2.plan_cache_hit
    assert rep2.partition_source == "measured"
    assert not calls                       # neither re-searched nor re-raced
    assert rep2.groups == rep1.groups      # same committed partition
    np.testing.assert_allclose(np.asarray(sf2(*args)), y,
                               rtol=1e-6, atol=1e-6)


def test_partition_source_model_without_autotune():
    args = _deep_args()
    rep = StitchedFunction(_deep).report(*args)
    assert rep.partition_source == "model"
    assert rep.partition_candidates >= 1
    assert rep.partition_index == 0


# -- COL-role interface outputs (exposed by candidate boundaries) -------------
def test_col_role_output_emits_correctly():
    """A partition boundary can turn a (1, C) per-column value into a
    kernel output; both Pallas wrappers must slice one copy back out
    instead of reshaping R broadcast copies."""
    def fn(x, g):
        c = jnp.exp(g) * 0.5 + 1.0
        return x * c, c

    x = rng.standard_normal((8, 128)).astype(np.float32)
    g = rng.standard_normal(128).astype(np.float32)
    ref_y, ref_c = fn(jnp.asarray(x), jnp.asarray(g))
    sf = StitchedFunction(fn)
    y, c = sf(x, g)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref_y),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(c), np.asarray(ref_c),
                               rtol=1e-6, atol=1e-6)

    # streaming wrapper path too (forced via override)
    from repro.core.codegen import emit_pattern
    graph = trace(fn, x, g)
    ctx = CostContext(graph)
    pattern = frozenset(graph.fusible_nodes())
    em = emit_pattern(graph, pattern, ctx=ctx,
                      schedule_override={"schedule": "streaming",
                                         "block_rows": 4, "block_cols": 64})
    if em.estimate.schedule == "streaming":
        outs = em.fn(jnp.asarray(x), jnp.asarray(g))
        by_id = dict(zip(em.out_ids, outs))
        for o, val in by_id.items():
            ref = {tuple(np.asarray(ref_y).shape): ref_y,
                   tuple(np.asarray(ref_c).shape): ref_c}[
                       tuple(graph.node(o).spec.shape)]
            np.testing.assert_allclose(np.asarray(val), np.asarray(ref),
                                       rtol=1e-5, atol=1e-5)


# -- deterministic beam tie-break ---------------------------------------------
def test_beam_winner_invariant_under_pattern_order():
    graph, plan, hw = _waist_case()
    base = None
    for seed in range(3):
        pats = list(plan.patterns)
        random.Random(seed).shuffle(pats)
        ctx = CostContext(graph, hw)
        res = search_groups(graph, FusionPlan(pats), hw, ctx=ctx,
                            beam_width=4)
        got = (_partition_fp(res.groups), res.stats.gain_s)
        if base is None:
            base = got
        else:
            assert got == base


def test_state_rank_key_breaks_equal_gain_ties_by_shape():
    p1, p2, p3 = frozenset({1}), frozenset({2}), frozenset({3})
    merged = _State(((p1, p2), (p3,)), (), frozenset(), 1.0, 0.0)
    split = _State(((p1,), (p2,), (p3,)), (), frozenset(), 1.0, 0.0)
    for perm in ((merged, split), (split, merged)):
        ranked = sorted(perm, key=_state_rank_key)
        assert ranked[0] is split          # shape (1,1,1) < (2,1)
    # gain still dominates the shape tie-break
    better = _State(((p1, p2), (p3,)), (), frozenset(), 2.0, 0.0)
    assert sorted((split, better), key=_state_rank_key)[0] is better


# -- _time_callable synchronization -------------------------------------------
class _Leaf:
    def __init__(self):
        self.blocked = 0

    def block_until_ready(self):
        self.blocked += 1


def test_time_callable_blocks_every_output_and_respects_warmup():
    l1, l2 = _Leaf(), _Leaf()
    calls = []

    def fn():
        calls.append(1)
        return (l1, [l2])                  # nested outputs: both must sync

    t = autotune_mod._time_callable(fn, (), warmup=2, iters=3, key=("k",))
    assert t >= 0.0
    assert len(calls) == 5                 # warmup + iters, all executed
    assert l1.blocked == 5 and l2.blocked == 5


# -- plan-cache eviction grace window -----------------------------------------
def test_evict_grace_protects_concurrent_stores(tmp_path):
    root = str(tmp_path)
    a = PlanCache(root, max_entries=2, evict_grace_s=60.0)
    old = time.time() - 3600
    for name in ("aaa", "bbb", "ccc"):
        a.store(name, {"format": 2, "signature": name, "patterns": []})
        os.utime(os.path.join(root, f"{name}.json"), (old, old))
    # a second process stores while the first is about to evict: its
    # fresh entry must survive even when the cache is over capacity
    b = PlanCache(root, max_entries=2, evict_grace_s=60.0)
    b.store("fresh", {"format": 2, "signature": "fresh", "patterns": []})
    assert b.load("fresh") is not None     # never the eviction victim
    a.store("ggg", {"format": 2, "signature": "ggg", "patterns": []})
    assert a.load("fresh") is not None and a.load("ggg") is not None
    assert a.load("aaa") is None and a.load("bbb") is None  # aged out
    # every remaining entry inside the grace window: eviction backs off
    # entirely, even far over capacity -- count shrinks on a later store
    c = PlanCache(root, max_entries=1, evict_grace_s=60.0)
    c.store("hhh", {"format": 2, "signature": "hhh", "patterns": []})
    for name in ("fresh", "ggg", "hhh"):
        assert c.load(name) is not None
