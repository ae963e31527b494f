"""ISSUE-3 tests: beam-search stitch partitioning (quality, determinism,
struct-keyed segment reuse), batched group-level measured autotune
(serial equivalence), tuned group schedules round-tripping the plan
cache, donation aliasing into the first schedule item's kernel, and
explicit VMEM scratch staging."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (CostContext, Hardware, StitchedFunction, make_plan,
                        search_groups, trace)
from repro.core import autotune as autotune_mod
from repro.core.autotune import tune_group, tune_pattern
from repro.core.ir import FusionPlan, Pattern
from repro.core.plan_cache import FORMAT_VERSION, PlanCache
from repro.core.stitcher import DEFAULT_BEAM_WIDTH, beam_width_from_env

rng = np.random.default_rng(29)


def _ln(x, g, b):
    m = jnp.mean(x, axis=-1, keepdims=True)
    v = jnp.mean((x - m) ** 2, axis=-1, keepdims=True)
    return (x - m) * jax.lax.rsqrt(v + 1e-5) * g + b


def _deep(x, g, b):
    for _ in range(8):
        x = _ln(x, g, b)
        x = jax.nn.gelu(x, approximate=True) + x
    return x


def _deep_args(R=64, C=512):
    return (rng.standard_normal((R, C)).astype(np.float32),
            (np.abs(rng.standard_normal(C)) + 0.5).astype(np.float32),
            rng.standard_normal(C).astype(np.float32))


def _waist(x, g, b):
    """Row stats -> wide waist -> combine: greedy's blind spot (the A+B
    union is VMEM-infeasible until the combine stage shrinks its IO)."""
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
    return graph, plan, Hardware(vmem_bytes=768 * 1024)


def _partition_gain(ctx, groups) -> float:
    total = 0.0
    for grp in groups:
        if grp.stitched:
            total += ctx.stitch_gain(tuple(grp.parts)).latency_gain_s
    return total


# -- beam-search partition quality --------------------------------------------
def test_beam_never_worse_than_greedy():
    cases = []
    args = _deep_args()
    graph = trace(_deep, *args)
    cases.append((graph, make_plan(graph), None))
    cases.append(_waist_case())
    for graph, plan, hw in cases:
        ctx = CostContext(graph, hw)
        g1, s1 = search_groups(graph, plan, hw or ctx.hw, ctx=ctx,
                               beam_width=1)
        for width in (2, 4, 8):
            gw, sw = search_groups(graph, plan, hw or ctx.hw, ctx=ctx,
                                   beam_width=width)
            assert sw.gain_s >= s1.gain_s - 1e-15
            assert _partition_gain(ctx, gw) >= _partition_gain(ctx, g1) \
                - 1e-15


def test_beam_strictly_beats_greedy_on_waist():
    """Greedy refuses the infeasible A+B intermediate and never reaches
    the full merge; the beam holds it and wins strictly."""
    graph, plan, hw = _waist_case()
    ctx = CostContext(graph, hw)
    greedy, s1 = search_groups(graph, plan, hw, ctx=ctx, beam_width=1)
    beam, s4 = search_groups(graph, plan, hw, ctx=ctx, beam_width=4)
    assert s4.gain_s > s1.gain_s + 1e-12
    assert len(beam) < len(greedy)          # the full merge happened
    assert s4.beam_width == 4 and s4.states_explored > 0
    # both partitions cover exactly the plan's pattern members (plus any
    # absorbed leftovers), each pattern exactly once
    covered = [n for grp in beam for p in grp.parts for n in p]
    assert len(covered) == len(set(covered))
    plan_members = {n for p in plan.patterns for n in p.members}
    assert plan_members <= set(covered)


def test_beam_deterministic_across_runs():
    graph, plan, hw = _waist_case()
    runs = []
    for _ in range(2):  # fresh context: no shared memoization between runs
        ctx = CostContext(graph, hw)
        groups, stats = search_groups(graph, plan, hw, ctx=ctx,
                                      beam_width=4)
        runs.append(([tuple(sorted(p) for p in grp.parts)
                      for grp in groups],
                     stats.gain_s, stats.states_explored))
    assert runs[0] == runs[1]

    args = _deep_args()
    graph2 = trace(_deep, *args)
    plans = [make_plan(graph2, ctx=CostContext(graph2)) for _ in range(2)]
    parts = []
    for plan2 in plans:
        groups, _ = search_groups(graph2, plan2,
                                  ctx=CostContext(graph2), beam_width=4)
        parts.append([tuple(sorted(p) for p in grp.parts)
                      for grp in groups])
    assert parts[0] == parts[1]


def test_beam_width_env_knob(monkeypatch):
    monkeypatch.delenv("REPRO_STITCH_BEAM", raising=False)
    assert beam_width_from_env() == DEFAULT_BEAM_WIDTH
    monkeypatch.setenv("REPRO_STITCH_BEAM", "7")
    assert beam_width_from_env() == 7
    monkeypatch.setenv("REPRO_STITCH_BEAM", "0")
    assert beam_width_from_env() == 1          # clamped to greedy
    monkeypatch.setenv("REPRO_STITCH_BEAM", "bogus")
    assert beam_width_from_env() == DEFAULT_BEAM_WIDTH


def test_isomorphic_segments_replay_partition():
    """Repeated blocks separated by opaque matmuls: later isomorphic
    segments replay the first one's searched partition."""
    C = 256
    w = (np.eye(C) * 0.9).astype(np.float32)

    def block(x, g, b):
        for _ in range(5):
            x = _ln(x, g, b)
            x = jax.nn.gelu(x, approximate=True) + x
        return x

    def stack(x, g, b):
        for _ in range(6):
            x = block(x, g, b) @ w
        return x

    args = _deep_args(16, C)
    graph = trace(stack, *args)
    ctx = CostContext(graph)
    plan = make_plan(graph, ctx=ctx)
    groups, stats = search_groups(graph, plan, ctx=ctx, beam_width=4)
    assert stats.segments >= 6
    assert stats.segments_reused >= 1       # middle blocks replayed
    assert sum(1 for g in groups if g.stitched) >= 6


def test_report_carries_beam_fields():
    args = _deep_args()
    rep = StitchedFunction(_deep).report(*args)
    assert rep.beam_width == DEFAULT_BEAM_WIDTH
    assert rep.beam_states_explored > 0


# -- batched vs serial autotune ----------------------------------------------
def _fake_timer(scores):
    """Deterministic _time_callable stand-in keyed on the candidate."""
    def timer(fn, args, *, warmup=1, iters=3, key=None):
        assert key is not None
        return scores.get(dict(key).get("schedule"), 99.0) \
            + dict(key).get("block_rows", 0) * 1e-3
    return timer


def test_batched_and_serial_sweeps_agree(monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE", "force")
    args = _deep_args()
    graph = trace(_deep, *args)
    ctx = CostContext(graph)
    plan = make_plan(graph, ctx=ctx)
    groups, _ = search_groups(graph, plan, ctx=ctx)
    grp = max(groups, key=len)
    assert grp.stitched
    # deterministic timing: onepass beats streaming, small blocks win
    monkeypatch.setattr(autotune_mod, "_time_callable",
                        _fake_timer({"onepass": 1.0, "streaming": 2.0}))
    over_b = tune_group(graph, grp.parts, ctx=ctx, batch_compile=True)
    over_s = tune_group(graph, grp.parts, ctx=ctx, batch_compile=False)
    assert over_b == over_s
    assert over_b is not None and over_b["schedule"] == "onepass"
    # flipped preference: both paths must follow
    monkeypatch.setattr(autotune_mod, "_time_callable",
                        _fake_timer({"onepass": 2.0, "streaming": 1.0}))
    over_b2 = tune_group(graph, grp.parts, ctx=ctx, batch_compile=True)
    over_s2 = tune_group(graph, grp.parts, ctx=ctx, batch_compile=False)
    assert over_b2 == over_s2
    assert over_b2["schedule"] == "streaming"
    # pattern-level sweep agrees across paths too
    pat = plan.patterns[0].members
    assert tune_pattern(graph, pat, ctx=ctx, batch_compile=True) \
        == tune_pattern(graph, pat, ctx=ctx, batch_compile=False)


def test_group_tune_measures_real_kernels():
    """Unmocked batched sweep returns a candidate that actually emits."""
    args = _deep_args(16, 256)
    graph = trace(_deep, *args)
    ctx = CostContext(graph)
    plan = make_plan(graph, ctx=ctx)
    groups, _ = search_groups(graph, plan, ctx=ctx)
    grp = max(groups, key=len)
    over = tune_group(graph, grp.parts, ctx=ctx, batch_compile=True)
    assert over is not None
    assert over["schedule"] in ("onepass", "streaming")
    assert over.get("block_rows", 0) > 0


# -- plan cache: tuned group schedules ----------------------------------------
def test_tuned_group_schedule_roundtrips_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE", "force")
    args = _deep_args()
    sf1 = StitchedFunction(_deep, autotune=True, plan_cache=str(tmp_path))
    rep1 = sf1.report(*args)
    assert rep1.autotuned and rep1.group_tuned >= 1

    entry = PlanCache(str(tmp_path)).load(rep1.signature)
    assert entry is not None and entry["format"] == FORMAT_VERSION
    tuned_recs = [r for r in entry["groups"] if r.get("tuned")]
    assert tuned_recs and all(
        r["schedule"] in ("onepass", "streaming") for r in tuned_recs)

    # second process: the measured pin is trusted, not re-measured
    calls = []
    real = autotune_mod.tune_group

    def counting(*a, **k):
        calls.append(a)
        return real(*a, **k)

    monkeypatch.setattr(autotune_mod, "tune_group", counting)
    sf2 = StitchedFunction(_deep, autotune=True, plan_cache=str(tmp_path))
    rep2 = sf2.report(*args)
    assert rep2.plan_cache_hit and rep2.group_tuned >= 1
    assert not calls                       # no re-measurement happened
    np.testing.assert_allclose(np.asarray(sf2(*args)),
                               np.asarray(sf1(*args)),
                               rtol=1e-6, atol=1e-6)


# -- donation aliasing + explicit scratch staging ------------------------------
@pytest.mark.filterwarnings("ignore:Some donated buffers were not usable")
def test_first_kernel_aliases_donated_inputs():
    args = _deep_args()
    sf = StitchedFunction(_deep, donate=True)
    compiled = sf.compiled(*args)
    kernels = [em for kind, em in compiled.schedule if kind == "pattern"]
    assert kernels[0].io_aliases          # x donated into the output
    assert set(kernels[0].io_aliases.values()) <= set(
        range(len(kernels[0].out_ids)))
    y = np.asarray(sf(*args))
    ref = np.asarray(_deep(*(jnp.asarray(a) for a in args)))
    np.testing.assert_allclose(y, ref, rtol=1e-4, atol=1e-4)

    # without donate=, no kernel-level aliasing either
    base = StitchedFunction(_deep).compiled(*args)
    assert all(not em.io_aliases
               for kind, em in base.schedule if kind == "pattern")

    # an input that is also consumed by a later schedule item (here: a
    # graph output passthrough) must not be aliased into the kernel
    def passthrough(x, g):
        return x, jnp.tanh(x * g) + x
    x = rng.standard_normal((8, 128)).astype(np.float32)
    g = np.ones(128, np.float32)
    cp = StitchedFunction(passthrough, donate=True).compiled(x, g)
    for kind, em in cp.schedule:
        if kind == "pattern" and em.io_aliases:
            xpos = [i for i, e in enumerate(em.ext_ids) if e == 0]
            assert not xpos or xpos[0] not in em.io_aliases


def test_group_emission_uses_explicit_scratch():
    args = _deep_args()
    sf = StitchedFunction(_deep)
    compiled = sf.compiled(*args)
    kernels = [em for kind, em in compiled.schedule if kind == "pattern"]
    stitched = [em for em in kernels if len(em.parts) > 1]
    assert stitched and any(em.staged_slots > 0 for em in stitched)
    y = np.asarray(sf(*args))
    ref = np.asarray(_deep(*(jnp.asarray(a) for a in args)))
    np.testing.assert_allclose(y, ref, rtol=1e-4, atol=1e-4)
