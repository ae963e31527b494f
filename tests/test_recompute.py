"""Recompute-aware stitching (ISSUE 5): the thread-composition scheme.

Covers the per-value stage-vs-recompute decision pass
(``memory_planner.plan_reuse`` / ``cost_model.recompute_cost``), the
emitter honoring it (numerics vs the ``dispatch="interpret"`` oracle in
fp32 and bf16), the illegal-across-reduce-level guard, the plan-cache
round-trip of recompute pins, the autotuned
stage-vs-recompute race branches, the report fields, the amortized
single-dispatch screening pass, multi-segment swap candidates and the
no-silent-caps / cache-counter observability satellites.
"""
from __future__ import annotations

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.core import (CostContext, Hardware, PlanCache, StitchedFunction,
                        best_estimate, trace)  # noqa: E402
from repro.core import autotune as autotune_mod  # noqa: E402
from repro.core.cost_model import (estimate_onepass, estimate_streaming,
                                   reuse_plan)  # noqa: E402
from repro.core.codegen import _emit_packed, emit_pattern  # noqa: E402
from repro.core.ir import FusionPlan, Pattern  # noqa: E402
from repro.core.memory_planner import plan_scratch  # noqa: E402
from repro.core.plan_cache import (FORMAT_VERSION,
                                   _sanitize_override)  # noqa: E402
from repro.core.stitcher import search_groups  # noqa: E402

rng = np.random.default_rng(7)

#: VMEM budget at which the wide fan-out chain below cannot stage every
#: live FULL intermediate even at the smallest legal row block (one
#: 8-row f32 tile), but fits under recompute.
TIGHT_VMEM = 256 * 1024


def _fanout(x, g):
    """Six tanh branches all live across two combine sweeps: peak VMEM
    liveness ~9 FULL rows, far beyond ``TIGHT_VMEM`` when staged."""
    t = x * g + 1.0
    us = [jnp.tanh(t * (0.1 * (i + 1))) for i in range(6)]
    acc = x
    for u in us:
        acc = acc + u
    for u in us:
        acc = acc * (u + 0.5)
    s = jnp.mean(acc, axis=-1, keepdims=True)
    return acc * s


def _fanout_args(R=64, C=512, dtype=np.float32):
    x = rng.standard_normal((R, C)).astype(dtype)
    g = (np.abs(rng.standard_normal(C)) + 0.5).astype(dtype)
    return x, g


def _layernorm(x, g, b):
    t = x * g + b
    m = jnp.mean(t, axis=-1, keepdims=True)
    v = jnp.mean((t - m) ** 2, axis=-1, keepdims=True)
    return (t - m) * jax.lax.rsqrt(v + 1e-5)


def _tight_hw() -> Hardware:
    return Hardware(vmem_bytes=TIGHT_VMEM)


# ---------------------------------------------------------------------------
# decision pass + cost model
# ---------------------------------------------------------------------------
def test_recompute_rescues_vmem_infeasible_onepass():
    x, g = _fanout_args()
    graph = trace(_fanout, x, g)
    pat = frozenset(graph.fusible_nodes())
    hw = _tight_hw()
    ctx = CostContext(graph, hw)
    info = ctx.info(pat)
    staged = estimate_onepass(graph, pat, info, 1, hw, ctx=ctx)
    assert not staged.feasible, "scenario must be staging-infeasible"
    best = best_estimate(graph, pat, hw, ctx=ctx)
    assert best.schedule == "onepass" and best.recompute_ids
    assert best.feasible
    # the recompute estimate stages less and computes more
    rec = estimate_onepass(graph, pat, info, best.block_rows, hw, ctx=ctx,
                           recompute=frozenset(best.recompute_ids))
    assert rec.scratch_bytes < staged.scratch_bytes
    assert rec.vpu_ops > staged.vpu_ops


def test_illegal_across_reduce_level_guard():
    """Values at or downstream of a reduce must stay staged."""
    R, C = 32, 256
    x = rng.standard_normal((R, C)).astype(np.float32)
    g = (np.abs(rng.standard_normal(C)) + 0.5).astype(np.float32)
    b = rng.standard_normal(C).astype(np.float32)
    graph = trace(_layernorm, x, g, b)
    pat = frozenset(graph.fusible_nodes())
    ctx = CostContext(graph, Hardware())
    from repro.core.ir import OpKind

    desc, anc = graph.reachability()
    reduce_mask = sum(1 << n for n in pat
                      if graph.node(n).kind is OpKind.REDUCE)
    outs = set(graph.pattern_outputs(pat))
    for nid in sorted(pat):
        rc = ctx.recompute_cost(pat, nid)
        node = graph.node(nid)
        crosses = bool(((anc[nid] | (1 << nid)) & reduce_mask))
        if node.kind is OpKind.REDUCE or crosses or nid in outs:
            assert not rc.legal, f"%{nid} {node.prim} must be illegal"
        elif any(c in pat for c in graph.consumers(nid)):
            assert rc.legal, f"%{nid} {node.prim} must be legal"
    # and the decision pass never flips an illegal value
    for br in (1, 8):
        rp = reuse_plan(graph, pat, ctx.info(pat), br,
                        Hardware(vmem_bytes=8 * 1024), ctx=ctx)
        if rp is None:
            continue
        for nid in rp.recompute:
            assert ctx.recompute_cost(pat, nid).legal


def test_plan_scratch_extends_liveness_of_recompute_cone_inputs():
    """A staged value read by a recomputed consumer stays live until the
    consumer's evaluation sites, not its definition site."""
    x, g = _fanout_args()
    graph = trace(_fanout, x, g)
    pat = frozenset(graph.fusible_nodes())
    ctx = CostContext(graph, _tight_hw())
    info = ctx.info(pat)
    base = plan_scratch(graph, pat, info)
    # flipping ONE tanh branch alone frees nothing: its cone input (the
    # shared affine t) now lives to the flip's late evaluation sites
    tanhs = [n for n in pat if graph.node(n).prim == "tanh"]
    one = plan_scratch(graph, pat, info, recompute=frozenset(tanhs[:1]))
    assert one.total_bytes >= base.total_bytes - 0  # no magic saving
    assert tanhs[0] not in one.slot_of


# ---------------------------------------------------------------------------
# emission: numerics vs the interpret oracle
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype,rtol", [(np.float32, 2e-5),
                                        ("bfloat16", 3e-2)])
def test_recompute_numerics_vs_interpret(dtype, rtol):
    if dtype == "bfloat16":
        x, g = _fanout_args(dtype=np.float32)
        x = jnp.asarray(x, jnp.bfloat16)
        g = jnp.asarray(g, jnp.bfloat16)
        # bf16 halves the staged rows but doubles the row tile (16)
        hw = Hardware(vmem_bytes=320 * 1024)
    else:
        x, g = _fanout_args(dtype=dtype)
        hw = _tight_hw()
    sf = StitchedFunction(_fanout, hw=hw)
    rep = sf.report(x, g)
    assert rep.n_recomputed > 0, "scenario must engage recompute"
    assert rep.n_pallas >= 1
    y = sf(x, g)
    oracle = StitchedFunction(_fanout, hw=hw, dispatch="interpret")
    y_ref = oracle(x, g)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32),
                               rtol=rtol, atol=rtol)


def test_recompute_emission_matches_packed_reference():
    x, g = _fanout_args()
    graph = trace(_fanout, x, g)
    pat = frozenset(graph.fusible_nodes())
    hw = _tight_hw()
    ctx = CostContext(graph, hw)
    em = emit_pattern(graph, pat, hw=hw, ctx=ctx)
    assert em.kind == "pallas" and em.n_recomputed > 0
    assert em.recompute_bytes_freed > 0
    args = [jnp.asarray(x), jnp.asarray(g)]
    ref = _emit_packed(graph, pat, em.ext_ids, em.out_ids)(*args)
    out = em.fn(*args)
    for a, b in zip(ref, out):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# report fields + observability satellites
# ---------------------------------------------------------------------------
def test_report_fields_and_cache_counters(tmp_path):
    x, g = _fanout_args()
    hw = _tight_hw()
    sf = StitchedFunction(_fanout, hw=hw, plan_cache=str(tmp_path))
    rep = sf.report(x, g)
    assert rep.n_recomputed > 0
    assert rep.recompute_bytes_freed > 0
    assert isinstance(rep.caps_hit, dict)
    assert rep.plan_cache_misses == 1 and rep.plan_cache_hits == 0
    sf2 = StitchedFunction(_fanout, hw=hw, plan_cache=str(tmp_path))
    rep2 = sf2.report(x, g)
    assert rep2.plan_cache_hit
    assert rep2.plan_cache_hits == 1 and rep2.plan_cache_misses == 0
    assert rep2.n_recomputed == rep.n_recomputed


def test_caps_hit_reports_max_pattern_truncation():
    """A graph long enough to exceed MAX_PATTERN must log the cap."""
    R, C = 8, 128

    def deep(x):
        for i in range(40):
            x = jnp.tanh(x * (1.0 + 0.01 * i)) + x
        return x

    x = rng.standard_normal((R, C)).astype(np.float32)
    sf = StitchedFunction(deep)
    rep = sf.report(x)
    assert any(k.startswith("max_pattern") for k in rep.caps_hit), \
        f"expected a max_pattern cap note, got {rep.caps_hit}"


# ---------------------------------------------------------------------------
# plan-cache: recompute pins round-trip
# ---------------------------------------------------------------------------
def test_v5_roundtrip_and_v4_degrade_upgrade(tmp_path):
    x, g = _fanout_args()
    hw = _tight_hw()
    cache_dir = str(tmp_path)
    sf = StitchedFunction(_fanout, hw=hw, plan_cache=cache_dir)
    rep = sf.report(x, g)
    y = np.asarray(sf(x, g))
    pc = PlanCache(cache_dir)
    entry = pc.load(rep.signature)
    assert entry["format"] == FORMAT_VERSION
    pins = [p for p in entry["patterns"] if p.get("recompute")]
    assert pins and all(isinstance(i, int) for p in pins
                        for i in p["recompute"])

    # replay: the recompute pin is honored without re-deciding
    sf2 = StitchedFunction(_fanout, hw=hw, plan_cache=cache_dir)
    rep2 = sf2.report(x, g)
    assert rep2.plan_cache_hit and rep2.n_recomputed == rep.n_recomputed
    np.testing.assert_allclose(np.asarray(sf2(x, g)), y, rtol=1e-6)


def test_sanitize_override_recompute():
    over = _sanitize_override({"schedule": "onepass", "block_rows": 8,
                               "recompute": [3, 5, 3]})
    assert over["recompute"] == [3, 5]
    # malformed lists are dropped, not fatal
    assert "recompute" not in _sanitize_override(
        {"schedule": "onepass", "recompute": [3, "x"]})
    assert "recompute" not in _sanitize_override(
        {"schedule": "streaming", "recompute": [3]})


# ---------------------------------------------------------------------------
# autotune: stage-vs-recompute race
# ---------------------------------------------------------------------------
class _ForcedStreamingCtx(CostContext):
    """A context whose ``best`` insists on streaming for one union --
    deterministically exercising the swap path where the analytic model
    prefers staging-streaming while a feasible recompute one-pass
    exists."""

    def __init__(self, graph, hw, forced_union):
        super().__init__(graph, hw)
        self._forced = forced_union

    def best(self, pattern):
        if pattern == self._forced:
            info = self.info(pattern)
            return estimate_streaming(self.graph, pattern, info, 8, 512,
                                      self.hw, ctx=self)
        return super().best(pattern)


def test_recompute_swap_override_builds_branch():
    x, g = _fanout_args()
    graph = trace(_fanout, x, g)
    pat = frozenset(graph.fusible_nodes())
    hw = _tight_hw()
    ctx = _ForcedStreamingCtx(graph, hw, pat)
    over = autotune_mod._recompute_swap_override(graph, pat, ctx.info(pat),
                                                 ctx, hw)
    assert over is not None and over["schedule"] == "onepass"
    assert over["recompute"], "the swap must carry the flip set"
    # and the honest context (recompute onepass is already best) yields
    # no redundant swap branch
    honest = CostContext(graph, hw)
    assert autotune_mod._recompute_swap_override(
        graph, pat, honest.info(pat), honest, hw) is None


def test_autotuned_stage_vs_recompute_commit(monkeypatch, tmp_path):
    """End-to-end: the partition race includes the recompute variant and
    the committed, persisted kernel honors the measured winner."""
    monkeypatch.setenv("REPRO_AUTOTUNE", "force")
    x, g = _fanout_args()
    hw = _tight_hw()
    sf = StitchedFunction(_fanout, hw=hw, autotune=True,
                          plan_cache=str(tmp_path))
    rep = sf.report(x, g)
    assert rep.autotuned
    assert rep.n_recomputed > 0, \
        "the committed kernel must still recompute (staging is infeasible)"
    y = np.asarray(sf(x, g))
    oracle = StitchedFunction(_fanout, hw=hw, dispatch="interpret")
    np.testing.assert_allclose(y, np.asarray(oracle(x, g)),
                               rtol=2e-5, atol=2e-5)
    entry = PlanCache(str(tmp_path)).load(rep.signature)
    assert entry["format"] == FORMAT_VERSION
    assert any(p.get("recompute") for p in entry["patterns"])


def test_remap_override_retargets_recompute_ids():
    from repro.core.stitch import _remap_override

    src, dst = [10, 11, 12, 15], [20, 21, 22, 25]
    over = {"schedule": "onepass", "block_rows": 4, "recompute": [11, 15]}
    out = _remap_override(over, src, dst)
    assert out["recompute"] == [21, 25]
    assert out["schedule"] == "onepass" and out["block_rows"] == 4
    assert over["recompute"] == [11, 15]  # source untouched
    # a broken correspondence drops the pin instead of miscompiling
    bad = _remap_override({"schedule": "onepass", "recompute": [99]},
                          src, dst)
    assert "recompute" not in bad


def test_struct_shared_tuned_pins_stay_within_members(monkeypatch, tmp_path):
    """Isomorphic blocks share one measured sweep; each sibling's
    persisted recompute pin must name ITS OWN node ids."""
    monkeypatch.setenv("REPRO_AUTOTUNE", "force")
    rng2 = np.random.default_rng(5)
    R, C = 64, 256
    x = rng2.standard_normal((R, C)).astype(np.float32)
    g = (np.abs(rng2.standard_normal(C)) + 0.5).astype(np.float32)
    w1 = (rng2.standard_normal((C, C)) / np.sqrt(C)).astype(np.float32)
    w2 = (rng2.standard_normal((C, C)) / np.sqrt(C)).astype(np.float32)

    def block(h, g):
        t = h * g + 1.0
        us = [jnp.tanh(t * (0.1 * (i + 1))) for i in range(6)]
        acc = h
        for u in us:
            acc = acc + u
        for u in us:
            acc = acc * (u + 0.5)
        return acc

    def f(x, g, w1, w2):
        h = block(x, g) @ w1
        h = block(h, g) @ w2
        return block(h, g)

    # only the recompute one-pass fits: every block carries a pin
    hw = Hardware(vmem_bytes=56 * 1024)
    sf = StitchedFunction(f, hw=hw, autotune=True, plan_cache=str(tmp_path))
    rep = sf.report(x, g, w1, w2)
    entry = PlanCache(str(tmp_path)).load(rep.signature)
    pinned = 0
    for prec in entry["patterns"]:
        rec = prec.get("recompute")
        if rec:
            pinned += 1
            assert set(rec) <= set(prec["members"]), \
                "a pattern's recompute pin must name its own members"
    for grec in entry.get("groups", []):
        rec = grec.get("recompute")
        if rec:
            members = set()
            for i in grec["parts"]:
                members |= set(entry["patterns"][i]["members"])
            members |= set(grec.get("extra", ()))
            assert set(rec) <= members, \
                "a group's recompute pin must name its own members"
    assert pinned >= 2, "several isomorphic blocks should carry pins"
    # numerics still match the interpret oracle
    y = np.asarray(sf(x, g, w1, w2))
    oracle = StitchedFunction(f, hw=hw, dispatch="interpret")
    np.testing.assert_allclose(y, np.asarray(oracle(x, g, w1, w2)),
                               rtol=5e-4, atol=5e-4)


def test_tuned_pin_on_recompute_only_union_is_honest(monkeypatch):
    """The measured sweep must not persist a staged pin whose kernel
    actually fell back to the recompute variant: on a staging-infeasible
    union every surviving onepass candidate carries its flip set."""
    monkeypatch.setenv("REPRO_AUTOTUNE", "force")
    x, g = _fanout_args()
    graph = trace(_fanout, x, g)
    pat = frozenset(graph.fusible_nodes())
    hw = _tight_hw()
    ctx = CostContext(graph, hw)
    over = autotune_mod.tune_group(graph, (pat,), hw=hw, ctx=ctx)
    assert over is not None
    if over["schedule"] == "onepass":
        assert over.get("recompute"), \
            "a staged onepass pin must not survive on a recompute-only union"
    # sanitized round-trip keeps the flip set
    assert _sanitize_override(dict(over)).get("recompute") \
        == over.get("recompute")


# ---------------------------------------------------------------------------
# amortized screening (single dispatch, per-branch timestamps)
# ---------------------------------------------------------------------------
def test_screen_single_dispatch_times_every_branch():
    def mk(k):
        def fn(a):
            out = a
            for _ in range(k + 1):
                out = jnp.tanh(out)
            return (out,)
        return fn

    fns = [mk(k) for k in range(4)]
    args = (jnp.asarray(rng.standard_normal((64, 64)), jnp.float32),)
    got = autotune_mod._screen_single_dispatch(fns, args, [0, 1, 2, 3])
    assert got is not None and sorted(got) == [0, 1, 2, 3]
    assert all(t >= 0.0 for t in got.values())


def test_measure_switch_branches_uses_amortized_path(monkeypatch):
    seen = []
    orig = autotune_mod._screen_single_dispatch

    def spy(fns, args, reps):
        out = orig(fns, args, reps)
        seen.append((tuple(reps), out is not None))
        return out

    monkeypatch.setattr(autotune_mod, "_screen_single_dispatch", spy)
    fns = [lambda a: (a + 1,), lambda a: (a * 2,), lambda a: (a - 3,)]
    args = (jnp.ones((8, 8), jnp.float32),)
    times = autotune_mod._measure_switch_branches(
        fns, args, [("k", i) for i in range(3)])
    assert times is not None and len(times) == 3
    assert seen == [((0, 1, 2), True)]


def test_amortized_screening_stands_down_for_seam_fakes(monkeypatch):
    """A patched ``_time_callable`` must keep deciding the sweep (the
    deterministic-fake contract tests and benches rely on)."""
    calls = []

    def fake(fn, args, *, warmup=1, iters=3, key=None):
        calls.append(key)
        return {("k", 0): 3e-3, ("k", 1): 1e-3, ("k", 2): 2e-3}[key]

    monkeypatch.setattr(autotune_mod, "_time_callable", fake)
    fns = [lambda a: (a + 1,), lambda a: (a * 2,), lambda a: (a - 3,)]
    args = (jnp.ones((8, 8), jnp.float32),)
    times = autotune_mod._measure_switch_branches(
        fns, args, [("k", i) for i in range(3)])
    assert times is not None
    assert times[1] == min(t for t in times if t is not None)
    assert calls, "the seam fake must have been consulted"


# ---------------------------------------------------------------------------
# multi-segment swap candidates
# ---------------------------------------------------------------------------
def _two_segment_case(R=128, C=1024):
    """Two waist-like subchains separated by an OPAQUE matmul: two
    independent segments, each with runner-up partitions."""
    x = rng.standard_normal((R, C)).astype(np.float32)
    g = (np.abs(rng.standard_normal(C)) + 0.5).astype(np.float32)
    w = rng.standard_normal((C, C)).astype(np.float32) / np.sqrt(C)

    def waist(t, x0):
        s = jnp.mean(jnp.tanh(t), -1, keepdims=True)
        s2 = jnp.mean(t * t, -1, keepdims=True)
        r = jax.lax.rsqrt(s2 + 1e-5) * (s + 1.0)
        u = jnp.tanh(x0 * r)
        v = jax.nn.gelu(x0 + r, approximate=True)
        c = u * v + jnp.exp(x0 * 0.1) * r
        return c * 0.5 + jnp.tanh(c)

    def f(x, g, w):
        a = waist(x * g + 1.0, x)
        h = a @ w  # opaque boundary: separate segments
        return waist(h * g + 0.5, h)

    graph = trace(f, x, g, w)
    fus = sorted(graph.fusible_nodes())
    opaque = [n for n in graph.nodes
              if graph.node(n).prim == "dot_general"]
    assert opaque
    cut = opaque[0]
    segs = ([n for n in fus if n < cut], [n for n in fus if n > cut])
    pats = []
    for seg in segs:
        stats = [n for n in seg
                 if len(graph.node(n).spec.shape) == 1
                 or graph.node(n).spec.shape[-1] == 1]
        a_end = max(stats)
        tail = [n for n in seg if n > a_end]
        b_end = tail[2 * len(tail) // 3 - 1]
        for lo, hi in ((min(seg) - 1, a_end), (a_end, b_end),
                       (b_end, max(seg))):
            members = frozenset(n for n in seg if lo < n <= hi)
            if members:
                pats.append(members)
    return graph, FusionPlan([Pattern(m, 0.0) for m in pats])


def test_multi_segment_pair_swap_candidates():
    graph, plan = _two_segment_case()
    hw = Hardware(vmem_bytes=160 * 1024)
    ctx = CostContext(graph, hw)
    res = search_groups(graph, plan, hw, ctx=ctx, topk=8)
    assert res.stats.segments >= 2
    assert res.stats.pair_swaps >= 1, \
        "two swappable segments must yield a combined 2-swap candidate"
    # every candidate still covers each node at most once
    for cand in res.candidates:
        members = [n for grp in cand.groups for p in grp.parts for n in p]
        assert len(members) == len(set(members))
    # deterministic ranking: best first
    gains = [c.gain_s for c in res.candidates]
    assert gains[0] == max(gains)
