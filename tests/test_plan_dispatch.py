"""ISSUE-1 tests: cost-context equivalence, single-dispatch executables,
and the persistent plan/tuning cache."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import trace
from repro.core.costctx import CostContext, NullContext, PatternBounds
from repro.core.ir import FUSIBLE_KINDS
from repro.core.plan_cache import (FORMAT_VERSION, PlanCache, entry_to_plan,
                                   graph_signature, plan_to_entry)
from repro.core.planner import make_plan
from repro.core.stitch import StitchedFunction, stitched_jit

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
rng = np.random.default_rng(7)


def layernorm(x, g, b):
    m = jnp.mean(x, axis=-1, keepdims=True)
    v = jnp.mean((x - m) ** 2, axis=-1, keepdims=True)
    return (x - m) * jax.lax.rsqrt(v + 1e-5) * g + b


def softmax(x):
    s = x - jnp.max(x, axis=-1, keepdims=True)
    e = jnp.exp(s)
    return e / jnp.sum(e, axis=-1, keepdims=True)


def rmsnorm(x, g):
    v = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(v + 1e-6) * g


def mini_transformer(x, g1, b1, w1, w2):
    h = layernorm(x, g1, b1)
    u = jax.nn.gelu(h @ w1, approximate=True)
    return softmax(x + u @ w2)


def _args(name):
    x = rng.standard_normal((16, 128)).astype(np.float32)
    g = np.abs(rng.standard_normal(128)).astype(np.float32) + 0.5
    b = rng.standard_normal(128).astype(np.float32)
    if name == "layernorm":
        return layernorm, (x, g, b)
    if name == "softmax":
        return softmax, (x,)
    if name == "rmsnorm":
        return rmsnorm, (x, g)
    w1 = (rng.standard_normal((128, 64)) * 0.05).astype(np.float32)
    w2 = (rng.standard_normal((64, 128)) * 0.05).astype(np.float32)
    return mini_transformer, (x, g, b, w1, w2)


# -- cost context vs seed-mode equivalence -----------------------------------
@pytest.mark.parametrize("name", ["layernorm", "softmax", "mini_transformer"])
def test_ctx_and_nullctx_plans_identical(name):
    fn, args = _args(name)
    graph = trace(fn, *args)
    p1 = make_plan(graph, ctx=CostContext(graph))
    p2 = make_plan(graph, ctx=NullContext(graph))
    assert sorted(map(sorted, (p.members for p in p1.patterns))) == \
        sorted(map(sorted, (p.members for p in p2.patterns)))


def test_bitset_convexity_matches_bfs():
    fn, args = _args("mini_transformer")
    graph = trace(fn, *args)
    fusible = graph.fusible_nodes()
    prng = np.random.default_rng(0)
    for _ in range(200):
        k = int(prng.integers(2, 9))
        pat = frozenset(prng.choice(fusible, size=k, replace=False).tolist())
        assert graph.is_convex(pat) == graph.is_convex_bfs(pat)


def test_union_bounds_match_scratch_compute():
    fn, args = _args("mini_transformer")
    graph = trace(fn, *args)
    ctx = CostContext(graph)
    fusible = sorted(graph.fusible_nodes())
    a = frozenset(fusible[:4])
    b = frozenset(fusible[3:8])
    u = ctx.union(a, b)
    got = ctx.bounds(u)
    want = PatternBounds.compute(graph, u, frozenset(graph.outputs))
    assert got == want


# -- single-dispatch executables ---------------------------------------------
@pytest.mark.parametrize("name", ["layernorm", "softmax", "rmsnorm",
                                  "mini_transformer"])
def test_single_dispatch_matches_interpreter(name):
    fn, args = _args(name)
    single = StitchedFunction(fn, dispatch="single")
    interp = StitchedFunction(fn, dispatch="interpret")
    y1 = np.asarray(single(*args))
    y2 = np.asarray(interp(*args))
    ref = np.asarray(fn(*(jnp.asarray(a) for a in args)))
    np.testing.assert_allclose(y1, y2, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(y1, ref, rtol=1e-4, atol=1e-5)


def test_single_dispatch_is_one_python_call():
    fn, args = _args("mini_transformer")
    sf = StitchedFunction(fn, dispatch="single")
    compiled = sf.compiled(*args)
    for _ in range(3):
        sf(*args)
    # the schedule body ran in Python exactly once (at jit trace time)
    assert compiled.exec_count == 1
    # while the seed-style interpreter re-enters Python per call
    si = StitchedFunction(fn, dispatch="interpret")
    ci = si.compiled(*args)
    for _ in range(3):
        si(*args)
    assert ci.exec_count == 3


def test_single_dispatch_composes_under_jit_and_grad():
    fn, args = _args("rmsnorm")
    wrapped = stitched_jit(fn, differentiable=True)
    y = jax.jit(wrapped)(*args)
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(fn(*(jnp.asarray(a) for a in args))),
        rtol=1e-4, atol=1e-5)
    g1 = jax.grad(lambda *a: jnp.sum(wrapped(*a)))(*args)
    g2 = jax.grad(lambda *a: jnp.sum(fn(*a)))(*(jnp.asarray(a)
                                                for a in args))
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=1e-3, atol=1e-4)


# -- persistent plan cache ----------------------------------------------------
def test_graph_signature_structural():
    fn, args = _args("layernorm")
    g1 = trace(fn, *args)
    g2 = trace(fn, *args)
    from repro.core.cost_model import V5E
    assert graph_signature(g1, V5E) == graph_signature(g2, V5E)
    # different shape -> different signature
    x2 = rng.standard_normal((16, 256)).astype(np.float32)
    g3 = trace(fn, x2, np.ones(256, np.float32), np.zeros(256, np.float32))
    assert graph_signature(g1, V5E) != graph_signature(g3, V5E)


def test_plan_cache_roundtrip(tmp_path):
    fn, args = _args("layernorm")
    graph = trace(fn, *args)
    from repro.core.cost_model import V5E
    sig = graph_signature(graph, V5E)
    plan = make_plan(graph)
    schedules = [{"schedule": "onepass", "block_rows": 8}
                 for _ in plan.patterns]
    cache = PlanCache(str(tmp_path))
    cache.store(sig, plan_to_entry(plan, schedules, sig))
    entry = cache.load(sig)
    assert entry is not None and entry["format"] == FORMAT_VERSION
    decoded = entry_to_plan(entry, graph)
    assert decoded is not None
    plan2, overrides = decoded
    assert [sorted(p.members) for p in plan2.patterns] == \
        [sorted(p.members) for p in plan.patterns]
    assert overrides[0]["block_rows"] == 8


def test_graph_signature_covers_remote_fusion_flag():
    fn, args = _args("layernorm")
    graph = trace(fn, *args)
    from repro.core.cost_model import V5E
    assert graph_signature(graph, V5E, remote_fusion=True) != \
        graph_signature(graph, V5E, remote_fusion=False)


def test_plan_cache_roundtrips_streaming_block_cols(tmp_path):
    fn, args = _args("layernorm")
    graph = trace(fn, *args)
    from repro.core.cost_model import V5E
    sig = graph_signature(graph, V5E)
    plan = make_plan(graph)
    schedules = [{"schedule": "streaming", "block_rows": 8,
                  "block_cols": 512} for _ in plan.patterns]
    cache = PlanCache(str(tmp_path))
    cache.store(sig, plan_to_entry(plan, schedules, sig))
    _, overrides = entry_to_plan(cache.load(sig), graph)
    assert overrides[0] == {"schedule": "streaming", "block_rows": 8,
                            "block_cols": 512}


def test_plan_cache_rejects_stale_entry(tmp_path):
    fn, args = _args("layernorm")
    graph = trace(fn, *args)
    entry = {"format": FORMAT_VERSION, "signature": "x",
             "patterns": [{"members": [99999]}]}
    assert entry_to_plan(entry, graph) is None        # unknown node
    entry = {"format": 1, "patterns": []}
    assert entry_to_plan(entry, graph) is None        # another format
    # an older layout is a miss too: only FORMAT_VERSION loads
    entry = {"format": 2, "signature": "x", "patterns": []}
    assert entry_to_plan(entry, graph) is None
    entry = {"format": FORMAT_VERSION, "signature": "x", "patterns": []}
    assert entry_to_plan(entry, graph) is not None


def test_plan_cache_tolerates_malformed_files_and_fields(tmp_path):
    fn, args = _args("layernorm")
    graph = trace(fn, *args)
    from repro.core.cost_model import V5E
    sig = graph_signature(graph, V5E)
    cache = PlanCache(str(tmp_path))
    # valid JSON that is not a dict must be treated as a miss, not crash
    with open(os.path.join(str(tmp_path), f"{sig}.json"), "w") as f:
        f.write("[1, 2]")
    assert cache.load(sig) is None
    # malformed schedule fields degrade to the analytic sweep
    plan = make_plan(graph)
    entry = plan_to_entry(
        plan, [{"schedule": "streaming", "block_rows": "abc",
                "block_cols": None} for _ in plan.patterns], sig)
    decoded = entry_to_plan(entry, graph)
    assert decoded is not None
    assert decoded[1][0] == {"schedule": "streaming"}
    entry = plan_to_entry(
        plan, [{"schedule": "bogus", "block_rows": 8}
               for _ in plan.patterns], sig)
    assert entry_to_plan(entry, graph)[1][0] == {}


def test_in_process_cache_hit_same_signature(tmp_path):
    fn, args = _args("rmsnorm")
    sf1 = StitchedFunction(fn, plan_cache=str(tmp_path))
    rep1 = sf1.report(*args)
    assert not rep1.plan_cache_hit
    # new StitchedFunction, same process: hits the on-disk entry
    sf2 = StitchedFunction(fn, plan_cache=str(tmp_path))
    rep2 = sf2.report(*args)
    assert rep2.plan_cache_hit
    assert rep2.signature == rep1.signature
    assert sorted(map(sorted, rep2.patterns)) == \
        sorted(map(sorted, rep1.patterns))
    np.testing.assert_allclose(np.asarray(sf2(*args)),
                               np.asarray(fn(*(jnp.asarray(a)
                                               for a in args))),
                               rtol=1e-4, atol=1e-5)


_FRESH_PROC = textwrap.dedent("""
    import json, sys
    import numpy as np
    import jax.numpy as jnp
    import jax
    from repro.core import explorer
    from repro.core.stitch import StitchedFunction

    def layernorm(x, g, b):
        m = jnp.mean(x, axis=-1, keepdims=True)
        v = jnp.mean((x - m) ** 2, axis=-1, keepdims=True)
        return (x - m) * jax.lax.rsqrt(v + 1e-5) * g + b

    rng = np.random.default_rng(3)
    x = rng.standard_normal((32, 256)).astype(np.float32)
    g = np.ones(256, np.float32)
    b = np.zeros(256, np.float32)
    sf = StitchedFunction(layernorm, plan_cache=sys.argv[1])
    rep = sf.report(x, g, b)
    y = np.asarray(sf(x, g, b))
    ref = np.asarray(layernorm(jnp.asarray(x), g, b))
    print(json.dumps({
        "cache_hit": rep.plan_cache_hit,
        "explore_runs": explorer.EXPLORE_RUNS,
        "signature": rep.signature,
        "max_err": float(np.max(np.abs(y - ref))),
    }))
""")


def test_plan_cache_hits_across_processes(tmp_path):
    """Second compile of an identical graph signature in a *fresh process*
    hits the persistent cache and skips exploration entirely."""
    env = dict(os.environ, PYTHONPATH=SRC)
    results = []
    for _ in range(2):
        proc = subprocess.run(
            [sys.executable, "-c", _FRESH_PROC, str(tmp_path)],
            env=env, capture_output=True, text=True, timeout=300)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        results.append(json.loads(proc.stdout.strip().splitlines()[-1]))
    first, second = results
    assert not first["cache_hit"] and first["explore_runs"] >= 1
    assert second["cache_hit"]
    assert second["explore_runs"] == 0       # exploration skipped
    assert second["signature"] == first["signature"]
    assert second["max_err"] < 1e-4


# -- measured autotune (forced on CPU) ----------------------------------------
def test_autotune_forced_produces_valid_override(monkeypatch, tmp_path):
    monkeypatch.setenv("REPRO_AUTOTUNE", "force")
    fn, args = _args("rmsnorm")
    sf = StitchedFunction(fn, autotune=True, plan_cache=str(tmp_path))
    rep = sf.report(*args)
    assert rep.autotuned
    np.testing.assert_allclose(np.asarray(sf(*args)),
                               np.asarray(fn(*(jnp.asarray(a)
                                               for a in args))),
                               rtol=1e-4, atol=1e-5)
    # tuned schedule was persisted
    sf2 = StitchedFunction(fn, plan_cache=str(tmp_path))
    assert sf2.report(*args).plan_cache_hit
