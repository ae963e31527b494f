"""Measured top-k partition tuning (ISSUE 4).

Part 1 -- honest race.  The waist scenario's top-k candidate partitions
(the cost-model winner plus the distinct runner-ups retained by
``search_groups``) are raced on the real backend by
``autotune.tune_partitions``: every (partition, candidate-schedule)
pair is one branch of a single jitted ``lax.switch``, screened with one
warmed sample each and refined for the top two.  The committed
partition is by construction never slower than the cost-model pick *on
the measured profile*; the row reports whether silicon confirmed or
overruled the model.

Part 2 -- model-vs-silicon gap, deterministically.  The static cost
model is a v5e roofline; deployed silicon can deviate (different VMEM,
different DMA behavior).  This part emulates such a chip through the
``_time_callable`` seam: branch times are priced by the *same* cost
model under a different ``Hardware`` (a VMEM-starved part on which the
big one-pass union must stream).  The model (v5e) ranks the full merge
first; the emulated silicon measures the split faster -- the measured
partition beats the cost-model pick by the reported margin, which is
exactly the gap ``tune_partitions`` closes.  Deterministic: no wall
clock in the decision, so the row is CI-stable.

Part 3 -- tune-once-run-many.  The measured partition persists in
the plan cache (``partition_source: measured``); a second process
replays it without re-searching or re-racing (asserted via call
counting), reporting the compile-time saving.
"""
from __future__ import annotations

import os
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (CostContext, Hardware, StitchedFunction, V5E,
                        make_plan, trace)
from repro.core import autotune as autotune_mod
from repro.core import stitch as stitch_mod
from repro.core.autotune import tune_partitions
from repro.core.codegen import _override_estimate
from repro.core.ir import FusionPlan, Pattern
from repro.core.plan_cache import FORMAT_VERSION, PlanCache
from repro.core.stitcher import search_groups
from .common import csv_row

rng = np.random.default_rng(31)


def _rand(shape):
    return rng.standard_normal(shape).astype(np.float32)


def _scale(n):
    return (np.abs(rng.standard_normal(n)) + 0.5).astype(np.float32)


def _ln(x, g, b):
    m = jnp.mean(x, axis=-1, keepdims=True)
    v = jnp.mean((x - m) ** 2, axis=-1, keepdims=True)
    return (x - m) * jax.lax.rsqrt(v + 1e-5) * g + b


def _deep(x, g, b):
    for _ in range(8):
        x = _ln(x, g, b)
        x = jax.nn.gelu(x, approximate=True) + x
    return x


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


def _waist_case(R=256, C=2048):
    x, g, b = _rand((R, C)), _scale(C), _rand(C)
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
    return graph, plan


def _honest_race() -> str:
    """Race the waist's top-k candidates on the real backend."""
    graph, plan = _waist_case()
    hw = Hardware(vmem_bytes=160 * 1024)
    ctx = CostContext(graph, hw)
    res = search_groups(graph, plan, hw, ctx=ctx)
    assert len(res.candidates) >= 2, "waist must yield runner-up partitions"
    t0 = time.perf_counter()
    out = tune_partitions(graph, [c.groups for c in res.candidates],
                          hw=hw, ctx=ctx)
    race_s = time.perf_counter() - t0
    assert out is not None
    t_model = out.measured_s[0]
    t_win = out.measured_s[out.index]
    assert t_win <= t_model + 1e-12, \
        "committed partition slower than the cost-model pick on silicon"
    verdict = ("silicon overruled the model" if out.index != 0
               else "silicon confirmed the model")
    return csv_row(
        "topk_race_waist", race_s * 1e6,
        f"candidates={len(res.candidates)} branches={out.branches}; "
        f"model_pick={t_model * 1e3:.2f}ms vs committed="
        f"{t_win * 1e3:.2f}ms (winner idx {out.index}: {verdict}); "
        f"model_gains_us={[round(c.gain_s * 1e6, 2) for c in res.candidates]}; "
        f"staged_scratch_B={[c.scratch_bytes for c in res.candidates]}")


def _emulated_silicon_gap() -> str:
    """Deterministic disagreement: silicon = the same cost model under a
    VMEM-starved Hardware; the v5e model's pick loses the race there."""
    graph, plan = _waist_case()
    hw_model = Hardware(vmem_bytes=160 * 1024)   # ranks the full merge first
    hw_silicon = Hardware(vmem_bytes=96 * 1024)  # merge must stream there
    ctx = CostContext(graph, hw_model)
    ctx_si = CostContext(graph, hw_silicon)
    res = search_groups(graph, plan, hw_model, ctx=ctx)
    assert len(res.candidates) >= 2
    cands = [c.groups for c in res.candidates]

    def silicon_price(ci: int, assignment: dict) -> float:
        total = 0.0
        for gi, grp in enumerate(cands[ci]):
            over = assignment.get(gi)
            est = None
            if over:
                est = _override_estimate(graph, grp.members,
                                         ctx_si.info(grp.members),
                                         dict(over), hw_silicon, ctx=ctx_si)
            if est is None:
                est = ctx_si.best(grp.members)
            total += est.latency_s
        return total

    def timer(fn, args, *, warmup=1, iters=3, key=None):
        assert key and key[0] == "partition"
        return silicon_price(key[1], dict(key[2]))

    real_timer = autotune_mod._time_callable
    autotune_mod._time_callable = timer
    try:
        out = tune_partitions(graph, cands, hw=hw_model, ctx=ctx)
    finally:
        autotune_mod._time_callable = real_timer
    assert out is not None
    t_model, t_win = out.measured_s[0], out.measured_s[out.index]
    assert out.index != 0, "emulated silicon must overrule the v5e model"
    assert t_win < t_model
    saving = (t_model - t_win) / t_model * 100.0
    return csv_row(
        "topk_measured_beats_model", t_win * 1e6,
        f"measured partition (idx {out.index}) beats the cost-model pick "
        f"on emulated low-VMEM silicon: {t_win * 1e6:.2f}us vs "
        f"{t_model * 1e6:.2f}us (saving={saving:.1f}%); "
        f"branches={out.branches}")


def _cache_replay() -> str:
    """Round-trip: the measured partition replays with no re-race."""
    args = (_rand((16, 256)), _scale(256), _rand(256))
    with tempfile.TemporaryDirectory() as cache_dir:
        t0 = time.perf_counter()
        sf1 = StitchedFunction(_deep, autotune=True, plan_cache=cache_dir)
        rep1 = sf1.report(*args)
        cold_s = time.perf_counter() - t0
        assert rep1.partition_source == "measured"
        entry = PlanCache(cache_dir).load(rep1.signature)
        assert entry["format"] == FORMAT_VERSION
        assert entry["partition_source"] == "measured"

        calls = []
        real_search = stitch_mod.search_groups
        real_tune = autotune_mod.tune_partitions
        stitch_mod.search_groups = \
            lambda *a, **k: calls.append("s") or real_search(*a, **k)
        autotune_mod.tune_partitions = \
            lambda *a, **k: calls.append("t") or real_tune(*a, **k)
        try:
            t0 = time.perf_counter()
            sf2 = StitchedFunction(_deep, autotune=True,
                                   plan_cache=cache_dir)
            rep2 = sf2.report(*args)
            warm_s = time.perf_counter() - t0
        finally:
            stitch_mod.search_groups = real_search
            autotune_mod.tune_partitions = real_tune
        assert rep2.plan_cache_hit and rep2.partition_source == "measured"
        assert not calls, "cache hit must skip the search and the race"
        y1 = np.asarray(sf1(*args))
        y2 = np.asarray(sf2(*args))
        np.testing.assert_allclose(y1, y2, rtol=1e-6, atol=1e-6)
    return csv_row(
        "topk_cache_replay", warm_s * 1e6,
        f"measured-partition replay: cold_compile={cold_s:.2f}s vs "
        f"replay={warm_s:.2f}s (speedup={cold_s / max(warm_s, 1e-9):.1f}x); "
        f"no re-search, no re-race")


def run() -> list[str]:
    os.environ.setdefault("REPRO_AUTOTUNE", "force")
    return [_honest_race(), _emulated_silicon_gap(), _cache_replay()]
