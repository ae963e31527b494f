"""Beam-search stitch partitioning + batched group autotune (ISSUE 3).

Part 1 -- partition quality.  Three scenario graphs are partitioned by
``search_groups`` at beam width 1 (the original greedy forward merge)
and width 4, and both partitions are priced by the cost model (sum of
each group's best-schedule latency; leftovers are identical on both
sides so they cancel).  The beam must never be worse, and on the
``waist`` scenario it is strictly better: greedy refuses the A+B merge
(that intermediate union's working set overflows the scenario's tight
VMEM) and never discovers that adding the combine stage C shrinks the
union's IO back into one-pass feasibility -- the beam holds the
infeasible intermediate and lands the full merge.

Part 2 -- group-autotune sweep time.  A transformer-like stack of
isomorphic stitched blocks is measured two ways under
``REPRO_AUTOTUNE=force``: the per-candidate serial compile-measure loop
(one eager warmup + timing per candidate, fresh dummy inputs each -- the
pre-ISSUE-3 sweep), and the batched path (every candidate a branch of
one jitted ``lax.switch``, shared dummy inputs, isomorphic groups tuned
once via ``struct_key``).  The acceptance bar is a >= 2x wall-time
reduction.
"""
from __future__ import annotations

import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import CostContext, Hardware, V5E, make_plan, trace
from repro.core.autotune import tune_group
from repro.core.ir import FusionPlan, Pattern
from repro.core.stitcher import search_groups
from .common import csv_row

rng = np.random.default_rng(23)


def _ln(x, g, b):
    m = jnp.mean(x, axis=-1, keepdims=True)
    v = jnp.mean((x - m) ** 2, axis=-1, keepdims=True)
    return (x - m) * jax.lax.rsqrt(v + 1e-5) * g + b


def _softmax(x):
    e = jnp.exp(x - jnp.max(x, axis=-1, keepdims=True))
    return e / jnp.sum(e, axis=-1, keepdims=True)


def _deep_stack(x, g, b):
    for _ in range(8):
        x = _ln(x, g, b)
        x = jax.nn.gelu(x, approximate=True) + x
    return x


def _softmax_chain(x, g):
    for _ in range(8):
        x = _softmax(x * jax.lax.rsqrt(
            jnp.mean(x * x, -1, keepdims=True) + 1e-6) * g)
    return x


def _waist(x, g, b):
    """Row stats -> wide 3-tensor waist -> combine (see module doc)."""
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


def _rand(shape):
    return rng.standard_normal(shape).astype(np.float32)


def _scale(n):
    return (np.abs(rng.standard_normal(n)) + 0.5).astype(np.float32)


def _waist_plan(graph):
    """Hand-split the waist chain at its stage boundaries (A: row stats,
    B: the three waist tensors, C: combine) -- the partition a planner
    guardrail produces on a model too big to fuse whole."""
    fus = sorted(graph.fusible_nodes())
    R = graph.node(graph.inputs[0]).spec.shape[0]
    stats = [n for n in fus
             if graph.node(n).spec.shape[0] == R
             and (len(graph.node(n).spec.shape) == 1
                  or graph.node(n).spec.shape[-1] == 1)]
    a_end = max(stats)                      # r, the last row-stat value
    tail = [n for n in fus if n > a_end]    # waist + combine (all FULL)
    b_end = tail[2 * len(tail) // 3 - 1]    # waist ends 2/3 in (u, v, w_)
    stages = ([n for n in fus if n <= a_end],
              [n for n in fus if a_end < n <= b_end],
              [n for n in fus if n > b_end])
    return FusionPlan([Pattern(frozenset(s), 0.0) for s in stages if s])


def _scenarios():
    x, g, b = _rand((64, 512)), _scale(512), _rand(512)
    graph = trace(_deep_stack, x, g, b)
    yield "ln_stack_64x512", graph, make_plan(graph), V5E

    x, g = _rand((16, 2048)), _scale(2048)
    graph = trace(_softmax_chain, x, g)
    yield "softmax_chain_16x2048", graph, make_plan(graph), V5E

    hw = Hardware(vmem_bytes=768 * 1024)  # the A+B infeasibility cliff
    x, g, b = _rand((512, 2048)), _scale(2048), _rand(2048)
    graph = trace(_waist, x, g, b)
    yield "waist_512x2048", graph, _waist_plan(graph), hw


def _partition_latency(ctx, groups) -> float:
    return sum(ctx.best(grp.members).latency_s for grp in groups)


def _tune_workload():
    """8 blocks of 5 LN+GELU layers between (opaque) matmuls: 8 stitched
    groups, 3 unique structures (first/last touch graph IO)."""
    C = 256
    w = (np.eye(C) * 0.9).astype(np.float32)

    def block(x, g, b):
        for _ in range(5):
            x = _ln(x, g, b)
            x = jax.nn.gelu(x, approximate=True) + x
        return x

    def stack(x, g, b):
        for _ in range(8):
            x = block(x, g, b) @ w
        return x

    return stack, (_rand((16, C)), _scale(C), _rand(C))


def run() -> list[str]:
    os.environ.setdefault("REPRO_AUTOTUNE", "force")
    rows = []

    # ---- part 1: beam vs greedy partition quality --------------------------
    strict_wins = 0
    for name, graph, plan, hw in _scenarios():
        ctx = CostContext(graph, hw)
        t0 = time.perf_counter()
        greedy, s1 = search_groups(graph, plan, hw, ctx=ctx, beam_width=1)
        beam, s4 = search_groups(graph, plan, hw, ctx=ctx, beam_width=4)
        search_us = (time.perf_counter() - t0) * 1e6
        lat_g = _partition_latency(ctx, greedy)
        lat_b = _partition_latency(ctx, beam)
        assert lat_b <= lat_g + 1e-15, \
            f"{name}: beam partition worse than greedy ({lat_b} > {lat_g})"
        win = lat_b < lat_g - 1e-15
        strict_wins += win
        rows.append(csv_row(
            f"beam_{name}", search_us,
            f"beam_latency={lat_b * 1e6:.2f}us vs greedy={lat_g * 1e6:.2f}us "
            f"({'strictly better' if win else 'equal'}); "
            f"groups={len(beam)} vs {len(greedy)}; "
            f"beam_gain={s4.gain_s * 1e6:.2f}us greedy_gain="
            f"{s1.gain_s * 1e6:.2f}us; states={s4.states_explored}; "
            f"segments={s4.segments} (reused {s4.segments_reused})"))
    assert strict_wins >= 1, "no scenario where beam strictly beats greedy"

    # ---- part 2: serial vs batched group-autotune sweep --------------------
    stack, args = _tune_workload()
    graph = trace(stack, *args)
    ctx = CostContext(graph)
    plan = make_plan(graph, ctx=ctx)
    groups, _ = search_groups(graph, plan, ctx=ctx)
    stitched = [grp for grp in groups if grp.stitched]

    t0 = time.perf_counter()
    tuned_by_struct: dict[tuple, dict | None] = {}
    for grp in stitched:  # the production path: batched + isomorphic reuse
        key = ctx.struct_key(grp.members)
        if key not in tuned_by_struct:
            tuned_by_struct[key] = tune_group(graph, grp.parts, ctx=ctx,
                                              batch_compile=True)
    t_batched = time.perf_counter() - t0

    t0 = time.perf_counter()
    for grp in stitched:  # pre-ISSUE-3: every group, candidate by candidate
        tune_group(graph, grp.parts, ctx=ctx, batch_compile=False)
    t_serial = time.perf_counter() - t0

    speedup = t_serial / max(t_batched, 1e-9)
    rows.append(csv_row(
        "beam_autotune_sweep", t_batched * 1e6,
        f"groups={len(stitched)} structs={len(tuned_by_struct)}; "
        f"batched={t_batched:.2f}s vs serial={t_serial:.2f}s; "
        f"speedup={speedup:.2f}x"))
    return rows
