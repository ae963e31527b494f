"""Compute-anchored megakernels: prologue/epilogue chains folded into
matmul and flash-attention Pallas bodies.

Covers the anchor pattern kind end to end: classification, the anchored
partition (fewer launches, more HBM saved than memory-only stitching),
numerics (fp32 exact vs the interpret oracle; bf16 within the widened
anchored band), the anchored plan-cache round-trip, and isomorphic
anchored-group emission dedup.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import CostContext, StitchedFunction, make_plan, \
    plan_stats, search_groups, trace
from repro.core.classify import classify, vpu_cost
from repro.core.ir import OpKind
from repro.core.plan_cache import FORMAT_VERSION, PlanCache
from repro.runtime import RUNG_ANCHORED
from repro.runtime.guard import (ANCHORED_VERIFY_TOLERANCES,
                                 VERIFY_TOLERANCES, tolerance_for)

rng = np.random.default_rng(7)


# ---------------------------------------------------------------------------
# graphs
# ---------------------------------------------------------------------------
def _mlp(x, w, r):
    """Prologue chain -> matmul -> epilogue chain: one anchored group."""
    h = x * 2.0 + 1.0
    y = h @ w
    return jnp.tanh(y) + r


def _mlp_args(M=64, K=32, N=48, dtype=np.float32):
    return (rng.standard_normal((M, K)).astype(dtype),
            rng.standard_normal((K, N)).astype(dtype),
            rng.standard_normal((M, N)).astype(dtype))


def _attn(q, k, v, bias):
    """Scale + bias folded into the attention inner loop."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * 0.125 + bias
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def _attn_args(B=2, H=4, S=64, D=32):
    return (rng.standard_normal((B, H, S, D)).astype(np.float32),
            rng.standard_normal((B, H, S, D)).astype(np.float32),
            rng.standard_normal((B, H, S, D)).astype(np.float32),
            rng.standard_normal((1, 1, S, S)).astype(np.float32))


# ---------------------------------------------------------------------------
# classification (satellite: explicit kinds + vpu_cost)
# ---------------------------------------------------------------------------
def test_classify_anchor_kinds():
    assert classify("dot_general") is OpKind.ANCHOR
    assert classify("conv_general_dilated") is OpKind.ANCHOR
    # anchors are costed per *output* element, well above light EW ops
    assert vpu_cost("dot_general") > vpu_cost("add")
    assert vpu_cost("flash_attention") >= vpu_cost("dot_general")
    # non-anchor kinds are untouched
    assert classify("add") is OpKind.LIGHT_EW
    assert classify("reduce_sum") is OpKind.REDUCE
    assert classify("sort") is OpKind.OPAQUE


# ---------------------------------------------------------------------------
# anchored matmul: numerics + plan shape
# ---------------------------------------------------------------------------
def test_matmul_anchored_exact_fp32():
    args = _mlp_args()
    sf = StitchedFunction(_mlp)
    rep = sf.report(*args)
    assert rep.n_anchored == 1
    assert rep.rung == RUNG_ANCHORED and not rep.fallbacks
    out = np.asarray(sf(*args))
    # anchored-vs-interpret is exact at fp32: same op order, same
    # accumulator, only the dispatch differs
    oracle = StitchedFunction(_mlp, dispatch="interpret")
    np.testing.assert_array_equal(out, np.asarray(oracle(*args)))
    # and the XLA reference agrees to float32 precision
    ref = np.asarray(_mlp(*(jnp.asarray(a) for a in args)))
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


def _anchored_vs_memory_only(fn, args):
    """``search_groups``' anchored candidate and the memory-only
    partition it keeps right behind it, with each one's kernel count."""
    graph = trace(fn, *args)
    ctx = CostContext(graph)
    plan = make_plan(graph, ctx=ctx)
    anchored, memory_only = search_groups(graph, plan, ctx=ctx).candidates[:2]
    assert any(g.anchors for g in anchored.groups)
    assert not any(g.anchors for g in memory_only.groups)
    kernels = [plan_stats(graph, plan, ctx=ctx, groups=c.groups)
               .n_kernels_stitched for c in (anchored, memory_only)]
    return anchored, memory_only, kernels


def test_anchored_beats_memory_only_stitching():
    """The anchored candidate must form fewer kernels and model a
    strictly higher gain than the memory-only partition of the same
    graph."""
    anchored, memory_only, (k_on, k_off) = _anchored_vs_memory_only(
        _mlp, _mlp_args())
    assert k_on < k_off
    assert anchored.gain_s > memory_only.gain_s


def test_attention_bias_scale_folded():
    args = _attn_args()
    sf = StitchedFunction(_attn)
    rep = sf.report(*args)
    assert rep.n_anchored >= 1
    assert rep.rung == RUNG_ANCHORED and not rep.fallbacks
    out = np.asarray(sf(*args))
    ref = np.asarray(_attn(*(jnp.asarray(a) for a in args)))
    # the flash inner loop re-orders the softmax reduction (online
    # max/sum), so fp32 agreement is tight but not bitwise
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


def test_attention_anchored_fewer_launches():
    anchored, memory_only, (k_on, k_off) = _anchored_vs_memory_only(
        _attn, _attn_args())
    assert k_on < k_off
    assert anchored.gain_s > memory_only.gain_s


# ---------------------------------------------------------------------------
# low precision: widened anchored verify band
# ---------------------------------------------------------------------------
def test_tolerance_for_anchored_band():
    # anchored widens only the low-precision dtypes
    assert tolerance_for(jnp.bfloat16, anchored=True) \
        == ANCHORED_VERIFY_TOLERANCES["bfloat16"]
    assert tolerance_for(jnp.float16, anchored=True) \
        == ANCHORED_VERIFY_TOLERANCES["float16"]
    assert tolerance_for(jnp.bfloat16, anchored=True)[1] \
        > tolerance_for(jnp.bfloat16)[1]
    # fp32 keeps the standard band either way
    assert tolerance_for(np.float32, anchored=True) \
        == VERIFY_TOLERANCES["float32"]
    assert tolerance_for(np.float32) == VERIFY_TOLERANCES["float32"]


def test_matmul_anchored_bf16():
    x, w, r = _mlp_args()
    args = tuple(jnp.asarray(a, dtype=jnp.bfloat16) for a in (x, w, r))
    sf = StitchedFunction(_mlp)
    rep = sf.report(*args)
    assert rep.n_anchored == 1
    out = np.asarray(sf(*args), dtype=np.float32)
    ref = np.asarray(_mlp(*args), dtype=np.float32)
    rtol, atol = ANCHORED_VERIFY_TOLERANCES["bfloat16"]
    np.testing.assert_allclose(out, ref, rtol=rtol, atol=atol)


def test_bf16_shadow_verification_passes(monkeypatch, tmp_path):
    """REPRO_VERIFY on an anchored bf16 dispatch uses the widened band:
    the run must not quarantine."""
    monkeypatch.setenv("REPRO_VERIFY", "first")
    x, w, r = _mlp_args()
    args = tuple(jnp.asarray(a, dtype=jnp.bfloat16) for a in (x, w, r))
    sf = StitchedFunction(_mlp, plan_cache=str(tmp_path))
    sf(*args)
    rep = sf.reports()[0]
    assert rep.n_anchored == 1
    assert rep.verified >= 1 and rep.verify_failures == 0
    assert not rep.quarantined


# ---------------------------------------------------------------------------
# plan cache: anchored round-trip
# ---------------------------------------------------------------------------
def _entry_on_disk(cache_dir, signature):
    with open(os.path.join(cache_dir, f"{signature}.json")) as f:
        return json.load(f)


def test_plan_cache_v6_roundtrip(tmp_path):
    args = _mlp_args()
    sf1 = StitchedFunction(_mlp, plan_cache=str(tmp_path))
    rep1 = sf1.report(*args)
    y1 = np.asarray(sf1(*args))
    assert rep1.n_anchored == 1

    entry = _entry_on_disk(str(tmp_path), rep1.signature)
    assert entry["format"] == FORMAT_VERSION
    anchored_recs = [g for g in entry["groups"] if g.get("anchors")]
    assert anchored_recs and all(
        isinstance(a, int) for g in anchored_recs for a in g["anchors"])

    sf2 = StitchedFunction(_mlp, plan_cache=str(tmp_path))
    rep2 = sf2.report(*args)
    assert rep2.plan_cache_hit
    assert rep2.n_anchored == rep1.n_anchored
    np.testing.assert_array_equal(np.asarray(sf2(*args)), y1)


# ---------------------------------------------------------------------------
# emission dedup across isomorphic anchored groups
# ---------------------------------------------------------------------------
def test_isomorphic_anchored_layers_share_emission():
    w = (rng.standard_normal((64, 64)) * 0.05).astype(np.float32)

    def stack(x):
        for _ in range(4):
            x = jnp.tanh((x * 2.0 + 1.0) @ w)
        return x

    x = rng.standard_normal((16, 64)).astype(np.float32)
    sf = StitchedFunction(stack)
    rep = sf.report(x)
    assert rep.n_anchored >= 2
    assert rep.emission_reused >= 1, \
        "isomorphic anchored groups must rebind one compiled kernel"
    ref = np.asarray(stack(jnp.asarray(x)))
    np.testing.assert_allclose(np.asarray(sf(x)), ref, rtol=1e-5, atol=1e-5)
