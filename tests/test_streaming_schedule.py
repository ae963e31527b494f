"""Streaming (warp-composition analogue) schedule: emitter correctness +
cost-model selection for rows too long for one-pass VMEM residency."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import trace
from repro.core.codegen import _emit_pallas_streaming, emit_pattern
from repro.core.cost_model import (Hardware, best_estimate, estimate_streaming,
                                   reduce_levels)
from repro.core.ir import OpKind
from repro.core.rowspec import analyze

rng = np.random.default_rng(5)


def _ln(x, g, b):
    m = jnp.mean(x, axis=-1, keepdims=True)
    v = jnp.mean((x - m) ** 2, axis=-1, keepdims=True)
    return (x - m) * jax.lax.rsqrt(v + 1e-6) * g + b


def _graph_and_pattern(fn, *args):
    G = trace(fn, *args)
    pat = frozenset(G.fusible_nodes())
    ext = [i for i in G.pattern_inputs(pat)
           if G.node(i).kind is not OpKind.CONST]
    return G, pat, ext


def test_reduce_levels_layernorm():
    x = np.zeros((4, 64), np.float32)
    G, pat, _ = _graph_and_pattern(_ln, x, np.zeros(64, np.float32),
                                   np.zeros(64, np.float32))
    lvl = reduce_levels(G, pat)
    assert max(lvl.values()) == 2  # mean pass, var pass, apply pass


@pytest.mark.parametrize("R,C,bc", [(4, 3000, 512), (3, 700, 512),
                                    (8, 1024, 1024)])
def test_streaming_layernorm_allclose(R, C, bc):
    x = rng.standard_normal((R, C)).astype(np.float32)
    g = rng.standard_normal(C).astype(np.float32)
    b = rng.standard_normal(C).astype(np.float32)
    G, pat, ext = _graph_and_pattern(_ln, x, g, b)
    info = analyze(G, pat)
    fn = _emit_pallas_streaming(G, pat, info, 4, ext,
                                G.pattern_outputs(pat),
                                block_cols=bc)
    np.testing.assert_allclose(np.asarray(fn(x, g, b)[0]),
                               np.asarray(_ln(x, g, b)),
                               rtol=1e-4, atol=1e-4)


def test_streaming_softmax_with_max_reduce():
    z = (rng.standard_normal((2, 5000)) * 4).astype(np.float32)
    fn_ref = lambda a: jax.nn.softmax(a, axis=-1)
    G, pat, ext = _graph_and_pattern(fn_ref, z)
    info = analyze(G, pat)
    fn = _emit_pallas_streaming(G, pat, info, 2, ext,
                                G.pattern_outputs(pat),
                                block_cols=1024)
    np.testing.assert_allclose(np.asarray(fn(z)[0]),
                               np.asarray(fn_ref(z)), rtol=1e-5, atol=1e-6)


def test_cost_model_selects_streaming_for_tiny_vmem():
    """With a tiny VMEM budget, one-pass is infeasible and the evaluator
    must fall back to streaming (not packed) for a reduce pattern."""
    x = np.zeros((64, 8192), np.float32)
    G, pat, _ = _graph_and_pattern(
        _ln, x, np.zeros(8192, np.float32), np.zeros(8192, np.float32))
    small = Hardware(vmem_bytes=256 * 1024)  # 256 KiB core
    est = best_estimate(G, pat, small)
    assert est.schedule in ("streaming", "packed")
    info = analyze(G, pat)
    stream = estimate_streaming(G, pat, info, 8, 512, small)
    assert stream.feasible
    assert stream.n_steps > 0 and stream.latency_s > 0


def test_emit_pattern_streaming_path_runs():
    """End-to-end: force the streaming branch through emit_pattern."""
    x = rng.standard_normal((4, 2048)).astype(np.float32)
    g = rng.standard_normal(2048).astype(np.float32)
    b = rng.standard_normal(2048).astype(np.float32)
    G, pat, ext = _graph_and_pattern(_ln, x, g, b)
    small = Hardware(vmem_bytes=96 * 1024)
    em = emit_pattern(G, pat, hw=small)
    out = em.fn(x, g, b)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(_ln(x, g, b)),
                               rtol=1e-4, atol=1e-4)
