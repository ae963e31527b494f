"""Cost-model + memory-planner invariants (paper §4.3, §4.4, §5.4)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import V5E, best_estimate, delta_evaluator, trace
from repro.core.cost_model import estimate_onepass, estimate_packed, estimate_unfused
from repro.core.ir import FUSIBLE_KINDS
from repro.core.memory_planner import dominators, plan_scratch
from repro.core.rowspec import analyze


def _ln_graph(R=64, C=128):
    def ln(x, g, b):
        m = jnp.mean(x, axis=-1, keepdims=True)
        v = jnp.mean((x - m) ** 2, axis=-1, keepdims=True)
        return (x - m) * jax.lax.rsqrt(v + 1e-6) * g + b
    return trace(ln, np.zeros((R, C), np.float32),
                 np.zeros(C, np.float32), np.zeros(C, np.float32))


def _full_pattern(G):
    return frozenset(n for n in G.fusible_nodes())


def test_delta_zero_for_singletons():
    G = _ln_graph()
    for nid in G.fusible_nodes():
        assert delta_evaluator(G, frozenset({nid})) == 0.0


def test_delta_positive_for_layernorm_fusion():
    G = _ln_graph()
    assert delta_evaluator(G, _full_pattern(G)) > 0


def test_latency_onepass_beats_unfused_for_ln():
    G = _ln_graph()
    pat = _full_pattern(G)
    best = best_estimate(G, pat)
    unf = estimate_unfused(G, pat)
    assert best.latency_s < unf.latency_s
    assert best.schedule in ("onepass", "packed")


def test_latency_monotone_in_rows():
    lat = {}
    for R in (64, 256):
        G = _ln_graph(R=R)
        pat = _full_pattern(G)
        info = analyze(G, pat)
        lat[R] = estimate_onepass(G, pat, info, 64).latency_s
    assert lat[256] > lat[64]


def test_packed_estimate_positive_and_single_launch():
    G = _ln_graph()
    est = estimate_packed(G, _full_pattern(G))
    assert est.latency_s > 0 and est.n_steps == 1


# -- memory planner ---------------------------------------------------------
def test_scratch_reuse_is_legal_and_smaller():
    G = _ln_graph()
    pat = _full_pattern(G)
    info = analyze(G, pat)
    plan = plan_scratch(G, pat, info)
    assert plan.total_bytes <= plan.naive_bytes
    # legality: two values in the same slot must have disjoint live ranges
    order = sorted(pat)
    pos = {n: i for i, n in enumerate(order)}
    outs = set(G.pattern_outputs(pat))
    last_use = {}
    for nid in order:
        for i in G.node(nid).inputs:
            if i in pat:
                last_use[i] = pos[nid]
    for o in outs:
        last_use[o] = len(order)
    by_slot = {}
    for nid, slot in plan.slot_of.items():
        by_slot.setdefault(slot, []).append(nid)
    for slot, members in by_slot.items():
        members.sort(key=lambda n: pos[n])
        for a, b in zip(members, members[1:]):
            assert last_use.get(a, pos[a]) <= pos[b], \
                f"slot {slot}: {a} still live when {b} allocated"


def test_dominator_sets_sane():
    G = _ln_graph()
    pat = _full_pattern(G)
    doms = dominators(G, pat)
    for nid, d in doms.items():
        assert nid in d  # every node dominates itself


@given(st.integers(2, 40), st.integers(1, 6))
@settings(max_examples=20, deadline=None)
def test_scratch_never_exceeds_naive(depth, width):
    """Property: slot sharing can only shrink total scratch."""
    def chain(x):
        vals = [x]
        for i in range(depth):
            vals.append(jnp.tanh(vals[max(0, i - width)]) + vals[-1])
        return vals[-1] / (jnp.sum(vals[-1], -1, keepdims=True) + 1.0)

    G = trace(chain, np.zeros((4, 32), np.float32))
    pat = frozenset(G.fusible_nodes())
    if not G.is_convex(pat):
        return
    info = analyze(G, pat)
    if info is None:
        return
    plan = plan_scratch(G, pat, info)
    assert plan.total_bytes <= plan.naive_bytes
    assert plan.total_bytes > 0


def test_block_rows_are_rounded_onto_the_sublane_tile():
    """Mosaic takes a row block that is a multiple of the dtype's
    sublane tile (8 rows for 4-byte, 16 for 2-byte, 32 for 1-byte
    types), or one that spans every row."""
    from repro.core.cost_model import legal_block_rows, sublane_rows

    assert legal_block_rows(1, 2048, 8) == 8
    assert legal_block_rows(12, 2048, 8) == 16
    assert legal_block_rows(8, 2048, 16) == 16
    assert legal_block_rows(64, 4, 8) == 4       # fewer rows than a tile
    for dtype, tile in [(np.float32, 8), (jnp.bfloat16, 16), (np.int8, 32)]:
        G = trace(lambda x: x * x, np.zeros((64, 128), dtype))
        assert sublane_rows(G, list(G.nodes)) == tile
    G = _ln_graph(R=64, C=128)
    est = best_estimate(G, _full_pattern(G), V5E)
    assert est.block_rows % 8 == 0 or est.block_rows == 64


def test_hardware_model_follows_the_device_kind(monkeypatch):
    from repro.core import cost_model

    assert cost_model.hardware() is V5E          # the CPU plans for v5e

    class Chip:
        platform = "tpu"

        def __init__(self, kind):
            self.device_kind = kind

    monkeypatch.setattr(jax, "devices", lambda: [Chip("TPU v5 lite")])
    assert cost_model.hardware() is V5E
    monkeypatch.setattr(jax, "devices", lambda: [Chip("TPU v99")])
    with pytest.raises(ValueError, match="no hardware model"):
        cost_model.hardware()
