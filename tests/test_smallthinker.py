"""SmallThinker (``layer_pattern`` of global NoPE and sliding-window RoPE
attention, a ring-buffer KV cache for the windowed layers, routing
before attention, ReGLU experts on one chip's share) against the plain
float32 reference ``chipbench/refs/smallthinker_moe.py``, at a reduced
size on the CPU with seeded random weights: d 256, "ASSS" twice, a
16-position window, 16 experts of which this chip holds 4-7, top 3.

Tolerance: the program and the reference both compute in float32 at
``highest`` and differ only in the order of their sums (the flash
kernel's blocked online softmax and the ring's order of keys against
one softmax over the keys in position order, the experts' products
fused differently).  The logits here are of order 1, and such
reorderings move them by a few 1e-6; 5e-5 leaves room for that and
fails any change of the mathematics (a key outside the window, a RoPE
on the wrong layer, routing on the FFN's input, SiLU for ReLU: each
moves them by 1e-2 or more, see ``test_each_departure_is_seen``).
"""
import contextlib
import dataclasses
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model
from repro.models import layers as L
from repro.runtime import spans
from repro.serving.scheduler import ContinuousBatcher

REF_PATH = (Path(__file__).resolve().parents[1] / "chipbench" / "refs"
            / "smallthinker_moe.py")
ATOL = 5e-5
WINDOW = 16


def _load_ref():
    spec = importlib.util.spec_from_file_location("smallthinker_moe_ref",
                                                  REF_PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load_ref()

CFG = get_config("smallthinker-21b-a3b").reduced(
    n_layers=8, layer_pattern="ASSS" * 2, sliding_window=WINDOW,
    d_model=256, n_heads=4, n_kv_heads=2, head_dim=32, d_ff=64,
    n_experts=16, top_k=3, experts_held=4, expert_offset=4)


def model_dict(cfg) -> dict:
    """The reference's model mapping for an ``ArchConfig``."""
    return dict(
        n_layers=cfg.n_layers, layer_pattern=cfg.layer_pattern,
        sliding_window=cfg.sliding_window, rope_theta=cfg.rope_theta,
        d_model=cfg.d_model, n_heads=cfg.n_heads,
        n_kv_heads=cfg.n_kv_heads, head_dim=cfg.resolved_head_dim,
        d_ff=cfg.d_ff, n_experts=cfg.n_experts, top_k=cfg.top_k,
        experts_held=cfg.n_experts_held, expert_offset=cfg.expert_offset,
        vocab_size=cfg.vocab_size, vocab_rows=cfg.padded_vocab,
        norm_eps=cfg.norm_eps)


M = model_dict(CFG)


@pytest.fixture(autouse=True)
def _highest():
    old = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", "highest")
    yield
    jax.config.update("jax_default_matmul_precision", old)


@pytest.fixture(scope="module")
def params():
    return jax.jit(lambda k: ref.init_params(M, k))(jax.random.PRNGKey(3))


def _tokens(n, seed=0, batch=2):
    return np.random.default_rng(seed).integers(
        0, CFG.vocab_size, (batch, n)).astype(np.int32)


@pytest.fixture(scope="module")
def want(params):
    """The reference's logits over 48 positions (three windows)."""
    jax.config.update("jax_default_matmul_precision", "highest")
    toks = _tokens(48)
    return toks, np.asarray(ref.logits(M, params, toks))


@pytest.mark.parametrize("fusion", ["xla", "stitched"])
def test_prefill_logits_match_reference(params, want, fusion):
    """A 40-token prefill (the ring wraps inside it: its last 16 keys
    land rolled by 8) against the reference's forward."""
    toks, ref_logits = want
    mdl = build_model(CFG, fusion_mode=fusion)
    cache = mdl.init_cache(2, 64)
    lg, _ = mdl.prefill(params, tokens=toks[:, :40], cache=cache)
    assert np.abs(ref_logits).max() > 1.0
    np.testing.assert_allclose(np.asarray(lg)[..., :CFG.vocab_size],
                               ref_logits[:, :40], atol=ATOL, rtol=0)


@pytest.mark.parametrize("plen", [9, WINDOW, 27])
def test_prefill_then_decode_matches_reference(params, want, plen):
    """Prefill ``plen`` tokens (short of, equal to and past the window),
    then decode to position 47 one token at a time: the ring wraps
    during decode, and every logit agrees with the reference's forward
    over the whole sequence."""
    toks, ref_logits = want
    mdl = build_model(CFG, fusion_mode="xla")
    cache = mdl.init_cache(2, 64)
    lp, cache = mdl.prefill(params, tokens=toks[:, :plen], cache=cache)
    step = jax.jit(lambda p, c, t, i: mdl.decode_step(p, c, t, i,
                                                      kv_len=i + 1))
    got = [lp]
    for i in range(plen, 47):
        lg, cache = step(params, cache, toks[:, i:i + 1], jnp.int32(i))
        got.append(lg)
    got = np.concatenate([np.asarray(g) for g in got], axis=1)
    np.testing.assert_allclose(got[..., :CFG.vocab_size],
                               ref_logits[:, :47], atol=ATOL, rtol=0)


class Recorder:
    """Records ``(name, meta)`` of every span."""

    def __init__(self):
        self.seen: list = []

    @contextlib.contextmanager
    def __call__(self, name, **meta):
        self.seen.append((name, meta))
        yield


@pytest.fixture(scope="module")
def served(params):
    """Requests served by the stitched batcher (2 slots, refills), with
    prompts short of, equal to and past the window: every ring wraps
    during decode, the last one inside its prefill too."""
    jax.config.update("jax_default_matmul_precision", "highest")
    mdl = build_model(CFG, fusion_mode="stitched")
    cb = ContinuousBatcher(mdl, params, n_slots=2, max_len=64)
    prompts = [_tokens(n, seed=n, batch=1)[0] for n in (9, WINDOW, 27)]
    rids = [cb.submit(p, max_new=20) for p in prompts]
    rec = Recorder()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(spans, "span", rec)
        out = cb.run()
    return cb, [(p, out[r]) for p, r in zip(prompts, rids)], rec.seen


def test_batcher_serves_the_reference_greedy_tokens(params, served):
    """Through ``ContinuousBatcher`` on the stitched path (one-slot
    prefill of the exact prompt into the ring, the vmap'd decode wave):
    at every served position the reference's logit of the served token
    is its best, to the tolerance."""
    cb, reqs, _ = served
    assert not any(r.fallbacks or r.quarantined for r in
                   cb._decode_wave.reports() + cb._prefill.reports())
    for prompt, out in reqs:
        assert len(out) == 20
        seq = np.concatenate([prompt, np.asarray(out[:-1], np.int32)])
        lg = np.asarray(ref.logits(M, params, seq[None]))[0]
        pos = np.arange(len(prompt) - 1, len(seq))
        gap = lg[pos].max(-1) - lg[pos, out]
        assert gap.max() <= ATOL, gap


def test_prefill_spans_count_ring_leaves_and_wrapping(served):
    """``serve.prefill`` says whether the prompt outran the window;
    ``prefill.cache_write`` counts every leaf written, the rings'
    included: 8 layers x (k, v)."""
    cb, _, seen = served
    pre = [m for n, m in seen if n == "serve.prefill"]
    assert [(m["plen"], m["wrapped"]) for m in pre] == [
        (9, False), (WINDOW, False), (27, True)]
    writes = [m for n, m in seen if n == "prefill.cache_write"]
    assert writes and all(m == {"leaves": 16} for m in writes)


@pytest.mark.parametrize("family", ["hybrid", "moe", "dense"])
def test_windowed_prompts_are_never_padded(params, family):
    """A pad key would land in a windowed layer's ring and overwrite a
    real one, so a model with a window prefills exact prompt lengths
    whatever family it is labelled."""
    cfg = dataclasses.replace(CFG, family=family)
    cb = ContinuousBatcher(build_model(cfg, fusion_mode="xla"), params,
                           n_slots=1, max_len=32)
    assert not cb._pad_prompts


def test_ring_cache_shapes_of_the_cell():
    """The cell's 12 layers at ``max_len`` 12288: 3 global caches of
    12288 positions and 9 rings of 4096, 24 leaves, 302 MB a slot."""
    cfg = dataclasses.replace(get_config("smallthinker-21b-a3b"),
                              n_layers=12, layer_pattern="ASSS" * 3)
    mdl = build_model(cfg)
    shapes = jax.eval_shape(lambda: mdl.init_cache(1, 12288))
    lens = [b["attn"]["k"].shape[2] for b in shapes["blocks"]]
    assert lens == [12288, 4096, 4096, 4096] * 3
    leaves = jax.tree_util.tree_leaves(shapes)
    assert len(leaves) == 24
    assert sum(x.size * 4 for x in leaves) == \
        (3 * 12288 + 9 * 4096) * 2 * 4 * 128 * 4


def test_rope_only_on_windowed_layers(params, monkeypatch):
    """RoPE rotates the "S" layers' queries and keys and not the global
    ones'; each windowed layer hands its window to ``attention``."""
    calls, windows = [], []
    real_rope, real_attn = L.rope, L.ops.attention

    def rope(*a, **kw):
        calls.append(a[0].shape)
        return real_rope(*a, **kw)

    def attention(*a, **kw):
        windows.append(kw.get("window", 0))
        return real_attn(*a, **kw)

    monkeypatch.setattr(L, "rope", rope)
    monkeypatch.setattr(L.ops, "attention", attention)
    mdl = build_model(CFG, fusion_mode="xla")
    mdl.apply(params, tokens=_tokens(12))
    assert len(calls) == CFG.layer_pattern.count("S") == 6
    assert windows == [0, WINDOW, WINDOW, WINDOW] * 2


def test_routing_follows_the_pre_attention_input(params, monkeypatch):
    """The router reads each layer's attention input (``norm1``'s
    output), not the FFN's: ``moe_share_route`` sees exactly what
    ``attn_apply`` sees, and routing on the FFN's input instead moves
    the logits."""
    routed, attended = [], []
    real_route, real_attn = L.moe_share_route, L.attn_apply

    def route(cfg, p, xt):
        routed.append(np.asarray(xt))
        return real_route(cfg, p, xt)

    def attn_apply(cfg, p, x, **kw):
        attended.append(np.asarray(x).reshape(-1, x.shape[-1]))
        return real_attn(cfg, p, x, **kw)

    monkeypatch.setattr(L, "moe_share_route", route)
    monkeypatch.setattr(L, "attn_apply", attn_apply)
    toks = _tokens(20)
    mdl = build_model(CFG, fusion_mode="xla")
    lg = np.asarray(mdl.apply(params, tokens=toks)[0])[..., :CFG.vocab_size]
    assert len(routed) == len(attended) == CFG.n_layers
    for r, a in zip(routed, attended):
        np.testing.assert_array_equal(r, a)
    late = build_model(dataclasses.replace(CFG, pre_attention_router=False),
                       fusion_mode="xla")
    lg_late = np.asarray(late.apply(params, tokens=toks)[0])
    assert np.abs(lg_late[..., :CFG.vocab_size] - lg).max() > 1e-2


def test_each_departure_is_seen(params, want):
    """The reference tells apart each mathematics the tolerance has to
    catch: a window one wider, RoPE on every layer, SiLU experts."""
    toks, ref_logits = want
    for change in (dict(sliding_window=WINDOW + 1),
                   dict(position_embedding="rope"),
                   dict(activation="silu")):
        mdl = build_model(dataclasses.replace(CFG, **change),
                          fusion_mode="xla")
        lg = np.asarray(mdl.apply(params, tokens=toks)[0])
        assert np.abs(lg[..., :CFG.vocab_size] - ref_logits).max() > 1e-2, \
            change


def test_expert_shares_sum_to_the_uncut_layer(params):
    """Four chips of four experts each: their layers' outputs, with
    what every chip computes alike (the residual and attention) counted
    once, add up to the reference's layer with all 16 experts held."""
    whole = dataclasses.replace(CFG, experts_held=0, expert_offset=0)
    mw = model_dict(whole)
    p = jax.jit(lambda k: ref.init_params(mw, k))(jax.random.PRNGKey(5))
    lp = p["blocks"][1]                       # a windowed layer
    h = jax.random.normal(jax.random.PRNGKey(6), (2, 24, CFG.d_model))
    x = ref._rms(h, lp["norm1"]["g"], CFG.norm_eps)
    base = h + ref._attention(mw, lp["attn"], x, "S", "highest")
    want = base + ref.expert_share(
        mw, lp["moe"], ref._rms(base, lp["norm2"]["g"], CFG.norm_eps),
        ref.route(mw, lp["moe"], x))

    from repro.models.model import pattern_block_apply
    fm = L.FusionMode("xla")
    total = jnp.zeros_like(h)
    for off in range(0, 16, 4):
        share = dataclasses.replace(CFG, experts_held=4, expert_offset=off)
        held = dict(lp, moe={k: (v if k == "router" else v[off:off + 4])
                             for k, v in lp["moe"].items()})
        out, _ = pattern_block_apply(share, held, h, fm=fm,
                                     positions=jnp.arange(24), kind="S")
        total = total + out
    np.testing.assert_allclose(np.asarray(total - 3 * base),
                               np.asarray(want), atol=ATOL, rtol=0)
    # one chip's share is a part, not the whole
    part = want - base
    assert float(jnp.max(jnp.abs(part))) > 1e-2
