"""Granite-4.0-H (``layer_pattern`` hybrid stack, NoPE GQA attention,
routed experts on one chip's share plus a shared MLP) against the plain
float32 reference ``chipbench/refs/granite_hybrid.py``, at a reduced size
on the CPU with seeded random weights.

Tolerances: the program and the reference both compute in float32 and
differ only in the order of their sums (the chunked SSD scan against the
token-by-token recurrence, blocked attention against one softmax, the
experts' products fused differently).  The logits here are below 0.2 in
magnitude (divided by ``logits_scaling`` 16), and such reorderings move
them by a few 1e-8; 1e-6 leaves room for that and fails any change of
the mathematics (a dropped multiplier, a RoPE, another scale, a routed
token lost, each moves them by 1e-3 or more).
"""
import dataclasses
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import ops
from repro.models import build_model
from repro.models import layers as L
from repro.serving.scheduler import ContinuousBatcher

REF_PATH = (Path(__file__).resolve().parents[1] / "chipbench" / "refs"
            / "granite_hybrid.py")
ATOL = 1e-6


def _load_ref():
    spec = importlib.util.spec_from_file_location("granite_hybrid_ref",
                                                  REF_PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load_ref()

#: reduced granite: "MAM", GQA 4 query heads over 2, 8 experts top-3,
#: this chip holding experts 2-4
CFG = get_config("granite-4.0-h-small").reduced(
    n_experts=8, top_k=3, experts_held=3, expert_offset=2)


def model_dict(cfg) -> dict:
    """The reference's model mapping for an ``ArchConfig``."""
    return dict(
        n_layers=cfg.n_layers, layer_pattern=cfg.layer_pattern,
        d_model=cfg.d_model, d_inner=cfg.resolved_d_inner,
        ssm_state=cfg.ssm_state, ssm_head_dim=cfg.ssm_head_dim,
        conv_width=cfg.conv_width, n_heads=cfg.n_heads,
        n_kv_heads=cfg.n_kv_heads, head_dim=cfg.resolved_head_dim,
        d_ff=cfg.d_ff, d_ff_shared=cfg.d_ff_shared,
        n_experts=cfg.n_experts, top_k=cfg.top_k,
        experts_held=cfg.n_experts_held, expert_offset=cfg.expert_offset,
        vocab_size=cfg.vocab_size, vocab_rows=cfg.padded_vocab,
        norm_eps=cfg.norm_eps,
        embedding_multiplier=cfg.embedding_multiplier,
        residual_multiplier=cfg.residual_multiplier,
        logits_scaling=cfg.logits_scaling,
        attention_multiplier=cfg.attention_multiplier,
        tie_embeddings=False)


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


@pytest.mark.parametrize("fusion", ["xla", "stitched"])
def test_prefill_then_decode_matches_reference(params, fusion):
    """Prefill 30 tokens into the cache, then decode 9 one at a time:
    every logit agrees with the reference's forward over the whole
    sequence (the Mamba-2 state, the conv state and the KV cache carry
    the prompt)."""
    mdl = build_model(CFG, fusion_mode=fusion)
    toks = _tokens(40)
    want = np.asarray(ref.logits(M, params, toks))
    assert np.abs(want).max() > 0.05
    cache = mdl.init_cache(2, 64)
    lp, cache = mdl.prefill(params, tokens=toks[:, :30], cache=cache)
    got = [lp[:, :, :CFG.vocab_size]]
    for i in range(30, 39):
        lg, cache = mdl.decode_step(params, cache, toks[:, i:i + 1], pos=i,
                                    kv_len=i + 1)
        got.append(lg[:, :, :CFG.vocab_size])
    got = np.concatenate([np.asarray(g) for g in got], axis=1)
    np.testing.assert_allclose(got, want[:, :39], atol=ATOL, rtol=0)


@pytest.fixture(scope="module")
def served(params):
    """Requests served by the stitched batcher (3 slots, refills)."""
    jax.config.update("jax_default_matmul_precision", "highest")
    mdl = build_model(CFG, fusion_mode="stitched")
    cb = ContinuousBatcher(mdl, params, n_slots=3, max_len=48)
    prompts = [_tokens(n, seed=n, batch=1)[0] for n in (9, 14, 9, 11)]
    rids = [cb.submit(p, max_new=8) for p in prompts]
    out = cb.run()
    return cb, [(p, out[r]) for p, r in zip(prompts, rids)]


def test_batcher_serves_the_reference_greedy_tokens(params, served):
    """Through ``ContinuousBatcher`` on the stitched path (one-slot
    prefill of the exact prompt, the vmap'd decode wave): at every
    served position the reference's logit of the served token is its
    best, to the tolerance."""
    cb, reqs = served
    assert not any(r.fallbacks or r.quarantined for r in
                   cb._decode_wave.reports() + cb._prefill.reports())
    for prompt, out in reqs:
        assert len(out) == 8
        seq = np.concatenate([prompt, np.asarray(out[:-1], np.int32)])
        lg = np.asarray(ref.logits(M, params, seq[None]))[0]
        pos = np.arange(len(prompt) - 1, len(seq))
        gap = lg[pos].max(-1) - lg[pos, out]
        assert gap.max() <= ATOL, gap


def test_planner_counts_top_k_once_per_layer(served):
    """The routing chain's ``top_k`` is an OPAQUE group boundary: the
    decode wave's planner meets one per layer; zamba2's meets none."""
    cb, _ = served
    (rep,) = cb._decode_wave.reports()
    assert rep.opaque_prims["top_k"] == CFG.n_layers
    assert rep.opaque_prims["pallas_call"] > 0

    zcfg = get_config("zamba2-1.2b").reduced()
    zmdl = build_model(zcfg, fusion_mode="stitched")
    zcb = ContinuousBatcher(zmdl, zmdl.init(jax.random.PRNGKey(0)),
                            n_slots=2, max_len=32)
    zcb.submit(_tokens(6, batch=1)[0], max_new=3)
    zcb.run()
    (zrep,) = zcb._decode_wave.reports()
    assert "top_k" not in zrep.opaque_prims and zrep.opaque_prims


def test_expert_shares_sum_to_the_uncut_layer(params):
    """Four chips of two experts each: the program's routed parts, with
    the shared MLP counted once, add up to the reference's whole FFN
    block with all 8 experts held."""
    whole = dataclasses.replace(CFG, experts_held=0, expert_offset=0)
    mw = model_dict(whole)
    p = jax.jit(lambda k: ref.init_params(mw, k))(jax.random.PRNGKey(5))
    lp = p["blocks"][0]
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 7, CFG.d_model))
    want = ref.ffn_block(mw, lp, x)

    fm = L.FusionMode("xla")
    total = L.mlp_apply(whole, lp["shared_mlp"], x, fm)
    for off in range(0, 8, 2):
        share = dataclasses.replace(CFG, experts_held=2, expert_offset=off)
        held = {k: (v if k == "router" else v[off:off + 2])
                for k, v in lp["moe"].items()}
        total = total + L.moe_share_apply(share, held, x, fm)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=1e-5, rtol=1e-5)
    # the held share is a part, not the whole: another share differs
    part = L.moe_share_apply(CFG, {k: (v if k == "router" else v[2:5])
                                   for k, v in lp["moe"].items()}, x, fm)
    assert float(jnp.max(jnp.abs(part))) > 1e-3


def test_nope_and_scale_reach_the_attention_kernels(params, monkeypatch):
    """No rotary embedding, and the configured 1/128 scale (not
    1/sqrt(head_dim)) handed to ``attention`` in prefill and to
    ``decode_attention`` in decode."""
    seen = []
    real_attn, real_dec = ops.attention, ops.decode_attention

    def attention(*a, **kw):
        seen.append(("attention", kw.get("scale")))
        return real_attn(*a, **kw)

    def decode_attention(*a, **kw):
        seen.append(("decode_attention", kw.get("scale")))
        return real_dec(*a, **kw)

    def no_rope(*a, **kw):
        raise AssertionError("RoPE applied under position_embedding=nope")

    monkeypatch.setattr(ops, "attention", attention)
    monkeypatch.setattr(ops, "decode_attention", decode_attention)
    monkeypatch.setattr(L, "rope", no_rope)
    mdl = build_model(CFG, fusion_mode="xla")
    toks = _tokens(12)
    cache = mdl.init_cache(2, 16)
    _, cache = mdl.prefill(params, tokens=toks[:, :11], cache=cache)
    mdl.decode_step(params, cache, toks[:, 11:], pos=11, kv_len=12)
    assert CFG.attention_multiplier == 1 / 128 != CFG.resolved_head_dim ** -.5
    assert seen == [("attention", 1 / 128), ("decode_attention", 1 / 128)]
