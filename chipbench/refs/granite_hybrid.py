"""Plain float32 reference of a Granite-4.0-H layer stack, on one chip's
share of the experts.

It imports nothing of the system under test.  The Mamba-2 mixer, the
norms and the matrix-product modes are those of ``mamba2_stack.py``
beside it (the recurrence stepped token by token, every product in
float32 at ``highest``, or from bfloat16 halves with ``mm="bf16_3x"``,
the control).  It holds the weights' layout (the pytree the served model
takes), draws the weights from a key, and computes the logits of whole
sequences, following ``GraniteMoeHybridDecoderLayer`` in transformers'
``modeling_granitemoehybrid.py``:

* the embedding times ``embedding_multiplier``;
* per layer (``layer_pattern``: "M" Mamba-2, "A" attention): RMS norm,
  the mixer, the residual add of its output times
  ``residual_multiplier``; RMS norm, the FFN block (the routed experts'
  share plus the shared gated-SiLU MLP of width ``d_ff_shared``), the
  residual add of its output times ``residual_multiplier``;
* attention: grouped-query (``n_heads`` query heads over ``n_kv_heads``
  key/value heads of ``head_dim``), no positional encoding (NoPE),
  scores scaled by ``attention_multiplier``, causal softmax over the
  whole sequence;
* the router scores all ``n_experts`` experts; a token's gates are the
  softmax over its ``top_k`` largest logits (``TopKGating``); held expert
  ``expert_offset + j`` (of ``experts_held``) adds its gate times
  (silu(x W_gate) * (x W_up)) W_down, with no capacity and nothing
  dropped; the experts not held add nothing;
* the final RMS norm, the head, the logits divided by ``logits_scaling``.

Departures from ``modeling_granitemoehybrid.py``: only the held experts'
share of the routed output (the chip's share of an expert-parallel
deployment; the absent experts' part is left out here and in the
program alike); random weights, the head drawn apart from the embedding
unless ``tie_embeddings`` (tied random weights make each position's own
token the greedy one whatever the stack computes); the experts' gate
and up projections are two tables (``input_linear`` is one table of
both).  Mamba-2 has one B/C group as published, its gated norm over the
whole ``d_inner``.
"""
from __future__ import annotations

import importlib.util
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np


def _sibling(name: str):
    path = Path(__file__).with_name(f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"chipbench_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_m2 = _sibling("mamba2_stack")
F32 = _m2.F32
_normal, _gain, _einsum, _rms, _silu = (_m2._normal, _m2._gain, _m2._einsum,
                                        _m2._rms, _m2._silu)


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------
def _attn(m, key):
    d, dh = m["d_model"], m["head_dim"]
    hq, hkv = m["n_heads"] * dh, m["n_kv_heads"] * dh
    k = jax.random.split(key, 4)
    return {"wq": _normal(k[0], (d, hq), 1 / math.sqrt(d)),
            "wk": _normal(k[1], (d, hkv), 1 / math.sqrt(d)),
            "wv": _normal(k[2], (d, hkv), 1 / math.sqrt(d)),
            "wo": _normal(k[3], (hq, d), 1 / math.sqrt(hq))}


def _ffn(m, key):
    d, ff, fs = m["d_model"], m["d_ff"], m["d_ff_shared"]
    held = m["experts_held"]
    k = jax.random.split(key, 7)
    return {
        "moe": {"router": _normal(k[0], (d, m["n_experts"]),
                                  1 / math.sqrt(d)),
                "w_gate": _normal(k[1], (held, d, ff), 1 / math.sqrt(d)),
                "w_up": _normal(k[2], (held, d, ff), 1 / math.sqrt(d)),
                "w_down": _normal(k[3], (held, ff, d), 1 / math.sqrt(ff))},
        "shared_mlp": {"w_gate": _normal(k[4], (d, fs), 1 / math.sqrt(d)),
                       "w_up": _normal(k[5], (d, fs), 1 / math.sqrt(d)),
                       "w_down": _normal(k[6], (fs, d), 1 / math.sqrt(fs))},
    }


def _layer(m, kind, key):
    k = jax.random.split(key, 4)
    mixer = ({"attn": _attn(m, k[0])} if kind == "A" else
             {"mamba": _m2._mamba_layer(m, k[0])["mamba"]})
    return {"norm1": {"g": _gain(k[1], m["d_model"])}, **mixer,
            "norm2": {"g": _gain(k[2], m["d_model"])}, **_ffn(m, k[3])}


def init_params(m: dict, key) -> dict:
    """Every weight in float32, drawn from ``key``.  Jit it: the whole
    tree is made on the device in one call."""
    d, rows = m["d_model"], m["vocab_rows"]
    ks = jax.random.split(key, m["n_layers"] + 3)
    embed = _normal(ks[-1], (rows, d), 0.02)
    return {"embed": embed,
            "final_norm": {"g": _gain(ks[-2], d)},
            "lm_head": (embed.T if m.get("tie_embeddings") else
                        _normal(ks[-3], (d, rows), 1 / math.sqrt(d))),
            "blocks": [_layer(m, kind, ks[i])
                       for i, kind in enumerate(m["layer_pattern"])]}


# ---------------------------------------------------------------------------
# arithmetic
# ---------------------------------------------------------------------------
def _attention(m, p, x, mm):
    """Causal grouped-query attention with no positional encoding."""
    B, S, _ = x.shape
    hkv, dh = m["n_kv_heads"], m["head_dim"]
    g = m["n_heads"] // hkv
    q = _einsum("bsd,de->bse", x, p["wq"], mm).reshape(B, S, hkv, g, dh)
    k = _einsum("bsd,de->bse", x, p["wk"], mm).reshape(B, S, hkv, dh)
    v = _einsum("bsd,de->bse", x, p["wv"], mm).reshape(B, S, hkv, dh)
    s = _einsum("bqkgd,bskd->bkgqs", q, k, mm) * m["attention_multiplier"]
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    s = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    probs = s / jnp.sum(s, axis=-1, keepdims=True)
    o = _einsum("bkgqs,bskd->bqkgd", probs, v, mm).reshape(B, S, -1)
    return _einsum("bse,ed->bsd", o, p["wo"], mm)


def _glu(x, w_gate, w_up, w_down, mm):
    h = _silu(_einsum("bsd,df->bsf", x, w_gate, mm)) \
        * _einsum("bsd,df->bsf", x, w_up, mm)
    return _einsum("bsf,fd->bsd", h, w_down, mm)


def expert_share(m, p, x, mm="highest"):
    """The held experts' part of the routed output, x [B, S, d]."""
    logits = _einsum("bsd,de->bse", x, p["router"], mm)
    top, idx = jax.lax.top_k(logits, m["top_k"])
    e = jnp.exp(top - jnp.max(top, axis=-1, keepdims=True))
    gates = e / jnp.sum(e, axis=-1, keepdims=True)
    out = jnp.zeros_like(x)
    for j in range(p["w_gate"].shape[0]):
        gate = jnp.sum(jnp.where(idx == m["expert_offset"] + j, gates, 0.0),
                       axis=-1)                               # [B, S]
        out = out + gate[..., None] * _glu(x, p["w_gate"][j], p["w_up"][j],
                                           p["w_down"][j], mm)
    return out


def ffn_block(m, p, x, mm="highest"):
    """The routed share plus the shared MLP, x already normed."""
    sh = p["shared_mlp"]
    return expert_share(m, p["moe"], x, mm) \
        + _glu(x, sh["w_gate"], sh["w_up"], sh["w_down"], mm)


def logits(m: dict, p: dict, tokens, mm: str = "highest"):
    """tokens [B, S] int32 -> logits [B, S, vocab_size] (float32)."""
    eps, r = m["norm_eps"], m["residual_multiplier"]
    h = p["embed"][tokens] * m["embedding_multiplier"]
    for kind, lp in zip(m["layer_pattern"], p["blocks"]):
        x = _rms(h, lp["norm1"]["g"], eps)
        y = (_attention(m, lp["attn"], x, mm) if kind == "A" else
             _m2._mamba(m, lp["mamba"], x, mm))
        h = h + y * r
        h = h + ffn_block(m, lp, _rms(h, lp["norm2"]["g"], eps), mm) * r
    h = _rms(h, p["final_norm"]["g"], eps)
    out = _einsum("bsd,dv->bsv", h, p["lm_head"][:, :m["vocab_size"]], mm)
    return out / m["logits_scaling"]


def readings(m: dict, p: dict, tokens, gather, mm: str = "highest"):
    """At every position of ``tokens`` [B, S]: the best logit, the logit
    of ``gather`` [B, S] there, and the token put first."""
    lg = logits(m, p, tokens, mm)
    best = jnp.max(lg, axis=-1)
    got = jnp.take_along_axis(lg, gather[..., None], axis=-1)[..., 0]
    return best, got, jnp.argmax(lg, axis=-1).astype(jnp.int32)


def attention_bytes_per_row(m: dict, seq: int) -> int:
    """Bytes of the largest temporaries one row of ``readings`` holds at
    once (the attention scores, the logits, the Mamba-2 input projection
    and the experts' hidden activations), for sizing blocks."""
    di, n = m["d_inner"], m["ssm_state"]
    width = (m["n_heads"] * seq + m["vocab_size"]
             + 2 * di + 2 * n + di // m["ssm_head_dim"]
             + 2 * m["d_ff"] + 2 * m["d_ff_shared"])
    return seq * width * 4 * 3


def param_count(m: dict) -> int:
    shapes = jax.eval_shape(lambda k: init_params(m, k),
                            jax.random.PRNGKey(0))
    return sum(int(np.prod(x.shape))
               for x in jax.tree_util.tree_leaves(shapes))
