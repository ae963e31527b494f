"""Plain float32 reference of a SmallThinker layer stack, on one chip's
share of the experts.

It imports nothing of the system under test.  The norms, the rotary
embedding and the matrix-product modes are those of ``mamba2_stack.py``
beside it (every product in float32 at ``highest``, or from bfloat16
halves with ``mm="bf16_3x"``, the control).  It holds the weights'
layout (the pytree the served model takes), draws the weights from a
key, and computes the logits of whole sequences:

* per layer (``layer_pattern``: "A" global attention, "S" attention over
  a sliding window, as ``sliding_window_layout`` gives them): ``x`` the
  RMS norm of the layer's input; the router reads ``x`` (it sits before
  attention); attention of ``x`` and its residual add; the RMS norm of
  the result, the routed experts' share under the gates routed from
  ``x``, and its residual add;
* attention: grouped-query (``n_heads`` query heads over ``n_kv_heads``
  key/value heads of ``head_dim``), no bias, scores scaled by
  ``1/sqrt(head_dim)``.  "S" layers rotate queries and keys at their
  absolute positions (RoPE, ``rope_theta``, halves rotated) and query
  ``i`` sees key ``j`` iff ``0 <= i - j < sliding_window``; "A" layers
  use no position encoding and see every earlier key.  The queries go
  in blocks of ``QUERY_BLOCK``, each against the keys it may see, so a
  row's scores are never held whole (at 12288 positions one row's
  scores are 16.9 GB);
* the router scores all ``n_experts`` experts, softmax over all of them,
  keeps the ``top_k`` most probable and renormalises their
  probabilities to sum to one (``norm_topk_prob``); held expert
  ``expert_offset + j`` (of ``experts_held``) adds its gate times
  (relu(y W_gate) * (y W_up)) W_down (ReGLU), with no capacity and
  nothing dropped; the experts not held add nothing;
* the final RMS norm and the untied head over the vocabulary's columns.

Departures from the published model: only the held experts' share of
the routed output (the chip's share of an expert-parallel deployment;
the absent experts' part is left out here and in the program alike);
random weights.
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
_normal, _gain, _einsum, _rms = _m2._normal, _m2._gain, _m2._einsum, _m2._rms

#: queries per block of the attention, and positions per block of the head
QUERY_BLOCK = 512


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------
def _layer(m, key):
    d, dh, ff = m["d_model"], m["head_dim"], m["d_ff"]
    hq, hkv = m["n_heads"] * dh, m["n_kv_heads"] * dh
    held = m["experts_held"]
    k = jax.random.split(key, 10)
    return {
        "norm1": {"g": _gain(k[0], d)},
        "attn": {"wq": _normal(k[1], (d, hq), 1 / math.sqrt(d)),
                 "wk": _normal(k[2], (d, hkv), 1 / math.sqrt(d)),
                 "wv": _normal(k[3], (d, hkv), 1 / math.sqrt(d)),
                 "wo": _normal(k[4], (hq, d), 1 / math.sqrt(hq))},
        "norm2": {"g": _gain(k[5], d)},
        "moe": {"router": _normal(k[6], (d, m["n_experts"]),
                                  1 / math.sqrt(d)),
                "w_gate": _normal(k[7], (held, d, ff), 1 / math.sqrt(d)),
                "w_up": _normal(k[8], (held, d, ff), 1 / math.sqrt(d)),
                "w_down": _normal(k[9], (held, ff, d), 1 / math.sqrt(ff))},
    }


def init_params(m: dict, key) -> dict:
    """Every weight in float32, drawn from ``key``.  Jit it: the whole
    tree is made on the device in one call."""
    d, rows = m["d_model"], m["vocab_rows"]
    ks = jax.random.split(key, m["n_layers"] + 3)
    return {"embed": _normal(ks[-1], (rows, d), 0.02),
            "final_norm": {"g": _gain(ks[-2], d)},
            "lm_head": _normal(ks[-3], (d, rows), 1 / math.sqrt(d)),
            "blocks": [_layer(m, ks[i]) for i in range(m["n_layers"])]}


# ---------------------------------------------------------------------------
# arithmetic
# ---------------------------------------------------------------------------
def _blocks(x, n: int):
    """x [B, S, ...] -> [S/n, B, n, ...], zero-padded to whole blocks."""
    B, S = x.shape[:2]
    pad = -S % n
    x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
    return jnp.moveaxis(x.reshape((B, (S + pad) // n, n) + x.shape[2:]),
                        1, 0)


def _attention(m, p, x, kind: str, mm):
    """Causal grouped-query attention, windowed with RoPE on "S"."""
    B, S, _ = x.shape
    hkv, dh = m["n_kv_heads"], m["head_dim"]
    g = m["n_heads"] // hkv
    q = _einsum("bsd,de->bse", x, p["wq"], mm).reshape(B, S, hkv * g, dh)
    k = _einsum("bsd,de->bse", x, p["wk"], mm).reshape(B, S, hkv, dh)
    v = _einsum("bsd,de->bse", x, p["wv"], mm).reshape(B, S, hkv, dh)
    windowed = kind == "S"
    if windowed:
        q, k = _m2._rope(q, m["rope_theta"]), _m2._rope(k, m["rope_theta"])
    q = q.reshape(B, S, hkv, g, dh) / math.sqrt(dh)
    n = min(QUERY_BLOCK, S)
    qs = _blocks(q, n)                          # [blocks, B, n, hkv, g, dh]
    padded = len(qs) * n
    # keys a query block may see: the n + W - 1 up to its last query, or
    # every key up to it; ``lead`` zero keys in front keep slices inside
    span = n + m["sliding_window"] - 1 if windowed else padded
    lead = span - n
    k = jnp.pad(k, ((0, 0), (lead, padded - S), (0, 0), (0, 0)))
    v = jnp.pad(v, ((0, 0), (lead, padded - S), (0, 0), (0, 0)))

    def one(args):
        i0, qb = args
        kb = jax.lax.dynamic_slice_in_dim(k, i0, span, axis=1)
        vb = jax.lax.dynamic_slice_in_dim(v, i0, span, axis=1)
        j = i0 - lead + jnp.arange(span)        # the keys' positions
        back = (i0 + jnp.arange(n))[:, None] - j
        seen = (back >= 0) & (j >= 0)
        if windowed:
            seen &= back < m["sliding_window"]
        s = _einsum("bqkgd,bskd->bkgqs", qb, kb, mm)
        s = jnp.where(seen, s, -jnp.inf)
        s = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
        probs = s / jnp.sum(s, axis=-1, keepdims=True)
        return _einsum("bkgqs,bskd->bqkgd", probs, vb, mm)

    o = jax.lax.map(one, (jnp.arange(len(qs)) * n, qs))
    o = jnp.moveaxis(o, 0, 1).reshape(B, padded, -1)[:, :S]
    return _einsum("bse,ed->bsd", o, p["wo"], mm)


def route(m, p, x, mm="highest"):
    """Each token's gate on each held expert, [B, S, held]: softmax over
    all experts, the top ``top_k`` kept and renormalised."""
    logits = _einsum("bsd,de->bse", x, p["router"], mm)
    e = jnp.exp(logits - jnp.max(logits, axis=-1, keepdims=True))
    probs = e / jnp.sum(e, axis=-1, keepdims=True)
    top, idx = jax.lax.top_k(probs, m["top_k"])
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    held = m["expert_offset"] + jnp.arange(p["w_gate"].shape[0])
    return jnp.sum(jnp.where(idx[..., None] == held, top[..., None], 0.0),
                   axis=-2)


def expert_share(m, p, y, gates, mm="highest"):
    """The held experts' part of the routed output for input ``y`` (the
    FFN's normed input) under ``gates`` [B, S, held]."""
    out = jnp.zeros_like(y)
    for j in range(p["w_gate"].shape[0]):
        h = jnp.maximum(_einsum("bsd,df->bsf", y, p["w_gate"][j], mm), 0.0) \
            * _einsum("bsd,df->bsf", y, p["w_up"][j], mm)
        out = out + gates[..., j:j + 1] * _einsum(
            "bsf,fd->bsd", h, p["w_down"][j], mm)
    return out


def hidden(m: dict, p: dict, tokens, mm: str = "highest"):
    """tokens [B, S] int32 -> the final normed hidden state [B, S, d]."""
    eps = m["norm_eps"]
    h = p["embed"][tokens]
    for kind, lp in zip(m["layer_pattern"], p["blocks"]):
        x = _rms(h, lp["norm1"]["g"], eps)
        gates = route(m, lp["moe"], x, mm)
        h = h + _attention(m, lp["attn"], x, kind, mm)
        h = h + expert_share(m, lp["moe"], _rms(h, lp["norm2"]["g"], eps),
                             gates, mm)
    return _rms(h, p["final_norm"]["g"], eps)


def logits(m: dict, p: dict, tokens, mm: str = "highest"):
    """tokens [B, S] int32 -> logits [B, S, vocab_size] (float32)."""
    return _einsum("bsd,dv->bsv", hidden(m, p, tokens, mm),
                   p["lm_head"][:, :m["vocab_size"]], mm)


def readings(m: dict, p: dict, tokens, gather, mm: str = "highest"):
    """At every position of ``tokens`` [B, S]: the best logit, the logit
    of ``gather`` [B, S] there, and the token put first.  The head runs
    on blocks of positions."""
    B, S = tokens.shape
    n = min(QUERY_BLOCK, S)
    head = p["lm_head"][:, :m["vocab_size"]]

    def one(args):
        hb, gb = args
        lg = _einsum("bsd,dv->bsv", hb, head, mm)
        return (jnp.max(lg, axis=-1),
                jnp.take_along_axis(lg, gb[..., None], axis=-1)[..., 0],
                jnp.argmax(lg, axis=-1).astype(jnp.int32))

    outs = jax.lax.map(one, (_blocks(hidden(m, p, tokens, mm), n),
                             _blocks(gather, n)))
    return tuple(jnp.moveaxis(o, 0, 1).reshape(B, -1)[:, :S] for o in outs)


def attention_bytes_per_row(m: dict, seq: int) -> int:
    """Bytes of the largest temporaries one row of ``readings`` holds at
    once, for sizing blocks of rows: one query block's scores against
    the whole sequence (a global layer; three live at once), one block
    of logits (three), and the whole sequence's activations (the hidden
    state, the normed input, queries, keys and values, one expert's two
    hidden products and their product)."""
    n = min(QUERY_BLOCK, seq)
    hq, hkv = m["n_heads"] * m["head_dim"], m["n_kv_heads"] * m["head_dim"]
    scores = m["n_heads"] * n * seq * 3
    head = n * m["vocab_size"] * 3
    acts = seq * (2 * m["d_model"] + hq + 2 * hkv + 3 * m["d_ff"])
    return (scores + head + acts) * 4


def param_count(m: dict) -> int:
    shapes = jax.eval_shape(lambda k: init_params(m, k),
                            jax.random.PRNGKey(0))
    return sum(int(np.prod(x.shape))
               for x in jax.tree_util.tree_leaves(shapes))
