"""Plain float32 reference of a Mamba-2 layer stack, with an optional
Zamba-style shared attention + MLP block.

It imports nothing of the system under test.  It holds the weights'
layout (the pytree the served model takes), draws the weights from a
key, and computes the logits of whole sequences in plain ``jax.numpy``:
the Mamba-2 recurrence token by token (no chunking), causal softmax
attention over the whole sequence, and every matrix product in float32
at ``highest`` precision.  ``mm="bf16_3x"`` computes every matrix
product from bfloat16 halves instead (what ``precision="high"`` does on
a TPU), which is the control that the comparison has to fail.

The model ``m`` is the ``model`` mapping of a configuration file:

* ``n_layers``, ``d_model``, ``d_inner``, ``ssm_state``, ``ssm_head_dim``,
  ``conv_width``, ``vocab_size``, ``vocab_rows`` (rows of the embedding
  table and columns of the output head, padding included), ``norm_eps``,
  ``tie_embeddings`` (the head is the embedding's transpose);
* for the shared block (``attn_every`` > 0, applied before layer ``i``
  whenever ``i % attn_every == 0``): ``n_heads``, ``head_dim``,
  ``d_ff``, ``rope_theta``.  Its input is the RMS-normed concatenation
  of the hidden state and the initial embedding (Zamba); its MLP is
  GeGLU with tanh GELU.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------
def _normal(key, shape, std):
    return jax.random.normal(key, shape, F32) * std


def _gain(key, n):
    return 1.0 + _normal(key, (n,), 0.1)


def _mamba_layer(m, key):
    d, di, n = m["d_model"], m["d_inner"], m["ssm_state"]
    h = di // m["ssm_head_dim"]
    cd = di + 2 * n
    ks = jax.random.split(key, 9)
    dt = jnp.exp(jax.random.uniform(ks[5], (h,), F32, math.log(1e-3),
                                    math.log(1e-1)))
    return {
        "norm1": {"g": _gain(ks[0], d)},
        "mamba": {
            "in_proj": _normal(ks[1], (d, 2 * di + 2 * n + h),
                               1 / math.sqrt(d)),
            "conv_w": _normal(ks[2], (m["conv_width"], cd), 0.2),
            "conv_b": _normal(ks[3], (cd,), 0.1),
            # A in [-16, -1], dt in [1e-3, 1e-1]: the published init
            "A_log": jnp.log(jax.random.uniform(ks[4], (h,), F32, 1.0,
                                                16.0)),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1(dt)
            "D": 1.0 + _normal(ks[6], (h,), 0.1),
            "norm_g": _gain(ks[7], di),
            "out_proj": _normal(ks[8], (di, d), 1 / math.sqrt(di)),
        },
    }


def init_params(m: dict, key) -> dict:
    """Every weight in float32, drawn from ``key``.  Jit it: the whole
    tree is made on the device in one call."""
    d, rows = m["d_model"], m["vocab_rows"]
    ks = jax.random.split(key, m["n_layers"] + 4)
    embed = _normal(ks[-1], (rows, d), 0.02)
    p = {"embed": embed,
         "final_norm": {"g": _gain(ks[-2], d)},
         "lm_head": (embed.T if m.get("tie_embeddings") else
                     _normal(ks[-3], (d, rows), 1 / math.sqrt(d)))}
    layers = [_mamba_layer(m, ks[i]) for i in range(m["n_layers"])]
    if m.get("attn_every", 0):
        p["blocks"] = layers
        hd = m["n_heads"] * m["head_dim"]
        k = jax.random.split(ks[-4], 7)
        p["shared_attn"] = {
            "norm1": {"g": _gain(k[0], 2 * d)},
            "attn": {"wq": _normal(k[1], (2 * d, hd), 1 / math.sqrt(2 * d)),
                     "wk": _normal(k[2], (2 * d, hd), 1 / math.sqrt(2 * d)),
                     "wv": _normal(k[3], (2 * d, hd), 1 / math.sqrt(2 * d)),
                     "wo": _normal(k[4], (hd, d), 1 / math.sqrt(hd))},
            "norm2": {"g": _gain(k[5], d)},
            "mlp": _mlp_init(m, k[6]),
        }
    else:
        p["blocks"] = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                             *layers)
    return p


def _mlp_init(m, key):
    d, ff = m["d_model"], m["d_ff"]
    k = jax.random.split(key, 3)
    return {"w_gate": _normal(k[0], (d, ff), 1 / math.sqrt(d)),
            "w_up": _normal(k[1], (d, ff), 1 / math.sqrt(d)),
            "w_down": _normal(k[2], (ff, d), 1 / math.sqrt(ff))}


# ---------------------------------------------------------------------------
# arithmetic
# ---------------------------------------------------------------------------
def _einsum(eq, a, b, mm):
    if mm == "highest":
        return jnp.einsum(eq, a, b, precision=HIGHEST,
                          preferred_element_type=F32)
    if mm == "bf16_3x":
        ah = a.astype(jnp.bfloat16)
        al = (a - ah.astype(F32)).astype(jnp.bfloat16)
        bh = b.astype(jnp.bfloat16)
        bl = (b - bh.astype(F32)).astype(jnp.bfloat16)

        def part(x, y):
            return jnp.einsum(eq, x, y, preferred_element_type=F32)
        return part(ah, bh) + (part(ah, bl) + part(al, bh))
    raise ValueError(f"unknown matmul mode {mm!r}")


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * g


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def _mamba(m, p, x, mm):
    """x: [B, S, d] -> [B, S, d], the recurrence one token at a time."""
    B, S, _ = x.shape
    di, n, hp = m["d_inner"], m["ssm_state"], m["ssm_head_dim"]
    h, w = di // hp, m["conv_width"]
    cd = di + 2 * n
    zxbcdt = _einsum("bsd,de->bse", x, p["in_proj"], mm)
    z, xbc, dt_raw = (zxbcdt[..., :di], zxbcdt[..., di:di + cd],
                      zxbcdt[..., di + cd:])
    padded = jnp.pad(xbc, ((0, 0), (w - 1, 0), (0, 0)))
    conv = sum(padded[:, j:j + S] * p["conv_w"][j] for j in range(w))
    xbc = _silu(conv + p["conv_b"])
    xs = xbc[..., :di].reshape(B, S, h, hp)
    bm, cm = xbc[..., di:di + n], xbc[..., di + n:]
    dt = jax.nn.softplus(dt_raw + p["dt_bias"])              # [B, S, H]
    a = -jnp.exp(p["A_log"])

    def step(state, inp):                                   # [B, H, P, N]
        dt_t, x_t, b_t, c_t = inp
        state = (state * jnp.exp(dt_t * a)[:, :, None, None]
                 + (dt_t[:, :, None] * x_t)[..., None] * b_t[:, None, None])
        y_t = jnp.sum(state * c_t[:, None, None], axis=-1)  # [B, H, P]
        return state, y_t

    seq = tuple(jnp.moveaxis(v, 1, 0) for v in (dt, xs, bm, cm))
    _, y = jax.lax.scan(step, jnp.zeros((B, h, hp, n), F32), seq)
    y = jnp.moveaxis(y, 0, 1) + p["D"][:, None] * xs        # [B, S, H, P]
    y = y.reshape(B, S, di) * _silu(z)
    y = _rms(y, p["norm_g"], m["norm_eps"])
    return _einsum("bse,ed->bsd", y, p["out_proj"], mm)


def _rope(x, theta):
    """x: [B, S, H, D], positions 0..S-1, halves rotated."""
    S, D = x.shape[1], x.shape[-1]
    half = D // 2
    freqs = jnp.exp(-jnp.arange(half, dtype=F32) * (math.log(theta) / half))
    ang = jnp.arange(S, dtype=F32)[:, None] * freqs          # [S, half]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _shared_block(m, p, h, emb0, mm):
    B, S, _ = h.shape
    nh, hd = m["n_heads"], m["head_dim"]
    u = _rms(jnp.concatenate([h, emb0], -1), p["norm1"]["g"], m["norm_eps"])
    a = p["attn"]
    q = _einsum("bsd,de->bse", u, a["wq"], mm).reshape(B, S, nh, hd)
    k = _einsum("bsd,de->bse", u, a["wk"], mm).reshape(B, S, nh, hd)
    v = _einsum("bsd,de->bse", u, a["wv"], mm).reshape(B, S, nh, hd)
    q, k = _rope(q, m["rope_theta"]), _rope(k, m["rope_theta"])
    s = _einsum("bqhd,bkhd->bhqk", q, k, mm) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    s = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    probs = s / jnp.sum(s, axis=-1, keepdims=True)
    o = _einsum("bhqk,bkhd->bqhd", probs, v, mm).reshape(B, S, nh * hd)
    h = h + _einsum("bse,ed->bsd", o, a["wo"], mm)
    x = _rms(h, p["norm2"]["g"], m["norm_eps"])
    ml = p["mlp"]
    g = _gelu_tanh(_einsum("bsd,df->bsf", x, ml["w_gate"], mm))
    up = _einsum("bsd,df->bsf", x, ml["w_up"], mm)
    return h + _einsum("bsf,fd->bsd", g * up, ml["w_down"], mm)


def logits(m: dict, p: dict, tokens, mm: str = "highest"):
    """tokens [B, S] int32 -> logits [B, S, vocab_size] (float32)."""
    h = p["embed"][tokens]
    emb0 = h
    every = m.get("attn_every", 0)
    for i in range(m["n_layers"]):
        if every and i % every == 0:
            h = _shared_block(m, p["shared_attn"], h, emb0, mm)
        lp = (p["blocks"][i] if isinstance(p["blocks"], list) else
              jax.tree_util.tree_map(lambda x: x[i], p["blocks"]))
        h = h + _mamba(m, lp["mamba"], _rms(h, lp["norm1"]["g"],
                                             m["norm_eps"]), mm)
    h = _rms(h, p["final_norm"]["g"], m["norm_eps"])
    return _einsum("bsd,dv->bsv", h, p["lm_head"][:, :m["vocab_size"]], mm)


def readings(m: dict, p: dict, tokens, gather, mm: str = "highest"):
    """At every position of ``tokens`` [B, S]: the best logit, the logit
    of ``gather`` [B, S] there, and the token put first."""
    lg = logits(m, p, tokens, mm)
    best = jnp.max(lg, axis=-1)
    got = jnp.take_along_axis(lg, gather[..., None], axis=-1)[..., 0]
    return best, got, jnp.argmax(lg, axis=-1).astype(jnp.int32)


def attention_bytes_per_row(m: dict, seq: int) -> int:
    """Bytes of the largest temporaries one row of ``readings`` holds at
    once (the attention scores and the logits), for sizing blocks."""
    att = (m["n_heads"] * seq * seq * 4 * 3) if m.get("attn_every") else 0
    return att + seq * m["vocab_size"] * 4 * 3


def param_count(m: dict) -> int:
    shapes = jax.eval_shape(lambda k: init_params(m, k),
                            jax.random.PRNGKey(0))
    return sum(int(np.prod(x.shape))
               for x in jax.tree_util.tree_leaves(shapes))
