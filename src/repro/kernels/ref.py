"""Pure-jnp oracles for every Pallas kernel in this package.

Each function is the numerical ground truth the kernels are tested
against (``tests/test_kernels.py`` sweeps shapes/dtypes and asserts
allclose).  They are also the ``fusion_mode="xla"`` execution path of the
model zoo.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


# --------------------------------------------------------------------------
# normalization (paper Fig. 1 flagship patterns)
# --------------------------------------------------------------------------
def layernorm(x, gamma, beta, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    return (y * gamma + beta).astype(x.dtype)


def rmsnorm(x, gamma, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps) * gamma).astype(x.dtype)


def softmax(x, axis: int = -1):
    xf = x.astype(jnp.float32)
    m = jnp.max(xf, axis=axis, keepdims=True)
    e = jnp.exp(xf - m)
    return (e / jnp.sum(e, axis=axis, keepdims=True)).astype(x.dtype)


def bias_gelu(x, bias):
    """Megatron-style fused bias + tanh-GELU (expensive-ew mid-chain)."""
    xf = (x + bias).astype(jnp.float32)
    inner = 0.7978845608028654 * (xf + 0.044715 * xf ** 3)
    return (0.5 * xf * (1.0 + jnp.tanh(inner))).astype(x.dtype)


# --------------------------------------------------------------------------
# attention
# --------------------------------------------------------------------------
def attention(q, k, v, *, causal: bool = True, scale: float | None = None,
              window: int = 0):
    """Reference multi-head attention (repeat-free GQA).

    q: [B, Hq, Sq, D]; k, v: [B, Hkv, Skv, D] (GQA: Hq % Hkv == 0).
    ``window`` > 0 (causal only): query ``i`` sees key ``j`` iff
    ``0 <= i + Skv - Sq - j < window``.
    The grouped-query einsum contracts against the UNEXPANDED kv tensors:
    materializing ``jnp.repeat(k, group)`` makes GSPMD all-gather the KV
    cache across the TP axis at decode shapes (1 GiB/layer for
    deepseek-67b x decode_32k -- EXPERIMENTS.md §Perf hillclimb 2).
    """
    B, Hq, Sq, D = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    group = Hq // Hkv
    scale = scale if scale is not None else 1.0 / np.sqrt(D)
    qg = q.reshape(B, Hkv, group, Sq, D)
    logits = jnp.einsum("bkgqd,bksd->bkgqs", qg, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        mask = jnp.tril(jnp.ones((Sq, Skv), bool), k=Skv - Sq)
        if window:
            mask &= ~jnp.tril(jnp.ones((Sq, Skv), bool), k=Skv - Sq - window)
        logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgqs,bksd->bkgqd", probs.astype(q.dtype), v)
    return out.reshape(B, Hq, Sq, D)


def decode_attention(q, k_cache, v_cache, lengths=None, scale=None):
    """Single-token decode: q [B, Hq, D]; caches [B, Hkv, S, D]."""
    B, Hq, D = q.shape
    Hkv, S = k_cache.shape[1], k_cache.shape[2]
    group = Hq // Hkv
    sc = scale if scale is not None else 1.0 / np.sqrt(D)
    qg = q.reshape(B, Hkv, group, D)
    logits = jnp.einsum("bkgd,bksd->bkgs", qg, k_cache,
                        preferred_element_type=jnp.float32) * sc
    if lengths is not None:
        mask = jnp.arange(S)[None, :] < lengths[:, None]
        logits = jnp.where(mask[:, None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgs,bksd->bkgd", probs.astype(q.dtype), v_cache)
    return out.reshape(B, Hq, D)


# --------------------------------------------------------------------------
# Mamba2 SSD (state-space duality) chunked scan
# --------------------------------------------------------------------------
def ssd_scan(x, dt, A, B, C, *, chunk: int = 64, init_state=None):
    """Chunked SSD scan (Mamba-2, arXiv:2405.21060 listing 1 semantics).

    x:  [batch, L, H, P]   inputs (already gated/projected)
    dt: [batch, L, H]      softplus-activated step sizes (> 0)
    A:  [H]                negative per-head decay
    B:  [batch, L, N]      input projections  (shared across heads, G=1)
    C:  [batch, L, N]      output projections
    returns y: [batch, L, H, P], final_state: [batch, H, P, N]
    """
    b, L, H, P = x.shape
    N = B.shape[-1]
    assert L % chunk == 0, "sequence must be divisible by chunk"
    nc = L // chunk
    in_dtype = x.dtype

    # f32 accumulation throughout (matches the Pallas kernel)
    xc = x.reshape(b, nc, chunk, H, P).astype(jnp.float32)
    dtc = dt.reshape(b, nc, chunk, H).astype(jnp.float32)
    Bc = B.reshape(b, nc, chunk, N).astype(jnp.float32)
    Cc = C.reshape(b, nc, chunk, N).astype(jnp.float32)
    A = A.astype(jnp.float32)

    a = dtc * A[None, None, None, :]                  # [b,nc,c,H] log-decay
    cum = jnp.cumsum(a, axis=2)                       # within-chunk cumsum

    # intra-chunk (quadratic within chunk)
    seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]   # [b,nc,c,c,H]
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    Lmat = jnp.where(causal[None, None, :, :, None], jnp.exp(seg), 0.0)
    cb = jnp.einsum("bzcn,bzsn->bzcs", Cc, Bc)            # [b,nc,c,c]
    y_intra = jnp.einsum("bzcs,bzcsh,bzsh,bzshp->bzchp",
                         cb, Lmat, dtc, xc)

    # chunk states: contribution of each chunk to the running state
    decay_states = jnp.exp(cum[:, :, -1:, :] - cum)       # [b,nc,c,H]
    states = jnp.einsum("bzsn,bzsh,bzsh,bzshp->bzhpn",
                        Bc, decay_states, dtc, xc)        # [b,nc,H,P,N]

    # inter-chunk recurrence over chunk index
    chunk_decay = jnp.exp(cum[:, :, -1, :])               # [b,nc,H]
    h0 = (jnp.zeros((b, H, P, N), jnp.float32) if init_state is None
          else init_state.astype(jnp.float32))

    def step(h, inp):
        dec, st = inp                                      # [b,H], [b,H,P,N]
        h_new = h * dec[:, :, None, None] + st
        return h_new, h

    (h_final, h_prevs) = jax.lax.scan(
        step, h0, (jnp.moveaxis(chunk_decay, 1, 0), jnp.moveaxis(states, 1, 0)))
    h_prevs = jnp.moveaxis(h_prevs, 0, 1)                  # [b,nc,H,P,N]

    state_decay = jnp.exp(cum)                             # [b,nc,c,H]
    y_inter = jnp.einsum("bzcn,bzch,bzhpn->bzchp",
                         Cc, state_decay, h_prevs)

    y = (y_intra + y_inter).reshape(b, L, H, P).astype(in_dtype)
    return y, h_final
