"""Composable model layers (pure-functional JAX, pytree params).

Every memory-intensive pattern (norms, softmax, attention inner loop, SSD
scan) routes through ``repro.kernels.ops`` so the execution mode is
selectable per model:

  fusion_mode="stitched" -> Pallas stitched kernels (the paper's technique)
  fusion_mode="xla"      -> pure-jnp oracles (XLA baseline)

GEMMs stay ``jnp.einsum`` (compute-intensive ops are fusion boundaries in
the paper, handled by cuBLAS there / the MXU here).
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.dist.partitioning import constrain
from repro.kernels import ops


@dataclass(frozen=True)
class FusionMode:
    name: str = "stitched"   # "stitched" | "xla"

    @property
    def use_pallas(self) -> bool:
        return self.name == "stitched"


STITCHED = FusionMode("stitched")
XLA = FusionMode("xla")


def _dense(key, d_in, d_out, dtype):
    scale = 1.0 / math.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------
def norm_init(cfg: ArchConfig, dtype):
    if cfg.norm == "layernorm":
        return {"g": jnp.ones((cfg.d_model,), dtype),
                "b": jnp.zeros((cfg.d_model,), dtype)}
    return {"g": jnp.ones((cfg.d_model,), dtype)}


def norm_apply(cfg: ArchConfig, p, x, fm: FusionMode):
    if cfg.norm == "layernorm":
        return ops.layernorm(x, p["g"], p["b"], cfg.norm_eps,
                             use_pallas=fm.use_pallas)
    return ops.rmsnorm(x, p["g"], cfg.norm_eps, use_pallas=fm.use_pallas)


# ---------------------------------------------------------------------------
# rotary embeddings
# ---------------------------------------------------------------------------
def rope(q, k, positions, theta: float):
    """q, k: [B, H, S, D]; positions: [S] or [B, S] or scalar."""
    D = q.shape[-1]
    half = D // 2
    freqs = jnp.exp(-jnp.arange(0, half, dtype=jnp.float32)
                    * (math.log(theta) / half))
    pos = jnp.asarray(positions, jnp.float32)
    angles = pos[..., None] * freqs                     # [..., S, half]
    while angles.ndim < q.ndim:                          # align to [B,H,S,half]
        angles = angles[None]
    cos, sin = jnp.cos(angles), jnp.sin(angles)

    def rot(x):
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin,
                                x2 * cos + x1 * sin], axis=-1).astype(x.dtype)

    return rot(q), rot(k)


# ---------------------------------------------------------------------------
# attention (GQA, optional KV cache)
# ---------------------------------------------------------------------------
def attn_init(cfg: ArchConfig, key, dtype, d_in: int | None = None):
    d = d_in or cfg.d_model
    Dh, Hq, Hkv = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "wq": _dense(k1, d, Hq * Dh, dtype),
        "wk": _dense(k2, d, Hkv * Dh, dtype),
        "wv": _dense(k3, d, Hkv * Dh, dtype),
        "wo": _dense(k4, Hq * Dh, cfg.d_model, dtype),
    }


def attn_apply(cfg: ArchConfig, p, x, *, fm: FusionMode, positions,
               cache=None, cache_pos=None, kv_len=None, x_kv=None,
               window: int = 0, use_rope: bool | None = None):
    """x: [B, S, d_in].  Prefill fills ``cache`` when provided with S > 1;
    decode (S == 1) updates ``cache`` at ``cache_pos`` and streams the
    cache.  Returns (out [B,S,d_model], new_cache).

    ``window`` > 0: a query sees the last ``window`` positions, and the
    cache is a ring of ``R = min(window, max_len)`` positions
    (``attn_cache_init``) holding position ``t`` at ``t mod R``: prefill
    writes the prompt's last ``R`` keys there, decode writes at
    ``cache_pos mod R`` and reads ``min(kv_len, R)`` slots.  Keys carry
    their rotary phase from before they were cached, and attention over
    a set of keys does not depend on their order in the ring.
    ``use_rope`` (default: ``cfg.position_embedding``) rotates q and k."""
    B, S, _ = x.shape
    Dh, Hq, Hkv = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads
    xk = x if x_kv is None else x_kv

    q = (x @ p["wq"]).reshape(B, S, Hq, Dh).transpose(0, 2, 1, 3)
    k = (xk @ p["wk"]).reshape(B, S, Hkv, Dh).transpose(0, 2, 1, 3)
    v = (xk @ p["wv"]).reshape(B, S, Hkv, Dh).transpose(0, 2, 1, 3)
    if use_rope is None:
        use_rope = cfg.position_embedding != "nope"
    if use_rope:
        q, k = rope(q, k, positions, cfg.rope_theta)
    q = constrain(q, "act_bhsd")
    scale = cfg.attention_multiplier or None

    if cache is None:
        o = ops.attention(q, k, v, causal=cfg.causal, scale=scale,
                          use_pallas=fm.use_pallas, window=window)
        new_cache = None
    elif S > 1:  # prefill into pre-allocated cache
        kc = _cache_fill(cache["k"], k, window)
        vc = _cache_fill(cache["v"], v, window)
        kc, vc = constrain(kc, "kv_cache"), constrain(vc, "kv_cache")
        o = ops.attention(q, k, v, causal=cfg.causal, scale=scale,
                          use_pallas=fm.use_pallas, window=window)
        new_cache = {"k": kc, "v": vc}
    else:        # decode one token
        ring = cache["k"].shape[2]
        at = jax.lax.rem(cache_pos, ring) if window else cache_pos
        kc = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, 0, at, 0))
        vc = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, 0, at, 0))
        kc, vc = constrain(kc, "kv_cache"), constrain(vc, "kv_cache")
        eff = kv_len if kv_len is not None else kc.shape[2]
        if window:
            eff = (min(eff, ring) if isinstance(eff, (int, np.integer))
                   else jnp.minimum(eff, ring))
        o = ops.decode_attention(q[:, :, 0, :], kc, vc, kv_len=eff,
                                 scale=scale,
                                 use_pallas=fm.use_pallas)[:, :, None, :]
        new_cache = {"k": kc, "v": vc}

    o = o.transpose(0, 2, 1, 3).reshape(B, S, Hq * Dh)
    return o @ p["wo"], new_cache


def _cache_fill(cache, x, window: int):
    """A prompt's keys or values ``x`` [B, H, S, D] written from position
    0: in order, or for a ring shorter than the prompt the last ``R``
    positions, position ``t`` at ``t mod R``."""
    x = x.astype(cache.dtype)
    S, ring = x.shape[2], cache.shape[2]
    if window and S > ring:
        return jnp.roll(x[:, :, S - ring:], (S - ring) % ring, axis=2)
    return jax.lax.dynamic_update_slice(cache, x, (0, 0, 0, 0))


def attn_cache_init(cfg: ArchConfig, batch: int, max_len: int, dtype,
                    window: int = 0):
    """KV of ``max_len`` positions, or a ring of ``min(window, max_len)``
    for a windowed layer."""
    Dh, Hkv = cfg.resolved_head_dim, cfg.n_kv_heads
    n = min(window, max_len) if window else max_len
    return {"k": jnp.zeros((batch, Hkv, n, Dh), dtype),
            "v": jnp.zeros((batch, Hkv, n, Dh), dtype)}


# ---------------------------------------------------------------------------
# MLP (SwiGLU / GeGLU / plain GELU)
# ---------------------------------------------------------------------------
def mlp_init(cfg: ArchConfig, key, dtype, d_ff: int | None = None):
    k1, k2, k3 = jax.random.split(key, 3)
    d, ff = cfg.d_model, d_ff or cfg.d_ff
    if cfg.activation == "gelu_mlp":
        return {"w_up": _dense(k1, d, ff, dtype), "w_down": _dense(k2, ff, d, dtype)}
    return {"w_gate": _dense(k1, d, ff, dtype),
            "w_up": _dense(k2, d, ff, dtype),
            "w_down": _dense(k3, ff, d, dtype)}


def _act(name: str, x):
    if name in ("gelu", "gelu_mlp"):
        return jax.nn.gelu(x, approximate=True)
    if name == "relu":
        return jax.nn.relu(x)
    return jax.nn.silu(x)


def mlp_apply(cfg: ArchConfig, p, x, fm: FusionMode):
    if cfg.activation == "gelu_mlp":
        return _act("gelu", x @ p["w_up"]) @ p["w_down"]
    return (_act(cfg.activation, x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]


# ---------------------------------------------------------------------------
# MoE (GShard capacity-based dense dispatch, top-k)
# ---------------------------------------------------------------------------
def moe_init(cfg: ArchConfig, key, dtype):
    k0, k1, k2, k3 = jax.random.split(key, 4)
    d, ff, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    scale = 1.0 / math.sqrt(d)
    return {
        "router": _dense(k0, d, E, dtype),
        "w_gate": (jax.random.normal(k1, (E, d, ff), jnp.float32) * scale).astype(dtype),
        "w_up": (jax.random.normal(k2, (E, d, ff), jnp.float32) * scale).astype(dtype),
        "w_down": (jax.random.normal(k3, (E, ff, d), jnp.float32)
                   / math.sqrt(ff)).astype(dtype),
    }


def moe_apply(cfg: ArchConfig, p, x, fm: FusionMode,
              impl: str | None = None):
    """Returns (y, aux_loss).  x: [B, S, d].

    impl="einsum": GShard dense one-hot dispatch (paper-era baseline;
    materializes [T, E, C] dispatch/combine tensors -- O(T*E*C) compute).
    impl="sort": sort/scatter dispatch (MegaBlocks-style): tokens are
    scattered into an [E, C, d] buffer by (expert, slot) index and
    gathered back -- O(k*T*d) data movement, expert GEMMs unchanged.
    The dry-run hillclimb (EXPERIMENTS.md §Perf) quantifies the gap.
    """
    impl = impl or getattr(cfg, "moe_impl", None) or "einsum"
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    T = B * S
    xt = x.reshape(T, d)

    logits = (xt @ p["router"]).astype(jnp.float32)          # [T, E]
    probs = ops.softmax(logits, use_pallas=fm.use_pallas)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)            # [T, k]
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    if impl == "sort":
        # grouped dispatch: one group per sequence (groups shard over DP),
        # capacity relative to the group -- index math never crosses
        # devices, buffers are [G, E, C_g, d] sharded (dp, model).
        y = _moe_sort_dispatch(cfg, p, x, gate_vals.reshape(B, S, k),
                               gate_idx.reshape(B, S, k))
        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(jax.nn.one_hot(gate_idx[:, 0], E, dtype=jnp.float32),
                      axis=0)
        return y.reshape(B, S, d), E * jnp.sum(me * ce)

    capacity = int(np.ceil(k * T / E * cfg.capacity_factor))
    capacity = max(capacity, 4)

    dispatch = jnp.zeros((T, E, capacity), xt.dtype)
    combine = jnp.zeros((T, E, capacity), jnp.float32)
    counts = jnp.zeros((E,), jnp.int32)
    for j in range(k):
        e = gate_idx[:, j]                                    # [T]
        onehot = jax.nn.one_hot(e, E, dtype=jnp.int32)        # [T, E]
        pos = jnp.cumsum(onehot, axis=0) - onehot + counts[None, :]
        slot = jnp.sum(pos * onehot, axis=-1)                 # [T]
        keep = slot < capacity
        counts = counts + jnp.sum(onehot, axis=0)
        oh_slot = jax.nn.one_hot(slot, capacity, dtype=xt.dtype) * keep[:, None]
        dispatch = dispatch + onehot.astype(xt.dtype)[:, :, None] * oh_slot[:, None, :]
        combine = combine + (onehot.astype(jnp.float32)
                             * gate_vals[:, j:j + 1])[:, :, None] \
            * oh_slot.astype(jnp.float32)[:, None, :]

    xe = jnp.einsum("tec,td->ecd", dispatch, xt)
    xe = constrain(xe, "expert_ecd")
    h = _act(cfg.activation, jnp.einsum("ecd,edf->ecf", xe, p["w_gate"])) \
        * jnp.einsum("ecd,edf->ecf", xe, p["w_up"])
    ye = jnp.einsum("ecf,efd->ecd", h, p["w_down"])
    ye = constrain(ye, "expert_ecd")
    y = jnp.einsum("tec,ecd->td", combine.astype(ye.dtype), ye)

    # GShard load-balance aux loss
    me = jnp.mean(probs, axis=0)                              # router prob mass
    ce = jnp.mean(jax.nn.one_hot(gate_idx[:, 0], E, dtype=jnp.float32), axis=0)
    aux = E * jnp.sum(me * ce)

    return y.reshape(B, S, d), aux


def _moe_sort_dispatch(cfg: ArchConfig, p, x, gate_vals, gate_idx):
    """Grouped sort/scatter MoE dispatch (MegaBlocks/GSPMD-style).

    x: [G, Tg, d]; gate_vals/idx: [G, Tg, k].  Capacity slots come from a
    per-group cumsum over (token, choice) assignments; overflow drops
    (same semantics as the einsum path per group).  The only large
    tensors are the [G, E, C_g, d] expert buffers, sharded (dp, model);
    all index math is group-local, so no collective ever carries index
    tensors -- the cross-device traffic is exactly the EP dispatch/combine
    volume O(k * cf * tokens * d).
    """
    G, Tg, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    capacity = max(int(np.ceil(k * Tg / E * cfg.capacity_factor)), 4)

    Tk = Tg * k
    flat_e = gate_idx.reshape(G, Tk)                   # [G, Tk]
    flat_g = gate_vals.reshape(G, Tk).astype(jnp.float32)

    # slot within expert via argsort (O(Tk) memory; the one-hot cumsum
    # alternative materializes [G, Tk, E] and dominated the memory
    # roofline term -- §Perf hillclimb 1, iteration 5)
    def _slots(fe):
        order = jnp.argsort(fe, stable=True)
        se = fe[order]
        seg_start = jnp.searchsorted(se, jnp.arange(E), side="left")
        slot_sorted = jnp.arange(Tk) - seg_start[se]
        inv = jnp.zeros_like(order).at[order].set(jnp.arange(Tk))
        return slot_sorted[inv]

    slot = jax.vmap(_slots)(flat_e)                               # [G,Tk]
    keep = slot < capacity

    # scatter tokens into expert buffers.  Flattened (expert, slot)
    # destinations + a vmap'd 1-D scatter keep the group dim an explicit
    # scatter batch dim, which GSPMD partitions over DP (a 3-D fancy-index
    # scatter gets *replicated* -- 48 GiB all-gathers; see §Perf log).
    dest = jnp.where(keep, flat_e * capacity + slot,
                     E * capacity)                                # [G,Tk]
    x_rep = jnp.repeat(x, k, axis=1)                              # [G,Tk,d] static
    buf = jax.vmap(
        lambda dst, upd: jnp.zeros(((E + 1) * capacity, d), x.dtype)
        .at[dst].set(upd, mode="drop"))(dest, x_rep)
    xe = constrain(buf[:, : E * capacity].reshape(G, E, capacity, d),
                   "expert_gecd")

    h = _act(cfg.activation, jnp.einsum("gecd,edf->gecf", xe, p["w_gate"])) \
        * jnp.einsum("gecd,edf->gecf", xe, p["w_up"])
    ye = jnp.einsum("gecf,efd->gecd", h, p["w_down"])
    ye = constrain(ye, "expert_gecd")

    # gather back (batched 1-D gather) + static-order combine: out rows are
    # (token-major, choice-minor), so the segment-sum over choices is a
    # reshape + sum -- no scatter, nothing for SPMD to replicate.
    ye_flat = ye.reshape(G, E * capacity, d)
    gsrc = jnp.where(keep, flat_e * capacity + slot, 0)
    out_tok = jax.vmap(lambda rows, idx: rows[idx])(ye_flat, gsrc)  # [G,Tk,d]
    out_tok = out_tok * (flat_g * keep).astype(ye.dtype)[..., None]
    y = jnp.sum(out_tok.reshape(G, Tg, k, d), axis=2)
    return y


def moe_share_init(cfg: ArchConfig, key, dtype):
    """A router over all ``n_experts`` and the weights of the experts
    this chip holds."""
    k0, k1, k2, k3 = jax.random.split(key, 4)
    d, ff, held = cfg.d_model, cfg.d_ff, cfg.n_experts_held
    scale = 1.0 / math.sqrt(d)
    return {
        "router": _dense(k0, d, cfg.n_experts, dtype),
        "w_gate": (jax.random.normal(k1, (held, d, ff), jnp.float32)
                   * scale).astype(dtype),
        "w_up": (jax.random.normal(k2, (held, d, ff), jnp.float32)
                 * scale).astype(dtype),
        "w_down": (jax.random.normal(k3, (held, ff, d), jnp.float32)
                   / math.sqrt(ff)).astype(dtype),
    }


def moe_share_route(cfg: ArchConfig, p, xt):
    """Each token's gate on each expert held here, [T, held], for tokens
    ``xt`` [T, d]: the router scores all ``n_experts``, and a token's
    gates are the softmax over its ``top_k`` logits (Granite's
    ``TopKGating``; the same as a softmax over all, kept to the top ``k``
    and renormalised), zero on a held expert it was not routed to."""
    top, idx = jax.lax.top_k((xt @ p["router"]).astype(jnp.float32),
                             cfg.top_k)                       # [T, k]
    gates = jax.nn.softmax(top, axis=-1)
    held = cfg.expert_offset + jnp.arange(p["w_gate"].shape[0])
    return jnp.sum(jnp.where(idx[:, :, None] == held, gates[:, :, None],
                             0.0), axis=1)                    # [T, held]


def moe_share_experts(cfg: ArchConfig, p, xt, gate):
    """The held experts' part of the routed output of tokens ``xt``
    [T, d] under the gates of ``moe_share_route``.  Held expert
    ``expert_offset + j`` adds ``gate * FFN_j(x)`` for every token routed
    to it: no capacity, nothing dropped.  The first cut is dense: every
    held expert runs on every token with a zero gate where it was not
    chosen, so each held expert's weights are read once per call."""
    h = _act(cfg.activation, jnp.einsum("td,edf->tef", xt, p["w_gate"])) \
        * jnp.einsum("td,edf->tef", xt, p["w_up"])
    h = h * gate[:, :, None].astype(h.dtype)
    return jnp.einsum("tef,efd->td", h, p["w_down"])


def moe_share_apply(cfg: ArchConfig, p, x, fm: FusionMode, gate=None):
    """The part of a routed expert layer's output that the experts held
    here give (expert parallelism without its exchange).  x: [B, S, d].
    Routed on ``x`` itself, or by ``gate`` [B*S, held] where the model
    routes on another input (``moe_share_route``)."""
    B, S, d = x.shape
    xt = x.reshape(B * S, d)
    if gate is None:
        gate = moe_share_route(cfg, p, xt)
    return moe_share_experts(cfg, p, xt, gate).reshape(B, S, d)


# ---------------------------------------------------------------------------
# Mamba2 block (SSD)
# ---------------------------------------------------------------------------
def mamba_init(cfg: ArchConfig, key, dtype):
    d, di, N = cfg.d_model, cfg.resolved_d_inner, cfg.ssm_state
    H, W = cfg.ssm_heads, cfg.conv_width
    conv_dim = di + 2 * N
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "in_proj": _dense(k1, d, 2 * di + 2 * N + H, dtype),
        "conv_w": (jax.random.normal(k2, (W, conv_dim), jnp.float32) * 0.2).astype(dtype),
        "conv_b": jnp.zeros((conv_dim,), dtype),
        "A_log": jnp.zeros((H,), jnp.float32),               # A = -exp(A_log) = -1
        "D": jnp.ones((H,), jnp.float32),
        "dt_bias": jnp.full((H,), -2.0, jnp.float32),        # softplus ~ 0.12
        "norm_g": jnp.ones((di,), dtype),
        "out_proj": _dense(k4, di, d, dtype),
    }


def _causal_depthwise_conv(x, w, b):
    """x: [B, S, C]; w: [W, C] depthwise causal conv."""
    W = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    out = jax.lax.conv_general_dilated(
        xp, w[:, None, :],                      # [W, 1, C] WIO
        window_strides=(1,), padding="VALID",
        dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=w.shape[1])
    return out + b


def mamba_apply(cfg: ArchConfig, p, x, *, fm: FusionMode, cache=None,
                cache_pos=None):
    """x: [B, S, d].  cache = {"conv": [B, W-1, conv_dim], "ssm": [B,H,P,N]}.

    S > 1 with cache: prefill (returns final state).  S == 1 with cache:
    single recurrence step.  Returns (y, new_cache).
    """
    B, S, d = x.shape
    di, N = cfg.resolved_d_inner, cfg.ssm_state
    H, P, W = cfg.ssm_heads, cfg.ssm_head_dim, cfg.conv_width
    conv_dim = di + 2 * N

    zxbcdt = x @ p["in_proj"]
    z = zxbcdt[..., :di]
    xBC = zxbcdt[..., di:di + conv_dim]
    dt_raw = zxbcdt[..., di + conv_dim:].astype(jnp.float32)  # [B,S,H]
    A = -jnp.exp(p["A_log"])

    if cache is not None and S == 1:
        conv_state = jnp.concatenate([cache["conv"], xBC], axis=1)  # [B,W,cd]
        xBC_c = jnp.einsum("bwc,wc->bc", conv_state, p["conv_w"]) + p["conv_b"]
        xBC_c = jax.nn.silu(xBC_c)
        xs = xBC_c[:, :di].reshape(B, H, P)
        Bv = xBC_c[:, di:di + N]
        Cv = xBC_c[:, di + N:]
        dt = jax.nn.softplus(dt_raw[:, 0] + p["dt_bias"])           # [B,H]
        decay = jnp.exp(dt * A[None, :])                            # [B,H]
        upd = jnp.einsum("bh,bn,bhp->bhpn", dt, Bv.astype(jnp.float32),
                         xs.astype(jnp.float32))
        h = cache["ssm"] * decay[..., None, None] + upd
        h = constrain(h, "ssm_state")
        y = jnp.einsum("bn,bhpn->bhp", Cv.astype(jnp.float32), h)
        y = y + p["D"][None, :, None] * xs.astype(jnp.float32)
        y = y.reshape(B, 1, di)
        new_cache = {"conv": conv_state[:, 1:], "ssm": h}
    else:
        xBC_raw = xBC                      # pre-conv values feed the decode cache
        xBC = _causal_depthwise_conv(xBC, p["conv_w"], p["conv_b"])
        xBC = jax.nn.silu(xBC)
        xs = xBC[..., :di]
        Bv = xBC[..., di:di + N]
        Cv = xBC[..., di + N:]
        dt = jax.nn.softplus(dt_raw + p["dt_bias"])                 # [B,S,H]

        chunk = min(cfg.ssm_chunk, S)
        pad = (-S) % chunk
        if pad:
            xs = jnp.pad(xs, ((0, 0), (0, pad), (0, 0)))
            dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
            Bv = jnp.pad(Bv, ((0, 0), (0, pad), (0, 0)))
            Cv = jnp.pad(Cv, ((0, 0), (0, pad), (0, 0)))
        y, state = ops.ssd_scan(
            xs.reshape(B, S + pad, H, P), dt, A, Bv, Cv,
            chunk=chunk, use_pallas=fm.use_pallas)
        y = y[:, :S].astype(jnp.float32)
        y = y + p["D"][None, None, :, None] * xs[:, :S].reshape(B, S, H, P).astype(jnp.float32)
        y = y.reshape(B, S, di)
        if cache is not None:
            new_cache = {"conv": xBC_raw[:, S - (W - 1):S] if S >= W - 1 else
                         jnp.pad(xBC_raw[:, :S], ((0, 0), (W - 1 - S, 0), (0, 0))),
                         "ssm": state}
        else:
            new_cache = None

    # gated RMSNorm epilogue (memory-intensive chain -> stitched kernel)
    y = y * jax.nn.silu(z.astype(jnp.float32))
    y = ops.rmsnorm(y.astype(x.dtype), p["norm_g"], cfg.norm_eps,
                    use_pallas=fm.use_pallas)
    return y @ p["out_proj"], new_cache


def mamba_cache_init(cfg: ArchConfig, batch: int, dtype):
    di, N = cfg.resolved_d_inner, cfg.ssm_state
    H, P, W = cfg.ssm_heads, cfg.ssm_head_dim, cfg.conv_width
    return {"conv": jnp.zeros((batch, W - 1, di + 2 * N), dtype),
            "ssm": jnp.zeros((batch, H, P, N), jnp.float32)}
