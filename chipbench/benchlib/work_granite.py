"""Bytes and operations a decode wave of a Granite-4.0-H layer stack
needs (``refs/granite_hybrid.py``), from the model's shapes.

Counted as ``work.py`` counts them, for the slots that hold a request, in
float32 (4 bytes):

* bytes: every weight once, except the embedding table, of which one
  row per slot is read, and the output head, of which the vocabulary's
  columns are read; all of each layer's held experts; each attention
  layer's keys and values up to each slot's own ``kv_len``, and the new
  row written; each Mamba-2 layer's SSM and convolution state, read and
  written; the logits written.
* operations: 2 per multiply-add of every matrix product the token goes
  through (of the held experts, the ``top_k * experts_held / n_experts``
  a token is routed to on average), the two attention products at each
  slot's ``kv_len``, and the state update and readout of each Mamba-2
  layer (6 per state element) and its convolution.

The bytes are an upper bound on what a wave needs: a held expert that no
slot of the wave is routed to need not be read.  At 32 slots, top-10 of
72, that is about 1% of the held experts (``(62/72) ** 32``).
"""
from __future__ import annotations

from .work import F32, WaveWork


def wave_work(m: dict) -> WaveWork:
    d, di, n = m["d_model"], m["d_inner"], m["ssm_state"]
    heads = di // m["ssm_head_dim"]
    cd = di + 2 * n
    w, v = m["conv_width"], m["vocab_size"]
    pattern = m["layer_pattern"]
    n_mamba, n_attn = pattern.count("M"), pattern.count("A")
    hq = m["n_heads"] * m["head_dim"]
    hkv = m["n_kv_heads"] * m["head_dim"]
    ff, held = m["d_ff"], m["experts_held"]

    in_proj, out_proj = d * (2 * di + 2 * n + heads), di * d
    mamba_small = w * cd + cd + 3 * heads + di    # conv, A, dt, D, gated norm
    attn = 2 * d * hq + 2 * d * hkv               # wq, wo; wk, wv
    router, expert = d * m["n_experts"], 3 * d * ff
    shared = 3 * d * m["d_ff_shared"]
    ffn = router + held * expert + shared
    weights = (n_mamba * (in_proj + out_proj + mamba_small)
               + n_attn * attn + len(pattern) * (ffn + 2 * d)
               + d + d * v)                        # final norm, head

    routed = m["top_k"] * held / m["n_experts"]   # held experts per token
    matmul = (n_mamba * (in_proj + out_proj) + n_attn * attn
              + len(pattern) * (router + routed * expert + shared) + d * v)
    state = n_mamba * (heads * m["ssm_head_dim"] * n + (w - 1) * cd) * F32
    ssm = n_mamba * (6 * heads * m["ssm_head_dim"] * n + 2 * w * cd)
    return WaveWork(weight_bytes=weights * F32, row_bytes=d * F32,
                    kv_row_bytes=2 * hkv * F32, n_apps=n_attn,
                    state_bytes=state, logit_bytes=v * F32,
                    matmul_flops=round(2 * matmul),
                    attn_flops_per_pos=n_attn * 2 * 2 * hq, ssm_flops=ssm)
