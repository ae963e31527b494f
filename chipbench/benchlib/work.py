"""Bytes and operations a decode wave needs, from the model's shapes.

Counted for the slots that hold a request (an idle slot's lane is work
the program wastes, not work the wave needs), in float32 (4 bytes):

* bytes: every weight once, except the embedding table, of which one
  row per slot is read, and the output head, of which the vocabulary's
  columns (not the padding) are read; the keys and values of each
  attention application up to each slot's own ``kv_len``, and the new
  row written; the SSM and convolution state, read and written; the
  logits written.
* operations: 2 per multiply-add of every matrix product the token goes
  through (the shared block's once per application), the two attention
  products at each slot's ``kv_len``, and the state update and readout
  of each Mamba-2 layer (6 per state element) and its convolution.

So a program that reads less than the whole cache, or the weights
fewer times, has a higher share, and none can pass the roofline.
"""
from __future__ import annotations

from dataclasses import dataclass

F32 = 4


@dataclass(frozen=True)
class WaveWork:
    weight_bytes: int        # every weight but the embedding, read once
    row_bytes: int           # one embedding row
    kv_row_bytes: int        # keys + values of one position, one app
    n_apps: int              # attention applications per token
    state_bytes: int         # SSM + conv state of one slot, all layers
    logit_bytes: int         # one slot's logits
    matmul_flops: int        # per token
    attn_flops_per_pos: int  # per token per cached position, all apps
    ssm_flops: int           # per token

    def bytes(self, kv_lens) -> int:
        """Bytes one wave needs, ``kv_lens`` holding each active slot's
        cache length after this token."""
        b = len(kv_lens)
        kv = self.kv_row_bytes * self.n_apps * (sum(kv_lens) + b)
        return (self.weight_bytes + b * (self.row_bytes + 2 * self.state_bytes
                                         + self.logit_bytes) + kv)

    def flops(self, kv_lens) -> int:
        b = len(kv_lens)
        return (b * (self.matmul_flops + self.ssm_flops)
                + self.attn_flops_per_pos * sum(kv_lens))


def wave_work(m: dict) -> WaveWork:
    d, di, n = m["d_model"], m["d_inner"], m["ssm_state"]
    heads = di // m["ssm_head_dim"]
    cd = di + 2 * n
    w, layers = m["conv_width"], m["n_layers"]
    v = m["vocab_size"]
    in_proj, out_proj = d * (2 * di + 2 * n + heads), di * d
    mamba_small = d + w * cd + cd + 3 * heads + di   # norms, conv, A, dt, D
    weights = layers * (in_proj + out_proj + mamba_small) + d + d * v
    matmul = layers * (in_proj + out_proj) + d * v
    every = m.get("attn_every", 0)
    n_apps = len([i for i in range(layers) if every and i % every == 0])
    kv_row = attn_pos = 0
    if n_apps:
        hd = m["n_heads"] * m["head_dim"]
        kvd = m.get("n_kv_heads", m["n_heads"]) * m["head_dim"]
        # wq, wk, wv read the 2d-wide concatenation; wo; GeGLU MLP
        shared_mm = 2 * d * hd + 2 * (2 * d * kvd) + hd * d \
            + 3 * d * m["d_ff"]
        weights += shared_mm + 3 * d
        matmul += n_apps * shared_mm
        kv_row = 2 * kvd * F32
        attn_pos = n_apps * 2 * 2 * hd
    state = layers * (heads * m["ssm_head_dim"] * n + (w - 1) * cd) * F32
    ssm = layers * (6 * heads * m["ssm_head_dim"] * n + 2 * w * cd)
    return WaveWork(weight_bytes=weights * F32, row_bytes=d * F32,
                    kv_row_bytes=kv_row, n_apps=n_apps, state_bytes=state,
                    logit_bytes=v * F32, matmul_flops=2 * matmul,
                    attn_flops_per_pos=attn_pos, ssm_flops=ssm)
