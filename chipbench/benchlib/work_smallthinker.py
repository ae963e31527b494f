"""Bytes and operations a decode wave of a SmallThinker layer stack
needs (``refs/smallthinker_moe.py``), from the model's shapes.

Counted as ``work.py`` counts them, for the slots that hold a request, in
float32 (4 bytes):

* bytes: every weight once, except the embedding table, of which one
  row per slot is read, and the output head, of which the vocabulary's
  columns are read; all of each layer's held experts; the keys and
  values of each global ("A") layer up to each slot's own ``kv_len``
  and of each windowed ("S") layer up to ``min(kv_len, window)``, and
  the new row written; the logits written.
* operations: 2 per multiply-add of every matrix product the token goes
  through (of the held experts, the ``top_k * experts_held / n_experts``
  a token is routed to on average), and the two attention products at
  the positions each layer reads.

The bytes are an upper bound on what a wave needs: a held expert that no
slot of the wave is routed to need not be read.  At 24 slots, top-6 of
64, that is about 9% of the held experts (``(58/64) ** 24``).
"""
from __future__ import annotations

from dataclasses import dataclass

from .work import F32


@dataclass(frozen=True)
class WindowWaveWork:
    weight_bytes: int        # every weight but the embedding, read once
    row_bytes: int           # one embedding row
    kv_row_bytes: int        # keys + values of one position, one layer
    n_global: int            # "A" layers: the whole cache
    n_window: int            # "S" layers: the last ``window`` positions
    window: int
    logit_bytes: int         # one slot's logits
    matmul_flops: int        # per token
    attn_flops_per_pos: int  # per token per position read, one layer

    def positions(self, kv_lens) -> int:
        """Positions a wave's attention reads, over all layers."""
        return (self.n_global * sum(kv_lens)
                + self.n_window * sum(min(k, self.window) for k in kv_lens))

    def bytes(self, kv_lens) -> int:
        """Bytes one wave needs, ``kv_lens`` holding each active slot's
        cache length after this token."""
        b = len(kv_lens)
        kv = self.kv_row_bytes * (self.positions(kv_lens)
                                  + b * (self.n_global + self.n_window))
        return (self.weight_bytes + b * (self.row_bytes + self.logit_bytes)
                + kv)

    def flops(self, kv_lens) -> int:
        return (len(kv_lens) * self.matmul_flops
                + self.attn_flops_per_pos * self.positions(kv_lens))


def wave_work(m: dict) -> WindowWaveWork:
    d, v, pattern = m["d_model"], m["vocab_size"], m["layer_pattern"]
    hq = m["n_heads"] * m["head_dim"]
    hkv = m["n_kv_heads"] * m["head_dim"]
    ff, held = m["d_ff"], m["experts_held"]
    attn = 2 * d * hq + 2 * d * hkv               # wq, wo; wk, wv
    router, expert = d * m["n_experts"], 3 * d * ff
    layer = attn + router + held * expert + 2 * d  # two norms
    weights = len(pattern) * layer + d + d * v     # final norm, head
    routed = m["top_k"] * held / m["n_experts"]   # held experts per token
    matmul = len(pattern) * (attn + router + routed * expert) + d * v
    return WindowWaveWork(
        weight_bytes=weights * F32, row_bytes=d * F32,
        kv_row_bytes=2 * hkv * F32, n_global=pattern.count("A"),
        n_window=pattern.count("S"), window=m["sliding_window"],
        logit_bytes=v * F32, matmul_flops=round(2 * matmul),
        attn_flops_per_pos=2 * 2 * hq)


def window_prefill_work(m: dict, plen: int) -> tuple[int, int]:
    """Operations and bytes of one windowed layer's prefill attention
    over ``plen`` prompt positions: query ``i`` sees ``min(i + 1,
    window)`` keys (two products of 2 operations per multiply-add);
    queries, keys and values read once and the output written once."""
    hq = m["n_heads"] * m["head_dim"]
    hkv = m["n_kv_heads"] * m["head_dim"]
    w = m["sliding_window"]
    seen = sum(min(i + 1, w) for i in range(plen))
    return 2 * 2 * hq * seen, (2 * hq + 2 * hkv) * plen * F32
