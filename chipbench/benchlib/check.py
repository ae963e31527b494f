"""Whether the served tokens are right: the plain reference's logits at
every served position, over a sample of the finished requests.

The number compared is the widest gap by which a served token's
reference logit lies below the reference's best logit at that position
(0 where the served token is the reference's greedy choice).  The
control puts the reference, with its matrix products in bfloat16
halves (``bf16_3x``, what ``precision="high"`` computes), in the
program's place: at the same served positions of the same sequences,
the gap of the token it puts first.
"""
from __future__ import annotations

import numpy as np

#: bytes the reference's temporaries may take per block of rows
BLOCK_BUDGET = 3e9


def sample(finished, seed: int, ladder, min_tokens: int,
           max_reqs: int):
    """Requests to compare, drawn from the seed: the one that served the
    most tokens, one of each prompt length that finished, then more at
    random until ``min_tokens`` served tokens are in (at most
    ``max_reqs`` requests)."""
    if not finished:
        return []
    rng = np.random.default_rng(seed ^ 0x5EED)
    order = [finished[i] for i in rng.permutation(len(finished))]
    pick = [max(order, key=lambda r: len(r.out))]
    for plen in ladder:
        same = [r for r in order if len(r.prompt) == plen and r not in pick]
        if same:
            pick.append(same[0])
    for r in order:
        if sum(len(p.out) for p in pick) >= min_tokens \
                or len(pick) >= max_reqs:
            break
        if r not in pick:
            pick.append(r)
    return pick[:max_reqs]


def _rows(reqs, length: int):
    """Input tokens, next tokens and lengths of each request's sequence
    (prompt + served tokens), right-padded to ``length``."""
    toks = np.zeros((len(reqs), length), np.int32)
    nxt = np.zeros((len(reqs), length), np.int32)
    for i, r in enumerate(reqs):
        seq = np.concatenate([r.prompt, np.asarray(r.out, np.int32)])
        n = min(len(seq), length + 1)
        toks[i, :n - 1] = seq[:n - 1]
        nxt[i, :n - 1] = seq[1:n]
    return toks, nxt


class Reference:
    """The cell's reference, compiled once for ``length`` positions and
    blocks of rows sized to the memory it may take."""

    def __init__(self, ref, model: dict, params, length: int):
        import jax

        self.params = params
        self.length = length
        per_row = ref.attention_bytes_per_row(model, length)
        self.rows = max(1, int(BLOCK_BUDGET // per_row))
        self._fn = jax.jit(
            lambda p, t, g, mm: ref.readings(model, p, t, g, mm),
            static_argnums=(3,))

    def readings(self, toks, gather, mm="highest"):
        """best, gathered and argmax [n, length] for every row."""
        n = len(toks)
        rows = min(self.rows, n)
        outs = []
        for i in range(0, n, rows):
            t, g = toks[i:i + rows], gather[i:i + rows]
            k = len(t)
            if k < rows:            # one compiled shape for every block
                t = np.concatenate([t, np.repeat(t[-1:], rows - k, 0)])
                g = np.concatenate([g, np.repeat(g[-1:], rows - k, 0)])
            outs.append([np.asarray(x)[:k]
                         for x in self._fn(self.params, t, g, mm)])
        return [np.concatenate(parts) for parts in zip(*outs)]


def _served(reqs, length: int):
    """(row, first, end) of each request's served positions: the
    positions whose next token was served, within ``length``."""
    for i, r in enumerate(reqs):
        lo = len(r.prompt) - 1
        hi = min(lo + len(r.out), length)
        if hi > lo:
            yield i, lo, hi


def _widest(best, got, spans) -> tuple[float, int]:
    gap, n = 0.0, 0
    for i, lo, hi in spans:
        gap = max(gap, float(np.max(best[i, lo:hi] - got[i, lo:hi])))
        n += hi - lo
    return gap, n


def served_gap(reference: Reference, reqs) -> tuple[float, int]:
    """The widest gap over every served token of ``reqs``, and how many
    tokens were compared."""
    toks, nxt = _rows(reqs, reference.length)
    best, got, _ = reference.readings(toks, nxt)
    return _widest(best, got, list(_served(reqs, reference.length)))


def control_gap(reference: Reference, reqs) -> tuple[float, int]:
    """The control, read as ``served_gap`` reads the program: at every
    served position of ``reqs``, the gap of the token that the
    ``bf16_3x`` reference puts first."""
    toks, nxt = _rows(reqs, reference.length)
    _, _, first = reference.readings(toks, nxt, mm="bf16_3x")
    best, got, _ = reference.readings(toks, first)
    return _widest(best, got, list(_served(reqs, reference.length)))
