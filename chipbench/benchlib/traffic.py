"""One general generator for every traffic mix.

A mix file gives the loop (``open``: arrivals on a schedule, or
``closed``: a backlog of ``waiting`` requests), the slots and cache
length of the server, a prompt-length ladder with integer weights, an
output-length distribution and, for an open loop, a rate and the
coefficient of variation of its gamma inter-arrival times.

Every seed serves the same work in another order.  Requests come in
blocks of ``block``; each block holds the ladder's lengths in exact
proportion and the output lengths (and gaps) at the block's evenly
spaced quantiles.  The schedule, which prompt length arrives when, is
shuffled by the mix's own ``schedule_seed`` and so is the same for
every seed: the tail of the time to first token is set by the bursts
and the prefills queued in them, and a seed that moved them would
change the work.  The seed shuffles the output lengths over the
requests and draws the token ids (and, elsewhere, the weights).
"""
from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np
from scipy import stats


@dataclass
class Req:
    rid: int
    prompt: np.ndarray      # int32 token ids
    max_new: int
    due: float | None       # seconds after the window opens (open loop)
    times: list = field(default_factory=list)   # host time of each token
    out: list | None = None                     # the served tokens


def _quantiles(mix_out: dict, k: int) -> np.ndarray:
    q = (np.arange(k) + 0.5) / k
    kind = mix_out["dist"]
    if kind == "uniform":
        vals = mix_out["lo"] + q * (mix_out["hi"] - mix_out["lo"])
    elif kind == "lognormal":
        vals = mix_out["median"] * np.exp(mix_out["sigma"]
                                          * stats.norm.ppf(q))
    else:
        raise ValueError(f"unknown output distribution {kind!r}")
    return np.clip(np.rint(vals), mix_out["lo"], mix_out["hi"]).astype(int)


def residual_quantiles(lengths: np.ndarray, k: int) -> np.ndarray:
    """``k`` evenly spaced quantiles of the tokens a request still has to
    produce when a slot is looked at in steady state: a length-biased
    request, at a uniform point of its output."""
    top = int(lengths.max())
    survival = np.array([(lengths >= r).mean() for r in range(1, top + 1)])
    cdf = np.cumsum(survival) / survival.sum()
    q = (np.arange(k) + 0.5) / k
    return np.searchsorted(cdf, q) + 1


class Traffic:
    """Requests of one mix for one seed, made block by block."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        self.mix = mix
        self.vocab = vocab
        self.rng = np.random.default_rng(seed)
        self.schedule = np.random.default_rng(int(mix["schedule_seed"]))
        self.block = int(mix["block"])
        ladder, weights = mix["prompt_lens"], mix["prompt_weights"]
        unit = sum(weights)
        if self.block % unit:
            raise ValueError(f"block {self.block} is not a multiple of the "
                             f"ladder's weights {weights}")
        self._plens = np.repeat(ladder, [w * self.block // unit
                                         for w in weights])
        self._outs = _quantiles(mix["output"], self.block)
        self._gaps = None
        if mix["loop"] == "open":
            shape = 1.0 / mix["arrival_cv"] ** 2
            g = stats.gamma.ppf((np.arange(self.block) + 0.5) / self.block,
                                shape)
            self._gaps = g / g.mean() / mix["rate_per_s"]
        self._queue: list[Req] = []
        self._next_id = 0
        self._clock = 0.0

    def _fill(self) -> None:
        plens = self.schedule.permutation(self._plens)
        gaps = (self.schedule.permutation(self._gaps)
                if self._gaps is not None else None)
        outs = self.rng.permutation(self._outs)
        for j in range(self.block):
            due = None
            if gaps is not None:
                self._clock += gaps[j]
                due = self._clock
            self._queue.append(self._make(int(plens[j]), int(outs[j]), due))

    def _make(self, plen: int, out: int, due) -> Req:
        prompt = self.rng.integers(0, self.vocab, plen, dtype=np.int32)
        req = Req(self._next_id, prompt,
                  min(out, self.mix["max_len"] - plen), due)
        self._next_id += 1
        return req

    def peek(self) -> Req:
        if not self._queue:
            self._fill()
        return self._queue[0]

    def pop(self) -> Req:
        req = self.peek()
        self._queue.pop(0)
        return req

    def first_fill(self, n: int) -> list[Req]:
        """``n`` requests for a closed loop's slots at the window's
        start, their outputs cut to steady-state remainders."""
        rest = self.rng.permutation(residual_quantiles(self._outs, n))
        reqs = [self.pop() for _ in range(n)]
        for req, r in zip(reqs, rest):
            req.max_new = int(min(r, self.mix["max_len"] - len(req.prompt)))
        return reqs

