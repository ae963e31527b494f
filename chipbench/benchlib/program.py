"""Where inside the program's own calls the device waits.

The program marks its steps with spans (``repro.runtime.spans``; the
names are ``PROGRAM_SPANS``), and each TPU plane lists the programs it
ran on its ``XLA Modules`` line as ``jit_<function>(<fingerprint>)``.
A stitched program's function is ``stitched_<name>``, and a stitched
kernel's op is ``stitch_<scheme>_<group>``.  With the device clock
shifted as ``trace.reduce`` shifts it, each traced decode wave (a
``serve.wave`` span) finds its program, the first
``stitched_decode_wave`` run that starts inside it, and its device idle
splits in three:

- dispatch: from the span's start to the program's start;
- op gaps: inside the program, between its ops;
- readback: from the program's end to the span's end.

The device's idle time in the whole window is also attributed, piece by
piece, to the innermost span covering it: a program span, else one of
the harness's spans (``trace.HOST_SPANS``), else ``untraced_host``.

This module reads the profile beside ``trace``, which it leaves as it
is: ``split(trace.from_profile(pd), events(pd))``.
"""
from __future__ import annotations

import bisect
from collections import defaultdict
from dataclasses import dataclass, field

import numpy as np

from .trace import Busy, Trace, device_offset, merge

#: the program's own spans, as ``repro`` names them
PROGRAM_SPANS = (
    "serve.prefill", "prefill.cache_init", "prefill.cache_write",
    "prefill.sample", "serve.wave", "wave.inputs", "wave.sample",
    "wave.retire", "stitch.call", "stitch.lookup", "stitch.launch",
    "stitch.guard", "stitch.build", "stitch.trace", "stitch.search",
    "stitch.emit")
#: the line of a TPU plane that holds one event per program run
MODULE_LINE = "XLA Modules"
WAVE_SPAN = "serve.wave"
#: the decode wave's program, as ``jit`` names its module
WAVE_PROGRAM = "stitched_decode_wave"
#: prefix of the ops of the stitching compiler's kernels
STITCHED_OP = "stitch_"
#: share of traced waves that may find no program: more, and the clocks
#: are not aligned, so the split is not trusted
MAX_MISSING = 0.02


@dataclass
class Events:
    """The program's events, in seconds on the trace clock."""
    spans: list = field(default_factory=list)    # [(name, s, e)] host
    modules: dict = field(default_factory=dict)  # device -> [(name, s, e)]


def module_name(event: str) -> str:
    """``jit_stitched_decode_wave(1640922085)`` ->
    ``jit_stitched_decode_wave``."""
    return event.split("(", 1)[0]


def events(pd) -> Events:
    """``pd``: a ``jax.profiler.ProfileData``."""
    ev = Events()
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and \
                plane.name[len("/device:TPU:"):].isdigit():
            ev.modules[plane.name] = [
                (module_name(e.name), e.start_ns * 1e-9, e.end_ns * 1e-9)
                for line in plane.lines if line.name == MODULE_LINE
                for e in line.events]
        elif plane.name.startswith("/host:CPU"):
            ev.spans.extend((e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                            for line in plane.lines for e in line.events
                            if e.name in PROGRAM_SPANS)
    ev.spans.sort(key=lambda s: s[1])
    return ev


@dataclass
class Wave:
    """One traced wave on one device, in seconds."""
    dispatch_s: float
    op_gap_s: float
    readback_s: float
    ops: int             # device ops that start inside the program
    busy_s: float        # union of the program's ops
    stitched_s: float    # time of its ops named ``stitch_*``


@dataclass
class Split:
    waves: list = field(default_factory=list)  # Wave per wave and device
    missing: int = 0     # traced waves (per device) that found no program
    gaps: list = field(default_factory=list)   # [[span, idle s]], mean

    @property
    def trusted(self) -> bool:
        n = len(self.waves) + self.missing
        return n > 0 and self.missing <= MAX_MISSING * n

    def median(self, part: str) -> float | None:
        """Median of one part of a trusted split's waves."""
        if not self.trusted:
            return None
        return float(np.median([getattr(w, part) for w in self.waves]))

    def stitched_pct(self) -> float | None:
        """Device time of the stitched kernels over the wave programs'
        busy time, in percent."""
        busy = sum(w.busy_s for w in self.waves)
        if not self.trusted or busy <= 0:
            return None
        return 100.0 * sum(w.stitched_s for w in self.waves) / busy


def split(tr: Trace, ev: Events, lo: float | None = None,
          hi: float | None = None) -> Split:
    """The traced waves' split and the idle time by innermost span within
    [lo, hi] (by default the extent that ``trace.reduce`` takes)."""
    ops, mods = {}, {}
    for dev, evs in tr.ops.items():
        d = device_offset(tr, evs)
        ops[dev] = sorted((s + d, e + d, n.startswith(STITCHED_OP))
                          for n, s, e in evs)
        mods[dev] = sorted((s + d, e + d)
                           for n, s, e in ev.modules.get(dev, ())
                           if n.endswith(WAVE_PROGRAM))
    if lo is None or hi is None:
        ends = [t for evs in ops.values() for s, e, _ in evs
                for t in (s, e)] + [t for _, s, e in tr.spans
                                    for t in (s, e)]
        lo = min(ends) if lo is None else lo
        hi = max(ends) if hi is None else hi
    waves = [(s, e) for n, s, e in ev.spans
             if n == WAVE_SPAN and s >= lo and e <= hi]
    pieces = innermost(tr.spans + ev.spans)
    starts = [a for a, _, _ in pieces]
    out = Split()
    idle: dict = defaultdict(float)
    for dev, dev_ops in ops.items():
        busy = Busy(merge((s, e) for s, e, _ in dev_ops))
        _waves(out, busy, dev_ops, mods[dev], waves)
        merged = merge((max(s, lo), min(e, hi)) for s, e, _ in dev_ops
                       if e > lo and s < hi)
        prev = lo
        for s, e in merged + [(hi, hi)]:
            if s > prev:
                _attribute(pieces, starts, prev, s, idle)
            prev = max(prev, e)
    nd = max(1, len(ops))
    out.gaps = [[k, v / nd]
                for k, v in sorted(idle.items(), key=lambda kv: -kv[1])]
    return out


def _waves(out: Split, busy: Busy, ops, mods, waves) -> None:
    op_starts = [s for s, _, _ in ops]
    mod_starts = [s for s, _ in mods]
    for ws, we in waves:
        k = bisect.bisect_left(mod_starts, ws)
        if k == len(mods) or mod_starts[k] > we:
            out.missing += 1
            continue
        ms, me = mods[k][0], min(mods[k][1], we)
        i = bisect.bisect_left(op_starts, ms)
        j = bisect.bisect_left(op_starts, me)
        out.waves.append(Wave(
            dispatch_s=_idle(busy, ws, ms), op_gap_s=_idle(busy, ms, me),
            readback_s=_idle(busy, me, we), ops=j - i,
            busy_s=busy.covered(ms, me),
            stitched_s=sum(min(e, me) - s for s, e, st in ops[i:j] if st)))


def _idle(busy: Busy, a: float, b: float) -> float:
    return max(0.0, b - a - busy.covered(a, b))


def innermost(spans) -> list[tuple[float, float, str]]:
    """Disjoint pieces of the spans' extent, in order, each named by the
    innermost span that covers it (the spans of one thread nest)."""
    evs = sorted(spans, key=lambda x: (x[1], -x[2]))
    cuts = sorted({t for _, s, e in evs for t in (s, e)})
    out, open_, k = [], [], 0
    for a, b in zip(cuts, cuts[1:]):
        while k < len(evs) and evs[k][1] <= a:
            open_.append(evs[k])
            k += 1
        open_ = [x for x in open_ if x[2] > a]
        if open_:
            out.append((a, b, open_[-1][0]))
    return out


def _attribute(pieces, starts, a: float, b: float, into: dict) -> None:
    """Add the seconds of [a, b] to the span of each piece they overlap,
    and what no piece covers to ``untraced_host``."""
    left = b - a
    k = max(0, bisect.bisect_right(starts, a) - 1)
    while k < len(pieces) and pieces[k][0] < b:
        s, e, name = pieces[k]
        d = min(e, b) - max(s, a)
        if d > 0:
            into[name] += d
            left -= d
        k += 1
    if left > 1e-12:
        into["untraced_host"] += left
