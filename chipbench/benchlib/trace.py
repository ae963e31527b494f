"""Reduce a profiler trace (``.xplane.pb``) to busy time, idle gaps and
per-operation time.

Device time is the union of the operations on each TPU plane's op line;
host spans are the harness's own ``TraceAnnotation``s (``prefill``,
``decode_wave``, ``host_loop``, ``submit``, ``idle``) on the host plane.
The device's clock is offset from the host's by about a millisecond in
the trace, so each device plane is shifted by the offset that puts the
most of its busy time inside the host's program calls (``prefill`` and
``decode_wave``, which both end in a host sync): all device work is
started, and waited for, inside them.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from dataclasses import dataclass, field

import numpy as np

#: the harness's host spans, as ``serve.py`` names them
HOST_SPANS = ("prefill", "decode_wave", "host_loop", "submit", "idle")
#: the line of a device plane that holds one event per executed op
OP_LINE = "XLA Ops"
#: host spans inside which every device operation runs
CALL_SPANS = ("prefill", "decode_wave")
#: offsets of the device clock tried, seconds, nearest to 0 first
OFFSETS = np.array(sorted(np.arange(-500, 501) * 1e-5, key=abs))


@dataclass
class Trace:
    """Events in seconds on the trace clock."""
    ops: dict = field(default_factory=dict)    # device -> [(name, s, e)]
    spans: list = field(default_factory=list)  # [(name, s, e)] host

    @property
    def n_devices(self) -> int:
        return len(self.ops)


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def from_profile(pd) -> Trace:
    """``pd``: a ``jax.profiler.ProfileData``."""
    tr = Trace()
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and \
                plane.name[len("/device:TPU:"):].isdigit():
            evs = [(op_name(e.name), e.start_ns * 1e-9, e.end_ns * 1e-9)
                   for line in plane.lines if line.name == OP_LINE
                   for e in line.events]
            tr.ops[plane.name] = evs
        elif plane.name.startswith("/host:CPU"):
            tr.spans.extend((e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                            for line in plane.lines for e in line.events
                            if e.name in HOST_SPANS)
    tr.spans.sort(key=lambda s: s[1])
    return tr


def op_name(hlo: str) -> str:
    """``%fusion.12 = f32[...] fusion(...), ...`` -> ``fusion.12``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def load(trace_dir: str) -> Trace:
    from jax.profiler import ProfileData

    return from_profile(ProfileData.from_file(find_xplane(trace_dir)))


def merge(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


class Busy:
    """Merged, disjoint intervals with prefix sums, so the busy seconds
    inside any [lo, hi] take two binary searches (``upto`` takes arrays
    too)."""

    def __init__(self, merged):
        self.s = np.array([a for a, _ in merged], float)
        self.e = np.array([b for _, b in merged], float)
        self.cum = np.concatenate([[0.0], np.cumsum(self.e - self.s)])

    def upto(self, t):
        """Busy seconds before ``t``."""
        i = np.searchsorted(self.s, t, side="right")     # starts <= t
        j = np.maximum(i - 1, 0)
        part = np.minimum(self.e[j], t) - self.s[j] if len(self.s) else 0.0
        return np.where(i > 0, self.cum[j] + part, 0.0)

    def covered(self, lo: float, hi: float) -> float:
        if hi <= lo:
            return 0.0
        return max(0.0, float(self.upto(hi) - self.upto(lo)))


def device_offset(tr: Trace, evs) -> float:
    """Seconds to add to a device plane's times to put the most of its
    busy time inside the host's program calls (the smallest such shift,
    where several tie)."""
    calls = merge((s, e) for name, s, e in tr.spans if name in CALL_SPANS)
    ops = merge((s, e) for _, s, e in evs)
    if not calls or not ops:
        return 0.0
    host = Busy(calls)
    s = np.array([a for a, _ in ops])
    e = np.array([b for _, b in ops])
    inside = [float(np.sum(host.upto(e + d) - host.upto(s + d)))
              for d in OFFSETS]
    return float(OFFSETS[int(np.argmax(inside))])


@dataclass
class Reduced:
    window_s: float               # the traced window's length
    offset_s: float               # device clock shift applied (chip 0)
    busy_s: float                 # union of device ops, mean over chips
    span_busy_s: dict             # host span name -> device busy inside
    span_count: dict              # host span name -> spans traced
    top_ops: list                 # [[op name, seconds]] by total, mean
    idle_by_span: list            # [[host span, seconds of device idle]]


def reduce(tr: Trace, lo: float | None = None,
           hi: float | None = None) -> Reduced:
    """Busy and idle time of the devices within [lo, hi] (by default the
    trace's own extent), each idle gap attributed to the host span that
    covers its middle."""
    if not tr.ops:
        raise ValueError("the trace holds no TPU device plane")
    offsets = {dev: device_offset(tr, evs) for dev, evs in tr.ops.items()}
    tr = Trace(ops={dev: [(n, s + offsets[dev], e + offsets[dev])
                          for n, s, e in evs]
                    for dev, evs in tr.ops.items()}, spans=tr.spans)
    if lo is None or hi is None:
        ends = [t for evs in tr.ops.values() for _, s, e in evs
                for t in (s, e)] + [t for _, s, e in tr.spans for t in (s, e)]
        lo = min(ends) if lo is None else lo
        hi = max(ends) if hi is None else hi
    nd = tr.n_devices
    starts = [s for _, s, _ in tr.spans]
    busy = 0.0
    span_busy: dict = defaultdict(float)
    idle: dict = defaultdict(float)
    per_op: dict = defaultdict(float)
    for evs in tr.ops.values():
        merged = merge((max(s, lo), min(e, hi)) for _, s, e in evs
                       if e > lo and s < hi)
        dev = Busy(merged)
        busy += dev.covered(lo, hi)
        for name, s, e in evs:
            per_op[name] += max(0.0, min(e, hi) - max(s, lo))
        for name, s, e in tr.spans:
            span_busy[name] += dev.covered(max(s, lo), min(e, hi))
        prev = lo
        for s, e in merged + [(hi, hi)]:
            if s > prev:
                idle[_span_at(tr.spans, starts, (prev + s) / 2)] += s - prev
            prev = max(prev, e)
    count: dict = defaultdict(int)
    for name, s, e in tr.spans:
        if s >= lo and e <= hi:
            count[name] += 1
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
    gaps = sorted(idle.items(), key=lambda kv: -kv[1])[:10]
    return Reduced(window_s=hi - lo, offset_s=next(iter(offsets.values())),
                   busy_s=busy / nd,
                   span_busy_s={k: v / nd for k, v in span_busy.items()},
                   span_count=dict(count),
                   top_ops=[[k, v / nd] for k, v in top],
                   idle_by_span=[[k, v / nd] for k, v in gaps])


def _span_at(spans, starts, t: float) -> str:
    """The host span that covers ``t``: the harness's spans follow one
    another and do not nest."""
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and spans[i][2] >= t:
        return spans[i][0]
    return "untraced_host"
