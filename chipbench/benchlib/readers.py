"""What the metric files under ``metrics/`` read a run with.

Each ``metrics/<name>.py`` holds one ``read(run)`` that returns the
metric's value, or None where the run holds nothing to read (then the
metric is left out of the result line).  ``run`` is a ``RunView``.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass
class RunView:
    win: object             # serve.Window
    mix: dict
    model: dict
    setup_s: float
    plan_s: float | None    # None off the stitched path
    first_call_s: float     # host time of every program's first call
    work: object            # work.WaveWork
    peak: dict              # peaks.json entry of this device kind
    trace: object = None    # trace.Reduced of the traced run


def open_loop(run) -> bool:
    return run.mix["loop"] == "open"


def p95(xs) -> float | None:
    return float(np.percentile(np.asarray(xs), 95)) if len(xs) else None


def median(xs) -> float | None:
    return float(np.median(np.asarray(xs))) if len(xs) else None


def in_window(run, t: float) -> bool:
    return run.win.t0 <= t <= run.win.t_close


def ttft_s(run) -> list[float]:
    """Due time to first token of every request due in the window that
    got one (every such request does, or the run is not correct)."""
    w = run.win
    return [r.times[0] - r.due for r in w.reqs
            if r.due <= w.t_close and r.times]


def prefill_s(run) -> list[float]:
    return [e - s for s, e, _ in run.win.prefills if in_window(run, e)]


def wave_s(run) -> list[float]:
    return [e - s for s, e, _ in run.win.waves if in_window(run, e)]


def traced_waves(run) -> list:
    return [run.win.waves[i] for i in run.win.traced_waves]


def wave_roofline_pct(run) -> float | None:
    """Least time of the traced waves at the chip's peaks, over the
    device's busy time inside them, in percent."""
    tr, waves = run.trace, traced_waves(run)
    busy = tr.span_busy_s.get("decode_wave", 0.0) if tr else 0.0
    if not waves or busy <= 0:
        return None
    least = sum(max(run.work.bytes(kv) / run.peak["hbm_bytes_per_s"],
                    run.work.flops(kv) / run.peak["flops_per_s"])
                for _, _, kv in waves)
    return 100.0 * least / busy


def decode_mfu_pct(run) -> float | None:
    """Operations of the traced waves over the traced window, as a share
    of the chip's peak."""
    waves = traced_waves(run)
    if not waves or run.trace is None or run.trace.window_s <= 0:
        return None
    flops = sum(run.work.flops(kv) for _, _, kv in waves)
    return 100.0 * flops / run.trace.window_s / run.peak["flops_per_s"]


def idle_pct(run) -> float | None:
    if run.trace is None or run.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
