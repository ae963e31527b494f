"""Percent of the roofline the traced decode waves reach: the bytes and
operations a SmallThinker stack's waves need (``work_smallthinker.py``,
all of each layer's held experts read, the windowed layers' keys only
inside the window) at the chip's peaks, over the device's busy time
inside them."""
import dataclasses

from benchlib import readers as R
from benchlib import work_smallthinker


def read(run):
    if "sliding_window" not in run.model:
        return None
    work = work_smallthinker.wave_work(run.model)
    return R.wave_roofline_pct(dataclasses.replace(run, work=work))
