"""Percent of the chip's peak FLOP/s that the traced window's decode
waves needed: the operations a SmallThinker stack's waves need
(``work_smallthinker.py``, the windowed layers' attention only inside
the window) over the traced window."""
import dataclasses

from benchlib import readers as R
from benchlib import work_smallthinker


def read(run):
    if "sliding_window" not in run.model:
        return None
    work = work_smallthinker.wave_work(run.model)
    return R.decode_mfu_pct(dataclasses.replace(run, work=work))
