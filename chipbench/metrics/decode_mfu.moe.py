"""Percent of the chip's peak FLOP/s that the traced window's decode
waves needed: the operations a Granite-4.0-H stack's waves need
(``work_granite.py``, all of each layer's held experts computed) over
the traced window."""
import dataclasses

from benchlib import readers as R
from benchlib import work_granite


def read(run):
    if "layer_pattern" not in run.model:
        return None
    work = work_granite.wave_work(run.model)
    return R.decode_mfu_pct(dataclasses.replace(run, work=work))
