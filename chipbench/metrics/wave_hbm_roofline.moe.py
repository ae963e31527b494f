"""Percent of the roofline the traced decode waves reach: the bytes and
operations a Granite-4.0-H stack's waves need (``work_granite.py``, all
of each layer's held experts read) at the chip's peaks, over the
device's busy time inside them."""
import dataclasses

from benchlib import readers as R
from benchlib import work_granite


def read(run):
    if "layer_pattern" not in run.model:
        return None
    work = work_granite.wave_work(run.model)
    return R.wave_roofline_pct(dataclasses.replace(run, work=work))
