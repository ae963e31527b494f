"""Percent of the roofline the traced decode waves reach: the bytes and
operations they need (``work.py``) at the chip's peaks, over the
device's busy time inside them."""
from benchlib import readers as R


def read(run):
    return R.wave_roofline_pct(run) if not R.open_loop(run) else None
