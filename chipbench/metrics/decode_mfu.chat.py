"""Percent of the chip's peak FLOP/s that the traced window's decode
waves needed: their operations (``work.py``) over the traced window."""
from benchlib import readers as R


def read(run):
    return R.decode_mfu_pct(run) if R.open_loop(run) else None
