"""Percent of the traced window in which no operation ran on the device."""
from benchlib import readers as R


def read(run):
    return R.idle_pct(run) if R.open_loop(run) else None
