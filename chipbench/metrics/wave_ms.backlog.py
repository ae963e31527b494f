"""Median host milliseconds of one decode wave in the window."""
from benchlib import readers as R


def read(run):
    if R.open_loop(run):
        return None
    v = R.median(R.wave_s(run))
    return None if v is None else 1e3 * v
