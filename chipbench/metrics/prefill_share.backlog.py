"""Percent of the window's wall time spent inside prefill calls."""
from benchlib import readers as R


def read(run):
    if R.open_loop(run) or run.win.seconds <= 0:
        return None
    return 100.0 * sum(R.prefill_s(run)) / run.win.seconds
