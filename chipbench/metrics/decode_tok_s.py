"""Tokens served in the window (first tokens included) over the window's
seconds, in a closed loop."""
from benchlib import readers as R


def read(run):
    if R.open_loop(run) or run.win.seconds <= 0:
        return None
    return run.win.tokens_in_window() / run.win.seconds
