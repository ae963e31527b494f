"""Median over every request due in the window of the time from when it
was due to its first token (a request still waiting at the close is
served during the drain and counted with its real wait)."""
from benchlib import readers as R


def read(run):
    if not R.open_loop(run):
        return None
    v = R.median(R.ttft_s(run))
    return None if v is None else 1e3 * v
