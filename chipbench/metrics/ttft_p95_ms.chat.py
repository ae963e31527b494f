"""p95 over every request due in the window of the time from when it was
due to its first token: the tail that bursts queued behind one-at-a-time
prefills make."""
from benchlib import readers as R


def read(run):
    if not R.open_loop(run):
        return None
    v = R.p95(R.ttft_s(run))
    return None if v is None else 1e3 * v
