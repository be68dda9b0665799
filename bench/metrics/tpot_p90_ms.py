"""90th percentile, over the requests due in the window that received two
tokens or more by its end, of each request's mean gap between output
tokens after the first (host clock, client side)."""
from benchlib.stats import percentile


def read(run):
    gaps = []
    for r in run.due_in_window():
        ts = [t for t in r.times if t <= run.t1]
        if len(ts) >= 2:
            gaps.append((ts[-1] - ts[0]) / (len(ts) - 1))
    p = percentile(gaps, 90)
    return None if p is None else p * 1e3
