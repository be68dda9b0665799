"""90th percentile, over the requests due in the window, of the time from
a request's due time to its first token (host clock, client side). A
request with no token by the window's end counts with its wait so far."""
from benchlib.stats import percentile


def read(run):
    waits = [((r.times[0] if r.times and r.times[0] <= run.t1 else run.t1)
              - r.due) for r in run.due_in_window()]
    p = percentile(waits, 90)
    return None if p is None else p * 1e3
