"""Output tokens delivered to clients inside the window, per second of it
(host clock, client side): every token taken off a stream in the window,
whatever request it belongs to."""
from benchlib.stats import rate


def read(run):
    n = sum(run.t0 <= t <= run.t1 for r in run.records for t in r.times)
    return rate(n, run.window_s)
