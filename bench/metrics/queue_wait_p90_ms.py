"""90th percentile, over the requests due in the window, of the time from
submission to first lane admission, by the program's own stamps
(``Request.submit_time`` and ``admit_time``). A request not admitted by
the window's end counts with its wait so far."""
from benchlib.stats import percentile


def read(run):
    waits = []
    for r in run.due_in_window():
        req = r.stream.req
        admit = req.admit_time if 0 <= req.admit_time <= run.t1 else run.t1
        waits.append(admit - req.submit_time)
    p = percentile(waits, 90)
    return None if p is None else p * 1e3
