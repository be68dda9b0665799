"""Median, over the steps dispatched in the window, of the serving loop's
busy time for one step: schedule + build + dispatch + apply, as the
program's step log times them (``repro.serving.steplog``; the same phases
as its ``serve.*`` spans). The time the loop waits on the device is not in
it. None where the program keeps no step log or logged no step in the
window."""
import os
import runpy

from benchlib.stats import percentile

window_steps = runpy.run_path(os.path.join(os.path.dirname(__file__),
                                           "step_slot_use.py"))["window_steps"]


def read(run):
    p = percentile([r.host_s for r in window_steps(run)], 50)
    return None if p is None else p * 1e3
