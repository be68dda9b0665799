"""Device time of one decode-only step: the median duration of the traced
step programs named ``jit_serve_step_decode`` (the program names each
step kind's program; ``XLA Modules`` line of each device), averaged over
the devices. None without a trace, or where no such program ran in it (a
program that does not name its step kinds)."""
from benchlib.stats import percentile
from benchlib.trace import DEVICE_PREFIX

MODULES_LINE = "XLA Modules"
MODULE = "jit_serve_step_decode"


def read(run):
    if run.trace_rows is None:
        return None
    per_dev = {}
    for plane, line, name, _, dur in run.trace_rows:
        if plane.startswith(DEVICE_PREFIX) and line == MODULES_LINE \
                and name.startswith(MODULE):
            per_dev.setdefault(plane, []).append(dur)
    if not per_dev:
        return None
    meds = [percentile(d, 50) for d in per_dev.values()]
    return sum(meds) / len(meds) / 1e6
