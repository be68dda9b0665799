"""Share of the token slots the window's step programs ran that carried a
real token: 100 x (prompt-chunk tokens + decode lanes) / (rows x bucket),
summed over the steps dispatched in the window, from the program's step
log (``repro.serving.steplog``). The rest is padding: rows of idle lanes
and chunk columns past a chunk's end. None where the program keeps no
step log or logged no step in the window."""


def window_steps(run):
    """The step-log records dispatched inside the run's window."""
    try:
        from repro.serving import steplog
    except ImportError:                 # a program without the step log
        return []
    return steplog.RECENT.between(run.t0, run.t1)


def read(run):
    steps = window_steps(run)
    slots = sum(r.slots for r in steps)
    if not slots:
        return None
    return 100.0 * sum(r.real_tokens for r in steps) / slots
