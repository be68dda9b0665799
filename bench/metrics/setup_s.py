"""Seconds from process start to the window's start: program load, seeded
weights, engine build and warm-up (compiles on a cell's first run,
compile-cache loads after it), and the warm-in's traffic."""


def read(run):
    return run.setup_s
