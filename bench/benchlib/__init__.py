"""The serving benchmark's harness: traffic, the driver around the frontend,
the yardstick (FLOP and byte models, chip peaks, the plain reference), and
the reduction from traces and counters to metrics.

Everything here is the benchmark's own. Of the program it uses only the
system under test (``repro.serving.AsyncEngine`` over ``Engine``), its
counters and its kernel names.
"""
