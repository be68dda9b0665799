"""The serving benchmark's harness: traffic, the driver around the frontend,
the yardstick's shared parts (the weight generator, the numerics of the
plain reference, chip peaks, the roofline's least time), the correctness
check, and the reduction from traces and counters to metrics. What depends
on a block's shape (its reference and control, its work counts, its
program keys) is in the block's module, ``bench/blocks/<block>.py``.

Everything here is the benchmark's own. Of the program it uses only the
system under test (``repro.serving.AsyncEngine`` over ``Engine``), its
counters and its kernel names.
"""
