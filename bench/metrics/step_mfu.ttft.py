"""``step_mfu`` as the bound on what a prefill kernel's gain can move in
time to first token: the same whole-step share of the chip's peak, an
entry of its own because a metric moves one end-to-end metric."""
import os
import runpy

read = runpy.run_path(os.path.join(os.path.dirname(__file__),
                                   "step_mfu.py"))["read"]
