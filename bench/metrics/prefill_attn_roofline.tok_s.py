"""``prefill_attn_roofline``, read in a cell whose time to first token and time per
output token are not end-to-end metrics (their 90th percentiles over a
window's few requests swing with host stalls there), so that it
moves ``output_tok_s``."""
import os
import runpy

read = runpy.run_path(os.path.join(os.path.dirname(__file__),
                                   "prefill_attn_roofline.py"))["read"]
