"""Decode attention's share of its roofline: the least time the decoded
tokens' attention needs at the chip's peaks (live fp8 K/V pages, their
scales and the query, per layer, as the dense GQA block counts them:
``bench/blocks/dense_gqa.py``) over the summed device time of the paged
decode kernel in the trace.

The work is that of the decode-only steps dispatched while the trace ran
(decode lanes of steps that also carry a prompt chunk run through the
chunk-prefill kernel instead). The kernel is found by the name of the
program's jitted wrapper around it (``kernels/ops.py``), which the kernel's
op carries in the trace."""
from benchlib import work
from benchlib.trace import kernel_seconds

KERNELS = ("_paged_pool_decode_single",)


def read(run):
    if run.trace_rows is None:
        return None
    blk, d = run.block, run.dims
    need = 0.0
    for s in run.steps:
        if s.kind != "decode" or not s.decode_ctx:
            continue
        f = sum(blk.attn_flops(d, c) for c in s.decode_ctx)
        b = sum(blk.decode_attn_bytes(d, c) for c in s.decode_ctx)
        need += d.layers * work.least_time(f, b, run.peaks)
    spent = kernel_seconds(run.trace_rows, KERNELS)
    if need <= 0 or spent <= 0:
        return None
    return 100.0 * need / spent
