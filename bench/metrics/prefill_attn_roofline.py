"""Chunked-prefill attention's share of its roofline: the least time that
the prompt chunks' attention (causal FLOPs over their true positions, live
fp8 K/V pages with scales read once, queries and outputs), and that of the
decode lanes riding in the same steps, need at the chip's peaks (as the
dense GQA block counts them: ``bench/blocks/dense_gqa.py``), over the
summed device time of the chunk-prefill kernel in the trace, found by the
name of the program's jitted wrapper around it (``kernels/ops.py``). The
work is that of the prompt-carrying steps dispatched while the trace
ran."""
from benchlib import work
from benchlib.trace import kernel_seconds

KERNELS = ("_paged_chunk_prefill_single",)


def read(run):
    if run.trace_rows is None:
        return None
    blk, d = run.block, run.dims
    need = 0.0
    for s in run.steps:
        if s.kind != "prefill":
            continue
        f = sum(blk.chunk_attn_flops(d, a, n) for a, n in s.chunks) + \
            sum(blk.attn_flops(d, c) for c in s.decode_ctx)
        b = sum(blk.chunk_attn_bytes(d, a, n) for a, n in s.chunks) + \
            sum(blk.decode_attn_bytes(d, c) for c in s.decode_ctx)
        need += d.layers * work.least_time(f, b, run.peaks)
    spent = kernel_seconds(run.trace_rows, KERNELS)
    if need <= 0 or spent <= 0:
        return None
    return 100.0 * need / spent
