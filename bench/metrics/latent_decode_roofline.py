"""Latent decode attention's share of its roofline: the least time the
decoded tokens' attention needs at the chip's peaks over the summed device
time of the paged latent decode kernel in the trace.

The work, from shapes (``bench/blocks/mla_moe.py``), per decode lane and
layer at context ``ctx``: ``ctx`` positions of the fp8 latent pool with
their two scales (584 B each at DeepSeek-V2-Lite's widths), the absorbed
query read and the latent-space output written (``decode_attn_bytes``),
and 2 x heads x (R + dr + R) FLOPs per position (``attn_flops``). The
work is that of the decode-only steps the driver saw dispatched while the
trace ran: decode lanes of steps that also carry a prompt chunk run
through the chunk kernel instead. The kernel is found by the name of the
program's jitted wrapper around it (``kernels/ops.py``). None without a
trace, or where no such kernel ran."""
from benchlib import work
from benchlib.trace import kernel_seconds

KERNELS = ("_paged_latent_decode_single",)


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
