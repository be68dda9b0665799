"""The routed experts' share of their roofline: the least time the traced
steps' expert work needs at the chip's peaks over the summed device time
of the grouped expert kernel in the trace.

The work, from shapes (``bench/blocks/mla_moe.py``): per step of ``n``
real tokens, ``top_k`` (token, expert) pairs per token through a SiLU-gated
expert in every MoE layer (``moe_step_flops``), and the bytes of the
experts such a step hits, expected under uniform routing (``experts *
(1 - ((experts - top_k) / experts) ** n)`` per layer, each read once),
plus each pair's activation in and out (``moe_step_bytes``): expectations,
not the routing a step had. The steps are those the driver saw dispatched
while the trace ran, prefill and decode alike, as the kernel runs in both.
The kernel is found by the name of the program's jitted wrapper around it
(``_moe_experts``, ``kernels/ops.py``), which its op carries in the trace.
None without a trace, or where no such kernel ran (a program without
it)."""
from benchlib import work
from benchlib.trace import kernel_seconds

KERNELS = ("_moe_experts",)


def read(run):
    if run.trace_rows is None:
        return None
    blk, d = run.block, run.dims
    need = 0.0
    for s in run.steps:
        n = sum(c[1] for c in s.chunks) + len(s.decode_ctx)
        if n:
            need += work.least_time(blk.moe_step_flops(d, n),
                                    blk.moe_step_bytes(d, n), run.peaks)
    spent = kernel_seconds(run.trace_rows, KERNELS)
    if need <= 0 or spent <= 0:
        return None
    return 100.0 * need / spent
