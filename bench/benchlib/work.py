"""Useful work, counted from shapes: the FLOPs and bytes that the traffic
needs, at real prompt and output lengths and true contexts. Padded token
slots, a kernel's grid and the compiled HLO never enter, so no program
change moves this yardstick and no share read against it can pass 100%.

What depends on a block's shape (its sizes, ``Dims``, and the FLOPs and
bytes of a token or a kernel call) is the cell's block module's
(``bench/blocks/<block>.py``); here are the parts every block shares.
"""
from __future__ import annotations


def kernel_bytes_per_call(B, P, ps, Hkv, D, *, opt_kv, opt_pa, opt_gqa, Hq,
                          cache_len, shared_prefix_pages=0, lanes_sharing=0,
                          share_visits=False):
    """HBM->VMEM traffic of one decode-attention call (bytes), for ``B``
    lanes at one ``cache_len``: live pages of K and V, their scales and
    the queries. ``shared_prefix_pages`` pages held by ``lanes_sharing``
    lanes stream once on the visit grid. (Copied from the program's
    ``benchmarks/kernel_micro.py`` so that it cannot move under a PR.)"""
    kv_elt = 1 if opt_kv else 2
    pages_touched = (min((cache_len + ps - 1) // ps, P) if opt_pa else P)
    page_streams = B * pages_touched
    if share_visits and lanes_sharing > 1 and shared_prefix_pages > 0:
        shared = min(shared_prefix_pages, pages_touched)
        page_streams -= (min(lanes_sharing, B) - 1) * shared
    streams = 1 if opt_gqa else Hq // Hkv
    kv_bytes = 2 * page_streams * ps * Hkv * D * kv_elt * streams
    scale_bytes = (2 * page_streams * ps * Hkv * 4 * streams
                   if opt_kv else 0)
    q_bytes = B * Hq * D * 2
    return kv_bytes + scale_bytes + q_bytes


def least_time(flops: float, nbytes: float, peaks: dict) -> float:
    """The roofline's least time for ``flops`` and ``nbytes``: the larger
    of compute at peak and traffic at peak bandwidth."""
    return max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bw"])


def window_flops(run) -> int:
    """A run's useful FLOPs in its window: the prompt positions whose K/V
    entered the pool in it (the program's ``Request.num_computed`` at the
    window's edges), the decode step of every token delivered in it after
    a request's first, and one output-head evaluation per delivered
    token, each counted by the cell's block (``prompt_flops``,
    ``decode_flops``, ``head_flops``)."""
    b, d = run.block, run.dims
    total = 0
    for r in run.records:
        if r.nc1 > r.nc0:
            total += b.prompt_flops(d, r.nc0, r.nc1 - r.nc0)
        for i, t in enumerate(r.times):
            if run.t0 <= t <= run.t1:
                total += (b.decode_flops(d, r.prompt_len + i) if i
                          else b.head_flops(d))
    return total
