"""Useful work, counted from shapes: the FLOPs and bytes that the traffic
needs, at real prompt and output lengths and true contexts. Padded token
slots, a kernel's grid and the compiled HLO never enter, so no program
change moves this yardstick and no share read against it can pass 100%.

All counts are for a dense GQA decoder (``Dims``), per token or per call of
one layer's attention kernel as stated.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Dims:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    page_size: int = 64
    kv_bytes: int = 1            # fp8 K/V pool
    scale_bytes: int = 4         # one f32 scale per (token, head) of K and V

    @classmethod
    def from_config(cls, cfg: dict) -> "Dims":
        return cls(layers=cfg["num_hidden_layers"],
                   d_model=cfg["hidden_size"],
                   heads=cfg["num_attention_heads"],
                   kv_heads=cfg["num_key_value_heads"],
                   head_dim=cfg["head_dim"],
                   d_ff=cfg["intermediate_size"],
                   vocab=cfg["vocab_size"],
                   page_size=cfg["kv_page_size"])


def layer_matmul_flops(d: Dims) -> int:
    """Dense-layer FLOPs of one token in one layer: q, k, v and o
    projections and the gated feed-forward (2 FLOPs per multiply-add)."""
    qkvo = (d.d_model * d.heads * d.head_dim * 2
            + d.d_model * d.kv_heads * d.head_dim * 2)
    return 2 * (qkvo + 3 * d.d_model * d.d_ff)


def head_flops(d: Dims) -> int:
    """The output head for one sampled position."""
    return 2 * d.d_model * d.vocab


def attn_flops(d: Dims, ctx: int) -> int:
    """One query attending ``ctx`` keys in one layer: scores and the
    weighted sum of values."""
    return 4 * d.heads * d.head_dim * ctx


def chunk_attn_flops(d: Dims, start: int, n: int) -> int:
    """A chunk of ``n`` causal queries at positions ``start..start+n-1`` in
    one layer (query ``p`` attends ``p + 1`` keys)."""
    return 4 * d.heads * d.head_dim * (n * start + n * (n + 1) // 2)


def prompt_flops(d: Dims, start: int, n: int) -> int:
    """Prefilling prompt positions ``start..start+n-1`` through every
    layer, without the head."""
    return d.layers * (n * layer_matmul_flops(d)
                       + chunk_attn_flops(d, start, n))


def decode_flops(d: Dims, ctx: int) -> int:
    """One decoded token at context ``ctx`` (keys attended, itself
    included) through every layer and the head."""
    return d.layers * (layer_matmul_flops(d) + attn_flops(d, ctx)) \
        + head_flops(d)


def _pages(tokens: int, page_size: int) -> int:
    return -(-tokens // page_size)


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


def decode_attn_bytes(d: Dims, ctx: int) -> int:
    """One decoded token's attention in one layer: the live fp8 pages of K
    and V with their scales, read once for all grouped heads, and its
    bf16 query (the Opt-KV, Opt-Pa, Opt-GQA pool of the ``coopt`` mode)."""
    return kernel_bytes_per_call(1, _pages(ctx, d.page_size), d.page_size,
                                 d.kv_heads, d.head_dim, opt_kv=True,
                                 opt_pa=True, opt_gqa=True, Hq=d.heads,
                                 cache_len=ctx)


def chunk_attn_bytes(d: Dims, start: int, n: int) -> int:
    """A chunk of ``n`` queries at ``start..start+n-1`` in one layer: the
    live pages of K and V up to the chunk's end with their scales, read
    once, plus the bf16 queries read and outputs written."""
    pages = _pages(start + n, d.page_size)
    kv = 2 * pages * d.page_size * d.kv_heads * (d.head_dim * d.kv_bytes
                                                 + d.scale_bytes)
    return kv + 2 * n * d.heads * d.head_dim * 2


def least_time(flops: float, nbytes: float, peaks: dict) -> float:
    """The roofline's least time for ``flops`` and ``nbytes``: the larger
    of compute at peak and traffic at peak bandwidth."""
    return max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bw"])


def window_flops(run) -> int:
    """A run's useful FLOPs in its window: the prompt positions whose K/V
    entered the pool in it (the program's ``Request.num_computed`` at the
    window's edges), the decode step of every token delivered in it after
    a request's first, and one output-head evaluation per delivered
    token."""
    d = run.dims
    total = 0
    for r in run.records:
        if r.nc1 > r.nc0:
            total += prompt_flops(d, r.nc0, r.nc1 - r.nc0)
        for i, t in enumerate(r.times):
            if run.t0 <= t <= run.t1:
                total += (decode_flops(d, r.prompt_len + i) if i
                          else head_flops(d))
    return total
