"""Opt-Pa — paged attention for long sequences (paper §3.3, Alg. 3).

Decode-phase attention of ONE query token per lane against the GLOBAL paged
KV pool: ``kv_pages (L, 2, P_total, Hkv, ps, D)`` of every layer, shared by
every lane, and a ``layer`` index naming the layer to attend, with a
per-lane ``page_table (B, P_lane)`` naming the lane's physical pages in
logical order (-1 = unallocated). The kernel path hands the pool on whole
(the kernels pick the layer in place); the jnp reference reads
``kv_pages[layer]``. Lanes never alias pages they can write
(refcounted pool, CoW prefix sharing), so the gather is race-free.

Two-stage strategy, mapped to TPU (DESIGN.md §3):
  Phase 1 — *valid-block filtering* (Eq. 9): only logical pages b in
  [0, ceil(t/B)) participate; unallocated (-1) table entries never load. In
  this jnp reference that is a gather of the lane's pages + masking; in the
  Pallas kernel (``paged_pool_decode``) the page table is scalar-prefetched
  and dereferenced inside the BlockSpec index_map, so skipped pages are never
  DMA'd — the paper's "lazy memory mapping" as data-dependent prefetch.
  Phase 2 — *block-wise softmax with shared-memory reduction* (Eq. 10): an
  online-softmax accumulation over page groups. The DCU's ``block_sum``
  shared-memory reduction becomes a VMEM-resident running (max, sum, acc).

The "Original" baseline (`coopt.opt_pa == False`) reproduces unmodified vLLM
semantics on this platform: every page in the lane's table is uniformly
loaded and a flat softmax is taken over the whole (padded) history — "all KVs
being loaded into memory regardless of whether they are actually useful"
(paper §2).

Opt-KV (fp8 dequant on read) and Opt-GQA (grouped queries) compose here;
``LLM-CoOpt`` = all three, which is what the fused kernel implements.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.coopt import CoOptConfig
from repro.core.opt_kv import (decode_page_select, dequant_pages,
                               gather_cached_kv, identity_page_table)
from repro.models.layers import repeat_kv, shard_act

_NEG = -1e30


def _scores(q, k, opt_gqa: bool):
    """q (B,Hq,D), k (B,T,Hkv,D) -> scores (B,Hq,T) f32 (scaled).

    Under the production mesh, q's and k's head_dim are kept model-sharded
    and the (much smaller) score tensor is the all-reduced partial sum —
    without the constraints GSPMD all-gathers the dequantized KV page group
    per scan step (EXPERIMENTS.md §Perf P3)."""
    B, Hq, D = q.shape
    Hkv = k.shape[2]
    scale = 1.0 / math.sqrt(D)
    q = shard_act(q, ("batch", None, "head_dim"))
    k = shard_act(k, ("batch", None, None, "head_dim"))
    if opt_gqa and Hkv != Hq:
        qg = q.reshape(B, Hkv, Hq // Hkv, D)
        s = jnp.einsum("bhgd,bthd->bhgt", qg, k,
                       preferred_element_type=jnp.float32)
        s = shard_act(s, ("batch", None, None, None))
        return s.reshape(B, Hq, -1) * scale
    k = repeat_kv(k, Hq // Hkv)
    s = jnp.einsum("bhd,bthd->bht", q, k,
                   preferred_element_type=jnp.float32)
    return shard_act(s, ("batch", None, None)) * scale


def _weighted_v(p, v, opt_gqa: bool, Hq: int):
    """p (B,Hq,T) f32, v (B,T,Hkv,D) -> (B,Hq,D) f32."""
    Hkv = v.shape[2]
    if opt_gqa and Hkv != Hq:
        pg = p.reshape(p.shape[0], Hkv, Hq // Hkv, p.shape[-1])
        o = jnp.einsum("bhgt,bthd->bhgd", pg, v.astype(jnp.float32))
        return o.reshape(p.shape[0], Hq, -1)
    v = repeat_kv(v, Hq // Hkv)
    return jnp.einsum("bht,bthd->bhd", p, v.astype(jnp.float32))


def paged_decode_attention(q, kv_pages, scale_pages, layer, cache_len, *,
                           coopt: CoOptConfig, window: int = 0,
                           sink_pages: int = 1,
                           page_table: Optional[jax.Array] = None) -> jax.Array:
    """q: (B, Hq, D); kv_pages: (L, 2, P_total, Hkv, ps, D) global pool of
    every layer, scale_pages (L, 2, P_total, Hkv, ps) | None; layer: int32
    scalar, the layer to attend; cache_len: (B,) tokens valid per lane (the
    current token must already be written); page_table: (B, P_lane)
    physical pages in logical order (default: static lane-identity
    partition of the pool).
    Returns (B, Hq, D) in q.dtype.
    """
    B, Hq, D = q.shape
    P_total, Hkv, ps = kv_pages.shape[2:5]
    if page_table is None:
        page_table = identity_page_table(B, P_total)

    if coopt.use_kernel:
        # (physical, logical) tables for the scalar-prefetched kernel —
        # Eq. 9 filtering / the {sink + window} policy decided host-free
        # (decode_page_select, shared with the MLA latent layout).
        from repro.kernels import ops
        phys, logical = decode_page_select(cache_len, page_table, ps,
                                           window=window,
                                           sink_pages=sink_pages,
                                           opt_pa=coopt.opt_pa)
        return ops.paged_pool_decode(
            q, kv_pages, scale_pages, layer, cache_len, phys, logical,
            opt_kv=coopt.opt_kv,
            opt_gqa=True if window else coopt.opt_gqa,
            window=window, sink_pages=sink_pages if window else 0,
            share_visits=coopt.share_visits)

    kv_pages = kv_pages[layer]
    scale_pages = None if scale_pages is None else scale_pages[layer]
    if window:
        # Block-sparse policy: Opt-KV SkipSet = outside {sinks + window},
        # decided in the logical page domain then mapped to physical pages
        # (same selection the kernel branch prefetches).
        phys, logical = decode_page_select(cache_len, page_table, ps,
                                           window=window,
                                           sink_pages=sink_pages)
        return _windowed(q, kv_pages, scale_pages, cache_len, phys, logical,
                         window, sink_pages, coopt)

    # jnp reference: gather the lane's pages (logical order) then reduce.
    flat = gather_cached_kv(kv_pages, scale_pages, page_table, coopt)
    Psel = page_table.shape[1]
    kv_lane = flat.reshape(2, B, Psel, ps, Hkv, D)
    valid = jnp.repeat(page_table >= 0, ps, axis=1)       # (B, Psel*ps)
    coopt = coopt.replace(opt_kv=False)                   # already dequantized
    if coopt.opt_pa:
        return _blockwise(q, kv_lane, None, cache_len, coopt, valid)
    return _flat(q, kv_lane, None, cache_len, coopt, valid)


# ------------------------------------------------ continuation prefill ----
def paged_chunk_attention(q, kv_pages, scale_pages, layer, positions,
                          page_table, coopt: CoOptConfig, *, window: int = 0,
                          sink_pages: int = 1, seg_q=None, page_seg=None,
                          page_base=None) -> jax.Array:
    """Chunked-continuation prefill attention (the ONE ragged step path):
    a chunk of queries per lane — q (B,S,Hq,D) with absolute ``positions``
    (B,S) — attends over the lane's WHOLE cached history (prefix-cache hits,
    earlier chunks, and this chunk, already written) in layer ``layer`` of
    the pool of every layer (``kv_pages`` (L,2,P_total,Hkv,ps,D),
    ``scale_pages`` | None) through its page table.
    Key j of the gathered view is the lane's logical position j, so causality
    is a plain position compare; a decode lane is a chunk of length 1.

    ``window`` > 0 applies the block-sparse {sliding window + sink} policy
    (griffin local attention, long-context decode) with the same mask as the
    decode path, so a token's logits are schedule-independent.

    Concat-prefill packing: ``seg_q`` (B,S), ``page_seg`` (B,NP) and
    ``page_base`` (B,NP) pack several prompts' chunks into one row — a
    query attends a key only when their segment ids match, and key
    positions restart per segment at ``page_base * ps``. None = unpacked
    (byte-identical to the pre-packing math).
    Returns (B, S, Hq, D) in q.dtype."""
    B, S, Hq, D = q.shape
    P_total, Hkv, ps = kv_pages.shape[2:5]
    if page_table is None:
        page_table = identity_page_table(B, P_total)

    if coopt.use_kernel:
        from repro.kernels import ops
        return ops.paged_chunk_prefill(
            q, positions, kv_pages, scale_pages, layer, page_table,
            opt_kv=coopt.opt_kv, opt_gqa=coopt.opt_gqa, window=window,
            sink_pages=sink_pages, seg_q=seg_q, page_seg=page_seg,
            page_base=page_base)

    # jnp reference: gather the lane's pages in logical order, then a
    # position-masked softmax over the gathered view.
    flat = gather_cached_kv(kv_pages[layer],
                            None if scale_pages is None
                            else scale_pages[layer], page_table, coopt)
    k, v = flat                                        # (B,T,Hkv,D) each
    T = k.shape[1]
    if not coopt.opt_gqa and Hkv != Hq:
        # Original: KV physically expanded per query head (Fig. 2)
        k, v = repeat_kv(k, Hq // Hkv), repeat_kv(v, Hq // Hkv)
        Hg, G = Hq, 1
    else:
        Hg, G = Hkv, Hq // Hkv
    qg = q.reshape(B, S, Hg, G, D).astype(jnp.float32)
    s = jnp.einsum("bshgd,bthd->bhgst", qg, k.astype(jnp.float32))
    s = s * (1.0 / math.sqrt(D))
    if page_base is not None:
        # packed: key j's position restarts per segment at page_base*ps
        kpos = (page_base.astype(jnp.int32)[:, :, None] * ps
                + jnp.arange(ps, dtype=jnp.int32)[None, None, :]
                ).reshape(B, T)[:, None, :]
    else:
        kpos = jnp.arange(T, dtype=jnp.int32)[None, None, :]
    qpos = positions[:, :, None]
    mask = (kpos <= qpos) & \
        jnp.repeat(page_table >= 0, ps, axis=1)[:, None, :]
    if seg_q is not None:
        mask &= (jnp.repeat(page_seg.astype(jnp.int32), ps, axis=1)[:, None]
                 == seg_q.astype(jnp.int32)[:, :, None])
    if window:
        mask &= (kpos > qpos - window) | (kpos < sink_pages * ps)
    s = jnp.where(mask[:, None, None], s, _NEG)
    pr = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgst,bthd->bshgd", pr, v.astype(jnp.float32))
    return o.reshape(B, S, Hq, D).astype(q.dtype)


# --------------------------------------------------------------- Original --
def _flat(q, kv_pages, scale_pages, cache_len, coopt, valid):
    B, Hq, D = q.shape
    _, _, P, ps, Hkv, _ = kv_pages.shape
    kv = dequant_pages(kv_pages, scale_pages, coopt)        # ALL pages loaded
    k, v = kv.reshape(2, B, P * ps, Hkv, D)
    s = _scores(q, k, coopt.opt_gqa)                        # (B,Hq,T)
    pos = jnp.arange(P * ps)[None, None, :]
    mask = pos < cache_len[:, None, None]
    if valid is not None:
        mask &= valid[:, None, :]
    s = jnp.where(mask, s, _NEG)
    m = jnp.max(s, axis=-1, keepdims=True)                  # Eq. 8 / Eq. 10
    p = jnp.exp(s - m)
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    o = _weighted_v(p, v, coopt.opt_gqa, Hq)
    return o.astype(q.dtype)


# ----------------------------------------------------- Opt-Pa (block-wise) --
def effective_page_group(num_pages: int, page_group: int) -> Tuple[int, int]:
    """Opt-Pa group size actually used by ``_blockwise`` for a pool of
    ``num_pages`` pages: (group, padded page count). The page axis is PADDED
    (masked) up to the next multiple of ``page_group`` instead of silently
    degrading the group — a group of 1 would turn Eq. 10's shared-memory
    block reduction into a per-page scan."""
    pg = max(min(page_group, num_pages), 1)
    return pg, num_pages + (-num_pages) % pg


def _blockwise(q, kv_pages, scale_pages, cache_len, coopt, valid):
    B, Hq, D = q.shape
    _, _, P, ps, Hkv, _ = kv_pages.shape
    pg, P_pad = effective_page_group(P, coopt.page_group)
    if P_pad != P:
        # keep the configured group: pad the page axis with masked pages
        # rather than halving pg down to a degenerate per-page scan
        pad = P_pad - P
        kv_pages = jnp.pad(kv_pages,
                           ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
        if scale_pages is not None:
            scale_pages = jnp.pad(
                scale_pages, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        if valid is None:                     # pad pages must be masked out
            valid = jnp.ones((B, P * ps), bool)
        valid = jnp.pad(valid, ((0, 0), (0, pad * ps)))
        P = P_pad
    NG, T = P // pg, pg * ps

    kv_g = kv_pages.reshape(2, B, NG, T, Hkv, D)
    sc_g = (scale_pages.reshape(2, B, NG, T, Hkv)
            if scale_pages is not None else None)
    valid_g = valid.reshape(B, NG, T) if valid is not None else None

    def body(carry, g):
        m, l, acc = carry
        kv = dequant_pages(kv_g[:, :, g], None if sc_g is None else sc_g[:, :, g],
                           coopt)
        k, v = kv
        s = _scores(q, k, coopt.opt_gqa)                    # (B,Hq,T)
        pos = g * T + jnp.arange(T)[None, None, :]
        mask = pos < cache_len[:, None, None]
        if valid_g is not None:
            mask &= valid_g[:, g][:, None, :]
        s = jnp.where(mask, s, _NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m - m_new)                           # block_sum analogue
        p = jnp.exp(s - m_new)
        l = l * corr[..., 0] + jnp.sum(p, axis=-1)
        acc = acc * corr + _weighted_v(p, v, coopt.opt_gqa, Hq)
        return (m_new, l, acc), None

    m0 = jnp.full((B, Hq, 1), _NEG, jnp.float32)
    l0 = jnp.zeros((B, Hq), jnp.float32)
    a0 = jnp.zeros((B, Hq, D), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), jnp.arange(NG))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


# ------------------------------------------------ window/sink block-sparse --
def _windowed(q, kv_pages, scale_pages, cache_len, phys_table, logical_table,
              window, sink_pages, coopt):
    B, Hq, D = q.shape
    ps = kv_pages.shape[3]
    flat = gather_cached_kv(kv_pages, scale_pages, phys_table, coopt)
    k, v = flat                                              # (B,Ts,H,D)
    pos = jnp.maximum(logical_table, 0)[:, :, None] * ps + \
        jnp.arange(ps)[None, None, :]
    pos = pos.reshape(B, -1)                                 # (B, Ts)
    in_ctx = pos < cache_len[:, None]
    in_win = pos >= jnp.maximum(cache_len[:, None] - window, 0)
    in_sink = pos < sink_pages * ps
    mask = in_ctx & (in_win | in_sink) & \
        (phys_table >= 0).repeat(ps, axis=1)
    s = _scores(q, k, coopt.opt_gqa)
    s = jnp.where(mask[:, None, :], s, _NEG)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    o = _weighted_v(p, v, coopt.opt_gqa, Hq)
    return o.astype(q.dtype)
