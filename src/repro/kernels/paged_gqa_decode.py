"""Fused LLM-CoOpt decode-attention Pallas kernel (the paper's hot path),
over the GLOBAL paged-KV pool.

One kernel fuses all three techniques (DESIGN.md §2):
  Opt-KV  — KV pages stored FP8 e4m3 + per-(token, head) scale; dequantized
            on the fly at the HBM->VMEM boundary (Eq. 6 ``gather_cached_kv``).
  Opt-GQA — queries arrive folded (B, Hkv, G, D); each KV tile is streamed
            into VMEM ONCE and shared by the G query heads of its group
            (Eq. 7/8). The Original (MHA-semantics) mode re-streams KV per
            query head — the redundancy the paper measures.
  Opt-Pa  — Phase 1 valid-block filtering (Eq. 9): the caller masks page-
            table entries wholly outside the live context to -1, and the
            kernel predicates them off with ``pl.when`` (neither DMA'd nor
            computed); Phase 2 block-wise softmax (Eq. 10): the DCU
            ``block_sum`` shared-memory reduction becomes a VMEM-resident
            running (max, sum, acc) carried across the page grid dim.

Pool addressing: the cache has NO batch dimension — the kernel takes the
WHOLE pool of every layer, ``kv_pages (L, 2, P_total, Hkv, ps, D)``, shared by
every lane, and a ``layer`` scalar. The layer is chosen in the BlockSpec
index_maps and one block carries a head page's K and V (a (2, ps, D) pair of
tiles), so no layer or half is ever sliced out of the pool into a buffer of
its own.
Each lane's *physical* page table is scalar-prefetched and dereferenced
inside the index_map too, so the block DMA'd at grid step (b, h, i) IS lane
b's i-th logical page — the paper's "lazy memory mapping" realised as
data-dependent prefetch. A parallel *logical* table supplies token positions
(logical page id) for the causal / sliding-window masks; for dense decode it
is simply ``arange``.

TPU layout: grid = (batch, kv_head, page). Heads come before tokens within
a page, so one grid step DMAs the K and V (page_size, head_dim) tiles of one
KV head — lane dim = head_dim, sublane = tokens, which satisfies Mosaic's
(8, 128) block rule (a token-major ``(ps, Hkv, D)`` page would need a block
of 1 in the sublane place). The per-token fp8 scales ``(L, 2, P_total, Hkv,
ps)`` are DMA'd a page at a time for all heads, K's and V's in one block
(each (Hkv, ps) spans the array's last two dims), and the kernel reads its
head's rows; they are applied to the
(G, ps) score and probability tiles as a row vector, which equals
dequantizing the K/V rows. Scratch lives in VMEM; (m, l) are kept
lane-replicated (G, 128) as on-chip reduction tiles.

The windowed variant (block-sparse long-context policy, DESIGN.md §5) is the
same kernel with ``window``/``sink_pages`` static parameters: the caller
passes a {sink + sliding-window} page selection, positions come from the
logical table, and out-of-policy tokens are masked in-register.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30


def _pool_kernel(len_ref, phys_ref, log_ref, lyr_ref,     # scalar prefetch
                 q_ref, kv_ref, *refs,
                 ps: int, rep: int, opt_kv: bool, window: int, sink: int,
                 num_sel: int, return_state: bool):
    # kv_ref (2, 1, 1, ps, D): one head page's K and V; sc_ref (2, 1, Hkv,
    # ps): its page's K and V scales, which come only under Opt-KV
    sc_ref, o_ref, *refs = refs if opt_kv else (None, *refs)
    if return_state:
        mo_ref, lo_ref, m_ref, l_ref, acc_ref = refs
    else:
        m_ref, l_ref, acc_ref = refs
    b = pl.program_id(0)
    kvh = pl.program_id(1) // rep
    s_i = pl.program_id(2)
    G, D = q_ref.shape[2], q_ref.shape[3]
    length = len_ref[b]
    page = phys_ref[b, s_i]
    lpage = log_ref[b, s_i]

    @pl.when(s_i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Eq. 9 Phase 1: SkipSet / unallocated / beyond-context pages (-1) are
    # predicated off — their DMA was redirected to page 0 by the index_map
    # but neither compute nor the running reduction ever sees them.
    @pl.when(page >= 0)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)                  # (G, D)
        k = kv_ref[0, 0, 0].astype(jnp.float32)              # (ps, D)
        v = kv_ref[1, 0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * (1.0 / math.sqrt(D))                         # (G, ps)
        if opt_kv:  # Opt-KV Eq. 6: fused dequant at the VMEM boundary
            s = s * sc_ref[0, 0, pl.ds(kvh, 1), :]           # (1, ps) row
        pos = lpage * ps + jax.lax.broadcasted_iota(jnp.int32, (G, ps), 1)
        mask = pos < length
        if window:
            in_win = pos >= jnp.maximum(length - window, 0)
            in_sink = pos < sink * ps
            mask &= in_win | in_sink
        s = jnp.where(mask, s, _NEG)

        # Eq. 10 Phase 2: block-wise softmax, VMEM running reduce.
        m_prev = m_ref[:, 0:1]                               # (G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                               # (G, ps)
        l_new = l_ref[:, 0:1] * corr + jnp.sum(p, -1, keepdims=True)
        pv = p * sc_ref[1, 0, pl.ds(kvh, 1), :] if opt_kv else p
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            pv, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(s_i == num_sel - 1)
    def _finalize():
        l = l_ref[:, 0:1]
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        if return_state:
            # per-shard partial softmax state for the shard_map lse merge:
            # lane-replicated (G, 128) tiles, column 0 is the value
            mo_ref[0, 0] = m_ref[...]
            lo_ref[0, 0] = l_ref[...]


def paged_pool_decode(q, kv_pages, scale_pages, layer, cache_len,
                      phys_table, log_table, *, opt_kv: bool, opt_gqa: bool,
                      window: int = 0, sink_pages: int = 0,
                      return_state: bool = False, interpret: bool = False):
    """q: (B, Hq, D); kv_pages: (L, 2, P_total, Hkv, ps, D) the GLOBAL pool of
    every layer [fp8 if opt_kv]; scale_pages: (L, 2, P_total, Hkv, ps) f32,
    read only under opt_kv (None otherwise); layer: int32 scalar, the layer
    to attend; cache_len: (B,) int32; phys_table/log_table: (B, NSel) int32
    — physical page to DMA / logical page id for positions; -1 = skip (never
    DMA'd). Returns (B, Hq, D); with ``return_state`` also the final
    online-softmax (m, l) as (B, Hq) f32 — a shard holding NONE of a lane's
    pages reports (m=-1e30, l=0), so its contribution vanishes in the
    cross-shard log-sum-exp merge (``kernels.sharded``)."""
    B, Hq, D = q.shape
    _, _, P, Hkv, ps, _ = kv_pages.shape
    NSel = phys_table.shape[1]

    if opt_gqa:
        G, heads, rep = Hq // Hkv, Hkv, 1
    else:
        # Original MHA semantics: every query head re-streams its KV head.
        G, heads, rep = 1, Hq, max(Hq // Hkv, 1)
    qf = q.reshape(B, heads, G, D)

    # the layer is picked here, in place in the pool; one block holds the
    # head page's K and V
    def kv_idx(b, h, s, L, phys, log, lyr):
        return (lyr[0], 0, jnp.maximum(phys[b, s], 0), h // rep, 0, 0)

    def sc_idx(b, h, s, L, phys, log, lyr):
        return (lyr[0], 0, jnp.maximum(phys[b, s], 0), 0, 0)

    in_specs = [pl.BlockSpec((1, 1, G, D),
                             lambda b, h, s, L, phys, log, lyr: (b, h, 0, 0)),
                pl.BlockSpec((None, 2, 1, 1, ps, D), kv_idx)]
    operands = [qf, kv_pages]
    if opt_kv:
        in_specs += [pl.BlockSpec((None, 2, 1, Hkv, ps), sc_idx)]
        operands += [scale_pages]

    out_blk = pl.BlockSpec((1, 1, G, D),
                           lambda b, h, s, L, phys, log, lyr: (b, h, 0, 0))
    st_blk = pl.BlockSpec((1, 1, G, 128),
                          lambda b, h, s, L, phys, log, lyr: (b, h, 0, 0))
    out_specs = [out_blk]
    out_shape = [jax.ShapeDtypeStruct((B, heads, G, D), q.dtype)]
    if return_state:
        out_specs += [st_blk, st_blk]
        out_shape += [jax.ShapeDtypeStruct((B, heads, G, 128), jnp.float32)] * 2

    kern = functools.partial(_pool_kernel, ps=ps, rep=rep, opt_kv=opt_kv,
                             window=window, sink=sink_pages, num_sel=NSel,
                             return_state=return_state)
    res = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(B, heads, NSel),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((G, 128), jnp.float32),
                pltpu.VMEM((G, 128), jnp.float32),
                pltpu.VMEM((G, D), jnp.float32),
            ],
        ),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(cache_len, phys_table, log_table,
      jnp.asarray(layer, jnp.int32).reshape(1), *operands)
    out = res[0].reshape(B, Hq, D)
    if not return_state:
        return out
    m = res[1][..., 0].reshape(B, Hq)
    l = res[2][..., 0].reshape(B, Hq)
    return out, m, l


def _visit_kernel(vp_ref, vm_ref, vl_ref, lyr_ref,    # scalar prefetch
                  q_ref, len_ref, kv_ref, *refs,
                  ps: int, G: int, rep: int, opt_kv: bool, window: int,
                  sink: int, num_visits: int, return_state: bool):
    """Cross-lane visit grid: one step per deduplicated (page, lane-set).

    Query rows of ALL lanes ride VMEM-resident as one (BG, D) tile
    (BG = B * G, row r = lane * G + group-head); each visit DMAs /
    dequantizes its page ONCE and scatters scores into every member lane's
    running (m, l, acc) state. Non-member rows take an exact identity
    update (corr = exp(0) = 1, hard-zeroed p contributes +0.0), and a
    lane's member visits arrive in the same ascending-slot order the
    per-lane grid walks (``kernels.visits``), so per-row softmax state
    evolves update-for-update like ``_pool_kernel`` — the no-sharing plan
    is bit-identical, a shared plan saves (members - 1) page streams.
    """
    sc_ref, o_ref, *refs = refs if opt_kv else (None, *refs)
    if return_state:
        mo_ref, lo_ref, m_ref, l_ref, acc_ref = refs
    else:
        m_ref, l_ref, acc_ref = refs
    kvh = pl.program_id(0) // rep
    v_i = pl.program_id(1)
    BG = q_ref.shape[1]
    page = vp_ref[v_i]
    lpage = vl_ref[v_i]
    lanes = vm_ref[v_i]

    @pl.when(v_i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(page >= 0)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                     # (BG, D)
        k = kv_ref[0, 0, 0].astype(jnp.float32)              # (ps, D)
        v = kv_ref[1, 0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * (1.0 / math.sqrt(q_ref.shape[2]))            # (BG, ps)
        if opt_kv:  # Opt-KV Eq. 6: fused dequant — ONCE per visit, not per lane
            s = s * sc_ref[0, 0, pl.ds(kvh, 1), :]
        # row r belongs to lane r // G; membership = lane's bit in the mask
        lane_r = jax.lax.broadcasted_iota(jnp.int32, (BG, 1), 0) // G
        member = jnp.equal(
            jnp.bitwise_and(jnp.right_shift(lanes, lane_r), 1), 1)
        length = len_ref[:, 0:1]                             # (BG, 1)
        pos = lpage * ps + jax.lax.broadcasted_iota(jnp.int32, (BG, ps), 1)
        mask = member & (pos < length)
        if window:
            in_win = pos >= jnp.maximum(length - window, 0)
            in_sink = pos < sink * ps
            mask &= in_win | in_sink
        s = jnp.where(mask, s, _NEG)

        m_prev = m_ref[:, 0:1]                               # (BG, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        # member rows follow _pool_kernel verbatim (no hard zero on the
        # positional mask — exp underflow self-corrects identically);
        # non-member rows hard-zero so their (m, l, acc) are untouched
        p = jnp.where(member, jnp.exp(s - m_new), 0.0)       # (BG, ps)
        l_new = l_ref[:, 0:1] * corr + jnp.sum(p, -1, keepdims=True)
        pv = p * sc_ref[1, 0, pl.ds(kvh, 1), :] if opt_kv else p
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            pv, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(v_i == num_visits - 1)
    def _finalize():
        l = l_ref[:, 0:1]
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        if return_state:
            mo_ref[0] = m_ref[...]
            lo_ref[0] = l_ref[...]


def paged_pool_decode_visits(q, kv_pages, scale_pages, layer, cache_len,
                             visit_page, visit_lanes, visit_log,
                             *, opt_kv: bool, opt_gqa: bool, window: int = 0,
                             sink_pages: int = 0, return_state: bool = False,
                             interpret: bool = False):
    """Batched-visit twin of ``paged_pool_decode``: same pool/layer/query/
    window semantics, but the page grid dim iterates a deduplicated
    cross-lane visit list (``kernels.visits.plan_visits``) instead of
    (lane x page) — each page shared by N lanes is streamed into VMEM once,
    not N times. visit_page/visit_lanes/visit_log: (NV,) int32 plan vectors.
    Requires B <= visits.MAX_VISIT_LANES (int32 lane bitmask); ``ops``
    dispatches back to the per-lane grid beyond that."""
    B, Hq, D = q.shape
    _, _, P, Hkv, ps, _ = kv_pages.shape
    NV = visit_page.shape[0]

    if opt_gqa:
        G, heads, rep = Hq // Hkv, Hkv, 1
    else:
        G, heads, rep = 1, Hq, max(Hq // Hkv, 1)
    BG = B * G
    # rows r = b * G + g per head plane: lane-contiguous row blocks
    qf = q.reshape(B, heads, G, D).transpose(1, 0, 2, 3).reshape(heads, BG, D)
    len_rows = jnp.broadcast_to(
        cache_len.astype(jnp.int32)[:, None, None], (B, G, 128)
    ).reshape(BG, 128)

    def kv_idx(h, v, vp, vl, vm, lyr):
        return (lyr[0], 0, jnp.maximum(vp[v], 0), h // rep, 0, 0)

    def sc_idx(h, v, vp, vl, vm, lyr):
        return (lyr[0], 0, jnp.maximum(vp[v], 0), 0, 0)

    in_specs = [
        pl.BlockSpec((1, BG, D), lambda h, v, vp, vl, vm, lyr: (h, 0, 0)),
        pl.BlockSpec((BG, 128), lambda h, v, vp, vl, vm, lyr: (0, 0)),
        pl.BlockSpec((None, 2, 1, 1, ps, D), kv_idx)]
    operands = [qf, len_rows, kv_pages]
    if opt_kv:
        in_specs += [pl.BlockSpec((None, 2, 1, Hkv, ps), sc_idx)]
        operands += [scale_pages]

    out_blk = pl.BlockSpec((1, BG, D),
                           lambda h, v, vp, vl, vm, lyr: (h, 0, 0))
    st_blk = pl.BlockSpec((1, BG, 128),
                          lambda h, v, vp, vl, vm, lyr: (h, 0, 0))
    out_specs = [out_blk]
    out_shape = [jax.ShapeDtypeStruct((heads, BG, D), q.dtype)]
    if return_state:
        out_specs += [st_blk, st_blk]
        out_shape += [jax.ShapeDtypeStruct((heads, BG, 128), jnp.float32)] * 2

    kern = functools.partial(_visit_kernel, ps=ps, G=G, rep=rep, opt_kv=opt_kv,
                             window=window, sink=sink_pages, num_visits=NV,
                             return_state=return_state)
    res = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(heads, NV),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((BG, 128), jnp.float32),
                pltpu.VMEM((BG, 128), jnp.float32),
                pltpu.VMEM((BG, D), jnp.float32),
            ],
        ),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(visit_page, visit_lanes, visit_log,
      jnp.asarray(layer, jnp.int32).reshape(1), *operands)

    def unrows(x, last):
        return x.reshape(heads, B, G, last).transpose(1, 0, 2, 3) \
                .reshape(B, Hq, last)
    out = unrows(res[0], D)
    if not return_state:
        return out
    m = unrows(res[1], 128)[..., 0]
    l = unrows(res[2], 128)[..., 0]
    return out, m, l
