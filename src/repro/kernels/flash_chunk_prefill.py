"""Paged continuation-prefill Pallas kernel — chunked prefill (Sarathi-style
mixed step) attending over the GLOBAL paged-KV pool.

This is the missing piece between ``flash_prefill`` (contiguous in-flight K/V,
whole-prompt causal tiles) and ``paged_gqa_decode`` (one query token against
the pool): a CHUNK of queries per lane, each with an absolute position, whose
keys are the lane's *already-cached* pages — earlier chunks, prefix-cache
hits, and the chunk itself (written before attention). The lane's physical
page table is scalar-prefetched and dereferenced inside the BlockSpec
index_map, so a chunk's queries attend over prior cached pages without the
host gathering the whole history into a contiguous buffer (Opt-Pa "lazy
memory mapping", paper §3.3, applied to the prefill continuation). The
kernel takes the whole pool of every layer and a ``layer`` scalar; the
index_maps pick the layer and one block carries a head page's K and V, so
neither a layer nor a half is sliced out of the pool.

Grid: (batch, kv_head, q_group, logical_page). Queries arrive grouped
(Opt-GQA): rows are (seq, group) pairs, so each KV page is streamed into VMEM
once per G query heads. Per-row absolute positions ride along as a VMEM
input blocked with the query tiles; the causal / sliding-window / sink masks
compare them against ``logical_page * ps + iota`` — Eq. 9's valid-block
filter in the logical page domain, Eq. 10's online softmax across pages.

Tile-resident chunk streaming: the page dim is innermost and every row-side
block (q, positions, out, state, scratch) is keyed on the RESIDENT GROUP
index only, so the whole group stays VMEM-resident across the inner page
loop and a page is DMA'd once per group — not once per small query tile.
The group is sized by ``resident_rows`` (largest divisor of R under
``RESIDENT_ROWS`` rows that keeps (seq, group) rows together), so a typical
chunk (R <= 1024 rows) streams each cached page exactly ONCE per (b, h);
the page re-stream factor is ceil(R / rq) instead of the former fixed
R / 256. VMEM stays under the 8 MiB budget: rows cost (2*D + 3*128) * 4 B
each double-buffered (~5.9 MiB at rq = 1024, D = 128).

Page skipping: table entries of -1 (unallocated, or masked beyond the lane's
``cache_len`` by the caller) are predicated off with ``pl.when`` — neither
DMA'd (index_map redirects to page 0) nor computed. Pages entirely in the
future of the query tile are skipped by the same predicate using the tile's
maximum position.

Concat-prefill packing: a row may hold SEVERAL prompts' chunks (segments).
The scalar-prefetch table then carries three planes per (row, slot) —
physical page, in-segment logical page index, and segment id — and each
query row carries its segment id alongside its position. Key positions are
computed from the in-segment page index (``base * ps + iota``) and the mask
additionally requires segment equality, so attention can NEVER leak across
packed prompts: a cross-segment page contributes exactly zero (its
probabilities are hard-zeroed, not just exp(-inf), so the online-softmax
state is bit-identical to the unpacked run). Defaults (no packing) reduce
to the exact previous math: base == slot index, one segment per row.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30

# VMEM-resident query-group row budget: sized so the group's q/out/state
# blocks plus (m, l, acc) scratch stay well inside the 8 MiB VMEM budget at
# D = 128 while letting a typical chunk's rows (R = S * G) fit in ONE group
# — the page streamed per group is then streamed per CHUNK.
RESIDENT_ROWS = 1024


def resident_rows(R: int, G: int, cap: int = 0) -> int:
    """Rows per VMEM-resident query group: the largest divisor of ``R``
    that is <= cap (default ``RESIDENT_ROWS``), keeps a sequence row's G
    grouped heads together, and is a multiple of 128 (the positions block
    puts the rows in the lane place, where Mosaic needs 128-aligned blocks).
    When no divisor qualifies the whole of ``R`` is one group. The page
    re-stream factor of the chunk kernel is ``R // resident_rows(R, G)``."""
    rq = min(cap or RESIDENT_ROWS, R)
    while rq and (R % rq or rq % G or rq % 128):
        rq -= 1
    return rq or R


def _chunk_kernel(phys_ref, lyr_ref,                 # scalar prefetch
                  q_ref, pos_ref, kv_ref, *refs,
                  ps: int, rep: int, opt_kv: bool, window: int, sink: int,
                  num_pages: int, return_state: bool):
    # kv_ref (2, 1, 1, ps, D): one head page's K and V; sc_ref (2, 1, Hkv,
    # ps): its page's K and V scales, which come only under Opt-KV
    sc_ref, o_ref, *refs = refs if opt_kv else (None, *refs)
    if return_state:
        mo_ref, lo_ref, m_ref, l_ref, acc_ref = refs
    else:
        m_ref, l_ref, acc_ref = refs
    b = pl.program_id(0)
    kvh = pl.program_id(1) // rep
    j = pl.program_id(3)                             # page-table slot
    rq, D = q_ref.shape[2], q_ref.shape[3]
    page = phys_ref[0, b, j]                         # physical page to DMA
    base = phys_ref[1, b, j]                         # in-segment logical page
    pseg = phys_ref[2, b, j]                         # page's segment id

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qpos = pos_ref[0, 0].astype(jnp.int32)           # (rq,) per-row position
    qseg = pos_ref[0, 1].astype(jnp.int32)           # (rq,) per-row segment
    # causal page skip: the page is dead if its first key position is beyond
    # every query in the tile (positions are non-decreasing per lane only
    # within a chunk, so use the tile max)
    live = jnp.logical_and(page >= 0, base * ps <= jnp.max(qpos))

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # (rq, D)
        k = kv_ref[0, 0, 0].astype(jnp.float32)      # (ps, D)
        v = kv_ref[1, 0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * (1.0 / math.sqrt(D))                 # (rq, ps)
        if opt_kv:                                   # Eq. 6 fused dequant
            s = s * sc_ref[0, 0, pl.ds(kvh, 1), :]   # per-key scale row
        kpos = base * ps + jax.lax.broadcasted_iota(jnp.int32, (rq, ps), 1)
        qp = jnp.broadcast_to(qpos[:, None], (rq, ps))
        mask = (kpos <= qp) & (qseg[:, None] == pseg)
        if window:
            mask &= (kpos > qp - window) | (kpos < sink * ps)
        s = jnp.where(mask, s, _NEG)
        m_prev = m_ref[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        # hard-zero masked probabilities: a row whose keys are ALL masked on
        # this page (cross-segment page, pad row) must contribute nothing —
        # exp(s - m_new) alone would yield 1.0 while m_new is still _NEG
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        l_new = l_ref[:, 0:1] * corr + jnp.sum(p, -1, keepdims=True)
        pv = p * sc_ref[1, 0, pl.ds(kvh, 1), :] if opt_kv else p
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            pv, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == num_pages - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, 0:1], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)
        if return_state:
            # per-shard partial softmax state for the shard_map lse merge
            mo_ref[0, 0] = m_ref[...]
            lo_ref[0, 0] = l_ref[...]


def flash_chunk_prefill(q, positions, kv_pages, scale_pages, layer,
                        phys_table, *, opt_kv: bool, opt_gqa: bool = True,
                        window: int = 0, sink_pages: int = 0,
                        block_q: int = 0, return_state: bool = False,
                        interpret: bool = False, seg_q=None, page_seg=None,
                        page_base=None):
    """q: (B, S, Hq, D) chunk queries; positions: (B, S) absolute per-row
    positions; kv_pages: (L, 2, P_total, Hkv, ps, D) the GLOBAL pool of every
    layer [fp8 if opt_kv]; scale_pages: (L, 2, P_total, Hkv, ps) f32, read
    only under opt_kv (None otherwise); layer: int32 scalar, the layer to
    attend; phys_table: (B, NP) int32 physical pages in logical order (-1 =
    skip, never DMA'd). The chunk's own K/V must already be written to the
    pool. Returns (B, S, Hq, D); with ``return_state`` also the final
    online-softmax (m, l) as (B, S, Hq) f32 for the cross-shard log-sum-exp
    merge (``kernels.sharded``).

    Concat-prefill packing (all three or none): ``seg_q`` (B, S) int32 is
    each query row's segment id (-1 = pad row, matches nothing);
    ``page_seg`` (B, NP) the segment each table slot belongs to; and
    ``page_base`` (B, NP) the slot's logical page index WITHIN its segment
    (key positions are ``page_base * ps + iota``). Defaults reproduce the
    unpacked layout exactly: one segment 0 per row, base == slot index."""
    B, S, Hq, D = q.shape
    _, _, P, Hkv, ps, _ = kv_pages.shape
    NP = phys_table.shape[1]
    if seg_q is None:
        seg_q = jnp.zeros((B, S), jnp.int32)
    if page_seg is None:
        page_seg = jnp.zeros((B, NP), jnp.int32)
    if page_base is None:
        page_base = jnp.broadcast_to(jnp.arange(NP, dtype=jnp.int32),
                                     (B, NP))
    if opt_gqa:
        G, heads, rep = Hq // Hkv, Hkv, 1
    else:
        # Original MHA semantics: every query head re-streams its KV head.
        G, heads, rep = 1, Hq, max(Hq // Hkv, 1)
    R = S * G

    # resident-group sizing: rows stay VMEM-resident across the whole inner
    # page loop, so NQ is the page re-stream factor (1 for typical chunks).
    # block_q = 0 means "as large as the VMEM budget allows" (RESIDENT_ROWS).
    rq = resident_rows(R, G, block_q)
    NQ = R // rq

    # (B,S,Hq,D) -> (B,heads,R,D): row r = s*G + g; positions repeat per
    # group (grouped mode) or per head block (MHA mode: R == S).
    qf = q.reshape(B, S, heads, G, D).transpose(0, 2, 1, 3, 4) \
          .reshape(B, heads, R, D)
    pos_rep = jnp.repeat(positions.astype(jnp.int32), G, axis=1)  # (B, R)
    seg_rep = jnp.repeat(seg_q.astype(jnp.int32), G, axis=1)      # (B, R)
    pos_rep = jnp.stack([pos_rep, seg_rep], axis=1)               # (B, 2, R)
    # scalar-prefetch planes: [physical page, in-segment base, segment id]
    table3 = jnp.stack([phys_table.astype(jnp.int32),
                        page_base.astype(jnp.int32),
                        page_seg.astype(jnp.int32)])              # (3, B, NP)

    # the layer is picked here, in place in the pool; one block holds the
    # head page's K and V
    def kv_idx(b, h, i, j, phys, lyr):
        return (lyr[0], 0, jnp.maximum(phys[0, b, j], 0), h // rep, 0, 0)

    def sc_idx(b, h, i, j, phys, lyr):
        return (lyr[0], 0, jnp.maximum(phys[0, b, j], 0), 0, 0)

    in_specs = [
        pl.BlockSpec((1, 1, rq, D), lambda b, h, i, j, phys, lyr: (b, h, i, 0)),
        pl.BlockSpec((1, 2, rq), lambda b, h, i, j, phys, lyr: (b, 0, i)),
        pl.BlockSpec((None, 2, 1, 1, ps, D), kv_idx)]
    operands = [qf, pos_rep, kv_pages]
    if opt_kv:
        in_specs += [pl.BlockSpec((None, 2, 1, Hkv, ps), sc_idx)]
        operands += [scale_pages]

    out_blk = pl.BlockSpec((1, 1, rq, D),
                           lambda b, h, i, j, phys, lyr: (b, h, i, 0))
    st_blk = pl.BlockSpec((1, 1, rq, 128),
                          lambda b, h, i, j, phys, lyr: (b, h, i, 0))
    out_specs = [out_blk]
    out_shape = [jax.ShapeDtypeStruct((B, heads, R, D), q.dtype)]
    if return_state:
        out_specs += [st_blk, st_blk]
        out_shape += [jax.ShapeDtypeStruct((B, heads, R, 128),
                                           jnp.float32)] * 2

    kern = functools.partial(_chunk_kernel, ps=ps, rep=rep, opt_kv=opt_kv,
                             window=window, sink=sink_pages, num_pages=NP,
                             return_state=return_state)
    res = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, heads, NQ, NP),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((rq, 128), jnp.float32),
                pltpu.VMEM((rq, 128), jnp.float32),
                pltpu.VMEM((rq, D), jnp.float32),
            ],
        ),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(table3, jnp.asarray(layer, jnp.int32).reshape(1), *operands)
    out = res[0].reshape(B, heads, S, G, D).transpose(0, 2, 1, 3, 4) \
                .reshape(B, S, Hq, D)
    if not return_state:
        return out

    def _rows(x):           # (B, heads, R, 128) -> (B, S, Hq)
        return x[..., 0].reshape(B, heads, S, G).transpose(0, 2, 1, 3) \
                        .reshape(B, S, Hq)

    return out, _rows(res[1]), _rows(res[2])
