"""MLA absorbed chunk-continuation-prefill Pallas kernel over the GLOBAL
paged LATENT pool — the chunk analogue of ``paged_latent_decode``, closing
the unified ragged step path for the MLA family.

A CHUNK of queries per lane (a decode lane is a chunk of length 1), each row
carrying its own absolute position, attends the lane's *already-cached*
latent history — prefix-cache hits, earlier chunks, and the chunk itself
(written before attention) — in matrix-absorption form. Queries arrive
already absorbed through ``w_uk`` (rows are (seq, head) pairs in LATENT
space), so every latent page is streamed into VMEM once per query tile and
shared by all H heads; K/V are never materialised per head, and the pool is
never gathered host-side (the ``jnp.take`` full-pool materialisation this
kernel replaces).

Latent pool addressing — identical to ``paged_latent_decode`` (see its
module docstring for the full scheme): the pool of every layer and a
``layer`` scalar read by the index_maps; ``lat_pages (L, P_total, ps, R+dr)``
packs ``[c_kv | k_rope]`` per token; ``scale_pages (L, P_total, ps, 2)`` holds
the DUAL FP8 scales (col 0 = c_kv, col 1 = k_rope — separate dynamic
ranges, Eq. 6); the lane's physical page table is scalar-prefetched and
dereferenced in the BlockSpec index_map (-1 = unallocated/SkipSet, never
DMA'd — the pool's sentinel last page never appears in a table).

Grid: (batch, q_group, logical_page). Per-row positions ride along as a
VMEM input blocked with the query tiles; the causal / sliding-window / sink
masks compare them against ``logical_page * ps + iota`` (Eq. 9's valid-block
filter in the logical page domain, Eq. 10's online softmax across pages).
Pages entirely in the future of a query tile are skipped by the same
``pl.when`` predicate using the tile's maximum position. The (m, l, acc)
accumulator is VMEM-resident with acc in LATENT space (rl, R); the ``w_uv``
expansion stays outside so weights never enter VMEM.

Tile-resident chunk streaming: the page dim is innermost and every row-side
block (ql, qr, positions, out, state, scratch) is keyed on the RESIDENT
GROUP index only, so the group stays VMEM-resident across the inner page
loop and a latent page is DMA'd once per group, not once per small query
tile. ``resident_rows`` sizes the group (largest divisor of RW = S * H
under ``RESIDENT_ROWS`` that keeps a token's H head rows together); latent
rows are ~4x wider than dense ones (R + 3*128 floats vs 2*D + 3*128), so
the cap is 512 rows (~7.0 MiB double-buffered at R = 512) and the page
re-stream factor is RW / rl instead of the former fixed RW / 256.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_NEG = -1e30

# VMEM-resident query-group row budget — half the dense kernel's cap: a
# latent row carries R = kv_lora_rank (typ. 512) accumulator floats, so 512
# rows keep blocks + (m, l, acc) scratch inside the 8 MiB VMEM budget.
RESIDENT_ROWS = 512


def resident_rows(RW: int, H: int, cap: int = 0) -> int:
    """Rows per VMEM-resident query group: the largest multiple of ``H``
    <= cap (default ``RESIDENT_ROWS``) that divides ``RW`` (a token's H head
    rows stay together; ``H`` always qualifies, so the search terminates).
    The page re-stream factor of the chunk kernel is ``RW // rl``."""
    rl = H * max(min(cap or RESIDENT_ROWS, RW) // H, 1)
    while RW % rl:
        rl -= H
    return rl


def _latent_chunk_kernel(phys_ref, lyr_ref,          # scalar prefetch
                         ql_ref, qr_ref, pos_ref, lat_ref, *refs,
                         ps: int, R: int, sm_scale: float, opt_kv: bool,
                         window: int, sink: int, num_pages: int,
                         return_state: bool):
    # the scale block comes only under Opt-KV
    sc_ref, o_ref, *refs = refs if opt_kv else (None, *refs)
    if return_state:
        mo_ref, lo_ref, m_ref, l_ref, acc_ref = refs
    else:
        m_ref, l_ref, acc_ref = refs
    b = pl.program_id(0)
    j = pl.program_id(2)                             # page-table slot
    rl = ql_ref.shape[1]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    page = phys_ref[0, b, j]                         # physical page to DMA
    base = phys_ref[1, b, j]                         # in-segment logical page
    pseg = phys_ref[2, b, j]                         # page's segment id
    qpos = pos_ref[0, 0].astype(jnp.int32)           # (rl,) per-row position
    qseg = pos_ref[0, 1].astype(jnp.int32)           # (rl,) per-row segment
    # causal page skip: the page is dead if its first key position is beyond
    # every query row in the tile
    live = jnp.logical_and(page >= 0, base * ps <= jnp.max(qpos))

    @pl.when(live)
    def _compute():
        ql = ql_ref[0].astype(jnp.float32)           # (rl, R)  absorbed q
        qr = qr_ref[0].astype(jnp.float32)           # (rl, dr)
        lat = lat_ref[0]                             # (ps, R+dr)
        c = lat[:, :R]
        r = lat[:, R:]
        if opt_kv:  # Eq. 6: fused dual-scale dequant at the VMEM boundary
            c = c.astype(jnp.float32) * sc_ref[0][:, 0].reshape(ps, 1)
            r = r.astype(jnp.float32) * sc_ref[0][:, 1].reshape(ps, 1)
        else:
            c = c.astype(jnp.float32)
            r = r.astype(jnp.float32)
        s = jax.lax.dot_general(ql, c, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s += jax.lax.dot_general(qr, r, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        s = s * sm_scale                             # (rl, ps)
        kpos = base * ps + jax.lax.broadcasted_iota(jnp.int32, (rl, ps), 1)
        qp = jnp.broadcast_to(qpos[:, None], (rl, ps))
        mask = (kpos <= qp) & (qseg[:, None] == pseg)
        if window:
            mask &= (kpos > qp - window) | (kpos < sink * ps)
        s = jnp.where(mask, s, _NEG)
        m_prev = m_ref[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        # hard-zero masked lanes: with packing a page can be ENTIRELY masked
        # for a row (other segment) while m is still _NEG, where exp(s-m_new)
        # would be exp(0)=1 and corrupt (l, acc). Unpacked this is a no-op
        # (exp(_NEG - m) underflows to exactly 0.0 in f32).
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        l_new = l_ref[:, 0:1] * corr + jnp.sum(p, -1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, c, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == num_pages - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, 0:1], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
        if return_state:
            # per-shard partial softmax state for the shard_map lse merge
            mo_ref[0] = m_ref[...]
            lo_ref[0] = l_ref[...]


def latent_chunk_prefill(q_lat, q_rope, positions, lat_pages, scale_pages,
                         layer, phys_table, *, sm_scale: float, opt_kv: bool,
                         window: int = 0, sink_pages: int = 0,
                         block_q: int = 0, return_state: bool = False,
                         interpret: bool = False, seg_q=None, page_seg=None,
                         page_base=None):
    """q_lat: (B, S, H, R) W_uk-absorbed chunk queries; q_rope: (B, S, H, dr);
    positions: (B, S) absolute per-row positions; lat_pages: (L, P_total,
    ps, R+dr) GLOBAL latent pool of every layer [fp8 if opt_kv]; scale_pages:
    (L, P_total, ps, 2) f32 dual scales, read only under opt_kv (None
    otherwise); layer: int32 scalar, the layer to attend; phys_table: (B, NP)
    int32 physical pages in
    logical order (-1 = skip, never DMA'd). The chunk's own latents must
    already be written to the pool. Returns o_lat (B, S, H, R) f32; the
    caller applies the ``w_uv`` expansion. With ``return_state`` also the
    final online-softmax (m, l) as (B, S, H) f32 for the cross-shard
    log-sum-exp merge (``kernels.sharded``).

    Concat-prefill packing: ``seg_q`` (B, S) int32 per-query segment ids,
    ``page_seg`` (B, NP) int32 per-slot segment ids, ``page_base`` (B, NP)
    int32 per-slot IN-SEGMENT logical page index. A query attends a key only
    when segments match; key positions come from ``page_base`` so every
    segment restarts its position domain. Defaults (no packing) reduce to
    the exact previous math: base == slot index, one segment everywhere."""
    B, S, H, R = q_lat.shape
    _, P, ps, W = lat_pages.shape
    dr = q_rope.shape[-1]
    NP = phys_table.shape[1]
    RW = S * H                                       # row r = s*H + h

    # resident-group sizing: rows stay VMEM-resident across the whole inner
    # page loop, so NQ is the page re-stream factor. block_q = 0 means "as
    # large as the VMEM budget allows" (RESIDENT_ROWS).
    rl = resident_rows(RW, H, block_q)
    NQ = RW // rl

    if seg_q is None:
        seg_q = jnp.zeros((B, S), jnp.int32)
    if page_seg is None:
        page_seg = jnp.zeros((B, NP), jnp.int32)
    if page_base is None:
        page_base = jnp.broadcast_to(jnp.arange(NP, dtype=jnp.int32), (B, NP))

    qlf = q_lat.reshape(B, RW, R)
    qrf = q_rope.reshape(B, RW, dr)
    pos_rep = jnp.repeat(positions.astype(jnp.int32), H, axis=1)  # (B, RW)
    seg_rep = jnp.repeat(seg_q.astype(jnp.int32), H, axis=1)      # (B, RW)
    pos_rep = jnp.stack([pos_rep, seg_rep], axis=1)               # (B, 2, RW)
    table3 = jnp.stack([phys_table.astype(jnp.int32),
                        page_base.astype(jnp.int32),
                        page_seg.astype(jnp.int32)])              # (3, B, NP)

    def lat_idx(b, i, j, phys, lyr):
        return (lyr[0], jnp.maximum(phys[0, b, j], 0), 0, 0)

    in_specs = [
        pl.BlockSpec((1, rl, R), lambda b, i, j, phys, lyr: (b, i, 0)),
        pl.BlockSpec((1, rl, dr), lambda b, i, j, phys, lyr: (b, i, 0)),
        pl.BlockSpec((1, 2, rl), lambda b, i, j, phys, lyr: (b, 0, i)),
        pl.BlockSpec((None, 1, ps, W), lat_idx)]
    operands = [qlf, qrf, pos_rep, lat_pages]
    if opt_kv:
        in_specs += [pl.BlockSpec((None, 1, ps, 2), lat_idx)]
        operands += [scale_pages]

    out_blk = pl.BlockSpec((1, rl, R), lambda b, i, j, phys, lyr: (b, i, 0))
    st_blk = pl.BlockSpec((1, rl, 128), lambda b, i, j, phys, lyr: (b, i, 0))
    out_specs = [out_blk]
    out_shape = [jax.ShapeDtypeStruct((B, RW, R), jnp.float32)]
    if return_state:
        out_specs += [st_blk, st_blk]
        out_shape += [jax.ShapeDtypeStruct((B, RW, 128), jnp.float32)] * 2

    kern = functools.partial(_latent_chunk_kernel, ps=ps, R=R,
                             sm_scale=sm_scale, opt_kv=opt_kv, window=window,
                             sink=sink_pages, num_pages=NP,
                             return_state=return_state)
    res = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, NQ, NP),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((rl, 128), jnp.float32),
                pltpu.VMEM((rl, 128), jnp.float32),
                pltpu.VMEM((rl, R), jnp.float32),
            ],
        ),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(table3, jnp.asarray(layer, jnp.int32).reshape(1), *operands)
    out = res[0].reshape(B, S, H, R)
    if not return_state:
        return out
    return (out, res[1][..., 0].reshape(B, S, H),
            res[2][..., 0].reshape(B, S, H))
