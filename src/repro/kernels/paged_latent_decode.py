"""Fused MLA absorbed-decode Pallas kernel over the GLOBAL paged LATENT pool.

This is ``paged_gqa_decode`` taken to the Opt-GQA limit G = H: MLA caches ONE
shared latent stream per token — the compressed c_kv (R = kv_lora_rank floats)
concatenated with the shared rotary key k_rope (dr floats) — and ALL H query
heads attend it in matrix-absorption form. Each latent page is therefore
streamed into VMEM exactly ONCE per decode step and shared by every absorbed
query head; there is no per-head KV expansion anywhere on the path (Eq. 7/8's
sharing argument with a group of size H).

Latent pool addressing (the pool of every layer, and a ``layer`` scalar
that the BlockSpec index_maps read, so no layer is sliced out of the pool):
  * ``lat_pages (L, P_total, ps, R+dr)`` — NO batch dimension; every lane
    shares the pool. A token's cache line packs ``[c_kv | k_rope]`` back to
    back, so one DMA fetches both score streams.
  * ``scale_pages (L, P_total, ps, 2)`` — DUAL per-token FP8 scales (Eq. 6):
    column 0 dequantizes the c_kv segment, column 1 the k_rope segment. The
    two segments come from different projections with different dynamic
    ranges; a shared scale would crush the smaller segment's mantissa.
  * Each lane's *physical* page table is scalar-prefetched and dereferenced
    inside the BlockSpec index_map, so the block DMA'd at grid step (b, i)
    IS lane b's i-th selected page — lazy page mapping as data-dependent
    prefetch (Opt-Pa). A parallel *logical* table supplies token positions.
    Entries of -1 (unallocated, SkipSet, beyond-context under Eq. 9
    filtering, or outside the {sink + sliding-window} policy) are predicated
    off with ``pl.when``: neither DMA'd (index_map redirects to page 0) nor
    computed. The pool's final page is the write path's SkipSet sentinel —
    the BlockManager never allocates it, so it never appears in a table.

The kernel fuses: dual-scale FP8 dequant at the HBM->VMEM boundary (Eq. 6),
the absorbed score ``s_h(t) = <q_lat_h, c_t> + <q_rope_h, k_rope_t>``, and a
VMEM-resident running (m, l, acc) block-wise softmax across the page grid
dim (Eq. 10). The accumulator lives in LATENT space (H, R) — the ``w_uk``
absorption and ``w_uv`` expansion stay OUTSIDE the kernel, so weight
matrices never enter VMEM and the output projection remains one dense
einsum per step.

The windowed variant (block-sparse long-context policy) is the same kernel
with ``window``/``sink_pages`` static parameters, matching
``opt_kv.window_page_table`` semantics: the caller passes the {sink +
sliding-window} page selection, positions come from the logical table, and
out-of-policy tokens are masked in-register.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_NEG = -1e30


def _latent_kernel(len_ref, phys_ref, log_ref, lyr_ref,   # scalar prefetch
                   ql_ref, qr_ref, lat_ref, *refs,
                   ps: int, R: int, sm_scale: float, opt_kv: bool,
                   window: int, sink: int, num_sel: int,
                   return_state: bool):
    # the scale block comes only under Opt-KV
    sc_ref, o_ref, *refs = refs if opt_kv else (None, *refs)
    if return_state:
        mo_ref, lo_ref, m_ref, l_ref, acc_ref = refs
    else:
        m_ref, l_ref, acc_ref = refs
    b = pl.program_id(0)
    s_i = pl.program_id(1)
    H = ql_ref.shape[1]
    length = len_ref[b]
    page = phys_ref[b, s_i]
    lpage = log_ref[b, s_i]

    @pl.when(s_i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Eq. 9 Phase 1: -1 pages (unallocated / beyond context / out of policy)
    # are predicated off — never DMA'd, never computed.
    @pl.when(page >= 0)
    def _compute():
        ql = ql_ref[0].astype(jnp.float32)               # (H, R)  absorbed q
        qr = qr_ref[0].astype(jnp.float32)               # (H, dr)
        lat = lat_ref[0]                                 # (ps, R+dr)
        c = lat[:, :R]
        r = lat[:, R:]
        if opt_kv:  # Eq. 6: fused DUAL-scale dequant at the VMEM boundary
            c = c.astype(jnp.float32) * sc_ref[0][:, 0].reshape(ps, 1)
            r = r.astype(jnp.float32) * sc_ref[0][:, 1].reshape(ps, 1)
        else:
            c = c.astype(jnp.float32)
            r = r.astype(jnp.float32)
        s = jax.lax.dot_general(ql, c, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s += jax.lax.dot_general(qr, r, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        s = s * sm_scale                                 # (H, ps)
        pos = lpage * ps + jax.lax.broadcasted_iota(jnp.int32, (H, ps), 1)
        mask = pos < length
        if window:
            in_win = pos >= jnp.maximum(length - window, 0)
            in_sink = pos < sink * ps
            mask &= in_win | in_sink
        s = jnp.where(mask, s, _NEG)

        # Eq. 10 Phase 2: block-wise softmax, VMEM running reduce — the
        # accumulator stays in latent space (H, R).
        m_prev = m_ref[:, 0:1]                           # (H, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                           # (H, ps)
        l_new = l_ref[:, 0:1] * corr + jnp.sum(p, -1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, c, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(s_i == num_sel - 1)
    def _finalize():
        l = l_ref[:, 0:1]
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        if return_state:
            # per-shard partial softmax state for the shard_map lse merge
            mo_ref[0] = m_ref[...]
            lo_ref[0] = l_ref[...]


def paged_latent_decode(q_lat, q_rope, lat_pages, scale_pages, layer,
                        cache_len, phys_table, log_table, *, sm_scale: float,
                        opt_kv: bool, window: int = 0, sink_pages: int = 0,
                        return_state: bool = False, interpret: bool = False):
    """q_lat: (B, H, R) W_uk-absorbed queries; q_rope: (B, H, dr); lat_pages:
    (L, P_total, ps, R+dr) GLOBAL latent pool of every layer [fp8 if opt_kv];
    scale_pages: (L, P_total, ps, 2) f32 dual c/k_rope scales, read only
    under opt_kv (None otherwise); layer: int32 scalar, the layer to attend;
    cache_len: (B,) int32;
    phys_table/log_table: (B, NSel) int32 — physical page to DMA / logical
    page id for positions; -1 = skip (never DMA'd). ``sm_scale`` is the
    softmax scale 1/sqrt(dn+dr) — NOT derivable from R (absorption changes
    the contraction width, not the score scale). Returns o_lat (B, H, R) f32;
    the caller applies the ``w_uv`` expansion. With ``return_state`` also
    the final online-softmax (m, l) as (B, H) f32 for the cross-shard
    log-sum-exp merge (``kernels.sharded``)."""
    B, H, R = q_lat.shape
    _, P, ps, W = lat_pages.shape
    NSel = phys_table.shape[1]

    def lat_idx(b, s, L, phys, log, lyr):
        return (lyr[0], jnp.maximum(phys[b, s], 0), 0, 0)

    in_specs = [
        pl.BlockSpec((1, H, R), lambda b, s, L, phys, log, lyr: (b, 0, 0)),
        pl.BlockSpec((1, H, q_rope.shape[-1]),
                     lambda b, s, L, phys, log, lyr: (b, 0, 0)),
        pl.BlockSpec((None, 1, ps, W), lat_idx)]
    operands = [q_lat, q_rope, lat_pages]
    if opt_kv:
        in_specs += [pl.BlockSpec((None, 1, ps, 2), lat_idx)]
        operands += [scale_pages]

    out_blk = pl.BlockSpec((1, H, R),
                           lambda b, s, L, phys, log, lyr: (b, 0, 0))
    st_blk = pl.BlockSpec((1, H, 128),
                          lambda b, s, L, phys, log, lyr: (b, 0, 0))
    out_specs = [out_blk]
    out_shape = [jax.ShapeDtypeStruct((B, H, R), jnp.float32)]
    if return_state:
        out_specs += [st_blk, st_blk]
        out_shape += [jax.ShapeDtypeStruct((B, H, 128), jnp.float32)] * 2

    kern = functools.partial(_latent_kernel, ps=ps, R=R, sm_scale=sm_scale,
                             opt_kv=opt_kv, window=window, sink=sink_pages,
                             num_sel=NSel, return_state=return_state)
    res = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(B, NSel),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((H, 128), jnp.float32),
                pltpu.VMEM((H, 128), jnp.float32),
                pltpu.VMEM((H, R), jnp.float32),
            ],
        ),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(cache_len, phys_table, log_table,
      jnp.asarray(layer, jnp.int32).reshape(1), *operands)
    if not return_state:
        return res[0]
    return res[0], res[1][..., 0], res[2][..., 0]


def _latent_visit_kernel(vp_ref, vm_ref, vl_ref, lyr_ref,   # scalar prefetch
                         ql_ref, qr_ref, len_ref, lat_ref, *refs,
                         ps: int, R: int, H: int, sm_scale: float,
                         opt_kv: bool, window: int, sink: int,
                         num_visits: int, return_state: bool):
    """Cross-lane visit grid for the absorbed-MLA decode (see
    ``paged_gqa_decode._visit_kernel`` for the scheme). Rows of all lanes'
    absorbed queries ride VMEM-resident as one (BH, R) tile (BH = B * H,
    row r = lane * H + head); each deduplicated visit streams and
    dual-dequantizes its latent page ONCE and updates every member lane's
    running (m, l, acc) state; non-member rows take exact identity updates
    so the no-sharing plan is bit-identical to ``_latent_kernel``."""
    sc_ref, o_ref, *refs = refs if opt_kv else (None, *refs)
    if return_state:
        mo_ref, lo_ref, m_ref, l_ref, acc_ref = refs
    else:
        m_ref, l_ref, acc_ref = refs
    v_i = pl.program_id(0)
    BH = ql_ref.shape[0]
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
        ql = ql_ref[...].astype(jnp.float32)             # (BH, R)
        qr = qr_ref[...].astype(jnp.float32)             # (BH, dr)
        lat = lat_ref[0]                                 # (ps, R+dr)
        c = lat[:, :R]
        r = lat[:, R:]
        if opt_kv:  # Eq. 6 dual-scale dequant — ONCE per visit, not per lane
            c = c.astype(jnp.float32) * sc_ref[0][:, 0].reshape(ps, 1)
            r = r.astype(jnp.float32) * sc_ref[0][:, 1].reshape(ps, 1)
        else:
            c = c.astype(jnp.float32)
            r = r.astype(jnp.float32)
        s = jax.lax.dot_general(ql, c, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s += jax.lax.dot_general(qr, r, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        s = s * sm_scale                                 # (BH, ps)
        lane_r = jax.lax.broadcasted_iota(jnp.int32, (BH, 1), 0) // H
        member = jnp.equal(
            jnp.bitwise_and(jnp.right_shift(lanes, lane_r), 1), 1)
        length = len_ref[:, 0:1]                         # (BH, 1)
        pos = lpage * ps + jax.lax.broadcasted_iota(jnp.int32, (BH, ps), 1)
        mask = member & (pos < length)
        if window:
            in_win = pos >= jnp.maximum(length - window, 0)
            in_sink = pos < sink * ps
            mask &= in_win | in_sink
        s = jnp.where(mask, s, _NEG)

        m_prev = m_ref[:, 0:1]                           # (BH, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.where(member, jnp.exp(s - m_new), 0.0)   # (BH, ps)
        l_new = l_ref[:, 0:1] * corr + jnp.sum(p, -1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, c, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(v_i == num_visits - 1)
    def _finalize():
        l = l_ref[:, 0:1]
        o_ref[...] = (acc_ref[...] /
                      jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        if return_state:
            mo_ref[...] = m_ref[...]
            lo_ref[...] = l_ref[...]


def paged_latent_decode_visits(q_lat, q_rope, lat_pages, scale_pages, layer,
                               cache_len, visit_page, visit_lanes, visit_log,
                               *, sm_scale: float, opt_kv: bool,
                               window: int = 0, sink_pages: int = 0,
                               return_state: bool = False,
                               interpret: bool = False):
    """Batched-visit twin of ``paged_latent_decode``: the page grid dim
    iterates a deduplicated cross-lane visit list (``kernels.visits``) so a
    latent page shared by N lanes is streamed/dequantized once per step.
    Pool, scales and ``layer`` as in ``paged_latent_decode``;
    visit_page/visit_lanes/visit_log: (NV,) int32 plan vectors; requires
    B <= visits.MAX_VISIT_LANES."""
    B, H, R = q_lat.shape
    _, P, ps, W = lat_pages.shape
    dr = q_rope.shape[-1]
    NV = visit_page.shape[0]
    BH = B * H
    # rows r = b * H + h: the natural reshape is already lane-contiguous
    qlf = q_lat.reshape(BH, R)
    qrf = q_rope.reshape(BH, dr)
    len_rows = jnp.broadcast_to(
        cache_len.astype(jnp.int32)[:, None, None], (B, H, 128)
    ).reshape(BH, 128)

    def lat_idx(v, vp, vl, vm, lyr):
        return (lyr[0], jnp.maximum(vp[v], 0), 0, 0)

    in_specs = [
        pl.BlockSpec((BH, R), lambda v, vp, vl, vm, lyr: (0, 0)),
        pl.BlockSpec((BH, dr), lambda v, vp, vl, vm, lyr: (0, 0)),
        pl.BlockSpec((BH, 128), lambda v, vp, vl, vm, lyr: (0, 0)),
        pl.BlockSpec((None, 1, ps, W), lat_idx)]
    operands = [qlf, qrf, len_rows, lat_pages]
    if opt_kv:
        in_specs += [pl.BlockSpec((None, 1, ps, 2), lat_idx)]
        operands += [scale_pages]

    out_blk = pl.BlockSpec((BH, R), lambda v, vp, vl, vm, lyr: (0, 0))
    st_blk = pl.BlockSpec((BH, 128), lambda v, vp, vl, vm, lyr: (0, 0))
    out_specs = [out_blk]
    out_shape = [jax.ShapeDtypeStruct((BH, R), jnp.float32)]
    if return_state:
        out_specs += [st_blk, st_blk]
        out_shape += [jax.ShapeDtypeStruct((BH, 128), jnp.float32)] * 2

    kern = functools.partial(_latent_visit_kernel, ps=ps, R=R, H=H,
                             sm_scale=sm_scale, opt_kv=opt_kv, window=window,
                             sink=sink_pages, num_visits=NV,
                             return_state=return_state)
    res = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(NV,),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((BH, 128), jnp.float32),
                pltpu.VMEM((BH, 128), jnp.float32),
                pltpu.VMEM((BH, R), jnp.float32),
            ],
        ),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(visit_page, visit_lanes, visit_log,
      jnp.asarray(layer, jnp.int32).reshape(1), *operands)
    out = res[0].reshape(B, H, R)
    if not return_state:
        return out
    m = res[1][..., 0].reshape(B, H)
    l = res[2][..., 0].reshape(B, H)
    return out, m, l
