"""jit'd public wrappers around the Pallas kernels + cache-layout adapters.

The engine-facing cache layout is the GLOBAL paged pool of every layer —
``(L, 2, P_total, Hkv, ps, D)`` with scales ``(L, 2, P_total, Hkv, ps)``
and NO batch dimension, shared by every lane (heads before tokens within a
page, so a head's page is one (ps, D) tile). The model's layer scan carries
the whole pool, and on the chip these wrappers hand it to the kernels
whole: a read kernel takes a ``layer`` scalar and picks the layer in its
BlockSpec index_maps (one block holds a head page's K and V), and the write
kernel names lines of the whole pool and updates it in place (aliased).
Nothing slices a layer or a K/V half out of the pool, so no step copies
it. Off the chip the Pallas interpreter, which copies every operand at each
grid step, is handed the one layer a kernel reads (``_interpreted``). The
wrappers plug into ``repro.core`` when ``CoOptConfig.use_kernel``.
Lanes address the pool through scalar-prefetched page tables (physical page
to DMA + logical page for positions) dereferenced inside BlockSpec
index_maps, and the write path scatters to lines of the whole pool,
``(layer * P_total + page) * ps + offset`` (negative lines — the SkipSet —
are not written).

ONE hot path, single-host AND distributed: when a ``sharded.ShardCtx`` is
installed (``set_mesh_ctx`` — the engine and ``launch.steps`` bind it at
trace time from their mesh), every wrapper dispatches to the ``shard_map``
layer in ``kernels.sharded`` — the same kernels run per mesh shard against
their owned page range, partial softmax states are lse-merged across the
pages axes, and writes stay shard-local. With no ctx (no mesh, or a mesh
whose pages axes have extent 1) the single-device kernels run unchanged.

Interpret mode is decided by the platform, at every call: the unjitted
dispatchers below run the kernels compiled when JAX's default backend is a
TPU and in the Pallas interpreter otherwise (``interpret_mode``). Nothing
has to be configured first, so an ``Engine`` built by any script runs the
compiled kernels on the chip.
"""
from __future__ import annotations

from contextlib import contextmanager
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.opt_kv import scatter_latent
from repro.kernels import flash_chunk_prefill as _fc
from repro.kernels import flash_prefill as _fp
from repro.kernels import kv_cache_write as _kw
from repro.kernels import latent_chunk_prefill as _lc
from repro.kernels import paged_gqa_decode as _pd
from repro.kernels import paged_latent_decode as _ld
from repro.kernels import sharded as _sh
from repro.kernels import visits as _vs

# pages-axis shard_map context — None = single-device hot path. Installed at
# TRACE time by whoever owns the mesh (serving.Engine step impls,
# launch.steps step fns), so jit-cached traces can never leak a stale mesh.
_MESH_CTX: Optional[_sh.ShardCtx] = None


def interpret_mode() -> bool:
    """True where the Pallas kernels must run in the interpreter: on every
    backend but a TPU."""
    return jax.default_backend() != "tpu"


def make_mesh_ctx(mesh) -> Optional[_sh.ShardCtx]:
    """ShardCtx for ``mesh`` (None when its pages axes have extent 1 — an
    unsharded mesh takes the identical code path as no mesh)."""
    return _sh.make_ctx(mesh)


def set_mesh_ctx(ctx: Optional[_sh.ShardCtx]) -> None:
    """Install (or clear) the pages-axis shard_map dispatch context."""
    global _MESH_CTX
    _MESH_CTX = ctx


def mesh_ctx() -> Optional[_sh.ShardCtx]:
    return _MESH_CTX


@contextmanager
def mesh_ctx_scope(ctx: Optional[_sh.ShardCtx]):
    """Bind the dispatch ctx for the duration of a trace and RESTORE the
    previous one after — mesh owners (engine step impls, launch.steps step
    fns) wrap their model calls in this so a trace can neither leak its
    mesh to later direct ops calls nor clobber a ctx a direct caller
    installed."""
    prev = _MESH_CTX
    set_mesh_ctx(ctx)
    try:
        yield
    finally:
        set_mesh_ctx(prev)


# ---------------------------------------------------------------------------
# NOTE: every jitted wrapper below takes `interpret` as a STATIC argument
# fed from the unjitted public dispatcher at call time (interpret_mode()),
# so the jit cache keys on it and no module state is read inside a trace
# (COOPT004, `python -m repro.analysis`).
def _one_layer(pool, layer):
    """Layer ``layer`` of ``pool`` as a pool of one layer (None stays None)."""
    if pool is None:
        return None
    return jax.lax.dynamic_slice_in_dim(pool, layer, 1)


def _interpreted(pool, scales, layer, interpret: bool):
    """``(pool, scales, layer)`` as a kernel takes them. The Pallas
    interpreter copies every operand whole at each grid step, so off the
    chip a kernel gets the one layer it reads, as layer 0 of a pool of one
    layer; on the chip it takes the whole pool and addresses the layer in
    place, and nothing is sliced."""
    if not interpret:
        return pool, scales, layer
    return _one_layer(pool, layer), _one_layer(scales, layer), 0


def _use_visits(share_visits: bool, B: int) -> bool:
    # the batched-visit grid pays off only with >1 lane, and its int32 lane
    # bitmask caps membership at MAX_VISIT_LANES; beyond either bound the
    # per-lane grid is the degenerate (and bit-identical) fallback
    return bool(share_visits) and 1 < B <= _vs.MAX_VISIT_LANES


@partial(jax.jit, static_argnames=("opt_kv", "opt_gqa", "window",
                                   "sink_pages", "share_visits", "interpret"))
def _paged_pool_decode_single(q, kv_pages, scale_pages, layer, cache_len,
                              phys_table, log_table, *, opt_kv: bool,
                              opt_gqa: bool, window: int, sink_pages: int,
                              share_visits: bool, interpret: bool):
    kv_pages, scale_pages, layer = _interpreted(kv_pages, scale_pages, layer,
                                                 interpret)
    if _use_visits(share_visits, q.shape[0]):
        # trace-time dedup: pages shared across lanes stream into VMEM once
        vp, vm, vl = _vs.plan_visits(phys_table.astype(jnp.int32),
                                     log_table.astype(jnp.int32))
        return _pd.paged_pool_decode_visits(
            q, kv_pages, scale_pages, layer, cache_len.astype(jnp.int32),
            vp, vm, vl, opt_kv=opt_kv, opt_gqa=opt_gqa, window=window,
            sink_pages=sink_pages, interpret=interpret)
    return _pd.paged_pool_decode(
        q, kv_pages, scale_pages, layer, cache_len.astype(jnp.int32),
        phys_table.astype(jnp.int32), log_table.astype(jnp.int32),
        opt_kv=opt_kv, opt_gqa=opt_gqa, window=window,
        sink_pages=sink_pages, interpret=interpret)


def paged_pool_decode(q, kv_pages, scale_pages, layer, cache_len, phys_table,
                      log_table, *, opt_kv: bool, opt_gqa: bool,
                      window: int = 0, sink_pages: int = 0,
                      share_visits: bool = False):
    """Fused decode over the global pool. q (B,Hq,D); kv_pages
    (L,2,P_total,Hkv,ps,D) the pool of every layer; scale_pages
    (L,2,P_total,Hkv,ps)|None; layer: int32 scalar, the layer to attend;
    phys/log_table (B,NSel) int32 (-1 = never DMA'd). ``share_visits``
    batches cross-lane shared pages through the deduplicated visit grid
    (``kernels.visits.plan_visits``); with no sharing present the result is
    bit-identical to the per-lane grid."""
    if _MESH_CTX is not None:
        return _sh.paged_pool_decode(
            _MESH_CTX, q, kv_pages, scale_pages, layer, cache_len,
            phys_table, log_table, opt_kv=opt_kv, opt_gqa=opt_gqa,
            window=window, sink_pages=sink_pages, share_visits=share_visits,
            interpret=interpret_mode())
    return _paged_pool_decode_single(
        q, kv_pages, scale_pages, layer, cache_len, phys_table, log_table,
        opt_kv=opt_kv, opt_gqa=opt_gqa, window=window,
        sink_pages=sink_pages, share_visits=share_visits,
        interpret=interpret_mode())


@partial(jax.jit, static_argnames=("opt_kv", "interpret"))
def _kv_cache_write_single(kv_cache, scale_cache, k_new, v_new, line_idx, *,
                           opt_kv: bool, interpret: bool):
    sc = scale_cache if opt_kv else None
    if not interpret:
        kv, sc = _kw.kv_cache_write(k_new, v_new, line_idx, kv_cache, sc,
                                    opt_kv=opt_kv, interpret=False)
    else:
        # the interpreter gets the one layer the call's lines name (see
        # ``_interpreted``), written and put back in place
        per_layer = kv_cache.shape[2] * kv_cache.shape[4]
        layer = jnp.maximum(jnp.max(line_idx), 0) // per_layer
        local = line_idx - layer * per_layer
        local = jnp.where((line_idx >= 0) & (local < per_layer), local, -1)
        kv1, sc1 = _kw.kv_cache_write(k_new, v_new, local,
                                      _one_layer(kv_cache, layer),
                                      _one_layer(sc, layer), opt_kv=opt_kv,
                                      interpret=True)
        kv = jax.lax.dynamic_update_slice_in_dim(kv_cache, kv1, layer, 0)
        if opt_kv:
            sc = jax.lax.dynamic_update_slice_in_dim(sc, sc1, layer, 0)
    return kv, (sc if opt_kv else scale_cache)


def kv_cache_write(kv_cache, scale_cache, k_new, v_new, line_idx, *,
                   opt_kv: bool):
    """Engine-layout adapter for the write kernel. kv_cache
    (L,2,P_total,Hkv,ps,D) the pool of every layer, scale_cache
    (L,2,P_total,Hkv,ps) | None; line_idx (B,S) lines of one layer of the
    pool, ``(layer * P_total + page) * ps + offset``
    (``opt_kv.pool_lines``); negative lines are not written. Returns the
    updated (kv_cache, scale_cache), written in place. Under a mesh ctx the
    scatter runs shard-local."""
    if _MESH_CTX is not None:
        return _sh.kv_pool_write(_MESH_CTX, kv_cache, scale_cache, k_new,
                                 v_new, line_idx, opt_kv=opt_kv,
                                 interpret=interpret_mode())
    return _kv_cache_write_single(kv_cache, scale_cache, k_new, v_new,
                                  line_idx, opt_kv=opt_kv,
                                  interpret=interpret_mode())


def latent_pool_write(lat_cache, scale_cache, latent, line_idx, *,
                      opt_kv: bool, lora_rank: int):
    """MLA latent write path: dual-scale quantization + a scatter to lines
    of the global latent pool of every layer (lat_cache (L,P,ps,R+dr),
    scale_cache (L,P,ps,2) | None; latent (B,S,R+dr); line_idx (B,S)
    ``(layer * P + page) * ps + offset``, negative lines drop). The scatter
    indexes the pool in place. Under a mesh ctx it runs shard-local;
    otherwise this is the plain jnp scatter (there is no Pallas latent write
    kernel — the write is already one fused scatter)."""
    if _MESH_CTX is not None:
        return _sh.latent_pool_write(_MESH_CTX, lat_cache, scale_cache,
                                     latent, line_idx, opt_kv=opt_kv,
                                     lora_rank=lora_rank)
    return scatter_latent(lat_cache, scale_cache, latent, line_idx,
                          opt_kv=opt_kv, lora_rank=lora_rank)


@partial(jax.jit, static_argnames=("window", "block_q", "block_k",
                                   "q_offset", "interpret"))
def _flash_prefill_single(q, k, v, *, window: int, block_q: int,
                          block_k: int, q_offset: int, interpret: bool):
    return _fp.flash_prefill(q, k, v, window=window, block_q=block_q,
                             block_k=block_k, q_offset=q_offset,
                             interpret=interpret)


def flash_prefill(q, k, v, *, window: int = 0, block_q: int = 256,
                  block_k: int = 256, q_offset: int = 0):
    """Self-attention prefill over in-chunk K/V (no pool paging)."""
    return _flash_prefill_single(q, k, v, window=window, block_q=block_q,
                                 block_k=block_k, q_offset=q_offset,
                                 interpret=interpret_mode())


@partial(jax.jit, static_argnames=("sm_scale", "opt_kv", "window",
                                   "sink_pages", "share_visits", "interpret"))
def _paged_latent_decode_single(q_lat, q_rope, lat_pages, scale_pages, layer,
                                cache_len, phys_table, log_table, *,
                                sm_scale: float, opt_kv: bool, window: int,
                                sink_pages: int, share_visits: bool,
                                interpret: bool):
    lat_pages, scale_pages, layer = _interpreted(lat_pages, scale_pages, layer,
                                                 interpret)
    if _use_visits(share_visits, q_lat.shape[0]):
        vp, vm, vl = _vs.plan_visits(phys_table.astype(jnp.int32),
                                     log_table.astype(jnp.int32))
        return _ld.paged_latent_decode_visits(
            q_lat, q_rope, lat_pages, scale_pages, layer,
            cache_len.astype(jnp.int32), vp, vm, vl, sm_scale=sm_scale,
            opt_kv=opt_kv, window=window, sink_pages=sink_pages,
            interpret=interpret)
    return _ld.paged_latent_decode(
        q_lat, q_rope, lat_pages, scale_pages, layer,
        cache_len.astype(jnp.int32), phys_table.astype(jnp.int32),
        log_table.astype(jnp.int32), sm_scale=sm_scale, opt_kv=opt_kv,
        window=window, sink_pages=sink_pages, interpret=interpret)


def paged_latent_decode(q_lat, q_rope, lat_pages, scale_pages, layer,
                        cache_len, phys_table, log_table, *, sm_scale: float,
                        opt_kv: bool, window: int = 0, sink_pages: int = 0,
                        share_visits: bool = False):
    """Fused MLA absorbed decode over the global latent pool. q_lat
    (B,H,R) W_uk-absorbed queries; q_rope (B,H,dr); lat_pages
    (L,P_total,ps,R+dr) [c_kv|k_rope] packed, the pool of every layer;
    scale_pages (L,P_total,ps,2) dual c/k_rope scales | None; layer: int32
    scalar, the layer to attend; phys/log_table (B,NSel) int32 (-1 = never
    DMA'd). Returns o_lat (B,H,R) f32 — w_uv expansion stays outside."""
    if _MESH_CTX is not None:
        return _sh.paged_latent_decode(
            _MESH_CTX, q_lat, q_rope, lat_pages, scale_pages, layer,
            cache_len, phys_table, log_table, sm_scale=sm_scale,
            opt_kv=opt_kv, window=window, sink_pages=sink_pages,
            share_visits=share_visits, interpret=interpret_mode())
    return _paged_latent_decode_single(
        q_lat, q_rope, lat_pages, scale_pages, layer, cache_len, phys_table,
        log_table, sm_scale=sm_scale, opt_kv=opt_kv, window=window,
        sink_pages=sink_pages, share_visits=share_visits,
        interpret=interpret_mode())


@partial(jax.jit, static_argnames=("sm_scale", "opt_kv", "window",
                                   "sink_pages", "interpret"))
def _latent_chunk_prefill_single(q_lat, q_rope, positions, lat_pages,
                                 scale_pages, layer, phys_table, seg_q,
                                 page_seg, page_base, *, sm_scale: float,
                                 opt_kv: bool, window: int, sink_pages: int,
                                 interpret: bool):
    lat_pages, scale_pages, layer = _interpreted(lat_pages, scale_pages, layer,
                                                 interpret)
    return _lc.latent_chunk_prefill(
        q_lat, q_rope, positions.astype(jnp.int32), lat_pages, scale_pages,
        layer, phys_table.astype(jnp.int32), sm_scale=sm_scale,
        opt_kv=opt_kv, window=window, sink_pages=sink_pages,
        interpret=interpret, seg_q=seg_q, page_seg=page_seg,
        page_base=page_base)


def latent_chunk_prefill(q_lat, q_rope, positions, lat_pages, scale_pages,
                         layer, phys_table, *, sm_scale: float, opt_kv: bool,
                         window: int = 0, sink_pages: int = 0, seg_q=None,
                         page_seg=None, page_base=None):
    """MLA absorbed continuation-prefill over the global latent pool of
    every layer (``lat_pages`` (L,P_total,ps,R+dr), ``scale_pages``
    (L,P_total,ps,2) | None, ``layer`` the int32 layer to attend): a chunk
    of absorbed queries q_lat (B,S,H,R) / q_rope (B,S,H,dr) with absolute
    ``positions`` (B,S) attends the lane's cached latent pages named by the
    scalar-prefetched ``phys_table`` (B,NP; -1 = never DMA'd). The chunk's
    own latents must already be written. Returns o_lat (B,S,H,R) f32.
    ``seg_q``/``page_seg``/``page_base`` enable concat-prefill packing
    (several prompts per row, see the kernel docstring); None = unpacked."""
    if _MESH_CTX is not None:
        return _sh.latent_chunk_prefill(
            _MESH_CTX, q_lat, q_rope, positions, lat_pages, scale_pages,
            layer, phys_table, sm_scale=sm_scale, opt_kv=opt_kv,
            window=window, sink_pages=sink_pages, interpret=interpret_mode(),
            seg_q=seg_q, page_seg=page_seg, page_base=page_base)
    return _latent_chunk_prefill_single(
        q_lat, q_rope, positions, lat_pages, scale_pages, layer, phys_table,
        seg_q, page_seg, page_base, sm_scale=sm_scale, opt_kv=opt_kv,
        window=window, sink_pages=sink_pages, interpret=interpret_mode())


@partial(jax.jit, static_argnames=("opt_kv", "opt_gqa", "window",
                                   "sink_pages", "interpret"))
def _paged_chunk_prefill_single(q, positions, kv_pages, scale_pages, layer,
                                phys_table, seg_q, page_seg, page_base, *,
                                opt_kv: bool, opt_gqa: bool,
                                window: int, sink_pages: int,
                                interpret: bool):
    kv_pages, scale_pages, layer = _interpreted(kv_pages, scale_pages, layer,
                                                 interpret)
    return _fc.flash_chunk_prefill(
        q, positions.astype(jnp.int32), kv_pages, scale_pages, layer,
        phys_table.astype(jnp.int32), opt_kv=opt_kv, opt_gqa=opt_gqa,
        window=window, sink_pages=sink_pages, interpret=interpret,
        seg_q=seg_q, page_seg=page_seg, page_base=page_base)


def paged_chunk_prefill(q, positions, kv_pages, scale_pages, layer,
                        phys_table, *, opt_kv: bool, opt_gqa: bool,
                        window: int = 0, sink_pages: int = 0, seg_q=None,
                        page_seg=None, page_base=None):
    """Continuation-prefill attention over the global pool of every layer
    (``kv_pages`` (L,2,P_total,Hkv,ps,D), ``scale_pages`` | None, ``layer``
    the int32 layer to attend): a chunk of queries (B,S,Hq,D) with absolute
    ``positions`` (B,S) attends the lane's cached pages named by the
    scalar-prefetched ``phys_table`` (B,NP; -1 = never DMA'd). The chunk's
    own K/V must already be written.
    ``seg_q``/``page_seg``/``page_base`` enable concat-prefill packing
    (several prompts per row, see the kernel docstring); None = unpacked."""
    if _MESH_CTX is not None:
        return _sh.paged_chunk_prefill(
            _MESH_CTX, q, positions, kv_pages, scale_pages, layer,
            phys_table, opt_kv=opt_kv, opt_gqa=opt_gqa, window=window,
            sink_pages=sink_pages, interpret=interpret_mode(), seg_q=seg_q,
            page_seg=page_seg, page_base=page_base)
    return _paged_chunk_prefill_single(
        q, positions, kv_pages, scale_pages, layer, phys_table, seg_q,
        page_seg, page_base, opt_kv=opt_kv, opt_gqa=opt_gqa, window=window,
        sink_pages=sink_pages, interpret=interpret_mode())
