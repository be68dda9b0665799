"""shard_map layer over the pooled Pallas kernels — ONE kernel hot path for
single-host AND distributed (GSPMD mesh) serving.

The device cache's ``pages`` axis is sharded over the mesh ``PAGES_AXES``
(``(pod, data)`` — the same partition PR 2 mirrored host-side as
``opt_kv.shard_page_ranges``). This module wraps each pooled kernel in a
``shard_map`` over those axes so every mesh shard runs the UNCHANGED
single-host kernel against only its owned contiguous page range:

  * the per-lane GLOBAL physical page table is translated to the shard's
    LOCAL page domain (``opt_kv.global_to_local_pages``) — entries outside
    the shard's range become -1 and are never DMA'd, exactly the kernels'
    existing hole semantics, so no page crosses the interconnect;
  * each shard's kernel emits its final online-softmax state
    (``return_state=True`` -> normalized partial output + (m, l)), and the
    partials are combined with the standard log-sum-exp merge across the
    pages axes:  m* = pmax(m);  w_s = exp(m_s - m*) * l_s;
    out = psum(w_s * o_s) / psum(w_s).  A shard holding none of a lane's
    pages reports (m = -1e30, l = 0) and so contributes nothing;
  * the write path stays shard-local too: lines of the whole pool are
    translated to lines of the shard's own pool and the same write kernel
    runs per shard (other shards' lines become SkipSet -1s), so the pool is
    written in place with NO cross-shard traffic.

Every layer's pool travels whole, ``(L, 2, P_total, Hkv, ps, D)`` (latent:
``(L, P_total, ps, R+dr)``), pages-sharded on its pages axis, with the
``layer`` scalar replicated: the per-shard kernels pick the layer in place.

The engine-facing contract is unchanged: callers pass GLOBAL pools, GLOBAL
tables/slots, and get replicated outputs — ``kernels.ops`` dispatches here
whenever a ``ShardCtx`` is installed (``ops.set_mesh_ctx``), and an
unsharded mesh (pages-axes extent 1) yields ``make_ctx(...) is None`` so a
1-device mesh takes the *identical* code path as no mesh at all.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

# PAGES_AXES — the mesh axes the pages axis is sharded over — lives with
# the shard-ownership math in core.opt_kv (re-exported here for kernel-side
# callers); host tooling reads it without importing the Pallas stack.
from repro.core.opt_kv import (PAGES_AXES,                  # noqa: F401
                               global_to_local_lines, global_to_local_pages,
                               scatter_latent)
from repro.kernels import flash_chunk_prefill as _fc
from repro.kernels import kv_cache_write as _kw
from repro.kernels import latent_chunk_prefill as _lc
from repro.kernels import paged_gqa_decode as _pd
from repro.kernels import paged_latent_decode as _ld
from repro.kernels import visits as _vs


@dataclass(frozen=True)
class ShardCtx:
    """Static description of the pages-axis partition of one mesh — the
    ``jax.jit``-static handle the ops wrappers key their dispatch on."""
    mesh: jax.sharding.Mesh
    axes: Tuple[str, ...]          # PAGES_AXES members present in the mesh
    num_shards: int                # product of their extents


def make_ctx(mesh) -> Optional[ShardCtx]:
    """ShardCtx for ``mesh``, or None when the pages axes have extent 1 —
    an unsharded (or absent) mesh takes the identical single-host path."""
    if mesh is None:
        return None
    axes = tuple(a for a in PAGES_AXES if a in mesh.shape)
    n = int(math.prod(mesh.shape[a] for a in axes)) if axes else 1
    if n <= 1:
        return None
    return ShardCtx(mesh=mesh, axes=axes, num_shards=n)


def _shard_index(ctx: ShardCtx):
    """Linear shard id along the pages axes, major-to-minor in mesh-axis
    order — matches both the device layout of ``PartitionSpec(ctx.axes)``
    and the host ``shard_page_ranges`` ordering."""
    idx = jnp.int32(0)
    for a in ctx.axes:
        idx = idx * ctx.mesh.shape[a] + jax.lax.axis_index(a)
    return idx


def _lse_merge(ctx: ShardCtx, o, m, l, out_dtype):
    """Combine per-shard normalized partials across the pages axes.
    o (..., D) f32-able; m/l (...,) f32. Standard log-sum-exp merge, with
    each shard's weight normalized BEFORE the sum: when one shard holds all
    of a row's pages its share is exactly 1 and the others' exactly 0, so
    the row comes out bit-identical to the single-device kernel's. (The
    TPU's f32 division need not return exactly 1 for w / w, hence the
    select.)"""
    m_all = jax.lax.pmax(m, ctx.axes)
    w = jnp.exp(m - m_all) * l                     # 0 for page-less shards
    den = jax.lax.psum(w, ctx.axes)
    share = jnp.where(w == den, 1.0, w / jnp.maximum(den, 1e-30))
    return jax.lax.psum(o.astype(jnp.float32) * share[..., None],
                        ctx.axes).astype(out_dtype)


def _pages_spec(ndim: int, pages_dim: int, ctx: ShardCtx) -> P:
    entries = [None] * ndim
    entries[pages_dim] = ctx.axes if len(ctx.axes) > 1 else ctx.axes[0]
    return P(*entries)


# ------------------------------------------------------------- read path --
@partial(jax.jit, static_argnames=("ctx", "opt_kv", "opt_gqa", "window",
                                   "sink_pages", "share_visits", "interpret"))
def paged_pool_decode(ctx: ShardCtx, q, kv_pages, scale_pages, layer,
                      cache_len, phys_table, log_table, *, opt_kv: bool,
                      opt_gqa: bool, window: int = 0, sink_pages: int = 0,
                      share_visits: bool = False, interpret: bool = False):
    """Distributed ``paged_gqa_decode``: kv_pages (L, 2, P_total, Hkv, ps, D)
    pages-sharded over ``ctx.axes``; q/layer/tables/cache_len replicated;
    returns the replicated (B, Hq, D) attention output. With
    ``share_visits`` each shard plans its visit list AFTER the global->local
    page translation, so visits are deduplicated within (and never cross)
    the shard's own page range."""
    P_total = kv_pages.shape[2]
    P_local = P_total // ctx.num_shards
    use_visits = share_visits and 1 < q.shape[0] <= _vs.MAX_VISIT_LANES

    def body(q, kv, sc, lyr, cl, phys, log):
        first = _shard_index(ctx) * P_local
        lphys = global_to_local_pages(phys, first, P_local)
        if use_visits:
            vp, vm, vl = _vs.plan_visits(lphys, log)
            o, m, l = _pd.paged_pool_decode_visits(
                q, kv, sc, lyr, cl, vp, vm, vl,
                opt_kv=opt_kv, opt_gqa=opt_gqa, window=window,
                sink_pages=sink_pages, return_state=True,
                interpret=interpret)
        else:
            o, m, l = _pd.paged_pool_decode(
                q, kv, sc, lyr, cl, lphys, log,
                opt_kv=opt_kv, opt_gqa=opt_gqa, window=window,
                sink_pages=sink_pages, return_state=True,
                interpret=interpret)
        return _lse_merge(ctx, o, m, l, q.dtype)

    return jax.shard_map(
        body, mesh=ctx.mesh,
        in_specs=(P(), _pages_spec(6, 2, ctx), _pages_spec(5, 2, ctx),
                  P(), P(), P(), P()),
        out_specs=P(), check_vma=False,
    )(q, kv_pages, scale_pages, jnp.asarray(layer, jnp.int32),
      cache_len.astype(jnp.int32), phys_table.astype(jnp.int32),
      log_table.astype(jnp.int32))


@partial(jax.jit, static_argnames=("ctx", "opt_kv", "opt_gqa", "window",
                                   "sink_pages", "interpret"))
def paged_chunk_prefill(ctx: ShardCtx, q, positions, kv_pages, scale_pages,
                        layer, phys_table, *, opt_kv: bool, opt_gqa: bool,
                        window: int = 0, sink_pages: int = 0,
                        interpret: bool = False, seg_q=None, page_seg=None,
                        page_base=None):
    """Distributed ``flash_chunk_prefill``: chunk queries (B, S, Hq, D)
    replicated, pool (L, 2, P_total, Hkv, ps, D) pages-sharded; per-shard
    partials lse-merged. The packing tables (seg/base) live in the LOGICAL
    page domain, so they ride along replicated and untranslated — only the
    physical table is mapped into each shard's local range."""
    B, S = positions.shape
    P_total = kv_pages.shape[2]
    NP = phys_table.shape[1]
    P_local = P_total // ctx.num_shards
    if seg_q is None:
        seg_q = jnp.zeros((B, S), jnp.int32)
    if page_seg is None:
        page_seg = jnp.zeros((B, NP), jnp.int32)
    if page_base is None:
        page_base = jnp.broadcast_to(jnp.arange(NP, dtype=jnp.int32), (B, NP))

    def body(q, pos, kv, sc, lyr, phys, sq, pseg, pbase):
        first = _shard_index(ctx) * P_local
        lphys = global_to_local_pages(phys, first, P_local)
        o, m, l = _fc.flash_chunk_prefill(
            q, pos, kv, sc, lyr, lphys,
            opt_kv=opt_kv, opt_gqa=opt_gqa, window=window,
            sink_pages=sink_pages, return_state=True, interpret=interpret,
            seg_q=sq, page_seg=pseg, page_base=pbase)
        return _lse_merge(ctx, o, m, l, q.dtype)

    return jax.shard_map(
        body, mesh=ctx.mesh,
        in_specs=(P(), P(), _pages_spec(6, 2, ctx), _pages_spec(5, 2, ctx),
                  P(), P(), P(), P(), P()),
        out_specs=P(), check_vma=False,
    )(q, positions.astype(jnp.int32), kv_pages, scale_pages,
      jnp.asarray(layer, jnp.int32), phys_table.astype(jnp.int32),
      seg_q.astype(jnp.int32), page_seg.astype(jnp.int32),
      page_base.astype(jnp.int32))


@partial(jax.jit, static_argnames=("ctx", "sm_scale", "opt_kv", "window",
                                   "sink_pages", "share_visits", "interpret"))
def paged_latent_decode(ctx: ShardCtx, q_lat, q_rope, lat_pages, scale_pages,
                        layer, cache_len, phys_table, log_table, *,
                        sm_scale: float, opt_kv: bool, window: int = 0,
                        sink_pages: int = 0, share_visits: bool = False,
                        interpret: bool = False):
    """Distributed ``paged_latent_decode``: latent pool (L, P_total, ps,
    R+dr) pages-sharded; absorbed queries and layer replicated; returns
    o_lat (B, H, R) f32. With ``share_visits`` each shard plans its visit
    list AFTER the global->local translation (shard-local visit lists, see
    ``paged_pool_decode``)."""
    P_total = lat_pages.shape[1]
    P_local = P_total // ctx.num_shards
    use_visits = share_visits and 1 < q_lat.shape[0] <= _vs.MAX_VISIT_LANES

    def body(ql, qr, lat, sc, lyr, cl, phys, log):
        first = _shard_index(ctx) * P_local
        lphys = global_to_local_pages(phys, first, P_local)
        if use_visits:
            vp, vm, vl = _vs.plan_visits(lphys, log)
            o, m, l = _ld.paged_latent_decode_visits(
                ql, qr, lat, sc, lyr, cl, vp, vm, vl, sm_scale=sm_scale,
                opt_kv=opt_kv, window=window, sink_pages=sink_pages,
                return_state=True, interpret=interpret)
        else:
            o, m, l = _ld.paged_latent_decode(
                ql, qr, lat, sc, lyr, cl, lphys, log, sm_scale=sm_scale,
                opt_kv=opt_kv, window=window, sink_pages=sink_pages,
                return_state=True, interpret=interpret)
        return _lse_merge(ctx, o, m, l, jnp.float32)

    return jax.shard_map(
        body, mesh=ctx.mesh,
        in_specs=(P(), P(), _pages_spec(4, 1, ctx), _pages_spec(4, 1, ctx),
                  P(), P(), P(), P()),
        out_specs=P(), check_vma=False,
    )(q_lat, q_rope, lat_pages, scale_pages, jnp.asarray(layer, jnp.int32),
      cache_len.astype(jnp.int32), phys_table.astype(jnp.int32),
      log_table.astype(jnp.int32))


@partial(jax.jit, static_argnames=("ctx", "sm_scale", "opt_kv", "window",
                                   "sink_pages", "interpret"))
def latent_chunk_prefill(ctx: ShardCtx, q_lat, q_rope, positions, lat_pages,
                         scale_pages, layer, phys_table, *, sm_scale: float,
                         opt_kv: bool, window: int = 0, sink_pages: int = 0,
                         interpret: bool = False, seg_q=None, page_seg=None,
                         page_base=None):
    """Distributed ``latent_chunk_prefill``: chunk of absorbed queries
    (B, S, H, R) replicated, latent pool (L, P_total, ps, R+dr)
    pages-sharded; returns o_lat (B, S, H, R) f32. Packing tables
    (seg/base) are logical-domain and ride along replicated — only the
    physical table is shard-translated."""
    B, S = positions.shape
    NP = phys_table.shape[1]
    P_total = lat_pages.shape[1]
    P_local = P_total // ctx.num_shards
    if seg_q is None:
        seg_q = jnp.zeros((B, S), jnp.int32)
    if page_seg is None:
        page_seg = jnp.zeros((B, NP), jnp.int32)
    if page_base is None:
        page_base = jnp.broadcast_to(jnp.arange(NP, dtype=jnp.int32), (B, NP))

    def body(ql, qr, pos, lat, sc, lyr, phys, sq, pseg, pbase):
        first = _shard_index(ctx) * P_local
        lphys = global_to_local_pages(phys, first, P_local)
        o, m, l = _lc.latent_chunk_prefill(
            ql, qr, pos, lat, sc, lyr, lphys, sm_scale=sm_scale,
            opt_kv=opt_kv, window=window, sink_pages=sink_pages,
            return_state=True, interpret=interpret, seg_q=sq,
            page_seg=pseg, page_base=pbase)
        return _lse_merge(ctx, o, m, l, jnp.float32)

    return jax.shard_map(
        body, mesh=ctx.mesh,
        in_specs=(P(), P(), P(), _pages_spec(4, 1, ctx),
                  _pages_spec(4, 1, ctx), P(), P(), P(), P(), P()),
        out_specs=P(), check_vma=False,
    )(q_lat, q_rope, positions.astype(jnp.int32), lat_pages, scale_pages,
      jnp.asarray(layer, jnp.int32), phys_table.astype(jnp.int32),
      seg_q.astype(jnp.int32), page_seg.astype(jnp.int32),
      page_base.astype(jnp.int32))


# ------------------------------------------------------------ write path --
@partial(jax.jit, static_argnames=("ctx", "opt_kv", "interpret"))
def kv_pool_write(ctx: ShardCtx, kv_cache, scale_cache, k_new, v_new,
                  line_idx, *, opt_kv: bool, interpret: bool = False):
    """Shard-local write into the pages-sharded KV pool of every layer: each
    shard runs the single-device write kernel (``kernels.kv_cache_write``)
    on its own page range, with lines of other shards' pages turned into
    SkipSet -1s. No cross-shard traffic, and each line is quantized by the
    same kernel as on one device. Returns updated (kv_cache, scale_cache)."""
    P_total, ps = kv_cache.shape[2], kv_cache.shape[4]
    P_local = P_total // ctx.num_shards
    sc_in = scale_cache if opt_kv else None

    def body(kv, sc, k, v, lines):
        local = global_to_local_lines(lines, _shard_index(ctx) * P_local,
                                      P_local, P_total, ps)
        return _kw.kv_cache_write(k, v, local, kv, sc, opt_kv=opt_kv,
                                  interpret=interpret)

    kv, sc = jax.shard_map(
        body, mesh=ctx.mesh,
        in_specs=(_pages_spec(6, 2, ctx), _pages_spec(5, 2, ctx),
                  P(), P(), P()),
        out_specs=(_pages_spec(6, 2, ctx), _pages_spec(5, 2, ctx)),
        check_vma=False,
    )(kv_cache, sc_in, k_new, v_new, line_idx.astype(jnp.int32))
    return kv, (sc if opt_kv else scale_cache)


@partial(jax.jit, static_argnames=("ctx", "opt_kv", "lora_rank"))
def latent_pool_write(ctx: ShardCtx, lat_cache, scale_cache, latent,
                      line_idx, *, opt_kv: bool, lora_rank: int):
    """Shard-local write into the pages-sharded MLA latent pool of every
    layer (lat_cache (L, P, ps, R+dr); latent (B, S, R+dr); line_idx (B, S)
    lines of the whole pool): each shard quantizes and scatters the lines it
    owns. Returns updated (lat_cache, scale_cache)."""
    _, Pt, ps, _ = lat_cache.shape
    P_local = Pt // ctx.num_shards
    sc_in = scale_cache if opt_kv else None

    def body(lat, sc, latent, lines):
        local = global_to_local_lines(lines, _shard_index(ctx) * P_local,
                                      P_local, Pt, ps)
        return scatter_latent(lat, sc, latent, local, opt_kv=opt_kv,
                              lora_rank=lora_rank)

    lat, sc = jax.shard_map(
        body, mesh=ctx.mesh,
        in_specs=(_pages_spec(4, 1, ctx), _pages_spec(4, 1, ctx), P(), P()),
        out_specs=(_pages_spec(4, 1, ctx), _pages_spec(4, 1, ctx)),
        check_vma=False,
    )(lat_cache, sc_in, latent, line_idx.astype(jnp.int32))
    return lat, (sc if opt_kv else scale_cache)
