"""Opt-KV — KV-cache write/read path optimization (paper §3.1, Alg. 1).

Write phase (Eq. 5): a token's K/V are cached only if its slot index is valid:
``slot_idx_i < 0 or slot_idx_i in SkipSet`` => skip. We realise the SkipSet as
slots pre-marked -1 by the caller (engine policy: padding tokens, prefix-cache
hits, evicted/out-of-window tokens), so the write itself is a single scatter
with ``mode='drop'`` — negative indices never touch memory, exactly the
paper's "skip caching of K_i, V_i".

Read phase (Eq. 6): cached K/V are FP8 and dequantized on the fly
(``gather_cached_kv``). The Pallas kernel in ``repro.kernels`` fuses this into
the attention loop — on a single host and, through the ``kernels.sharded``
shard_map layer, per shard of a GSPMD mesh; this module is the
numerically-identical jnp parity reference used by tests.

Cache layout — ONE GLOBAL POOL for every layer, no batch dimension:
    kv (L, 2, P_total, Hkv, ps, D) + scale (L, 2, P_total, Hkv, ps).
Heads come before tokens within a page, so one head's page is a (ps, D)
tile: the block the Pallas kernels DMA (Mosaic needs the last two block
dims to be multiples of (8, 128) or whole). Flat slot ``page * ps + off``
names row ``off`` of page ``page`` in every head of a layer; the write path
addresses LINES of the whole pool, ``(layer * P_total + page) * ps + off``
(``pool_lines``), so the model's layer scan hands the pool on whole and no
layer is ever sliced out of it.
All sequences share the pool; the host-side ``BlockManager`` hands each
sequence a disjoint set of pages (refcounted, prefix-cache shareable) and the
per-step batch carries *global* flat slot indices and per-lane page tables.
Writes only ever target exclusively-owned pages (copy-on-write by
construction), so lane isolation needs no device-side masking.

Direct (non-engine) callers get a static lane-identity layout: pool =
``batch * pages(max_len)`` pages, lane b owning the contiguous range
``[b * P_lane, (b+1) * P_lane)`` — see ``identity_page_table`` /
``identity_slots``. Skipped tokens are not written anywhere. The engine's
BlockManager still never allocates the pool's last page, which once took
the write kernel's skipped tokens and no writer needs any more.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.cache.quant import dequantize_fp8, quantize_fp8
from repro.core.coopt import CoOptConfig


# ------------------------------------------------------- shard ownership --
# Pure-integer page-range math lives with the host-side allocator (which
# must stay importable without jax); re-exported here because the device
# side — models' ``init_cache`` pool sizing and mesh-aware page-table
# construction — keys off the same partition.
from repro.cache.block_manager import (padded_pool_pages,   # noqa: F401
                                       shard_page_ranges)

# Mesh axes the cache ``pages`` axis is sharded over — THE partition of the
# whole system: CACHE_RULES maps pages onto it, ``shard_page_ranges`` is its
# host mirror, ``launch.mesh.kv_shard_count`` takes its extent from it, and
# the ``kernels.sharded`` shard_map layer runs one kernel per shard of it.
# Lives here (not in the kernel package) so host-side tooling can read it
# without importing the Pallas stack.
PAGES_AXES = ("pod", "data")


def pool_layout(batch: int, max_len: int, coopt, num_shards: int = 1,
                cache_cfg=None):
    """Resolve the device pool's pages-axis layout -> ``(P, page_size)``.

    THE one sizing rule every model's ``cache_shape`` and the scheduler's
    BlockManager must agree on: ``P`` is the requested pool size —
    ``CacheConfig.num_pages`` when set, else ``batch * pages(max_len)`` —
    padded so the pages axis tiles evenly over the KV shards. (The engine
    reserves the final padded page, so the host allocator sees ``P - 1``
    usable pages.)"""
    ps = coopt.page_size
    pages = 0
    if cache_cfg is not None:
        ps = cache_cfg.page_size or ps
        num_shards = cache_cfg.num_shards or num_shards
        pages = cache_cfg.num_pages
    if not pages:
        pages = batch * (-(-max_len // ps))
    return padded_pool_pages(pages, num_shards), ps


def kv_pool_shapes(num_layers: int, batch: int, max_len: int,
                   num_kv_heads: int, head_dim: int, coopt, num_shards: int = 1,
                   cache_cfg=None):
    """Cache-shape entries ``{"kv", "scale"}`` — (shape, dtype, logical
    axes) — of a paged K/V pool over ``num_layers`` layers: THE pool layout
    every model's ``cache_shape`` uses. ``scale`` is present under
    Opt-KV."""
    P, ps = pool_layout(batch, max_len, coopt, num_shards, cache_cfg)
    L, H, D = num_layers, num_kv_heads, head_dim
    out = {"kv": ((L, 2, P, H, ps, D), coopt.kv_dtype,
                  ("layers", None, "pages", "kv_heads", None, "head_dim"))}
    if coopt.opt_kv:
        out["scale"] = ((L, 2, P, H, ps), jnp.float32,
                        ("layers", None, "pages", "kv_heads", None))
    return out


def global_to_local_pages(phys_table, first_page, num_local: int):
    """Translate a GLOBAL physical page table to one mesh shard's LOCAL page
    domain: entries inside the shard's contiguous range
    ``[first_page, first_page + num_local)`` become local indices, every
    other entry (other shards' pages, and -1 holes) becomes -1 — exactly the
    kernels' existing hole semantics, so non-owned pages are never DMA'd.
    Used inside the ``kernels.sharded`` shard_map bodies."""
    local = phys_table - first_page
    owned = (phys_table >= 0) & (local >= 0) & (local < num_local)
    return jnp.where(owned, local, -1).astype(jnp.int32)


def global_to_local_lines(line_idx, first_page, num_local: int,
                          num_pages: int, page_size: int):
    """Line analogue of ``global_to_local_pages``: lines of the whole pool,
    ``(layer * num_pages + page) * ps + off``, become lines of one mesh
    shard's pool, ``(layer * num_local + page - first_page) * ps + off``;
    lines of pages outside the shard's ``[first_page, first_page +
    num_local)`` (and -1 / SkipSet) become -1, which the writers drop."""
    per_layer = num_pages * page_size
    layer = line_idx // per_layer
    local = line_idx % per_layer - first_page * page_size
    owned = (line_idx >= 0) & (local >= 0) & (local < num_local * page_size)
    return jnp.where(owned, layer * num_local * page_size + local,
                     -1).astype(jnp.int32)


def pool_lines(slot_idx, layer, num_pages: int, page_size: int):
    """One layer's flat slots (``page * ps + off``, -1 = SkipSet) -> lines
    of the whole pool, ``(layer * num_pages + page) * ps + off``, the
    addresses the write path takes; -1 stays -1."""
    return jnp.where(slot_idx >= 0, slot_idx + layer * num_pages * page_size,
                     -1).astype(jnp.int32)


def make_pool(num_layers: int, num_pages: int, page_size: int,
              num_kv_heads: int, head_dim: int, coopt: CoOptConfig):
    """Zero-initialised GLOBAL paged cache of ``num_layers`` layers
    (kv, scale|None)."""
    shape = (num_layers, 2, num_pages, num_kv_heads, page_size)
    kv = jnp.zeros(shape + (head_dim,), coopt.kv_dtype)
    scale = jnp.zeros(shape, jnp.float32) if coopt.opt_kv else None
    return kv, scale


def scatter_kv(kv, scale, vals, scl, lines):
    """Scatter new tokens to lines of the pool: kv (L, 2, P, H, ps, D),
    scale (L, 2, P, H, ps) | None; vals (2, B, S, H, D) in the pool dtype;
    scl (2, B, S, H) | None; lines (B, S) ``(layer * P + page) * ps + off``.
    Lines that are negative or past the pool are dropped."""
    L, _, P, _, ps, _ = kv.shape
    layer = jnp.where(lines < 0, L, lines // (P * ps))
    page = lines // ps % P
    off = lines % ps
    # advanced indices split by slices put (B, S) first: (B, S, 2, H[, D])
    kv = kv.at[layer, :, page, :, off].set(jnp.moveaxis(vals, 0, 2),
                                           mode="drop")
    if scale is not None:
        scale = scale.at[layer, :, page, :, off].set(
            jnp.moveaxis(scl, 0, 2), mode="drop")
    return kv, scale


def scatter_latent(lat_cache, scale_cache, latent, lines, *, opt_kv: bool,
                   lora_rank: int):
    """The MLA latent write: dual-scale quantization (under ``opt_kv``) and a
    scatter to lines of the latent pool (L, P, ps, R+dr), scales
    (L, P, ps, 2) | None; latent (B, S, R+dr); lines (B, S). Negative lines
    are dropped: they are sent past the pool's end, where ``mode="drop"``
    discards them (a negative index would wrap)."""
    L, P, ps, W = lat_cache.shape
    n = L * P * ps
    lines = jnp.where(lines < 0, n, lines)
    flat = lat_cache.reshape(n, W)
    if opt_kv:
        from repro.cache.quant import quantize_latent
        qv, s = quantize_latent(latent, lora_rank)
        flat = flat.at[lines].set(qv.astype(flat.dtype), mode="drop")
        sf = scale_cache.reshape(n, 2).at[lines].set(s, mode="drop")
        scale_cache = sf.reshape(L, P, ps, 2)
    else:
        flat = flat.at[lines].set(latent.astype(flat.dtype), mode="drop")
    return flat.reshape(L, P, ps, W), scale_cache


# ------------------------------------------------------- identity layout --
def pages_per_lane(total_pages: int, batch: int) -> int:
    return max(total_pages // batch, 1)


def identity_page_table(batch: int, total_pages: int) -> jax.Array:
    """Static lane-partitioned page table (B, P_lane): lane b owns the
    contiguous page range [b*P_lane, (b+1)*P_lane). Default for direct
    (non-engine) callers of prefill/decode_step."""
    P_lane = pages_per_lane(total_pages, batch)
    return (jnp.arange(batch, dtype=jnp.int32)[:, None] * P_lane
            + jnp.arange(P_lane, dtype=jnp.int32)[None, :])


def identity_slots(batch: int, positions, total_pages: int,
                   page_size: int) -> jax.Array:
    """Logical positions (B, S) -> global flat slots under the lane-identity
    layout (slot == lane_offset + position)."""
    P_lane = pages_per_lane(total_pages, batch)
    off = jnp.arange(batch, dtype=jnp.int32)[:, None] * (P_lane * page_size)
    return (positions.astype(jnp.int32) + off)


def write_kv(kv_cache, scale_cache, k_new, v_new, line_idx,
             coopt: CoOptConfig):
    """Write new tokens' K/V into the global paged cache of every layer.

    kv_cache: (L, 2, P, Hkv, ps, D), scale_cache (L, 2, P, Hkv, ps) | None;
    k_new/v_new: (B, S, Hkv, D); line_idx: (B, S) int32 — lines of the
    whole pool, ``(layer * P + page) * page_size + offset`` (``pool_lines``
    of one layer's flat slots); -1/SkipSet => skip. Returns updated
    (kv_cache, scale_cache); the kernel path writes them in place.
    """
    if coopt.use_kernel:
        from repro.kernels import ops
        return ops.kv_cache_write(kv_cache, scale_cache, k_new, v_new,
                                  line_idx, opt_kv=coopt.opt_kv)
    new = jnp.stack([k_new, v_new])                      # (2,B,S,H,D)
    if coopt.opt_kv:
        q, s = quantize_fp8(new, axis=-1)                # (2,B,S,H,D),(2,B,S,H)
        return scatter_kv(kv_cache, scale_cache, q.astype(kv_cache.dtype), s,
                          line_idx)
    return scatter_kv(kv_cache, scale_cache, new.astype(kv_cache.dtype),
                      None, line_idx)


def dequant_pages(kv_pages, scale_pages, coopt: CoOptConfig, dtype=jnp.bfloat16):
    """Eq. 6 read path: fp8 pages -> compute dtype."""
    if coopt.opt_kv:
        return dequantize_fp8(kv_pages, scale_pages, axis=-1, dtype=dtype)
    return kv_pages.astype(dtype)


def gather_cached_kv(kv_cache, scale_cache, page_table, coopt: CoOptConfig,
                     dtype=jnp.bfloat16):
    """Reference of the paper's dedicated ``gather_cached_kv`` kernel.

    kv_cache: (2, P, Hkv, ps, D) one layer of the global pool
    (``pool[layer]``), scale_cache likewise; page_table: (B, Psel) int32
    physical page ids in logical order (negative => zero page). Returns
    (2, B, Psel*ps, Hkv, D) dequantized — token j of the output is the lane's
    logical position j, so downstream masks index by position directly.
    """
    _, P, H, ps, D = kv_cache.shape
    B, Psel = page_table.shape
    pt = jnp.maximum(page_table, 0)
    # (2,B,Psel,H,ps,D) -> token-major (2,B,Psel,ps,H,D)
    gathered = jnp.take(kv_cache, pt, axis=1).swapaxes(3, 4)
    if coopt.opt_kv:
        sg = jnp.take(scale_cache, pt, axis=1).swapaxes(3, 4)
        out = dequantize_fp8(gathered, sg, axis=-1, dtype=dtype)
    else:
        out = gathered.astype(dtype)
    valid = (page_table >= 0)[None, :, :, None, None, None]
    out = jnp.where(valid, out, 0)
    return out.reshape(2, B, Psel * ps, H, D)


def window_page_table(cache_len, num_pages: int, page_size: int,
                      window: int, sink_pages: int):
    """Opt-KV SkipSet as block sparsity (DESIGN.md §5 long-context policy).

    Operates in the LOGICAL page domain of one sequence: selects sink pages
    [0, sink) plus the trailing ``ceil(window/ps)+1`` pages covering the
    sliding window, for a scalar/array ``cache_len`` (inclusive count of
    tokens already cached). Returns (B, Psel) logical page ids, -1 = skipped;
    callers translate to physical pages via the per-lane page table
    (``jnp.take_along_axis(page_table, ...)``).

    A logical page id beyond the lane's table width (``cache_len`` larger
    than the table can back) becomes -1 — a SKIP, never an alias: clamping
    it onto page ``num_pages - 1`` would silently attend the wrong page's
    content.
    """
    wpages = window // page_size + 1
    # page holding the most recent token (cache_len is an inclusive count)
    last_page = jnp.maximum(jnp.asarray(cache_len) - 1, 0) // page_size  # (B,)
    start = jnp.maximum(last_page - (wpages - 1), 0)
    win = start[:, None] + jnp.arange(wpages)[None, :]        # (B, wpages)
    win = jnp.where(win <= last_page[:, None], win, -1)
    sink = jnp.broadcast_to(jnp.arange(sink_pages)[None, :],
                            (win.shape[0], sink_pages))
    sink = jnp.where(sink < jnp.minimum(start, sink_pages)[:, None], sink, -1)
    table = jnp.concatenate([sink, win], axis=1).astype(jnp.int32)
    return jnp.where(table >= num_pages, -1, table)


def logical_to_physical(logical_table, page_table):
    """Map a (B, NSel) LOGICAL page selection (-1 = skipped) through the
    per-lane (B, P_lane) physical page table, preserving -1 sentinels."""
    phys = jnp.take_along_axis(page_table,
                               jnp.maximum(logical_table, 0), axis=1)
    return jnp.where(logical_table < 0, -1, phys).astype(jnp.int32)


def decode_page_select(cache_len, page_table, page_size: int, *,
                       window: int = 0, sink_pages: int = 1,
                       opt_pa: bool = True):
    """(physical, logical) page selection for ONE decode step against the
    pool — the table pair every fused decode kernel (dense/moe KV pages and
    the MLA latent layout alike) scalar-prefetches.

    Dense (``window == 0``): logical pages are simply ``arange``; under
    Opt-Pa, physical entries wholly beyond the live context are masked to
    -1 (Eq. 9 valid-block filtering, host-free — the kernel never DMAs
    them), while the Original baseline streams every allocated page.
    Windowed: the {sink + sliding-window} block-sparse policy is decided in
    the logical page domain (``window_page_table``) then mapped through the
    lane's table, -1 sentinels preserved (skips, never aliases)."""
    B, P = page_table.shape
    if window:
        logical = window_page_table(cache_len, P, page_size, window,
                                    sink_pages)
        return logical_to_physical(logical, page_table), logical
    logical = jnp.broadcast_to(jnp.arange(P, dtype=jnp.int32)[None], (B, P))
    if opt_pa:
        beyond = logical * page_size >= cache_len[:, None]
        phys = jnp.where(beyond, -1, page_table)
    else:
        phys = page_table
    return phys.astype(jnp.int32), logical
