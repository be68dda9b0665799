"""Multi-head Latent Attention (deepseek-v2) with a *paged, quantizable latent
cache* — Opt-KV/Opt-Pa applied to MLA (DESIGN.md §5).

The per-token cache entry is the compressed latent c_kv (R) concatenated with
the shared rotary key k_rope (dr): one vector of R+dr floats. Opt-KV
quantizes it to FP8 with DUAL per-token scales (c_kv and k_rope have
different dynamic ranges — ``cache.quant.quantize_latent``); Opt-Pa pages it
and runs block-wise online softmax. Decode and chunk continuation both use
the matrix-absorption form (queries projected into latent space), so K/V are
never materialised per head.

Hot path: under ``coopt.use_kernel`` both ``mla_paged_decode`` and
``mla_chunk_attention`` dispatch to the fused Pallas kernels
(``kernels.paged_latent_decode`` / ``kernels.latent_chunk_prefill``) that
stream latent pages HBM->VMEM once for all H heads straight off the FP8
pool — no ``jnp.take`` full-pool gather. Under a GSPMD mesh the SAME
kernels run per shard against their owned latent page range through the
``kernels.sharded`` shard_map layer (partial softmax states lse-merged
across the pages axes) — there is no separate distributed hot path. The
jnp code below is the numerically-equivalent PARITY REFERENCE used by
tests; the ``w_uk`` absorption and ``w_uv`` expansion live outside the
kernels in both cases, so weights never enter VMEM.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.core.coopt import CoOptConfig
from repro.cache.quant import dequantize_latent
from repro.models.layers import (apply_rope, causal_attention, linear,
                                 rmsnorm, shard_act)

_NEG = -1e30


def mla_project(x, p, cfg, positions):
    """Shared projections. x (B,S,d) -> q_nope (B,S,H,dn), q_rope (B,S,H,dr),
    latent (B,S,R+dr) (k_rope already rotated)."""
    H, dn, dr, R = (cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                    cfg.kv_lora_rank)
    B, S, _ = x.shape
    q = linear(x, p["wq"]).reshape(B, S, H, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    ckv = linear(x, p["w_dkv"])                      # (B,S,R+dr)
    c, k_rope = ckv[..., :R], ckv[..., R:]
    c = rmsnorm(c, p["kv_norm"], cfg.norm_eps)
    k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta)[:, :, 0]
    latent = jnp.concatenate([c, k_rope], axis=-1)
    return q_nope, q_rope, latent


def mla_full_attention(q_nope, q_rope, latent, p, cfg, *, window: int = 0):
    """Train/prefill path: expand latent -> per-head K/V, chunked causal attn."""
    H, dn, dr, R, dv = (cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                        cfg.kv_lora_rank, cfg.v_head_dim)
    B, S, _ = latent.shape
    c, k_rope = latent[..., :R], latent[..., R:]
    k_nope = jnp.einsum("btr,rhd->bthd", c, p["w_uk"].reshape(R, H, dn))
    v = jnp.einsum("btr,rhd->bthd", c, p["w_uv"].reshape(R, H, dv))
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (B, S, H, dr))], axis=-1)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    o = causal_attention(q, k, v, window=window)     # (B,S,H,dn+dr->dv? no:)
    return o                                          # (B,S,H,dv)


def _absorb_q(q_nope, p, cfg):
    """W_uk absorption OUTSIDE the kernel: q_lat_h = q_nope_h @ W_uk_h, so
    score_h(t) = <q_lat_h, c_t> + <q_rope_h, k_rope_t> against raw latents."""
    H, dn, R = cfg.num_heads, cfg.qk_nope_head_dim, cfg.kv_lora_rank
    spec = "bshd,rhd->bshr" if q_nope.ndim == 4 else "bhd,rhd->bhr"
    return jnp.einsum(spec, q_nope.astype(jnp.float32),
                      p["w_uk"].reshape(R, H, dn).astype(jnp.float32))


def _expand_o(o_lat, p, cfg, dtype):
    """W_uv expansion OUTSIDE the kernel: latent-space attention output ->
    per-head values. o_lat (..., H, R) -> (..., H, dv)."""
    H, R, dv = cfg.num_heads, cfg.kv_lora_rank, cfg.v_head_dim
    spec = "bshr,rhd->bshd" if o_lat.ndim == 4 else "bhr,rhd->bhd"
    return jnp.einsum(spec, o_lat,
                      p["w_uv"].reshape(R, H, dv).astype(jnp.float32)
                      ).astype(dtype)


def mla_chunk_attention(q_nope, q_rope, lat_pages, scale_pages, layer,
                        positions, page_table, p, cfg, coopt: CoOptConfig, *,
                        window: int = 0, sink_pages: int = 1, seg_q=None,
                        page_seg=None, page_base=None):
    """Matrix-absorption CHUNK attention against the global latent pool —
    the MLA leg of the unified chunked-continuation prefill path.

    q_nope (B,S,H,dn), q_rope (B,S,H,dr) are this chunk's queries with
    absolute ``positions`` (B,S); ``lat_pages`` (L,P_total,ps,R+dr) and
    ``scale_pages`` are the latent pool of every layer and ``layer`` the one
    attended. The chunk's latents are already written to
    the paged cache, so queries attend the lane's WHOLE latent history
    (prefix-cache hits + earlier chunks + this one) in absorbed form
    — K/V are never materialised per head, exactly like decode (a decode
    lane is a chunk of length 1). Under ``coopt.use_kernel`` this dispatches
    to the fused ``latent_chunk_prefill`` Pallas kernel (latent pages
    streamed off the FP8 pool, no host-side gather); the jnp body below is
    the parity reference. ``seg_q``/``page_seg``/``page_base`` enable
    concat-prefill packing (segment-masked attention, per-segment position
    restart — see ``opt_pa.paged_chunk_attention``); None = unpacked.
    Returns (B,S,H,dv)."""
    H, dn, dr, R, dv = (cfg.num_heads, cfg.qk_nope_head_dim,
                        cfg.qk_rope_head_dim, cfg.kv_lora_rank,
                        cfg.v_head_dim)
    B, S = q_nope.shape[:2]
    P_total, ps = lat_pages.shape[1:3]
    if page_table is None:
        from repro.core.opt_kv import identity_page_table
        page_table = identity_page_table(B, P_total)
    scale = 1.0 / math.sqrt(dn + dr)
    q_lat = _absorb_q(q_nope, p, cfg)                  # (B,S,H,R)

    if coopt.use_kernel:
        from repro.kernels import ops
        o_lat = ops.latent_chunk_prefill(
            q_lat, q_rope.astype(jnp.float32), positions, lat_pages,
            scale_pages if coopt.opt_kv else None, layer, page_table,
            sm_scale=scale, opt_kv=coopt.opt_kv, window=window,
            sink_pages=sink_pages, seg_q=seg_q, page_seg=page_seg,
            page_base=page_base)
        return _expand_o(o_lat, p, cfg, q_nope.dtype)

    q_lat = shard_act(q_lat, ("batch", None, None, "latent"))
    q_rope = shard_act(q_rope.astype(jnp.float32),
                       ("batch", None, None, "latent"))

    pt = jnp.maximum(page_table, 0)
    lat = jnp.take(lat_pages[layer], pt, axis=0)       # (B,NP,ps,R+dr)
    if coopt.opt_kv:
        sc = jnp.take(scale_pages[layer], pt, axis=0)
        lat = dequantize_latent(lat, sc, R, dtype=jnp.float32)
    else:
        lat = lat.astype(jnp.float32)
    T = page_table.shape[1] * ps
    lat = lat.reshape(B, T, R + dr)
    lat_c = shard_act(lat[..., :R], ("batch", None, "latent"))
    lat_r = shard_act(lat[..., R:], ("batch", None, "latent"))

    s = (jnp.einsum("bshr,btr->bhst", q_lat, lat_c)
         + jnp.einsum("bshe,bte->bhst", q_rope, lat_r)) * scale
    s = shard_act(s, ("batch", None, None, None))
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
    s = jnp.where(mask[:, None], s, _NEG)
    pr = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("bhst,btr->bshr", pr, lat_c)
    return _expand_o(o_lat, p, cfg, q_nope.dtype)


def mla_paged_decode(q_nope, q_rope, lat_pages, scale_pages, layer, cache_len,
                     p, cfg, coopt: CoOptConfig, *, window: int = 0,
                     sink_pages: int = 1, page_table=None):
    """Absorbed decode against the GLOBAL latent pool. q_nope/q_rope
    (B,H,dn|dr); lat_pages (L,P_total,ps,R+dr) the pool of every layer,
    shared by all lanes, and ``layer`` the one attended; page_table
    (B,P_lane) physical pages in logical order (default: lane-identity
    partition). Under ``coopt.use_kernel`` this dispatches to
    the fused ``paged_latent_decode`` Pallas kernel — each latent page
    streamed into VMEM once and shared by all H absorbed heads, dual-scale
    FP8 dequant fused at the HBM->VMEM boundary; the jnp body below is the
    parity reference. Returns (B,H,dv)."""
    H, dn, dr, R, dv = (cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                        cfg.kv_lora_rank, cfg.v_head_dim)
    B = q_nope.shape[0]
    P_total, ps = lat_pages.shape[1:3]
    if page_table is None:
        from repro.core.opt_kv import identity_page_table
        page_table = identity_page_table(B, P_total)
    P = page_table.shape[1]
    scale = 1.0 / math.sqrt(dn + dr)
    # absorb W_uk into q: score_h(t) = <q_lat_h, c_t> + <q_rope_h, k_rope_t>
    q_lat = _absorb_q(q_nope, p, cfg)                  # (B,H,R)

    if coopt.use_kernel:
        # (physical, logical) tables for the scalar-prefetched latent
        # kernel: Eq. 9 filtering / the {sink + window} policy decided
        # host-free, shared with the dense-KV path (decode_page_select).
        from repro.core.opt_kv import decode_page_select
        from repro.kernels import ops
        phys, logical = decode_page_select(cache_len, page_table, ps,
                                           window=window,
                                           sink_pages=sink_pages,
                                           opt_pa=coopt.opt_pa)
        o_lat = ops.paged_latent_decode(
            q_lat, q_rope.astype(jnp.float32), lat_pages,
            scale_pages if coopt.opt_kv else None, layer, cache_len, phys,
            logical,
            sm_scale=scale, opt_kv=coopt.opt_kv, window=window,
            sink_pages=sink_pages, share_visits=coopt.share_visits)
        return _expand_o(o_lat, p, cfg, q_nope.dtype)

    # (q_lat resharded once per layer to match the model-sharded latent
    # cache — its r dim inherits w_uk's d_in->data otherwise, §Perf P2)
    q_lat = shard_act(q_lat, ("batch", None, "latent"))
    q_rope = shard_act(q_rope, ("batch", None, "latent"))
    lat_pages = lat_pages[layer]
    scale_pages = scale_pages[layer] if coopt.opt_kv else None

    def dequant(pages, scales):
        """pages (..., R+dr); scales (..., 2) — separate c / rope scales."""
        if coopt.opt_kv:
            return dequantize_latent(pages, scales, R, dtype=jnp.float32)
        return pages.astype(jnp.float32)

    if window:
        from repro.core.opt_kv import logical_to_physical, window_page_table
        logical = window_page_table(cache_len, P, ps, window, sink_pages)
        phys = logical_to_physical(logical, page_table)
        pt = jnp.maximum(phys, 0)
        lat = jnp.take(lat_pages, pt, axis=0)          # (B,NSel,ps,R+dr)
        sc = (jnp.take(scale_pages, pt, axis=0) if coopt.opt_kv else None)
        lat = dequant(lat, sc)
        lat = lat.reshape(B, -1, R + dr)
        pos = (jnp.maximum(logical, 0)[:, :, None] * ps
               + jnp.arange(ps)[None, None]).reshape(B, -1)
        ok = (pos < cache_len[:, None]) \
            & ((pos >= jnp.maximum(cache_len[:, None] - window, 0))
               | (pos < sink_pages * ps)) \
            & jnp.repeat(phys >= 0, ps, axis=1)
        s = (jnp.einsum("bhr,btr->bht", q_lat, lat[..., :R])
             + jnp.einsum("bhe,bte->bht", q_rope.astype(jnp.float32),
                          lat[..., R:])) * scale
        s = jnp.where(ok[:, None], s, _NEG)
        m = jnp.max(s, axis=-1, keepdims=True)
        pr = jnp.exp(s - m)
        pr = pr / jnp.maximum(jnp.sum(pr, axis=-1, keepdims=True), 1e-30)
        o_lat = jnp.einsum("bht,btr->bhr", pr, lat[..., :R])
        return _expand_o(o_lat, p, cfg, q_nope.dtype)

    # dense path: gather the lane's pages in logical order, then reduce —
    # token j of the gathered view is logical position j.
    pt = jnp.maximum(page_table, 0)
    lat_lane = jnp.take(lat_pages, pt, axis=0)         # (B,P,ps,R+dr)
    sc_lane = (jnp.take(scale_pages, pt, axis=0) if coopt.opt_kv else None)
    valid = jnp.repeat(page_table >= 0, ps, axis=1)    # (B, P*ps)

    pg = coopt.page_group if coopt.opt_pa else P
    while P % pg:
        pg //= 2
    pg = max(pg, 1)
    NG, T = P // pg, pg * ps
    lat_g = lat_lane.reshape(B, NG, T, R + dr)
    sc_g = sc_lane.reshape(B, NG, T, 2) if coopt.opt_kv else None
    valid_g = valid.reshape(B, NG, T)

    def body(carry, g):
        m, l, acc = carry
        lat = dequant(lat_g[:, g], None if sc_g is None else sc_g[:, g])
        # keep the dequantized latent model-sharded along its width and
        # force the (tiny) score tensor to be the all-reduced partial sum —
        # without this GSPMD all-gathers the full latent page group per
        # scan step (EXPERIMENTS.md §Perf P2)
        lat_c = shard_act(lat[..., :R], ("batch", None, "latent"))
        lat_r = shard_act(lat[..., R:], ("batch", None, "latent"))
        s = (jnp.einsum("bhr,btr->bht", q_lat, lat_c)
             + jnp.einsum("bhe,bte->bht", q_rope.astype(jnp.float32),
                          lat_r)) * scale
        s = shard_act(s, ("batch", None, None))
        pos = g * T + jnp.arange(T)[None, None, :]
        ok = (pos < cache_len[:, None, None]) & valid_g[:, g][:, None, :]
        s = jnp.where(ok, s, _NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m - m_new)
        pr = jnp.exp(s - m_new)
        l = l * corr[..., 0] + jnp.sum(pr, axis=-1)
        acc = acc * corr + shard_act(
            jnp.einsum("bht,btr->bhr", pr, lat_c),
            ("batch", None, "latent"))
        return (m_new, l, acc), None

    m0 = jnp.full((B, H, 1), _NEG, jnp.float32)
    l0 = jnp.zeros((B, H), jnp.float32)
    a0 = jnp.zeros((B, H, R), jnp.float32)
    if NG == 1:
        (m, l, acc), _ = body((m0, l0, a0), 0)
    else:
        (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), jnp.arange(NG))
    o_lat = acc / jnp.maximum(l, 1e-30)[..., None]
    return _expand_o(o_lat, p, cfg, q_nope.dtype)
