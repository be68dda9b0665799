"""Pure-jnp oracles for every Pallas kernel (flat softmax, no blocking, no
online accumulation) — the ground truth for the per-kernel allclose sweeps.
Deliberately written in the most naive form so a kernel bug cannot be
mirrored here. Layouts follow the GLOBAL paged pool: kv pages carry no batch
dimension; lanes address the pool through (physical, logical) page tables.
"""
from __future__ import annotations

import math

import jax.numpy as jnp
import jax

from repro.cache.quant import FP8_MAX

_NEG = -1e30


def _dq(pages, scales, opt_kv):
    if opt_kv:
        return pages.astype(jnp.float32) * scales[..., None]
    return pages.astype(jnp.float32)


def paged_pool_decode_ref(q, k_pages, v_pages, k_scale, v_scale, cache_len,
                          phys_table, log_table, *, opt_kv: bool,
                          window: int = 0, sink_pages: int = 0):
    """Flat-softmax oracle of the fused pooled decode kernel.

    q (B,Hq,D); k/v_pages (P_total, Hkv, ps, D); k/v_scale (P_total, Hkv,
    ps) | None; phys/log_table (B, NSel),
    -1 = skipped. Gathers each lane's selected pages, places token j of
    logical page L at position L*ps+j, and reduces with one flat softmax —
    the kernel's online accumulation must match this exactly (modes agree
    numerically; Opt-Pa/Opt-GQA only change the compute schedule).
    """
    B, Hq, D = q.shape
    P, Hkv, ps, _ = k_pages.shape
    G = Hq // Hkv
    pt = jnp.maximum(phys_table, 0)

    def take(x):    # gathered (B, NSel, Hkv, ps[, D]) -> token-major
        return None if x is None else jnp.take(x, pt, axis=0).swapaxes(2, 3)
    k = _dq(take(k_pages), take(k_scale), opt_kv)       # (B,NSel,ps,Hkv,D)
    v = _dq(take(v_pages), take(v_scale), opt_kv)
    NSel = phys_table.shape[1]
    k = k.reshape(B, NSel * ps, Hkv, D)
    v = v.reshape(B, NSel * ps, Hkv, D)
    qf = q.reshape(B, Hkv, G, D).astype(jnp.float32)
    s = jnp.einsum("bhgd,bthd->bhgt", qf, k) / math.sqrt(D)
    pos = (jnp.maximum(log_table, 0)[:, :, None] * ps
           + jnp.arange(ps)[None, None]).reshape(B, -1)
    ok = (pos < cache_len[:, None]) & jnp.repeat(phys_table >= 0, ps, axis=1)
    if window:
        ok &= ((pos >= jnp.maximum(cache_len[:, None] - window, 0))
               | (pos < sink_pages * ps))
    s = jnp.where(ok[:, None, None, :], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgt,bthd->bhgd", p, v)
    return o.reshape(B, Hq, D).astype(q.dtype)


def _dq_latent(lat, scales, lora_rank, opt_kv):
    """Dual-scale latent dequant, written out naively: col 0 scales the
    c_kv segment, col 1 the k_rope segment."""
    lat = lat.astype(jnp.float32)
    if not opt_kv:
        return lat
    c = lat[..., :lora_rank] * scales[..., 0:1]
    r = lat[..., lora_rank:] * scales[..., 1:2]
    return jnp.concatenate([c, r], axis=-1)


def paged_latent_decode_ref(q_lat, q_rope, lat_pages, scale_pages, cache_len,
                            phys_table, log_table, *, sm_scale: float,
                            opt_kv: bool, window: int = 0,
                            sink_pages: int = 0):
    """Flat-softmax oracle of the fused MLA latent decode kernel.

    q_lat (B,H,R) absorbed queries; q_rope (B,H,dr); lat_pages (P_total, ps,
    R+dr) [c_kv|k_rope]; scale_pages (P_total, ps, 2) dual scales | None;
    phys/log_table (B, NSel), -1 = skipped. Gathers each lane's selected
    pages, places token j of logical page L at position L*ps+j, and reduces
    with one flat softmax over the latent-space scores. Returns o_lat
    (B,H,R) f32 — the w_uv expansion stays with the caller."""
    B, H, R = q_lat.shape
    P, ps, W = lat_pages.shape
    NSel = phys_table.shape[1]
    pt = jnp.maximum(phys_table, 0)
    lat = _dq_latent(jnp.take(lat_pages, pt, axis=0),
                     None if scale_pages is None
                     else jnp.take(scale_pages, pt, axis=0),
                     R, opt_kv).reshape(B, NSel * ps, W)
    s = (jnp.einsum("bhr,btr->bht", q_lat.astype(jnp.float32), lat[..., :R])
         + jnp.einsum("bhe,bte->bht", q_rope.astype(jnp.float32),
                      lat[..., R:])) * sm_scale
    pos = (jnp.maximum(log_table, 0)[:, :, None] * ps
           + jnp.arange(ps)[None, None]).reshape(B, -1)
    ok = (pos < cache_len[:, None]) & jnp.repeat(phys_table >= 0, ps, axis=1)
    if window:
        ok &= ((pos >= jnp.maximum(cache_len[:, None] - window, 0))
               | (pos < sink_pages * ps))
    s = jnp.where(ok[:, None, :], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bht,btr->bhr", p, lat[..., :R])


def latent_chunk_prefill_ref(q_lat, q_rope, positions, lat_pages,
                             scale_pages, phys_table, *, sm_scale: float,
                             opt_kv: bool, window: int = 0,
                             sink_pages: int = 0):
    """Flat-softmax oracle of the MLA latent chunk-prefill kernel: chunk
    queries q_lat (B,S,H,R) / q_rope (B,S,H,dr) with per-row ``positions``
    (B,S) against the gathered latent history. Returns o_lat (B,S,H,R)."""
    B, S, H, R = q_lat.shape
    P, ps, W = lat_pages.shape
    NP = phys_table.shape[1]
    pt = jnp.maximum(phys_table, 0)
    lat = _dq_latent(jnp.take(lat_pages, pt, axis=0),
                     None if scale_pages is None
                     else jnp.take(scale_pages, pt, axis=0),
                     R, opt_kv).reshape(B, NP * ps, W)
    s = (jnp.einsum("bshr,btr->bhst", q_lat.astype(jnp.float32),
                    lat[..., :R])
         + jnp.einsum("bshe,bte->bhst", q_rope.astype(jnp.float32),
                      lat[..., R:])) * sm_scale
    kpos = jnp.arange(NP * ps, dtype=jnp.int32)[None, None, :]
    qpos = positions[:, :, None]
    ok = (kpos <= qpos) & jnp.repeat(phys_table >= 0, ps, axis=1)[:, None, :]
    if window:
        ok &= (kpos > qpos - window) | (kpos < sink_pages * ps)
    s = jnp.where(ok[:, None], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhst,btr->bshr", p, lat[..., :R])


def kv_cache_write_ref(k_new, v_new, slot_idx, k_cache, v_cache, k_scale,
                       v_scale, *, opt_kv: bool):
    """Scatter-with-drop oracle over the GLOBAL flat pool (NSlot, Hkv, D):
    SkipSet tokens (negative slots) write no line."""
    # out of range, so dropped (a -1 would wrap onto the last line)
    slots = jnp.where(slot_idx < 0, k_cache.shape[0], slot_idx)   # (B, S)

    def put(cache, scale, new):
        newf = new.astype(jnp.float32)
        if opt_kv:
            amax = jnp.max(jnp.abs(newf), axis=-1)
            sc = jnp.maximum(amax, 1e-12) / FP8_MAX
            qv = (newf / sc[..., None]).astype(cache.dtype)
            cache = cache.at[slots].set(qv, mode="drop")
            scale = scale.at[slots].set(sc, mode="drop")
        else:
            cache = cache.at[slots].set(newf.astype(cache.dtype),
                                        mode="drop")
        return cache, scale

    k_cache, k_scale = put(k_cache, k_scale, k_new)
    v_cache, v_scale = put(v_cache, v_scale, v_new)
    return k_cache, v_cache, k_scale, v_scale


def flash_prefill_ref(q, k, v, *, window: int = 0, q_offset: int = 0):
    """Naive full-matrix causal (windowed) GQA attention."""
    B, S, Hq, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    qf = q.reshape(B, S, Hkv, G, D).astype(jnp.float32)
    s = jnp.einsum("bshgd,bthd->bhgst", qf, k.astype(jnp.float32)) \
        / math.sqrt(D)
    spos = q_offset + jnp.arange(S)[:, None]
    kpos = jnp.arange(T)[None, :]
    mask = spos >= kpos
    if window:
        mask &= (spos - kpos) < window
    s = jnp.where(mask[None, None, None], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgst,bthd->bshgd", p, v.astype(jnp.float32))
    return o.reshape(B, S, Hq, D).astype(q.dtype)
