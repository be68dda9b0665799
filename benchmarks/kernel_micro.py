"""Kernel micro-benchmark: the fused paged decode hot path, plus the fused
MLA latent kernels (absorbed decode / chunk prefill off the FP8 latent pool).

On this CPU container Pallas runs in interpret mode, so wall-clock is NOT a
TPU prediction; what this table establishes is
  (a) numerical parity kernel-vs-oracle per mode (max |err|),
  (b) the ANALYTIC per-call traffic model of each mode: HBM bytes touched by
      the kernel per token (the quantity Opt-KV/Opt-Pa actually optimize),
  (c) CPU-relative timings between the jnp reference paths of the modes
      (same schedule the TPU executes, jit-compiled by XLA:CPU).
The ``mla-latent-*`` rows compare the jnp gather reference (which
materialises the lane's whole latent history in f32 via ``jnp.take``) with
the fused kernels that stream only live fp8 pages — the "beats" claim is the
traffic column; kernel rows' wall-clock is interpret-mode and only recorded
for completeness.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.cache.quant import quantize_fp8, quantize_latent
from repro.configs import get_config
from repro.core.coopt import MODES
from repro.core.opt_kv import identity_page_table
from repro.core.opt_pa import paged_decode_attention
from repro.kernels import ops, ref
from repro.models import mla as mla_mod

from benchmarks.common import write_csv


def kernel_bytes_per_call(B, P, ps, Hkv, D, *, opt_kv, opt_pa, opt_gqa, Hq,
                          cache_len, shared_prefix_pages=0, lanes_sharing=0,
                          share_visits=False):
    """HBM->VMEM traffic of one decode-attention call (bytes).

    ``shared_prefix_pages``/``lanes_sharing`` describe a prompt prefix whose
    pages are refcount-shared by ``lanes_sharing`` lanes. The per-lane grid
    streams each of those pages once PER LANE; the cross-lane visit grid
    (``share_visits=True``, kernels.visits) streams each once TOTAL, so the
    duplicate ``(lanes_sharing - 1) * shared`` page streams drop out."""
    kv_elt = 1 if opt_kv else 2                   # fp8 vs bf16
    pages_touched = (min((cache_len + ps - 1) // ps, P) if opt_pa else P)
    page_streams = B * pages_touched              # per-lane page visits
    if share_visits and lanes_sharing > 1 and shared_prefix_pages > 0:
        shared = min(shared_prefix_pages, pages_touched)
        page_streams -= (min(lanes_sharing, B) - 1) * shared
    streams = 1 if opt_gqa else Hq // Hkv         # KV re-streamed per q head
    kv_bytes = 2 * page_streams * ps * Hkv * D * kv_elt * streams
    scale_bytes = (2 * page_streams * ps * Hkv * 4 * streams
                   if opt_kv else 0)
    q_bytes = B * Hq * D * 2
    return kv_bytes + scale_bytes + q_bytes


def latent_bytes_per_call(B, NP, ps, R, dr, *, fused: bool, opt_kv: bool,
                          cache_len: int, shared_prefix_pages=0,
                          lanes_sharing=0, share_visits=False):
    """HBM traffic of one MLA absorbed decode-attention call (bytes).

    The jnp gather reference ``jnp.take``s the lane's ENTIRE page table and
    materialises it in f32 (read stored dtype + write f32 + re-read f32 for
    the score/value einsums); the fused kernel streams only pages holding
    live context HBM->VMEM ONCE, in the stored (fp8) dtype, shared by all H
    absorbed heads — Opt-GQA at its G = H limit, so head count drops out."""
    W = R + dr
    elt = 1 if opt_kv else 2                       # fp8 vs bf16 storage
    if fused:
        pages = min((cache_len + ps - 1) // ps, NP)  # Eq. 9: -1 never DMA'd
        page_streams = B * pages
        if share_visits and lanes_sharing > 1 and shared_prefix_pages > 0:
            # cross-lane visit grid: shared prefix pages stream once total
            page_streams -= ((min(lanes_sharing, B) - 1)
                             * min(shared_prefix_pages, pages))
        scale = page_streams * ps * 2 * 4 if opt_kv else 0
        return page_streams * ps * W * elt + scale
    stored = B * NP * ps * W * elt + (B * NP * ps * 2 * 4 if opt_kv else 0)
    f32 = B * NP * ps * W * 4
    return stored + 2 * f32                        # materialise + re-read


def _time(fn, *args, n=20):
    out = fn(*args)
    jax.tree.map(lambda x: x.block_until_ready(), out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.tree.map(lambda x: x.block_until_ready(), out)
    return (time.perf_counter() - t0) / n * 1e6


_LATENT_ROWS_CACHE = {}


def latent_rows(quick: bool = False):
    """``mla-latent-{decode,chunk}-{jnp,kernel}`` rows: deepseek-v2-lite
    shaped (H=16, dn=128, dr=64, R=512) unless ``quick`` (reduced dims).
    Memoized per ``quick`` — a full sweep hits this from both the ``kernel``
    and ``mla`` benches, and interpret-mode kernel timing is expensive; the
    CSV and BENCH_mla.json must carry the SAME rows anyway."""
    if quick in _LATENT_ROWS_CACHE:
        return _LATENT_ROWS_CACHE[quick]
    cfg = get_config("deepseek-v2-lite-16b" + ("-reduced" if quick else ""))
    H, dn, dr = cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    R, dv = cfg.kv_lora_rank, cfg.v_head_dim
    B, P, ps, S = (2, 8, 16, 8) if quick else (4, 32, 16, 16)
    cache_len = P * ps // 2
    co = MODES["coopt"]
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    p = {"w_uk": jax.random.normal(ks[0], (R, H * dn)) * 0.05,
         "w_uv": jax.random.normal(ks[1], (R, H * dv)) * 0.05}
    qn = jax.random.normal(ks[2], (B, H, dn)).astype(jnp.bfloat16)
    qr = jax.random.normal(ks[3], (B, H, dr)).astype(jnp.bfloat16)
    # a latent pool of one layer, attended at layer 0
    latf = jax.random.normal(ks[4], (1, B * P, ps, R + dr), jnp.float32)
    lat, sc = quantize_latent(latf, R)
    cl = jnp.full((B,), cache_len, jnp.int32)
    pt = identity_page_table(B, B * P)

    rows = []

    def cell(name, fn, args, fused_traffic, jnp_traffic):
        jnp_fn = jax.jit(lambda *a: fn(*a, co.replace(use_kernel=False)))
        kern_fn = lambda *a: fn(*a, co.replace(use_kernel=True))  # noqa:E731
        us_jnp = _time(jnp_fn, *args)
        err = float(np.abs(np.asarray(jnp_fn(*args), np.float32)
                           - np.asarray(kern_fn(*args), np.float32)).max())
        us_k = _time(kern_fn, *args)
        rows.append([f"{name}-jnp", round(us_jnp, 1), jnp_traffic, ""])
        rows.append([f"{name}-kernel", round(us_k, 1), fused_traffic,
                     f"{err:.4f}"])
        print(f"kernel_micro {name}: jnp={us_jnp:9.1f}us/call "
              f"traffic={jnp_traffic / 1024:8.1f}KiB -> fused "
              f"traffic={fused_traffic / 1024:8.1f}KiB "
              f"({100 * (1 - fused_traffic / jnp_traffic):.1f}% less), "
              f"err={err:.4f}", flush=True)

    tr = dict(ps=ps, R=R, dr=dr, opt_kv=True, cache_len=cache_len)
    cell("mla-latent-decode",
         lambda qn_, qr_, lat_, sc_, cl_, pt_, co_: mla_mod.mla_paged_decode(
             qn_, qr_, lat_, sc_, 0, cl_, p, cfg, co_, page_table=pt_),
         (qn, qr, lat, sc, cl, pt),
         latent_bytes_per_call(B, P, **tr, fused=True),
         latent_bytes_per_call(B, P, **tr, fused=False))

    qn4 = jnp.broadcast_to(qn[:, None], (B, S, H, dn))
    qr4 = jnp.broadcast_to(qr[:, None], (B, S, H, dr))
    positions = jnp.broadcast_to(jnp.arange(cache_len - S, cache_len),
                                 (B, S)).astype(jnp.int32)
    cell("mla-latent-chunk",
         lambda qn_, qr_, lat_, sc_, pos_, pt_, co_:
             mla_mod.mla_chunk_attention(qn_, qr_, lat_, sc_, 0, pos_, pt_,
                                         p, cfg, co_),
         (qn4, qr4, lat, sc, positions, pt),
         latent_bytes_per_call(B, P, **tr, fused=True),
         latent_bytes_per_call(B, P, **tr, fused=False))
    _LATENT_ROWS_CACHE[quick] = rows
    return rows


def run(quick: bool = False):
    B, P, ps, Hkv, G, D = (2, 8, 16, 2, 4, 128) if quick else \
        (4, 32, 16, 2, 4, 128)
    Hq = Hkv * G
    cache_len = P * ps // 2
    PT = B * P                    # global pool, lane-identity partitioned
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, Hq, D)).astype(jnp.bfloat16)
    kf = jax.random.normal(ks[1], (PT, Hkv, ps, D), jnp.float32)
    vf = jax.random.normal(ks[2], (PT, Hkv, ps, D), jnp.float32)
    cl = jnp.full((B,), cache_len, jnp.int32)
    phys = identity_page_table(B, PT)
    log = jnp.broadcast_to(jnp.arange(P, dtype=jnp.int32)[None], (B, P))

    kq, ksc = quantize_fp8(kf)
    vq, vsc = quantize_fp8(vf)
    # pools of one layer, attended at layer 0
    kv8, sc8 = jnp.stack([kq, vq])[None], jnp.stack([ksc, vsc])[None]
    kv16 = jnp.stack([kf, vf])[None].astype(jnp.bfloat16)

    rows = []
    for mode, co in MODES.items():
        kv, sc = (kv8, sc8) if co.opt_kv else (kv16, None)
        # jnp reference path (jit, XLA:CPU) — the schedule comparison
        fn = jax.jit(lambda q, kv, sc, cl, co=co: paged_decode_attention(
            q, kv, sc, 0, cl, coopt=co))
        out = fn(q, kv, sc, cl).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(20):
            out = fn(q, kv, sc, cl)
        out.block_until_ready()
        us = (time.perf_counter() - t0) / 20 * 1e6

        # kernel parity (interpret mode): Eq. 9 filtering arrives as -1
        # entries in the physical table when opt_pa is on
        if co.opt_pa:
            beyond = log * ps >= cl[:, None]
            kphys = jnp.where(beyond, -1, phys)
        else:
            kphys = phys
        kout = ops.paged_pool_decode(q, kv, sc, 0, cl, kphys, log,
                                     opt_kv=co.opt_kv, opt_gqa=co.opt_gqa)
        ksl = sc[0, 0] if sc is not None else None
        vsl = sc[0, 1] if sc is not None else None
        expected = ref.paged_pool_decode_ref(q, kv[0, 0], kv[0, 1], ksl, vsl,
                                             cl, phys, log, opt_kv=co.opt_kv)
        err = float(np.abs(np.asarray(kout, np.float32) -
                           np.asarray(expected, np.float32)).max())

        traffic = kernel_bytes_per_call(
            B, P, ps, Hkv, D, opt_kv=co.opt_kv, opt_pa=co.opt_pa,
            opt_gqa=co.opt_gqa, Hq=Hq, cache_len=cache_len)
        rows.append([mode, round(us, 1), traffic, f"{err:.4f}"])
        print(f"kernel_micro {mode:9s} jnp={us:9.1f}us/call  "
              f"hbm_traffic={traffic/1024:8.1f}KiB/call  kern_err={err:.4f}",
              flush=True)

    base = rows[0][2]
    print(f"kernel_micro traffic reduction original->coopt: "
          f"{100 * (1 - rows[-1][2] / base):.1f}%")
    rows += latent_rows(quick)
    path = write_csv("kernel_micro.csv",
                     ["mode", "jnp_us_per_call", "hbm_bytes_per_call",
                      "kernel_max_err"], rows)
    return path, rows


if __name__ == "__main__":
    run()
