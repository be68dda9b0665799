"""Opt-KV write-path Pallas kernel (paper §3.1 Alg. 1 Phase 1 + Eq. 5),
scattering into the GLOBAL paged-KV pool.

Scatters new tokens' K/V into the shared pool with (a) SkipSet filtering —
tokens whose slot is negative are never written ("skip caching of K_i,
V_i"; padding, prefix-cache hits), and (b) fused FP8 e4m3 quantization:
amax-per-(token, head) scale computed in VREGs, quantized row written in
the same pass, so the unquantized K/V never round-trip to HBM.

Mechanics: the pool is head-major within a page, ``(2, P, Hkv, ps, D)``,
so one token's line is a row of every head's (ps, D) tile — a block Mosaic
can only move whole. The grid walks the B*S new tokens sorted by slot
(stable, so a later write of the same slot still wins); the block at each
step is the WHOLE page (k and v, all heads) that holds the token, named by
the scalar-prefetched page index. The first step of a run of tokens in the
same page copies the page in; each step then replaces its token's row in
VMEM with a select on the row index; the page is written back once, when
the run ends (Pallas writes an output block back when its block index
changes). Sorting makes each page one run, so no page is read back after
it was written. SkipSet tokens sort last and keep the last page; they write
nothing. The pool is passed aliased (donated), so pages no token touches
keep their contents.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.cache.quant import FP8_MAX


def _write_kernel(page_ref, off_ref, tok_ref, new_ref, kv_in, sc_in,
                  kv_ref, sc_ref, *, opt_kv: bool):
    # new_ref: (2, 1, Hkv, D) — one token's k and v, all kv heads;
    # kv_ref: (2, 1, Hkv, ps, D) — the page holding it; sc_ref (2, 1, Hkv, ps)
    i = pl.program_id(0)

    @pl.when((i == 0) | (page_ref[jnp.maximum(i - 1, 0)] != page_ref[i]))
    def _load():                    # first token of a run: start from HBM
        kv_ref[...] = kv_in[...]
        sc_ref[...] = sc_in[...]

    off = off_ref[i]

    @pl.when(off >= 0)
    def _write():
        _, _, Hkv, ps, D = kv_ref.shape
        row = jax.lax.broadcasted_iota(jnp.int32, (Hkv, ps, D), 1) == off
        col = jax.lax.broadcasted_iota(jnp.int32, (Hkv, ps), 1) == off
        for c in range(2):                              # k, then v
            x = new_ref[c, 0].astype(jnp.float32)       # (Hkv, D)
            if opt_kv:
                amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
                scale = jnp.maximum(amax, 1e-12) / FP8_MAX     # (Hkv, 1)
                x = x / scale
            else:
                scale = jnp.zeros((Hkv, 1), jnp.float32)
            page = kv_ref[c, 0].astype(jnp.float32)      # (Hkv, ps, D)
            kv_ref[c, 0] = jnp.where(
                row, jnp.broadcast_to(x[:, None, :], (Hkv, ps, D)),
                page).astype(kv_ref.dtype)
            sc_ref[c, 0] = jnp.where(
                col, jnp.broadcast_to(scale, (Hkv, ps)), sc_ref[c, 0])


def kv_cache_write(k_new, v_new, slot_idx, kv_pages, kv_scale, *,
                   opt_kv: bool, interpret: bool = False):
    """k/v_new: (B, S, Hkv, D); slot_idx: (B, S) int32 GLOBAL flat slots
    (page * ps + offset; negative => SkipSet, not written); kv_pages:
    (2, P, Hkv, ps, D) one layer's pool [fp8 if opt_kv]; kv_scale:
    (2, P, Hkv, ps) f32 (zeros ok if !opt_kv). Returns the updated
    (kv_pages, kv_scale)."""
    B, S, Hkv, D = k_new.shape
    _, P, _, ps, _ = kv_pages.shape
    N = B * S
    slots = slot_idx.reshape(N).astype(jnp.int32)
    tok = jnp.argsort(jnp.where(slots >= 0, slots, jnp.iinfo(jnp.int32).max),
                      stable=True).astype(jnp.int32)
    slots = slots[tok]
    # pages ascend along the sorted slots; the trailing SkipSet tokens keep
    # the last page (page 0 when nothing is written)
    page = jax.lax.cummax(jnp.maximum(slots, 0) // ps)
    off = jnp.where(slots >= 0, slots % ps, -1)
    new = jnp.stack([k_new, v_new]).reshape(2, N, Hkv, D)

    # no -1 reaches these maps: pages are clamped to >= 0 above and ``tok``
    # is a permutation of the token indices
    kv_blk = pl.BlockSpec(
        (2, 1, Hkv, ps, D),
        lambda i, pg, of, tk: (0, pg[i], 0, 0, 0))  # coopt: allow[COOPT005]
    sc_blk = pl.BlockSpec(
        (2, 1, Hkv, ps),
        lambda i, pg, of, tk: (0, pg[i], 0, 0))  # coopt: allow[COOPT005]
    kern = functools.partial(_write_kernel, opt_kv=opt_kv)
    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(N,),
            in_specs=[
                pl.BlockSpec(
                    (2, 1, Hkv, D),
                    lambda i, pg, of, tk: (0, tk[i], 0, 0)),  # coopt: allow[COOPT005]
                kv_blk, sc_blk,
            ],
            out_specs=[kv_blk, sc_blk],
        ),
        out_shape=[jax.ShapeDtypeStruct(kv_pages.shape, kv_pages.dtype),
                   jax.ShapeDtypeStruct(kv_scale.shape, jnp.float32)],
        # aliased: pages no token touches keep their contents
        input_output_aliases={4: 0, 5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(page, off, tok, new, kv_pages, kv_scale)
