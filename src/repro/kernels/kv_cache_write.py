"""Opt-KV write-path Pallas kernel (paper §3.1 Alg. 1 Phase 1 + Eq. 5),
scattering into the GLOBAL paged-KV pool.

Scatters new tokens' K/V into the shared pool with (a) SkipSet filtering —
tokens whose slot is negative are never written ("skip caching of K_i,
V_i"; padding, prefix-cache hits), and (b) fused FP8 e4m3 quantization:
amax-per-(token, head) scale computed in VREGs, quantized row written in
the same pass, so the unquantized K/V never round-trip to HBM.

Mechanics: the pool holds every layer, ``(L, 2, P, Hkv, ps, D)``, head-major
within a page, so one token's line is a row of every head's (ps, D) tile — a
block Mosaic can only move whole. A write names LINES of the whole pool,
``(layer * P + page) * ps + offset``, so the layer rides in the line and the
kernel needs no layer argument. The grid walks the B*S new tokens sorted by
line (stable, so a later write of the same line still wins); the block at
each step is the WHOLE page (k and v, all heads) that holds the token, named
by the scalar-prefetched page index of the whole pool (``layer * P + page``,
split into layer and page in the index_map). The first step of a run of
tokens in the same page copies the page in; each step then replaces its
token's row in VMEM with a select on the row index; the page is written back
once, when the run ends (Pallas writes an output block back when its block
index changes). Sorting makes each page one run, so no page is read back
after it was written. SkipSet tokens sort last and keep the last page; they
write nothing. The whole pool is passed aliased (donated), so the write
updates it in place and pages no token touches keep their contents.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.cache.quant import FP8_MAX


def _write_kernel(page_ref, off_ref, tok_ref, new_ref, kv_in, *refs,
                  opt_kv: bool):
    # new_ref: (2, 1, Hkv, D) — one token's k and v, all kv heads;
    # kv_ref: (2, 1, Hkv, ps, D) — the page holding it; sc_ref (2, 1, Hkv, ps)
    # — its scales, which come only under Opt-KV
    sc_in, kv_ref, sc_ref = refs if opt_kv else (None, *refs, None)
    i = pl.program_id(0)

    @pl.when((i == 0) | (page_ref[jnp.maximum(i - 1, 0)] != page_ref[i]))
    def _load():                    # first token of a run: start from HBM
        kv_ref[...] = kv_in[...]
        if opt_kv:
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
                sc_ref[c, 0] = jnp.where(
                    col, jnp.broadcast_to(scale, (Hkv, ps)), sc_ref[c, 0])
            page = kv_ref[c, 0].astype(jnp.float32)      # (Hkv, ps, D)
            kv_ref[c, 0] = jnp.where(
                row, jnp.broadcast_to(x[:, None, :], (Hkv, ps, D)),
                page).astype(kv_ref.dtype)


def kv_cache_write(k_new, v_new, line_idx, kv_pages, kv_scale, *,
                   opt_kv: bool, interpret: bool = False):
    """k/v_new: (B, S, Hkv, D); line_idx: (B, S) int32 lines of the whole
    pool, ``(layer * P + page) * ps + offset`` (negative => SkipSet, not
    written); kv_pages: (L, 2, P, Hkv, ps, D) the pool of every layer [fp8
    if opt_kv]; kv_scale: (L, 2, P, Hkv, ps) f32 under opt_kv, else None.
    Returns the updated (kv_pages, kv_scale), written in place."""
    B, S, Hkv, D = k_new.shape
    _, _, P, _, ps, _ = kv_pages.shape
    N = B * S
    lines = line_idx.reshape(N).astype(jnp.int32)
    tok = jnp.argsort(jnp.where(lines >= 0, lines, jnp.iinfo(jnp.int32).max),
                      stable=True).astype(jnp.int32)
    lines = lines[tok]
    # pages of the whole pool (layer * P + page) ascend along the sorted
    # lines; the trailing SkipSet tokens keep the last page (page 0 of layer
    # 0 when nothing is written)
    page = jax.lax.cummax(jnp.maximum(lines, 0) // ps)
    off = jnp.where(lines >= 0, lines % ps, -1)
    new = jnp.stack([k_new, v_new]).reshape(2, N, Hkv, D)

    # no -1 reaches these maps: pages are clamped to >= 0 above and ``tok``
    # is a permutation of the token indices
    kv_blk = pl.BlockSpec(
        (None, 2, 1, Hkv, ps, D),
        lambda i, pg, of, tk: (pg[i] // P, 0, pg[i] % P, 0, 0, 0))  # coopt: allow[COOPT005]
    sc_blk = pl.BlockSpec(
        (None, 2, 1, Hkv, ps),
        lambda i, pg, of, tk: (pg[i] // P, 0, pg[i] % P, 0, 0))  # coopt: allow[COOPT005]
    in_specs = [
        pl.BlockSpec(
            (2, 1, Hkv, D),
            lambda i, pg, of, tk: (0, tk[i], 0, 0)),  # coopt: allow[COOPT005]
        kv_blk]
    out_specs = [kv_blk]
    operands = [new, kv_pages]
    out_shape = [jax.ShapeDtypeStruct(kv_pages.shape, kv_pages.dtype)]
    if opt_kv:
        in_specs += [sc_blk]
        out_specs += [sc_blk]
        operands += [kv_scale]
        out_shape += [jax.ShapeDtypeStruct(kv_scale.shape, jnp.float32)]
    kern = functools.partial(_write_kernel, opt_kv=opt_kv)
    res = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(N,),
            in_specs=in_specs,
            out_specs=out_specs,
        ),
        out_shape=out_shape,
        # aliased: the pool is updated in place, and pages no token touches
        # keep their contents
        input_output_aliases={4: 0, 5: 1} if opt_kv else {4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(page, off, tok, *operands)
    return res[0], (res[1] if opt_kv else None)
