"""Grouped SwiGLU experts as one Pallas kernel: the routed FFN of a
mixture-of-experts layer over a buffer of (token, expert) pairs sorted by
expert (``models/moe.py`` builds the buffer and the tile map).

Layout: ``xs (M, d)`` holds each expert's pairs from a row that is a
multiple of ``tm``, so every tile of ``tm`` rows belongs to one expert,
``tile_expert[i]``. Only the first ``num_tiles`` tiles carry pairs; the
rest of the buffer is padding that no caller reads back.

The weights are those of every layer of a segment, stacked (``wg``/``wu``
``(L, E, d, f)``, ``wd`` ``(L, E, f, d)``), and a ``layer`` scalar picks the
layer in the index maps, so no layer's experts are sliced out (a slice
feeding a kernel would be copied whole, every layer of every step).

Grid: one step per tile. The step DMAs the tile's rows and its expert's
three matrices whole (``wg``/``wu`` ``(d, f)``, ``wd`` ``(f, d)``) and
computes ``silu(x wg) * (x wu) @ wd`` with float32 accumulation. Tiles
past ``num_tiles`` name the last real tile's blocks, so they move no bytes
and compute nothing. Consecutive tiles of one expert keep its weights
resident, so an expert that is hit streams its weights once per step at
decode sizes (a tile of 16 rows holds all its pairs) and once per tile of
128 rows at prefill sizes. The weights are double-buffered: the next
expert's matrices stream while this one computes, which is what bounds a
decode step (the FLOPs are those of the pairs, the bytes those of the
experts hit).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# VMEM for two buffers of an expert's three matrices at DeepSeek-V2-Lite's
# widths (2 x 17.3 MB) plus a tile of rows in and out
VMEM_LIMIT = 64 * 1024 * 1024


def _kernel(tile_e_ref, ntiles_ref, layer_ref, x_ref, wg_ref, wu_ref, wd_ref,
            o_ref):
    del tile_e_ref, layer_ref           # read by the index maps only

    @pl.when(pl.program_id(0) < ntiles_ref[0])
    def _():
        x = x_ref[...]
        g = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
        u = jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
        h = (g / (1.0 + jnp.exp(-g)) * u).astype(wd_ref.dtype)
        o_ref[...] = jnp.dot(h, wd_ref[0], preferred_element_type=jnp.float32
                             ).astype(o_ref.dtype)


def moe_experts(xs, wg, wu, wd, layer, tile_expert, num_tiles, *, tm: int,
                interpret: bool = False):
    """xs (M, d) pairs sorted by expert, each expert's from a multiple of
    ``tm``; wg/wu (L, E, d, f), wd (L, E, f, d) and ``layer`` the int32
    layer to run; tile_expert (M // tm,) int32; num_tiles (1,) int32, the
    tiles that carry pairs. Returns (M, d) in ``xs.dtype``; rows of tiles
    past ``num_tiles`` are left unwritten."""
    M, d = xs.shape
    f = wg.shape[-1]
    if M % tm:
        raise ValueError(f"{M} rows do not split into tiles of {tm}")

    def tile(i, nt):
        # past the real tiles: stay on the last one (no DMA, no compute)
        return jnp.minimum(i, jnp.maximum(nt[0] - 1, 0))

    def rows(i, te, nt, lyr):
        return (tile(i, nt), 0)

    def expert(i, te, nt, lyr):
        return (lyr[0], jnp.maximum(te[tile(i, nt)], 0), 0, 0)

    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(M // tm,),
            in_specs=[pl.BlockSpec((tm, d), rows),
                      pl.BlockSpec((None, 1, d, f), expert),
                      pl.BlockSpec((None, 1, d, f), expert),
                      pl.BlockSpec((None, 1, f, d), expert)],
            out_specs=pl.BlockSpec((tm, d), rows),
        ),
        out_shape=jax.ShapeDtypeStruct((M, d), xs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(tile_expert.astype(jnp.int32), num_tiles.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), xs, wg, wu, wd)


@partial(jax.jit, static_argnames=("out_dtype",))
def moe_experts_ref(xs, wg, wu, wd, layer, group_sizes, out_dtype=None):
    """The same grouped SwiGLU in ``jax.lax.ragged_dot`` over layer
    ``layer`` of the stacked weights (the parity reference, and the path
    without kernels): ``group_sizes`` (E,) rows per expert, in order from
    row 0; rows past their sum come out zero. Returns ``out_dtype``
    (default ``xs.dtype``)."""
    wg, wu, wd = wg[layer], wu[layer], wd[layer]
    gs = group_sizes.astype(jnp.int32)
    g = jax.lax.ragged_dot(xs, wg, gs, preferred_element_type=jnp.float32)
    u = jax.lax.ragged_dot(xs, wu, gs, preferred_element_type=jnp.float32)
    h = (jax.nn.silu(g) * u).astype(wd.dtype)
    return jax.lax.ragged_dot(h, wd, gs, preferred_element_type=jnp.float32
                              ).astype(out_dtype or xs.dtype)
