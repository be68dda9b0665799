"""Blockwise causal GQA flash-attention Pallas kernel — the Opt-Pa strategy
("first segment long sequences into manageable chunks, then apply lazy ...
computation", paper §3.3) applied to the prefill phase.

Queries arrive grouped (Opt-GQA): rows are (seq, group) pairs for one KV head,
so each KV tile is streamed once per group of G query heads. The online
softmax across KV blocks is the same Eq. 10 block-wise reduction as decode.
Causal skipping: KV blocks entirely in the future of a query block are
predicated off; with a sliding window, KV blocks entirely before the window
are skipped too — Eq. 9's valid-block filter in both directions.

Tiles: q (block_q rows, D lanes), kv (block_k, D). block_q rows span
block_q // G sequence positions; both default to 128/256 (MXU-aligned).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_NEG = -1e30


def _prefill_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                    *, block_q: int, block_k: int, G: int, window: int,
                    num_kv_blocks: int, q_offset: int):
    qb = pl.program_id(2)
    kb = pl.program_id(3)
    D = q_ref.shape[-1]

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # query rows r = s*G + g  ->  seq position s = r // G
    row0 = qb * block_q
    q_first = q_offset + row0 // G                     # first seq pos in tile
    q_last = q_offset + (row0 + block_q - 1) // G
    k0 = kb * block_k
    live = k0 <= q_last                                 # some key <= some query
    if window:
        live = jnp.logical_and(live, k0 + block_k - 1 >= q_first - window + 1)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)             # (block_q, D)
        k = k_ref[0, 0].astype(jnp.float32)             # (block_k, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * (1.0 / math.sqrt(D))
        rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        spos = q_offset + rows // G
        kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = spos >= kpos
        if window:
            mask &= (spos - kpos) < window
        s = jnp.where(mask, s, _NEG)
        m_prev = m_ref[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_ref[:, 0:1] * corr + jnp.sum(p, -1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(kb == num_kv_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, 0:1], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_prefill(q, k, v, *, window: int = 0, block_q: int = 256,
                  block_k: int = 256, q_offset: int = 0,
                  interpret: bool = False):
    """q: (B, S, Hq, D); k, v: (B, T, Hkv, D). Causal (optionally windowed)
    grouped-query flash attention. Returns (B, S, Hq, D) in q.dtype."""
    B, S, Hq, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    R = S * G                                           # grouped query rows
    # rows of one seq position must stay in one tile => block_q % G == 0
    bq = min(block_q, R)
    while R % bq or bq % G:
        bq -= 1
    bk = min(block_k, T)
    while T % bk:
        bk //= 2
    bk = max(bk, 1)
    NQ, NK = R // bq, T // bk

    # (B,S,Hq,D) -> (B,Hkv,S*G,D): row r = s*G + g
    qf = q.reshape(B, S, Hkv, G, D).transpose(0, 2, 1, 3, 4) \
          .reshape(B, Hkv, R, D)
    kf = k.transpose(0, 2, 1, 3)                        # (B,Hkv,T,D)
    vf = v.transpose(0, 2, 1, 3)

    kern = functools.partial(_prefill_kernel, block_q=bq, block_k=bk, G=G,
                             window=window, num_kv_blocks=NK,
                             q_offset=q_offset)
    out = pl.pallas_call(
        kern,
        grid=(B, Hkv, NQ, NK),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        out_shape=jax.ShapeDtypeStruct((B, Hkv, R, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, Hkv, S, G, D).transpose(0, 2, 1, 3, 4) \
              .reshape(B, S, Hq, D)
