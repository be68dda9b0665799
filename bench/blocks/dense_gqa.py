"""The dense GQA decoder block (Qwen2/Qwen3 layout): what the harness
needs to know of its shape. The program's keys for its sizes, the leaves
the weight generator perturbs, the plain float32 reference with its
control, and the useful work its steps need.

The published block: pre-norm RMSNorm; q, k, v projections (with bias when
``attention_bias``); per-head RMSNorm of q and k when ``qk_norm``; rotary
embedding on the two halves of each head (rotate-half); causal attention
with each K/V head shared by its group of query heads; output projection;
residual; RMSNorm; SiLU-gated feed-forward; residual. Final RMSNorm, then
the output head. The configuration states a K/V cache in float8 e4m3 with
one float32 scale per (token, head) of absmax / 448, so the reference
reads K and V through that rounding too.

The reference is float32 with exact products against the bfloat16 weights
(``benchlib.numerics.mm``); attention runs at ``Precision.HIGHEST``. It
imports nothing of the program; it reads weights by path from the
benchmark's own generator (``benchlib.model.weights_by_path``).

The control computes every matrix product in float8 e4m3, one step below
the configuration's bfloat16: weights per output channel and activations
per token (not the embedding lookup), accumulated in float32. That is the
format a later change would be tempted by.

The work counts are per token, or per call of one layer's attention
kernel, as stated; padded slots never enter (``benchlib.work``).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from benchlib.numerics import (FP8, FP8_MAX, HI, fp8_rows, fp8_weight, mm,
                               rmsnorm, rope)
from benchlib.work import kernel_bytes_per_call

# published config key -> the program's ModelConfig field
PROGRAM_KEYS = {
    "hidden_size": "d_model",
    "intermediate_size": "d_ff",
    "num_hidden_layers": "num_layers",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "vocab_size": "vocab_size",
    "rms_norm_eps": "norm_eps",
    "rope_theta": "rope_theta",
    "attention_bias": "qkv_bias",
    "qk_norm": "qk_norm",
}
REQUIRED = tuple(PROGRAM_KEYS)

# leaves the generator perturbs: norm scales and biases
NORMS = ("ln1", "ln2", "final_norm", "q_norm", "k_norm")
BIASES = ("bq", "bk", "bv")

Q_BLOCK = 256          # query rows per attention block
POS_BLOCK = 256        # positions per block of output-head logits


# ------------------------------------------------------------ reference --
class Arch(NamedTuple):
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    eps: float
    theta: float
    qk_norm: bool
    bias: bool

    @classmethod
    def from_config(cls, cfg: dict) -> "Arch":
        return cls(cfg["num_hidden_layers"], cfg["num_attention_heads"],
                   cfg["num_key_value_heads"], cfg["head_dim"],
                   float(cfg["rms_norm_eps"]), float(cfg["rope_theta"]),
                   bool(cfg["qk_norm"]), bool(cfg["attention_bias"]))


def fp8_kv(x):
    """The cache's rounding: float8 e4m3 with one scale per (token, head)."""
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True),
                        1e-12) / FP8_MAX
    return (x / scale).astype(FP8).astype(jnp.float32) * scale


def attention(q, k, v):
    """Causal GQA: q (S, Hq, D), k/v (S, Hkv, D) -> (S, Hq, D)."""
    S, Hq, D = q.shape
    Hkv = k.shape[1]
    qg = q.reshape(S // Q_BLOCK, Q_BLOCK, Hkv, Hq // Hkv, D)
    kpos = jnp.arange(S)

    def block(args):
        i, qb = args
        s = jnp.einsum("qhgd,khd->hgqk", qb, k, precision=HI) / math.sqrt(D)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", p, v, precision=HI)

    o = jax.lax.map(block, (jnp.arange(S // Q_BLOCK), qg))
    return o.reshape(S, Hq, D)


LAYER_WEIGHTS = ("wq", "wk", "wv", "wo", "wg", "wu", "wd")


def by_leaf_name(W):
    """The weights by leaf name: a dense model has one segment of stacked
    layers beside its embedding, final norm and head."""
    segments = {k.split("/")[1] for k in W if k.startswith("segments/")}
    if segments != {"0"}:
        raise ValueError(f"a dense model has one segment, not {segments}")
    return {k.rsplit("/", 1)[-1]: x for k, x in W.items()}


def hidden(W, tokens, arch: Arch, control: bool):
    """Final-normed hidden states (S, d) of a causal pass over tokens."""
    S = tokens.shape[0]
    pos = jnp.arange(S)
    h = W["embed"][tokens].astype(jnp.float32)
    names = [n for n in ("ln1", "ln2", "q_norm", "k_norm", "bq", "bk", "bv")
             + LAYER_WEIGHTS if n in W]

    mm_ = (lambda x, w: mm(fp8_rows(x), w)) if control else mm

    def layer(h, lw):
        if control:
            lw = dict(lw, **{n: fp8_weight(lw[n]) for n in LAYER_WEIGHTS})
        H, Hkv, D = arch.heads, arch.kv_heads, arch.head_dim
        x = rmsnorm(h, lw["ln1"], arch.eps)
        q, k, v = mm_(x, lw["wq"]), mm_(x, lw["wk"]), mm_(x, lw["wv"])
        if arch.bias:
            q, k, v = (q + lw["bq"].astype(jnp.float32),
                       k + lw["bk"].astype(jnp.float32),
                       v + lw["bv"].astype(jnp.float32))
        q, k, v = (q.reshape(S, H, D), k.reshape(S, Hkv, D),
                   v.reshape(S, Hkv, D))
        if arch.qk_norm:
            q = rmsnorm(q, lw["q_norm"], arch.eps)
            k = rmsnorm(k, lw["k_norm"], arch.eps)
        q, k = rope(q, pos, arch.theta), rope(k, pos, arch.theta)
        o = attention(q, fp8_kv(k), fp8_kv(v)).reshape(S, H * D)
        h = h + mm_(o, lw["wo"])
        x = rmsnorm(h, lw["ln2"], arch.eps)
        f = jax.nn.silu(mm_(x, lw["wg"])) * mm_(x, lw["wu"])
        return h + mm_(f, lw["wd"]), None

    h, _ = jax.lax.scan(layer, h, {n: W[n] for n in names})
    return rmsnorm(h, W["final_norm"], arch.eps)


@partial(jax.jit, static_argnames=("arch", "control"))
def gaps(W, tokens, served, lo, hi, *, arch: Arch, control: bool = False):
    """Per position ``p`` of ``tokens`` (S,), for ``lo <= p < hi``: how far
    the reference's logit of ``served[p]`` (the token that followed ``p``)
    lies below its best logit (0 elsewhere). With ``control``, also how far
    the reference's logit of the control's top token lies below the best.
    ``W``: the weights by path (``benchlib.model.weights_by_path``).
    """
    W = by_leaf_name(W)
    head = W["lm_head"]
    if control:
        head_c = fp8_weight(head)
    h = hidden(W, tokens, arch, False)
    hc = hidden(W, tokens, arch, True) if control else h
    S = tokens.shape[0]
    nb = S // POS_BLOCK

    def block(args):
        hb, hcb, sb = args
        logits = mm(hb, head)                                # (P, V)
        best = jnp.max(logits, axis=-1)
        g = best - jnp.take_along_axis(logits, sb[:, None], -1)[:, 0]
        if not control:
            return g, g
        top = jnp.argmax(mm(fp8_rows(hcb), head_c), axis=-1)
        gc = best - jnp.take_along_axis(logits, top[:, None], -1)[:, 0]
        return g, gc

    rs = lambda x: x.reshape(nb, POS_BLOCK, *x.shape[1:])
    g, gc = jax.lax.map(block, (rs(h), rs(hc), rs(served)))
    p = jnp.arange(S)
    keep = (p >= lo) & (p < hi)
    return (jnp.where(keep, g.reshape(S), 0.0),
            jnp.where(keep, gc.reshape(S), 0.0))


# ----------------------------------------------------------------- work --
@dataclass(frozen=True)
class Dims:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    page_size: int = 64
    kv_bytes: int = 1            # fp8 K/V pool
    scale_bytes: int = 4         # one f32 scale per (token, head) of K and V

    @classmethod
    def from_config(cls, cfg: dict) -> "Dims":
        return cls(layers=cfg["num_hidden_layers"],
                   d_model=cfg["hidden_size"],
                   heads=cfg["num_attention_heads"],
                   kv_heads=cfg["num_key_value_heads"],
                   head_dim=cfg["head_dim"],
                   d_ff=cfg["intermediate_size"],
                   vocab=cfg["vocab_size"],
                   page_size=cfg["kv_page_size"])


def layer_matmul_flops(d: Dims) -> int:
    """Dense-layer FLOPs of one token in one layer: q, k, v and o
    projections and the gated feed-forward (2 FLOPs per multiply-add)."""
    qkvo = (d.d_model * d.heads * d.head_dim * 2
            + d.d_model * d.kv_heads * d.head_dim * 2)
    return 2 * (qkvo + 3 * d.d_model * d.d_ff)


def head_flops(d: Dims) -> int:
    """The output head for one sampled position."""
    return 2 * d.d_model * d.vocab


def attn_flops(d: Dims, ctx: int) -> int:
    """One query attending ``ctx`` keys in one layer: scores and the
    weighted sum of values."""
    return 4 * d.heads * d.head_dim * ctx


def chunk_attn_flops(d: Dims, start: int, n: int) -> int:
    """A chunk of ``n`` causal queries at positions ``start..start+n-1`` in
    one layer (query ``p`` attends ``p + 1`` keys)."""
    return 4 * d.heads * d.head_dim * (n * start + n * (n + 1) // 2)


def prompt_flops(d: Dims, start: int, n: int) -> int:
    """Prefilling prompt positions ``start..start+n-1`` through every
    layer, without the head."""
    return d.layers * (n * layer_matmul_flops(d)
                       + chunk_attn_flops(d, start, n))


def decode_flops(d: Dims, ctx: int) -> int:
    """One decoded token at context ``ctx`` (keys attended, itself
    included) through every layer and the head."""
    return d.layers * (layer_matmul_flops(d) + attn_flops(d, ctx)) \
        + head_flops(d)


def _pages(tokens: int, page_size: int) -> int:
    return -(-tokens // page_size)


def decode_attn_bytes(d: Dims, ctx: int) -> int:
    """One decoded token's attention in one layer: the live fp8 pages of K
    and V with their scales, read once for all grouped heads, and its
    bf16 query (the Opt-KV, Opt-Pa, Opt-GQA pool of the ``coopt`` mode)."""
    return kernel_bytes_per_call(1, _pages(ctx, d.page_size), d.page_size,
                                 d.kv_heads, d.head_dim, opt_kv=True,
                                 opt_pa=True, opt_gqa=True, Hq=d.heads,
                                 cache_len=ctx)


def chunk_attn_bytes(d: Dims, start: int, n: int) -> int:
    """A chunk of ``n`` queries at ``start..start+n-1`` in one layer: the
    live pages of K and V up to the chunk's end with their scales, read
    once, plus the bf16 queries read and outputs written."""
    pages = _pages(start + n, d.page_size)
    kv = 2 * pages * d.page_size * d.kv_heads * (d.head_dim * d.kv_bytes
                                                 + d.scale_bytes)
    return kv + 2 * n * d.heads * d.head_dim * 2
