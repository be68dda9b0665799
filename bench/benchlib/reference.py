"""Plain float32 reference of a dense GQA decoder (Qwen2/Qwen3 layout), and
the control that stands in for a lower-precision program.

The published block: pre-norm RMSNorm; q, k, v projections (with bias when
``attention_bias``); per-head RMSNorm of q and k when ``qk_norm``; rotary
embedding on the two halves of each head (rotate-half); causal attention
with each K/V head shared by its group of query heads; output projection;
residual; RMSNorm; SiLU-gated feed-forward; residual. Final RMSNorm, then
the output head. The configuration states a K/V cache in float8 e4m3 with
one float32 scale per (token, head) of absmax / 448, so the reference
reads K and V through that rounding too.

Everything is float32. The weights are exactly bfloat16 numbers (the
configuration's dtype), so a matrix product splits the float32 activation
into three bfloat16 parts and sums their exact products in float32: the
float32 product, without a float32 copy of the weights. Attention runs at
``Precision.HIGHEST``. It imports nothing of the program; it reads weights
by name from the benchmark's own generator.

The control computes every matrix product in float8 e4m3, one step below
the configuration's bfloat16: weights per output channel and activations
per token (not the embedding lookup), accumulated in float32. That is the
format a later change would be tempted by.
"""
from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0
Q_BLOCK = 256          # query rows per attention block
POS_BLOCK = 256        # positions per block of output-head logits


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


def mm(x, w):
    """float32 ``x`` times bfloat16-exact ``w`` (contracting x's last axis
    with w's first), exactly rounded products summed in float32."""
    hi = x.astype(jnp.bfloat16)
    r = x - hi.astype(jnp.float32)
    mid = r.astype(jnp.bfloat16)
    lo = (r - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    w = w.astype(jnp.bfloat16)
    dot = partial(jnp.tensordot, axes=((x.ndim - 1,), (0,)),
                  preferred_element_type=jnp.float32)
    return dot(hi, w) + dot(mid, w) + dot(lo, w)


def rmsnorm(x, w, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * w.astype(jnp.float32)


def rope(x, pos, theta):
    """x (S, H, D), pos (S,): rotate-half rotary embedding."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32)
                             * 2.0 / x.shape[-1]))
    ang = pos.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def fp8_kv(x):
    """The cache's rounding: float8 e4m3 with one scale per (token, head)."""
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True),
                        1e-12) / FP8_MAX
    return (x / scale).astype(FP8).astype(jnp.float32) * scale


def fp8_rows(x):
    """Control: float8 e4m3 with one scale per row (token) of ``x``."""
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True),
                        1e-12) / FP8_MAX
    return (x / scale).astype(FP8).astype(jnp.float32) * scale


def fp8_weight(w):
    """Control: float8 e4m3 per output channel, held as bfloat16."""
    w = w.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=-2, keepdims=True),
                        1e-12) / FP8_MAX
    return ((w / scale).astype(FP8).astype(jnp.float32) * scale
            ).astype(jnp.bfloat16)


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
    """
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
