"""Numerics that every block's plain reference and control share.

Everything is float32. The weights are exactly bfloat16 numbers (the
configurations' dtype), so a matrix product splits the float32 activation
into three bfloat16 parts and sums their exact products in float32: the
float32 product, without a float32 copy of the weights. The control's
roundings take float8 e4m3, one step below bfloat16.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


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
