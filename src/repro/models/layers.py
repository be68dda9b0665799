"""Shared layer primitives + parameter-spec / sharding utilities.

Parameters are declared once as a pytree of ``Spec`` (shape, logical axes,
init); ``init_tree`` materialises arrays and ``make_pspecs`` maps logical axes
to mesh axes (dropping any axis whose dim is not divisible by the mesh axis —
this transparently handles e.g. whisper's vocab 51865 % 16 != 0 or MQA kv=1).

Logical weight axes (DESIGN.md §7):
  "d_in"  -> "data"   (ZeRO-3-ish input-dim shard)
  "d_out" -> "model"  (tensor-parallel output/ffn/head shard)
  "vocab" -> "data"   (embedding rows)
anything else (e.g. "layers" for scan-stacked params) -> unsharded.
"""
from __future__ import annotations

import contextlib
import math
from contextvars import ContextVar
from typing import Any, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS

DEFAULT_RULES = {"d_in": "data", "d_out": "model", "vocab": "data",
                 "experts": "data",
                 # expert d_model keeps ZeRO sharding even in decode-only
                 # weight rules (experts dominate MoE bytes; §Perf P3.2)
                 "moe_d_in": "data"}


class Spec(NamedTuple):
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    init: str = "normal"          # "normal" | "zeros" | "ones" | "embed" | "uniform1"
    dtype: Any = jnp.bfloat16
    scale: Optional[float] = None  # stddev override for "normal"


def _init_one(key, s: Spec):
    if s.init == "zeros":
        return jnp.zeros(s.shape, s.dtype)
    if s.init == "ones":
        return jnp.ones(s.shape, s.dtype)
    if s.init == "uniform1":  # uniform in [0, 1): RWKV mix coefficients
        return jax.random.uniform(key, s.shape, jnp.float32).astype(s.dtype)
    if s.init == "embed":
        std = s.scale if s.scale is not None else 0.02
        return (jax.random.normal(key, s.shape, jnp.float32) * std).astype(s.dtype)
    # fan-in scaled normal
    fan_in = s.shape[-2] if len(s.shape) >= 2 else s.shape[-1]
    std = s.scale if s.scale is not None else 1.0 / math.sqrt(max(fan_in, 1))
    return (jax.random.normal(key, s.shape, jnp.float32) * std).astype(s.dtype)


def init_tree(key, spec_tree):
    leaves, treedef = jax.tree.flatten(spec_tree, is_leaf=lambda x: isinstance(x, Spec))
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(treedef, [_init_one(k, s) for k, s in zip(keys, leaves)])


def shapes_tree(spec_tree):
    """ShapeDtypeStruct tree (dry-run param stand-ins, no allocation)."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype),
        spec_tree, is_leaf=lambda x: isinstance(x, Spec))


def spec_pspec(s: Spec, mesh: Mesh, rules=None) -> PS:
    """Logical axes -> PartitionSpec. Rule values may be one mesh axis or a
    tuple of them (e.g. MoE expert ff -> ("data", "model") for 256-way
    sharding); non-divisible or already-used mesh axes are dropped
    per-tensor."""
    rules = rules or DEFAULT_RULES
    entries, used = [], set()
    for dim, ax in zip(s.shape, s.axes):
        m = rules.get(ax) if ax else None
        if m is None:
            entries.append(None)
            continue
        ms = (m,) if isinstance(m, str) else tuple(m)
        ms = tuple(a for a in ms if a in mesh.shape and a not in used)
        size = math.prod(mesh.shape[a] for a in ms) if ms else 1
        if ms and dim % size == 0:
            entries.append(ms if len(ms) > 1 else ms[0])
            used.update(ms)
        else:
            entries.append(None)
    return PS(*entries)


def make_pspecs(spec_tree, mesh: Mesh, rules=None):
    return jax.tree.map(lambda s: spec_pspec(s, mesh, rules),
                        spec_tree, is_leaf=lambda x: isinstance(x, Spec))


def make_shardings(spec_tree, mesh: Mesh, rules=None):
    return jax.tree.map(lambda s: NamedSharding(mesh, spec_pspec(s, mesh, rules)),
                        spec_tree, is_leaf=lambda x: isinstance(x, Spec))


def param_bytes(spec_tree) -> int:
    leaves = jax.tree.leaves(spec_tree, is_leaf=lambda x: isinstance(x, Spec))
    return sum(math.prod(s.shape) * jnp.dtype(s.dtype).itemsize for s in leaves)


def param_count(spec_tree) -> int:
    leaves = jax.tree.leaves(spec_tree, is_leaf=lambda x: isinstance(x, Spec))
    return sum(math.prod(s.shape) for s in leaves)


# ----------------------------------------------------------------------------
# Activation sharding constraints (no-op outside a mesh context).
# ----------------------------------------------------------------------------
_ACT_CTX: ContextVar[Optional[Tuple[Mesh, dict]]] = ContextVar("act_ctx", default=None)


@contextlib.contextmanager
def activation_sharding(mesh: Mesh, rules: dict):
    """rules: logical activation axis -> mesh axis (or tuple of mesh axes)."""
    tok = _ACT_CTX.set((mesh, rules))
    try:
        yield
    finally:
        _ACT_CTX.reset(tok)


def act_mesh_axes(axis: str, size: int):
    """(mesh, mesh axes) that the logical activation ``axis`` maps to under
    the active ``activation_sharding`` context, when the axes exist and
    divide ``size``; None outside a context or when nothing shards it."""
    ctx = _ACT_CTX.get()
    if ctx is None:
        return None
    mesh, rules = ctx
    m = rules.get(axis)
    ms = (m,) if isinstance(m, str) else tuple(m or ())
    ms = tuple(a for a in ms if a in mesh.shape)
    n = math.prod(mesh.shape[a] for a in ms)
    if n <= 1 or size % n:
        return None
    return mesh, ms


def shard_act(x: jax.Array, axes: Sequence[Optional[str]]) -> jax.Array:
    ctx = _ACT_CTX.get()
    if ctx is None:
        return x
    mesh, rules = ctx
    entries, used = [], set()
    for dim, ax in zip(x.shape, axes):
        m = rules.get(ax) if ax else None
        if m is None:
            entries.append(None)
            continue
        ms = (m,) if isinstance(m, str) else tuple(m)
        ms = tuple(a for a in ms if a in mesh.shape and a not in used)
        size = math.prod(mesh.shape[a] for a in ms) if ms else 1
        if ms and dim % size == 0:
            entries.append(ms if len(ms) > 1 else ms[0])
            used.update(ms)
        else:
            entries.append(None)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, PS(*entries)))


def no_counters() -> jax.Array:
    """The (2,) f32 expert counters a step program returns beside its
    logits and cache, for a step without routed experts."""
    return jnp.zeros(2, jnp.float32)


# ----------------------------------------------------------------------------
# Norms / activations / projections
# ----------------------------------------------------------------------------
def rmsnorm(x, scale, eps=1e-5):
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * scale.astype(jnp.float32)).astype(x.dtype)


def layernorm(x, scale, bias, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)


def linear(x, w, b=None):
    y = jnp.einsum("...i,io->...o", x, w)
    if b is not None:
        y = y + b.astype(y.dtype)
    return y


def swiglu(x, wg, wu, wd):
    h = jax.nn.silu(linear(x, wg)) * linear(x, wu)
    return linear(h, wd)


def gelu_mlp(x, w1, b1, w2, b2):
    return linear(jax.nn.gelu(linear(x, w1, b1)), w2, b2)


# ----------------------------------------------------------------------------
# RoPE
# ----------------------------------------------------------------------------
def yarn_get_mscale(scale: float, mscale: float = 1.0) -> float:
    """YaRN's attention-temperature factor for a context stretched by
    ``scale`` (1 when nothing is stretched)."""
    return 0.1 * mscale * math.log(scale) + 1.0 if scale > 1 else 1.0


def yarn_inv_freq(dim: int, base: float, scaling: dict) -> np.ndarray:
    """YaRN's rotary frequencies (DeepSeek-V2's
    ``DeepseekV2YarnRotaryEmbedding``): per pair j of ``dim``, a blend of
    the extrapolated ``base^(-2j/dim)`` and the interpolated one divided by
    ``factor``, ramped linearly between the correction dims that
    ``beta_fast`` and ``beta_slow`` rotations over the original context
    give."""
    factor = float(scaling["factor"])
    orig = float(scaling["original_max_position_embeddings"])

    def correction_dim(rotations):
        return (dim * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(scaling.get("beta_fast", 32))), 0)
    high = min(math.ceil(correction_dim(scaling.get("beta_slow", 1))),
               dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return (extra / factor * ramp + extra * (1.0 - ramp)).astype(np.float32)


def rope_angles(positions, head_dim, theta, scaling=()):
    """positions (...,) -> cos/sin (..., head_dim/2). ``scaling``: the
    config's ``rope_scaling`` pairs; () is plain rope, ``type`` "yarn" is
    YaRN (``yarn_inv_freq``, cos and sin times the ratio of the two
    mscales)."""
    half = head_dim // 2
    if not scaling:
        freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32)
                                 * 2.0 / head_dim))
        ang = positions.astype(jnp.float32)[..., None] * freqs
        return jnp.cos(ang), jnp.sin(ang)
    sc = dict(scaling)
    if sc.get("type", sc.get("rope_type")) != "yarn":
        raise ValueError(f"rope scaling {sc} is not supported (only yarn)")
    freqs = jnp.asarray(yarn_inv_freq(head_dim, theta, sc))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    m = (yarn_get_mscale(sc["factor"], sc.get("mscale", 1.0))
         / yarn_get_mscale(sc["factor"], sc.get("mscale_all_dim", 0.0)))
    return jnp.cos(ang) * m, jnp.sin(ang) * m


def apply_rope(x, positions, theta, scaling=()):
    """x: (..., S, H, D); positions: (..., S) broadcastable."""
    d = x.shape[-1]
    cos, sin = rope_angles(positions, d, theta, scaling)   # (..., S, d/2)
    cos = cos[..., None, :]                        # (..., S, 1, d/2)
    sin = sin[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ----------------------------------------------------------------------------
# Full-sequence (train / prefill) grouped-query attention, q-chunked so the
# (S x S) score matrix is never materialised — the Opt-Pa "segment long
# sequences into manageable chunks" strategy applied to prefill.
# ----------------------------------------------------------------------------
def causal_attention(q, k, v, *, window: int = 0, chunk_q: int = 256,
                     causal: bool = True, q_offset=0, scale=None):
    """q: (B,S,Hq,D)  k,v: (B,T,Hkv,D)  -> (B,S,Hq,D).

    Grouped (Opt-GQA Eq. 7/8): q heads are folded to (Hkv, G) and share each
    KV head. ``window>0`` = sliding-window (mixtral/griffin local attention).
    ``q_offset``: absolute position of q[0] (prefill continuation).
    ``scale``: the score scale (default 1/sqrt(D)).
    """
    B, S, Hq, D = q.shape
    T, Hkv, Dv = k.shape[1], k.shape[2], v.shape[3]
    G = Hq // Hkv
    qg = q.reshape(B, S, Hkv, G, D)
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    kpos = jnp.arange(T)

    nchunks = max(S // chunk_q, 1)
    cq = S // nchunks if S % nchunks == 0 else S  # fall back to single chunk
    nchunks = S // cq

    def one_chunk(ci):
        qs = jax.lax.dynamic_slice_in_dim(qg, ci * cq, cq, axis=1)
        qpos = q_offset + ci * cq + jnp.arange(cq)
        s = jnp.einsum("bqhgd,bthd->bhgqt", qs, k,
                       preferred_element_type=jnp.float32) * scale
        mask = jnp.ones((cq, T), bool)
        if causal:
            mask &= qpos[:, None] >= kpos[None, :]
        if window:
            mask &= qpos[:, None] - kpos[None, :] < window
        s = jnp.where(mask[None, None, None], s, -jnp.inf)
        # Eq. 8: max-subtracted softmax
        m = jnp.max(s, axis=-1, keepdims=True)
        m = jnp.maximum(m, -1e30)  # rows that are fully masked
        p = jnp.exp(s - m)
        p = p / jnp.sum(p, axis=-1, keepdims=True)
        o = jnp.einsum("bhgqt,bthd->bqhgd", p.astype(v.dtype), v)
        return o.reshape(B, cq, Hq, Dv)

    if nchunks == 1:
        return one_chunk(0)
    outs = jax.lax.map(one_chunk, jnp.arange(nchunks))       # (N,B,cq,Hq,Dv)
    return jnp.moveaxis(outs, 0, 1).reshape(B, S, Hq, Dv)


def repeat_kv(x, repeats: int):
    """Original-mode (non-Opt-GQA) path: materialise duplicated KV heads."""
    if repeats == 1:
        return x
    B, T, Hkv, D = x.shape
    return jnp.broadcast_to(x[:, :, :, None], (B, T, Hkv, repeats, D)
                            ).reshape(B, T, Hkv * repeats, D)
