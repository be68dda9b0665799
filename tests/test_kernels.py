"""Per-kernel allclose sweeps vs the pure-jnp oracles (ref.py), across
shapes, dtypes and mode flags — interpret=True on CPU. Layouts follow the
GLOBAL paged pool of every layer (no batch dim on kv pages, heads before
tokens within a page; lanes address the pool through scalar-prefetched
page tables, and the kernels pick the layer in place). Off the chip the
``ops`` wrappers hand the interpreter the one layer a kernel reads, so the
cases at a nonzero layer of a pool of several layers also call the kernel
on the whole pool, where its index_maps pick the layer, and check that the
result is bit-identical to the one-layer result."""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cache.quant import quantize_fp8
from repro.core.opt_kv import (identity_page_table, logical_to_physical,
                               pool_lines, window_page_table)
from repro.kernels import flash_chunk_prefill as fc
from repro.kernels import kv_cache_write as kw
from repro.kernels import ops, paged_gqa_decode as pd, ref

KEY = jax.random.PRNGKey(0)


# (layers in the pool, layer attended): one layer alone, and a nonzero
# layer of three
LAYERS = [(1, 0), (3, 2)]


def _pool_inputs(B, P, ps, Hkv, G, D, opt_kv, seed=0, L=1):
    """Pool of ``L`` layers of B*P pages each, lane-identity partitioned,
    every layer with contents of its own."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    Hq = Hkv * G
    PT = B * P
    q = jax.random.normal(ks[0], (B, Hq, D), jnp.float32).astype(jnp.bfloat16)
    k = jax.random.normal(ks[1], (L, PT, Hkv, ps, D), jnp.float32)
    v = jax.random.normal(ks[2], (L, PT, Hkv, ps, D), jnp.float32)
    phys = identity_page_table(B, PT)
    log = jnp.broadcast_to(jnp.arange(P, dtype=jnp.int32)[None], (B, P))
    if opt_kv:
        kq, ksc = quantize_fp8(k)
        vq, vsc = quantize_fp8(v)
        return (q, jnp.stack([kq, vq], 1), jnp.stack([ksc, vsc], 1), phys,
                log)
    return q, jnp.stack([k, v], 1).astype(jnp.bfloat16), None, phys, log


def _scales(sc, layer=0):
    return (sc[layer, 0], sc[layer, 1]) if sc is not None else (None, None)


def _others(pool, layer):
    """Every layer of ``pool`` but ``layer``, as float32."""
    return np.delete(np.asarray(pool, np.float32), layer, axis=0)


def _same(a, b):
    np.testing.assert_array_equal(np.asarray(a, np.float32),
                                  np.asarray(b, np.float32))


def _flat(pages):
    """One pool plane (P, Hkv, ps[, D]) -> flat token lines (P*ps, Hkv[, D])."""
    P, Hkv, ps = pages.shape[:3]
    return jnp.swapaxes(pages, 1, 2).reshape((P * ps, Hkv) + pages.shape[3:])


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("L,layer", LAYERS)
@pytest.mark.parametrize("opt_kv,opt_gqa",
                         list(itertools.product([False, True], repeat=2)))
def test_pool_decode_modes(opt_kv, opt_gqa, L, layer):
    q, kv, sc, phys, log = _pool_inputs(2, 8, 16, 2, 4, 128, opt_kv, L=L)
    cl = jnp.array([8 * 16, 55], jnp.int32)
    out = ops.paged_pool_decode(q, kv, sc, layer, cl, phys, log,
                                opt_kv=opt_kv, opt_gqa=opt_gqa)
    ks, vs = _scales(sc, layer)
    exp = ref.paged_pool_decode_ref(q, kv[layer, 0], kv[layer, 1], ks, vs,
                                    cl, phys, log, opt_kv=opt_kv)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), atol=3e-2)
    if layer:       # the index_maps pick the layer of the whole pool
        _same(out, pd.paged_pool_decode(q, kv, sc, layer, cl, phys, log,
                                        opt_kv=opt_kv, opt_gqa=opt_gqa,
                                        interpret=True))


@pytest.mark.parametrize("B,P,ps,Hkv,G,D", [
    (1, 4, 8, 1, 1, 64),       # MQA, single group (griffin-like)
    (3, 8, 16, 2, 7, 128),     # odd group count (yi-like 56/8)
    (2, 16, 32, 4, 4, 128),    # larger pages
    (2, 8, 16, 8, 1, 64),      # MHA-as-GQA (whisper: G=1)
])
def test_pool_decode_shape_sweep(B, P, ps, Hkv, G, D):
    q, kv, sc, phys, log = _pool_inputs(B, P, ps, Hkv, G, D, opt_kv=True)
    lens = (np.arange(B) * 17 + 3) % (P * ps) + 1
    cl = jnp.asarray(lens, jnp.int32)
    out = ops.paged_pool_decode(q, kv, sc, 0, cl, phys, log, opt_kv=True,
                                opt_gqa=True)
    exp = ref.paged_pool_decode_ref(q, kv[0, 0], kv[0, 1], sc[0, 0],
                                    sc[0, 1], cl, phys, log, opt_kv=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), atol=3e-2)


def test_pool_decode_scattered_table():
    """Pages physically scattered across the shared pool (the refcounted
    allocator's normal state) must decode identically to contiguous
    placement with the same logical content."""
    B, P, ps, Hkv, G, D = 1, 4, 16, 2, 4, 64
    q, kv, sc, phys, log = _pool_inputs(B, P, ps, Hkv, G, D, opt_kv=True)
    cl = jnp.array([P * ps], jnp.int32)
    base = ops.paged_pool_decode(q, kv, sc, 0, cl, phys, log, opt_kv=True,
                                 opt_gqa=True)
    perm = jnp.array([3, 1, 0, 2], jnp.int32)
    kv_s = kv.at[:, :, perm].set(kv[:, :, :P])    # scatter the 4 pages
    sc_s = sc.at[:, :, perm].set(sc[:, :, :P])
    out = ops.paged_pool_decode(q, kv_s, sc_s, 0, cl, perm[None], log,
                                opt_kv=True, opt_gqa=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(base, np.float32), atol=1e-5)


@pytest.mark.parametrize("window,sink", [(32, 1), (64, 2), (16, 0)])
def test_pool_decode_window_sweep(window, sink):
    B, P, ps = 2, 16, 16
    q, kv, sc, pt, _ = _pool_inputs(B, P, ps, 2, 4, 128, opt_kv=True)
    cl = jnp.array([P * ps, 100], jnp.int32)
    log = window_page_table(cl, P, ps, window, sink)
    phys = logical_to_physical(log, pt)
    out = ops.paged_pool_decode(q, kv, sc, 0, cl, phys, log, opt_kv=True,
                                opt_gqa=True, window=window, sink_pages=sink)
    exp = ref.paged_pool_decode_ref(q, kv[0, 0], kv[0, 1], sc[0, 0],
                                    sc[0, 1], cl, phys, log, opt_kv=True,
                                    window=window, sink_pages=sink)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), atol=3e-2)


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("L,layer", LAYERS)
@pytest.mark.parametrize("opt_kv", [False, True])
@pytest.mark.parametrize("Hkv,D", [(2, 128), (1, 64), (4, 64)])
def test_cache_write_sweep(opt_kv, Hkv, D, L, layer):
    B, S, P, ps = 2, 8, 8, 16
    kn = jax.random.normal(KEY, (B, S, Hkv, D), jnp.float32) \
        .astype(jnp.bfloat16)
    vn = jax.random.normal(jax.random.PRNGKey(9), (B, S, Hkv, D),
                           jnp.float32).astype(jnp.bfloat16)
    # lanes write DISJOINT global slots; -1 = SkipSet
    slots = jnp.array([[0, 5, -1, 17, 33, -1, 62, 2],
                       [64, -1, 73, 74, 75, 104, -1, 125]], jnp.int32)
    dt = jnp.float8_e4m3fn if opt_kv else jnp.bfloat16
    # every layer but the written one holds contents of its own
    kv_c = jax.random.normal(jax.random.PRNGKey(4), (L, 2, P, Hkv, ps, D)
                             ).astype(dt).at[layer].set(0)
    sc_c = (jax.random.uniform(jax.random.PRNGKey(5), (L, 2, P, Hkv, ps))
            .at[layer].set(0) if opt_kv else None)
    kv2, sc2 = ops.kv_cache_write(kv_c, sc_c, kn, vn,
                                  pool_lines(slots, layer, P, ps),
                                  opt_kv=opt_kv)

    NS = P * ps
    zeros_s = jnp.zeros((NS, Hkv))
    ek, ev, esk, esv = ref.kv_cache_write_ref(
        kn, vn, slots, _flat(kv_c[layer, 0]), _flat(kv_c[layer, 1]),
        zeros_s, zeros_s, opt_kv=opt_kv)
    # every line, the last included: SkipSet tokens write nowhere
    got = np.asarray(_flat(kv2[layer, 0]), np.float32)
    expd = np.asarray(ek, np.float32)
    # fp8 e4m3 (3-bit mantissa): allow 1 ULP rounding skew vs the oracle
    tol = np.maximum(np.abs(expd), 1.0) * 2.0 ** -3 + 1e-6
    assert np.all(np.abs(got - expd) <= tol)
    if opt_kv:
        np.testing.assert_allclose(np.asarray(_flat(sc2[layer, 0])),
                                   np.asarray(esk), atol=1e-7)
    # every other layer keeps every byte
    _same(_others(kv2, layer), _others(kv_c, layer))
    if opt_kv:
        _same(_others(sc2, layer), _others(sc_c, layer))
    if layer:       # the index_maps split the whole pool's pages
        kv1, sc1 = kw.kv_cache_write(kn, vn, pool_lines(slots, layer, P, ps),
                                     kv_c, sc_c, opt_kv=opt_kv,
                                     interpret=True)
        _same(kv1, kv2)
        if opt_kv:
            _same(sc1, sc2)


@pytest.mark.parametrize("L,layer", LAYERS)
def test_cache_write_preserves_other_lines(L, layer):
    """Aliasing semantics: unwritten cache lines keep their old contents,
    in the written layer and in every other, and a SkipSet token writes no
    line at all."""
    B, S, Hkv, D, P, ps = 1, 2, 1, 64, 2, 8
    old = jnp.full((L, 2, P, Hkv, ps, D), 7.0, jnp.bfloat16)
    kn = jnp.ones((B, S, Hkv, D), jnp.bfloat16)
    slots = jnp.array([[3, -1]], jnp.int32)
    kv2, _ = ops.kv_cache_write(old, None, kn, kn,
                                pool_lines(slots, layer, P, ps), opt_kv=False)
    flat = np.asarray(_flat(kv2[layer, 0]), np.float32)
    assert np.all(flat[3] == 1.0)
    untouched = [i for i in range(P * ps) if i != 3]
    assert np.all(flat[untouched] == 7.0)
    assert np.all(_others(kv2, layer) == 7.0)


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("S,Hq,Hkv,D,window", [
    (128, 8, 2, 64, 0),
    (128, 8, 2, 64, 32),
    (64, 4, 1, 128, 0),        # MQA
    (256, 14, 2, 64, 0),       # odd G=7
])
def test_flash_prefill_sweep(S, Hq, Hkv, D, window):
    B = 2
    q = jax.random.normal(KEY, (B, S, Hq, D)).astype(jnp.bfloat16)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, Hkv, D)) \
        .astype(jnp.bfloat16)
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, Hkv, D)) \
        .astype(jnp.bfloat16)
    out = ops.flash_prefill(q, k, v, window=window, block_q=64, block_k=32)
    exp = ref.flash_prefill_ref(q, k, v, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), atol=3e-2)


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("L,layer", LAYERS)
@pytest.mark.parametrize("opt_kv,opt_gqa,window,sink", [
    (False, True, 0, 0),
    (True, True, 0, 0),
    (True, False, 0, 0),       # Original MHA semantics: KV re-streamed
    (True, True, 32, 1),       # griffin-style local window + sink
])
def test_chunk_prefill_kernel_vs_reference(opt_kv, opt_gqa, window, sink, L,
                                           layer):
    """The continuation-prefill kernel (scalar-prefetched page table +
    per-row positions) matches the jnp gather reference, including -1
    page skips and decode lanes (chunk of length 1 semantics)."""
    from repro.core.coopt import CoOptConfig
    from repro.core.opt_pa import paged_chunk_attention

    B, P, ps, Hkv, G, D, S = 2, 4, 16, 2, 4, 64, 8
    qk = jax.random.normal(jax.random.PRNGKey(7), (B, S, Hkv * G, D)) \
        .astype(jnp.bfloat16)
    _, kv, sc, phys, _ = _pool_inputs(B, P, ps, Hkv, G, D, opt_kv, seed=7,
                                      L=L)
    # lane 0: continuation chunk at positions [24, 32); lane 1: a decode
    # lane — one real token at position 40, padding clamped to it — with
    # its final page unallocated (-1: never DMA'd, masked in the reference)
    positions = jnp.stack([jnp.arange(24, 32),
                           jnp.full((S,), 40)]).astype(jnp.int32)
    phys = phys.at[1, P - 1].set(-1)

    ref_cfg = CoOptConfig(opt_kv=opt_kv, opt_gqa=opt_gqa, opt_pa=True,
                          use_kernel=False)
    exp = paged_chunk_attention(qk, kv, sc, layer, positions, phys, ref_cfg,
                                window=window, sink_pages=sink)
    out = ops.paged_chunk_prefill(qk, positions, kv, sc, layer, phys,
                                  opt_kv=opt_kv, opt_gqa=opt_gqa,
                                  window=window, sink_pages=sink)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), atol=3e-2)
    if layer:       # the index_maps pick the layer of the whole pool
        _same(out, fc.flash_chunk_prefill(qk, positions, kv, sc, layer, phys,
                                          opt_kv=opt_kv, opt_gqa=opt_gqa,
                                          window=window, sink_pages=sink,
                                          interpret=True))


def test_flash_prefill_f32():
    B, S, Hq, Hkv, D = 1, 64, 4, 2, 64
    q = jax.random.normal(KEY, (B, S, Hq, D), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, Hkv, D), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, Hkv, D), jnp.float32)
    out = ops.flash_prefill(q, k, v, block_q=32, block_k=32)
    exp = ref.flash_prefill_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=1e-4)
