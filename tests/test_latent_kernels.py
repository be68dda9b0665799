"""Fused MLA latent-attention kernels (absorbed decode + chunk prefill off
the global FP8 latent pool) — parity sweeps vs the naive oracle AND vs the
jnp model path they replace, across {fp8, bf16} x {windowed, dense} x ragged
page tables with -1 holes, at one layer alone and at a nonzero layer of a
pool of several (the kernel on the whole pool, its index_maps picking the
layer, bit-identical to the one-layer result the ``ops`` wrapper hands the
interpreter); plus the rule that the platform, not a launcher, decides
interpret mode. interpret=True on CPU."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cache.quant import quantize_latent
from repro.configs import get_config
from repro.core.coopt import MODES
from repro.core.opt_kv import decode_page_select, identity_page_table
from repro.kernels import latent_chunk_prefill as lc
from repro.kernels import ops, paged_latent_decode as ld, ref
from repro.models import mla as mla_mod

CFG = get_config("deepseek-v2-lite-16b-reduced")
H, DN, DR = CFG.num_heads, CFG.qk_nope_head_dim, CFG.qk_rope_head_dim
R, DV = CFG.kv_lora_rank, CFG.v_head_dim
SCALE = 1.0 / math.sqrt(DN + DR)


# (layers in the pool, layer attended): one layer alone, and a nonzero
# layer of three
LAYERS = [(1, 0), (3, 2)]


def _latent_pool(B, P, ps, fp8, seed=0, L=1):
    """Pool of ``L`` layers of B*P latent pages each (contents of their
    own), lane-identity partitioned, with the LAST page of lane B-1 left
    unallocated (-1 hole in the ragged table)."""
    latf = jax.random.normal(jax.random.PRNGKey(seed),
                             (L, B * P, ps, R + DR), jnp.float32)
    pt = identity_page_table(B, B * P).at[B - 1, P - 1].set(-1)
    if fp8:
        lat, sc = quantize_latent(latf, R)
        return lat, sc, pt
    return latf.astype(jnp.bfloat16), None, pt


def _at(x, layer):
    return None if x is None else x[layer]


def _absorb_params(seed=1):
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    return {"w_uk": jax.random.normal(ks[0], (R, H * DN)) * 0.05,
            "w_uv": jax.random.normal(ks[1], (R, H * DV)) * 0.05}


# ----------------------------------------------------------- decode kernel --
@pytest.mark.parametrize("L,layer", LAYERS)
@pytest.mark.parametrize("fp8", [True, False])
@pytest.mark.parametrize("window,sink", [(0, 0), (32, 1), (16, 2)])
def test_latent_decode_kernel_vs_oracle(fp8, window, sink, L, layer):
    B, P, ps = 2, 4, 16
    lat, sc, pt = _latent_pool(B, P, ps, fp8, L=L)
    ks = jax.random.split(jax.random.PRNGKey(3), 2)
    ql = jax.random.normal(ks[0], (B, H, R), jnp.float32)
    qr = jax.random.normal(ks[1], (B, H, DR), jnp.float32)
    cl = jnp.array([P * ps, 37], jnp.int32)      # lane 1: ragged, holed table
    phys, log = decode_page_select(cl, pt, ps, window=window,
                                   sink_pages=sink, opt_pa=True)
    out = ops.paged_latent_decode(ql, qr, lat, sc, layer, cl, phys, log,
                                  sm_scale=SCALE, opt_kv=fp8, window=window,
                                  sink_pages=sink)
    exp = ref.paged_latent_decode_ref(ql, qr, lat[layer], _at(sc, layer),
                                      cl, phys, log, sm_scale=SCALE,
                                      opt_kv=fp8, window=window,
                                      sink_pages=sink)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=1e-4)
    if layer:       # the index_maps pick the layer of the whole pool
        np.testing.assert_array_equal(np.asarray(out), np.asarray(
            ld.paged_latent_decode(ql, qr, lat, sc, layer, cl, phys, log,
                                   sm_scale=SCALE, opt_kv=fp8,
                                   window=window, sink_pages=sink,
                                   interpret=True)))


@pytest.mark.parametrize("fp8", [True, False])
@pytest.mark.parametrize("window", [0, 32])
def test_mla_paged_decode_dispatch_parity(fp8, window):
    """The full model path: mla_paged_decode under use_kernel must match
    the jnp parity reference bit-for-bit after the bf16 output cast, for
    every mode x window combination — including -1 page holes."""
    B, P, ps = 2, 4, 16
    lat, sc, pt = _latent_pool(B, P, ps, fp8, seed=5)
    p = _absorb_params()
    ks = jax.random.split(jax.random.PRNGKey(6), 2)
    qn = jax.random.normal(ks[0], (B, H, DN)).astype(jnp.bfloat16)
    qr = jax.random.normal(ks[1], (B, H, DR)).astype(jnp.bfloat16)
    cl = jnp.array([P * ps, 37], jnp.int32)
    co = MODES["coopt" if fp8 else "original"]
    a = mla_mod.mla_paged_decode(qn, qr, lat, sc, 0, cl, p, CFG,
                                 co.replace(use_kernel=False), window=window,
                                 sink_pages=1, page_table=pt)
    b = mla_mod.mla_paged_decode(qn, qr, lat, sc, 0, cl, p, CFG,
                                 co.replace(use_kernel=True), window=window,
                                 sink_pages=1, page_table=pt)
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), atol=2e-2)


def test_latent_decode_scattered_table():
    """Physically scattered pages (the refcounted allocator's normal state)
    decode identically to contiguous placement with the same content."""
    B, P, ps = 1, 4, 16
    lat, sc, _ = _latent_pool(B, P, ps, fp8=True, seed=8)
    ks = jax.random.split(jax.random.PRNGKey(9), 2)
    ql = jax.random.normal(ks[0], (B, H, R), jnp.float32)
    qr = jax.random.normal(ks[1], (B, H, DR), jnp.float32)
    cl = jnp.array([P * ps], jnp.int32)
    log = jnp.arange(P, dtype=jnp.int32)[None]
    base = ops.paged_latent_decode(ql, qr, lat, sc, 0, cl, log, log,
                                   sm_scale=SCALE, opt_kv=True)
    perm = jnp.array([3, 1, 0, 2], jnp.int32)
    lat_s = lat.at[:, perm].set(lat[:, :P])
    sc_s = sc.at[:, perm].set(sc[:, :P])
    out = ops.paged_latent_decode(ql, qr, lat_s, sc_s, 0, cl, perm[None],
                                  log, sm_scale=SCALE, opt_kv=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(base), atol=1e-5)


# ------------------------------------------------------------ chunk kernel --
@pytest.mark.parametrize("L,layer", LAYERS)
@pytest.mark.parametrize("fp8", [True, False])
@pytest.mark.parametrize("window,sink", [(0, 0), (32, 1)])
def test_latent_chunk_kernel_vs_oracle(fp8, window, sink, L, layer):
    """Chunk continuation with per-row positions: lane 0 a true chunk at
    [24, 32), lane 1 a decode lane (length-1 chunk, padding clamped) with
    its final page a -1 hole (never DMA'd)."""
    B, P, ps, S = 2, 4, 16, 8
    lat, sc, pt = _latent_pool(B, P, ps, fp8, seed=11, L=L)
    ks = jax.random.split(jax.random.PRNGKey(12), 2)
    ql = jax.random.normal(ks[0], (B, S, H, R), jnp.float32)
    qr = jax.random.normal(ks[1], (B, S, H, DR), jnp.float32)
    positions = jnp.stack([jnp.arange(24, 32),
                           jnp.full((S,), 40)]).astype(jnp.int32)
    out = ops.latent_chunk_prefill(ql, qr, positions, lat, sc, layer, pt,
                                   sm_scale=SCALE, opt_kv=fp8,
                                   window=window, sink_pages=sink)
    exp = ref.latent_chunk_prefill_ref(ql, qr, positions, lat[layer],
                                       _at(sc, layer), pt, sm_scale=SCALE,
                                       opt_kv=fp8, window=window,
                                       sink_pages=sink)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=1e-4)
    if layer:       # the index_maps pick the layer of the whole pool
        np.testing.assert_array_equal(np.asarray(out), np.asarray(
            lc.latent_chunk_prefill(ql, qr, positions, lat, sc, layer, pt,
                                    sm_scale=SCALE, opt_kv=fp8,
                                    window=window, sink_pages=sink,
                                    interpret=True)))


@pytest.mark.parametrize("fp8", [True, False])
@pytest.mark.parametrize("window", [0, 32])
def test_mla_chunk_attention_dispatch_parity(fp8, window):
    B, P, ps, S = 2, 4, 16, 8
    lat, sc, pt = _latent_pool(B, P, ps, fp8, seed=13)
    p = _absorb_params()
    ks = jax.random.split(jax.random.PRNGKey(14), 2)
    qn = jax.random.normal(ks[0], (B, S, H, DN)).astype(jnp.bfloat16)
    qr = jax.random.normal(ks[1], (B, S, H, DR)).astype(jnp.bfloat16)
    positions = jnp.stack([jnp.arange(24, 32),
                           jnp.full((S,), 40)]).astype(jnp.int32)
    co = MODES["coopt" if fp8 else "original"]
    a = mla_mod.mla_chunk_attention(qn, qr, lat, sc, 0, positions, pt, p,
                                    CFG, co.replace(use_kernel=False),
                                    window=window, sink_pages=1)
    b = mla_mod.mla_chunk_attention(qn, qr, lat, sc, 0, positions, pt, p,
                                    CFG, co.replace(use_kernel=True),
                                    window=window, sink_pages=1)
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), atol=2e-2)


# ------------------------------------------------ interpret mode by platform --
def test_configure_for_backend_flips_interpret(monkeypatch):
    """Interpret mode follows the backend at every dispatch: compiled
    kernels under a (faked) TPU backend, the interpreter anywhere else —
    with nothing to configure in between."""
    seen = []

    def spy(q, k, v, **kw):
        seen.append(kw["interpret"])
        return q

    monkeypatch.setattr(ops, "_flash_prefill_single", spy)
    x = jnp.zeros((1, 8, 2, 128), jnp.bfloat16)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops.interpret_mode() is False
    ops.flash_prefill(x, x, x)
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert ops.interpret_mode() is True
    ops.flash_prefill(x, x, x)
    assert seen == [False, True]


def test_launchers_call_configure_for_backend(monkeypatch):
    """No launcher has to switch interpret mode off: an ``Engine`` built
    directly (as examples and scripts do) dispatches every pooled kernel
    compiled under a TPU backend. The faked backend only steers the
    dispatch; the spies still run the kernels interpreted on this CPU."""
    from repro.serving import Engine, EngineConfig
    seen = []

    def spying(name):
        real = getattr(ops, name)

        def spy(*a, **kw):
            seen.append((name, kw["interpret"]))
            return real(*a, **{**kw, "interpret": True})
        monkeypatch.setattr(ops, name, spy)

    for name in ("_paged_chunk_prefill_single", "_paged_pool_decode_single",
                 "_kv_cache_write_single"):
        spying(name)
    cfg = get_config("qwen3-4b-reduced")
    eng = Engine(cfg, MODES["coopt"].replace(use_kernel=True),
                 EngineConfig(num_lanes=1, max_len=64,
                              prefill_buckets=(16,)))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    eng.generate([np.arange(1, 9, dtype=np.int32)], max_new_tokens=2)
    assert {n for n, _ in seen} == {"_paged_chunk_prefill_single",
                                    "_paged_pool_decode_single",
                                    "_kv_cache_write_single"}
    assert not any(interp for _, interp in seen)
