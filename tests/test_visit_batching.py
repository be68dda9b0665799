"""Cross-lane shared-prefix visit batching (kernels.visits) and
tile-resident chunk streaming.

Covers the visit planner's dedup/ownership/ordering contract, parity of the
visit-grid decode kernels vs the jnp oracles over {fp8, bf16} x {dense,
windowed} with 2 and 8 sharing lanes, BIT-identity of the visit grid vs the
per-lane grid (with and without sharing present — the visit grid processes
each lane's pages in the same ascending-slot order, so even the
floating-point reduction order is unchanged), multi-resident-block chunk
parity (block_q forcing NQ > 1 must not change results), and engine-level
greedy identity with ``share_visits`` on vs off plus the sharing
observability counters. At a nonzero layer of a pool of several layers the
visit kernels also run on the whole pool, their index_maps picking the
layer, bit-identical to the one-layer result of the ``ops`` wrappers."""
import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cache.quant import quantize_fp8, quantize_latent
from repro.configs import get_config
from repro.core.coopt import MODES
from repro.core.opt_kv import identity_page_table
from repro.kernels import ops, ref
from repro.kernels.paged_gqa_decode import paged_pool_decode_visits
from repro.kernels.paged_latent_decode import paged_latent_decode_visits
from repro.kernels.visits import (MAX_VISIT_LANES, plan_visits,
                                  sharing_stats)
from repro.serving import Engine, EngineConfig


def _shared_tables(B, P, shared):
    """Physical pages 0..shared-1 common to all lanes, tails private."""
    phys = np.zeros((B, P), np.int32)
    for b in range(B):
        for i in range(P):
            phys[b, i] = i if i < shared else \
                shared + b * (P - shared) + (i - shared)
    log = np.ascontiguousarray(
        np.broadcast_to(np.arange(P, dtype=np.int32)[None], (B, P)))
    total = shared + B * (P - shared)
    return jnp.asarray(phys), jnp.asarray(log), total


# (layers in the pool, layer attended): one layer alone, and a nonzero
# layer of three
LAYERS = [(1, 0), (3, 2)]


def _gqa_inputs(B, P, shared, ps, Hkv, G, D, opt_kv, seed=0, L=1):
    """A pool of ``L`` layers, each with contents of its own."""
    phys, log, PT = _shared_tables(B, P, shared)
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, Hkv * G, D)).astype(jnp.bfloat16)
    k = jax.random.normal(ks[1], (L, PT, Hkv, ps, D), jnp.float32)
    v = jax.random.normal(ks[2], (L, PT, Hkv, ps, D), jnp.float32)
    if opt_kv:
        kq, ksc = quantize_fp8(k)
        vq, vsc = quantize_fp8(v)
        return (q, jnp.stack([kq, vq], 1), jnp.stack([ksc, vsc], 1), phys,
                log)
    return q, jnp.stack([k, v], 1).astype(jnp.bfloat16), None, phys, log


# ------------------------------------------------------------ plan_visits --
def test_plan_visits_dedups_shared_pages():
    phys = jnp.asarray([[0, 3], [0, 4], [0, 5]], jnp.int32)
    log = jnp.asarray([[0, 1]] * 3, jnp.int32)
    vp, vm, vl = (np.asarray(x) for x in plan_visits(phys, log))
    B = 3
    # s-major flatten: visit v = s*B + b. Slot 0: page 0 owned by lane 0
    # with all three lanes' bits; lanes 1/2 emit dead visits.
    assert vp[0] == 0 and vm[0] == 0b111 and vl[0] == 0
    assert vp[1] == -1 and vm[1] == 0 and vp[2] == -1
    # slot 1: three private pages, each its own visit with its own bit
    assert list(vp[3:]) == [3, 4, 5]
    assert list(vm[3:]) == [0b001, 0b010, 0b100]
    assert list(vl[3:]) == [1, 1, 1]


def test_plan_visits_skips_holes_and_keys_on_logical_id():
    # a -1 (never-DMA'd) entry is dead; equal physical page under DIFFERENT
    # logical ids (window remap) must NOT be merged
    phys = jnp.asarray([[7, -1], [7, 9]], jnp.int32)
    log = jnp.asarray([[2, 3], [5, 3]], jnp.int32)
    vp, vm, vl = (np.asarray(x) for x in plan_visits(phys, log))
    # slot 0: same phys page 7 but logical 2 vs 5 -> two separate visits
    assert list(vp[:2]) == [7, 7]
    assert list(vm[:2]) == [0b01, 0b10]
    assert list(vl[:2]) == [2, 5]
    # slot 1: lane 0's hole emits nothing; lane 1's page stands alone
    assert vp[2] == -1 and vm[2] == 0
    assert vp[3] == 9 and vm[3] == 0b10 and vl[3] == 3


def test_plan_visits_per_lane_slot_order_preserved():
    """Each lane's member visits appear in ascending slot order in the
    flattened list — the property that makes the visit grid's reduction
    order (hence floating point) identical to the per-lane grid."""
    B, P, shared = 4, 6, 3
    phys, log, _ = _shared_tables(B, P, shared)
    vp, vm, _ = (np.asarray(x) for x in plan_visits(phys, log))
    for lane in range(B):
        member = (vp >= 0) & ((vm >> lane) & 1 == 1)
        slots = np.nonzero(member)[0] // B     # visit v = s*B + b
        assert list(slots) == sorted(slots)
        assert len(slots) == P                 # every slot visited once


def test_sharing_stats_counts_dup_streams():
    phys, _, _ = _shared_tables(4, 6, 3)
    st = sharing_stats(np.asarray(phys))
    assert st["shared_page_visits"] == 3           # 3 shared slots
    assert st["dup_page_streams_saved"] == 3 * 3   # (4-1) lanes x 3 pages
    assert st["lanes_per_shared_page"] == {4: 3}


# ------------------------------------------------- GQA decode visit grid --
@pytest.mark.parametrize("L,layer", LAYERS)
@pytest.mark.parametrize("opt_kv", [False, True])
@pytest.mark.parametrize("window", [0, 48])
@pytest.mark.parametrize("lanes", [2, 8])
def test_gqa_visit_parity_vs_oracle(opt_kv, window, lanes, L, layer):
    B, P, shared, ps, Hkv, G, D = lanes, 6, 4, 16, 2, 4, 64
    q, kv, sc, phys, log = _gqa_inputs(B, P, shared, ps, Hkv, G, D, opt_kv,
                                       L=L)
    # varied lengths across the sharing lanes: the positional mask is
    # per-member inside one shared visit
    cl = jnp.asarray(P * ps - 5 * np.arange(B), jnp.int32)
    out = ops.paged_pool_decode(q, kv, sc, layer, cl, phys, log,
                                opt_kv=opt_kv, opt_gqa=True, window=window,
                                share_visits=True)
    ks, vs = (sc[layer, 0], sc[layer, 1]) if sc is not None else (None, None)
    exp = ref.paged_pool_decode_ref(q, kv[layer, 0], kv[layer, 1], ks, vs,
                                    cl, phys, log, opt_kv=opt_kv,
                                    window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), atol=3e-2)
    if layer:       # the index_maps pick the layer of the whole pool
        whole = paged_pool_decode_visits(q, kv, sc, layer, cl,
                                         *plan_visits(phys, log),
                                         opt_kv=opt_kv, opt_gqa=True,
                                         window=window, interpret=True)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(whole))


@pytest.mark.parametrize("shared", [0, 4])
def test_gqa_visit_grid_bit_identical_to_per_lane(shared):
    """share_visits on vs off: bitwise-equal outputs, both with NO sharing
    (pure degenerate case) and WITH sharing (ascending-slot visit order
    reproduces the per-lane reduction order exactly)."""
    B, P, ps, Hkv, G, D = 4, 6, 16, 2, 4, 64
    q, kv, sc, phys, log = _gqa_inputs(B, P, shared, ps, Hkv, G, D,
                                       opt_kv=True)
    cl = jnp.asarray(P * ps - 7 * np.arange(B), jnp.int32)
    off = ops.paged_pool_decode(q, kv, sc, 0, cl, phys, log, opt_kv=True,
                                opt_gqa=True, share_visits=False)
    on = ops.paged_pool_decode(q, kv, sc, 0, cl, phys, log, opt_kv=True,
                               opt_gqa=True, share_visits=True)
    np.testing.assert_array_equal(np.asarray(off), np.asarray(on))


def test_visit_dispatch_gate():
    """B = 1 and B > MAX_VISIT_LANES stay on the per-lane grid (the int32
    lane bitmask bounds the visit grid) — outputs must still match."""
    for B in (1, MAX_VISIT_LANES + 1):
        P, ps, Hkv, G, D = 2, 8, 1, 2, 64
        q, kv, sc, phys, log = _gqa_inputs(B, P, 0, ps, Hkv, G, D,
                                           opt_kv=True, seed=2)
        cl = jnp.full((B,), P * ps, jnp.int32)
        off = ops.paged_pool_decode(q, kv, sc, 0, cl, phys, log,
                                    opt_kv=True, opt_gqa=True,
                                    share_visits=False)
        on = ops.paged_pool_decode(q, kv, sc, 0, cl, phys, log, opt_kv=True,
                                   opt_gqa=True, share_visits=True)
        np.testing.assert_array_equal(np.asarray(off), np.asarray(on))


# -------------------------------------------------- latent (MLA) visits --
@pytest.mark.parametrize("L,layer", LAYERS)
@pytest.mark.parametrize("opt_kv", [False, True])
@pytest.mark.parametrize("window", [0, 48])
def test_latent_visit_parity_vs_oracle(opt_kv, window, L, layer):
    B, P, shared, ps, H, R, dr = 8, 6, 4, 16, 8, 64, 32
    phys, log, PT = _shared_tables(B, P, shared)
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    ql = jax.random.normal(ks[0], (B, H, R)).astype(jnp.bfloat16)
    qr = jax.random.normal(ks[1], (B, H, dr)).astype(jnp.bfloat16)
    latf = jax.random.normal(ks[2], (L, PT, ps, R + dr), jnp.float32)
    if opt_kv:
        lat, sc = quantize_latent(latf, R)
    else:
        lat, sc = latf.astype(jnp.bfloat16), None
    cl = jnp.asarray(P * ps - 5 * np.arange(B), jnp.int32)
    sm = (R + dr) ** -0.5
    out = ops.paged_latent_decode(ql, qr, lat, sc, layer, cl, phys, log,
                                  sm_scale=sm, opt_kv=opt_kv, window=window,
                                  share_visits=True)
    exp = ref.paged_latent_decode_ref(ql, qr, lat[layer],
                                      None if sc is None else sc[layer], cl,
                                      phys, log, sm_scale=sm, opt_kv=opt_kv,
                                      window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), atol=3e-2)
    if layer:       # the index_maps pick the layer of the whole pool
        whole = paged_latent_decode_visits(
            ql, qr, lat, sc, layer, cl, *plan_visits(phys, log), sm_scale=sm,
            opt_kv=opt_kv, window=window, interpret=True)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(whole))


@pytest.mark.parametrize("shared", [0, 4])
def test_latent_visit_grid_bit_identical_to_per_lane(shared):
    B, P, ps, H, R, dr = 4, 6, 16, 8, 64, 32
    phys, log, PT = _shared_tables(B, P, shared)
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    ql = jax.random.normal(ks[0], (B, H, R)).astype(jnp.bfloat16)
    qr = jax.random.normal(ks[1], (B, H, dr)).astype(jnp.bfloat16)
    lat, sc = quantize_latent(
        jax.random.normal(ks[2], (1, PT, ps, R + dr), jnp.float32), R)
    cl = jnp.asarray(P * ps - 7 * np.arange(B), jnp.int32)
    sm = (R + dr) ** -0.5
    off = ops.paged_latent_decode(ql, qr, lat, sc, 0, cl, phys, log,
                                  sm_scale=sm, opt_kv=True,
                                  share_visits=False)
    on = ops.paged_latent_decode(ql, qr, lat, sc, 0, cl, phys, log,
                                 sm_scale=sm, opt_kv=True,
                                 share_visits=True)
    np.testing.assert_array_equal(np.asarray(off), np.asarray(on))


# ------------------------------------- tile-resident chunk streaming -----
def test_chunk_prefill_multi_resident_block_parity():
    """Forcing several resident row-blocks per chunk (NQ > 1) must match
    both the single-resident-block run and the jnp oracle — the restructure
    changed the streaming schedule, not the math."""
    from repro.core.coopt import CoOptConfig
    from repro.core.opt_pa import paged_chunk_attention
    from repro.kernels.flash_chunk_prefill import (flash_chunk_prefill,
                                                   resident_rows)

    B, P, ps, Hkv, G, D, S = 2, 4, 16, 2, 4, 64, 64
    q = jax.random.normal(jax.random.PRNGKey(7),
                          (B, S, Hkv * G, D)).astype(jnp.bfloat16)
    phys = identity_page_table(B, B * P)
    k = jax.random.normal(jax.random.PRNGKey(8), (B * P, Hkv, ps, D),
                          jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(9), (B * P, Hkv, ps, D),
                          jnp.float32)
    kq, ksc = quantize_fp8(k)
    vq, vsc = quantize_fp8(v)
    kv, sc = jnp.stack([kq, vq])[None], jnp.stack([ksc, vsc])[None]
    positions = jnp.stack([jnp.arange(0, 64),
                           jnp.arange(0, 64) // 2 + 32]).astype(jnp.int32)
    R = S * G
    # row groups are 128-aligned (Mosaic lane tiling of the positions block)
    assert resident_rows(R, G, 128) == 128 and R // 128 > 1  # forces NQ > 1
    tiled = flash_chunk_prefill(q, positions, kv, sc, 0, phys,
                                opt_kv=True, block_q=128, interpret=True)
    whole = flash_chunk_prefill(q, positions, kv, sc, 0, phys,
                                opt_kv=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(tiled), np.asarray(whole))
    exp = paged_chunk_attention(
        q, kv, sc, 0, positions, phys,
        CoOptConfig(opt_kv=True, opt_gqa=True, opt_pa=True))
    np.testing.assert_allclose(np.asarray(whole, np.float32),
                               np.asarray(exp, np.float32), atol=3e-2)


def test_latent_chunk_multi_resident_block_parity():
    from repro.kernels.latent_chunk_prefill import (latent_chunk_prefill,
                                                    resident_rows)

    B, P, ps, H, R, dr, S = 2, 4, 16, 8, 64, 32, 4
    phys = identity_page_table(B, B * P)
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    ql = jax.random.normal(ks[0], (B, S, H, R)).astype(jnp.bfloat16)
    qr = jax.random.normal(ks[1], (B, S, H, dr)).astype(jnp.bfloat16)
    lat, sc = quantize_latent(
        jax.random.normal(ks[2], (1, B * P, ps, R + dr), jnp.float32), R)
    positions = jnp.stack([jnp.arange(24, 28),
                           jnp.arange(60, 64)]).astype(jnp.int32)
    sm = (R + dr) ** -0.5
    RW = S * H
    assert resident_rows(RW, H, H) == H and RW // H > 1   # forces NQ > 1
    tiled = latent_chunk_prefill(ql, qr, positions, lat, sc, 0, phys,
                                 sm_scale=sm, opt_kv=True, block_q=H,
                                 interpret=True)
    whole = latent_chunk_prefill(ql, qr, positions, lat, sc, 0, phys,
                                 sm_scale=sm, opt_kv=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(tiled), np.asarray(whole))
    exp = ref.latent_chunk_prefill_ref(ql, qr, positions, lat[0], sc[0],
                                       phys, sm_scale=sm, opt_kv=True)
    np.testing.assert_allclose(np.asarray(whole, np.float32),
                               np.asarray(exp, np.float32), atol=3e-2)


# --------------------------------------------- engine greedy identity ----
def test_engine_greedy_identical_and_sharing_observed():
    """Shared-prompt serving through the kernel path: greedy outputs are
    bit-identical with ``share_visits`` on vs off, and with it on the
    engine's sharing counters see the refcount-shared prefix pages."""
    cfg = get_config("qwen3-4b-reduced")
    ecfg = EngineConfig(num_lanes=2, max_len=192,
                        prefill_buckets=(16, 32, 64, 128))
    prompt = (np.arange(80, dtype=np.int32) * 7 + 11) % cfg.vocab_size

    def serve(share):
        from repro.serving import Request
        co = MODES["coopt"].replace(use_kernel=True, share_visits=share)
        eng = Engine(cfg, co, ecfg)
        warm = Request(req_id=0, prompt=prompt.copy(), max_new_tokens=2)
        eng.add_request(warm)
        eng.run()                 # commits the prompt's pages to the
        eng.stats.__init__()      # prefix cache, then reset counters
        rs = [Request(req_id=i + 1, prompt=prompt.copy(), max_new_tokens=5)
              for i in range(2)]
        for r in rs:
            eng.add_request(r)
        eng.run()
        return [r.output for r in rs], eng.stats

    out_on, stats_on = serve(True)
    out_off, _ = serve(False)
    assert out_on == out_off
    assert all(len(o) == 5 for o in out_on)
    # both lanes decoded off the same cached prompt pages -> the decode
    # steps' page tables carried genuinely shared pages
    assert stats_on.shared_page_visits > 0
    assert stats_on.dup_page_streams_saved > 0
    assert 2 in stats_on.lanes_per_shared_page
    assert ("shared_page_visits"
            in stats_on.latency_summary())


def test_block_manager_shared_page_accessors():
    from repro.cache.block_manager import BlockManager
    m = BlockManager(num_pages=8, page_size=4)
    toks = list(range(12))                       # three full pages
    pages1, _ = m.allocate(1, len(toks), token_ids=toks)
    m.commit_prefill(1, len(toks), token_ids=toks)
    pages2, cached = m.allocate(2, len(toks), token_ids=toks)
    # leading full pages hit; the final page stays writable (unshared)
    assert cached > 0 and cached % m.page_size == 0
    shared = m.shared_page_counts()
    n_shared = cached // m.page_size
    assert set(shared) == set(pages1[:n_shared]) == set(pages2[:n_shared])
    assert all(r == 2 for r in shared.values())
    assert m.sharing_histogram() == {2: n_shared}
    m.free(2)
    assert m.shared_page_counts() == {} and m.sharing_histogram() == {}
