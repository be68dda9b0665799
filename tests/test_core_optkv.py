"""Opt-KV write/read path semantics over the GLOBAL pool (paper §3.1,
Eq. 5/6): a pool of one layer, written at lines of layer 0 (its flat
slots) and gathered from ``pool[0]``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.coopt import CoOptConfig, COOPT, ORIGINAL, OPT_KV
from repro.core.opt_kv import (gather_cached_kv, identity_page_table,
                               identity_slots, logical_to_physical,
                               make_pool, window_page_table, write_kv)


def _mk(P=8, ps=8, H=2, D=16, B=2, S=5, coopt=OPT_KV):
    kv, sc = make_pool(1, P, ps, H, D, coopt)
    k = jax.random.normal(jax.random.PRNGKey(0), (B, S, H, D), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(1), (B, S, H, D), jnp.float32)
    return kv, sc, k, v


def test_skipset_negative_slots_never_written():
    """Eq. 5: slot < 0 => the token's K/V must not touch the cache."""
    kv, sc, k, v = _mk()
    # lanes write DISJOINT global slots (refcounted pool invariant)
    slots = jnp.array([[0, -1, 2, -1, 4], [-1, 33, -1, 35, -1]], jnp.int32)
    kv2, sc2 = write_kv(kv, sc, k, v, slots, OPT_KV)
    # (1, 2, P, H, ps, D) -> flat token lines (2, P*ps, H, D)
    flat = np.asarray(jnp.swapaxes(kv2[0], 2, 3).reshape(2, -1, 2, 16)
                      .astype(jnp.float32))
    # skipped slots stay zero
    assert np.all(flat[:, 1] == 0) and np.all(flat[:, 3] == 0)
    assert np.all(flat[:, 32] == 0) and np.all(flat[:, 34] == 0)
    # written slots are non-zero
    assert np.abs(flat[0, 0]).max() > 0
    assert np.abs(flat[0, 33]).max() > 0


def test_write_then_gather_roundtrip_fp8():
    """Eq. 6: gather_cached_kv dequantizes what write_kv stored."""
    kv, sc, k, v = _mk()
    # lane 0 -> page 0 (slots 0..), lane 1 -> page 4 (slots 32..): the
    # identity partition of an 8-page pool between 2 lanes
    slots = identity_slots(2, jnp.broadcast_to(jnp.arange(5), (2, 5)), 8, 8)
    kv2, sc2 = write_kv(kv, sc, k, v, slots, OPT_KV)
    table = identity_page_table(2, 8)[:, :1]      # each lane's first page
    out = gather_cached_kv(kv2[0], sc2[0], table, OPT_KV, dtype=jnp.float32)
    amax = float(np.abs(np.asarray(k)).max())
    np.testing.assert_allclose(np.asarray(out[0, :, :5]), np.asarray(k),
                               atol=amax * 2 ** -3)


def test_bf16_mode_is_exactish():
    co = ORIGINAL
    kv, sc, k, v = _mk(coopt=co)
    slots = identity_slots(2, jnp.broadcast_to(jnp.arange(5), (2, 5)), 8, 8)
    kv2, _ = write_kv(kv, None, k, v, slots, co)
    table = identity_page_table(2, 8)[:, :1]
    out = gather_cached_kv(kv2[0], None, table, co, dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(out[0, :, :5]), np.asarray(k),
                               atol=0.01, rtol=0.01)


def test_gather_negative_pages_are_zero():
    kv, sc, k, v = _mk()
    slots = identity_slots(2, jnp.broadcast_to(jnp.arange(5), (2, 5)), 8, 8)
    kv2, sc2 = write_kv(kv, sc, k, v, slots, OPT_KV)
    table = jnp.array([[0, -1], [-1, 4]], jnp.int32)
    out = np.asarray(gather_cached_kv(kv2[0], sc2[0], table, OPT_KV,
                                      dtype=jnp.float32))
    ps = 8
    assert np.all(out[:, 0, ps:] == 0)            # lane 0, table slot 1 = -1
    assert np.all(out[:, 1, :ps] == 0)            # lane 1, table slot 0 = -1


def test_shared_page_read_by_two_lanes():
    """Prefix caching: the SAME physical page appears in two lanes' tables
    and both gathers see identical content (CoW read sharing)."""
    kv, sc, k, v = _mk()
    slots = jnp.broadcast_to(jnp.arange(5), (1, 5)).astype(jnp.int32)
    kv2, sc2 = write_kv(kv, sc, k[:1], v[:1], slots, OPT_KV)
    table = jnp.array([[0], [0]], jnp.int32)      # both lanes -> page 0
    out = np.asarray(gather_cached_kv(kv2[0], sc2[0], table, OPT_KV,
                                      dtype=jnp.float32))
    np.testing.assert_array_equal(out[:, 0], out[:, 1])


class TestWindowPageTable:
    def test_selects_sink_and_window(self):
        # 16 pages x 16 tokens; window 64 => 5 window pages + 1 sink
        t = window_page_table(jnp.array([256]), 16, 16, 64, 1)
        sel = set(int(x) for x in np.asarray(t[0]) if x >= 0)
        assert 0 in sel                            # sink page
        assert {11, 12, 13, 14, 15} <= sel         # window pages

    def test_no_duplicates_at_full_cache(self):
        """Regression: cache_len == P*ps must not duplicate the last page."""
        t = np.asarray(window_page_table(jnp.array([256]), 16, 16, 64, 1)[0])
        live = t[t >= 0]
        assert len(live) == len(set(live.tolist()))

    def test_short_context_no_sink_overlap(self):
        t = np.asarray(window_page_table(jnp.array([40]), 16, 16, 64, 1)[0])
        live = t[t >= 0]
        assert len(live) == len(set(live.tolist()))
        assert set(live.tolist()) <= {0, 1, 2}     # only pages 0..2 exist

    def test_logical_to_physical_preserves_skips(self):
        logical = jnp.array([[0, 2, -1]], jnp.int32)
        table = jnp.array([[7, 5, 3]], jnp.int32)  # lane's physical pages
        phys = np.asarray(logical_to_physical(logical, table))
        assert phys.tolist() == [[7, 3, -1]]

    def test_beyond_table_width_skips_not_aliases(self):
        """Regression: cache_len > num_pages * ps used to CLAMP the window
        pages onto page num_pages-1 (attending the wrong page's content);
        out-of-range logical ids must come back -1 (a skip)."""
        # 4-page table, 16-token pages, cache_len far past the table
        t = np.asarray(window_page_table(jnp.array([400]), 4, 16, 64, 1)[0])
        assert t.max() < 4                        # nothing aliased onto p3
        live = t[t >= 0]
        assert len(live) == len(set(live.tolist()))
        # every window page (ids 20..24) is out of range -> skipped
        assert set(live.tolist()) <= {0, 1, 2, 3}
        assert (t == -1).sum() >= 5

    def test_partially_beyond_table_keeps_in_range_pages(self):
        # cache_len 100 -> last_page 6; table width 5: pages 5,6 skipped,
        # pages 2..4 of the window survive
        t = np.asarray(window_page_table(jnp.array([100]), 5, 16, 64, 1)[0])
        live = set(t[t >= 0].tolist())
        assert live == {0, 2, 3, 4}
        assert t.max() < 5
