"""Opt-GQA (Eq. 7/8) and Opt-Pa (Eq. 9/10) numerics over the GLOBAL pool
(here a pool of one layer, attended at layer 0)."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.coopt import CoOptConfig, MODES
from repro.core.opt_gqa import fold_queries, group_index, mha_to_gqa, \
    unfold_outputs
from repro.core.opt_kv import identity_page_table
from repro.core.opt_pa import effective_page_group, paged_decode_attention
from repro.cache.quant import quantize_fp8
from repro.models.layers import causal_attention, repeat_kv


# ------------------------------------------------------------- Opt-GQA -----
def test_group_index_eq7():
    # H_q = 8, H_k = 2 -> H_g = 4; head i maps to group i // 4
    assert [group_index(i, 8, 2) for i in range(8)] == [0] * 4 + [1] * 4


def test_fold_unfold_roundtrip():
    q = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 16))
    assert jnp.all(unfold_outputs(fold_queries(q, 2)) == q)


def test_mha_to_gqa_mean_pools():
    wk = jnp.arange(4 * 8, dtype=jnp.float32).reshape(4, 8)  # d=4, Hq=4, D=2
    pk, _ = mha_to_gqa(wk, wk, num_kv_heads=2, head_dim=2)
    assert pk.shape == (4, 4)
    # group 0 = heads {0,1}: mean of cols (0,1) and (2,3)
    np.testing.assert_allclose(np.asarray(pk[:, 0]),
                               np.asarray((wk[:, 0] + wk[:, 2]) / 2))


def test_grouped_equals_expanded_attention():
    """Opt-GQA restructuring is numerically identical to MHA over
    duplicated KV heads (the paper's accuracy-preservation claim)."""
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (2, 32, 8, 16), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 2, 16), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (2, 32, 2, 16), jnp.float32)
    grouped = causal_attention(q, k, v)
    expanded = causal_attention(q, repeat_kv(k, 4), repeat_kv(v, 4))
    np.testing.assert_allclose(np.asarray(grouped), np.asarray(expanded),
                               atol=1e-5)


# ------------------------------------------------------------- Opt-Pa ------
def _paged(B=2, P=8, ps=16, Hq=8, Hkv=2, D=32, opt_kv=False, seed=0):
    """Global pool of one layer holding B lanes x P pages each
    (lane-identity layout)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    PT = B * P
    q = jax.random.normal(ks[0], (B, Hq, D), jnp.float32)
    k = jax.random.normal(ks[1], (PT, Hkv, ps, D), jnp.float32)
    v = jax.random.normal(ks[2], (PT, Hkv, ps, D), jnp.float32)
    if opt_kv:
        kq, ksc = quantize_fp8(k)
        vq, vsc = quantize_fp8(v)
        return q, jnp.stack([kq, vq])[None], jnp.stack([ksc, vsc])[None]
    return q, jnp.stack([k, v])[None].astype(jnp.bfloat16), None


@settings(max_examples=10, deadline=None)
@given(cache_len=st.integers(1, 128), seed=st.integers(0, 100))
def test_blockwise_softmax_equals_flat(cache_len, seed):
    """Eq. 10 online block-wise softmax == flat softmax, any context len."""
    q, kv, sc = _paged(seed=seed)
    cl = jnp.array([cache_len, max(cache_len // 2, 1)], jnp.int32)
    flat = paged_decode_attention(q, kv, sc, 0, cl,
                                  coopt=CoOptConfig(opt_pa=False))
    blk = paged_decode_attention(q, kv, sc, 0, cl,
                                 coopt=CoOptConfig(opt_pa=True, page_group=2))
    np.testing.assert_allclose(np.asarray(flat, np.float32),
                               np.asarray(blk, np.float32), atol=2e-2)


def test_all_modes_agree_bf16():
    """The five paper modes are schedules, not approximations (fp8 aside):
    original / opt-gqa / opt-pa must agree to bf16 tolerance."""
    q, kv, sc = _paged()
    cl = jnp.array([100, 37], jnp.int32)
    outs = {}
    for name in ("original", "opt-gqa", "opt-pa"):
        outs[name] = np.asarray(paged_decode_attention(
            q, kv, sc, 0, cl, coopt=MODES[name]), np.float32)
    np.testing.assert_allclose(outs["original"], outs["opt-gqa"], atol=2e-2)
    np.testing.assert_allclose(outs["original"], outs["opt-pa"], atol=2e-2)


def test_effective_page_group_pads_instead_of_degrading():
    """Regression: a page_group that does not divide P used to be halved
    all the way to 1 — a silent per-page scan with none of Eq. 10's block
    reduction. The page axis is now PADDED (masked) to the next multiple,
    keeping the configured group."""
    assert effective_page_group(8, 3) == (3, 9)     # pad 8 -> 9, group 3
    assert effective_page_group(8, 8) == (8, 8)     # divides: no pad
    assert effective_page_group(2, 8) == (2, 2)     # clamped to pool size
    assert effective_page_group(7, 4) == (4, 8)
    assert effective_page_group(1, 8) == (1, 1)


def test_blockwise_nondividing_page_group_matches_flat():
    """Numerics with the padded page axis: page_group=3 over an 8-page lane
    must equal the flat softmax (the pad pages are fully masked)."""
    q, kv, sc = _paged()
    cl = jnp.array([100, 37], jnp.int32)
    flat = paged_decode_attention(q, kv, sc, 0, cl,
                                  coopt=CoOptConfig(opt_pa=False))
    blk = paged_decode_attention(
        q, kv, sc, 0, cl, coopt=CoOptConfig(opt_pa=True, page_group=3))
    np.testing.assert_allclose(np.asarray(flat, np.float32),
                               np.asarray(blk, np.float32), atol=2e-2)


def test_explicit_page_table_matches_identity_default():
    """Passing the lane-identity table explicitly == the default."""
    q, kv, sc = _paged()
    cl = jnp.array([100, 37], jnp.int32)
    pt = identity_page_table(2, kv.shape[2])
    a = paged_decode_attention(q, kv, sc, 0, cl, coopt=MODES["opt-pa"])
    b = paged_decode_attention(q, kv, sc, 0, cl, coopt=MODES["opt-pa"],
                               page_table=pt)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_permuted_page_table_matches_contiguous():
    """A lane whose pages are scattered across the pool (the whole point of
    the shared allocator) must attend identically to a contiguous lane with
    the same logical content."""
    B, P, ps, Hq, Hkv, D = 1, 4, 16, 4, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (B, Hq, D), jnp.float32)
    pages_k = jax.random.normal(ks[1], (P, Hkv, ps, D), jnp.float32)
    pages_v = jax.random.normal(ks[2], (P, Hkv, ps, D), jnp.float32)
    perm = [2, 0, 3, 1]                       # physical placement
    scat_k = jnp.zeros((8, Hkv, ps, D)).at[jnp.array(perm)].set(pages_k)
    scat_v = jnp.zeros((8, Hkv, ps, D)).at[jnp.array(perm)].set(pages_v)
    cl = jnp.array([P * ps], jnp.int32)
    a = paged_decode_attention(
        q, jnp.stack([pages_k, pages_v])[None].astype(jnp.bfloat16), None, 0,
        cl,
        coopt=MODES["opt-pa"],
        page_table=jnp.arange(P, dtype=jnp.int32)[None])
    b = paged_decode_attention(
        q, jnp.stack([scat_k, scat_v])[None].astype(jnp.bfloat16), None, 0,
        cl,
        coopt=MODES["opt-pa"],
        page_table=jnp.array(perm, jnp.int32)[None])
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), atol=1e-5)


def test_fp8_mode_close_to_bf16():
    q, kvq, scq = _paged(opt_kv=True)
    _, kvb, _ = _paged(opt_kv=False)
    cl = jnp.array([128, 64], jnp.int32)
    a = paged_decode_attention(q, kvb, None, 0, cl, coopt=MODES["original"])
    b = paged_decode_attention(q, kvq, scq, 0, cl, coopt=MODES["coopt"])
    # fp8 K/V perturbs attention outputs by O(2^-3) of value scale
    err = np.abs(np.asarray(a, np.float32) - np.asarray(b, np.float32))
    assert err.max() < 0.25, err.max()


def test_window_policy_matches_dense_when_window_covers_all():
    """Window >= context => block-sparse result == dense result."""
    q, kv, sc = _paged(P=4)
    cl = jnp.array([64, 40], jnp.int32)
    dense = paged_decode_attention(q, kv, sc, 0, cl, coopt=MODES["original"])
    win = paged_decode_attention(q, kv, sc, 0, cl, coopt=MODES["original"],
                                 window=4 * 16, sink_pages=1)
    np.testing.assert_allclose(np.asarray(dense, np.float32),
                               np.asarray(win, np.float32), atol=2e-2)


def test_window_policy_drops_middle_tokens():
    """With a small window, only {sink + recent window} tokens attend."""
    B, P, ps, Hq, Hkv, D = 1, 8, 16, 4, 1, 32
    q = jnp.ones((B, Hq, D), jnp.float32)
    k = jnp.zeros((P, Hkv, ps, D))
    # middle token with huge key would dominate IF not skipped
    k = k.at[3, :, 0].set(100.0)
    v = jnp.ones_like(k)
    kv = jnp.stack([k, v])[None].astype(jnp.bfloat16)
    cl = jnp.array([128], jnp.int32)
    out = paged_decode_attention(q, kv, None, 0, cl, coopt=MODES["original"],
                                 window=32, sink_pages=1)
    # all values are 1 where attended; the spike token is outside the window
    np.testing.assert_allclose(np.asarray(out, np.float32), 1.0, atol=1e-2)
