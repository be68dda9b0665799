"""Chunked prefill (Sarathi-style continuation) must equal monolithic
prefill: same cache contents, same final logits, decode continues
identically — now over the GLOBAL pool (chunks carry global slot indices
under the lane-identity partition)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.coopt import COOPT, ORIGINAL
from repro.core.opt_kv import identity_slots
from repro.models import get_model


@pytest.mark.parametrize("arch", ["qwen3-4b-reduced", "yi-34b-reduced",
                                  "deepseek-v2-lite-16b-reduced"])
@pytest.mark.parametrize("coopt", [ORIGINAL, COOPT], ids=["bf16", "coopt"])
def test_chunked_equals_monolithic_prefill(arch, coopt):
    cfg = get_config(arch)
    m = get_model(cfg)
    p = m.init(jax.random.PRNGKey(0))
    B, S, C = 2, 64, 16
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                              cfg.vocab_size)

    mono_cache = m.init_cache(B, S + 8, coopt)
    mono_logits, mono_cache, _ = m.prefill(p, {"tokens": toks}, mono_cache,
                                        coopt)

    ch_cache = m.init_cache(B, S + 8, coopt)
    # mla latent pool: (L, P, ps, R+dr); others: (L, 2, P, ps, Hkv, D)
    P_total = ch_cache["kv"].shape[1 if cfg.family == "mla" else 2]
    for i in range(0, S, C):
        pos = jnp.broadcast_to(jnp.arange(i, i + C), (B, C)).astype(jnp.int32)
        slots = identity_slots(B, pos, P_total, coopt.page_size)
        ch_logits, ch_cache, _ = m.prefill(
            p, {"tokens": toks[:, i:i + C], "positions": pos,
                "slot_idx": slots}, ch_cache, coopt)

    np.testing.assert_array_equal(np.asarray(ch_cache["length"]),
                                  np.asarray(mono_cache["length"]))
    a = np.asarray(mono_logits, np.float32)
    b = np.asarray(ch_logits, np.float32)
    # chunked reads its keys back through the (possibly fp8) cache: allow
    # quantization skew in coopt mode, near-exact in bf16 mode
    atol = (0.15 if coopt.opt_kv else 0.05) * max(np.abs(a).max(), 1.0)
    np.testing.assert_allclose(a, b, atol=atol)

    # decode continues identically from either cache
    tok = jnp.argmax(mono_logits, -1)[:, None].astype(jnp.int32)
    d1, _, _ = m.decode_step(p, {"token": tok}, mono_cache, coopt)
    d2, _, _ = m.decode_step(p, {"token": tok}, ch_cache, coopt)
    np.testing.assert_allclose(np.asarray(d1, np.float32),
                               np.asarray(d2, np.float32), atol=atol)


def test_mixed_step_decode_lane_matches_pure_decode():
    """A decode token fed through the chunked path (chunk of length 1, the
    token-budget scheduler's mixed step) must produce the same logits as the
    dedicated decode path — bf16 mode, exact schedule equivalence."""
    cfg = get_config("qwen3-4b-reduced")
    m = get_model(cfg)
    p = m.init(jax.random.PRNGKey(0))
    B, S = 1, 24
    coopt = ORIGINAL
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                              cfg.vocab_size)
    cache = m.init_cache(B, S + 8, coopt)
    logits, cache, _ = m.prefill(p, {"tokens": toks}, cache, coopt)
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)

    P_total = cache["kv"].shape[2]
    pos = jnp.full((B, 1), S, jnp.int32)
    slots = identity_slots(B, pos, P_total, coopt.page_size)
    via_decode, _, _ = m.decode_step(
        p, {"token": tok, "positions": pos, "slot_idx": slots,
            "cache_len": jnp.full((B,), S + 1, jnp.int32)}, cache, coopt)
    via_chunk, _, _ = m.prefill(
        p, {"tokens": tok, "positions": pos, "slot_idx": slots,
            "cache_len": jnp.full((B,), S + 1, jnp.int32),
            "last_pos": jnp.zeros((B,), jnp.int32)}, cache, coopt)
    a = np.asarray(via_decode, np.float32)
    b = np.asarray(via_chunk, np.float32)
    atol = 0.05 * max(np.abs(a).max(), 1.0)
    np.testing.assert_allclose(a, b, atol=atol)


def test_mixed_step_decode_lane_matches_pure_decode_mla():
    """MLA's absorbed chunk attention (chunk of length 1) must agree with
    its absorbed paged decode — same matrix-absorption, same latent bytes."""
    cfg = get_config("deepseek-v2-lite-16b-reduced")
    m = get_model(cfg)
    p = m.init(jax.random.PRNGKey(0))
    B, S = 1, 24
    coopt = ORIGINAL
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                              cfg.vocab_size)
    cache = m.init_cache(B, S + 8, coopt)
    logits, cache, _ = m.prefill(p, {"tokens": toks}, cache, coopt)
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)

    P_total = cache["kv"].shape[1]
    pos = jnp.full((B, 1), S, jnp.int32)
    slots = identity_slots(B, pos, P_total, coopt.page_size)
    via_decode, _, _ = m.decode_step(
        p, {"token": tok, "positions": pos, "slot_idx": slots,
            "cache_len": jnp.full((B,), S + 1, jnp.int32)}, cache, coopt)
    via_chunk, _, _ = m.prefill(
        p, {"tokens": tok, "positions": pos, "slot_idx": slots,
            "cache_len": jnp.full((B,), S + 1, jnp.int32),
            "last_pos": jnp.zeros((B,), jnp.int32)}, cache, coopt)
    a = np.asarray(via_decode, np.float32)
    b = np.asarray(via_chunk, np.float32)
    atol = 0.05 * max(np.abs(a).max(), 1.0)
    np.testing.assert_allclose(a, b, atol=atol)
