"""Shared-pool serving semantics: prefix-cache reuse, preempt-and-resume,
and rejection surfacing (the PR's acceptance criteria).

All comparisons run greedy (temperature 0) so scheduling differences can
only show up as genuine numeric differences.
"""
import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.coopt import MODES, ORIGINAL
from repro.core.opt_kv import identity_slots
from repro.models import get_model
from repro.serving import Engine, EngineConfig, Request
from repro.serving.request import RequestState

CFG = get_config("qwen3-4b-reduced")


def _prompt(rng, n):
    return rng.integers(0, CFG.vocab_size, n, dtype=np.int32)


# ---------------------------------------------------------------- prefix --
def test_prefix_cache_model_level_bit_identical_logits():
    """Warm prefill (shared prefix pages reused, only the tail computed)
    returns BIT-IDENTICAL last-token logits vs recomputing everything with
    the same chunk schedule — reused pages hold exactly the bytes the cold
    path would have written."""
    m = get_model(CFG)
    p = m.init(jax.random.PRNGKey(0))
    coopt = MODES["coopt"]                    # fp8 cache: bytes, not floats
    ps = coopt.page_size
    B, prefix_len, tail_len = 2, ps, 16       # prefix = exactly one page
    rng = np.random.default_rng(0)
    prefix = _prompt(rng, prefix_len)
    tail = _prompt(rng, tail_len)

    cache = m.init_cache(B, 2 * ps, coopt)
    P_total = cache["kv"].shape[2]            # 4 pages: lane0 {0,1} lane1 {2,3}

    def chunk(cache, lane, tokens, start, page_table):
        n = len(tokens)
        toks = np.zeros((B, n), np.int32)
        toks[lane] = tokens
        pos = np.broadcast_to(np.arange(start, start + n), (B, n))
        slots = np.full((B, n), -1, np.int32)
        slots[lane] = np.asarray(
            identity_slots(B, jnp.asarray(pos), P_total, ps))[lane]
        logits, cache, _ = m.prefill(
            p, {"tokens": jnp.asarray(toks),
                "positions": jnp.asarray(pos.astype(np.int32)),
                "slot_idx": jnp.asarray(slots),
                "page_table": jnp.asarray(page_table)}, cache, coopt)
        return logits, cache

    own = np.asarray(jnp.stack([jnp.array([0, 1]), jnp.array([2, 3])]),
                     np.int32)
    # lane 0: cold — prefix chunk then tail chunk into its own pages
    _, cache = chunk(cache, 0, prefix, 0, own)
    cold_logits, cache = chunk(cache, 0, tail, prefix_len, own)
    # lane 1 COLD REFERENCE: same two chunks into its own pages
    _, cache_ref = chunk(cache, 1, prefix, 0, own)
    ref_logits, _ = chunk(cache_ref, 1, tail, prefix_len, own)
    # lane 1 WARM: skip the prefix — page table aliases lane 0's prefix page
    shared = own.copy()
    shared[1, 0] = 0                           # lane 1 reads lane 0's page 0
    warm_logits, _ = chunk(cache, 1, tail, prefix_len, shared)

    np.testing.assert_array_equal(np.asarray(ref_logits[1]),
                                  np.asarray(warm_logits[1]))
    np.testing.assert_array_equal(np.asarray(cold_logits[0]),
                                  np.asarray(warm_logits[1]))


def test_prefix_cache_engine_fewer_pages_and_same_tokens():
    """Acceptance: two requests sharing a >= 1-page prompt prefix allocate
    fewer total pages than two cold requests (pool-utilization stat) and
    generate identical greedy tokens."""
    rng = np.random.default_rng(1)
    ps = MODES["coopt"].page_size
    shared = _prompt(rng, 2 * ps)             # 2 full shared pages
    tails = [_prompt(rng, 7), _prompt(rng, 9)]
    prompts = [np.concatenate([shared, t]) for t in tails]
    ecfg = EngineConfig(num_lanes=2, max_len=256,
                        prefill_buckets=(16, 32, 64, 128, 256))

    outs, stats = {}, {}
    for label, enabled in (("warm", True), ("cold", False)):
        eng = Engine(CFG, MODES["coopt"],
                     EngineConfig(**{**ecfg.__dict__,
                                     "enable_prefix_cache": enabled}))
        # sequential: the second request arrives after the first finished,
        # so its full prompt pages are committed and reusable
        o1 = eng.generate([prompts[0]], max_new_tokens=4)
        o2 = eng.generate([prompts[1]], max_new_tokens=4)
        outs[label] = (o1, o2)
        stats[label] = eng.stats

    assert outs["warm"] == outs["cold"]
    assert stats["warm"].prefix_cache_hits >= 2          # 2 full pages reused
    assert stats["warm"].fresh_pages_allocated < \
        stats["cold"].fresh_pages_allocated
    assert stats["cold"].prefix_cache_hits == 0


# ------------------------------------------------------------ preemption --
def test_preempt_and_resume_matches_unconstrained_greedy():
    """Acceptance: an over-subscribed workload (aggregate demand > pool)
    completes via preemption with outputs identical to an unconstrained run
    under greedy sampling."""
    rng = np.random.default_rng(2)
    prompts = [_prompt(rng, 50), _prompt(rng, 50)]
    mode = ORIGINAL                           # bf16: bit-stable recompute

    # pool = lanes * pages(max_len) - 1 = 3 pages of 64 tokens; demand =
    # 2 * ceil(70/64) = 4 pages -> must preempt
    tight = EngineConfig(num_lanes=2, max_len=128,
                         prefill_buckets=(16, 32, 64, 128))
    roomy = EngineConfig(num_lanes=2, max_len=256,
                         prefill_buckets=(16, 32, 64, 128, 256))

    eng_t = Engine(CFG, mode, tight)
    out_t = eng_t.generate(prompts, max_new_tokens=20)
    eng_r = Engine(CFG, mode, roomy)
    out_r = eng_r.generate(prompts, max_new_tokens=20)

    assert eng_t.stats.preemptions > 0
    assert eng_r.stats.preemptions == 0
    assert all(len(o) == 20 for o in out_t)
    assert out_t == out_r


def test_pool_smaller_than_static_partition_still_serves():
    """The point of the shared pool: lanes whose requests are short leave
    room for a long one — aggregate > per-lane share but < pool."""
    rng = np.random.default_rng(3)
    ecfg = EngineConfig(num_lanes=4, max_len=192,
                        prefill_buckets=(16, 32, 64, 128, 192))
    eng = Engine(CFG, MODES["coopt"], ecfg)
    # one long request (2.5 pages) + three tiny ones: under the old static
    # partition each lane capped at 3 pages; here they share 11
    prompts = [_prompt(rng, 150)] + [_prompt(rng, 8) for _ in range(3)]
    outs = eng.generate(prompts, max_new_tokens=4)
    assert all(len(o) == 4 for o in outs)
    assert eng.stats.peak_pages_in_use <= eng.stats.pool_pages


# ------------------------------------------------------------- rejection --
def test_rejected_state_surfaced_from_generate():
    eng = Engine(CFG, MODES["coopt"],
                 EngineConfig(num_lanes=2, max_len=128,
                              prefill_buckets=(16, 32, 64, 128)))
    ok = _prompt(np.random.default_rng(4), 20)
    too_long = _prompt(np.random.default_rng(5), 200)   # 200 + 8 > 128
    reqs = eng.generate([ok, too_long], max_new_tokens=8,
                        return_requests=True)
    assert reqs[0].state is RequestState.FINISHED
    assert len(reqs[0].output) == 8
    assert reqs[1].state is RequestState.REJECTED
    assert reqs[1].output == []
    assert eng.stats.rejected == 1


def test_prompt_over_largest_bucket_served_chunked_every_family():
    """The old "no bucket -> REJECT" rule is gone: every family serves a
    prompt larger than the largest bucket via chunked continuation prefill
    (here rwkv6, the family that used to reject)."""
    cfg = get_config("rwkv6-7b-reduced")
    eng = Engine(cfg, MODES["coopt"],
                 EngineConfig(num_lanes=2, max_len=256,
                              prefill_buckets=(16, 32)))
    big = _prompt(np.random.default_rng(6), 100)        # > bucket 32
    reqs = eng.generate([big], max_new_tokens=4, return_requests=True)
    assert reqs[0].state is RequestState.FINISHED
    assert len(reqs[0].output) == 4
    assert eng.stats.rejected == 0
    assert eng.stats.prefill_calls > 1                  # really chunked
