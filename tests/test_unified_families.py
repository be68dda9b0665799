"""The ONE ragged step path (PR 3 acceptance): every model family runs
chunked continuation prefill through the same engine/scheduler composition —
greedy parity chunked-vs-whole-prompt, prefix-cache reuse on repeated
prompts, preemption with token-identical greedy resume, recurrent state
threaded across chunks, and fair mixed-step timing attribution.

All output comparisons run greedy in ORIGINAL (bf16) mode so schedule
differences can only surface as genuine numeric differences.
"""
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.coopt import COOPT, MODES, ORIGINAL
from repro.serving import Engine, EngineConfig, Request
from repro.serving.request import RequestState

FAMILIES = ["qwen3-4b", "deepseek-v2-lite-16b", "internvl2-2b",
            "whisper-small", "rwkv6-7b", "recurrentgemma-9b"]
RECURRENT = ["rwkv6-7b", "recurrentgemma-9b"]


def _cfg(arch):
    return get_config(arch + "-reduced")


def _prompt(cfg, n, seed=0):
    return np.random.default_rng(seed).integers(0, cfg.vocab_size, n,
                                                dtype=np.int32)


# ---------------------------------------------------------------- parity --
@pytest.mark.parametrize("arch", FAMILIES)
def test_chunked_vs_whole_prompt_greedy_parity(arch):
    """Small buckets force multi-chunk prefill; big buckets serve the whole
    prompt in one chunk. Both run the SAME continuation path over the same
    cached bytes, so greedy outputs are identical."""
    cfg = _cfg(arch)
    prompt = _prompt(cfg, 100, seed=1)
    outs = []
    for buckets in ((16, 32), (64, 128, 256)):
        eng = Engine(cfg, ORIGINAL,
                     EngineConfig(num_lanes=2, max_len=256,
                                  prefill_buckets=buckets))
        outs.append(eng.generate([prompt], max_new_tokens=8)[0])
        assert len(outs[-1]) == 8
    assert outs[0] == outs[1]


# ---------------------------------------------------------------- prefix --
@pytest.mark.parametrize("arch", FAMILIES)
def test_prefix_cache_hits_on_repeated_prompt(arch):
    """A repeated prompt (>= 1 full page) prefix-hits for EVERY family —
    attention families reuse KV/latent pages; recurrent families also
    restore the page-boundary state snapshot — with identical greedy
    output warm vs cold."""
    cfg = _cfg(arch)
    prompt = _prompt(cfg, 100, seed=2)                # > page_size 64
    eng = Engine(cfg, ORIGINAL,
                 EngineConfig(num_lanes=2, max_len=256,
                              prefill_buckets=(16, 32, 64, 128)))
    cold = eng.generate([prompt], max_new_tokens=4)[0]
    warm = eng.generate([prompt], max_new_tokens=4)[0]
    assert eng.stats.prefix_cache_hits > 0
    assert cold == warm


# ------------------------------------------------------------ preemption --
@pytest.mark.parametrize("arch", FAMILIES)
def test_preempt_and_resume_token_identical(arch):
    """An over-subscribed pool completes via preemption with outputs
    identical to an unconstrained run — uniformly, including the families
    that used to run the monolithic tier."""
    cfg = _cfg(arch)
    # admit on one page each, collide on the shared 3rd page during decode
    # growth (vlm's 16-position patch stub counts against its page)
    plen = 44 if cfg.family == "vlm" else 50
    prompts = [_prompt(cfg, plen, seed=3 + i) for i in range(2)]
    tight = EngineConfig(num_lanes=2, max_len=128,
                         prefill_buckets=(16, 32, 64, 128))
    roomy = EngineConfig(num_lanes=2, max_len=256,
                         prefill_buckets=(16, 32, 64, 128, 256))
    eng_t = Engine(cfg, ORIGINAL, tight)
    out_t = eng_t.generate(prompts, max_new_tokens=20)
    eng_r = Engine(cfg, ORIGINAL, roomy)
    out_r = eng_r.generate(prompts, max_new_tokens=20)
    assert eng_t.stats.preemptions > 0
    assert eng_r.stats.preemptions == 0
    assert all(len(o) == 20 for o in out_t)
    assert out_t == out_r


# ---------------------------------------------------- recurrent regression --
@pytest.mark.parametrize("arch", RECURRENT)
def test_recurrent_state_threads_across_chunks(arch):
    """Model-level regression: feeding a prompt as N continuation chunks
    (state after chunk k = input state of chunk k+1) matches the monolithic
    single-call prefill — final logits and recurrent state agree."""
    import jax
    import jax.numpy as jnp
    from repro.core.opt_kv import identity_slots
    from repro.models import get_model

    cfg = _cfg(arch)
    m = get_model(cfg)
    p = m.init(jax.random.PRNGKey(0))
    B, S, C = 2, 48, 16
    coopt = ORIGINAL
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                              cfg.vocab_size)

    mono_cache = m.init_cache(B, S + 16, coopt)
    mono_logits, mono_cache, _ = m.prefill(p, {"tokens": toks}, mono_cache,
                                        coopt)

    ch_cache = m.init_cache(B, S + 16, coopt)
    P_total = (ch_cache["kv"].shape[2] if "kv" in ch_cache
               else 1)                                   # rwkv6: no pool
    for i in range(0, S, C):
        pos = jnp.broadcast_to(jnp.arange(i, i + C), (B, C)).astype(jnp.int32)
        slots = identity_slots(B, pos, P_total, coopt.page_size)
        ch_logits, ch_cache, _ = m.prefill(
            p, {"tokens": toks[:, i:i + C], "positions": pos,
                "slot_idx": slots,
                "cache_len": jnp.full((B,), i + C, jnp.int32)},
            ch_cache, coopt)

    a = np.asarray(mono_logits, np.float32)
    b = np.asarray(ch_logits, np.float32)
    atol = 0.05 * max(np.abs(a).max(), 1.0)
    np.testing.assert_allclose(a, b, atol=atol)
    # the carried state itself must agree, not just the logits
    for leaf in m.recurrent_leaves:
        x = np.asarray(mono_cache[leaf], np.float32)
        y = np.asarray(ch_cache[leaf], np.float32)
        np.testing.assert_allclose(
            x, y, atol=0.05 * max(np.abs(x).max(), 1.0),
            err_msg=f"{arch} state leaf {leaf} diverged across chunks")


@pytest.mark.parametrize("arch", RECURRENT)
def test_recurrent_lane_reuse_does_not_leak_state(arch):
    """A request admitted on a lane previously used by another request must
    see ZERO initial state, not the previous occupant's — its output equals
    a fresh-engine run of the same prompt."""
    cfg = _cfg(arch)
    ecfg = EngineConfig(num_lanes=1, max_len=256,
                        prefill_buckets=(16, 32, 64),
                        enable_prefix_cache=False)
    p1, p2 = _prompt(cfg, 40, seed=7), _prompt(cfg, 40, seed=8)
    eng = Engine(cfg, ORIGINAL, ecfg)
    eng.generate([p1], max_new_tokens=4)                # dirties lane 0
    reused = eng.generate([p2], max_new_tokens=4)[0]
    fresh = Engine(cfg, ORIGINAL, ecfg).generate([p2], max_new_tokens=4)[0]
    assert reused == fresh


@pytest.mark.parametrize("arch", RECURRENT)
def test_recurrent_prefix_hit_with_multi_page_chunk(arch):
    """Regression: a prompt prefilled as ONE multi-page chunk snapshots
    state only at the chunk-end boundary; matching must TRIM to that
    boundary (deepest gated hash), not break at the first page whose hash
    lacks a snapshot — which yielded zero hits."""
    cfg = _cfg(arch)
    prompt = _prompt(cfg, 200, seed=11)             # 3 full pages + tail
    eng = Engine(cfg, ORIGINAL,
                 EngineConfig(num_lanes=2, max_len=256,
                              prefill_buckets=(64, 128, 256)))
    cold = eng.generate([prompt], max_new_tokens=4)[0]
    warm = eng.generate([prompt], max_new_tokens=4)[0]
    assert eng.stats.prefix_cache_hits >= 3         # all 3 full pages reused
    assert cold == warm


def test_long_window_decode_schedule_independent():
    """Regression: with ``long_window`` set, a decode token must get the
    same {sink + sliding window} policy whether its step is decode-only or
    shares the device call with another request's prefill chunks."""
    cfg = _cfg("qwen3-4b")
    ecfg = EngineConfig(num_lanes=2, max_len=256,
                        prefill_buckets=(16, 32, 64, 128), long_window=32)
    r1 = _prompt(cfg, 120, seed=12)
    r2 = _prompt(cfg, 100, seed=13)

    eng_solo = Engine(cfg, ORIGINAL, ecfg)
    solo = eng_solo.generate([r1], max_new_tokens=10)[0]

    eng_mix = Engine(cfg, ORIGINAL, ecfg)
    req1 = Request(req_id=1, prompt=r1, max_new_tokens=10)
    eng_mix.add_request(req1)
    for _ in range(6):                              # r1 reaches decode
        eng_mix.step()
    eng_mix.add_request(Request(req_id=2, prompt=r2, max_new_tokens=10))
    eng_mix.run()                                   # r1 decodes in MIXED steps
    assert eng_mix.stats.mixed_steps > 0
    assert req1.output == solo


# ------------------------------------------------------- timing / latency --
def test_mixed_step_timing_attribution_and_latency_metrics():
    """Mixed-step wall time splits by planned token share: a prefill-only
    run books nothing under decode_time, and a decode-bearing run books
    both. Per-request TTFT/TPOT percentiles populate from finished
    requests."""
    cfg = _cfg("qwen3-4b")
    ecfg = EngineConfig(num_lanes=2, max_len=128,
                        prefill_buckets=(16, 32, 64))
    eng = Engine(cfg, MODES["coopt"], ecfg)
    prompts = [_prompt(cfg, 40, seed=9), _prompt(cfg, 30, seed=10)]

    reqs = eng.generate(prompts, max_new_tokens=1, return_requests=True)
    assert all(r.state is RequestState.FINISHED for r in reqs)
    assert eng.stats.prefill_time > 0
    assert eng.stats.decode_time == 0                  # no decode tokens ran
    assert len(eng.stats.ttft_s) == 2
    assert all(t > 0 for t in eng.stats.ttft_s)
    assert eng.stats.tpot_s == []                      # 1 token: no TPOT

    out = eng.generate(prompts, max_new_tokens=8)
    assert all(len(o) == 8 for o in out)
    assert eng.stats.decode_time > 0
    assert eng.stats.prefill_time > 0
    assert len(eng.stats.tpot_s) == 2
    summ = eng.stats.latency_summary()
    assert summ["ttft_p95_s"] >= summ["ttft_p50_s"] > 0
    assert summ["tpot_p95_s"] >= summ["tpot_p50_s"] > 0


# ------------------------------------------------- MLA fused latent path --
def _served_logits_and_routes(cfg, coopt, prompts, monkeypatch,
                              forced=None):
    """Serve ``prompts`` greedily (8 tokens each) and record, per step, the
    logits of each sampled row at its sampled position and, per MoE layer,
    every token's top-k experts and the router-logit gap between its k-th
    and (k+1)-th expert (a spy around ``moe._moe_route``). ``forced``: the
    tokens another run sampled, fed back step by step (teacher forcing), so
    both runs see the same inputs at every step."""
    import jax
    import jax.numpy as jnp
    from repro.models import moe

    routes, steps = [], []
    real = moe._moe_route

    def route_spy(x, wr, valid, **kw):
        lg = jnp.dot(x, wr, preferred_element_type=jnp.float32)
        top, exp = jax.lax.top_k(lg, kw["top_k"] + 1)
        jax.debug.callback(
            lambda *a: routes.append(tuple(map(np.asarray, a))),
            exp[:, :-1], top[:, -2] - top[:, -1], valid, ordered=True)
        return real(x, wr, valid, **kw)

    monkeypatch.setattr(moe, "_moe_route", route_spy)
    eng = Engine(cfg, coopt, EngineConfig(num_lanes=2, max_len=256,
                                          prefill_buckets=(16, 32, 64, 128)))
    sample, post = eng._sample, eng._postprocess

    def sample_spy(logits):
        toks = sample(logits)
        jax.effects_barrier()
        steps.append({"logits": np.asarray(logits, np.float32),
                      "toks": toks, "routes": list(routes)})
        routes.clear()
        return toks if forced is None else forced[len(steps) - 1]["toks"]

    def post_spy(sb, toks, now):
        tok = sb.batch["tokens" if "tokens" in sb.batch else "token"]
        S = tok.shape[1]
        last = np.asarray(sb.batch.get("last_pos",
                                       np.full(tok.shape[0], S - 1)))
        # (row, flat token index of the sampled position in the step)
        steps[-1]["rows"] = [(i[0], i[0] * S + int(last[i[0]]))
                             for _, _, i in sb.samples]
        return post(sb, toks, now)

    eng._sample, eng._postprocess = sample_spy, post_spy
    out = eng.generate(prompts, max_new_tokens=8)
    monkeypatch.setattr(moe, "_moe_route", real)
    assert all(len(o) == 8 for o in out)
    return steps


@pytest.mark.parametrize("mode", ["coopt", "original"])
def test_mla_engine_use_kernel_greedy_identical(mode, monkeypatch):
    """End-to-end MLA serving through the fused latent Pallas kernels
    (absorbed decode + chunk prefill straight off the paged latent pool)
    must match the jnp parity reference — fp8 (coopt) and bf16 (original),
    across multi-chunk prefill, prefix reuse and decode — with the
    published dropless top-k routing. The kernel run is teacher-forced
    with the reference's greedy tokens, so every step sees the same inputs,
    and at each sampled row the two paths' logits agree within 0.1 and
    their greedy tokens agree wherever the reference's top-1 margin is
    clear. The two paths round differently in bf16, so where the router's
    k-th and (k+1)-th experts nearly tie the kernel run may pick the other
    one: that flip may happen only at such a near-tie, and the row whose
    own token flipped is held to 1.0 instead (a flipped expert moves a
    logit by ~0.5 here; a softmax scale off by 10% moves every row's
    logits by 0.3-1.1, one without the YaRN mscale by 2-4)."""
    cfg = _cfg("deepseek-v2-lite-16b")
    prompts = [_prompt(cfg, 100, seed=21), _prompt(cfg, 45, seed=22)]
    ref = _served_logits_and_routes(cfg, MODES[mode], prompts, monkeypatch)
    ker = _served_logits_and_routes(
        cfg, MODES[mode].replace(use_kernel=True), prompts, monkeypatch,
        forced=ref)
    assert len(ker) == len(ref) == 9
    checked = flipped = 0
    for r, k in zip(ref, ker):
        assert k["rows"] == r["rows"]
        assert len(k["routes"]) == len(r["routes"]) > 0
        flips = np.zeros(len(r["routes"][0][0]), bool)
        for (e_r, gap, valid), (e_k, _, _) in zip(r["routes"], k["routes"]):
            moved = valid & np.any(np.sort(e_r, -1) != np.sort(e_k, -1), -1)
            # a token's first flip is at a near-tie (the paths' gaps differ
            # by < 0.01); after it, its later layers see another input
            assert np.all(gap[moved & ~flips] < 0.05), gap[moved & ~flips]
            flips |= moved
        for row, tok in r["rows"]:
            a, b = r["logits"][row], k["logits"][row]
            if flips[tok]:
                flipped += 1
                assert np.max(np.abs(a - b)) < 1.0
                continue
            checked += 1
            assert np.max(np.abs(a - b)) < 0.1
            top2 = np.sort(a)[-2:]
            if top2[1] - top2[0] > 0.2:
                assert a.argmax() == b.argmax()
    assert checked >= 14 and checked + flipped == 16


def test_mla_engine_use_kernel_windowed_greedy_identical():
    """The windowed latent-kernel variant ({sink + sliding window}
    block-sparse policy) matches the jnp reference through the engine."""
    cfg = _cfg("deepseek-v2-lite-16b")
    prompts = [_prompt(cfg, 120, seed=23)]
    outs = []
    for uk in (False, True):
        eng = Engine(cfg, MODES["coopt"].replace(use_kernel=uk),
                     EngineConfig(num_lanes=2, max_len=256,
                                  prefill_buckets=(16, 32, 64, 128),
                                  long_window=32))
        outs.append(eng.generate(prompts, max_new_tokens=10))
    assert outs[0] == outs[1]


@pytest.mark.skipif(len(__import__("jax").devices()) < 8,
                    reason="needs XLA_FLAGS=--xla_force_host_platform_"
                           "device_count=8 (CI mesh-matrix job)")
def test_mla_kernel_engine_on_simulated_mesh():
    """mesh8 variant of the mla use_kernel engine run: the engine places
    the latent pool PAGES-SHARDED on a real (data=4, model=2) mesh and the
    fused latent kernels run per shard through the ``kernels.sharded``
    shard_map layer (global tables translated to per-shard holes, partial
    softmax states lse-merged) — one kernel hot path, single-host and
    distributed — staying greedy-identical to the meshless single-shard
    jnp reference."""
    from repro.launch.mesh import kv_shard_count, make_sim_mesh

    cfg = _cfg("deepseek-v2-lite-16b")
    mesh = make_sim_mesh(data=4, model=2)
    ns = kv_shard_count(mesh)
    assert ns == 4
    prompts = [_prompt(cfg, 70, seed=24), _prompt(cfg, 30, seed=25)]
    ecfg = EngineConfig(num_lanes=2, max_len=256,
                        prefill_buckets=(16, 32, 64, 128))

    ref = Engine(cfg, MODES["coopt"], ecfg)
    out_ref = ref.generate(prompts, max_new_tokens=5)

    eng = Engine(cfg, MODES["coopt"].replace(use_kernel=True), ecfg,
                 mesh=mesh)                   # num_shards derived = 4
    assert eng._kernel_ctx is not None
    out_mesh = eng.generate(prompts, max_new_tokens=5)
    assert out_ref == out_mesh
    assert eng.stats.num_shards == ns


def test_one_step_path_no_two_tier_scheduler():
    """The two-tier architecture is gone: the scheduler has no
    allow_chunked knob and the engine no monolithic prefill method."""
    from repro.serving.engine import Engine as E
    from repro.serving.scheduler import Scheduler as S
    assert not hasattr(E, "_run_prefill")
    assert not hasattr(E, "_run_decode")
    assert "allow_chunked" not in S.__init__.__code__.co_varnames
