"""AsyncEngine pipeline semantics: greedy token identity vs the sync loop
under interleaved submissions, cancel releasing pool pages mid-stream,
zero steady-state traces after AOT warmup, and TTFT/queue-wait provenance
(latency anchored at submission).

All generation runs greedy (temperature 0) so any pipeline reordering
could only show up as a genuine token difference.
"""
import copy

import numpy as np
import pytest

from repro.configs import get_config
from repro.core.coopt import MODES
from repro.serving import AsyncEngine, Engine, EngineConfig, Request
from repro.serving.request import RequestState
from repro.serving.sampler import SamplingParams

CFG = get_config("qwen3-4b-reduced")


def _engine(num_lanes=4, max_len=128, pack=False, seed=0):
    ecfg = EngineConfig(num_lanes=num_lanes, max_len=max_len,
                        prefill_buckets=(32, 64, 128),
                        sampling=SamplingParams(temperature=0.0),
                        seed=seed, pack_prefill=pack)
    return Engine(CFG, MODES["coopt"], ecfg)


def _prompts(n, rng, lo=4, hi=40):
    return [rng.integers(0, CFG.vocab_size, int(rng.integers(lo, hi)),
                         dtype=np.int32) for _ in range(n)]


def _sync_outputs(prompts, max_new_tokens):
    eng = _engine()
    return eng.generate(prompts, max_new_tokens=max_new_tokens)


# ---------------------------------------------------------- identity -----
def test_async_matches_sync_greedy_interleaved():
    """Interleaved submissions (a second wave submitted while the first is
    mid-decode) produce BIT-IDENTICAL greedy tokens to the synchronous
    loop serving the same prompts."""
    rng = np.random.default_rng(11)
    prompts = _prompts(6, rng)
    sync_out = _sync_outputs(prompts, 12)

    eng = _engine()
    fe = AsyncEngine(eng, warmup=True)
    streams = [fe.submit(p, max_new_tokens=12) for p in prompts[:3]]
    # run a few pipeline turns so wave 1 is mid-decode, then submit wave 2
    for _ in range(6):
        fe._loop_once()
    streams += [fe.submit(p, max_new_tokens=12) for p in prompts[3:]]
    fe.run_until_idle()

    async_out = [list(s.req.output) for s in streams]
    assert async_out == [list(o) for o in sync_out]
    assert all(s.req.state is RequestState.FINISHED for s in streams)


def test_stream_yields_all_tokens_in_order():
    rng = np.random.default_rng(3)
    prompts = _prompts(2, rng)
    eng = _engine()
    fe = AsyncEngine(eng, warmup=True)
    handles = [fe.submit(p, max_new_tokens=8) for p in prompts]
    fe.run_until_idle()
    for h in handles:
        assert list(fe.stream(h)) == list(h.req.output)
        assert len(h.req.output) == 8


# ------------------------------------------------------------ cancel -----
def test_cancel_mid_stream_releases_pool_pages_and_lane():
    """cancel() mid-generation drops the request (state CANCELLED), frees
    its lane, and returns the pool to baseline: after the surviving
    requests finish, zero pages stay referenced."""
    rng = np.random.default_rng(7)
    prompts = _prompts(3, rng, lo=8, hi=24)
    eng = _engine(num_lanes=4)
    fe = AsyncEngine(eng, warmup=True)
    victim = fe.submit(prompts[0], max_new_tokens=64)
    others = [fe.submit(p, max_new_tokens=10) for p in prompts[1:]]
    # let the victim produce a few tokens, then abandon it mid-stream
    for _ in range(8):
        fe._loop_once()
    assert len(victim.req.output) > 0
    fe.cancel(victim)
    fe.run_until_idle()

    assert victim.req.state is RequestState.CANCELLED
    assert all(o.req.state is RequestState.FINISHED for o in others)
    assert len(victim.req.output) < 64          # stopped early
    # lane freed and every page back to the allocator
    assert not eng.scheduler.running
    eng._update_pool_stats()
    assert eng.stats.pages_in_use == 0
    # the victim's stream is closed: iteration terminates and yields
    # exactly the tokens that were emitted before the cancel landed
    assert list(victim) == list(victim.req.output)


def test_cancelled_tokens_never_reach_stream_after_cancel():
    rng = np.random.default_rng(9)
    eng = _engine(num_lanes=2)
    fe = AsyncEngine(eng, warmup=True)
    h = fe.submit(_prompts(1, rng)[0], max_new_tokens=64)
    for _ in range(4):
        fe._loop_once()
    fe.cancel(h)
    n_at_cancel = len(h.req.output)
    fe.run_until_idle()
    # the pipeline may deliver at most the already-dispatched steps
    assert len(h.req.output) <= n_at_cancel + 2


# -------------------------------------------------- AOT / zero-retrace ---
def test_zero_traces_after_warmup():
    """After ``warmup()`` pre-compiles the bucket lattice, a serving run
    performs ZERO new jit traces and never misses the AOT cache."""
    rng = np.random.default_rng(5)
    prompts = _prompts(5, rng)
    eng = _engine()
    fe = AsyncEngine(eng, warmup=True)
    assert fe.warmed_shapes > 0
    traces = dict(eng.trace_counts)
    for p in prompts:
        fe.submit(p, max_new_tokens=10)
    fe.run_until_idle()
    assert eng.aot_misses == 0
    assert eng.trace_counts == traces


def test_warmup_covers_packed_lattice_too():
    eng = _engine(pack=True)
    fe = AsyncEngine(eng, warmup=True)
    traces = dict(eng.trace_counts)
    rng = np.random.default_rng(13)
    for p in _prompts(5, rng, lo=4, hi=20):
        fe.submit(p, max_new_tokens=6)
    fe.run_until_idle()
    assert eng.aot_misses == 0
    assert eng.trace_counts == traces
    assert eng.stats.packed_steps > 0


# ------------------------------------------------- latency provenance ----
def test_ttft_measured_from_submission_includes_queue_wait():
    """More requests than lanes: the overflow request queues, so its TTFT
    (anchored at submit time) must include the queue wait, and
    ``queue_wait_s`` percentiles are populated."""
    rng = np.random.default_rng(21)
    prompts = _prompts(5, rng, lo=8, hi=24)
    eng = _engine(num_lanes=2)
    fe = AsyncEngine(eng, warmup=True)
    for p in prompts:
        fe.submit(p, max_new_tokens=8)
    fe.run_until_idle()

    s = eng.stats
    assert len(s.ttft_s) == len(prompts)
    assert len(s.queue_wait_s) == len(prompts)
    assert all(t > 0 for t in s.ttft_s)
    assert all(q >= 0 for q in s.queue_wait_s)
    # every TTFT contains that request's queue wait
    assert all(t >= q for t, q in zip(sorted(s.ttft_s),
                                      sorted(s.queue_wait_s)))
    summary = s.latency_summary()
    for k in ("ttft_p50_s", "ttft_p95_s", "tpot_p50_s", "tpot_p95_s",
              "queue_wait_p50_s", "queue_wait_p95_s"):
        assert k in summary
    # with 5 requests on 2 lanes SOMEONE waited for a lane
    assert summary["queue_wait_p95_s"] > 0


def test_sync_generate_stamps_real_submission_times():
    rng = np.random.default_rng(2)
    eng = _engine(num_lanes=2)
    reqs = eng.generate(_prompts(4, rng, lo=6, hi=16), max_new_tokens=4,
                        return_requests=True)
    assert all(r.submit_time > 0 for r in reqs)
    assert all(r.admit_time >= r.submit_time for r in reqs)
    assert len(eng.stats.queue_wait_s) == 4
