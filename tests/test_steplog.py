"""The serving loop's own measurement: the step log (``serving.steplog``),
the ``serve.*`` profiler spans, one named step program per step kind, and
the step time ``EngineStats`` books under the async pipeline.

All runs are greedy on the reduced qwen3-4b config on the CPU.
"""
import glob
import os
import tempfile
import time

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.coopt import MODES
from repro.serving import AsyncEngine, Engine, EngineConfig, steplog
from repro.serving.sampler import SamplingParams
from repro.serving.scheduler import bucket_len

CFG = get_config("qwen3-4b-reduced")
BUCKETS = (32, 64, 128)


def _engine(num_lanes=4, pack=False):
    ecfg = EngineConfig(num_lanes=num_lanes, max_len=128,
                        prefill_buckets=BUCKETS,
                        sampling=SamplingParams(temperature=0.0),
                        pack_prefill=pack)
    return Engine(CFG, MODES["coopt"], ecfg)


def _prompts(n, seed, lo=4, hi=40):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CFG.vocab_size, int(rng.integers(lo, hi)),
                         dtype=np.int32) for _ in range(n)]


def _records(eng):
    return [r for r in steplog.RECENT if r.engine == eng.engine_id]


@pytest.fixture(scope="module")
def served():
    """An AsyncEngine run with every dispatched plan seen from outside:
    chunk starts as the requests' progress before the step, decode
    contexts as prompt plus tokens sampled so far (emitted or in
    flight)."""
    eng = _engine()
    fe = AsyncEngine(eng, warmup=True)
    seen = []
    dispatch = eng._dispatch_async

    def spy(sb, lane_tok):
        plan = sb.plan
        seen.append({
            "chunks": tuple((c.req.num_computed, len(c.tokens))
                            for c in plan.prefill),
            "ctx": tuple(d.req.prompt_len + len(d.req.output)
                         + d.req.inflight for d in plan.decode),
            "n_max": max((len(c.tokens) for c in plan.prefill), default=0)})
        return dispatch(sb, lane_tok)

    eng._dispatch_async = spy
    t0 = time.perf_counter()
    for p in _prompts(6, seed=3):
        fe.submit(p, max_new_tokens=10)
    fe.run_until_idle()
    wall = time.perf_counter() - t0
    fe.close()
    return eng, seen, wall


def test_async_run_logs_every_dispatched_step(served):
    eng, seen, _ = served
    recs = _records(eng)
    assert [r.seq for r in recs] == list(range(len(seen)))
    assert len(recs) == eng.steps_dispatched > 0
    B = eng.ecfg.num_lanes
    for r, s in zip(recs, seen):
        assert r.chunks == s["chunks"]
        assert r.decode_ctx == s["ctx"]
        assert r.real_tokens == sum(n for _, n in s["chunks"]) + len(s["ctx"])
        if s["chunks"]:
            assert r.kind == "prefill"
            assert (r.rows, r.bucket) == (B, bucket_len(s["n_max"], BUCKETS))
        else:
            assert r.kind == "decode"
            assert (r.rows, r.bucket) == (B, 1)
        assert r.slots == r.rows * r.bucket >= r.real_tokens
        assert len(r.req_ids) == len(r.chunks) + len(r.decode_ctx)
    assert {r.kind for r in recs} == {"prefill", "decode"}


def test_fetch_follows_dispatch_and_warm_steps_never_compile(served):
    eng, _, _ = served
    recs = _records(eng)
    assert eng.aot_misses == 0
    assert not any(r.compiled for r in recs)
    for r in recs:
        assert 0 < r.t_dispatched <= r.t_fetched
        assert min(r.schedule_s, r.build_s, r.dispatch_s, r.apply_s) >= 0
        assert r.build_s > 0 and r.dispatch_s > 0 and r.apply_s > 0
        assert r.host_s == pytest.approx(
            r.schedule_s + r.build_s + r.dispatch_s + r.apply_s)
    fetched = [r.t_fetched for r in recs]
    assert fetched == sorted(fetched)          # the device's order


def test_unwarmed_step_is_logged_as_compiled():
    eng = _engine(num_lanes=2)
    fe = AsyncEngine(eng, warmup=False)
    fe.submit(_prompts(1, seed=5)[0], max_new_tokens=3)
    fe.run_until_idle()
    fe.close()
    recs = _records(eng)
    assert sum(r.compiled for r in recs) == eng.aot_misses == len(recs)


def test_async_stats_book_step_time(served):
    """Counts at dispatch, time when applied: the steps' times are
    disjoint spans of the run, so they sum to no more than its wall
    time (paper Eq. 11/12 read real numbers under the pipeline)."""
    eng, _, wall = served
    s = eng.stats
    recs = _records(eng)
    assert s.prefill_calls == sum(bool(r.chunks) for r in recs)
    assert s.decode_steps == sum(bool(r.decode_ctx) for r in recs)
    assert s.decode_time > 0 and s.prefill_time > 0
    assert s.prefill_time + s.decode_time <= wall
    assert s.throughput() > 0


def test_sync_generate_logs_steps_too():
    """``Engine.generate`` writes the same records: one prefill step for
    both prompts, then a decode step per further token, with fetch time
    taken once the step is ready and a trace marking a compile."""
    eng = _engine(num_lanes=2)
    prompts = _prompts(2, seed=7)
    eng.generate(prompts, max_new_tokens=4)
    recs = _records(eng)
    assert [r.kind for r in recs] == ["prefill"] + ["decode"] * 3
    assert sorted(recs[0].chunks) == sorted((0, len(p)) for p in prompts)
    for k, r in enumerate(recs[1:], start=1):
        assert sorted(r.decode_ctx) == sorted(len(p) + k for p in prompts)
        assert (r.rows, r.bucket) == (2, 1)
    assert recs[0].slots == 2 * bucket_len(max(map(len, prompts)), BUCKETS)
    assert [r.compiled for r in recs] == [True, True, False, False]
    for r in recs:
        assert 0 < r.t_dispatched <= r.t_fetched
        assert r.schedule_s > 0 and r.build_s > 0 and r.apply_s > 0


def test_steps_of_a_model_without_experts_count_none(served):
    eng, _, _ = served
    recs = _records(eng)
    assert recs and all(r.expert_tokens_max == 0 and r.experts_hit == 0.0
                        for r in recs)


def test_step_log_holds_the_routing_counters_of_each_step():
    """A model with routed experts: every step's record carries what its
    step program counted, the most tokens one expert took (at least the
    even share of the step's pairs, at most its tokens) and the experts
    that took any."""
    cfg = get_config("deepseek-v2-lite-16b-reduced")
    eng = Engine(cfg, MODES["coopt"],
                 EngineConfig(num_lanes=2, max_len=128, prefill_buckets=(32,),
                              sampling=SamplingParams(temperature=0.0)))
    fe = AsyncEngine(eng, warmup=False)
    rng = np.random.default_rng(5)
    for n in (20, 9):
        fe.submit(rng.integers(0, cfg.vocab_size, n, dtype=np.int32),
                  max_new_tokens=3)
    fe.run_until_idle()
    fe.close()
    recs = _records(eng)
    assert recs
    E, K = cfg.num_experts, cfg.top_k
    for r in recs:
        assert -(-r.real_tokens * K // E) <= r.expert_tokens_max \
            <= r.real_tokens, r
        assert 1 <= r.experts_hit <= min(E, r.real_tokens * K), r


# ------------------------------------------------- one name per kind ----
@pytest.mark.parametrize("pack", [False, True])
def test_each_step_kind_lowers_to_its_own_named_module(pack):
    """Each kind's program is the single ``_async_step_impl`` program with
    the kind as a static argument, under its own module name."""
    eng = _engine(num_lanes=2, pack=pack)
    single = jax.jit(eng._async_step_impl, static_argnames=("kind",),
                     donate_argnums=(2, 4))
    kinds = set()
    for kind, _, args in eng._lattice_args():
        if kind in kinds:
            continue
        kinds.add(kind)
        got = eng._step_fns[kind].lower(*args).as_text()
        want = single.lower(*args, kind=kind).as_text()
        assert f"module @jit_serve_step_{kind} " in got
        assert "module @jit__async_step_impl " in want
        assert got == want.replace("@jit__async_step_impl ",
                                   f"@jit_serve_step_{kind} ")
    assert kinds == ({"decode", "prefill", "packed"} if pack
                     else {"decode", "prefill"})


# ------------------------------------------------------------ spans -----
def _host_events(path):
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):      # a line per thread
            for ev in line.events:
                out.append(((plane.name, i), ev.name, ev.start_ns,
                            ev.start_ns + ev.duration_ns))
    return out


def test_profiler_trace_holds_serve_spans():
    """A CPU profile of a few turns, each inside an outer annotation as a
    client's own span would be: the loop's spans nest in it, and the emit
    worker's fetch shows on its own thread."""
    from jax.profiler import TraceAnnotation
    eng = _engine(num_lanes=2)
    fe = AsyncEngine(eng, warmup=True)
    for p in _prompts(2, seed=9):
        fe.submit(p, max_new_tokens=4)
    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d)
    try:
        while fe._has_work:
            with TraceAnnotation("client.turn"):
                fe._loop_once()
    finally:
        jax.profiler.stop_trace()
    fe.close()
    path = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)[0]
    evs = _host_events(path)
    names = {n for _, n, _, _ in evs}
    assert {"serve.turn", "serve.schedule", "serve.build", "serve.dispatch",
            "serve.apply", "serve.fetch"} <= names
    assert names & {"serve.wait", "serve.apply"}
    outer = [(line, s, e) for line, n, s, e in evs if n == "client.turn"]
    for line, n, s, e in evs:
        if n in ("serve.turn", "serve.build", "serve.dispatch", "serve.wait",
                 "serve.apply"):
            assert any(ol == line and os_ <= s and e <= oe
                       for ol, os_, oe in outer), n
    fetch_lines = {line for line, n, _, _ in evs if n == "serve.fetch"}
    assert fetch_lines.isdisjoint(line for line, _, _ in outer)
