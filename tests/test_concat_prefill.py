"""Concat-prefill packing: packed-vs-unpacked parity (jnp AND Pallas
kernel paths), the segment-id mask regression (two prompts sharing one
packed row), and ``pack_rows`` invariants (constraints respected, a
request never splits across rows or shards).

All generation runs greedy (temperature 0): a segment-mask leak would
perturb a neighbour prompt's logits and show up as a token difference.
"""
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.coopt import MODES
from repro.serving import Engine, EngineConfig, Request
from repro.serving.sampler import SamplingParams
from repro.serving.scheduler import (PackedRow, PrefillChunk, chunk_pages,
                                     pack_rows)

CFG = get_config("qwen3-4b-reduced")


def _engine(pack, use_kernel=False, num_lanes=4, seed=0):
    ecfg = EngineConfig(num_lanes=num_lanes, max_len=128,
                        prefill_buckets=(32, 64, 128),
                        sampling=SamplingParams(temperature=0.0),
                        seed=seed, pack_prefill=pack)
    return Engine(CFG, MODES["coopt"].replace(use_kernel=use_kernel), ecfg)


def _prompts(n, rng, lo=4, hi=24):
    return [rng.integers(0, CFG.vocab_size, int(rng.integers(lo, hi)),
                         dtype=np.int32) for _ in range(n)]


def _first_token_logits(pack, prompts, use_kernel=False):
    """Admit ``prompts``, build ONE step, run its impl directly and return
    {req_id: last-token logits} plus the StepBatch (to inspect layout)."""
    eng = _engine(pack, use_kernel=use_kernel)
    for i, p in enumerate(prompts):
        eng.add_request(Request(req_id=i, prompt=np.asarray(p, np.int32),
                                max_new_tokens=1))
    plan = eng.scheduler.schedule_step()
    sb = eng._build_step(plan)
    fn = eng._packed_fn if sb.kind == "packed" else eng._prefill_fn
    logits, _ = fn(eng.params, sb.batch, eng.cache,
                   eng._dev_const(sb.lane_mask))
    logits = np.asarray(logits)
    return {req.req_id: logits[idx] for req, _, idx in sb.samples}, sb


# ----------------------------------------------------- logit parity ------
@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["jnp", "kernel"])
def test_two_prompts_one_row_logit_parity(use_kernel):
    """THE segment-mask regression: two short prompts packed into ONE row
    produce (near-)identical first-token logits to each prompt prefilled
    in its own lane — any attention leak across the shared row would
    perturb them. Kernel and jnp paths each compared within themselves."""
    rng = np.random.default_rng(17)
    prompts = [rng.integers(0, CFG.vocab_size, 9, dtype=np.int32),
               rng.integers(0, CFG.vocab_size, 6, dtype=np.int32)]
    packed, sb = _first_token_logits(True, prompts, use_kernel=use_kernel)
    unpacked, _ = _first_token_logits(False, prompts, use_kernel=use_kernel)

    assert sb.kind == "packed"
    # both prompts really share row 0 (segment ids 0 and 1 both present)
    segs = set(np.asarray(sb.batch["seg_q"])[0]) - {-1}
    assert segs == {0, 1}
    for rid in (0, 1):
        assert np.argmax(packed[rid]) == np.argmax(unpacked[rid])
        np.testing.assert_allclose(packed[rid], unpacked[rid],
                                   rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["jnp", "kernel"])
def test_packed_vs_unpacked_greedy_identity(use_kernel):
    """End-to-end: packing ON vs OFF serves identical greedy tokens, and
    the packed run really packed (rows saved > 0)."""
    rng = np.random.default_rng(23)
    prompts = _prompts(6, rng)
    toks = 3 if use_kernel else 6           # interpret-mode kernels are slow

    ref = _engine(False, use_kernel=use_kernel).generate(
        prompts, max_new_tokens=toks)
    eng = _engine(True, use_kernel=use_kernel)
    got = eng.generate(prompts, max_new_tokens=toks)

    assert [list(o) for o in got] == [list(o) for o in ref]
    assert eng.stats.packed_steps > 0
    assert eng.stats.packed_rows_saved > 0


# ------------------------------------------------- pack_rows invariants --
def _mk_chunks(sizes, shards, page_size=16):
    chunks = []
    for i, (n, sh) in enumerate(zip(sizes, shards)):
        r = Request(req_id=i, prompt=np.zeros(n, np.int32),
                    max_new_tokens=1)
        r.shard = sh
        chunks.append(PrefillChunk(req=r, start=0,
                                   tokens=np.zeros(n, np.int32),
                                   final=True, first=True))
    return chunks


def test_pack_rows_respects_all_constraints():
    width, slots, ppl, ps = 32, 2, 4, 16
    chunks = _mk_chunks([20, 16, 8, 8, 4, 4], [0, 0, 0, 0, 0, 0], ps)
    rows = pack_rows(chunks, width, slots, ppl, ps)
    packed = [c for row in rows for c in row.chunks]
    # every chunk lands whole, exactly once (never split)
    assert sorted(c.req.req_id for c in packed) == list(range(len(chunks)))
    for row in rows:
        assert sum(c.n for c in row.chunks) == row.tokens <= width
        assert sum(chunk_pages(c, ps) for c in row.chunks) == row.pages <= ppl
        assert sum(int(c.final) for c in row.chunks) == row.finals <= slots
    # it actually packs: fewer rows than chunks
    assert len(rows) < len(chunks)


def test_pack_rows_never_mixes_shards():
    """A packed row gathers pages from ONE KV shard: chunks pinned to
    different shards must never share a row, however well they'd fit."""
    ps = 16
    chunks = _mk_chunks([4, 4, 4, 4], [0, 1, 0, 1], ps)
    rows = pack_rows(chunks, width=32, pack_slots=4, pages_per_lane=8,
                     page_size=ps)
    assert len(rows) == 2
    for row in rows:
        shards = {c.req.shard for c in row.chunks}
        assert shards == {row.shard}


def test_pack_rows_chunk_pages_cover_history():
    """A continuation chunk's page need covers the WHOLE cached history
    (it attends to everything), not just its own tokens."""
    r = Request(req_id=0, prompt=np.zeros(40, np.int32), max_new_tokens=1)
    r.shard = 0
    c = PrefillChunk(req=r, start=32, tokens=np.zeros(8, np.int32),
                     final=True)
    assert chunk_pages(c, 16) == -(-(32 + 8) // 16) == 3
    rows = pack_rows([c], width=32, pack_slots=4, pages_per_lane=2,
                     page_size=16)
    # needs 3 page slots but rows only have 2: it still lands (alone, in
    # its own fresh row) rather than being dropped or split
    assert len(rows) == 1 and rows[0].chunks == [c]


def test_scheduler_packing_never_splits_requests_across_shards():
    """Engine-level, two KV shards: every packed step's rows stay
    shard-pure while outputs still match the unpacked two-shard run."""
    rng = np.random.default_rng(31)
    prompts = _prompts(6, rng, lo=4, hi=16)

    ref = Engine(CFG, MODES["coopt"],
                 EngineConfig(num_lanes=4, max_len=128,
                              prefill_buckets=(32, 64, 128),
                              sampling=SamplingParams(temperature=0.0),
                              seed=0, num_shards=2)).generate(
        prompts, max_new_tokens=4)
    eng = Engine(CFG, MODES["coopt"],
                 EngineConfig(num_lanes=4, max_len=128,
                              prefill_buckets=(32, 64, 128),
                              sampling=SamplingParams(temperature=0.0),
                              seed=0, num_shards=2, pack_prefill=True))
    got = eng.generate(prompts, max_new_tokens=4)
    assert [list(o) for o in got] == [list(o) for o in ref]
    assert eng.stats.packed_steps > 0
