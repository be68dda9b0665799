"""The serving benchmark's harness on the CPU: the last line's schema, the
arithmetic of its metrics, its FLOP and byte models, the trace reduction
on a recorded chip trace, its refusal to run without a TPU, and the
correctness check, which has to pass a sound run and fail a run whose
served path is broken and the control.

The runs here use a two-layer model at small widths with the Pallas
kernels interpreted; the chip runs use the cells' own sizes.
"""
import json
import math
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from benchlib import check, model, runner, spec, stats, trace, traffic, \
    work  # noqa: E402
from benchlib.driver import Record  # noqa: E402
from benchlib.traffic import Req  # noqa: E402
from repro.models import get_model  # noqa: E402

SAMPLE_TRACE = os.path.join(BENCH, "testdata", "qwen3-4b.chat.trace.json.gz")
DENSE = spec.load_block({"block": "dense_gqa"})
SEED = 2 ** 31 + 17          # larger than 32 signed bits hold
LIMIT = 0.003                # the small cell's mean_logit_gap (see below)
MIN_TOKENS = 20


def small_cell(name="qwen3-4b.chat") -> spec.Cell:
    """A cell of the real harness at a size the CPU serves in seconds.

    Its limit on the mean gap, 0.003: sound runs at this size read
    0.0001-0.0007, the control 0.012-0.020
    (``test_control_fails_at_test_size``). A sound run's widest gap here
    reaches 0.026, so the mean needs 20 served tokens or more to stay
    under the limit; the window is long enough for that on a busy CPU."""
    bm = spec.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cfg = spec.load_json(os.path.join(BENCH, "configs", "qwen3-4b.json"))
    cfg.update(hidden_size=256, intermediate_size=512, num_hidden_layers=2,
               num_attention_heads=4, num_key_value_heads=2, head_dim=64,
               vocab_size=4096)
    mix = {"loop": "open",
           "prompt": {"dist": "lognormal", "median": 20, "log_std": 0.8,
                      "min": 4, "max": 100},
           "output": {"dist": "lognormal", "median": 8, "log_std": 0.5,
                      "min": 2, "max": 16},
           "warm_in_s": 1, "order_seed": 1}
    params = {"config": "qwen3-4b", "traffic": "chat", "lanes": 4,
              "max_len": 256, "pool_pages": 16, "rate_rps": 3.0,
              "check": {"requests": 8, "min_tokens": MIN_TOKENS,
                        "mean_logit_gap": LIMIT}}
    return spec.Cell(name=name, chips=1, params=params, config=cfg,
                     traffic=mix,
                     end_to_end=[m for m in bm["end_to_end"]
                                 if spec._applies(m, name)],
                     per_layer=[m for m in bm["per_layer"]
                                if spec._applies(m, name)])


def serve_small(seconds=12.0):
    return runner.run_cell(small_cell(), SEED, seconds, False,
                           time.perf_counter(), require_tpu=False)


@pytest.fixture(scope="module")
def sound_run():
    return serve_small()


# ------------------------------------------------------------ last line --
def test_result_line_schema(sound_run):
    res = json.loads(json.dumps(sound_run))
    keys = list(res)
    assert keys[:3] == ["correct", "attempted", "failed"]
    assert keys[-1] == "compared"
    assert {"metrics", "device"} <= set(keys)
    cell = small_cell()
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    for name, m in res["metrics"].items():
        unit = next(x["unit"] for x in cell.end_to_end if x["name"] == name)
        assert m["unit"] == unit and math.isfinite(m["value"])
        assert m["value"] > 0
    dev = res["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    assert dev["count"] == len(jax.devices())
    for c in res["compared"].values():
        assert set(c) == {"value", "limit"}
    assert isinstance(res["attempted"], int) and res["attempted"] > 0


def test_sound_run_is_correct(sound_run):
    c = sound_run["compared"]
    assert sound_run["correct"], c
    assert sound_run["failed"] == 0
    assert c["mean_logit_gap"]["value"] <= LIMIT
    assert c["served_tokens_compared"]["value"] >= MIN_TOKENS


def _sample_plus_one(logits, key, **kw):
    """The sampler with every token moved to the next id."""
    return ((jnp.argmax(logits, axis=-1) + 1) % logits.shape[-1]
            ).astype(jnp.int32)


def _write_nothing(kv_cache, scale_cache, k_new, v_new, slot_idx, coopt):
    """A pool write that returns the pool unchanged."""
    return kv_cache, scale_cache


@pytest.mark.parametrize("fault", ["token_altered", "state_unchanged"])
def test_fault_makes_run_incorrect(fault, monkeypatch):
    if fault == "token_altered":
        monkeypatch.setattr("repro.serving.engine.sample", _sample_plus_one)
    else:
        monkeypatch.setattr("repro.models.transformer.write_kv",
                            _write_nothing)
    res = serve_small()
    assert not res["correct"]
    assert res["compared"]["mean_logit_gap"]["value"] > LIMIT


def test_control_fails_at_test_size():
    """The control (every matrix product in float8) read at each position
    of seeded sequences; its mean gap passes the limit."""
    cell = small_cell()
    mdl = get_model(model.program_config(cell.config, DENSE))
    arch = DENSE.Arch.from_config(cell.config)
    for seed in (1, 2, 3):
        W = model.weights_by_path(model.seeded_params(mdl, seed, True,
                                                      DENSE))
        seq = np.random.default_rng(seed).integers(
            0, cell.config["vocab_size"], 256, dtype=np.int32)
        g, gc = DENSE.gaps(W, jnp.asarray(seq), jnp.asarray(seq), 64, 255,
                           arch=arch, control=True)
        assert float(jnp.mean(gc[64:255])) > LIMIT


def test_control_run_is_incorrect():
    """A whole run with the control in the program's place in the
    comparison: the harness's own expression finds it not correct."""
    res = runner.run_cell(small_cell(), SEED, 12.0, False,
                          time.perf_counter(), require_tpu=False,
                          control=True)
    c = res["compared"]
    assert not res["correct"], c
    assert c["mean_logit_gap"]["value"] > LIMIT
    assert c["mean_logit_gap"]["value"] == res["readings"]["control_mean_gap"]
    assert c["served_tokens_compared"]["value"] >= MIN_TOKENS
    assert c["failed_requests"]["value"] == 0


def test_closed_loop_compares_in_flight_requests(monkeypatch):
    """Requests still open at the close are compared on what they served:
    a sound closed-loop run whose requests outlive the window is correct,
    and its sample holds in-flight requests."""
    cell = small_cell("qwen3-4b.decode")
    cell.traffic = {"loop": "closed", "clients": 3, "requests_per_client": 2,
                    "stagger": True,
                    "prompt": {"dist": "uniform", "min": 8, "max": 40},
                    "output": {"dist": "uniform", "min": 180, "max": 200},
                    "warm_in_s": 1, "order_seed": 1}
    cell.params = dict(cell.params, traffic="decode", lanes=3,
                       check=dict(cell.params["check"], requests=2,
                                  in_flight=2))
    seen = []
    readings = check.readings

    def spy(gaps, W, pairs, length, control=False):
        seen.extend(pairs)
        return readings(gaps, W, pairs, length, control=control)

    monkeypatch.setattr(check, "readings", spy)
    res = runner.run_cell(cell, SEED, 6.0, False, time.perf_counter(),
                          require_tpu=False)
    assert res["correct"], res["compared"]
    assert res["attempted"] >= 3
    reqs = {r.prompt.tobytes(): r.max_new for q in
            traffic.closed_loop(cell.traffic, cell.config["vocab_size"], SEED)
            for r in q}
    assert any(0 < len(t) < reqs[p.tobytes()] for p, t in seen)


def test_check_samples_the_longest_first():
    pairs = [(np.zeros(n, np.int32), [1] * m)
             for n, m in ((5, 3), (50, 30), (7, 2), (9, 9))]
    pick = check.sample(pairs, 3, SEED)
    assert pick[0] == 1 and len(pick) == 3 and len(set(pick)) == 3
    assert pick == check.sample(pairs, 3, SEED)
    assert check.sample(pairs, 0, SEED) == []


# ---------------------------------------------------------- arithmetic --
def test_percentile_matches_hand_counts():
    assert stats.percentile([], 90) is None
    assert stats.percentile([7.0], 90) == 7.0
    # ranks 0..9, q=90 -> rank 8.1: 8 + 0.1 * (9 - 8)
    assert stats.percentile(list(range(10)), 90) == pytest.approx(8.1)
    assert stats.percentile([4, 1, 3, 2], 50) == pytest.approx(2.5)
    xs = np.random.default_rng(0).random(37)
    assert stats.percentile(list(xs), 90) == pytest.approx(
        float(np.percentile(xs, 90)))
    assert stats.rate(300, 20.0) == 15.0
    with pytest.raises(ValueError):
        stats.rate(1, 0.0)


def _run_data(records, t0=10.0, t1=20.0):
    cell = small_cell()
    return runner.RunData(cell=cell,
                          dims=DENSE.Dims.from_config(cell.config),
                          peaks={"bf16_flops": 1e12, "hbm_bw": 1e9},
                          t0=t0, t1=t1, setup_s=12.5, records=records)


def _rec(due, times, prompt_len=8, max_new=10):
    r = Record(req=Req(idx=0, prompt=np.zeros(prompt_len, np.int32),
                       max_new=max_new), due=due)
    r.times = list(times)
    return r


def test_client_side_metrics_match_hand_counts():
    recs = [
        _rec(9.0, [10.5, 11.0, 11.5]),       # due before the window
        _rec(12.0, [12.5, 13.0, 14.0]),      # TTFT 0.5, TPOT 0.75
        _rec(15.0, [17.0, 17.2]),            # TTFT 2.0, TPOT 0.2
        _rec(19.0, []),                      # no token: waits 1.0 so far
        _rec(19.5, [19.9, 21.0]),            # one token in the window
    ]
    run = _run_data(recs)
    read = lambda n: spec.metric_reader(n)(run)  # noqa: E731
    assert read("output_tok_s") == pytest.approx(9 / 10.0)
    # TTFT of the 4 requests due in the window: 0.5, 2.0, 1.0, 0.4
    assert read("ttft_p90_ms") == pytest.approx(
        stats.percentile([0.5, 2.0, 1.0, 0.4], 90) * 1e3)
    assert read("tpot_p90_ms") == pytest.approx(
        stats.percentile([0.75, 0.2], 90) * 1e3)
    assert read("setup_s") == 12.5
    # the same quantities, per layer in a cell whose tails are not end to end
    for name in ("ttft_p90_ms", "tpot_p90_ms"):
        assert read(name + ".tok_s") == read(name)
    for name in ("idle_share", "decode_attn_roofline",
                 "prefill_attn_roofline", "prefill_attn_roofline.tok_s"):
        assert read(name) is None        # nothing traced, nothing read


# ------------------------------------------------------ work and peaks --
def test_flop_and_byte_models_match_hand_counts():
    d = DENSE.Dims(layers=2, d_model=8, heads=4, kv_heads=2, head_dim=4,
                   d_ff=16, vocab=32, page_size=4)
    # q,o: 8*16 each; k,v: 8*8 each; ffn 3*8*16 -> 2*(256+128+384)
    assert DENSE.layer_matmul_flops(d) == 1536
    assert DENSE.head_flops(d) == 2 * 8 * 32
    assert DENSE.attn_flops(d, 5) == 4 * 4 * 4 * 5
    # positions 3,4,5 attend 4,5,6 keys: 15 query-key pairs
    assert DENSE.chunk_attn_flops(d, 3, 3) == 4 * 4 * 4 * 15
    assert DENSE.prompt_flops(d, 3, 3) == 2 * (3 * 1536 + 960)
    assert DENSE.decode_flops(d, 5) == 2 * (1536 + 320) + 512
    # 5 keys -> 2 pages of 4 tokens; K and V: 2*2*4*2 heads*4 B fp8 + 4 B
    # scales per (token, head); query 4 heads * 4 * 2 B
    assert DENSE.decode_attn_bytes(d, 5) == 2 * 2 * 4 * 2 * 4 + \
        2 * 2 * 4 * 2 * 4 + 4 * 4 * 2
    # chunk at 3..5: 6 keys -> 2 pages; plus queries read, outputs written
    assert DENSE.chunk_attn_bytes(d, 3, 3) == 2 * 2 * 4 * 2 * (4 + 4) + \
        2 * 3 * 4 * 4 * 2
    peaks = {"bf16_flops": 100.0, "hbm_bw": 10.0}
    assert work.least_time(1000, 50, peaks) == 10.0
    assert work.least_time(100, 50, peaks) == 5.0


def test_peaks_are_keyed_by_device_kind():
    from benchlib.peaks import chip_peaks
    assert chip_peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(ValueError, match="no published peaks"):
        chip_peaks("TPU v9000")


# -------------------------------------------------------------- traffic --
def test_every_seed_gets_the_same_work():
    mix = spec.load_json(os.path.join(BENCH, "traffic", "chat.json"))
    a = traffic.open_loop(mix, 0.5, 20.0, 40.0, 1000, 1)
    b = traffic.open_loop(mix, 0.5, 20.0, 40.0, 1000, 2 ** 33 + 5)
    assert len(a) == len(b) == 10 + 20
    win = lambda reqs: [r for r in reqs if 20.0 <= r.due < 60.0]  # noqa
    assert len(win(a)) == len(win(b)) == 20
    for f in (lambda r: len(r.prompt), lambda r: r.max_new, lambda r: r.due):
        assert list(map(f, a)) == list(map(f, b))
    assert [r.prompt.tolist() for r in a] != [r.prompt.tolist() for r in b]
    assert [r.due for r in a] == sorted(r.due for r in a)
    other = traffic.open_loop(dict(mix, order_seed=2), 0.5, 20.0, 40.0,
                              1000, 1)
    assert sorted(r.max_new for r in win(other)) == \
        sorted(r.max_new for r in win(a))
    assert [r.max_new for r in other] != [r.max_new for r in a]
    assert [r.prompt.tolist() for r in a] == [
        r.prompt.tolist()
        for r in traffic.open_loop(mix, 0.5, 20.0, 40.0, 1000, 1)]
    dec = spec.load_json(os.path.join(BENCH, "traffic", "decode.json"))
    per = traffic.closed_loop(dec, 1000, 3)
    assert len(per) == dec["clients"]
    # the first round arrives aged: decoded tokens moved into the prompt,
    # client i's share (i + 0.5) / clients, prompt plus output as drawn
    firsts = [q[0] for q in per]
    assert min(r.max_new for r in firsts) < dec["output"]["min"]
    assert max(len(r.prompt) for r in firsts) > dec["prompt"]["max"]
    aged = [len(r.prompt) / (len(r.prompt) + r.max_new) for r in firsts]
    assert aged[-1] > 0.9 and aged == sorted(aged)
    for r in firsts:
        assert dec["prompt"]["min"] + dec["output"]["min"] <= \
            len(r.prompt) + r.max_new <= traffic.max_context(dec)
    assert all(dec["output"]["min"] <= r.max_new <= dec["output"]["max"]
               for q in per for r in q[1:])
    other = traffic.closed_loop(dec, 1000, 2 ** 33 + 5)
    assert [(len(r.prompt), r.max_new) for q in per for r in q] == \
        [(len(r.prompt), r.max_new) for q in other for r in q]


# ---------------------------------------------------------------- trace --
def test_trace_reduction_on_recorded_sample():
    rows = trace.load_rows(SAMPLE_TRACE)
    red = trace.reduce(rows)
    assert 0 < red["busy_s"] <= red["window_s"]
    assert red["devices"] == 1
    times = [t for _, t in red["device_ops"]]
    assert times == sorted(times, reverse=True) and len(times) <= 10
    names = [n for n, _ in red["device_ops"]]
    assert not any(n.startswith("%while") for n in names)
    assert all(" = " not in n for n in names)
    assert len(red["idle_gaps"]) <= 10
    assert all(g > 0 for _, g in red["idle_gaps"])
    dec = trace.kernel_seconds(rows, ("_paged_pool_decode_single",))
    pre = trace.kernel_seconds(rows, ("_paged_chunk_prefill_single",))
    assert 0 < dec < red["busy_s"] and 0 < pre < red["busy_s"]


def test_trace_leaves_drop_loop_ops():
    rows = [("d", "l", "%while.1 = x", 0.0, 10.0),
            ("d", "l", "%a.1 = y", 1.0, 2.0),
            ("d", "l", "%b.2 = z", 4.0, 3.0),
            ("d", "l", "%c.3 = z", 12.0, 1.0)]
    assert [r[2] for r in trace.leaves(rows)] == \
        ["%a.1 = y", "%b.2 = z", "%c.3 = z"]


# ------------------------------------------------- files and the chip --
def test_benchmark_files_are_found_by_name():
    bm = spec.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    for w in bm["workloads"]:
        cell = spec.load_cell(w["name"], bm)
        assert cell.config["name"] == w["config"]
        assert traffic.max_context(cell.traffic) <= cell.params["max_len"]
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        moves = {m["moves"] for m in cell.per_layer}
        assert moves <= {m["name"] for m in cell.end_to_end}
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert callable(spec.metric_reader(m["name"]))
    for c in bm["configs"]:
        cfg = spec.load_json(os.path.join(ROOT, c["file"]))
        assert cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]


def test_refuses_to_run_without_tpu(capsys):
    import importlib.util
    s = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(BENCH, "run.py"))
    run = importlib.util.module_from_spec(s)
    s.loader.exec_module(run)
    rc = run.main(["--workload", "qwen3-4b.chat", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out, err = capsys.readouterr()
    assert rc != 0
    assert "no TPU" in err
    assert not out.strip()
    assert jax.config.jax_compilation_cache_dir != os.path.join(
        ROOT, ".jax_cache")
