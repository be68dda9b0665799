"""The readers of the program's step log and named step programs:
``step_slot_use``, ``host_step_ms`` and ``decode_step_ms``.

Hand counts on a hand-built step log and synthetic trace rows; the step
log against the driver's outside reconstruction of the same steps
(``StepSample``, which ``step_mfu`` and the kernel rooflines are computed
from) in a small CPU run; and a recorded chip trace of ``qwen3-4b.chat``
taken with the ``serve.*`` spans in the program.
"""
import os
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(ROOT, "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from benchlib import runner, spec, trace, work  # noqa: E402
from benchlib.driver import Driver  # noqa: E402
from repro import serving  # noqa: E402
from repro.serving import steplog  # noqa: E402

SERVE_TRACE = os.path.join(BENCH, "testdata",
                           "qwen3-4b.chat.serve-trace.json.gz")
OLD_TRACE = os.path.join(BENCH, "testdata", "qwen3-4b.chat.trace.json.gz")
READERS = ("step_slot_use", "host_step_ms", "decode_step_ms")


def _run(t0=10.0, t1=20.0, rows=None):
    cell = spec.load_cell("qwen3-4b.chat")
    return runner.RunData(cell=cell, dims=work.Dims.from_config(cell.config),
                          peaks={"bf16_flops": 1e12, "hbm_bw": 1e9},
                          t0=t0, t1=t1, setup_s=1.0, records=[],
                          trace_rows=rows)


def _step(t, kind, rows, bucket, real, phases):
    s, b, d, a = phases
    return steplog.StepRecord(engine=0, seq=0, kind=kind, rows=rows,
                              bucket=bucket, real_tokens=real, chunks=(),
                              decode_ctx=(), req_ids=(), t_dispatched=t,
                              schedule_s=s, build_s=b, dispatch_s=d,
                              apply_s=a, t_fetched=t + 0.1)


def _read(name, run):
    return spec.metric_reader(name)(run)


@pytest.fixture
def ring(monkeypatch):
    r = steplog.StepRing(maxlen=16)
    monkeypatch.setattr(steplog, "RECENT", r)
    return r


def test_step_log_readers_match_hand_counts(ring):
    ring.extend([
        _step(9.0, "prefill", 16, 256, 170, (1, 1, 1, 1)),   # before
        _step(10.0, "prefill", 16, 256, 170, (0.001, 0.002, 0.003, 0.001)),
        _step(11.0, "decode", 16, 1, 16, (0.0005, 0.001, 0.001, 0.0005)),
        _step(12.0, "decode", 16, 1, 12, (0.0005, 0.002, 0.001, 0.0005)),
        _step(20.0, "packed", 4, 64, 100, (0.001, 0.001, 0.001, 0.001)),
        _step(20.5, "decode", 16, 1, 16, (1, 1, 1, 1)),      # after
    ])
    run = _run()
    # slots 4096 + 16 + 16 + 256, real 170 + 16 + 12 + 100
    assert _read("step_slot_use", run) == pytest.approx(
        100.0 * 298 / 4384)
    # host times 7, 3, 4, 4 ms: the median of four is 4 ms
    assert _read("host_step_ms", run) == pytest.approx(4.0)
    assert _read("decode_step_ms", run) is None       # nothing traced
    for name in ("step_slot_use", "host_step_ms"):
        assert _read(name, _run(30.0, 40.0)) is None   # no step in window


def test_decode_step_ms_matches_hand_counts():
    mod = "XLA Modules"
    rows = [
        ("/device:TPU:0", mod, "jit_serve_step_decode(11)", 0.0, 98e6),
        ("/device:TPU:0", mod, "jit_serve_step_decode(11)", 1e8, 102e6),
        ("/device:TPU:0", mod, "jit_serve_step_decode(11)", 3e8, 99e6),
        ("/device:TPU:0", mod, "jit_serve_step_prefill(12)", 5e8, 227e6),
        ("/device:TPU:0", "XLA Ops", "jit_serve_step_decode(11)", 0.0, 9e9),
        ("/device:TPU:1", mod, "jit_serve_step_decode(11)", 0.0, 101e6),
        ("/device:TPU:1", mod, "jit_serve_step_decode(11)", 1e8, 103e6),
        ("/host:CPU", mod, "jit_serve_step_decode(11)", 0.0, 1e9),
    ]
    # medians 99 ms and 102 ms, averaged over the two devices
    assert _read("decode_step_ms", _run(rows=rows)) == pytest.approx(100.5)
    assert _read("decode_step_ms", _run(rows=rows[3:5])) is None


def test_readers_find_nothing_in_a_program_without_them(ring, monkeypatch):
    """Run against a program that keeps no step log and names no step
    kind, as the benchmark's traced runs do on an older commit, each
    reader returns None and raises nothing."""
    ring.append(_step(15.0, "decode", 16, 1, 16, (0.001,) * 4))
    monkeypatch.delattr(serving, "steplog")
    monkeypatch.setitem(sys.modules, "repro.serving.steplog", None)
    run = _run(rows=trace.load_rows(OLD_TRACE))
    for name in READERS:
        assert _read(name, run) is None


# ---------------------------------------- the step log against outside --
def test_step_log_matches_driver_step_samples(monkeypatch):
    """In a small CPU run of the harness, each step the driver
    reconstructs from counters around a turn (``StepSample``: chunks from
    ``num_computed``, decode contexts from output lengths) carries what the
    program logged for the step it dispatched."""
    if HERE not in sys.path:
        sys.path.insert(0, HERE)
    from test_bench import SEED, small_cell
    drivers = []

    class Sampling(Driver):
        """The driver sampling steps from start to close, with no
        profiler behind it."""

        def __init__(self, fe, source, *, t0, t1, trace=None, annotate=None):
            super().__init__(fe, source, t0=t0, t1=t1, annotate=annotate,
                             trace=((lambda: None, lambda: None),
                                    (0.0, t1)))
            drivers.append(self)

    monkeypatch.setattr(runner, "Driver", Sampling)
    runner.run_cell(small_cell(), SEED, 4.0, False, time.perf_counter(),
                    require_tpu=False)
    (drv,) = drivers
    recs = steplog.RECENT.between(*drv.trace_span)
    assert len(drv.steps) == len(recs) >= 4
    kinds = set()
    for s, r in zip(drv.steps, recs):
        assert s.kind == ("prefill" if r.chunks else "decode")
        assert sorted(s.chunks) == sorted(r.chunks)
        assert sorted(s.decode_ctx) == sorted(r.decode_ctx)
        assert r.t_dispatched <= s.t and not r.compiled
        kinds.add(r.kind)
    assert kinds == {"prefill", "decode"}


# ------------------------------------------------ a recorded chip trace --
def test_recorded_serve_trace_reads_step_metrics():
    """The recorded chip trace names its step kinds, its idle gaps fall
    in ``serve.*`` spans, and the kernels keep the names their roofline
    readers match."""
    rows = trace.load_rows(SERVE_TRACE)
    ms = _read("decode_step_ms", _run(rows=rows))
    # two decode programs of 91.542 and 91.758 ms around a prefill one
    assert ms == pytest.approx(91.650, abs=1e-3)
    red = trace.reduce(rows)
    labels = [lab for lab, _ in red["idle_gaps"]]
    assert any("serve." in lab for lab in labels), labels
    for kernel in ("_paged_pool_decode_single",
                   "_paged_chunk_prefill_single"):
        assert trace.kernel_seconds(rows, (kernel,)) > 0
    host = {r[2] for r in rows if r[0] == trace.HOST_PLANE}
    assert {"serve.turn", "serve.schedule", "serve.build", "serve.dispatch",
            "serve.apply", "serve.fetch"} <= host
