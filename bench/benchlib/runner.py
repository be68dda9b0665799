"""One run of one cell: set-up, warm-in, the measured window (optionally
traced), the correctness check against the reference, and the metrics.

Set-up is everything from process start to the window's start: loading
the program, making the weights, building and warming the engine (its
step programs come from the compile cache after a cell's first run) and
the warm-in, during which traffic already flows. The reference runs after
the window, once the device's peak memory has been read and the program's
state is freed; it is not counted in set-up.
"""
from __future__ import annotations

import gc
import sys
import time
from dataclasses import dataclass, field
from functools import partial
from typing import List, Optional

from benchlib import check, spec, traffic
from benchlib.driver import (SLOW_TURN_S, ClosedSource, Driver, OpenSource,
                             Record)
from benchlib.peaks import chip_peaks

TRACE_S = 6.0          # traced seconds, the window's last ones
FAILED = ("rejected", "error", "shed", "timed_out", "preemption_limit")


class NoChip(RuntimeError):
    """JAX finds no accelerator of a known kind, or too few of them."""


def check_devices(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU (first device is "
                     f"{devs[0].platform}); nothing was run")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX finds {len(devs)}")
    chip_peaks(devs[0].device_kind)      # a kind without peaks is refused
    return devs


@dataclass
class RunData:
    """What metric readers read (``bench/metrics/<name>.py``)."""
    cell: spec.Cell
    dims: object                  # the cell's block's Dims
    peaks: dict
    t0: float
    t1: float
    setup_s: float
    records: List[Record]
    peak_pages: int = 0
    pool_pages: int = 0
    steps: list = field(default_factory=list)     # traced turns' steps
    trace_rows: Optional[list] = None
    trace: Optional[dict] = None                  # trace.reduce(...)

    @property
    def block(self):
        """The cell's block module (``bench/blocks/<block>.py``)."""
        return self.cell.block

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    def due_in_window(self) -> List[Record]:
        return [r for r in self.records if self.t0 <= r.due <= self.t1]

    def open_in_window(self) -> List[Record]:
        """Requests sent by the window's close and not closed before its
        start: what the window served, in a closed loop too."""
        return [r for r in self.records if r.due <= self.t1
                and (r.closed is None or r.closed >= self.t0)]


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _memory_lines(engine) -> None:
    """Each warmed step program's memory plan (compiled.memory_analysis),
    where the program keeps them; for the record only."""
    for key, comp in getattr(engine, "_aot", {}).items():
        try:
            ma = comp.memory_analysis()
        except Exception as e:          # not every backend reports it
            _log(f"memory_analysis unavailable: {e}")
            return
        _log(f"step {key[0]}: arguments {ma.argument_size_in_bytes} B, "
             f"temporaries {ma.temp_size_in_bytes} B, outputs "
             f"{ma.output_size_in_bytes} B, aliased "
             f"{ma.alias_size_in_bytes} B")


class FullCollections:
    """Python's full (generation 2) garbage collections, timed: each one
    stops every thread of the process, the serving loop's too."""

    def __init__(self):
        self.spans: List[tuple] = []      # (perf_counter start, seconds)
        self._t = None
        gc.callbacks.append(self._cb)

    def _cb(self, phase: str, info: dict) -> None:
        if info["generation"] != 2:
            return
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.spans.append((self._t, time.perf_counter() - self._t))

    def close(self) -> None:
        gc.callbacks.remove(self._cb)


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             t_start: float, require_tpu: bool = True,
             control: bool = False, trace_rows: Optional[str] = None,
             rate: Optional[float] = None) -> dict:
    """Run ``cell`` once; returns the result line as a dict. With
    ``control`` the control's gap takes the program's place in the
    comparison that decides ``correct`` (no metrics);
    ``trace_rows``: a path to keep the traced rows at; ``rate``: an
    arrival rate in place of the cell's (to find a cell's capacity)."""
    import jax
    from benchlib import model as bm
    from benchlib.trace import Tracer, reduce, save_rows
    from repro.configs.base import CacheConfig
    from repro.models import get_model
    from repro.serving import AsyncEngine, Engine, EngineConfig

    devs = check_devices(cell.chips) if require_tpu else jax.devices()
    dev = devs[0]
    peaks = chip_peaks(dev.device_kind) if require_tpu else \
        chip_peaks("TPU v5 lite")
    cfg, p, mix, block = cell.config, cell.params, cell.traffic, cell.block
    if traffic.max_context(mix) > p["max_len"]:
        raise spec.SpecError(f"{cell.name}: the mix reaches "
                             f"{traffic.max_context(mix)} tokens, more than "
                             f"max_len {p['max_len']}")
    t_ready = time.perf_counter()

    pc = bm.program_config(cfg, block)
    mdl = get_model(pc)
    tied = bool(cfg["tie_word_embeddings"])
    params = jax.block_until_ready(bm.seeded_params(mdl, seed, tied, block))
    t_weights = time.perf_counter()

    ecfg = EngineConfig(num_lanes=p["lanes"], max_len=p["max_len"],
                        cache=CacheConfig(num_pages=p["pool_pages"]))
    engine = Engine(pc, bm.coopt_mode(), ecfg, params=params)
    fe = AsyncEngine(engine)
    t_engine = time.perf_counter()
    _memory_lines(engine)

    warm_in = p.get("warm_in_s", mix["warm_in_s"])
    if mix["loop"] == "open":
        reqs = traffic.open_loop(mix, rate or p["rate_rps"], warm_in,
                                 seconds, cfg["vocab_size"], seed)
    else:
        per_client = traffic.closed_loop(mix, cfg["vocab_size"], seed)
    t_arr = time.perf_counter()
    t0 = t_arr + warm_in
    t1 = t0 + seconds
    source = (OpenSource(reqs, t_arr) if mix["loop"] == "open"
              else ClosedSource(per_client, t_arr))
    tracer, tr = None, None
    if trace:
        # the trace ends with the window: stopping it stalls the host for
        # seconds while the profile is collected
        tracer = Tracer()
        tr = ((tracer.start, tracer.stop),
              (t1 - min(TRACE_S, 0.5 * seconds), t1))
    drv = Driver(fe, source, t0=t0, t1=t1, trace=tr,
                 annotate=jax.profiler.TraceAnnotation if trace else None)
    full_gc = FullCollections()
    drv.run()
    full_gc.close()
    drv.stop()
    mem_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devs[:max(cell.chips, 1)])
    n_tracked = len(gc.get_objects())
    fe.close()
    stats = engine.stats
    _log(f"engine: {stats.prefill_calls} prefill-carrying steps "
         f"({drv.steps_at_t0[0]} before the window), {stats.decode_steps} "
         f"decode-carrying ({drv.steps_at_t0[1]} before), "
         f"{stats.mixed_steps} mixed; preemptions {stats.preemptions}; "
         f"step programs "
         f"re-traced after warm-up: {engine.aot_misses}")
    drv.fe = None
    del fe, engine, params, stats
    gc.collect()

    run = RunData(cell=cell, dims=block.Dims.from_config(cfg), peaks=peaks,
                  t0=t0, t1=t1, setup_s=t0 - t_start, records=drv.records,
                  peak_pages=drv.peak_pages, pool_pages=drv.pool_pages,
                  steps=drv.steps)
    if trace:
        run.trace_rows = tracer.rows()
        if trace_rows:
            save_rows(run.trace_rows, trace_rows)
        run.trace = reduce(run.trace_rows)
    win = run.due_in_window()
    late = sorted(r.submit - r.due for r in win)
    _log(f"setup: start {t_ready - t_start:.3f} s, weights "
         f"{t_weights - t_ready:.3f} s, engine and warm-up "
         f"{t_engine - t_weights:.3f} s, warm-in {t0 - t_arr:.3f} s")
    if late:
        _log(f"generator lateness over {len(late)} requests due in the "
             f"window: median {late[len(late) // 2] * 1e3:.3f} ms, max "
             f"{late[-1] * 1e3:.3f} ms")
    _log(f"loop turns over {SLOW_TURN_S} s in the window: "
         f"{len(drv.slow_turns)}; (s into the window, s long): "
         + ", ".join(f"({a:.3f}, {b:.3f})" for a, b in drv.slow_turns[:8]))
    _log(f"full garbage collections from warm-in to close: "
         f"{len(full_gc.spans)}, {n_tracked} objects tracked at the close; "
         f"(s into the window, s long): "
         + ", ".join(f"({a - t0:.3f}, {b:.3f})"
                     for a, b in full_gc.spans[:8]))

    # ------------------------------------------------------- correctness --
    limits = p["check"]
    finished = [(r.req.prompt, r.tokens) for r in drv.records
                if r.reason == "finished" and len(r.tokens) == r.req.max_new]
    # requests still open at the close, by what they had served: a loop
    # whose requests outlive the window is compared at its longest contexts
    in_flight = [(r.req.prompt, r.tokens) for r in drv.in_flight if r.tokens]
    pairs = [finished[i] for i in
             check.sample(finished, limits["requests"], seed)]
    pairs += [in_flight[i] for i in
              check.sample(in_flight, limits.get("in_flight", 0), seed)]
    t_check = time.perf_counter()
    W = bm.weights_by_path(bm.seeded_params(mdl, seed, tied, block))
    gaps = partial(block.gaps, arch=block.Arch.from_config(cfg))
    got = check.readings(gaps, W, pairs, p["max_len"], control=control)
    del W
    gc.collect()
    _log(f"check: {len(pairs)} requests ({len(finished)} finished, "
         f"{len(in_flight)} in flight at the close), longest "
         f"{max((len(a) + len(b) for a, b in pairs), default=0)} positions, "
         f"{got['tokens_compared']} served tokens, reference "
         f"{time.perf_counter() - t_check:.3f} s; widest gap "
         f"{got['max_gap']}, share not the reference's top token "
         f"{got['flipped']}")
    served = run.open_in_window()
    failed = sum(r.reason in FAILED for r in served)
    # the control is held to the same comparison in the program's place
    gap = got["control_mean_gap"] if control else got["mean_gap"]
    compared = {
        "mean_logit_gap": {"value": gap, "limit": limits["mean_logit_gap"]},
        "failed_requests": {"value": failed, "limit": 0},
        "served_tokens_compared": {"value": got["tokens_compared"],
                                   "limit": limits["min_tokens"]},
    }
    correct = (gap <= limits["mean_logit_gap"] and failed == 0
               and got["tokens_compared"] >= limits["min_tokens"])

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": int(mem_peak)}
    out = {"correct": bool(correct), "attempted": len(served),
           "failed": int(failed)}
    if control:
        out["readings"] = got
    else:
        metrics = cell.per_layer if trace else cell.end_to_end
        out["metrics"] = spec.read_metrics(metrics, run)
    if trace:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        out["breakdown"] = {"device_ops": run.trace["device_ops"],
                            "idle_gaps": run.trace["idle_gaps"]}
    out["device"] = device
    out["compared"] = compared
    return out
