"""Device trace: taking it, and reducing it to seconds.

``Tracer`` runs ``jax.profiler`` over a few seconds inside the window, with
Python's own tracer off (only the benchmark's ``bench.*`` host spans and
the runtime's host events are kept). ``load`` flattens the profile into
``(plane, line, name, start_ns, dur_ns)`` rows, the form a recorded sample
is kept in; ``reduce`` turns the rows into device busy time over the
traced window, the device operations that took most time, the summed time
of named kernels, and the longest idle gaps labelled by what the host was
doing.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import shutil
import tempfile
from typing import Dict, Iterable, List, Sequence, Tuple

Row = Tuple[str, str, str, float, float]

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."


class Tracer:
    def __init__(self):
        self.dir = None

    def start(self) -> None:
        import jax
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self) -> None:
        import jax
        jax.profiler.stop_trace()

    def rows(self) -> List[Row]:
        """The trace's rows; the profile's files are removed."""
        try:
            paths = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                              recursive=True)
            if not paths:
                raise RuntimeError("the profiler wrote no trace")
            return load(paths[0])
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def load(path: str) -> List[Row]:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out: List[Row] = []
    for plane in pd.planes:
        keep_host = plane.name == HOST_PLANE
        if not (keep_host or plane.name.startswith(DEVICE_PREFIX)):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.duration_ns <= 0:
                    continue
                out.append((plane.name, line.name, ev.name,
                            float(ev.start_ns), float(ev.duration_ns)))
    return out


def save_rows(rows: Sequence[Row], path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump([list(r) for r in rows], f)


def load_rows(path: str) -> List[Row]:
    with gzip.open(path, "rt") as f:
        return [tuple(r) for r in json.load(f)]


def _union(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(ivs, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in ivs if e > lo and s < hi]


def window_of(rows: Sequence[Row]) -> Tuple[float, float]:
    """The traced window: from the first to the last end of the
    benchmark's own host spans."""
    spans = [(r[3], r[3] + r[4]) for r in rows
             if r[0] == HOST_PLANE and r[2].startswith(SPAN_PREFIX)]
    if not spans:
        raise ValueError("the trace holds none of the benchmark's spans")
    return min(s for s, _ in spans), max(e for _, e in spans)


def device_ops(rows: Sequence[Row]) -> Dict[str, List[Row]]:
    """The rows of each device's op line, by device plane."""
    out: Dict[str, List[Row]] = {}
    for r in rows:
        if r[0].startswith(DEVICE_PREFIX) and r[1] == OPS_LINE:
            out.setdefault(r[0], []).append(r)
    return out


def op_name(name: str) -> str:
    """An op's HLO instruction name: the trace names ops by their whole
    instruction text (``%name.N = shape op(...)``)."""
    return name.split(" = ", 1)[0]


def leaves(ops: Sequence[Row]) -> List[Row]:
    """The ops that hold no other op of their line: a loop or call op
    spans the ops of its body, which the line lists too."""
    ops = sorted(ops, key=lambda r: (r[3], -r[4]))
    out = []
    for i, r in enumerate(ops):
        nxt = ops[i + 1] if i + 1 < len(ops) else None
        if nxt is None or nxt[3] >= r[3] + r[4]:
            out.append(r)
    return out


def kernel_seconds(rows: Sequence[Row], patterns: Sequence[str]) -> float:
    """Summed device time, inside the traced window and averaged over the
    devices, of the ops whose name contains any of ``patterns``."""
    lo, hi = window_of(rows)
    per_dev = device_ops(rows)
    total = sum(min(r[3] + r[4], hi) - max(r[3], lo)
                for ops in per_dev.values() for r in ops
                if any(p in op_name(r[2]) for p in patterns)
                and r[3] < hi and r[3] + r[4] > lo)
    return total / max(len(per_dev), 1) / 1e9


def reduce(rows: Sequence[Row], top: int = 10) -> dict:
    """``busy_s`` (union of op intervals in the window, averaged over the
    devices that ran any), ``window_s``, ``device_ops`` (the ``top`` op
    names by summed time, loop and call ops left out for their bodies) and ``idle_gaps`` (the ``top`` longest gaps
    between ops, each labelled by the innermost host event around its
    middle, under the benchmark span holding it)."""
    lo, hi = window_of(rows)
    per_dev = device_ops(rows)
    if not per_dev:
        raise ValueError("the trace holds no device operations")
    busy, by_name, gaps = [], {}, []
    for dev, ops in sorted(per_dev.items()):
        u = _union(_clip([(r[3], r[3] + r[4]) for r in ops], lo, hi))
        busy.append(sum(e - s for s, e in u))
        for r in leaves(ops):
            d = min(r[3] + r[4], hi) - max(r[3], lo)
            if d <= 0:
                continue
            n = op_name(r[2])
            by_name[n] = by_name.get(n, 0.0) + d
        edges = [lo] + [x for iv in u for x in iv] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    ops_top = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    host = [r for r in rows if r[0] == HOST_PLANE]
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return {
        "busy_s": sum(busy) / len(busy) / 1e9,
        "window_s": (hi - lo) / 1e9,
        "devices": len(busy),
        "device_ops": [[n, t / 1e9] for n, t in ops_top],
        "idle_gaps": [[_label(host, (s + e) / 2), (e - s) / 1e9]
                      for s, e in gaps],
    }


def _label(host: Sequence[Row], t: float) -> str:
    around = [r for r in host if r[3] <= t <= r[3] + r[4]]
    if not around:
        return "none"
    inner = min(around, key=lambda r: r[4])[2]
    spans = [r for r in around if r[2].startswith(SPAN_PREFIX)]
    if spans:
        outer = min(spans, key=lambda r: r[4])[2]
        if outer != inner:
            return f"{outer}/{inner}"
    return inner
