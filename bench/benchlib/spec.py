"""Find a cell's files by the names in ``BENCHMARK.json``.

A cell ``<c>`` is ``bench/cells/<c>.json`` (its deployment sizing, rate and
correctness limits), naming a configuration ``bench/configs/<cfg>.json`` and
a traffic mix ``bench/traffic/<mix>.json``. A metric ``<m>`` is read by
``bench/metrics/<m>.py``. Adding any of them takes new files and new
``BENCHMARK.json`` entries, never an edit.
"""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class SpecError(ValueError):
    """A cell, configuration, mix or metric is missing or inconsistent."""


def load_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing file {os.path.relpath(path, ROOT)}") \
            from None


@dataclass
class Cell:
    name: str
    chips: int
    params: dict                  # bench/cells/<name>.json
    config: dict                  # bench/configs/<config>.json
    traffic: dict                 # bench/traffic/<mix>.json
    end_to_end: List[dict] = field(default_factory=list)
    per_layer: List[dict] = field(default_factory=list)


def _applies(metric: dict, cell: str) -> bool:
    wl = metric.get("workloads")
    return wl is None or cell in wl


def load_cell(name: str, benchmark: Optional[dict] = None) -> Cell:
    """The cell ``name`` with its files and the metrics it reports."""
    bm = benchmark if benchmark is not None else load_json(
        os.path.join(ROOT, "BENCHMARK.json"))
    entry = next((w for w in bm["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json")
    params = load_json(os.path.join(BENCH_DIR, "cells", name + ".json"))
    for key in ("config", "traffic"):
        if params.get(key) != entry[key]:
            raise SpecError(f"cell {name}: {key} {params.get(key)!r} in its "
                            f"file but {entry[key]!r} in BENCHMARK.json")
    cfg = load_json(os.path.join(BENCH_DIR, "configs",
                                 entry["config"] + ".json"))
    mix = load_json(os.path.join(BENCH_DIR, "traffic",
                                 entry["traffic"] + ".json"))
    e2e = [m for m in bm["end_to_end"] if _applies(m, name)]
    per = [m for m in bm["per_layer"] if _applies(m, name)]
    return Cell(name=name, chips=int(entry["chips"]), params=params,
                config=cfg, traffic=mix, end_to_end=e2e, per_layer=per)


def metric_reader(name: str):
    """The ``read(run)`` function of ``bench/metrics/<name>.py``."""
    path = os.path.join(BENCH_DIR, "metrics", name + ".py")
    if not os.path.exists(path):
        raise SpecError(f"no reader bench/metrics/{name}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_metrics(metrics: List[dict], run) -> Dict[str, dict]:
    """``{name: {"value", "unit"}}`` of every metric whose reader found
    something to read (a reader that finds nothing returns None)."""
    out: Dict[str, dict] = {}
    for m in metrics:
        v = metric_reader(m["name"])(run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out
