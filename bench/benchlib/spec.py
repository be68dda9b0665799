"""Find a cell's files by the names in ``BENCHMARK.json``.

A cell ``<c>`` is ``bench/cells/<c>.json`` (its deployment sizing, rate and
correctness limits), naming a configuration ``bench/configs/<cfg>.json`` and
a traffic mix ``bench/traffic/<mix>.json``. A configuration names its block
module ``bench/blocks/<block>.py`` under its key ``"block"``. A metric
``<m>`` is read by ``bench/metrics/<m>.py``. Adding any of them takes new
files and new ``BENCHMARK.json`` entries, never an edit.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys
from dataclasses import dataclass, field
from typing import Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


# what a block module defines (see load_block)
BLOCK_API = ("PROGRAM_KEYS", "REQUIRED", "NORMS", "BIASES", "Arch", "gaps",
             "Dims", "prompt_flops", "decode_flops", "head_flops")
_BLOCKS: Dict[str, object] = {}       # loaded block modules, by path


class SpecError(ValueError):
    """A cell, configuration, block, mix or metric is missing or
    inconsistent."""


def load_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing file {os.path.relpath(path, ROOT)}") \
            from None


def _module(name: str, path: str):
    """The module at ``path``, entered in ``sys.modules`` as ``name`` (a
    dataclass looks its module up there)."""
    name = name.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_block(cfg: dict):
    """The block module that configuration ``cfg`` names, loaded once per
    process. It holds everything that depends on the block's shape:

    - ``PROGRAM_KEYS``: {published config key: the program's ModelConfig
      field}, mapped where the file has the key; ``REQUIRED``: the keys
      the file must have;
    - ``NORMS``, ``BIASES``: leaf names that the weight generator fills as
      norm scales (1 + N(0, 0.1^2)) and as biases (N(0, 0.5^2));
    - ``Arch.from_config(cfg)``: the reference's static description, and
      ``gaps(W, tokens, served, lo, hi, *, arch, control)``: the plain
      reference's logit gaps and its control's (``benchlib.check``), over
      the weights by path (``benchlib.model.weights_by_path``);
    - ``Dims.from_config(cfg)``: the sizes its work counts take, and
      ``prompt_flops(d, start, n)``, ``decode_flops(d, ctx)``,
      ``head_flops(d)`` (``benchlib.work.window_flops``); a kernel's
      roofline reader may ask for more.
    """
    name = cfg.get("block")
    if not isinstance(name, str) or not name.isidentifier():
        raise SpecError(f"configuration {cfg.get('name')!r} names no block "
                        f"(its key \"block\": {name!r})")
    path = os.path.join(BENCH_DIR, "blocks", name + ".py")
    if path not in _BLOCKS:
        if not os.path.exists(path):
            raise SpecError(f"no block {os.path.relpath(path, ROOT)}")
        mod = _module("bench_block_" + name, path)
        missing = [a for a in BLOCK_API if not hasattr(mod, a)]
        if missing:
            raise SpecError(f"block {name} lacks {', '.join(missing)}")
        _BLOCKS[path] = mod
    return _BLOCKS[path]


@dataclass
class Cell:
    name: str
    chips: int
    params: dict                  # bench/cells/<name>.json
    config: dict                  # bench/configs/<config>.json
    traffic: dict                 # bench/traffic/<mix>.json
    end_to_end: List[dict] = field(default_factory=list)
    per_layer: List[dict] = field(default_factory=list)
    block: object = None          # bench/blocks/<config's block>.py

    def __post_init__(self):
        if self.block is None:
            self.block = load_block(self.config)


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
    return _module("bench_metric_" + name, path).read


def read_metrics(metrics: List[dict], run) -> Dict[str, dict]:
    """``{name: {"value", "unit"}}`` of every metric whose reader found
    something to read (a reader that finds nothing returns None)."""
    out: Dict[str, dict] = {}
    for m in metrics:
        v = metric_reader(m["name"])(run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out
