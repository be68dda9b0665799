"""Roofline analysis (deliverable g): three terms per (arch x shape) from the
single-pod dry-run artifacts (experiments/dryrun_single.jsonl).

  compute    = HLO_FLOPs_per_device / peak_FLOP/s          (197 TF bf16, v5e)
  memory     = HLO_bytes_per_device / HBM_bw               (819 GB/s)
  collective = collective_bytes_per_device / link_bw       (~50 GB/s ICI)

cost_analysis() runs on the SPMD-partitioned per-device module, so flops /
bytes are already per-chip. MODEL_FLOPS = 6*N(_active)*D tokens — forward 2ND
+ backward 4ND for train; forward-only shapes use 2ND. The useful-compute
ratio MODEL_FLOPS / (HLO_FLOPs * chips) exposes remat/redundancy waste.
"""
from __future__ import annotations

import json
import os
from typing import Dict, List, Optional

from repro.configs import ARCH_IDS, SHAPES, get_config
from repro.launch.mesh import TARGET_DEVICE_KIND, chip_peaks

from benchmarks.common import RESULTS_DIR, write_csv

_OPT = os.path.join(RESULTS_DIR, "dryrun_single_opt.jsonl")
_BASE = os.path.join(RESULTS_DIR, "dryrun_single.jsonl")
# primary = the optimized sweep when present (§Perf); baseline kept alongside
DRYRUN_FILE = _OPT if os.path.exists(_OPT) else _BASE


def model_flops(arch: str, shape_name: str) -> float:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    n_active = cfg.active_param_count()
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode"
                                   else 1)
    mult = 6 if shape.kind == "train" else 2
    return mult * n_active * tokens


def load_records(path: str = DRYRUN_FILE) -> List[Dict]:
    recs = []
    with open(path) as f:
        for line in f:
            recs.append(json.loads(line))
    # keep last record per (arch, shape) — reruns append
    seen = {}
    for r in recs:
        seen[(r["arch"], r["shape"])] = r
    return list(seen.values())


def analyze(rec: Dict) -> Optional[Dict]:
    if rec.get("status") != "ok":
        return None
    flops = rec["cost"].get("flops", 0.0)
    mem_bytes = rec["cost"].get("bytes accessed", 0.0)
    coll = rec.get("collective_bytes", 0.0)
    pk = chip_peaks(TARGET_DEVICE_KIND)
    t_c = flops / pk.bf16_flops
    t_m = mem_bytes / pk.hbm_bw
    t_x = coll / pk.ici_bw
    terms = {"compute": t_c, "memory": t_m, "collective": t_x}
    dom = max(terms, key=terms.get)
    mf = model_flops(rec["arch"], rec["shape"])
    useful = mf / (flops * rec["devices"]) if flops else 0.0
    return {
        "arch": rec["arch"], "shape": rec["shape"], "kind": rec.get("kind"),
        "compute_s": t_c, "memory_s": t_m, "collective_s": t_x,
        "dominant": dom, "model_flops": mf,
        "useful_ratio": useful,
        "temp_bytes": rec["memory"]["temp_bytes"],
    }


def run(path: str = DRYRUN_FILE, out_csv: str = "roofline.csv"):
    rows, analyses = [], []
    for rec in sorted(load_records(path),
                      key=lambda r: (r["arch"], r["shape"])):
        a = analyze(rec)
        if a is None:
            rows.append([rec["arch"], rec["shape"], rec["status"],
                         "", "", "", "", "", ""])
            continue
        analyses.append(a)
        rows.append([a["arch"], a["shape"], "ok",
                     f"{a['compute_s']:.3e}", f"{a['memory_s']:.3e}",
                     f"{a['collective_s']:.3e}", a["dominant"],
                     f"{a['useful_ratio']:.3f}", a["temp_bytes"]])
        print(f"{a['arch']:22s} {a['shape']:12s} "
              f"C={a['compute_s']:.2e}s M={a['memory_s']:.2e}s "
              f"X={a['collective_s']:.2e}s -> {a['dominant']:10s} "
              f"useful={a['useful_ratio']:.2f}", flush=True)
    p = write_csv(out_csv,
                  ["arch", "shape", "status", "compute_s", "memory_s",
                   "collective_s", "dominant", "useful_ratio",
                   "temp_bytes_per_dev"], rows)
    return p, analyses


if __name__ == "__main__":
    run()
