"""Readings of the correctness check's control on the chip, for setting a
cell's limit; the benchmark's own runs never run it.

    python3 bench/control.py --workload qwen3-4b.chat --seconds 20 \
        --seeds 11 12 13

For each seed, in this one process: a run of the cell (its warm-in and a
short window at its own load), then the reference over the sampled served
requests. The control, the cell's block's reference one precision step
down (the dense block's: every matrix product in float8 e4m3), is read at
each position of the same prompts and tokens, and its mean gap takes the
program's place in the comparison that decides ``correct``. One JSON line per seed: that ``correct``, the numbers compared
beside their limits, and every reading (the program's ``mean_gap`` and
``max_gap``, the control's ``control_*``).
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    for p in (HERE, os.path.join(ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from benchlib import runner, spec
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = spec.load_cell(args.workload)
    t = T_START
    for seed in args.seeds:
        res = runner.run_cell(cell, seed, args.seconds, False, t,
                              control=True)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "correct": res["correct"],
                          "compared": res["compared"], **res["readings"]}),
              flush=True)
        t = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
