"""Run one cell of the serving benchmark on the chip(s) of this machine.

    python3 bench/run.py --workload qwen3-4b.chat --seed 7 --seconds 30 \
        --trace 0

The cell's configuration, traffic mix and metrics are found by the names in
``BENCHMARK.json`` (see ``benchlib.spec``). The last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer ones), ``device``, with ``--trace 1`` ``breakdown``, and last
``compared``: each number the correctness check compared, beside its limit.
Those numbers are also the last lines of standard error.

Exits non-zero, printing no result, when JAX finds no TPU of a known kind
or fewer chips than the cell needs, or when anything fails. The compile
cache is kept in ``<checkout>/.jax_cache``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-rows", default=None,
                    help="also write the traced rows (gzipped JSON) here")
    ap.add_argument("--rate", type=float, default=None,
                    help="arrival rate in place of the cell's (a sweep for "
                         "the cell's capacity)")
    args = ap.parse_args(argv)

    for p in (HERE, os.path.join(ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from benchlib import runner, spec

    try:
        cell = spec.load_cell(args.workload)
        runner.check_devices(cell.chips)
        import jax
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        res = runner.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), T_START,
                              trace_rows=args.trace_rows, rate=args.rate)
    except runner.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    except Exception:
        traceback.print_exc()
        return 1
    for name, c in res["compared"].items():
        print(f"compared {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
