"""Benchmark harness entry point: one function per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run            # everything
  PYTHONPATH=src python -m benchmarks.run --quick    # smoke variant
  PYTHONPATH=src python -m benchmarks.run --only fig6,roofline

Outputs CSVs under experiments/ and a summary to stdout.
"""
from __future__ import annotations

import argparse
import os
import time
import traceback

ALL = ("fig6", "fig7", "table12", "kernel", "kernels", "mla", "serving",
       "roofline")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of " + ",".join(ALL))
    args = ap.parse_args(argv)
    which = args.only.split(",") if args.only else list(ALL)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    t0 = time.time()
    failures = []
    for name in which:
        print(f"\n===== {name} =====", flush=True)
        try:
            if name == "fig6":
                from benchmarks.fig6_latency import run
                run(quick=args.quick)
            elif name == "fig7":
                from benchmarks.fig7_throughput import run
                run(quick=args.quick)
            elif name == "table12":
                from benchmarks.table12_accuracy import run
                run(quick=args.quick)
            elif name == "kernel":
                from benchmarks.kernel_micro import run
                run(quick=args.quick)
            elif name == "kernels":
                from benchmarks.bench_kernels import run
                run(quick=args.quick)
            elif name == "mla":
                from benchmarks.bench_mla import run
                run(quick=args.quick)
            elif name == "serving":
                from benchmarks.bench_serving import run
                run(quick=args.quick)
            elif name == "roofline":
                from benchmarks.roofline import run, DRYRUN_FILE
                if os.path.exists(DRYRUN_FILE):
                    run()
                else:
                    print(f"(no {DRYRUN_FILE}; run "
                          f"`python -m repro.launch.dryrun --all --out "
                          f"{DRYRUN_FILE}` first)")
            else:
                print(f"unknown benchmark {name!r}")
        except Exception:
            traceback.print_exc()
            failures.append(name)
    print(f"\n== benchmarks done in {time.time() - t0:.0f}s; "
          f"failures: {failures or 'none'} ==")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
