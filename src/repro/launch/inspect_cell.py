import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Perf-iteration profiler: compile one (arch x shape) cell and dump the
top collective ops (with scan multipliers and jaxpr provenance) plus the
roofline terms — the 'profile' the §Perf loop reads (no real TPU here).

  python -m repro.launch.inspect_cell --arch mixtral-8x22b --shape train_4k
"""
import argparse

import jax

from repro.core.coopt import MODES
from repro.launch import mesh as mesh_lib
from repro.launch.hlo_cost import HloCostModel
from repro.launch.steps import make_step


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--mode", default="coopt", choices=list(MODES))
    ap.add_argument("--top", type=int, default=15)
    ap.add_argument("--micro", type=int, default=None)
    args = ap.parse_args(argv)

    mesh = mesh_lib.make_production_mesh(multi_pod=args.multi_pod)
    bundle = make_step(args.arch, args.shape, mesh, MODES[args.mode],
                       num_microbatches=args.micro)
    with mesh:
        compiled = bundle.lower().compile()
    model = HloCostModel(compiled.as_text())
    s = model.summary()
    print(f"flops/dev={s['flops']:.3e}  bytes/dev={s['bytes']:.3e}  "
          f"coll/dev={s['collective_bytes']:.3e}")
    pk = mesh_lib.chip_peaks(mesh_lib.TARGET_DEVICE_KIND)
    print(f"terms: C={s['flops']/pk.bf16_flops:.2e}s "
          f"M={s['bytes']/pk.hbm_bw:.2e}s "
          f"X={s['collective_bytes']/pk.ici_bw:.2e}s")
    mem = compiled.memory_analysis()
    print(f"temp/dev={mem.temp_size_in_bytes/2**30:.1f}GiB")
    print(f"\ntop {args.top} collectives by wire bytes:")
    for b, d in sorted(model.collective_ops, reverse=True)[:args.top]:
        print(f"  {b:.3e}B  {d}")


if __name__ == "__main__":
    main()
