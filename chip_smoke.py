"""Serve qwen3-4b at full width on a TPU and check what comes out.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the pages-sharded pool on four chips

One chip: 36 layers at d_model 2560 with seeded bf16 weights, 8 requests of
a few hundred prompt tokens and 32 new tokens each, served through
``launch.serve.ServeRunner`` (coopt mode, compiled Pallas kernels). It
fails unless every request finishes with all its tokens and the kernel
path's first prefill and decode logits agree with the jnp path's on the
same weights (``launch.smoke``).

``--chips 4``: the same requests on one chip and then on a (data=4,
model=1) mesh — KV pool sharded by pages, kernels under shard_map, weights
replicated — in this one process. The mesh's first prefill and decode
logits must agree with one chip's on the same weights; how many requests
decode to identical greedy tokens is reported. It runs that comparison
only.

The last line of standard output is ``{"ok": true, "device": {...}}``,
printed only when every check passed; any failure exits non-zero without
it. Runs in one process and starts none.
"""
import argparse
import json
import os
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: compare the pages-sharded four-chip mesh with "
                         "one chip (and run nothing else)")
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (first device is {dev.platform}); "
              "nothing was run", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} devices", file=sys.stderr)
        return 1
    from repro.kernels import ops
    if ops.interpret_mode():
        print("chip_smoke: the Pallas kernels would run interpreted",
              file=sys.stderr)
        return 1
    print(f"device_kind: {dev.device_kind} x {len(devices)}; compilation "
          f"cache: {cache_dir}", flush=True)

    from repro.launch.smoke import SmokeFailure, run_mesh_smoke, run_smoke

    def log(msg):
        print(msg, flush=True)

    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            report = run_mesh_smoke("qwen3-4b", shards=4, log=log)
        else:
            report = run_smoke("qwen3-4b", log=log)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    stats = dev.memory_stats() or {}
    print(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')} "
          f"(of {stats.get('bytes_limit')})")
    print(f"total: {time.perf_counter() - t0:.3f} s")
    print(json.dumps({"report": report}))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
