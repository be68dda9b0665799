"""MLA fused-latent-kernel serving benchmark -> experiments/BENCH_mla.json.

Runs the SAME synthetic ShareGPT workload through the continuous-batching
engine twice for the mla family — jnp gather reference vs the fused Pallas
latent kernels (``coopt.use_kernel``) — and records Eq. 12 tokens/s plus
per-request TPOT p50/p95, alongside the ``kernel_micro`` latent rows (jnp
wall-clock, analytic HBM traffic of gather-vs-fused, kernel parity error).

On this CPU container the kernels run in Pallas interpret mode, so the
kernel-path wall-clock numbers are NOT a TPU prediction (interpret mode is
an emulator); the HBM-traffic column is the quantity the fused kernels
actually optimize — the jnp reference materialises the lane's whole latent
history in f32 per step, the kernel streams only live fp8 pages once for
all H heads. The JSON keeps both so the perf trajectory starts recording
and TPU runs can drop straight in.
"""
from __future__ import annotations

import json
import os

from benchmarks.common import ensure_results_dir

ARCH = "deepseek-v2-lite-16b"
SERVE_KEYS = ("generated_tokens", "throughput_tok_s", "tpot_p50_s",
              "tpot_p95_s", "ttft_p50_s", "ttft_p95_s", "latency_s")


def run(quick: bool = False):
    from benchmarks.kernel_micro import latent_rows
    from repro.launch.serve import serve_workload

    requests, new_toks = (4, 6) if quick else (8, 12)
    out = {"arch": ARCH + "-reduced", "mode": "coopt",
           "note": ("CPU container: kernel path runs in Pallas interpret "
                    "mode (emulated) — compare hbm_bytes_per_call, not "
                    "wall-clock; on TPU the kernels run compiled."),
           "serve": {}}
    from repro.kernels import ops
    for label, uk in (("jnp", False), ("kernel", True)):
        r = serve_workload(ARCH + "-reduced", "coopt", requests=requests,
                           num_lanes=2, max_len=256,
                           max_new_tokens=new_toks, use_kernel=uk)
        out["serve"][label] = {k: r[k] for k in SERVE_KEYS}
        # wall-clock honesty: interpret-mode kernel timings are emulator
        # timings, never comparable to the compiled jnp path
        out["serve"][label]["timing"] = ("interpret" if uk and ops.interpret_mode()
                                         else "compiled-xla")
        print(f"bench_mla serve[{label}]: "
              f"{r['throughput_tok_s']} tok/s, "
              f"tpot p50/p95 = {r['tpot_p50_s']}/{r['tpot_p95_s']} s "
              f"[{out['serve'][label]['timing']}]",
              flush=True)
    # headline throughput considers ONLY compiled timings; an interpret-mode
    # kernel run is excluded rather than mislabelled as kernel wall-clock
    out["headline_throughput_tok_s"] = max(
        (s["throughput_tok_s"] for s in out["serve"].values()
         if s["timing"] != "interpret"), default=None)

    header = ["mode", "jnp_us_per_call", "hbm_bytes_per_call",
              "kernel_max_err"]
    out["kernel_micro_latent"] = [dict(zip(header, row))
                                  for row in latent_rows(quick)]
    by_mode = {r["mode"]: r for r in out["kernel_micro_latent"]}
    out["latent_decode_hbm_reduction"] = round(
        1 - by_mode["mla-latent-decode-kernel"]["hbm_bytes_per_call"]
        / by_mode["mla-latent-decode-jnp"]["hbm_bytes_per_call"], 4)

    path = os.path.join(ensure_results_dir(), "BENCH_mla.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    print(f"bench_mla: wrote {path} (latent decode HBM traffic "
          f"-{100 * out['latent_decode_hbm_reduction']:.1f}%)", flush=True)
    return path, out


if __name__ == "__main__":
    run()
