"""Kernel benchmark lane -> experiments/BENCH_kernels.json.

Three sections, with wall-clock honesty as the organizing rule:

``analytic``
    The HBM-traffic model of the decode kernels (``kernel_micro``'s bytes
    model extended with cross-lane visit dedup) evaluated at ONE canonical
    shape set — 8 lanes sharing a 32-page prompt prefix plus 4 private tail
    pages each — regardless of ``--quick``. These columns are deterministic
    and are the regression surface CI gates on (``--compare-baseline``):
    a >5% increase in any ``bytes_per_token`` entry vs the committed
    baseline fails the run. The headline number is the per-lane -> visit
    grid traffic reduction, which must stay >= 4x for this scenario.

``chunk_restream``
    Tile-resident chunk streaming accounting: how many times one KV page is
    streamed per prefill chunk before (fixed 256-row query blocks) vs after
    (``resident_rows()``-sized blocks) for the dense and latent chunk
    kernels, computed from the kernels' own sizing functions.

``measured``
    What this container can honestly time. The jnp reference path is real
    compiled XLA wall-clock and gets ``tokens_per_s``/``tpot_us``. Kernel
    timings are labelled by how they ran: on a real accelerator backend
    they are ``kernel_us`` with throughput; under Pallas interpret mode
    they are recorded as ``interpret_us`` with ``tokens_per_s: null`` and
    an explanatory note — an emulator timing is NEVER reported as kernel
    wall-clock. Parity of the visit grid vs the per-lane grid on a genuinely
    shared page table is checked here too.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import ensure_results_dir
from benchmarks.kernel_micro import (kernel_bytes_per_call,
                                     latent_bytes_per_call)

OUT_NAME = "BENCH_kernels.json"

# canonical shared-prefix decode scenario (acceptance: >=4x traffic drop).
# Analytic columns use these shapes ALWAYS — --quick only shrinks the
# measured section — so a quick CI run compares against the committed
# baseline one-to-one.
CANON = dict(B=8, shared_pages=32, tail_pages=4, ps=64, Hkv=2, G=4, D=128,
             R=512, dr=64)


def _analytic():
    c = CANON
    P = c["shared_pages"] + c["tail_pages"]
    cache_len = P * c["ps"]
    Hq = c["Hkv"] * c["G"]
    common = dict(ps=c["ps"], Hkv=c["Hkv"], D=c["D"], opt_kv=True,
                  opt_pa=True, opt_gqa=True, Hq=Hq, cache_len=cache_len)
    share = dict(shared_prefix_pages=c["shared_pages"], lanes_sharing=c["B"])
    B = c["B"]
    gqa_lane = kernel_bytes_per_call(B, P, **common) / B
    gqa_vis = kernel_bytes_per_call(B, P, **common, **share,
                                    share_visits=True) / B
    lat_args = dict(ps=c["ps"], R=c["R"], dr=c["dr"], fused=True,
                    opt_kv=True, cache_len=cache_len)
    lat_lane = latent_bytes_per_call(B, P, **lat_args) / B
    lat_vis = latent_bytes_per_call(B, P, **lat_args, **share,
                                    share_visits=True) / B
    return {
        "scenario": {**c, "pages_per_lane": P, "cache_len": cache_len},
        # regression-gated columns: analytic HBM bytes per generated token
        "bytes_per_token": {
            "decode-gqa-per-lane": gqa_lane,
            "decode-gqa-visits": gqa_vis,
            "decode-latent-per-lane": lat_lane,
            "decode-latent-visits": lat_vis,
        },
        "gqa_traffic_reduction_x": round(gqa_lane / gqa_vis, 3),
        "latent_traffic_reduction_x": round(lat_lane / lat_vis, 3),
    }


def _chunk_restream():
    from repro.kernels import flash_chunk_prefill as fcp
    from repro.kernels import latent_chunk_prefill as lcp
    out = {}
    G, H = CANON["G"], 16
    for name, rows, fn in (("dense", 1024, lambda r: fcp.resident_rows(r, G)),
                           ("latent", 1024,
                            lambda r: lcp.resident_rows(r, H))):
        rr = fn(rows)
        before = -(-rows // 256)            # fixed 256-row blocks (old)
        after = -(-rows // rr)              # resident-rows blocks (new)
        out[name] = {"chunk_rows": rows, "resident_rows": rr,
                     "page_streams_per_chunk_before": before,
                     "page_streams_per_chunk_after": after,
                     "restream_reduction_x": round(before / after, 3)}
    return out


def _shared_tables(B, P, shared, ps):
    """Physical/logical page tables where pages 0..shared-1 are common to
    every lane (refcount-shared prefix) and tails are lane-private."""
    phys = np.zeros((B, P), np.int32)
    for b in range(B):
        for i in range(P):
            phys[b, i] = i if i < shared else \
                shared + b * (P - shared) + (i - shared)
    log = np.broadcast_to(np.arange(P, dtype=np.int32)[None], (B, P))
    total = shared + B * (P - shared)
    return jnp.asarray(phys), jnp.asarray(np.ascontiguousarray(log)), total


def _time(fn, *args, n=10):
    out = fn(*args)
    jax.tree.map(lambda x: x.block_until_ready(), out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.tree.map(lambda x: x.block_until_ready(), out)
    return (time.perf_counter() - t0) / n * 1e6


def _measured(quick: bool):
    from repro.cache.quant import quantize_fp8
    from repro.kernels import ops, ref

    B = 8
    shared, tail, ps = (4, 2, 16) if quick else (8, 4, 16)
    P = shared + tail
    Hkv, G, D = 1, 4, 128
    Hq = Hkv * G
    phys, log, PT = _shared_tables(B, P, shared, ps)
    cache_len = P * ps
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, Hq, D)).astype(jnp.bfloat16)
    kf = jax.random.normal(ks[1], (PT, Hkv, ps, D), jnp.float32)
    vf = jax.random.normal(ks[2], (PT, Hkv, ps, D), jnp.float32)
    kq, ksc = quantize_fp8(kf)
    vq, vsc = quantize_fp8(vf)
    # a pool of one layer, attended at layer 0
    kv, sc = jnp.stack([kq, vq])[None], jnp.stack([ksc, vsc])[None]
    cl = jnp.full((B,), cache_len, jnp.int32)

    def kern(share):
        return ops.paged_pool_decode(q, kv, sc, 0, cl, phys, log,
                                     opt_kv=True, opt_gqa=True,
                                     share_visits=share)

    o_lane = kern(False)
    o_vis = kern(True)
    parity = float(np.abs(np.asarray(o_vis, np.float32)
                          - np.asarray(o_lane, np.float32)).max())

    # honest compiled-XLA wall-clock: the jnp gather oracle on the SAME
    # shared page table
    jref = jax.jit(lambda q_, cl_: ref.paged_pool_decode_ref(
        q_, kv[0, 0], kv[0, 1], sc[0, 0], sc[0, 1], cl_, phys, log,
        opt_kv=True))
    err = float(np.abs(np.asarray(o_vis, np.float32)
                       - np.asarray(jref(q, cl), np.float32)).max())
    us_jnp = _time(jref, q, cl)
    out = {
        "shape": {"B": B, "shared_pages": shared, "tail_pages": tail,
                  "ps": ps, "Hkv": Hkv, "G": G, "D": D},
        "visit_vs_perlane_max_err": parity,
        "visit_vs_oracle_max_err": err,
        "jnp_reference": {
            "timing": "compiled-xla",
            "us_per_call": round(us_jnp, 1),
            "tpot_us": round(us_jnp, 1),       # 1 token/lane/call
            "tokens_per_s": round(B / (us_jnp * 1e-6), 1),
        },
    }
    us_lane = _time(kern, False)
    us_vis = _time(kern, True)
    if ops.interpret_mode():
        # emulator timings: recorded for completeness, never as kernel
        # wall-clock, never with a throughput number
        out["kernel"] = {
            "timing": "interpret",
            "interpret_us_per_lane_grid": round(us_lane, 1),
            "interpret_us_visit_grid": round(us_vis, 1),
            "tokens_per_s": None,
            "tpot_us": None,
            "note": ("Pallas interpret mode (no accelerator backend): "
                     "these are emulator timings — compare the analytic "
                     "bytes_per_token columns, not wall-clock."),
        }
    else:
        out["kernel"] = {
            "timing": "compiled",
            "backend": jax.default_backend(),
            "us_per_call_per_lane_grid": round(us_lane, 1),
            "us_per_call_visit_grid": round(us_vis, 1),
            "tpot_us": round(us_vis, 1),
            "tokens_per_s": round(B / (us_vis * 1e-6), 1),
        }
    return out


def run(quick: bool = False):
    analytic = _analytic()
    out = {
        "backend": jax.default_backend(),
        "analytic": analytic,
        "chunk_restream": _chunk_restream(),
        "measured": _measured(quick),
    }
    path = os.path.join(ensure_results_dir(), OUT_NAME)
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    bt = analytic["bytes_per_token"]
    print(f"bench_kernels: wrote {path}\n"
          f"  gqa   bytes/token {bt['decode-gqa-per-lane']:.0f} -> "
          f"{bt['decode-gqa-visits']:.0f} "
          f"({analytic['gqa_traffic_reduction_x']}x)\n"
          f"  latent bytes/token {bt['decode-latent-per-lane']:.0f} -> "
          f"{bt['decode-latent-visits']:.0f} "
          f"({analytic['latent_traffic_reduction_x']}x)", flush=True)
    return path, out


def compare_baseline(result: dict, baseline_path: str,
                     tol: float = 0.05) -> int:
    """Gate: fail (1) if any analytic bytes/token column regressed >tol
    vs the committed baseline. Timing keys are NEVER gated — wall-clock on
    shared CI runners is noise; the analytic model is the contract."""
    with open(baseline_path) as f:
        base = json.load(f)
    new = result["analytic"]["bytes_per_token"]
    old = base["analytic"]["bytes_per_token"]
    bad = []
    for k, b in old.items():
        n = new.get(k)
        if n is None:
            bad.append(f"{k}: column disappeared")
        elif n > b * (1 + tol):
            bad.append(f"{k}: {b:.0f} -> {n:.0f} bytes/token "
                       f"(+{100 * (n / b - 1):.1f}% > {100 * tol:.0f}%)")
    if bad:
        print("bench_kernels: analytic traffic REGRESSION vs baseline:\n  "
              + "\n  ".join(bad), file=sys.stderr)
        return 1
    print(f"bench_kernels: analytic bytes/token within {100 * tol:.0f}% of "
          f"baseline ({baseline_path})", flush=True)
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--compare-baseline", default=None, metavar="PATH",
                    help="committed BENCH_kernels.json to gate analytic "
                         "bytes/token columns against (>5%% fails)")
    args = ap.parse_args(argv)
    _, out = run(quick=args.quick)
    if args.compare_baseline:
        return compare_baseline(out, args.compare_baseline)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
