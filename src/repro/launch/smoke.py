"""Bring-up smoke of the serving path, independent of the device.

``run_smoke`` serves a few seeded requests through ``launch.serve.
ServeRunner`` in ``coopt`` mode with the Pallas kernels on, then checks
what came out: every request FINISHED with all its tokens, and the kernel
path's first prefill and decode logits agree with the jnp path's on the
same weights. ``run_mesh_smoke`` serves the same requests on one device
and on a pages-sharded ``(data=n, model=1)`` mesh, compares their greedy
tokens, and requires the mesh's first prefill and decode logits to agree
with one device's on the same tokens. ``chip_smoke.py`` runs both at full
width on TPUs; the CPU tests run them at reduced width in interpret mode.
Any failed check raises ``SmokeFailure``.

Why logits and not tokens decide the mesh check: with seeded random
weights the top logits of a 150k vocabulary are nearly tied, so greedy
decoding follows any last-bit difference. A mesh computes the model's
dense layers partitioned, which on the TPU may round differently from one
device; the tokens then split at a near-tie and stay split. The agreement
is reported; a sharding error shows as a logit error of order one.
"""
from __future__ import annotations

import gc
import time
from functools import partial
from typing import Callable, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core.coopt import MODES
from repro.models import get_model

# Kernel vs jnp logits: both paths read the same fp8 pool and bf16 weights
# and differ in the order and precision of their attention arithmetic
# (f32 online softmax in VMEM against XLA's bf16 einsums), so across a deep
# model they agree to bf16 rounding: max |kernel - jnp| over max |jnp|.
LOGIT_RTOL = 1e-1


class SmokeFailure(RuntimeError):
    """A check of the smoke failed: the served path is not sound."""


def seeded_params(model, seed: int, sharding=None):
    """The model's seeded init, built on the device (``sharding``: where
    to put it; default the first device)."""
    init = jax.jit(model.init, out_shardings=sharding)
    return init(jax.random.PRNGKey(seed))


def weights_fingerprint(params) -> List[int]:
    """Exact per-leaf checksums (integer sums of the raw bits), equal on
    any device layout exactly when the weights are bit-identical."""
    def bits(x):
        u = jax.lax.bitcast_convert_type(
            x, {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}[x.dtype.itemsize])
        return jnp.sum(u.astype(jnp.uint32))
    return [int(bits(x)) for x in jax.tree.leaves(params)]


def first_step_logits(model, params, tokens: np.ndarray, max_len: int,
                      coopt, mesh=None) -> List[np.ndarray]:
    """Logits of one chunked prefill of ``tokens`` (B, S) and one decode
    step after it, through ``model`` under ``coopt``, on a fresh pool —
    sharded by pages over ``mesh`` when one is given."""
    from repro.kernels import ops
    B, S = tokens.shape
    ctx, shards = None, 1
    if mesh is not None:
        from repro.launch.mesh import kv_shard_count
        ctx, shards = ops.make_mesh_ctx(mesh), kv_shard_count(mesh)
    cache = model.init_cache(B, max_len, coopt, num_shards=shards)
    if mesh is not None:
        from repro.serving.engine import place_cache
        cache = place_cache(cache, model.cache_shape(
            B, max_len, coopt, num_shards=shards), mesh, coopt.use_kernel)

    def step(fn, params, batch, cache):
        with ops.mesh_ctx_scope(ctx):            # bound while tracing
            return fn(params, batch, cache, coopt)

    prefill = jax.jit(partial(step, model.prefill))
    decode = jax.jit(partial(step, model.decode_step))
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    logits, cache = prefill(params, {
        "tokens": jnp.asarray(tokens), "positions": pos,
        "cache_len": jnp.full((B,), S, jnp.int32)}, cache)
    dlogits, _ = decode(params, {
        "token": jnp.asarray(tokens[:, :1]),
        "positions": jnp.full((B, 1), S, jnp.int32),
        "cache_len": jnp.full((B,), S + 1, jnp.int32)}, cache)
    return [np.asarray(logits, np.float32), np.asarray(dlogits, np.float32)]


def compare_logits(got: np.ndarray, ref: np.ndarray, what: str) -> float:
    """Relative max error of ``got`` against ``ref``; raises on a
    non-finite logit or an error past ``LOGIT_RTOL``."""
    if got.shape != ref.shape:
        raise SmokeFailure(f"{what}: logits {got.shape} vs {ref.shape}")
    if not (np.isfinite(got).all() and np.isfinite(ref).all()):
        raise SmokeFailure(f"{what}: non-finite logits")
    err = float(np.max(np.abs(got - ref)) / max(np.max(np.abs(ref)), 1e-6))
    if err > LOGIT_RTOL:
        raise SmokeFailure(f"{what}: logits differ by {err:.3e} of max "
                           f"|logit| (limit {LOGIT_RTOL})")
    return err


def check_finished(requests) -> int:
    """Every request FINISHED with all its tokens; returns the tokens."""
    from repro.serving import FinishReason
    total = 0
    for r in requests:
        if (r.finish_reason is not FinishReason.FINISHED
                or len(r.output) != r.max_new_tokens):
            raise SmokeFailure(
                f"request {r.req_id} ended {r.finish_reason} with "
                f"{len(r.output)}/{r.max_new_tokens} tokens")
        total += len(r.output)
    return total


def _serve(arch: str, params, log: Callable, *, requests: int, lanes: int,
           max_len: int, new_tokens: int, bucket: int, scale: float,
           seed: int, mesh=None, pool_pages: int = 0, label: str = ""):
    """One warmed ServeRunner pass; returns (report, requests served)."""
    from repro.launch.serve import ServeRunner
    t0 = time.perf_counter()
    runner = ServeRunner(arch, "coopt", requests=requests, num_lanes=lanes,
                         max_len=max_len, max_new_tokens=new_tokens,
                         scale=scale, seed=seed, use_kernel=True,
                         prefill_buckets=(bucket,), params=params,
                         mesh=mesh, pool_pages=pool_pages, warmup_pass=True)
    warm = time.perf_counter() - t0
    wall = runner.measure()
    reqs = runner.last_requests
    tokens = check_finished(reqs)
    rep = {"warmup_s": warm, "wall_s": wall, "tokens": tokens,
           "prompt_lens": [r.prompt_len for r in reqs]}
    log(f"{label}compile+warmup pass: {warm:.3f} s (step traces "
        f"{dict(runner.engine.trace_counts)})")
    log(f"{label}served pass: {wall:.3f} s wall, {tokens} tokens generated "
        f"for {len(reqs)} requests (prompts {rep['prompt_lens']})")
    return rep, reqs


def run_smoke(arch: str = "qwen3-4b", *, requests: int = 8, lanes: int = 8,
              max_len: int = 768, new_tokens: int = 32, bucket: int = 256,
              scale: float = 1.0, seed: int = 0,
              log: Callable = print) -> Dict:
    """Serve ``requests`` seeded requests of ``arch`` on the default device
    and check them (see the module docstring). Returns the report."""
    cfg = get_config(arch)
    model = get_model(cfg)
    coopt = MODES["coopt"].replace(use_kernel=True)
    t0 = time.perf_counter()
    params = seeded_params(model, seed)
    nbytes = sum(x.nbytes for x in jax.tree.leaves(params))
    jax.block_until_ready(params)
    log(f"{arch}: {cfg.num_layers} layers, d_model {cfg.d_model}, "
        f"{nbytes} bytes of seeded weights in "
        f"{time.perf_counter() - t0:.3f} s")
    rep, _ = _serve(arch, params, log, requests=requests, lanes=lanes,
                    max_len=max_len, new_tokens=new_tokens, bucket=bucket,
                    scale=scale, seed=seed)

    tokens = _probe_tokens(cfg, lanes, bucket, seed)
    t0 = time.perf_counter()
    got = first_step_logits(model, params, tokens, max_len, coopt)
    ref = first_step_logits(model, params, tokens, max_len,
                            coopt.replace(use_kernel=False))
    rep["logit_check_s"] = time.perf_counter() - t0
    rep["prefill_logit_err"] = compare_logits(got[0], ref[0],
                                              "kernel vs jnp prefill")
    rep["decode_logit_err"] = compare_logits(got[1], ref[1],
                                             "kernel vs jnp decode")
    log(f"kernel vs jnp logits (max |diff| / max |logit|, limit "
        f"{LOGIT_RTOL}): prefill {rep['prefill_logit_err']:.3e}, decode "
        f"{rep['decode_logit_err']:.3e} ({rep['logit_check_s']:.3f} s)")
    return rep


def _probe_tokens(cfg, rows: int, length: int, seed: int) -> np.ndarray:
    """Seeded (rows, length) tokens for the logit checks. ``rows`` is the
    served batch's lane count, so a mesh splits the probe's batch as it
    splits a served step's."""
    rng = np.random.default_rng(seed)
    return rng.integers(0, cfg.vocab_size, (rows, length), dtype=np.int32)


def run_mesh_smoke(arch: str = "qwen3-4b", *, shards: int = 4,
                   requests: int = 8, lanes: int = 8, max_len: int = 768,
                   new_tokens: int = 32, bucket: int = 256,
                   scale: float = 1.0, seed: int = 0,
                   log: Callable = print) -> Dict:
    """Serve the same seeded requests on one device, then on a
    ``(data=shards, model=1)`` mesh (pages-sharded pool, kernels through
    ``kernels.sharded``, weights replicated), in this process. Both must
    finish every request on the same weights, and the mesh's first-step
    logits must agree with one device's; greedy agreement is reported."""
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.launch.mesh import make_sim_mesh
    cfg = get_config(arch)
    model = get_model(cfg)
    coopt = MODES["coopt"].replace(use_kernel=True)
    probe = _probe_tokens(cfg, lanes, bucket, seed)
    kw = dict(requests=requests, lanes=lanes, max_len=max_len,
              new_tokens=new_tokens, bucket=bucket, scale=scale, seed=seed,
              # room for every request in whichever shard it is pinned to
              pool_pages=shards * lanes * -(-max_len // 64))
    params = seeded_params(model, seed)
    one, reqs1 = _serve(arch, params, log, label="1 device: ", **kw)
    out1 = [list(r.output) for r in reqs1]
    ref = first_step_logits(model, params, probe, max_len, coopt)
    fp1 = weights_fingerprint(params)
    # free the one-device weights before the replicated copy lands on the
    # same chip
    del params, reqs1
    gc.collect()

    mesh = make_sim_mesh(data=shards, model=1)
    params = seeded_params(model, seed,
                           NamedSharding(mesh, PartitionSpec()))
    if weights_fingerprint(params) != fp1:
        raise SmokeFailure("the mesh's seeded weights differ from one "
                           "device's: nothing to compare")
    sh, reqs4 = _serve(arch, params, log, mesh=mesh,
                       label=f"{shards}-shard mesh: ", **kw)
    out4 = [list(r.output) for r in reqs4]
    got = first_step_logits(model, params, probe, max_len, coopt, mesh=mesh)
    rep = {"one_device": one, "mesh": sh, "devices": mesh.devices.size,
           "prefill_logit_err": compare_logits(
               got[0], ref[0], "mesh vs one device prefill"),
           "decode_logit_err": compare_logits(
               got[1], ref[1], "mesh vs one device decode")}
    prefix = [next((i for i, (a, b) in enumerate(zip(x, y)) if a != b),
                   len(x)) for x, y in zip(out1, out4)]
    rep["identical_requests"] = sum(p == len(x) for p, x in zip(prefix,
                                                                  out1))
    rep["common_prefix_tokens"] = prefix
    log(f"{shards}-shard mesh vs 1 device, same weights: first-step logits "
        f"differ by {rep['prefill_logit_err']:.3e} (prefill) and "
        f"{rep['decode_logit_err']:.3e} (decode) of max |logit| (limit "
        f"{LOGIT_RTOL}); greedy tokens identical for "
        f"{rep['identical_requests']}/{len(out1)} requests, common prefix "
        f"per request {prefix} of {new_tokens}")
    return rep
