"""Compile every Pallas kernel of the serving path for a TPU v5e, at real
widths, without a chip: the TPU compiler is installed and compiles against
a described ``v5e:2x2`` topology. Interpret mode checks none of what Mosaic
enforces (block tiling, VMEM limits), so these compiles are the guard that
the kernels the chip runs are accepted at all. Nothing runs; only shapes
are passed.

Widths: the dense pooled kernels at qwen3-4b (Hq 32, Hkv 8, D 128, page 64)
in bf16 and fp8, each over a pool of several layers with the layer a traced
scalar; ``flash_prefill`` at the same heads; the latent kernels at
deepseek-v2-lite (H 16, kv_lora 512, rope 64); and one ``shard_map``'d
paged decode over a 4-device described mesh.

The step guard compiles the dense model's decode step and chunked prefill
at qwen3-4b widths (a few layers, an fp8 pool with scales, donated as the
engine donates it) and checks that the compiled program never moves the
K/V pool: no op but the kernels' custom calls and the layer loop has a
result as large as one layer's pool.

The topology is described only inside the module fixture below (never at
import), so every test worker collects the same tests and only the worker
that runs this file loads the TPU library.
"""
import dataclasses
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.configs.base import CacheConfig
from repro.core.coopt import MODES
from repro.kernels import flash_chunk_prefill as fc
from repro.kernels import flash_prefill as fp
from repro.kernels import kv_cache_write as kw
from repro.kernels import latent_chunk_prefill as lc
from repro.kernels import paged_gqa_decode as pd
from repro.kernels import paged_latent_decode as ld
from repro.kernels import sharded
from repro.models import get_model

FP8 = jnp.float8_e4m3fn
B, HQ, HKV, D, PS, POOL, NSEL, CHUNK = 8, 32, 8, 128, 64, 129, 8, 256
LAYERS = 3                   # pools hold several layers; a scalar picks one
MLA_H, MLA_R, MLA_DR = 16, 512, 64
MLA_SCALE = 1.0 / math.sqrt(128 + MLA_DR)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    old_log = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around them
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:          # noqa: BLE001 — any failure to describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
        compilation_cache.reset_cache()
        if old_log is None:
            os.environ.pop("TPU_LOG_DIR", None)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding):
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    return spec


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _dense_pool(s, dtype):
    kv = s((LAYERS, 2, POOL, HKV, PS, D), dtype)
    sc = s((LAYERS, 2, POOL, HKV, PS), jnp.float32)
    return kv, sc


@pytest.mark.parametrize("dtype", [jnp.bfloat16, FP8], ids=["bf16", "fp8"])
def test_paged_pool_decode_compiles(one_chip, dtype):
    s = _spec(one_chip)
    kv, sc = _dense_pool(s, dtype)
    opt_kv = dtype == FP8
    _compile(lambda q, kv, sc, lyr, cl, ph, lg: pd.paged_pool_decode(
        q, kv, sc, lyr, cl, ph, lg, opt_kv=opt_kv, opt_gqa=True),
        s((B, HQ, D), jnp.bfloat16), kv, sc, s((), jnp.int32),
        s((B,), jnp.int32), s((B, NSEL), jnp.int32), s((B, NSEL), jnp.int32))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, FP8], ids=["bf16", "fp8"])
def test_paged_pool_decode_visits_compiles(one_chip, dtype):
    s = _spec(one_chip)
    kv, sc = _dense_pool(s, dtype)
    opt_kv = dtype == FP8
    nv = B * NSEL
    _compile(lambda q, kv, sc, lyr, cl, vp, vm, vl:
             pd.paged_pool_decode_visits(q, kv, sc, lyr, cl, vp, vm, vl,
                                         opt_kv=opt_kv, opt_gqa=True),
             s((B, HQ, D), jnp.bfloat16), kv, sc, s((), jnp.int32),
             s((B,), jnp.int32), s((nv,), jnp.int32), s((nv,), jnp.int32),
             s((nv,), jnp.int32))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, FP8], ids=["bf16", "fp8"])
def test_flash_chunk_prefill_compiles(one_chip, dtype):
    s = _spec(one_chip)
    kv, sc = _dense_pool(s, dtype)
    opt_kv = dtype == FP8
    _compile(lambda q, pos, kv, sc, lyr, ph: fc.flash_chunk_prefill(
        q, pos, kv, sc, lyr, ph, opt_kv=opt_kv, opt_gqa=True),
        s((2, CHUNK, HQ, D), jnp.bfloat16), s((2, CHUNK), jnp.int32),
        kv, sc, s((), jnp.int32), s((2, NSEL), jnp.int32))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, FP8], ids=["bf16", "fp8"])
def test_kv_cache_write_compiles(one_chip, dtype):
    s = _spec(one_chip)
    kv, sc = _dense_pool(s, dtype)
    opt_kv = dtype == FP8
    _compile(lambda k, v, lines, kv, sc: kw.kv_cache_write(
        k, v, lines, kv, sc if opt_kv else None, opt_kv=opt_kv),
        s((2, CHUNK, HKV, D), jnp.bfloat16),
        s((2, CHUNK, HKV, D), jnp.bfloat16), s((2, CHUNK), jnp.int32),
        kv, sc)


def test_flash_prefill_compiles(one_chip):
    s = _spec(one_chip)
    _compile(lambda q, k, v: fp.flash_prefill(q, k, v),
             s((1, 512, HQ, D), jnp.bfloat16),
             s((1, 512, HKV, D), jnp.bfloat16),
             s((1, 512, HKV, D), jnp.bfloat16))


def _latent_pool(s, dtype):
    return (s((LAYERS, POOL, PS, MLA_R + MLA_DR), dtype),
            s((LAYERS, POOL, PS, 2), jnp.float32))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, FP8], ids=["bf16", "fp8"])
def test_paged_latent_decode_compiles(one_chip, dtype):
    s = _spec(one_chip)
    lat, sc = _latent_pool(s, dtype)
    opt_kv = dtype == FP8
    _compile(lambda ql, qr, lt, sp, lyr, cl, ph, lg: ld.paged_latent_decode(
        ql, qr, lt, sp, lyr, cl, ph, lg, sm_scale=MLA_SCALE, opt_kv=opt_kv),
        s((B, MLA_H, MLA_R), jnp.bfloat16), s((B, MLA_H, MLA_DR),
                                             jnp.bfloat16),
        lat, sc, s((), jnp.int32), s((B,), jnp.int32),
        s((B, NSEL), jnp.int32), s((B, NSEL), jnp.int32))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, FP8], ids=["bf16", "fp8"])
def test_paged_latent_decode_visits_compiles(one_chip, dtype):
    s = _spec(one_chip)
    lat, sc = _latent_pool(s, dtype)
    opt_kv = dtype == FP8
    nv = B * NSEL
    _compile(lambda ql, qr, lt, sp, lyr, cl, vp, vm, vl:
             ld.paged_latent_decode_visits(ql, qr, lt, sp, lyr, cl, vp, vm,
                                           vl, sm_scale=MLA_SCALE,
                                           opt_kv=opt_kv),
             s((B, MLA_H, MLA_R), jnp.bfloat16),
             s((B, MLA_H, MLA_DR), jnp.bfloat16), lat, sc,
             s((), jnp.int32), s((B,), jnp.int32), s((nv,), jnp.int32),
             s((nv,), jnp.int32), s((nv,), jnp.int32))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, FP8], ids=["bf16", "fp8"])
def test_latent_chunk_prefill_compiles(one_chip, dtype):
    s = _spec(one_chip)
    lat, sc = _latent_pool(s, dtype)
    opt_kv = dtype == FP8
    _compile(lambda ql, qr, pos, lt, sp, lyr, ph: lc.latent_chunk_prefill(
        ql, qr, pos, lt, sp, lyr, ph, sm_scale=MLA_SCALE, opt_kv=opt_kv),
        s((2, CHUNK, MLA_H, MLA_R), jnp.bfloat16),
        s((2, CHUNK, MLA_H, MLA_DR), jnp.bfloat16),
        s((2, CHUNK), jnp.int32), lat, sc, s((), jnp.int32),
        s((2, NSEL), jnp.int32))


def test_sharded_paged_decode_compiles(topo):
    """The pages-sharded pool over four chips: the per-shard kernel and the
    log-sum-exp merge's collectives compile as one program."""
    mesh = Mesh(topo.devices[:4], ("data",), axis_types=(AxisType.Auto,))
    ctx = sharded.ShardCtx(mesh=mesh, axes=("data",), num_shards=4)
    rep = _spec(NamedSharding(mesh, P()))
    pages = _spec(NamedSharding(mesh, P(None, None, "data")))
    compiled = sharded.paged_pool_decode.lower(
        ctx, rep((B, HQ, D), jnp.bfloat16),
        pages((LAYERS, 2, 4 * POOL, HKV, PS, D), FP8),
        pages((LAYERS, 2, 4 * POOL, HKV, PS), jnp.float32),
        rep((), jnp.int32), rep((B,), jnp.int32),
        rep((B, NSEL), jnp.int32), rep((B, NSEL), jnp.int32),
        opt_kv=True, opt_gqa=True).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-reduce" in text


# ------------------------------------------------------------- step guard --
# the guard's pool has a deployment's page count, so the compiler sees a
# pool too large to stage in on-chip memory, as it is on the chip
GUARD_PAGES, GUARD_LANES, GUARD_CHUNK, GUARD_TABLE = 768, 8, 64, 16
# ops that only name a buffer, and the ones allowed to hold the pool: the
# kernels (the write updates the pool in place) and the layer loop
NAMING_OPS = {"parameter", "get-tuple-element", "tuple", "bitcast",
              "custom-call", "while"}
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
_ARRAY = re.compile(r"\b([a-z][a-z0-9]*)\[([0-9,]*)\]")
POOL_DTYPE = "f8e4m3fn"      # no weight or activation of the step is fp8


def pool_moves(hlo_text: str, layer_values: int):
    """(opcode, instruction) of every op in ``hlo_text`` — fused
    computations and loop bodies included — that is not in ``NAMING_OPS``
    and whose result holds ``layer_values`` or more values of the fp8
    pool's dtype (a tuple counts all of its arrays)."""
    out = []
    for line in hlo_text.splitlines():
        if " = " not in line:
            continue
        lhs, rhs = line.split(" = ", 1)
        m = _OPCODE.search(" " + rhs)
        if m is None or m.group(1) in NAMING_OPS:
            continue
        values = sum(math.prod(int(d) for d in dims.split(",") if d)
                     for dtype, dims in _ARRAY.findall(
                         (" " + rhs)[:m.start()]) if dtype == POOL_DTYPE)
        if values >= layer_values:
            out.append((m.group(1), lhs.split()[-1]))
    return out


def test_pool_moves_reads_compiled_hlo_lines():
    """The guard's reader on HLO lines in the compiler's own form: the four
    moves a step that slices the pool per layer compiles to are found; the
    pool's parameter, the write kernel and a weight-sized bf16 fusion are
    not."""
    pool, layer = "f8e4m3fn[4,2,768,8,64,128]", "f8e4m3fn[2,768,8,64,128]"
    half = "f8e4m3fn[768,8,64,128]{3,2,1,0:T(8,128)(4,1)}"
    text = "\n".join([
        f"  %dynamic-slice_bitcast_fusion.2 = {layer}{{4,3,2,1,0}} "
        "fusion(%p, %i), kind=kLoop",
        f"  %slice_bitcast_fusion.9 = ({half}, {half}) fusion(%d), "
        "kind=kLoop",
        f"  %bitcast_dynamic-update-slice_fusion.2 = {pool}{{5,4,3,2,1,0}} "
        "fusion(%b, %c), kind=kLoop",
        f"  %copy.109 = {pool}{{5,4,3,2,1,0}} copy(%w)",
        f"  %c__kv__.1 = {pool}{{5,4,3,2,1,0}} parameter(19)",
        f"  %_kv_cache_write_single.6 = ({pool}{{5,4,3,2,1,0}}) "
        "custom-call(%a), custom_call_target=\"tpu_custom_call\"",
        "  %fusion.84 = bf16[2560,151936]{1,0} fusion(%x), kind=kLoop",
    ])
    got = pool_moves(text, 2 * 768 * 8 * 64 * 128)
    assert got == [("fusion", "%dynamic-slice_bitcast_fusion.2"),
                   ("fusion", "%slice_bitcast_fusion.9"),
                   ("fusion", "%bitcast_dynamic-update-slice_fusion.2"),
                   ("copy", "%copy.109")]


def _guard_config(kind):
    cfg = dataclasses.replace(get_config("qwen3-4b"), num_layers=2)
    if kind == "two_segments":
        # one dense layer, then experts: the layer scan runs two segments
        cfg = dataclasses.replace(cfg, family="moe", num_layers=3,
                                  first_dense_layers=1, num_experts=4,
                                  top_k=2, moe_d_ff=1536)
    return cfg


@pytest.mark.parametrize("step", ["decode", "prefill"])
@pytest.mark.parametrize("kind", ["dense", "two_segments"])
def test_step_never_moves_the_pool(one_chip, monkeypatch, kind, step):
    """The compiled decode step and chunked prefill at qwen3-4b widths hold
    no op outside the kernels and the layer loop with a result of one
    layer's K/V pool or more: the layer scan carries the pool whole and the
    kernels address layer and K/V half in place, so nothing slices, splits,
    restacks or copies it."""
    # the dispatchers pick the compiled kernels, as on the chip
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = _guard_config(kind)
    model = get_model(cfg)
    coopt = MODES["coopt"].replace(use_kernel=True)
    s = _spec(one_chip)
    params = jax.tree.map(lambda x: s(x.shape, x.dtype),
                          jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = {k: s(sh, dt) for k, (sh, dt, _) in model.cache_shape(
        GUARD_LANES, 4096, coopt,
        cache_cfg=CacheConfig(num_pages=GUARD_PAGES)).items()}
    L, _, pages, hkv, ps, d = cache["kv"].shape
    assert (L, pages, cache["kv"].dtype) == (cfg.num_layers, GUARD_PAGES,
                                              FP8)
    assert "scale" in cache
    lanes, S = GUARD_LANES, (1 if step == "decode" else GUARD_CHUNK)
    i32 = lambda *shape: s(shape, jnp.int32)                 # noqa: E731
    batch = {"positions": i32(lanes, S), "slot_idx": i32(lanes, S),
             "page_table": i32(lanes, GUARD_TABLE),
             "cache_len": i32(lanes)}
    if step == "decode":
        batch["token"], fn = i32(lanes, 1), model.decode_step
    else:
        batch["tokens"], fn = i32(lanes, S), model.prefill
    # the engine donates the cache to every step; so does the guard
    compiled = jax.jit(lambda p, b, c: fn(p, b, c, coopt),
                       donate_argnums=2).lower(params, batch, cache).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert pool_moves(text, 2 * pages * hkv * ps * d) == []
