"""Compile every Pallas kernel of the serving path for a TPU v5e, at real
widths, without a chip: the TPU compiler is installed and compiles against
a described ``v5e:2x2`` topology. Interpret mode checks none of what Mosaic
enforces (block tiling, VMEM limits), so these compiles are the guard that
the kernels the chip runs are accepted at all. Nothing runs; only shapes
are passed.

Widths: the dense pooled kernels at qwen3-4b (Hq 32, Hkv 8, D 128, page 64)
in bf16 and fp8; ``flash_prefill`` at the same heads; the latent kernels at
deepseek-v2-lite (H 16, kv_lora 512, rope 64); and one ``shard_map``'d
paged decode over a 4-device described mesh.

The topology is described only inside the module fixture below (never at
import), so every test worker collects the same tests and only the worker
that runs this file loads the TPU library.
"""
import math
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.kernels import flash_chunk_prefill as fc
from repro.kernels import flash_prefill as fp
from repro.kernels import kv_cache_write as kw
from repro.kernels import latent_chunk_prefill as lc
from repro.kernels import paged_gqa_decode as pd
from repro.kernels import paged_latent_decode as ld
from repro.kernels import sharded

FP8 = jnp.float8_e4m3fn
B, HQ, HKV, D, PS, POOL, NSEL, CHUNK = 8, 32, 8, 128, 64, 129, 8, 256
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
    kv = s((POOL, HKV, PS, D), dtype)
    sc = s((POOL, HKV, PS), jnp.float32)
    return kv, sc


@pytest.mark.parametrize("dtype", [jnp.bfloat16, FP8], ids=["bf16", "fp8"])
def test_paged_pool_decode_compiles(one_chip, dtype):
    s = _spec(one_chip)
    kv, sc = _dense_pool(s, dtype)
    opt_kv = dtype == FP8
    _compile(lambda q, k, v, ks, vs, cl, ph, lg: pd.paged_pool_decode(
        q, k, v, ks, vs, cl, ph, lg, opt_kv=opt_kv, opt_gqa=True),
        s((B, HQ, D), jnp.bfloat16), kv, kv, sc, sc, s((B,), jnp.int32),
        s((B, NSEL), jnp.int32), s((B, NSEL), jnp.int32))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, FP8], ids=["bf16", "fp8"])
def test_paged_pool_decode_visits_compiles(one_chip, dtype):
    s = _spec(one_chip)
    kv, sc = _dense_pool(s, dtype)
    opt_kv = dtype == FP8
    nv = B * NSEL
    _compile(lambda q, k, v, ks, vs, cl, vp, vm, vl:
             pd.paged_pool_decode_visits(q, k, v, ks, vs, cl, vp, vm, vl,
                                         opt_kv=opt_kv, opt_gqa=True),
             s((B, HQ, D), jnp.bfloat16), kv, kv, sc, sc,
             s((B,), jnp.int32), s((nv,), jnp.int32), s((nv,), jnp.int32),
             s((nv,), jnp.int32))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, FP8], ids=["bf16", "fp8"])
def test_flash_chunk_prefill_compiles(one_chip, dtype):
    s = _spec(one_chip)
    kv, sc = _dense_pool(s, dtype)
    opt_kv = dtype == FP8
    _compile(lambda q, pos, k, v, ks, vs, ph: fc.flash_chunk_prefill(
        q, pos, k, v, ks, vs, ph, opt_kv=opt_kv, opt_gqa=True),
        s((2, CHUNK, HQ, D), jnp.bfloat16), s((2, CHUNK), jnp.int32),
        kv, kv, sc, sc, s((2, NSEL), jnp.int32))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, FP8], ids=["bf16", "fp8"])
def test_kv_cache_write_compiles(one_chip, dtype):
    s = _spec(one_chip)
    opt_kv = dtype == FP8
    _compile(lambda k, v, slots, kv, sc: kw.kv_cache_write(
        k, v, slots, kv, sc, opt_kv=opt_kv),
        s((2, CHUNK, HKV, D), jnp.bfloat16),
        s((2, CHUNK, HKV, D), jnp.bfloat16), s((2, CHUNK), jnp.int32),
        s((2, POOL, HKV, PS, D), dtype), s((2, POOL, HKV, PS), jnp.float32))


def test_flash_prefill_compiles(one_chip):
    s = _spec(one_chip)
    _compile(lambda q, k, v: fp.flash_prefill(q, k, v),
             s((1, 512, HQ, D), jnp.bfloat16),
             s((1, 512, HKV, D), jnp.bfloat16),
             s((1, 512, HKV, D), jnp.bfloat16))


def _latent_pool(s, dtype):
    return (s((POOL, PS, MLA_R + MLA_DR), dtype),
            s((POOL, PS, 2), jnp.float32))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, FP8], ids=["bf16", "fp8"])
def test_paged_latent_decode_compiles(one_chip, dtype):
    s = _spec(one_chip)
    lat, sc = _latent_pool(s, dtype)
    opt_kv = dtype == FP8
    _compile(lambda ql, qr, lt, sp, cl, ph, lg: ld.paged_latent_decode(
        ql, qr, lt, sp, cl, ph, lg, sm_scale=MLA_SCALE, opt_kv=opt_kv),
        s((B, MLA_H, MLA_R), jnp.bfloat16), s((B, MLA_H, MLA_DR),
                                             jnp.bfloat16),
        lat, sc, s((B,), jnp.int32), s((B, NSEL), jnp.int32),
        s((B, NSEL), jnp.int32))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, FP8], ids=["bf16", "fp8"])
def test_paged_latent_decode_visits_compiles(one_chip, dtype):
    s = _spec(one_chip)
    lat, sc = _latent_pool(s, dtype)
    opt_kv = dtype == FP8
    nv = B * NSEL
    _compile(lambda ql, qr, lt, sp, cl, vp, vm, vl:
             ld.paged_latent_decode_visits(ql, qr, lt, sp, cl, vp, vm, vl,
                                           sm_scale=MLA_SCALE,
                                           opt_kv=opt_kv),
             s((B, MLA_H, MLA_R), jnp.bfloat16),
             s((B, MLA_H, MLA_DR), jnp.bfloat16), lat, sc,
             s((B,), jnp.int32), s((nv,), jnp.int32), s((nv,), jnp.int32),
             s((nv,), jnp.int32))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, FP8], ids=["bf16", "fp8"])
def test_latent_chunk_prefill_compiles(one_chip, dtype):
    s = _spec(one_chip)
    lat, sc = _latent_pool(s, dtype)
    opt_kv = dtype == FP8
    _compile(lambda ql, qr, pos, lt, sp, ph: lc.latent_chunk_prefill(
        ql, qr, pos, lt, sp, ph, sm_scale=MLA_SCALE, opt_kv=opt_kv),
        s((2, CHUNK, MLA_H, MLA_R), jnp.bfloat16),
        s((2, CHUNK, MLA_H, MLA_DR), jnp.bfloat16),
        s((2, CHUNK), jnp.int32), lat, sc, s((2, NSEL), jnp.int32))


def test_sharded_paged_decode_compiles(topo):
    """The pages-sharded pool over four chips: the per-shard kernel and the
    log-sum-exp merge's collectives compile as one program."""
    mesh = Mesh(topo.devices[:4], ("data",), axis_types=(AxisType.Auto,))
    ctx = sharded.ShardCtx(mesh=mesh, axes=("data",), num_shards=4)
    rep = _spec(NamedSharding(mesh, P()))
    pages = _spec(NamedSharding(mesh, P(None, "data")))
    compiled = sharded.paged_pool_decode.lower(
        ctx, rep((B, HQ, D), jnp.bfloat16),
        pages((2, 4 * POOL, HKV, PS, D), FP8),
        pages((2, 4 * POOL, HKV, PS), jnp.float32), rep((B,), jnp.int32),
        rep((B, NSEL), jnp.int32), rep((B, NSEL), jnp.int32),
        opt_kv=True, opt_gqa=True).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-reduce" in text
