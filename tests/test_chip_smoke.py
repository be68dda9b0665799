"""The chip smoke's body at reduced width on the CPU (kernels interpreted),
the device check in front of it, and the bring-up rules it relies on: the
compile-cache location, the peaks table, weights placed on the mesh, and a
host tier that refuses to run without host memory."""
import importlib.util
import json
import os

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import CacheConfig
from repro.core.coopt import MODES
from repro.launch import compile_cache, smoke
from repro.launch.mesh import chip_peaks, make_host_mesh
from repro.serving import Engine, EngineConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REDUCED = dict(requests=3, lanes=2, max_len=256, new_tokens=3, bucket=32,
               scale=0.2)


def _quiet(msg):
    pass


def test_smoke_body_serves_and_agrees_with_jnp_reduced():
    rep = smoke.run_smoke("qwen3-4b-reduced", log=_quiet, **REDUCED)
    assert rep["tokens"] == REDUCED["requests"] * REDUCED["new_tokens"]
    assert rep["prefill_logit_err"] <= smoke.LOGIT_RTOL
    assert rep["decode_logit_err"] <= smoke.LOGIT_RTOL


def test_mesh_smoke_plumbing_on_one_shard():
    """The four-chip comparison's plumbing (one-device pass, weights freed,
    mesh-replicated weights, token comparison) on the one CPU device."""
    rep = smoke.run_mesh_smoke("qwen3-4b-reduced", shards=1, log=_quiet,
                               **REDUCED)
    assert rep["identical_requests"] == REDUCED["requests"]
    assert rep["devices"] == 1


def test_smoke_checks_fail_loudly():
    ref = np.ones((2, 8), np.float32)
    with pytest.raises(smoke.SmokeFailure, match="non-finite"):
        smoke.compare_logits(ref * np.nan, ref, "x")
    with pytest.raises(smoke.SmokeFailure, match="differ"):
        smoke.compare_logits(ref * 2, ref, "x")
    assert smoke.compare_logits(ref, ref, "x") == 0.0

    class Req:
        req_id, output, max_new_tokens, finish_reason = 1, [5], 2, None
    with pytest.raises(smoke.SmokeFailure, match="ended"):
        smoke.check_finished([Req()])


def test_chip_smoke_refuses_without_tpu(capsys):
    """On the CPU the script exits non-zero before running anything and
    prints no ok line."""
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    old = jax.config.jax_compilation_cache_dir
    try:
        assert mod.main([]) == 1
    finally:
        jax.config.update("jax_compilation_cache_dir", old)
    out, err = capsys.readouterr()
    assert "no TPU" in err
    for line in out.splitlines():
        assert '"ok"' not in line or not json.loads(line).get("ok")


@pytest.fixture
def restore_cache_dir():
    old = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", old)


def test_compile_cache_env_var_wins(monkeypatch, tmp_path,
                                    restore_cache_dir):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == os.path.join(ROOT, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path


def test_chip_peaks_by_device_kind():
    v5e = chip_peaks("TPU v5 lite")
    assert v5e.bf16_flops == 197e12 and v5e.hbm_bw == 819e9
    assert "TPU v5e" in v5e.source
    with pytest.raises(ValueError, match="no published peaks"):
        chip_peaks("TPU v9000")


def test_engine_places_params_on_mesh_once():
    cfg = get_config("qwen3-4b-reduced")
    mesh = make_host_mesh()
    eng = Engine(cfg, MODES["coopt"],
                 EngineConfig(num_lanes=1, max_len=64,
                              prefill_buckets=(16,)), mesh=mesh)
    for leaf in jax.tree.leaves(eng.params):
        assert isinstance(leaf.sharding, jax.sharding.NamedSharding)
        assert leaf.sharding.mesh == mesh and leaf.sharding.is_fully_replicated


def test_host_tier_without_cpu_backend_raises(monkeypatch):
    real = jax.devices

    def no_cpu(backend=None):
        if backend == "cpu":
            raise RuntimeError("Unknown backend cpu")
        return real(backend)

    monkeypatch.setattr(jax, "devices", no_cpu)
    with pytest.raises(RuntimeError, match="host memory"):
        Engine(get_config("qwen3-4b-reduced"), MODES["coopt"],
               EngineConfig(num_lanes=1, max_len=64, prefill_buckets=(16,),
                            cache=CacheConfig(host_pages=4)))
