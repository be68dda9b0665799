"""shard_map'd pooled Pallas kernels — ONE kernel hot path for single-host
AND distributed serving (PR 5 acceptance).

Covers: kernel-level parity of every sharded wrapper vs the jnp reference
(at one layer alone and at a nonzero layer of a pool of several, where the
result must equal the same wrapper's on that layer alone bit for bit),
engine-level greedy identity (dense AND mla) with ``use_kernel`` under a
simulated multi-device mesh, the no-pool-all-gather HLO guarantee of the
sharded step, the EngineConfig.num_shards <-> mesh consistency bugfix, and
the regression that an UNSHARDED mesh takes the identical code path as no
mesh at all.

Mesh sizing is driven by the CI mesh matrix: ``REPRO_KV_SHARDS`` (default:
4 when >= 8 simulated devices are available, else 1) picks the pages-axis
extent; tests that need a sharded mesh skip when the environment cannot
form one (device_count 1/2 cells of the matrix).
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import opt_kv, opt_pa
from repro.core.coopt import COOPT, MODES
from repro.kernels import ops
from repro.launch.mesh import kv_shard_count, make_host_mesh, make_sim_mesh
from repro.serving import Engine, EngineConfig

NDEV = len(jax.devices())
KV_SHARDS = int(os.environ.get("REPRO_KV_SHARDS", "0")) or \
    (4 if NDEV >= 8 else 1)
MODEL_PAR = 2 if NDEV >= 2 * KV_SHARDS else 1

needs_sharded_mesh = pytest.mark.skipif(
    KV_SHARDS < 2 or NDEV < KV_SHARDS * MODEL_PAR,
    reason=f"needs a sharded pages axis: REPRO_KV_SHARDS={KV_SHARDS} with "
           f"{NDEV} devices (CI mesh matrix provides both)")


@pytest.fixture
def mesh():
    return make_sim_mesh(data=KV_SHARDS, model=MODEL_PAR)


@pytest.fixture(autouse=True)
def _clear_ctx():
    yield
    ops.set_mesh_ctx(None)


# (layers in the pool, layer attended): one layer alone, and a nonzero
# layer of three
LAYERS = [(1, 0), (3, 2)]


def _sharded_pool(mesh, arr, pages_dim):
    from jax.sharding import NamedSharding, PartitionSpec as P
    spec = [None] * arr.ndim
    spec[pages_dim] = "data"
    return jax.device_put(arr, NamedSharding(mesh, P(*spec)))


# ----------------------------------------------------- unsharded == no mesh --
def test_unsharded_mesh_is_identical_code_path():
    """A mesh whose pages axes have extent 1 yields NO shard ctx — ops
    dispatch, engine ctx and outputs are identical to running meshless."""
    assert ops.make_mesh_ctx(None) is None
    assert ops.make_mesh_ctx(make_host_mesh()) is None
    assert ops.make_mesh_ctx(make_sim_mesh(data=1, model=1)) is None
    if NDEV >= 2:
        assert ops.make_mesh_ctx(make_sim_mesh(data=1, model=2)) is None

    cfg = get_config("qwen3-4b-reduced")
    prompts = [np.random.default_rng(0).integers(0, cfg.vocab_size, 40,
                                                 dtype=np.int32)]
    ecfg = EngineConfig(num_lanes=2, max_len=128,
                        prefill_buckets=(16, 32, 64))
    coopt = MODES["coopt"].replace(use_kernel=True)
    out_nomesh = Engine(cfg, coopt, ecfg).generate(prompts, max_new_tokens=4)
    eng = Engine(cfg, coopt, ecfg, mesh=make_host_mesh())
    assert eng._kernel_ctx is None
    assert eng.ecfg.num_shards == 1
    assert eng.generate(prompts, max_new_tokens=4) == out_nomesh


def test_configure_for_backend_composes_with_mesh_dispatch(monkeypatch):
    """The platform's interpret rule (``ops.interpret_mode``) and the mesh
    ctx dispatch compose: what the backend implies is forwarded into the
    shard_map layer, and with no ctx the single-device wrapper runs instead
    — same rule, one dispatch point."""
    import jax as _jax
    from repro.kernels import sharded as _sh

    seen = {}
    monkeypatch.setattr(
        ops._sh, "paged_pool_decode",
        lambda ctx, *a, **kw: seen.update(ctx=ctx, **kw) or "sharded")
    monkeypatch.setattr(
        ops, "_paged_pool_decode_single", lambda *a, **kw: "single")

    monkeypatch.setattr(_jax, "default_backend", lambda: "tpu")
    assert ops.interpret_mode() is False
    ctx = _sh.ShardCtx(mesh=None, axes=("data",), num_shards=2)  # dummy
    ops.set_mesh_ctx(ctx)
    args = (jnp.zeros((1, 2, 4)), jnp.zeros((1, 2, 4, 2, 2, 4)), None, 0,
            jnp.zeros(1, jnp.int32), jnp.zeros((1, 2), jnp.int32),
            jnp.zeros((1, 2), jnp.int32))
    assert ops.paged_pool_decode(*args, opt_kv=False, opt_gqa=True) \
        == "sharded"
    assert seen["ctx"] is ctx and seen["interpret"] is False

    monkeypatch.setattr(_jax, "default_backend", lambda: "cpu")
    assert ops.interpret_mode() is True
    ops.set_mesh_ctx(None)
    assert ops.paged_pool_decode(*args, opt_kv=False, opt_gqa=True) \
        == "single"


# ------------------------------------------------- num_shards <-> mesh fix --
def test_engine_derives_num_shards_from_mesh_and_rejects_conflict():
    """Bugfix: a config built before the mesh can disagree with
    kv_shard_count — the engine derives the default and hard-rejects an
    inconsistent explicit value."""
    cfg = get_config("qwen3-4b-reduced")
    mesh1 = make_sim_mesh(data=1, model=1)
    assert kv_shard_count(mesh1) == 1
    eng = Engine(cfg, MODES["coopt"],
                 EngineConfig(num_lanes=2, max_len=128,
                              prefill_buckets=(16, 32, 64)), mesh=mesh1)
    assert eng.ecfg.num_shards == 1
    with pytest.raises(ValueError, match="disagrees"):
        Engine(cfg, MODES["coopt"],
               EngineConfig(num_lanes=2, max_len=128,
                            prefill_buckets=(16, 32, 64), num_shards=3),
               mesh=mesh1)


@needs_sharded_mesh
def test_engine_derives_num_shards_from_sharded_mesh(mesh):
    cfg = get_config("qwen3-4b-reduced")
    ecfg = EngineConfig(num_lanes=2, max_len=128,
                        prefill_buckets=(16, 32, 64))
    eng = Engine(cfg, MODES["coopt"], ecfg, mesh=mesh)
    assert eng.ecfg.num_shards == kv_shard_count(mesh) == KV_SHARDS
    # explicit matching value is accepted unchanged
    eng2 = Engine(cfg, MODES["coopt"],
                  EngineConfig(**{**ecfg.__dict__,
                                  "num_shards": KV_SHARDS}), mesh=mesh)
    assert eng2.ecfg.num_shards == KV_SHARDS


# ------------------------------------------------------ kernel-level parity --
@needs_sharded_mesh
@pytest.mark.parametrize("L,layer", LAYERS)
@pytest.mark.parametrize("opt_kv_on", [False, True])
def test_sharded_decode_kernel_matches_jnp_reference(mesh, opt_kv_on, L,
                                                     layer):
    """The shard_map'd decode kernel (global table -> local holes, partial
    (m, l) lse-merged across the pages axis) matches the jnp gather
    reference on a pool whose pages are scattered across shards."""
    B, Hq, Hkv, D, ps, P_total = 2, 8, 4, 128, 8, 16
    coopt = COOPT.replace(opt_kv=opt_kv_on, use_kernel=False)
    kv = (jax.random.normal(jax.random.PRNGKey(1),
                            (L, 2, P_total, Hkv, ps, D), jnp.float32) * 0.3)
    scale = None
    if opt_kv_on:
        from repro.cache.quant import quantize_fp8
        kv, scale = quantize_fp8(kv, axis=-1)
    q = jax.random.normal(jax.random.PRNGKey(0), (B, Hq, D), jnp.float32)
    cache_len = jnp.array([37, 90], jnp.int32)
    pt = opt_kv.identity_page_table(B, P_total)
    ref = opt_pa.paged_decode_attention(q, kv, scale, layer, cache_len,
                                        coopt=coopt, page_table=pt)

    phys, log = opt_kv.decode_page_select(cache_len, pt, ps, opt_pa=True)
    ops.set_mesh_ctx(ops.make_mesh_ctx(mesh))

    def decode(kv, scale, layer):
        return ops.paged_pool_decode(
            q, _sharded_pool(mesh, kv, 2),
            _sharded_pool(mesh, scale, 2) if scale is not None else None,
            layer, cache_len, phys, log, opt_kv=opt_kv_on, opt_gqa=True)

    out = decode(kv, scale, layer)
    tol = 0.05 if opt_kv_on else 5e-3
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol)
    if layer:
        alone = decode(kv[layer:layer + 1],
                       None if scale is None else scale[layer:layer + 1], 0)
        np.testing.assert_array_equal(np.asarray(out, np.float32),
                                      np.asarray(alone, np.float32))


@needs_sharded_mesh
def test_sharded_visit_grid_shard_local_and_matches_reference(mesh):
    """``share_visits`` under shard_map: every shard plans its visit list
    AFTER the global->local page remap, so visits reference only
    shard-local page ids and shared prefix pages dedup inside the one
    shard that owns them (pages in other shards become -1 holes there).
    The table here shares prefix pages living in DIFFERENT shards and
    must match both the jnp reference and the per-lane sharded grid
    bit-for-bit."""
    B, Hq, Hkv, D, ps, P_total, NP = 4, 8, 4, 128, 8, 16, 4
    from repro.cache.quant import quantize_fp8
    coopt = COOPT.replace(opt_kv=True, use_kernel=False)
    kv = (jax.random.normal(jax.random.PRNGKey(1),
                            (1, 2, P_total, Hkv, ps, D), jnp.float32) * 0.3)
    kv, scale = quantize_fp8(kv, axis=-1)
    q = jax.random.normal(jax.random.PRNGKey(0), (B, Hq, D), jnp.float32)
    # prefix pages 0 and 9 shared by ALL lanes (they land in different
    # shards under the page-range partition); two private tail pages each
    pt = jnp.asarray([[0, 9, 2 + b, 12 + b] for b in range(B)], jnp.int32)
    cache_len = jnp.asarray([NP * ps - 3 * b for b in range(B)], jnp.int32)
    ref = opt_pa.paged_decode_attention(q, kv, scale, 0, cache_len,
                                        coopt=coopt, page_table=pt)

    phys, log = opt_kv.decode_page_select(cache_len, pt, ps, opt_pa=True)
    kv_sh = _sharded_pool(mesh, kv, 2)
    sc_sh = _sharded_pool(mesh, scale, 2)
    ops.set_mesh_ctx(ops.make_mesh_ctx(mesh))
    on = ops.paged_pool_decode(q, kv_sh, sc_sh, 0, cache_len, phys, log,
                               opt_kv=True, opt_gqa=True, share_visits=True)
    off = ops.paged_pool_decode(q, kv_sh, sc_sh, 0, cache_len, phys, log,
                                opt_kv=True, opt_gqa=True,
                                share_visits=False)
    # near-exact vs the per-lane grid: the visit grid batches all lanes'
    # rows into one (B*G, ps) score dot where the per-lane grid runs
    # (G, ps) dots, and the backend's matmul blocking may round a ULP
    # apart at different M — tolerance covers exactly that, nothing more
    np.testing.assert_allclose(np.asarray(on, np.float32),
                               np.asarray(off, np.float32), atol=1e-6)
    np.testing.assert_allclose(np.asarray(on, np.float32),
                               np.asarray(ref, np.float32), atol=0.05)


@needs_sharded_mesh
@pytest.mark.parametrize("L,layer", LAYERS)
def test_sharded_chunk_kernel_matches_jnp_reference(mesh, L, layer):
    B, S, Hq, Hkv, D, ps, P_total = 2, 4, 8, 4, 128, 8, 16
    coopt = COOPT.replace(opt_kv=False, use_kernel=False)
    kv = (jax.random.normal(jax.random.PRNGKey(1),
                            (L, 2, P_total, Hkv, ps, D), jnp.float32) * 0.3)
    q = jax.random.normal(jax.random.PRNGKey(3), (B, S, Hq, D), jnp.float32)
    positions = jnp.stack([jnp.arange(33, 37),
                           jnp.arange(86, 90)]).astype(jnp.int32)
    pt = opt_kv.identity_page_table(B, P_total)
    ref = opt_pa.paged_chunk_attention(q, kv, None, layer, positions, pt,
                                       coopt)

    ops.set_mesh_ctx(ops.make_mesh_ctx(mesh))
    out = ops.paged_chunk_prefill(q, positions, _sharded_pool(mesh, kv, 2),
                                  None, layer, pt, opt_kv=False,
                                  opt_gqa=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=5e-3)
    if layer:
        alone = ops.paged_chunk_prefill(
            q, positions, _sharded_pool(mesh, kv[layer:layer + 1], 2), None,
            0, pt, opt_kv=False, opt_gqa=True)
        np.testing.assert_array_equal(np.asarray(out, np.float32),
                                      np.asarray(alone, np.float32))


@needs_sharded_mesh
@pytest.mark.parametrize("L,layer", LAYERS)
def test_sharded_write_stays_shard_local_and_drops_foreign_slots(mesh, L,
                                                                 layer):
    """The shard-local write scatters exactly the intended lines: no
    sentinel-line aliasing on mid-pool shards (a foreign/-1 slot is OOB-
    dropped, never wrapped), matching the global jnp write bit-for-bit, and
    every other layer keeps every byte."""
    B, Hkv, D, ps, P_total = 2, 4, 16, 8, 16
    kv = (jax.random.normal(jax.random.PRNGKey(1),
                            (L, 2, P_total, Hkv, ps, D), jnp.float32))
    k_new = jnp.full((B, 1, Hkv, D), 7.0)
    v_new = jnp.full((B, 1, Hkv, D), 9.0)
    # one mid-pool slot + one SkipSet (-1) token
    slots = jnp.array([[37], [-1]], jnp.int32)
    lines = opt_kv.pool_lines(slots, layer, P_total, ps)
    ref, _ = opt_kv.write_kv(kv, None, k_new, v_new, lines,
                             COOPT.replace(opt_kv=False, use_kernel=False))
    ops.set_mesh_ctx(ops.make_mesh_ctx(mesh))
    out, _ = ops.kv_cache_write(_sharded_pool(mesh, kv, 2), None,
                                k_new, v_new, lines, opt_kv=False)
    # both writes DROP the -1 token: the pools match bit-for-bit, and no
    # line — the last one included — absorbed the skip
    def lines_of(pool):     # (L, 2, P, Hkv, ps, D) -> (L, 2, P*ps, Hkv, D)
        return np.asarray(pool).swapaxes(3, 4).reshape(L, 2, P_total * ps,
                                                       Hkv, D)
    o, r = lines_of(out), lines_of(ref)
    np.testing.assert_array_equal(o, r)
    keep = np.ones((L, P_total * ps), bool)
    keep[layer, 37] = False
    np.testing.assert_array_equal(o.swapaxes(1, 2)[keep],
                                  lines_of(kv).swapaxes(1, 2)[keep])


# ---------------------------------------------------- engine greedy parity --
@needs_sharded_mesh
@pytest.mark.parametrize("arch", ["qwen3-4b", "deepseek-v2-lite-16b"])
def test_engine_kernel_greedy_identical_on_mesh(mesh, arch):
    """Acceptance: with ``use_kernel`` on under the sharded mesh, engine
    greedy decoding (multi-chunk prefill + decode, shard-affine placement,
    pages-sharded device pool) is identical to the meshless jnp reference
    for the dense AND mla families."""
    cfg = get_config(arch + "-reduced")
    rng = np.random.default_rng(21)
    prompts = [rng.integers(0, cfg.vocab_size, n, dtype=np.int32)
               for n in (100, 45)]
    ecfg = EngineConfig(num_lanes=2, max_len=256,
                        prefill_buckets=(16, 32, 64, 128))

    ref = Engine(cfg, MODES["coopt"], ecfg)
    out_ref = ref.generate(prompts, max_new_tokens=6)

    eng = Engine(cfg, MODES["coopt"].replace(use_kernel=True), ecfg,
                 mesh=mesh)
    assert eng._kernel_ctx is not None
    assert eng.ecfg.num_shards == KV_SHARDS
    out_mesh = eng.generate(prompts, max_new_tokens=6)
    assert out_ref == out_mesh
    assert all(len(o) == 6 for o in out_mesh)


# --------------------------------------------------------- HLO: no gather --
@needs_sharded_mesh
@pytest.mark.parametrize("arch", ["qwen3-4b", "deepseek-v2-lite-16b"])
def test_sharded_step_hlo_has_no_pool_all_gather(mesh, arch):
    """Acceptance: the compiled HLO of the engine's sharded kernel decode
    step contains no all-gather of the KV/latent pool — every all-gather
    moves strictly less than one shard's pool bytes (the lse merge moves
    only (B, H)-sized partials). Asserted via the HLO text walk of
    ``launch.hlo_cost``."""
    from repro.launch.hlo_cost import HloCostModel

    cfg = get_config(arch + "-reduced")
    eng = Engine(cfg, MODES["coopt"].replace(use_kernel=True),
                 EngineConfig(num_lanes=2, max_len=256,
                              prefill_buckets=(16, 32, 64, 128)),
                 mesh=mesh)
    B = eng.ecfg.num_lanes
    NP = eng.scheduler.pages_per_lane
    batch = {"token": jnp.zeros((B, 1), jnp.int32),
             "positions": jnp.full((B, 1), 5, jnp.int32),
             "slot_idx": jnp.full((B, 1), 5, jnp.int32),
             "page_table": jnp.zeros((B, NP), jnp.int32),
             "cache_len": jnp.full((B,), 6, jnp.int32)}
    compiled = eng._decode_fn.lower(eng.params, batch, eng.cache,
                                    jnp.ones((B,), bool)).compile()
    model = HloCostModel(compiled.as_text())

    pool_bytes = sum(eng.cache[k].nbytes for k in ("kv", "scale")
                     if k in eng.cache)
    shard_bytes = pool_bytes // KV_SHARDS
    offenders = [d for b, d in model.collective_ops
                 if "all-gather" in d and b >= shard_bytes]
    assert not offenders, \
        f"pool-sized all-gather in sharded step HLO: {offenders[:3]}"
