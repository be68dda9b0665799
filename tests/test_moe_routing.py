"""Dropless expert routing and YaRN rope, as DeepSeek-V2-Lite publishes
them.

The MoE layer routes every real token over all experts and drops none at
any chunk length: it must equal a per-token evaluation of each token's top
k experts, with the top-k weights renormalised only where the config says
so, give padded slots no (token, expert) pair, and count its routing as
defined. YaRN's frequencies and the latent attention's score scale must
come out as the published equations give at the published sizes.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.layers import yarn_get_mscale, yarn_inv_freq
from repro.models.mla import mla_softmax_scale
from repro.models.moe import _moe_route, buffer_rows, moe_ffn, tile_rows

D, E, F, K = 64, 8, 32, 3


def _weights(seed, skew=0.0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    wr = jax.random.normal(ks[0], (D, E)) / 8
    # skew > 0 sends most tokens to expert 0: past any per-expert capacity
    wr = wr.at[:, 0].add(skew)
    bf = lambda a: a.astype(jnp.bfloat16)                    # noqa: E731
    return (bf(wr), bf(jax.random.normal(ks[1], (E, D, F)) / 8),
            bf(jax.random.normal(ks[2], (E, D, F)) / 8),
            bf(jax.random.normal(ks[3], (E, F, D)) / 6))


def _per_token(x, wr, wg, wu, wd, norm, valid):
    """Each real token through its own top-K experts, one at a time."""
    x = np.asarray(x.reshape(-1, D))
    logits = jnp.dot(x, wr, preferred_element_type=jnp.float32)
    top_p, top_e = jax.lax.top_k(jax.nn.softmax(logits, -1), K)
    if norm:
        top_p = top_p / top_p.sum(-1, keepdims=True)
    out = np.zeros((x.shape[0], D), np.float32)
    for t in np.flatnonzero(valid.reshape(-1)):
        for j in range(K):
            e = int(top_e[t, j])
            g = jnp.dot(x[t], wg[e], preferred_element_type=jnp.float32)
            u = jnp.dot(x[t], wu[e], preferred_element_type=jnp.float32)
            h = (jax.nn.silu(g) * u).astype(jnp.bfloat16)
            y = jnp.dot(h, wd[e], preferred_element_type=jnp.float32)
            out[t] += float(top_p[t, j]) * np.asarray(
                y.astype(jnp.bfloat16), np.float32)
    return out, np.asarray(top_e)


@pytest.mark.parametrize("kernel", [False, True], ids=["ragged_dot", "kernel"])
@pytest.mark.parametrize("norm", [False, True], ids=["as_is", "renorm"])
@pytest.mark.parametrize("shape", [(2, 48), (8, 1)], ids=["chunk", "decode"])
def test_dropless_moe_equals_per_token_experts(shape, norm, kernel):
    """A prefill chunk whose tokens crowd one expert far past the old
    capacity ceil(S*k/E*1.25) = 18, and a decode step of one token per row:
    every token gets its whole top-K mixture, pads get nothing, and the
    counters read the most pairs of one expert and the experts hit."""
    wr, wg, wu, wd = _weights(1, skew=0.6 if shape[1] > 1 else 0.0)
    x = jax.random.normal(jax.random.PRNGKey(2), shape + (D,)
                          ).astype(jnp.bfloat16)
    valid = np.ones(shape, bool)
    valid[0, -3:] = False                     # padded slots of one row
    want, top_e = _per_token(x, wr, wg, wu, wd, norm, valid)
    got, aux = moe_ffn(x, wr, wg, wu, wd, top_k=K, norm_topk_prob=norm,
                       valid=jnp.asarray(valid), kernel=kernel)
    got = np.asarray(got, np.float32).reshape(-1, D)
    # the per-token sum rounds each expert's output to bf16 as the grouped
    # path does; what is left is the final bf16 rounding of the mixture
    np.testing.assert_allclose(got, want, atol=2e-2 * np.abs(want).max())
    assert np.all(got[~valid.reshape(-1)] == 0)
    counts = np.bincount(top_e[valid.reshape(-1)].reshape(-1), minlength=E)
    assert int(aux.expert_tokens_max) == counts.max()
    assert int(aux.experts_hit) == np.count_nonzero(counts)
    if shape[1] > 1:
        assert counts.max() > math.ceil(shape[1] * K / E * 1.25)


def test_route_gives_pads_no_pair_and_tiles_one_expert_each():
    wr, _, _, _ = _weights(3)
    T = 40
    x = jax.random.normal(jax.random.PRNGKey(4), (T, D)).astype(jnp.bfloat16)
    valid = np.arange(T) % 5 != 0
    tm = tile_rows(T * K)
    plan, _ = _moe_route(x, wr, jnp.asarray(valid), top_k=K,
                         norm_topk_prob=False, tm=tm)
    M = buffer_rows(T * K, E, tm)
    dest = np.asarray(plan.dest)
    assert plan.src.shape == (M,)
    assert np.all(dest[~valid] == M)          # pads: no row in the buffer
    real = dest[valid].reshape(-1)
    assert len(set(real.tolist())) == real.size and real.max() < M
    src = np.asarray(plan.src)
    assert np.all(src[real] == np.repeat(np.flatnonzero(valid), K))
    # each expert's rows start on a tile, and every real tile is its own
    sizes = np.asarray(plan.group_sizes)
    starts = np.cumsum(sizes) - sizes
    assert np.all(sizes % tm == 0)
    tiles = np.asarray(plan.tile_expert)[:int(plan.num_tiles[0])]
    assert np.array_equal(tiles, np.repeat(np.arange(E), sizes // tm))
    for e in range(E):
        rows = real[np.asarray(jax.lax.top_k(
            jnp.dot(x, wr, preferred_element_type=jnp.float32), K)[1]
        )[valid].reshape(-1) == e]
        assert np.all((rows >= starts[e]) & (rows < starts[e] + sizes[e]))


def test_yarn_frequencies_at_the_published_sizes():
    """64 rope dims, base 10000, factor 40 over 4096 positions, beta 32 and
    1: correction dims 10 and 23, and the ramp between them."""
    scaling = {"beta_fast": 32, "beta_slow": 1, "factor": 40,
               "mscale": 0.707, "mscale_all_dim": 0.707,
               "original_max_position_embeddings": 4096, "type": "yarn"}
    low = math.floor(64 * math.log(4096 / (32 * 2 * math.pi))
                     / (2 * math.log(10000)))
    high = math.ceil(64 * math.log(4096 / (1 * 2 * math.pi))
                     / (2 * math.log(10000)))
    assert (low, high) == (10, 23)
    j = np.arange(32)
    extra = 10000.0 ** (-2 * j / 64)
    ramp = np.clip((j - 10) / (23 - 10), 0, 1)
    want = extra / 40 * ramp + extra * (1 - ramp)
    np.testing.assert_allclose(yarn_inv_freq(64, 10000.0, scaling), want,
                               rtol=1e-6)
    assert yarn_get_mscale(40, 0.707) == pytest.approx(1.2608038, abs=1e-6)


def test_deepseek_v2_lite_is_the_published_config():
    cfg = get_config("deepseek-v2-lite-16b")
    assert (cfg.rope_theta, cfg.norm_eps, cfg.norm_topk_prob) == \
        (10000.0, 1e-6, False)
    assert dict(cfg.rope_scaling)["type"] == "yarn"
    assert mla_softmax_scale(cfg) == pytest.approx(0.1147214, abs=1e-7)
    hash(cfg)                 # a static jit argument: hashable
    # a dense config keeps plain rope at 1/sqrt(head dim)
    assert get_config("qwen3-4b").rope_scaling == ()


_MESH_SCRIPT = r'''
import json, os, re
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.mesh import make_sim_mesh
from repro.launch.steps import ACT_RULES_SEQ
from repro.models.layers import activation_sharding
from repro.models.moe import moe_ffn

B, S, d, E, ff, k = 8, 32, 256, 8, 64, 2
mesh = make_sim_mesh(data=4, model=2)
ks = jax.random.split(jax.random.PRNGKey(0), 5)
x = jax.random.normal(ks[0], (B, S, d), jnp.bfloat16)
wr = jax.random.normal(ks[1], (d, E), jnp.bfloat16)
w = [(jax.random.normal(kk, s) * 0.05).astype(jnp.bfloat16)
     for kk, s in zip(ks[2:], [(1, E, d, ff)] * 2 + [(1, E, ff, d)])]
specs = [P("data", "model", None), P(), P(None, "data", None, "model"),
         P(None, "data", None, "model"), P(None, "data", "model", None)]
args = [jax.device_put(a, NamedSharding(mesh, s))
        for a, s in zip([x, wr] + w, specs)]

def layer(x, wr, wg, wu, wd):
    return moe_ffn(x, wr, wg, wu, wd, top_k=k, layer=jnp.int32(0))

def on_mesh(*a):
    with activation_sharding(mesh, ACT_RULES_SEQ):
        return layer(*a)

f = jax.jit(on_mesh)
hlo = f.lower(*args).compile().as_text()
ops = []
for m in re.finditer(r"= (\w+)\[([\d,]*)\]\S* (all-gather|all-reduce|"
                     r"all-to-all|collective-permute|reduce-scatter)", hlo):
    ops.append([m.group(3), [int(v) for v in m.group(2).split(",") if v]])
(y1, a1), (y0, a0) = f(*args), jax.jit(layer)(x, wr, *w)
print(json.dumps({"ops": ops,
                  "err": float(jnp.max(jnp.abs(y1.astype(jnp.float32)
                                               - y0.astype(jnp.float32)))),
                  "aux": [[float(v) for v in a1], [float(v) for v in a0]]}))
'''


def test_moe_on_simulated_mesh_sorts_each_batch_shard_alone():
    """On a (data 4, model 2) mesh of simulated devices under the training
    activation rules, the MoE layer sorts and runs each batch shard's pairs
    on its own shard: the only collectives are the experts' weights
    gathered over ``data`` (ff stays on ``model``), the shard's sequence
    gathered over ``model``, the float32 partial down-projections of its
    own tokens summed over ``model``, and the routing sums -- no collective
    carries the whole batch's tokens or sorted buffer. Its output equals the meshless layer's
    up to bf16 rounding, and its aux losses and counters are those of the
    whole batch."""
    import json
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=os.path.join(root, "src"),
               JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, "-c", _MESH_SCRIPT], cwd=root,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    B, S, d, E, ff = 8, 32, 256, 8, 64
    shard_tokens = B // 4 * S
    kinds = {op for op, _ in out["ops"]}
    assert kinds <= {"all-gather", "all-reduce"}, out["ops"]
    for op, shape in out["ops"]:
        if op == "all-gather" and len(shape) == 4:
            assert shape[:2] == [1, E]                 # one layer's experts
            assert sorted(shape[2:]) == [ff // 2, d]   # ff on "model"
        else:
            assert math.prod(shape) <= shard_tokens * d, (op, shape)
    assert out["err"] <= 2 ** -7                       # bf16 at |y| ~ 1
    np.testing.assert_allclose(*out["aux"], rtol=1e-5)
