"""Per-architecture smoke tests (deliverable f): every assigned arch as a
REDUCED variant of the same family — forward + one train step + prefill +
decode on CPU, asserting shapes and no NaNs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ALL_IDS, get_config
from repro.configs.shapes import InputShape
from repro.core.coopt import COOPT
from repro.models import get_model
from repro.training import Trainer


def _batch(m, cfg, B, S, key):
    sh = InputShape("t", S, B, "train")
    out = {}
    for k, v in m.input_specs(sh).items():
        if v.dtype == jnp.int32:
            out[k] = jax.random.randint(key, v.shape, 0, cfg.vocab_size)
        else:
            out[k] = jax.random.normal(key, v.shape).astype(v.dtype)
    return out


@pytest.mark.parametrize("arch", ALL_IDS)
def test_forward_shapes_and_finite(arch):
    cfg = get_config(arch + "-reduced")
    assert cfg.num_layers <= 3 and cfg.d_model <= 512
    if cfg.num_experts:
        assert cfg.num_experts <= 4
    m = get_model(cfg)
    p = m.init(jax.random.PRNGKey(0))
    B, S = 2, 64
    batch = _batch(m, cfg, B, S, jax.random.PRNGKey(1))
    logits, _aux = m.forward(p, batch, COOPT)
    S_text = S - (cfg.num_patches if cfg.family == "vlm" else 0)
    assert logits.shape == (B, S_text, cfg.vocab_size)
    assert not np.isnan(np.asarray(logits, np.float32)).any()


@pytest.mark.parametrize("arch", ALL_IDS)
def test_one_train_step(arch):
    cfg = get_config(arch + "-reduced")
    m = get_model(cfg)
    tr = Trainer(cfg, lr=1e-3)
    B, S = 2, 32
    batch = _batch(m, cfg, B, S, jax.random.PRNGKey(2))
    S_text = S - (cfg.num_patches if cfg.family == "vlm" else 0)
    batch["labels"] = jax.random.randint(jax.random.PRNGKey(3),
                                         (B, S_text), 0, cfg.vocab_size)
    metrics = tr.step(batch)
    assert np.isfinite(metrics["loss"])
    assert metrics["loss"] > 0


@pytest.mark.parametrize("arch", ALL_IDS)
def test_prefill_decode_roundtrip(arch):
    cfg = get_config(arch + "-reduced")
    m = get_model(cfg)
    p = m.init(jax.random.PRNGKey(0))
    B, S = 2, 32
    batch = _batch(m, cfg, B, S, jax.random.PRNGKey(1))
    batch.pop("labels", None)
    cache = m.init_cache(B, S + 4, COOPT)
    logits, cache, _ = m.prefill(p, batch, cache, COOPT)
    assert logits.shape == (B, cfg.vocab_size)
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    logits2, cache, _ = m.decode_step(p, {"token": tok}, cache, COOPT)
    assert logits2.shape == (B, cfg.vocab_size)
    assert not np.isnan(np.asarray(logits2, np.float32)).any()
    # input_specs already folds the vlm patch prefix into S
    np.testing.assert_array_equal(np.asarray(cache["length"]), S + 1)


@pytest.mark.parametrize("arch", ALL_IDS)
def test_decode_consistency_with_forward(arch):
    """Greedy continuation via prefill+decode must match teacher forcing:
    decode logits at position t == forward logits at t (same tokens)."""
    cfg = get_config(arch + "-reduced")
    m = get_model(cfg)
    p = m.init(jax.random.PRNGKey(0))
    B, S = 1, 24
    key = jax.random.PRNGKey(5)
    batch = _batch(m, cfg, B, S + 1, key)
    batch.pop("labels", None)
    full_tokens = batch["tokens"]

    coopt = COOPT

    fwd_logits, _ = m.forward(p, dict(batch), coopt)

    pre = dict(batch)
    pre["tokens"] = full_tokens[:, :-1]
    S_text = pre["tokens"].shape[1]
    cache = m.init_cache(B, S + 8, coopt)
    pl_logits, cache, _ = m.prefill(p, pre, cache, coopt)
    # prefill last-token logits == forward logits at position S_text-1
    a = np.asarray(fwd_logits[:, S_text - 1], np.float32)
    b = np.asarray(pl_logits, np.float32)
    atol = 0.15 * max(np.abs(a).max(), 1.0)   # fp8 cache + bf16 skew
    np.testing.assert_allclose(a, b, atol=atol)

    # decode of the held-out token == forward logits at position S_text
    tok = full_tokens[:, -1:].astype(jnp.int32)
    de_logits, _, _ = m.decode_step(p, {"token": tok}, cache, coopt)
    a2 = np.asarray(fwd_logits[:, S_text], np.float32)
    b2 = np.asarray(de_logits, np.float32)
    np.testing.assert_allclose(a2, b2, atol=atol)
