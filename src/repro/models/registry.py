"""Model registry: family -> implementation class.

Every model exposes the same engine-facing protocol:
  param_specs() / init(key)                    — parameter pytree (stacked layers)
  forward(params, batch, coopt)                — teacher-forced logits (+aux)
  prefill(params, batch, cache, coopt)         — last-token logits + filled cache
  decode_step(params, batch, cache, coopt, long_window) — one-token step
      — both return (logits, cache, counters): the step's (2,) f32 expert
        counters (zeros for a model without routed experts)
  cache_shape(batch, max_len, coopt, num_shards=1) / init_cache(...)
      — num_shards pads the paged-KV pages axis so it tiles evenly over
        the mesh (pod, data) shards of the sharded pool
  input_specs(shape)                           — ShapeDtypeStructs per input
"""
from __future__ import annotations

from functools import lru_cache

from repro.configs.base import ModelConfig


@lru_cache(maxsize=64)
def _get(cfg: ModelConfig):
    if cfg.family in ("dense", "moe", "mla", "vlm"):
        from repro.models.transformer import TransformerModel
        return TransformerModel(cfg)
    if cfg.family == "rwkv6":
        from repro.models.rwkv6 import RWKV6Model
        return RWKV6Model(cfg)
    if cfg.family == "griffin":
        from repro.models.griffin import GriffinModel
        return GriffinModel(cfg)
    if cfg.family == "whisper":
        from repro.models.whisper import WhisperModel
        return WhisperModel(cfg)
    raise KeyError(f"unknown family {cfg.family!r}")


def get_model(cfg: ModelConfig):
    return _get(cfg)
