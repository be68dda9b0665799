"""Model / input-shape configuration for the LLM-CoOpt reproduction.

Every assigned architecture is expressed as a frozen ``ModelConfig``.  The
``family`` field selects the model implementation in ``repro.models.registry``:

  dense    – llama-style decoder (yi-34b, qwen*, deepseek-67b, internvl2 LM)
  moe      – dense attention + mixture-of-experts FFN (mixtral)
  mla      – multi-head latent attention + MoE (deepseek-v2-lite)
  rwkv6    – attention-free RWKV-6 "Finch" (data-dependent decay)
  griffin  – RG-LRU + local-attention hybrid (recurrentgemma)
  whisper  – encoder-decoder with stub conv/mel frontend
  vlm      – dense LM consuming stub ViT patch embeddings (internvl2)
"""
from __future__ import annotations

import dataclasses
from collections.abc import Mapping
from dataclasses import dataclass
from typing import Any, Tuple


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    source: str = ""                 # citation (arXiv / model card)

    # -- attention details ----------------------------------------------
    qk_norm: bool = False            # qwen3-style per-head RMSNorm on q,k
    qkv_bias: bool = False           # qwen2.5-style bias on qkv projections
    attn_window: int = 0             # 0 = full causal; >0 sliding window
    sink_blocks: int = 1             # Opt-KV SkipSet: KV pages always kept
    rope_theta: float = 10000.0
    # published ``rope_scaling`` as sorted (key, value) pairs (a dict given
    # here is converted: the config is a static, hashable jit argument);
    # () = plain rope. Latent attention reads YaRN from it (models/mla.py)
    rope_scaling: Tuple[Tuple[str, Any], ...] = ()

    # -- MoE --------------------------------------------------------------
    num_experts: int = 0             # routed experts
    num_shared_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0                # per-expert hidden dim
    first_dense_layers: int = 0      # leading layers with dense FFN
    norm_topk_prob: bool = True      # divide the top-k router weights by
                                     # their sum (False: use them as they are)

    # -- MLA (deepseek-v2) -------------------------------------------------
    kv_lora_rank: int = 0
    q_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0

    # -- hybrid (griffin / recurrentgemma) ----------------------------------
    block_pattern: Tuple[str, ...] = ()   # e.g. ("rec", "rec", "attn")
    local_window: int = 0
    lru_width: int = 0
    conv1d_width: int = 4

    # -- encoder-decoder (whisper) ------------------------------------------
    encoder_layers: int = 0
    num_frames: int = 0              # stub frontend: encoder sequence length

    # -- vlm ------------------------------------------------------------------
    num_patches: int = 0             # stub ViT: patch embeddings per image

    # -- misc -----------------------------------------------------------------
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    dtype: str = "bfloat16"

    def __post_init__(self):
        if isinstance(self.rope_scaling, Mapping):
            object.__setattr__(self, "rope_scaling",
                               tuple(sorted(self.rope_scaling.items())))

    # ------------------------------------------------------------------
    @property
    def is_attention_free(self) -> bool:
        return self.family == "rwkv6"

    @property
    def q_per_kv(self) -> int:
        """Opt-GQA Eq. 7: H_g = H_q / H_k (query heads per group)."""
        return self.num_heads // max(self.num_kv_heads, 1)

    @property
    def has_subquadratic_path(self) -> bool:
        """Eligible for long_500k (see DESIGN.md §5)."""
        if self.family in ("rwkv6", "griffin"):
            return True
        return self.attn_window > 0

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    def param_count(self) -> int:
        """Analytic parameter count (total, incl. all experts)."""
        from repro.models.registry import get_model
        return get_model(self).param_count()

    def active_param_count(self) -> int:
        from repro.models.registry import get_model
        return get_model(self).active_param_count()


@dataclass(frozen=True)
class CacheConfig:
    """Paged-KV pool geometry and hierarchical-cache policy in ONE place.

    Consolidates the knobs that previously crawled through ``BlockManager``,
    ``Scheduler``, ``EngineConfig`` and the model ``cache_shape`` signatures
    as loose positionals (``num_pages`` / ``page_size`` / ``num_shards`` /
    ``enable_prefix_cache``), plus the host-DRAM capacity tier added with
    the hierarchical cache.

    ``num_pages`` is the REQUESTED device pool size in pages, before shard
    padding; the pool actually allocated is
    ``padded_pool_pages(num_pages, num_shards)`` with the last page reserved
    as the SkipSet write sentinel, exactly as when the pool is derived from
    ``num_lanes * pages(max_len)`` (the ``num_pages == 0`` default).
    ``page_size == 0`` inherits ``CoOptConfig.page_size``; ``BlockManager``
    itself requires a resolved (> 0) value.

    ``host_pages > 0`` turns on the host-DRAM spill tier: LRU-evicted
    registered prefix pages are spilled host-side instead of destroyed and
    asynchronously prefetched back (see ``cache/block_manager.py`` module
    docstring for the residency state machine). ``prefetch_depth`` bounds
    how many queued requests the scheduler scans for prefetchable prefixes
    per turn. ``host_quant`` additionally fp8-encodes bf16 pool pages on
    spill (halves host bytes; breaks tier-on/off bit-identity, so it
    defaults off — with ``opt_kv`` pools the pages are already fp8 and the
    spill is byte-lossless either way).
    """
    num_pages: int = 0           # 0 = derive from num_lanes * pages(max_len)
    page_size: int = 0           # 0 = inherit CoOptConfig.page_size
    num_shards: int = 1
    enable_prefix_cache: bool = True
    host_pages: int = 0          # host-DRAM tier capacity in pages; 0 = off
    prefetch_depth: int = 2
    host_quant: bool = False

    def __post_init__(self):
        if self.num_pages < 0 or self.page_size < 0 or self.host_pages < 0:
            raise ValueError("CacheConfig sizes must be >= 0")
        if self.num_shards < 1:
            raise ValueError("CacheConfig.num_shards must be >= 1")

    def replace(self, **kw) -> "CacheConfig":
        return dataclasses.replace(self, **kw)

    def resolve(self, *, page_size: int, num_pages: int) -> "CacheConfig":
        """Fill the inherit-defaults (0) fields from the engine context."""
        return self.replace(page_size=self.page_size or page_size,
                            num_pages=self.num_pages or num_pages)


def reduced(cfg: ModelConfig) -> ModelConfig:
    """Smoke-test variant of the same family: 2 layers, d_model<=512, <=4 experts."""
    kw = dict(
        name=cfg.name + "-reduced",
        num_layers=2,
        d_model=256,
        num_heads=4,
        num_kv_heads=min(cfg.num_kv_heads, 2) if cfg.num_kv_heads > 1 else 1,
        head_dim=64,
        d_ff=512,
        vocab_size=512,
    )
    if cfg.num_experts:
        kw.update(num_experts=4, top_k=2, moe_d_ff=128,
                  num_shared_experts=min(cfg.num_shared_experts, 1),
                  first_dense_layers=min(cfg.first_dense_layers, 1))
    if cfg.family == "mla":
        kw.update(kv_lora_rank=64, q_lora_rank=0, qk_nope_head_dim=64,
                  qk_rope_head_dim=32, v_head_dim=64)
    if cfg.family == "griffin":
        # keep one full pattern period: (rec, rec, attn)
        kw.update(num_layers=3, lru_width=256, local_window=64)
    if cfg.family == "whisper":
        kw.update(encoder_layers=2, num_frames=32)
    if cfg.family == "vlm":
        kw.update(num_patches=16)
    if cfg.attn_window:
        kw.update(attn_window=64)
    return cfg.replace(**kw)
