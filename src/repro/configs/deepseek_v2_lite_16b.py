"""deepseek-v2-lite-16b — MLA (kv_lora=512) + MoE 2 shared + 64 routed top-6
[arXiv:2405.04434], at the values of the published config
(https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/config.json):
softmax routing without renormalising the top 6, YaRN rope (factor 40 over
4096 positions, mscale 0.707), RMSNorm eps 1e-6.

Opt-KV applies to the *latent* cache (c_kv + k_rope are still a per-token KV
cache -> FP8 + paging). Opt-GQA degenerates: MLA already shares one latent
across all heads (extreme grouping).
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite-16b",
    family="mla",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,         # MLA: all heads read the shared latent
    head_dim=128,            # = qk_nope_head_dim
    d_ff=10944,              # dense FFN (first layer)
    moe_d_ff=1408,           # per routed expert
    vocab_size=102400,
    num_experts=64,
    num_shared_experts=2,
    top_k=6,
    norm_topk_prob=False,
    first_dense_layers=1,
    kv_lora_rank=512,
    q_lora_rank=0,           # v2-lite has no q compression
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    rope_theta=10000.0,
    rope_scaling={"beta_fast": 32, "beta_slow": 1, "factor": 40,
                  "mscale": 0.707, "mscale_all_dim": 0.707,
                  "original_max_position_embeddings": 4096, "type": "yarn"},
    norm_eps=1e-6,
    source="https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/"
           "config.json",
)
