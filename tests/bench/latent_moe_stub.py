"""A stand-in block for the harness's tests: a decoder with latent
attention and routed plus shared experts (the DeepSeek-V2 layout), as the
program names its sizes and leaves. It has no reference: its gaps read 0
and keep what they were handed, and its work counts are a stand-in that
keeps its calls. ``tests/bench/test_second_block.py`` adds it to a copy of
the benchmark's tree as ``blocks/latent_moe_stub.py``.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import NamedTuple

import jax.numpy as jnp

PROGRAM_KEYS = {
    "hidden_size": "d_model",
    "intermediate_size": "d_ff",
    "moe_intermediate_size": "moe_d_ff",
    "num_hidden_layers": "num_layers",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "n_routed_experts": "num_experts",
    "n_shared_experts": "num_shared_experts",
    "num_experts_per_tok": "top_k",
    "first_k_dense_replace": "first_dense_layers",
    "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim",
    "v_head_dim": "v_head_dim",
    "vocab_size": "vocab_size",
    "rms_norm_eps": "norm_eps",
    "rope_theta": "rope_theta",
}
REQUIRED = tuple(PROGRAM_KEYS)
NORMS = ("ln1", "ln2", "final_norm", "kv_norm")
BIASES = ()

SEEN = []          # per call of gaps: {path: shape} of the weights
COUNTED = []       # per call of a work count: its name


class Arch(NamedTuple):
    layers: int

    @classmethod
    def from_config(cls, cfg: dict) -> "Arch":
        return cls(cfg["num_hidden_layers"])


def gaps(W, tokens, served, lo, hi, *, arch: Arch, control: bool = False):
    SEEN.append({k: tuple(x.shape) for k, x in W.items()})
    zero = jnp.zeros(tokens.shape[0], jnp.float32)
    return zero, zero


@dataclass(frozen=True)
class Dims:
    layers: int
    d_model: int
    vocab: int

    @classmethod
    def from_config(cls, cfg: dict) -> "Dims":
        return cls(cfg["num_hidden_layers"], cfg["hidden_size"],
                   cfg["vocab_size"])


def head_flops(d: Dims) -> int:
    COUNTED.append("head_flops")
    return 2 * d.d_model * d.vocab


def prompt_flops(d: Dims, start: int, n: int) -> int:
    COUNTED.append("prompt_flops")
    return 2 * n * d.layers * d.d_model * d.d_model


def decode_flops(d: Dims, ctx: int) -> int:
    COUNTED.append("decode_flops")
    return 2 * d.layers * d.d_model * d.d_model + head_flops(d)
