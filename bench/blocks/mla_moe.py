"""The DeepSeek-V2 decoder block (latent attention, then a dense or a
mixture-of-experts feed-forward): what the harness needs to know of its
shape. The program's keys for its sizes, the leaves the weight generator
perturbs, the plain float32 reference with its control, and the useful
work its steps and its two kernels need.

The published block (``modeling_deepseek.py`` of DeepSeek-V2-Lite, with
``q_lora_rank`` null), for a token's normed input x:

- attention: q = x W_q, split per head into q_nope (128) and q_rope (64);
  [c | k_rope] = x W_kv_a with c the 512-wide latent, normed by RMSNorm;
  per head k_nope = c W_uk and v = c W_uv; k_rope is one rotary key shared
  by all heads. Rope is YaRN (below) on q_rope and k_rope. Scores are
  (q_nope . k_nope + q_rope . k_rope) * s, causal softmax, the values
  summed, then W_o;
- YaRN, for the 64 rope dims at base ``rope_theta``: ``inv_freq_j`` blends
  ``base^(-2j/64)`` with the same divided by ``factor``, by a ramp from 0
  to 1 between the correction dims floor(64 ln(4096/(beta_fast 2 pi)) /
  (2 ln base)) and ceil(the same for beta_slow); cos and sin are scaled by
  ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)`` (1 here);
  s = 192^-0.5 * mscale(factor, mscale_all_dim)^2, with mscale(f, m) =
  0.1 m ln f + 1;
- layers before ``first_k_dense_replace``: a SiLU-gated feed-forward of
  ``intermediate_size``;
- the others: p = softmax(x W_r) over the routed experts in float32; the
  top ``num_experts_per_tok`` by p (greedy, one group); their weights are
  p as they are (``norm_topk_prob`` false) times ``routed_scaling_factor``
  (1); the output is the weighted sum of those experts' SiLU-gated
  feed-forwards of ``moe_intermediate_size``, plus the shared experts'
  one feed-forward of ``n_shared_experts`` times that width, unweighted.

Pre-norm RMSNorm and residuals around both; final RMSNorm, then the head.
The configuration states a latent pool in float8 e4m3 with two float32
scales per token (absmax / 448 of the latent and of the rotated rope key),
so the reference reads the latent and the rope key through that rounding.

Departures, each a relabelling that seeded random weights cannot tell
apart: the published rope de-interleaves the 64 rope dims before rotating
halves; here, as in the program, halves are rotated as they are, so the
rope columns of W_q and W_kv_a are the published ones permuted. W_kv_b is
held as W_uk and W_uv (the nope and value columns of each head).

The reference is float32 under ``jax.default_matmul_precision("highest")``,
with exact products against the bfloat16 weights
(``benchlib.numerics.mm``); attention runs at ``Precision.HIGHEST`` in
blocks of query rows so that 8192 positions fit; the router's top experts
come from its own float32 logits; each expert runs over every position,
weighted by its router weight where it is among the top ones and by 0
elsewhere. It imports nothing of the program; it reads weights by path
from the benchmark's own generator (``benchlib.model.weights_by_path``).

The control computes every matrix product in float8 e4m3, one step below
the configuration's bfloat16, as the dense block's control does: weights
per output channel and activations per token, accumulated in float32.

The work counts are per token, per step or per call of one layer's
kernel, as stated; padded slots never enter (``benchlib.work``).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from benchlib.numerics import (HI, fp8_rows, fp8_weight, mm, rmsnorm)

# published config key -> the program's ModelConfig field
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
    "norm_topk_prob": "norm_topk_prob",
    "first_k_dense_replace": "first_dense_layers",
    "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim",
    "v_head_dim": "v_head_dim",
    "vocab_size": "vocab_size",
    "rms_norm_eps": "norm_eps",
    "rope_theta": "rope_theta",
    "rope_scaling": "rope_scaling",
}
# the published keys whose values the reference holds the file to
ROUTING = {"scoring_func": "softmax", "topk_method": "greedy", "n_group": 1,
           "topk_group": 1, "routed_scaling_factor": 1, "moe_layer_freq": 1,
           "q_lora_rank": None, "attention_bias": False,
           "hidden_act": "silu"}
REQUIRED = tuple(PROGRAM_KEYS) + tuple(ROUTING) + ("kv_page_size",)

# leaves the generator perturbs: norm scales (no biases in this block)
NORMS = ("ln1", "ln2", "final_norm", "kv_norm")
BIASES = ()

Q_BLOCK = 256          # query rows per attention block
POS_BLOCK = 256        # positions per block of output-head logits


# ------------------------------------------------------------ reference --
class Arch(NamedTuple):
    layers: int
    dense_layers: int
    heads: int
    nope: int
    rope: int
    v: int
    rank: int
    experts: int
    top_k: int
    norm_topk: bool
    eps: float
    theta: float
    yarn: tuple            # (factor, original positions, beta_fast,
                           #  beta_slow, mscale, mscale_all_dim)

    @classmethod
    def from_config(cls, cfg: dict) -> "Arch":
        off = {k: cfg.get(k) for k, v in ROUTING.items() if cfg.get(k) != v}
        if off:
            raise ValueError(f"{cfg['name']}: the block follows "
                             f"{ROUTING}, not {off}")
        y = cfg["rope_scaling"]
        if y.get("type") != "yarn":
            raise ValueError(f"{cfg['name']}: rope scaling {y} is not YaRN")
        return cls(cfg["num_hidden_layers"], cfg["first_k_dense_replace"],
                   cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                   cfg["qk_rope_head_dim"], cfg["v_head_dim"],
                   cfg["kv_lora_rank"], cfg["n_routed_experts"],
                   cfg["num_experts_per_tok"], bool(cfg["norm_topk_prob"]),
                   float(cfg["rms_norm_eps"]), float(cfg["rope_theta"]),
                   (float(y["factor"]),
                    float(y["original_max_position_embeddings"]),
                    float(y["beta_fast"]), float(y["beta_slow"]),
                    float(y["mscale"]), float(y["mscale_all_dim"])))


def yarn_mscale(factor: float, m: float) -> float:
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(dim: int, base: float, yarn: tuple) -> np.ndarray:
    """YaRN's frequencies of the ``dim`` rope dims (module docstring)."""
    factor, orig, fast, slow = yarn[:4]

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(corr(fast)), 0)
    high = min(math.ceil(corr(slow)), dim - 1)
    extra = base ** (-np.arange(0, dim, 2) / dim)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return extra / factor * ramp + extra * (1 - ramp)


def softmax_scale(arch: Arch) -> float:
    m = yarn_mscale(arch.yarn[0], arch.yarn[5])
    return (arch.nope + arch.rope) ** -0.5 * m * m


def yarn_rope(x, pos, arch: Arch):
    """x (S, H, 64), pos (S,): rotate-half with YaRN's frequencies."""
    half = x.shape[-1] // 2
    inv = jnp.asarray(yarn_inv_freq(x.shape[-1], arch.theta, arch.yarn),
                      jnp.float32)
    m = (yarn_mscale(arch.yarn[0], arch.yarn[4])
         / yarn_mscale(arch.yarn[0], arch.yarn[5]))
    ang = pos.astype(jnp.float32)[:, None] * inv
    cos, sin = (jnp.cos(ang) * m)[:, None], (jnp.sin(ang) * m)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, scale):
    """Causal attention, every head its own K: q/k (S, H, Dk), v (S, H, Dv)
    -> (S, H, Dv)."""
    S, H, Dk = q.shape
    qb = q.reshape(S // Q_BLOCK, Q_BLOCK, H, Dk)
    kpos = jnp.arange(S)

    def block(args):
        i, qq = args
        s = jnp.einsum("qhd,khd->hqk", qq, k, precision=HI) * scale
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

    o = jax.lax.map(block, (jnp.arange(S // Q_BLOCK), qb))
    return o.reshape(S, H, v.shape[-1])


ATTN_WEIGHTS = ("wq", "w_dkv", "w_uk", "w_uv", "wo")
DENSE_WEIGHTS = ("wg", "wu", "wd")
MOE_WEIGHTS = ("wr", "wg_e", "wu_e", "wd_e", "wg_s", "wu_s", "wd_s")


def segments(W):
    """The weights by segment and leaf name: segment 0 holds the leading
    dense layers, segment 1 the expert layers, each stacked by layer."""
    segs = {}
    for k, x in W.items():
        if k.startswith("segments/"):
            _, i, name = k.split("/")
            segs.setdefault(int(i), {})[name] = x
    if sorted(segs) != [0, 1]:
        raise ValueError(f"expected a dense and an expert segment, not "
                         f"{sorted(segs)}")
    return segs[0], segs[1]


def hidden(W, tokens, arch: Arch, control: bool):
    """Final-normed hidden states (S, d) of a causal pass over tokens."""
    S = tokens.shape[0]
    pos = jnp.arange(S)
    H, dn, dr, R = arch.heads, arch.nope, arch.rope, arch.rank
    scale = softmax_scale(arch)
    h = W["embed"][tokens].astype(jnp.float32)
    mm_ = (lambda x, w: mm(fp8_rows(x), w)) if control else mm
    q8 = (lambda lw, names: dict(lw, **{n: fp8_weight(lw[n]) for n in names})
          ) if control else (lambda lw, names: lw)

    def swiglu(x, wg, wu, wd):
        return mm_(jax.nn.silu(mm_(x, wg)) * mm_(x, wu), wd)

    def attend(h, lw):
        x = rmsnorm(h, lw["ln1"], arch.eps)
        q = mm_(x, lw["wq"]).reshape(S, H, dn + dr)
        ckv = mm_(x, lw["w_dkv"])
        c = rmsnorm(ckv[:, :R], lw["kv_norm"], arch.eps)
        k_rope = yarn_rope(ckv[:, None, R:], pos, arch)[:, 0]
        # the pool's rounding: fp8 e4m3, one scale per token for each part
        c, k_rope = fp8_rows(c), fp8_rows(k_rope)
        k = jnp.concatenate(
            [mm_(c, lw["w_uk"]).reshape(S, H, dn),
             jnp.broadcast_to(k_rope[:, None], (S, H, dr))], -1)
        v = mm_(c, lw["w_uv"]).reshape(S, H, arch.v)
        q = jnp.concatenate([q[..., :dn], yarn_rope(q[..., dn:], pos, arch)],
                            -1)
        o = attention(q, k, v, scale).reshape(S, H * arch.v)
        return h + mm_(o, lw["wo"])

    def dense_layer(h, lw):
        lw = q8(lw, ATTN_WEIGHTS + DENSE_WEIGHTS)
        h = attend(h, lw)
        x = rmsnorm(h, lw["ln2"], arch.eps)
        return h + swiglu(x, lw["wg"], lw["wu"], lw["wd"]), None

    def moe_layer(h, lw):
        lw = q8(lw, ATTN_WEIGHTS + MOE_WEIGHTS)
        h = attend(h, lw)
        x = rmsnorm(h, lw["ln2"], arch.eps)
        p = jax.nn.softmax(mm_(x, lw["wr"]), axis=-1)          # (S, E)
        top_p, top_e = jax.lax.top_k(p, arch.top_k)
        if arch.norm_topk:
            top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
        # each token's weight on each expert: its p where chosen, else 0
        w = jnp.sum(jax.nn.one_hot(top_e, arch.experts) * top_p[..., None],
                    axis=1)                                     # (S, E)

        def expert(acc, ew):
            wg, wu, wd, we = ew
            return acc + we[:, None] * swiglu(x, wg, wu, wd), None

        routed, _ = jax.lax.scan(
            expert, jnp.zeros_like(h),
            (lw["wg_e"], lw["wu_e"], lw["wd_e"], w.T))
        shared = swiglu(x, lw["wg_s"], lw["wu_s"], lw["wd_s"])
        return h + routed + shared, None

    dense, moe = segments(W)
    h, _ = jax.lax.scan(dense_layer, h, dense)
    h, _ = jax.lax.scan(moe_layer, h, moe)
    return rmsnorm(h, W["final_norm"], arch.eps)


@partial(jax.jit, static_argnames=("arch", "control"))
def gaps(W, tokens, served, lo, hi, *, arch: Arch, control: bool = False):
    """Per position ``p`` of ``tokens`` (S,), for ``lo <= p < hi``: how far
    the reference's logit of ``served[p]`` (the token that followed ``p``)
    lies below its best logit (0 elsewhere). With ``control``, also how far
    the reference's logit of the control's top token lies below the best.
    ``W``: the weights by path (``benchlib.model.weights_by_path``).
    """
    with jax.default_matmul_precision("highest"):
        head = W["lm_head"]
        head_c = fp8_weight(head) if control else head
        h = hidden(W, tokens, arch, False)
        hc = hidden(W, tokens, arch, True) if control else h
        S = tokens.shape[0]
        nb = S // POS_BLOCK

        def block(args):
            hb, hcb, sb = args
            logits = mm(hb, head)                                # (P, V)
            best = jnp.max(logits, axis=-1)
            g = best - jnp.take_along_axis(logits, sb[:, None], -1)[:, 0]
            if not control:
                return g, g
            top = jnp.argmax(mm(fp8_rows(hcb), head_c), axis=-1)
            gc = best - jnp.take_along_axis(logits, top[:, None], -1)[:, 0]
            return g, gc

        rs = lambda x: x.reshape(nb, POS_BLOCK, *x.shape[1:])
        g, gc = jax.lax.map(block, (rs(h), rs(hc), rs(served)))
        p = jnp.arange(S)
        keep = (p >= lo) & (p < hi)
        return (jnp.where(keep, g.reshape(S), 0.0),
                jnp.where(keep, gc.reshape(S), 0.0))


# ----------------------------------------------------------------- work --
@dataclass(frozen=True)
class Dims:
    layers: int
    dense_layers: int
    d_model: int
    heads: int
    nope: int
    rope: int
    v: int
    rank: int
    d_ff: int
    moe_d_ff: int
    experts: int
    top_k: int
    shared: int
    vocab: int
    page_size: int = 64
    lat_bytes: int = 1           # fp8 latent pool
    scale_bytes: int = 8         # two f32 scales per token
    weight_bytes: int = 2        # bf16 weights and activations

    @classmethod
    def from_config(cls, cfg: dict) -> "Dims":
        return cls(layers=cfg["num_hidden_layers"],
                   dense_layers=cfg["first_k_dense_replace"],
                   d_model=cfg["hidden_size"],
                   heads=cfg["num_attention_heads"],
                   nope=cfg["qk_nope_head_dim"],
                   rope=cfg["qk_rope_head_dim"],
                   v=cfg["v_head_dim"], rank=cfg["kv_lora_rank"],
                   d_ff=cfg["intermediate_size"],
                   moe_d_ff=cfg["moe_intermediate_size"],
                   experts=cfg["n_routed_experts"],
                   top_k=cfg["num_experts_per_tok"],
                   shared=cfg["n_shared_experts"],
                   vocab=cfg["vocab_size"], page_size=cfg["kv_page_size"])

    @property
    def moe_layers(self) -> int:
        return self.layers - self.dense_layers


def attn_proj_flops(d: Dims) -> int:
    """One token's attention projections in one layer, in the absorbed
    form the latent pool serves (2 FLOPs per multiply-add): q, the latent
    and rope key, the query's absorption into latent space (W_uk), the
    output's expansion (W_uv) and W_o."""
    H = d.heads
    return 2 * (d.d_model * H * (d.nope + d.rope)
                + d.d_model * (d.rank + d.rope)
                + H * d.nope * d.rank + H * d.rank * d.v
                + H * d.v * d.d_model)


def expert_flops(d: Dims) -> int:
    """One (token, expert) pair: a SiLU-gated feed-forward of
    ``moe_d_ff``."""
    return 2 * 3 * d.d_model * d.moe_d_ff


def ffn_flops(d: Dims, dense: bool) -> int:
    """One token's feed-forward in one layer: the dense SwiGLU, or the
    router, its ``top_k`` experts and the shared experts."""
    if dense:
        return 2 * 3 * d.d_model * d.d_ff
    return (2 * d.d_model * d.experts
            + (d.top_k + d.shared) * expert_flops(d))


def token_flops(d: Dims) -> int:
    """One token through every layer's projections and feed-forward."""
    return (d.layers * attn_proj_flops(d)
            + d.dense_layers * ffn_flops(d, True)
            + d.moe_layers * ffn_flops(d, False))


def head_flops(d: Dims) -> int:
    """The output head for one sampled position."""
    return 2 * d.d_model * d.vocab


def attn_flops(d: Dims, ctx: int) -> int:
    """One query attending ``ctx`` latent positions in one layer, absorbed:
    scores against the latent and the rope key (R + dr) and the weighted
    sum of latents (R), for each head."""
    return 2 * d.heads * (d.rank + d.rope + d.rank) * ctx


def chunk_attn_flops(d: Dims, start: int, n: int) -> int:
    """A chunk of ``n`` causal queries at ``start..start+n-1`` in one
    layer (query ``p`` attends ``p + 1`` positions)."""
    return attn_flops(d, n * start + n * (n + 1) // 2)


def prompt_flops(d: Dims, start: int, n: int) -> int:
    """Prefilling prompt positions ``start..start+n-1`` through every
    layer, without the head."""
    return n * token_flops(d) + d.layers * chunk_attn_flops(d, start, n)


def decode_flops(d: Dims, ctx: int) -> int:
    """One decoded token at context ``ctx`` (positions attended, itself
    included) through every layer and the head."""
    return (token_flops(d) + d.layers * attn_flops(d, ctx)
            + head_flops(d))


# ------------------------------------------------- the two kernels' work --
def expert_bytes(d: Dims) -> int:
    """One routed expert's three matrices."""
    return 3 * d.d_model * d.moe_d_ff * d.weight_bytes


def experts_hit(d: Dims, n: int) -> float:
    """Experts of one layer that ``n`` tokens hit, expected under uniform
    routing: each token picks ``top_k`` distinct experts of ``experts``."""
    return d.experts * (1.0 - ((d.experts - d.top_k) / d.experts) ** n)


def moe_step_flops(d: Dims, n: int) -> int:
    """The routed experts of one step of ``n`` real tokens, every MoE
    layer: ``top_k`` pairs per token."""
    return d.moe_layers * n * d.top_k * expert_flops(d)


def moe_step_bytes(d: Dims, n: int) -> float:
    """The routed experts' HBM traffic in one step of ``n`` real tokens,
    every MoE layer: each expert hit (expected, ``experts_hit``) read
    once, and each pair's activation read and its output written."""
    act = 2 * n * d.top_k * d.d_model * d.weight_bytes
    return d.moe_layers * (experts_hit(d, n) * expert_bytes(d) + act)


def latent_token_bytes(d: Dims) -> int:
    """One position of the fp8 latent pool: the latent and rope key and
    their two scales (584 B at DeepSeek-V2-Lite's widths)."""
    return (d.rank + d.rope) * d.lat_bytes + d.scale_bytes


def decode_attn_bytes(d: Dims, ctx: int) -> int:
    """One decoded token's latent attention in one layer: ``ctx``
    positions of the pool read once for all heads, the f32 absorbed query
    (latent and rope parts) read and the f32 latent-space output
    written."""
    return (ctx * latent_token_bytes(d)
            + 4 * d.heads * (d.rank + d.rope) + 4 * d.heads * d.rank)
