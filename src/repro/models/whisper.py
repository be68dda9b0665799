"""Whisper-small — encoder-decoder transformer [arXiv:2212.04356].

The mel-spectrogram + conv feature extractor is a STUB per the task carve-out:
``input_specs`` provides precomputed frame embeddings (B, num_frames, d_model).
This module implements the transformer that consumes them:

  encoder  — bidirectional pre-LN attention over frames (kv=12 -> Opt-GQA
             grouping is the identity, but the code path is shared),
  decoder  — causal self-attention with the LLM-CoOpt paged cache (Opt-KV fp8
             write/read, Opt-Pa block-wise softmax) + cross-attention whose
             K/V are computed ONCE from the encoder output at prefill and
             stored (Opt-KV-quantized) in the cache — the "static KV is
             quantized once" case from DESIGN.md §5.

Whisper uses LayerNorm + GELU MLP + learned positional embeddings (sinusoidal
for the encoder); we keep that (not RMSNorm/SwiGLU).

long_500k is skipped for this arch (full-attention decoder, 448-token native
context — DESIGN.md §5); decode_32k runs as a stress shape.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.coopt import CoOptConfig, COOPT
from repro.core.opt_kv import (identity_page_table, identity_slots,
                               kv_pool_shapes, pool_lines, write_kv)
from repro.core.opt_pa import paged_chunk_attention, paged_decode_attention
from repro.cache.quant import quantize_fp8, dequantize_fp8
from repro.models.layers import (Spec, causal_attention, gelu_mlp, init_tree,
                                 layernorm, linear, repeat_kv, shard_act)

_MAX_POS = 32768 * 2   # learned decoder positions (stress shapes included)


def _pages(seq_len: int, page_size: int) -> int:
    return max((seq_len + page_size - 1) // page_size, 1)


class WhisperModel:
    def __init__(self, cfg: ModelConfig):
        assert cfg.family == "whisper"
        self.cfg = cfg

    # ------------------------------------------------------------- params --
    def _block_specs(self, L: int, cross: bool):
        cfg = self.cfg
        d, H, D = cfg.d_model, cfg.num_heads, cfg.head_dim
        s = {
            "ln1": Spec((L, d), ("layers", None), "ones", jnp.float32),
            "ln1_b": Spec((L, d), ("layers", None), "zeros", jnp.float32),
            "wq": Spec((L, d, H * D), ("layers", "d_in", "d_out")),
            "bq": Spec((L, H * D), ("layers", "d_out"), "zeros"),
            "wk": Spec((L, d, H * D), ("layers", "d_in", "d_out")),
            "wv": Spec((L, d, H * D), ("layers", "d_in", "d_out")),
            "bv": Spec((L, H * D), ("layers", "d_out"), "zeros"),
            "wo": Spec((L, H * D, d), ("layers", "d_out", "d_in")),
            "bo": Spec((L, d), ("layers", None), "zeros"),
            "ln2": Spec((L, d), ("layers", None), "ones", jnp.float32),
            "ln2_b": Spec((L, d), ("layers", None), "zeros", jnp.float32),
            "w1": Spec((L, d, cfg.d_ff), ("layers", "d_in", "d_out")),
            "b1": Spec((L, cfg.d_ff), ("layers", "d_out"), "zeros"),
            "w2": Spec((L, cfg.d_ff, d), ("layers", "d_out", "d_in")),
            "b2": Spec((L, d), ("layers", None), "zeros"),
        }
        if cross:
            s.update({
                "lnx": Spec((L, d), ("layers", None), "ones", jnp.float32),
                "lnx_b": Spec((L, d), ("layers", None), "zeros", jnp.float32),
                "xwq": Spec((L, d, H * D), ("layers", "d_in", "d_out")),
                "xbq": Spec((L, H * D), ("layers", "d_out"), "zeros"),
                "xwk": Spec((L, d, H * D), ("layers", "d_in", "d_out")),
                "xwv": Spec((L, d, H * D), ("layers", "d_in", "d_out")),
                "xbv": Spec((L, H * D), ("layers", "d_out"), "zeros"),
                "xwo": Spec((L, H * D, d), ("layers", "d_out", "d_in")),
                "xbo": Spec((L, d), ("layers", None), "zeros"),
            })
        return s

    def param_specs(self):
        cfg = self.cfg
        return {
            "embed": Spec((cfg.vocab_size, cfg.d_model), ("vocab", "d_out"),
                          "embed"),
            "pos_dec": Spec((_MAX_POS, cfg.d_model), (None, "d_out"), "embed"),
            "enc": self._block_specs(cfg.encoder_layers, cross=False),
            "enc_ln": Spec((cfg.d_model,), (None,), "ones", jnp.float32),
            "enc_ln_b": Spec((cfg.d_model,), (None,), "zeros", jnp.float32),
            "dec": self._block_specs(cfg.num_layers, cross=True),
            "final_norm": Spec((cfg.d_model,), (None,), "ones", jnp.float32),
            "final_norm_b": Spec((cfg.d_model,), (None,), "zeros",
                                 jnp.float32),
            "lm_head": Spec((cfg.d_model, cfg.vocab_size), ("d_in", "d_out")),
        }

    def init(self, key):
        return init_tree(key, self.param_specs())

    # -------------------------------------------------------------- encoder --
    @staticmethod
    def _sinusoids(length: int, channels: int):
        half = channels // 2
        log_ts = math.log(10000.0) / (half - 1)
        inv = jnp.exp(-log_ts * jnp.arange(half, dtype=jnp.float32))
        t = jnp.arange(length, dtype=jnp.float32)[:, None] * inv[None]
        return jnp.concatenate([jnp.sin(t), jnp.cos(t)], axis=-1)

    def encode(self, params, frames):
        """frames (B, F, d) stub embeddings -> encoder states (B, F, d)."""
        cfg = self.cfg
        B, F, d = frames.shape
        H, D = cfg.num_heads, cfg.head_dim
        h = frames.astype(jnp.bfloat16) + \
            self._sinusoids(F, d).astype(jnp.bfloat16)[None]
        h = shard_act(h, ("batch", "seq", None))

        def body(hh, pl):
            x = layernorm(hh, pl["ln1"], pl["ln1_b"], cfg.norm_eps)
            q = linear(x, pl["wq"], pl["bq"]).reshape(B, F, H, D)
            k = linear(x, pl["wk"]).reshape(B, F, H, D)
            v = linear(x, pl["wv"], pl["bv"]).reshape(B, F, H, D)
            o = causal_attention(q, k, v, causal=False)
            hh = hh + linear(o.reshape(B, F, H * D), pl["wo"], pl["bo"])
            x = layernorm(hh, pl["ln2"], pl["ln2_b"], cfg.norm_eps)
            hh = hh + gelu_mlp(x, pl["w1"], pl["b1"], pl["w2"], pl["b2"])
            return shard_act(hh, ("batch", "seq", None)), None

        h, _ = jax.lax.scan(jax.checkpoint(body), h, params["enc"])
        return layernorm(h, params["enc_ln"], params["enc_ln_b"],
                         cfg.norm_eps)

    # ---------------------------------------------------------- cross-attn --
    def _cross_kv(self, pl, enc):
        """Static cross-attention K/V from encoder states (per layer)."""
        cfg = self.cfg
        B, F, _ = enc.shape
        H, D = cfg.num_heads, cfg.head_dim
        k = linear(enc, pl["xwk"]).reshape(B, F, H, D)
        v = linear(enc, pl["xwv"], pl["xbv"]).reshape(B, F, H, D)
        return k, v

    def _cross_attn(self, pl, x, xk, xv, xscale, coopt):
        """x (B,S,d); xk/xv (B,F,H,D) possibly fp8 (+ per-token scale)."""
        cfg = self.cfg
        B, S, _ = x.shape
        H, D = cfg.num_heads, cfg.head_dim
        q = linear(x, pl["xwq"], pl["xbq"]).reshape(B, S, H, D)
        if coopt.opt_kv and xscale is not None:
            xk = dequantize_fp8(xk, xscale[0], axis=-1)
            xv = dequantize_fp8(xv, xscale[1], axis=-1)
        else:
            xk, xv = xk.astype(q.dtype), xv.astype(q.dtype)
        o = causal_attention(q, xk, xv, causal=False)
        return linear(o.reshape(B, S, H * D), pl["xwo"], pl["xbo"])

    # -------------------------------------------------------------- decoder --
    def _decoder(self, params, tokens, cache, coopt, positions, slots,
                 write_cache: bool, long_window: int = 0,
                 page_table=None, cache_len=None, chunk_attn: bool = False):
        cfg = self.cfg
        B, S = tokens.shape
        H, D = cfg.num_heads, cfg.head_dim
        h = params["embed"][tokens].astype(jnp.bfloat16)
        h = h + params["pos_dec"][positions].astype(jnp.bfloat16)
        h = shard_act(h, ("batch", "seq", None))
        if page_table is None:
            page_table = identity_page_table(B, cache["kv"].shape[2])
        page_table = page_table.astype(jnp.int32)
        new_len = (cache["length"] + S if cache_len is None
                   else cache_len).astype(jnp.int32)

        # the self-attention pool rides whole in the carry; each layer
        # writes and reads its own layer of it in place
        kv, sc = cache["kv"], (cache["scale"] if coopt.opt_kv else None)
        P, ps = kv.shape[2], kv.shape[4]
        xs = (params["dec"], cache["xk"], cache["xv"],
              cache["xscale"] if coopt.opt_kv else None,
              jnp.arange(kv.shape[0], dtype=jnp.int32))

        def body(carry, xs):
            hh, kv, sc = carry
            pl, xk, xv, xsc, layer = xs
            x = layernorm(hh, pl["ln1"], pl["ln1_b"], cfg.norm_eps)
            q = linear(x, pl["wq"], pl["bq"]).reshape(B, S, H, D)
            k = linear(x, pl["wk"]).reshape(B, S, H, D)
            v = linear(x, pl["wv"], pl["bv"]).reshape(B, S, H, D)
            kv, sc = write_kv(kv, sc, k, v, pool_lines(slots, layer, P, ps),
                              coopt)
            if chunk_attn:
                # continuation chunk: attend the lane's whole cached history
                # (prefix hits + earlier chunks + this one) with true
                # positions — the unified ragged step path; the long_window
                # policy mirrors the decode branch so a token's logits are
                # step-composition independent
                o = paged_chunk_attention(q, kv, sc, layer, positions,
                                          page_table, coopt,
                                          window=long_window,
                                          sink_pages=cfg.sink_blocks)
            elif S == 1:
                o = paged_decode_attention(
                    q[:, 0], kv, sc, layer, new_len, coopt=coopt,
                    window=long_window, sink_pages=cfg.sink_blocks,
                    page_table=page_table)[:, None]
            else:
                o = causal_attention(q, k, v)
            hh = hh + linear(o.reshape(B, S, H * D), pl["wo"], pl["bo"])
            x = layernorm(hh, pl["lnx"], pl["lnx_b"], cfg.norm_eps)
            hh = hh + self._cross_attn(pl, x, xk, xv, xsc, coopt)
            x = layernorm(hh, pl["ln2"], pl["ln2_b"], cfg.norm_eps)
            hh = hh + gelu_mlp(x, pl["w1"], pl["b1"], pl["w2"], pl["b2"])
            return (shard_act(hh, ("batch", "seq", None)), kv, sc), None

        body_fn = jax.checkpoint(body) if S > 1 else body
        (h, kv, sc), _ = jax.lax.scan(body_fn, (h, kv, sc), xs)
        cache = dict(cache)
        cache["kv"] = kv
        if coopt.opt_kv:
            cache["scale"] = sc
        cache["length"] = new_len
        h = layernorm(h, params["final_norm"], params["final_norm_b"],
                      cfg.norm_eps)
        return h, cache

    def _fill_cross(self, params, cache, enc, coopt):
        """Compute + (optionally fp8-) store per-layer cross K/V."""
        def per_layer(pl):
            return self._cross_kv(pl, enc)

        k, v = jax.lax.map(lambda pl: per_layer(pl), params["dec"])
        cache = dict(cache)
        if coopt.opt_kv:
            qk, sk = quantize_fp8(k, axis=-1)
            qv, sv = quantize_fp8(v, axis=-1)
            cache["xk"], cache["xv"] = qk, qv
            cache["xscale"] = jnp.stack([sk, sv], axis=1)   # (L, 2, B, F, H)
        else:
            cache["xk"], cache["xv"] = k.astype(jnp.bfloat16), \
                v.astype(jnp.bfloat16)
        return cache

    # ------------------------------------------------------------- forward --
    def forward(self, params, batch, coopt: CoOptConfig = COOPT):
        """Teacher-forced decoder logits over text tokens (B, S_text, V)."""
        cfg = self.cfg
        tokens = batch["tokens"]
        B, S = tokens.shape
        enc = self.encode(params, batch["frames"])
        cache = self.init_cache(B, S, coopt)
        cache = self._fill_cross(params, cache, enc, coopt)
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        slots = identity_slots(B, positions, cache["kv"].shape[2],
                               coopt.page_size)
        h, _ = self._decoder(params, tokens, cache, coopt, positions,
                             slots, True)
        return linear(h, params["lm_head"]), {}

    def prefill(self, params, batch, cache, coopt: CoOptConfig = COOPT,
                long_window: int = 0):
        """Prompt prefill — monolithic (whole right-padded prompt) or
        chunked continuation (``positions`` present: absolute per-lane
        positions, the unified ragged step path).

        Cross-attention K/V are computed ONCE per request, on its FIRST
        chunk: pass ``frames`` plus a per-lane bool ``cross_mask`` naming
        the lanes whose cross K/V should be (re)filled; steps with no new
        first chunk omit ``frames`` and skip the encoder entirely."""
        cfg = self.cfg
        tokens = batch["tokens"]
        B, S = tokens.shape
        chunked = "positions" in batch
        if "frames" in batch:
            enc = self.encode(params, batch["frames"])
            filled = self._fill_cross(params, cache, enc, coopt)
            cm = batch.get("cross_mask")
            if cm is None:
                cache = filled
            else:
                merged = dict(cache)
                keys = [("xk", 1), ("xv", 1)]
                if coopt.opt_kv:
                    keys.append(("xscale", 2))       # (L, 2, B, F, H)
                for key, ax in keys:
                    new = filled[key]
                    m = cm.reshape((1,) * ax + (-1,) +
                                   (1,) * (new.ndim - ax - 1))
                    merged[key] = jnp.where(m, new, cache[key])
                cache = merged
        if chunked:
            positions = batch["positions"].astype(jnp.int32)
        else:
            positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        if "slot_idx" in batch:
            slots = batch["slot_idx"].astype(jnp.int32)
        else:
            slots = identity_slots(B, positions, cache["kv"].shape[2],
                                   coopt.page_size)
        h, cache = self._decoder(params, tokens, cache, coopt, positions,
                                 slots, True, long_window=long_window,
                                 page_table=batch.get("page_table"),
                                 cache_len=batch.get("cache_len"),
                                 chunk_attn=chunked)
        last_pos = batch.get("last_pos")
        if last_pos is not None:
            if not chunked:
                # pads carry slot -1 (never cached); length = real tokens
                cache["length"] = (last_pos + 1).astype(jnp.int32)
            h_last = jnp.take_along_axis(
                h, last_pos[:, None, None].astype(jnp.int32), axis=1)[:, 0]
        else:
            h_last = h[:, -1]
        return linear(h_last, params["lm_head"]), cache

    def decode_step(self, params, batch, cache, coopt: CoOptConfig = COOPT,
                    long_window: int = 0):
        B = batch["token"].shape[0]
        positions = batch.get("positions")
        if positions is None:
            positions = cache["length"][:, None]
        positions = positions.astype(jnp.int32)
        if "slot_idx" in batch:
            slots = batch["slot_idx"].astype(jnp.int32)
        else:
            slots = identity_slots(B, positions, cache["kv"].shape[2],
                                   coopt.page_size)
        h, cache = self._decoder(params, batch["token"], cache, coopt,
                                 positions, slots, True,
                                 long_window=long_window,
                                 page_table=batch.get("page_table"),
                                 cache_len=batch.get("cache_len"))
        return linear(h[:, 0], params["lm_head"]), cache

    # ------------------------------------------------------------- caching --
    def cache_shape(self, batch: int, max_len: int, coopt: CoOptConfig,
                    num_shards: int = 1, cache_cfg=None):
        cfg = self.cfg
        L, H, D, F = cfg.num_layers, cfg.num_heads, cfg.head_dim, \
            cfg.num_frames
        out = {
            # decoder self-attn KV: GLOBAL pool (no batch dim); cross-attn
            # K/V are static per-lane encoder projections and stay
            # batch-major (quantized once — DESIGN.md §5).
            **kv_pool_shapes(L, batch, max_len, H, D, coopt, num_shards,
                             cache_cfg),
            "xk": ((L, batch, F, H, D), coopt.kv_dtype,
                   ("layers", "batch", None, "kv_heads", "head_dim")),
            "xv": ((L, batch, F, H, D), coopt.kv_dtype,
                   ("layers", "batch", None, "kv_heads", "head_dim")),
            "length": ((batch,), jnp.int32, ("batch",)),
        }
        if coopt.opt_kv:
            out["xscale"] = ((L, 2, batch, F, H), jnp.float32,
                             ("layers", None, "batch", None, "kv_heads"))
        return out

    def init_cache(self, batch: int, max_len: int, coopt: CoOptConfig,
                   num_shards: int = 1, cache_cfg=None):
        return {k: jnp.zeros(sh, dt)
                for k, (sh, dt, _) in
                self.cache_shape(batch, max_len, coopt,
                                 num_shards=num_shards,
                                 cache_cfg=cache_cfg).items()}

    # -------------------------------------------------------------- specs --
    def input_specs(self, shape) -> Dict[str, jax.ShapeDtypeStruct]:
        cfg = self.cfg
        B, S = shape.global_batch, shape.seq_len
        tok = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
        if shape.kind == "decode":
            return {"token": tok(B, 1)}
        out = {"tokens": tok(B, S),
               "frames": jax.ShapeDtypeStruct((B, cfg.num_frames, cfg.d_model),
                                              jnp.bfloat16)}
        if shape.kind == "train":
            out["labels"] = tok(B, S)
        return out

    def param_count(self) -> int:
        from repro.models.layers import param_count
        return param_count(self.param_specs())

    def active_param_count(self) -> int:
        return self.param_count()
