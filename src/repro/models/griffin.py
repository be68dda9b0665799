"""Griffin / RecurrentGemma — RG-LRU + local-attention hybrid, pattern 1 attn
per 2 recurrent layers [arXiv:2402.19427].

LLM-CoOpt applicability (DESIGN.md §5): the local-attention layers carry a
(windowed) paged KV cache — Opt-KV (fp8 + SkipSet), Opt-GQA (kv=1 -> MQA
grouping) and Opt-Pa (valid-block filtering + online softmax) all apply there.
RG-LRU layers carry O(1) recurrent state (kept f32 — quantizing the recurrence
would compound error across steps and is not claimed by the paper).

RG-LRU recurrence (per channel):
    r_t = sigmoid(W_a x_t);  i_t = sigmoid(W_x x_t)
    a_t = exp(c * r_t * (-softplus(LAMBDA)))            # c = 8
    h_t = a_t h_{t-1} + sqrt(1 - a_t^2) * (i_t * x_t)
Train/prefill realises the linear recurrence with ``lax.associative_scan``
(TPU-idiomatic parallel prefix, O(log T) depth); decode is the O(1) step.

Layer layout for scan-over-layers: recurrent layers and attention layers are
stacked separately; we scan over pattern *periods* (rec, rec, attn), plus a
trailing mini-scan for ``num_layers % 3`` leftover recurrent layers.
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
from repro.models.layers import (Spec, apply_rope, causal_attention, init_tree,
                                 linear, repeat_kv, rmsnorm, shard_act)

_C = 8.0  # RG-LRU temperature


def _pages(seq_len: int, page_size: int) -> int:
    return max((seq_len + page_size - 1) // page_size, 1)


class GriffinModel:
    # batch-major cache leaves carrying cross-chunk recurrent state: the
    # engine zeroes them on a request's first chunk and snapshots them at
    # committed page boundaries (prefix-cache resume points)
    recurrent_leaves = ("conv", "lru")

    def __init__(self, cfg: ModelConfig):
        assert cfg.family == "griffin"
        self.cfg = cfg
        self.n_periods = cfg.num_layers // 3
        self.n_trail = cfg.num_layers % 3          # leftover rec layers
        self.n_rec = self.n_periods * 2 + self.n_trail
        self.n_attn = self.n_periods

    # ------------------------------------------------------------- params --
    def _rec_specs(self, L: int):
        cfg = self.cfg
        d, W = cfg.d_model, cfg.lru_width
        cw = cfg.conv1d_width
        return {
            "ln": Spec((L, d), ("layers", None), "ones", jnp.float32),
            "w_gelu": Spec((L, d, W), ("layers", "d_in", "d_out")),
            "w_rec_in": Spec((L, d, W), ("layers", "d_in", "d_out")),
            "conv_w": Spec((L, cw, W), ("layers", None, "d_out")),
            "conv_b": Spec((L, W), ("layers", "d_out"), "zeros"),
            "w_a": Spec((L, W, W), ("layers", "d_in", "d_out")),
            "w_x": Spec((L, W, W), ("layers", "d_in", "d_out")),
            "lam": Spec((L, W), ("layers", "d_out"), "ones", jnp.float32),
            "w_rec_out": Spec((L, W, d), ("layers", "d_out", "d_in")),
            "ln_f": Spec((L, d), ("layers", None), "ones", jnp.float32),
            "wg": Spec((L, d, cfg.d_ff), ("layers", "d_in", "d_out")),
            "wu": Spec((L, d, cfg.d_ff), ("layers", "d_in", "d_out")),
            "wd": Spec((L, cfg.d_ff, d), ("layers", "d_out", "d_in")),
        }

    def _attn_specs(self, L: int):
        cfg = self.cfg
        d, H, Hkv, D = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        return {
            "ln": Spec((L, d), ("layers", None), "ones", jnp.float32),
            "wq": Spec((L, d, H * D), ("layers", "d_in", "d_out")),
            "wk": Spec((L, d, Hkv * D), ("layers", "d_in", "d_out")),
            "wv": Spec((L, d, Hkv * D), ("layers", "d_in", "d_out")),
            "wo": Spec((L, H * D, d), ("layers", "d_out", "d_in")),
            "ln_f": Spec((L, d), ("layers", None), "ones", jnp.float32),
            "wg": Spec((L, d, cfg.d_ff), ("layers", "d_in", "d_out")),
            "wu": Spec((L, d, cfg.d_ff), ("layers", "d_in", "d_out")),
            "wd": Spec((L, cfg.d_ff, d), ("layers", "d_out", "d_in")),
        }

    def param_specs(self):
        cfg = self.cfg
        return {
            "embed": Spec((cfg.vocab_size, cfg.d_model), ("vocab", "d_out"),
                          "embed"),
            "rec": self._rec_specs(self.n_rec),
            "attn": self._attn_specs(self.n_attn),
            "final_norm": Spec((cfg.d_model,), (None,), "ones", jnp.float32),
            "lm_head": Spec((cfg.d_model, cfg.vocab_size), ("d_in", "d_out")),
        }

    def init(self, key):
        return init_tree(key, self.param_specs())

    # ---------------------------------------------------------- RG-LRU core --
    def _rg_lru(self, pl, x, h0, valid=None):
        """x (B,S,W) f32; h0 (B,W) f32. Returns (y (B,S,W), h_S).
        ``valid`` (B,S) freezes the recurrence on padding (a=1, b=0)."""
        log_a0 = -jax.nn.softplus(pl["lam"].astype(jnp.float32))  # (W,) < 0
        r = jax.nn.sigmoid(linear(x, pl["w_a"]).astype(jnp.float32))
        i = jax.nn.sigmoid(linear(x, pl["w_x"]).astype(jnp.float32))
        log_a = _C * r * log_a0                                   # (B,S,W)
        a = jnp.exp(log_a)
        b = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12)) \
            * (i * x.astype(jnp.float32))
        if valid is not None:
            vm = valid[:, :, None]
            a = jnp.where(vm, a, 1.0)
            b = b * vm
        if x.shape[1] == 1:
            h = a[:, 0] * h0 + b[:, 0]
            return h[:, None], h
        # associative scan: h_t = a_t h_{t-1} + b_t
        b0 = b.at[:, 0].add(a[:, 0] * h0)

        def comb(u, v):
            au, bu = u
            av, bv = v
            return au * av, av * bu + bv

        _, hs = jax.lax.associative_scan(comb, (a, b0), axis=1)
        return hs, hs[:, -1]

    def _rec_block(self, pl, x, conv_state, h0, valid=None, last_pos=None):
        """Recurrent block. x (B,S,d). Returns (out, new conv_state, h_S)."""
        cfg = self.cfg
        B, S, _ = x.shape
        cw = cfg.conv1d_width
        gel = jax.nn.gelu(linear(x, pl["w_gelu"]))
        u = linear(x, pl["w_rec_in"])                    # (B,S,W)
        if valid is not None:  # padding contributes nothing to the conv taps
            u = u * valid[:, :, None].astype(u.dtype)
        # causal depthwise conv1d
        upad = jnp.concatenate([conv_state.astype(u.dtype), u], axis=1)
        w = pl["conv_w"].astype(jnp.float32)             # (cw, W)
        conv = sum(upad[:, k:k + S].astype(jnp.float32) * w[k]
                   for k in range(cw))
        conv = (conv + pl["conv_b"].astype(jnp.float32)).astype(u.dtype)
        if last_pos is None:
            new_conv_state = upad[:, S:S + cw - 1]
        else:  # last cw-1 REAL inputs end at last_pos (right padding)
            idx = last_pos[:, None] + 2 - cw + jnp.arange(cw - 1)[None]
            idx = jnp.maximum(idx + (cw - 1), 0)         # upad offset
            new_conv_state = jnp.take_along_axis(
                upad, idx[:, :, None].astype(jnp.int32), axis=1)
        y, h = self._rg_lru(pl, conv, h0, valid)
        y = (y.astype(x.dtype) * gel)
        return linear(y, pl["w_rec_out"]), new_conv_state, h

    # --------------------------------------------------------- attn blocks --
    def _attn_full(self, pl, x, positions, coopt):
        cfg = self.cfg
        B, S, _ = x.shape
        H, Hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        q = linear(x, pl["wq"]).reshape(B, S, H, D)
        k = linear(x, pl["wk"]).reshape(B, S, Hkv, D)
        v = linear(x, pl["wv"]).reshape(B, S, Hkv, D)
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
        if coopt.opt_gqa or Hkv == H:
            o = causal_attention(q, k, v, window=cfg.local_window)
        else:
            o = causal_attention(q, repeat_kv(k, H // Hkv),
                                 repeat_kv(v, H // Hkv),
                                 window=cfg.local_window)
        return linear(o.reshape(B, S, H * D), pl["wo"]), k, v

    def _mlp(self, pl, x):
        h = jax.nn.gelu(linear(x, pl["wg"])) * linear(x, pl["wu"])
        return linear(h, pl["wd"])

    # ------------------------------------------------------------- forward --
    def _period_scan(self, params, cache, h, positions, slots, coopt, attn_fn,
                     valid=None, last_pos=None):
        """Scan over (rec, rec, attn) periods + trailing rec layers.

        The attention layers' K/V pool (one layer per period) rides whole
        in the carry. attn_fn(pl, x, kv, sc, layer) -> (attn_out, kv, sc),
        with ``layer`` the period's traced index into the pool."""
        cfg = self.cfg
        NP, NT = self.n_periods, self.n_trail
        rec_p = params["rec"]
        rec_main = jax.tree.map(
            lambda a: a[:NP * 2].reshape(NP, 2, *a.shape[1:]), rec_p)
        rec_trail = jax.tree.map(lambda a: a[NP * 2:], rec_p)

        cs, hs = cache["conv"], cache["lru"]
        cs_main = cs[:NP * 2].reshape(NP, 2, *cs.shape[1:])
        hs_main = hs[:NP * 2].reshape(NP, 2, *hs.shape[1:])
        kv = cache["kv"]
        sc = cache.get("scale") if coopt.opt_kv else None

        def one_rec(hh, pl, c0, h0):
            x = rmsnorm(hh, pl["ln"], cfg.norm_eps)
            a, c1, h1 = self._rec_block(pl, x, c0, h0, valid, last_pos)
            hh = hh + a
            hh = hh + self._mlp(pl, rmsnorm(hh, pl["ln_f"], cfg.norm_eps))
            return shard_act(hh, ("batch", "seq", None)), c1, h1

        def period(carry, xs):
            hh, kv, sc = carry
            rp, c0, h0, ap, layer = xs
            c_out, h_out = [], []
            for j in range(2):
                rj = jax.tree.map(lambda a: a[j], rp)
                hh, c1, h1 = one_rec(hh, rj, c0[j], h0[j])
                c_out.append(c1)
                h_out.append(h1)
            x = rmsnorm(hh, ap["ln"], cfg.norm_eps)
            a, kv, sc = attn_fn(ap, x, kv, sc, layer)
            hh = hh + a
            hh = hh + self._mlp(ap, rmsnorm(hh, ap["ln_f"], cfg.norm_eps))
            hh = shard_act(hh, ("batch", "seq", None))
            return (hh, kv, sc), (jnp.stack(c_out), jnp.stack(h_out))

        xs = (rec_main, cs_main, hs_main, params["attn"],
              jnp.arange(NP, dtype=jnp.int32))
        period_fn = jax.checkpoint(period) if h.shape[1] > 1 else period
        (h, new_kv, new_sc), ys = jax.lax.scan(period_fn, (h, kv, sc), xs)
        new_conv = ys[0].reshape(NP * 2, *cs.shape[1:])
        new_lru = ys[1].reshape(NP * 2, *hs.shape[1:])

        # trailing rec layers (static count <= 2)
        trail_c, trail_h = [], []
        for j in range(NT):
            rj = jax.tree.map(lambda a: a[j], rec_trail)
            h, c1, h1 = one_rec(h, rj, cs[NP * 2 + j], hs[NP * 2 + j])
            trail_c.append(c1)
            trail_h.append(h1)
        if NT:
            new_conv = jnp.concatenate([new_conv, jnp.stack(trail_c)], 0)
            new_lru = jnp.concatenate([new_lru, jnp.stack(trail_h)], 0)

        cache = dict(cache)
        cache["conv"], cache["lru"], cache["kv"] = new_conv, new_lru, new_kv
        if coopt.opt_kv:
            cache["scale"] = new_sc
        return h, cache

    def forward(self, params, batch, coopt: CoOptConfig = COOPT):
        cfg = self.cfg
        tokens = batch["tokens"]
        B, S = tokens.shape
        h = params["embed"][tokens].astype(jnp.bfloat16)
        h = shard_act(h, ("batch", "seq", None))
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        cache = self.init_cache(B, S, coopt)
        slots = positions.astype(jnp.int32)

        def attn_fn(ap, x, kv_c, sc_c, layer):
            # training: in-flight attention only, no cache writes
            a, _, _ = self._attn_full(ap, x, positions, coopt)
            return a, kv_c, sc_c

        h, _ = self._period_scan(params, cache, h, positions, slots, coopt,
                                 attn_fn)
        h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
        return linear(h, params["lm_head"]), {}

    def prefill(self, params, batch, cache, coopt: CoOptConfig = COOPT,
                long_window: int = 0):
        """Prompt prefill (``long_window`` accepted for engine-call
        uniformity; local attention always uses ``cfg.local_window``,
        matching ``decode_step``). With ``batch["positions"]`` (B,S) this
        is a CONTINUATION chunk (the unified ragged step path): the recurrent
        state in the cache is the state after the previous chunk and is
        threaded straight through (state after chunk k feeds chunk k+1),
        while the local-attention layers write this chunk's K/V to the paged
        pool and attend the lane's whole cached history with true positions
        — a decode lane is a chunk of length 1."""
        cfg = self.cfg
        tokens = batch["tokens"]
        B, S = tokens.shape
        h = params["embed"][tokens].astype(jnp.bfloat16)
        h = shard_act(h, ("batch", "seq", None))
        chunked = "positions" in batch
        if chunked:
            positions = batch["positions"].astype(jnp.int32)
        else:
            positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        P_total = cache["kv"].shape[2]
        page_table = batch.get("page_table")
        if "slot_idx" in batch:
            slots = batch["slot_idx"].astype(jnp.int32)
        else:
            slots = identity_slots(B, positions, P_total, coopt.page_size)
        valid = batch.get("pad_mask")
        last_pos = batch.get("last_pos")
        H, Hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        ps = cache["kv"].shape[4]

        def attn_fn(ap, x, kv_c, sc_c, layer):
            lines = pool_lines(slots, layer, P_total, ps)
            if chunked:
                q = linear(x, ap["wq"]).reshape(B, S, H, D)
                k = linear(x, ap["wk"]).reshape(B, S, Hkv, D)
                v = linear(x, ap["wv"]).reshape(B, S, Hkv, D)
                q = apply_rope(q, positions, cfg.rope_theta)
                k = apply_rope(k, positions, cfg.rope_theta)
                kv_c, sc_c = write_kv(kv_c, sc_c, k, v, lines, coopt)
                o = paged_chunk_attention(
                    q, kv_c, sc_c, layer, positions, page_table, coopt,
                    window=cfg.local_window, sink_pages=cfg.sink_blocks)
                return linear(o.reshape(B, S, H * D), ap["wo"]), kv_c, sc_c
            a, k, v = self._attn_full(ap, x, positions, coopt)
            kv_c, sc_c = write_kv(kv_c, sc_c, k, v, lines, coopt)
            return a, kv_c, sc_c

        h, cache = self._period_scan(params, cache, h, positions, slots,
                                     coopt, attn_fn, valid, last_pos)
        new_len = batch.get("cache_len")
        if new_len is None:
            added = S if valid is None else jnp.sum(valid, axis=1)
            new_len = cache["length"] + added
        cache["length"] = new_len.astype(jnp.int32)
        h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
        if last_pos is not None:
            h_last = jnp.take_along_axis(
                h, last_pos[:, None, None].astype(jnp.int32), axis=1)[:, 0]
        else:
            h_last = h[:, -1]
        return linear(h_last, params["lm_head"]), cache

    def decode_step(self, params, batch, cache, coopt: CoOptConfig = COOPT,
                    long_window: int = 0):
        cfg = self.cfg
        h = params["embed"][batch["token"]].astype(jnp.bfloat16)
        B = h.shape[0]
        positions = batch.get("positions")
        if positions is None:
            positions = cache["length"][:, None]
        positions = positions.astype(jnp.int32)
        P_total = cache["kv"].shape[2]
        page_table = batch.get("page_table")
        if page_table is None:
            page_table = identity_page_table(B, P_total)
        page_table = page_table.astype(jnp.int32)
        if "slot_idx" in batch:
            slots = batch["slot_idx"].astype(jnp.int32)
        else:
            slots = identity_slots(B, positions, P_total, coopt.page_size)
        new_len = batch.get("cache_len")
        if new_len is None:
            new_len = cache["length"] + 1
        new_len = new_len.astype(jnp.int32)
        H, Hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        ps = cache["kv"].shape[4]

        def attn_fn(ap, x, kv_c, sc_c, layer):
            q = linear(x, ap["wq"]).reshape(B, 1, H, D)
            k = linear(x, ap["wk"]).reshape(B, 1, Hkv, D)
            v = linear(x, ap["wv"]).reshape(B, 1, Hkv, D)
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
            kv_c, sc_c = write_kv(kv_c, sc_c, k, v,
                                  pool_lines(slots, layer, P_total, ps), coopt)
            o = paged_decode_attention(
                q[:, 0], kv_c, sc_c, layer, new_len, coopt=coopt,
                window=cfg.local_window, sink_pages=cfg.sink_blocks,
                page_table=page_table)
            return linear(o.reshape(B, 1, H * D), ap["wo"]), kv_c, sc_c

        h, cache = self._period_scan(params, cache, h, positions, slots,
                                     coopt, attn_fn)
        cache["length"] = new_len
        h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
        return linear(h[:, 0], params["lm_head"]), cache

    # ------------------------------------------------------------- caching --
    def cache_shape(self, batch: int, max_len: int, coopt: CoOptConfig,
                    num_shards: int = 1, cache_cfg=None):
        cfg = self.cfg
        # GLOBAL-POOL layout for the attention layers' paged KV (see
        # transformer.TransformerModel.cache_shape), pages padded to tile
        # over the KV shards; recurrent state (conv taps, RG-LRU h) is O(1)
        # per lane and stays batch-major.
        Hkv, D, W = cfg.num_kv_heads, cfg.head_dim, cfg.lru_width
        out = {
            "conv": ((self.n_rec, batch, cfg.conv1d_width - 1, W), jnp.bfloat16,
                     ("layers", "batch", None, "d_model")),
            "lru": ((self.n_rec, batch, W), jnp.float32,
                    ("layers", "batch", "d_model")),
            **kv_pool_shapes(self.n_attn, batch, max_len, Hkv, D, coopt,
                             num_shards, cache_cfg),
            "length": ((batch,), jnp.int32, ("batch",)),
        }
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
        B, S = shape.global_batch, shape.seq_len
        tok = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
        if shape.kind == "decode":
            return {"token": tok(B, 1)}
        out = {"tokens": tok(B, S)}
        if shape.kind == "train":
            out["labels"] = tok(B, S)
        return out

    def param_count(self) -> int:
        from repro.models.layers import param_count
        return param_count(self.param_specs())

    def active_param_count(self) -> int:
        return self.param_count()
