"""Generic decoder-only transformer covering the dense / moe / mla / vlm
families, with scan-over-layers (stacked params), paged KV caching, and the
three LLM-CoOpt techniques toggled by a ``CoOptConfig``.

Step kinds (configs/shapes.py):
  forward     – teacher-forced full sequence (train)
  prefill     – forward + KV-cache population (in-flight bf16 attention;
                the cache stores the Opt-KV-quantized copy for later decode)
  decode_step – ONE token against the paged cache (Opt-Pa / Opt-KV read path)
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.coopt import CoOptConfig, COOPT
from repro.core.opt_kv import (identity_page_table, identity_slots,
                               kv_pool_shapes, pool_layout, pool_lines,
                               write_kv)
from repro.core.opt_pa import paged_chunk_attention, paged_decode_attention
from repro.models import mla as mla_mod
from repro.models.layers import (Spec, apply_rope, causal_attention, init_tree,
                                 linear, no_counters, repeat_kv, rmsnorm,
                                 shard_act, swiglu)
from repro.models.moe import moe_ffn


# routed experts' weights: the serving layer scan hands them to the expert
# kernel stacked, with the layer's index (``_scan_with_cache``)
EXPERT_LEAVES = ("wg_e", "wu_e", "wd_e")


def _pages(seq_len: int, page_size: int) -> int:
    return max((seq_len + page_size - 1) // page_size, 1)


class TransformerModel:
    """Families: dense (yi/qwen/deepseek/llama), moe (mixtral), mla
    (deepseek-v2), vlm (internvl2 — stub patch embeddings prepended)."""

    def __init__(self, cfg: ModelConfig):
        assert cfg.family in ("dense", "moe", "mla", "vlm")
        self.cfg = cfg

    # ------------------------------------------------------------- params --
    def _segments(self):
        cfg = self.cfg
        moe = "moe" if cfg.num_experts else "dense"
        if cfg.num_experts and cfg.first_dense_layers:
            return [(cfg.first_dense_layers, "dense"),
                    (cfg.num_layers - cfg.first_dense_layers, moe)]
        return [(cfg.num_layers, moe)]

    def _attn_specs(self, L: int) -> Dict[str, Spec]:
        cfg = self.cfg
        d, H, Hkv, D = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        s: Dict[str, Spec] = {
            "ln1": Spec((L, d), ("layers", None), "ones", jnp.float32)}
        if cfg.family == "mla":
            dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
            R, dv = cfg.kv_lora_rank, cfg.v_head_dim
            s.update(
                wq=Spec((L, d, H * (dn + dr)), ("layers", "d_in", "d_out")),
                w_dkv=Spec((L, d, R + dr), ("layers", "d_in", "d_out")),
                kv_norm=Spec((L, R), ("layers", None), "ones", jnp.float32),
                w_uk=Spec((L, R, H * dn), ("layers", "d_in", "d_out")),
                w_uv=Spec((L, R, H * dv), ("layers", "d_in", "d_out")),
                wo=Spec((L, H * dv, d), ("layers", "d_out", "d_in")),
            )
            return s
        s.update(
            wq=Spec((L, d, H * D), ("layers", "d_in", "d_out")),
            wk=Spec((L, d, Hkv * D), ("layers", "d_in", "d_out")),
            wv=Spec((L, d, Hkv * D), ("layers", "d_in", "d_out")),
            wo=Spec((L, H * D, d), ("layers", "d_out", "d_in")),
        )
        if cfg.qkv_bias:
            s.update(bq=Spec((L, H * D), ("layers", "d_out"), "zeros"),
                     bk=Spec((L, Hkv * D), ("layers", "d_out"), "zeros"),
                     bv=Spec((L, Hkv * D), ("layers", "d_out"), "zeros"))
        if cfg.qk_norm:
            s.update(q_norm=Spec((L, D), ("layers", None), "ones", jnp.float32),
                     k_norm=Spec((L, D), ("layers", None), "ones", jnp.float32))
        return s

    def _ffn_specs(self, L: int, kind: str) -> Dict[str, Spec]:
        cfg = self.cfg
        d = cfg.d_model
        s = {"ln2": Spec((L, d), ("layers", None), "ones", jnp.float32)}
        if kind == "dense":
            ff = cfg.d_ff
            s.update(wg=Spec((L, d, ff), ("layers", "d_in", "d_out")),
                     wu=Spec((L, d, ff), ("layers", "d_in", "d_out")),
                     wd=Spec((L, ff, d), ("layers", "d_out", "d_in")))
        else:
            E, ff = cfg.num_experts, cfg.moe_d_ff
            s.update(
                wr=Spec((L, d, E), ("layers", "d_in", None)),
                # expert-parallel: experts -> "data" when divisible (else
                # d_in takes it), ff -> model. (§Perf P1: un-sharding d and
                # putting ff on (data, model) replicated the expert compute
                # 100x — refuted; what holds is keeping each batch shard's
                # tokens on it, moe_ffn's per-shard dispatch.)
                wg_e=Spec((L, E, d, ff), ("layers", "experts", "moe_d_in",
                                          "d_out")),
                wu_e=Spec((L, E, d, ff), ("layers", "experts", "moe_d_in",
                                          "d_out")),
                wd_e=Spec((L, E, ff, d), ("layers", "experts", "d_out",
                                          "moe_d_in")),
            )
            if cfg.num_shared_experts:
                sf = ff * cfg.num_shared_experts
                s.update(wg_s=Spec((L, d, sf), ("layers", "d_in", "d_out")),
                         wu_s=Spec((L, d, sf), ("layers", "d_in", "d_out")),
                         wd_s=Spec((L, sf, d), ("layers", "d_out", "d_in")))
        return s

    def param_specs(self):
        cfg = self.cfg
        segs = []
        for count, kind in self._segments():
            seg = dict(self._attn_specs(count))
            seg.update(self._ffn_specs(count, kind))
            segs.append(seg)
        return {
            "embed": Spec((cfg.vocab_size, cfg.d_model), ("vocab", "d_out"),
                          "embed"),
            "segments": segs,
            "final_norm": Spec((cfg.d_model,), (None,), "ones", jnp.float32),
            "lm_head": Spec((cfg.d_model, cfg.vocab_size), ("d_in", "d_out")),
        }

    def init(self, key):
        return init_tree(key, self.param_specs())

    # -------------------------------------------------------------- layers --
    def _attention_full(self, p, x, positions, coopt: CoOptConfig,
                        as_cached: bool = False):
        """Full-sequence attention (train/prefill). Returns (out, k, v) —
        k/v are the per-token cache entries (None head-expanded).
        ``as_cached`` (prefill): MLA attends the latent as the pool holds
        it (the fp8 round trip under Opt-KV), as chunked prefill and decode
        read it, so a monolithic prefill attends what a chunked one does."""
        cfg = self.cfg
        B, S, _ = x.shape
        H, Hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        if cfg.family == "mla":
            qn, qr, latent = mla_mod.mla_project(x, p, cfg, positions)
            seen = latent
            if as_cached and coopt.opt_kv:
                from repro.cache.quant import (dequantize_latent,
                                               quantize_latent)
                seen = dequantize_latent(
                    *quantize_latent(latent, cfg.kv_lora_rank),
                    cfg.kv_lora_rank, dtype=latent.dtype)
            o = mla_mod.mla_full_attention(qn, qr, seen, p, cfg,
                                           window=cfg.attn_window)
            out = linear(o.reshape(B, S, -1), p["wo"])
            return out, latent, None
        q = linear(x, p["wq"], p.get("bq")).reshape(B, S, H, D)
        k = linear(x, p["wk"], p.get("bk")).reshape(B, S, Hkv, D)
        v = linear(x, p["wv"], p.get("bv")).reshape(B, S, Hkv, D)
        if cfg.qk_norm:
            q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
            k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
        if coopt.use_kernel:
            from repro.kernels import ops
            if coopt.opt_gqa or Hkv == H:
                o = ops.flash_prefill(q, k, v, window=cfg.attn_window)
            else:
                o = ops.flash_prefill(q, repeat_kv(k, H // Hkv),
                                      repeat_kv(v, H // Hkv),
                                      window=cfg.attn_window)
        elif coopt.opt_gqa or Hkv == H:
            o = causal_attention(q, k, v, window=cfg.attn_window)
        else:  # Original: KV physically expanded per query head (Fig. 2)
            o = causal_attention(q, repeat_kv(k, H // Hkv),
                                 repeat_kv(v, H // Hkv), window=cfg.attn_window)
        return linear(o.reshape(B, S, H * D), p["wo"]), k, v

    def _attention_decode(self, p, x, kv_c, sc_c, layer, positions, new_len,
                          page_table, coopt, long_window: int):
        """One-token attention against layer ``layer`` of the GLOBAL paged
        pool of every layer (kv_c, sc_c; already containing the new token);
        page_table: (B, P_lane) physical pages in logical order. Returns
        projected output (B,1,d)."""
        cfg = self.cfg
        B = x.shape[0]
        window = cfg.attn_window or long_window
        if cfg.family == "mla":
            qn, qr, _lat = None, None, None
            H = cfg.num_heads
            dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
            q = linear(x, p["wq"]).reshape(B, 1, H, dn + dr)
            qn, qr = q[..., :dn], q[..., dn:]
            qr = mla_mod.mla_rope(qr, positions, cfg)
            o = mla_mod.mla_paged_decode(
                qn[:, 0], qr[:, 0], kv_c, sc_c, layer,
                new_len, p, cfg, coopt, window=window,
                sink_pages=cfg.sink_blocks, page_table=page_table)
            return linear(o.reshape(B, 1, -1), p["wo"])
        H, Hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        q = linear(x, p["wq"], p.get("bq")).reshape(B, 1, H, D)
        if cfg.qk_norm:
            q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        q = apply_rope(q, positions, cfg.rope_theta)
        o = paged_decode_attention(
            q[:, 0], kv_c, sc_c, layer, new_len,
            coopt=coopt, window=window, sink_pages=cfg.sink_blocks,
            page_table=page_table)
        return linear(o.reshape(B, 1, H * D), p["wo"])

    def _new_kv(self, p, x, positions):
        """Per-token cache entries (decode token or prefill chunk). Returns
        (k, v) or (latent, None) for MLA. Shapes (B,S,Hkv,D) / (B,S,R+dr)."""
        cfg = self.cfg
        B, S, _ = x.shape
        if cfg.family == "mla":
            _, _, latent = mla_mod.mla_project(x, p, cfg, positions)
            return latent, None
        Hkv, D = cfg.num_kv_heads, cfg.head_dim
        k = linear(x, p["wk"], p.get("bk")).reshape(B, S, Hkv, D)
        v = linear(x, p["wv"], p.get("bv")).reshape(B, S, Hkv, D)
        if cfg.qk_norm:
            k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
        k = apply_rope(k, positions, cfg.rope_theta)
        return k, v

    def _ffn(self, p, x, kind, valid=None, serving: bool = False):
        """valid: (B,S) bool of the real tokens (the MoE routes only
        those); None = every token. Under ``_scan_with_cache`` the experts
        come stacked for the segment with ``p["expert_layer"]`` the layer's
        index in it (``EXPERT_LEAVES``). ``serving`` steps run the experts
        through the grouped kernel, the training forward through its
        differentiable reference."""
        cfg = self.cfg
        if kind == "dense":
            return swiglu(x, p["wg"], p["wu"], p["wd"]), None
        shared = ((p["wg_s"], p["wu_s"], p["wd_s"])
                  if cfg.num_shared_experts else None)
        return moe_ffn(x, p["wr"], p["wg_e"], p["wu_e"], p["wd_e"],
                       top_k=cfg.top_k, norm_topk_prob=cfg.norm_topk_prob,
                       layer=p.get("expert_layer"), valid=valid,
                       shared=shared, kernel=serving)

    @staticmethod
    def _layer_counters(aux):
        """(2,) f32 of one layer: the most tokens one expert received and
        the experts that received any (zeros for a dense layer)."""
        if aux is None:
            return no_counters()
        return jnp.stack([aux.expert_tokens_max,
                          aux.experts_hit]).astype(jnp.float32)

    # ------------------------------------------------------------- forward --
    def _embed(self, params, batch):
        """Token (+ modality-stub) embedding. Returns (h, text_offset)."""
        cfg = self.cfg
        h = params["embed"][batch["tokens"]].astype(jnp.bfloat16)
        off = 0
        if cfg.family == "vlm" and "patches" in batch:
            h = jnp.concatenate(
                [batch["patches"].astype(jnp.bfloat16), h], axis=1)
            off = cfg.num_patches
        return h, off

    def forward(self, params, batch, coopt: CoOptConfig = COOPT):
        """Teacher-forced logits aligned with batch['labels'] (see
        input_specs): dense -> (B,S,V); vlm -> (B,S_text,V)."""
        cfg = self.cfg
        h, off = self._embed(params, batch)
        B, S, _ = h.shape
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        h = shard_act(h, ("batch", "seq", None))
        auxes = []
        for seg_params, (count, kind) in zip(params["segments"],
                                             self._segments()):
            def body(carry, pl, kind=kind):
                hh = carry
                a, _, _ = self._attention_full(pl, rmsnorm(hh, pl["ln1"],
                                                           cfg.norm_eps),
                                               positions, coopt)
                hh = hh + a
                f, aux = self._ffn(pl, rmsnorm(hh, pl["ln2"], cfg.norm_eps),
                                   kind)
                hh = shard_act(hh + f, ("batch", "seq", None))
                aux_v = (jnp.zeros(2, jnp.float32) if aux is None
                         else jnp.stack([aux.load_balance_loss,
                                         aux.router_z_loss]))
                return hh, aux_v
            body = jax.checkpoint(body)
            h, aux = jax.lax.scan(body, h, seg_params)
            auxes.append(aux)
        h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
        if off:
            # same convention as dense: logits[i] predicts text token i+1
            h = h[:, off:]
        logits = linear(h, params["lm_head"])
        aux = jnp.sum(jnp.concatenate(auxes, 0), axis=0)
        return logits, {"load_balance": aux[0], "router_z": aux[1]}

    # ------------------------------------------------------------ caching --
    def cache_shape(self, batch: int, max_len: int, coopt: CoOptConfig,
                    num_shards: int = 1, cache_cfg=None):
        """Dict of (shape, dtype, logical axes) — consumed by launch/dryrun
        for ShapeDtypeStructs + shardings, and by init_cache.

        GLOBAL-POOL layout: kv/scale leaves carry no batch dimension — the
        pool holds ``batch * pages(max_len)`` pages shared by every lane
        (refcounted + prefix-cached by the host-side BlockManager), padded
        up so the pages axis tiles evenly over ``num_shards`` mesh shards
        (CACHE_RULES: pages -> (pod, data)). A ``CacheConfig`` overrides
        the pool size / page size / shard count (opt_kv.pool_layout is the
        shared sizing rule). Direct callers fall back to the static
        lane-identity partition; the engine reserves the final page.
        ``length`` stays per-lane."""
        cfg = self.cfg
        P, ps = pool_layout(batch, max_len, coopt, num_shards, cache_cfg)
        out: Dict[str, Any] = {}
        if cfg.family == "mla":
            width = cfg.kv_lora_rank + cfg.qk_rope_head_dim
            out["kv"] = ((cfg.num_layers, P, ps, width),
                         coopt.kv_dtype,
                         ("layers", "pages", None, "latent"))
            if coopt.opt_kv:
                # two scales per token: c_kv and k_rope magnitudes differ,
                # a shared scale would crush the smaller segment's mantissa
                out["scale"] = ((cfg.num_layers, P, ps, 2),
                                jnp.float32,
                                ("layers", "pages", None, None))
        else:
            out.update(kv_pool_shapes(cfg.num_layers, batch, max_len,
                                      cfg.num_kv_heads, cfg.head_dim, coopt,
                                      num_shards, cache_cfg))
        out["length"] = ((batch,), jnp.int32, ("batch",))
        return out

    def init_cache(self, batch: int, max_len: int, coopt: CoOptConfig,
                   num_shards: int = 1, cache_cfg=None):
        return {k: jnp.zeros(sh, dt)
                for k, (sh, dt, _) in
                self.cache_shape(batch, max_len, coopt,
                                 num_shards=num_shards,
                                 cache_cfg=cache_cfg).items()}

    def _write_layer(self, kv_c, sc_c, layer, new_a, new_b, slots, coopt):
        """Write layer ``layer``'s cache entries (GLOBAL flat slots; -1 =
        SkipSet drop) into the pool of every layer, addressed as lines of
        the whole pool (``opt_kv.pool_lines``). MLA: new_a=(B,S,R+dr),
        kv_c=(L,P,ps,R+dr)."""
        if self.cfg.family == "mla":
            # ops dispatch: shard-local scatter under a mesh ctx, the
            # identical jnp scatter otherwise (ONE write implementation)
            from repro.kernels import ops
            _, P, ps, _ = kv_c.shape
            return ops.latent_pool_write(
                kv_c, sc_c, new_a, pool_lines(slots, layer, P, ps),
                opt_kv=coopt.opt_kv, lora_rank=self.cfg.kv_lora_rank)
        P, ps = kv_c.shape[2], kv_c.shape[4]
        return write_kv(kv_c, sc_c, new_a, new_b,
                        pool_lines(slots, layer, P, ps), coopt)

    def _scan_with_cache(self, params, cache, h, new_len, coopt, step_fn):
        """Scan the layers with the WHOLE pool in the carry: ``cache["kv"]``
        (L,2,P,Hkv,ps,D) and ``cache["scale"]`` (L,2,P,Hkv,ps) (MLA:
        (L,P,ps,R+dr) / (L,P,ps,2)) are never sliced per layer nor restacked.
        ``step_fn(h, layer_params, kv, sc, layer, kind)`` gets the full pool
        and the traced global layer index (``start + i`` across segments),
        and returns ``(h, kv, sc, counters)``: the kernels address the layer
        in place and the write updates the carried pool in place;
        ``counters`` is the layer's ``_layer_counters``. ``new_len`` (B,) is
        the per-lane token count after this step — supplied by the engine
        (global slots carry no length info). Returns ``(h, cache,
        counters)``, the step's (2,) f32 counters: the most tokens one
        expert received in any MoE layer, and the mean over MoE layers of
        the experts that received any (zeros without MoE layers)."""
        start = 0
        kv, sc = cache["kv"], (cache["scale"] if coopt.opt_kv else None)
        moe = []
        for seg_params, (count, kind) in zip(params["segments"],
                                             self._segments()):
            # the experts stay whole: the kernel picks the layer in place
            whole = {k: seg_params[k] for k in EXPERT_LEAVES
                     if k in seg_params}
            scanned = {k: v for k, v in seg_params.items() if k not in whole}

            def body(carry, xs, kind=kind, whole=whole):
                hh, kv, sc = carry
                pl, layer, i = xs
                if whole:
                    pl = dict(pl, expert_layer=i, **whole)
                hh, kv, sc, ctr = step_fn(hh, pl, kv, sc, layer, kind)
                return (hh, kv, sc), ctr

            local = jnp.arange(count, dtype=jnp.int32)
            (h, kv, sc), ctr = jax.lax.scan(body, (h, kv, sc),
                                            (scanned, start + local, local))
            if kind == "moe":
                moe.append(ctr)
            start += count
        cache = dict(cache)
        cache["kv"] = kv
        if coopt.opt_kv:
            cache["scale"] = sc
        cache["length"] = new_len
        if not moe:
            return h, cache, jnp.zeros(2, jnp.float32)
        ctr = jnp.concatenate(moe, axis=0)
        return h, cache, jnp.stack([jnp.max(ctr[:, 0]), jnp.mean(ctr[:, 1])])

    def _attention_chunk(self, p, x, positions, kv_c, sc_c, layer,
                         page_table, coopt, long_window: int = 0, seg_q=None,
                         page_seg=None, page_base=None):
        """Prefill-continuation attention (chunked prefill / mixed step):
        the chunk's K/V are already written to layer ``layer`` of the
        GLOBAL paged cache of every layer (kv_c, sc_c);
        queries attend over the lane's WHOLE cache (prefix-cache hits +
        previous chunks + this one) through its page table with true
        positions — see ``core.opt_pa.paged_chunk_attention``. Supports
        PER-LANE query positions (the token-budget scheduler mixes decode
        lanes, chunk length 1, with prefill-chunk lanes in one call). MLA
        runs the matrix-absorption form against the latent pool. The
        ``long_window`` block-sparse policy matches ``_attention_decode``,
        so a token's logits are step-composition independent."""
        cfg = self.cfg
        B, S, _ = x.shape
        window = cfg.attn_window or long_window
        if cfg.family == "mla":
            H = cfg.num_heads
            dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
            q = linear(x, p["wq"]).reshape(B, S, H, dn + dr)
            qn, qr = q[..., :dn], q[..., dn:]
            qr = mla_mod.mla_rope(qr, positions, cfg)
            o = mla_mod.mla_chunk_attention(
                qn, qr, kv_c, sc_c, layer, positions, page_table, p, cfg,
                coopt, window=window, sink_pages=cfg.sink_blocks,
                seg_q=seg_q, page_seg=page_seg, page_base=page_base)
            return linear(o.reshape(B, S, -1), p["wo"])
        H, Hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        q = linear(x, p["wq"], p.get("bq")).reshape(B, S, H, D)
        if cfg.qk_norm:
            q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        q = apply_rope(q, positions, cfg.rope_theta)
        o = paged_chunk_attention(q, kv_c, sc_c, layer, positions,
                                  page_table, coopt, window=window,
                                  sink_pages=cfg.sink_blocks, seg_q=seg_q,
                                  page_seg=page_seg, page_base=page_base)
        return linear(o.reshape(B, S, H * D).astype(x.dtype), p["wo"])

    def _pool_defaults(self, cache, batch, B):
        """(page_table, total_pages) — batch-provided or lane-identity."""
        axis = 1 if self.cfg.family == "mla" else 2
        P_total = cache["kv"].shape[axis]
        pt = batch.get("page_table")
        if pt is None:
            pt = identity_page_table(B, P_total)
        return pt.astype(jnp.int32), P_total

    def prefill(self, params, batch, cache, coopt: CoOptConfig = COOPT,
                long_window: int = 0):
        """Full-prompt forward + cache population. Returns
        (last-token logits (B,V), cache, the step's (2,) f32 expert
        counters (``_scan_with_cache``)). Only the tokens
        ``batch["pad_mask"]`` marks (all without one) are routed to
        experts.

        Chunked-prefill continuation (Sarathi-style / mixed decode+prefill
        step — the engine's ONE ragged step path): pass
        ``batch["positions"]`` (B, S) with each lane's absolute positions
        plus matching GLOBAL ``slot_idx``, the lane ``page_table`` and the
        post-step ``cache_len``; attention then runs over the whole cached
        history so chunk k+1 sees chunks 0..k — and a decode lane is just a
        chunk of length 1. All transformer families: dense/moe/vlm via
        ``paged_chunk_attention``, MLA via the absorbed latent form. For vlm,
        token column j IS position ``positions[:, j]``: columns whose
        position falls inside the patch-stub prefix take their embedding
        from ``batch["patches"]`` instead of the token table."""
        cfg = self.cfg
        chunked = "positions" in batch
        if chunked:
            positions = batch["positions"].astype(jnp.int32)
            h = params["embed"][batch["tokens"]].astype(jnp.bfloat16)
            off = cfg.num_patches if cfg.family == "vlm" else 0
            if off and "patches" in batch:
                pidx = jnp.clip(positions, 0, off - 1)
                pe = jnp.take_along_axis(
                    batch["patches"].astype(jnp.bfloat16),
                    pidx[..., None], axis=1)
                h = jnp.where((positions < off)[..., None], pe, h)
            B, S, _ = h.shape
        else:
            h, off = self._embed(params, batch)
            B, S, _ = h.shape
            positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        h = shard_act(h, ("batch", "seq", None))
        page_table, P_total = self._pool_defaults(cache, batch, B)
        if "slot_idx" in batch:
            slots = batch["slot_idx"].astype(jnp.int32)
        else:
            slots = identity_slots(B, positions, P_total, coopt.page_size)
        new_len = batch.get("cache_len")
        if new_len is None:
            new_len = jnp.maximum(cache["length"],
                                  jnp.max(positions, axis=1) + 1)
        new_len = new_len.astype(jnp.int32)
        seg_q = batch.get("seg_q")
        page_seg = batch.get("page_seg")
        page_base = batch.get("page_base")
        valid = batch.get("pad_mask")

        def step(hh, pl, kv_c, sc_c, layer, kind):
            x = rmsnorm(hh, pl["ln1"], cfg.norm_eps)
            if chunked:
                new_a, new_b = self._new_kv(pl, x, positions)
                kv_c, sc_c = self._write_layer(kv_c, sc_c, layer, new_a,
                                               new_b, slots, coopt)
                a = self._attention_chunk(pl, x, positions, kv_c, sc_c,
                                          layer, page_table, coopt,
                                          long_window, seg_q=seg_q,
                                          page_seg=page_seg,
                                          page_base=page_base)
            else:
                a, new_a, new_b = self._attention_full(pl, x, positions,
                                                       coopt, as_cached=True)
                kv_c, sc_c = self._write_layer(kv_c, sc_c, layer, new_a,
                                               new_b, slots, coopt)
            hh = hh + a
            f, aux = self._ffn(pl, rmsnorm(hh, pl["ln2"], cfg.norm_eps),
                               kind, valid=valid, serving=True)
            return (shard_act(hh + f, ("batch", "seq", None)), kv_c, sc_c,
                    self._layer_counters(aux))

        h, cache, ctr = self._scan_with_cache(params, cache, h, new_len,
                                              coopt, step)
        h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
        last = batch.get("last_pos", jnp.full((B,), S - 1, jnp.int32))
        if last.ndim == 2:
            # packed rows sample SEVERAL columns per row (one per finished
            # segment): last (B, G) -> logits (B, G, V)
            h_last = jnp.take_along_axis(h, last[..., None], axis=1)
        else:
            h_last = jnp.take_along_axis(h, last[:, None, None],
                                         axis=1)[:, 0]
        logits = linear(h_last, params["lm_head"])
        return logits, cache, ctr

    def decode_step(self, params, batch, cache, coopt: CoOptConfig = COOPT,
                    long_window: int = 0):
        """ONE token (B,1) against the paged cache. Returns (logits (B,V),
        cache, the step's expert counters) as ``prefill``. The engine supplies ``positions``/``slot_idx``/
        ``page_table``/``cache_len``; direct callers fall back to the
        per-lane ``length`` leaf and the lane-identity pool partition. A
        lane whose ``cache_len`` is 0 is idle: its token is not routed to
        experts."""
        cfg = self.cfg
        h = params["embed"][batch["token"]].astype(jnp.bfloat16)  # (B,1,d)
        B = h.shape[0]
        positions = batch.get("positions")
        if positions is None:
            positions = cache["length"][:, None]                   # (B,1)
        positions = positions.astype(jnp.int32)
        page_table, P_total = self._pool_defaults(cache, batch, B)
        if "slot_idx" in batch:
            slots = batch["slot_idx"].astype(jnp.int32)
        else:
            slots = identity_slots(B, positions, P_total, coopt.page_size)
        new_len = batch.get("cache_len")
        if new_len is None:
            new_len = cache["length"] + 1
        new_len = new_len.astype(jnp.int32)

        def step(hh, pl, kv_c, sc_c, layer, kind):
            x = rmsnorm(hh, pl["ln1"], cfg.norm_eps)
            new_a, new_b = self._new_kv(pl, x, positions)
            kv_c, sc_c = self._write_layer(kv_c, sc_c, layer, new_a, new_b,
                                           slots, coopt)
            a = self._attention_decode(pl, x, kv_c, sc_c, layer, positions,
                                       new_len, page_table, coopt,
                                       long_window)
            hh = hh + a
            f, aux = self._ffn(pl, rmsnorm(hh, pl["ln2"], cfg.norm_eps),
                               kind, valid=(new_len > 0)[:, None],
                               serving=True)
            return hh + f, kv_c, sc_c, self._layer_counters(aux)

        h, cache, ctr = self._scan_with_cache(params, cache, h, new_len,
                                              coopt, step)
        h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
        logits = linear(h[:, 0], params["lm_head"])
        return logits, cache, ctr

    # -------------------------------------------------------------- specs --
    def input_specs(self, shape) -> Dict[str, jax.ShapeDtypeStruct]:
        cfg = self.cfg
        B, S = shape.global_batch, shape.seq_len
        tok = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
        if shape.kind == "decode":
            return {"token": tok(B, 1)}
        st = S - cfg.num_patches if cfg.family == "vlm" else S
        out = {"tokens": tok(B, st)}
        if cfg.family == "vlm":
            out["patches"] = jax.ShapeDtypeStruct(
                (B, cfg.num_patches, cfg.d_model), jnp.bfloat16)
        if shape.kind == "train":
            out["labels"] = tok(B, st)
        return out

    # --------------------------------------------------------------- misc --
    def param_count(self) -> int:
        from repro.models.layers import param_count
        return param_count(self.param_specs())

    def active_param_count(self) -> int:
        cfg = self.cfg
        total = self.param_count()
        if not cfg.num_experts:
            return total
        per_layer = 3 * cfg.d_model * cfg.moe_d_ff
        moe_layers = cfg.num_layers - cfg.first_dense_layers
        return total - per_layer * (cfg.num_experts - cfg.top_k) * moe_layers
