"""RWKV-6 "Finch" — attention-free, data-dependent decay [arXiv:2404.05892].

LLM-CoOpt's three techniques are inapplicable here (no KV cache to quantize
or page, no query heads to group) — see DESIGN.md §5. The model is implemented
WITHOUT the technique, per the task instructions, but still first-class in the
framework: paged-cache plumbing is replaced by an O(1) recurrent state pytree
(per-layer (B, H, D, D) wkv state + (B, d) token-shift buffers), so
``prefill``/``decode_step`` have the same engine-facing signature as the
attention families.

Recurrence (per head, head_dim D, diag decay w_t in (0,1)):
    S_t = diag(w_t) S_{t-1} + k_t v_t^T          (state (D, D))
    o_t = (u ⊙ k_t) (q_t · v_t accumulation) ... realised as
    o_t = q_t^T (S_{t-1} + diag(u) k_t v_t^T)
where w_t = exp(-exp(ww_t)) is *data-dependent* (the Finch contribution) via
the low-rank "time-mix" MLP, and u is the per-head bonus for the current token.

Training/prefill uses a chunked scan: within a chunk the contribution of the
running state is a matmul, and the intra-chunk part is a masked quadratic form
— the standard linear-attention chunked form, O(T/C · C² + T·D²).
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.coopt import CoOptConfig, COOPT
from repro.models.layers import (Spec, init_tree, linear, no_counters,
                                 rmsnorm, shard_act)

_LORA = 64        # low-rank dim of the data-dependent decay MLP
_CHUNK = 32       # chunked-scan length — bounds the (C,C,H,D) pairwise-decay
                  # tensor of the intra-chunk term (exact, clamp-free)


class RWKV6Model:
    # batch-major cache leaves carrying cross-chunk recurrent state: the
    # engine zeroes them on a request's first chunk and snapshots them at
    # committed page boundaries (prefix-cache resume points)
    recurrent_leaves = ("wkv", "shift_t", "shift_c")

    def __init__(self, cfg: ModelConfig):
        assert cfg.family == "rwkv6"
        self.cfg = cfg

    # ------------------------------------------------------------- params --
    def param_specs(self):
        cfg = self.cfg
        L, d, H, D = cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.head_dim
        lay = {
            "ln1": Spec((L, d), ("layers", None), "ones", jnp.float32),
            "ln2": Spec((L, d), ("layers", None), "ones", jnp.float32),
            # token-shift mix coefficients (r,k,v,w,g) — Finch "ddlerp" base
            "mix": Spec((L, 5, d), ("layers", None, None), "uniform1",
                        jnp.float32),
            "wr": Spec((L, d, H * D), ("layers", "d_in", "d_out")),
            "wk": Spec((L, d, H * D), ("layers", "d_in", "d_out")),
            "wv": Spec((L, d, H * D), ("layers", "d_in", "d_out")),
            "wg": Spec((L, d, H * D), ("layers", "d_in", "d_out")),
            "wo": Spec((L, H * D, d), ("layers", "d_out", "d_in")),
            # data-dependent decay: w = base + B @ tanh(A @ x)
            "w_base": Spec((L, H * D), ("layers", "d_out"), "zeros",
                           jnp.float32),
            "dd_a": Spec((L, d, _LORA), ("layers", "d_in", None)),
            "dd_b": Spec((L, _LORA, H * D), ("layers", None, "d_out")),
            "u": Spec((L, H, D), ("layers", None, None), "zeros", jnp.float32),
            "gn": Spec((L, H * D), ("layers", None), "ones", jnp.float32),
            # channel-mix (FFN): relu² k, sigmoid-gated
            "ck": Spec((L, d, cfg.d_ff), ("layers", "d_in", "d_out")),
            "cv": Spec((L, cfg.d_ff, d), ("layers", "d_out", "d_in")),
            "cr": Spec((L, d, d), ("layers", "d_in", "d_out")),
        }
        return {
            "embed": Spec((cfg.vocab_size, cfg.d_model), ("vocab", "d_out"),
                          "embed"),
            "layers": lay,
            "final_norm": Spec((cfg.d_model,), (None,), "ones", jnp.float32),
            "lm_head": Spec((cfg.d_model, cfg.vocab_size), ("d_in", "d_out")),
        }

    def init(self, key):
        return init_tree(key, self.param_specs())

    # ------------------------------------------------------- wkv recurrence --
    def _proj(self, pl, x, x_prev):
        """Token-shifted projections. x (B,S,d); x_prev (B,1,d) = token before
        x[0]. Returns r,k,v,g (B,S,H,D), w (B,S,H,D) decay in (0,1),
        and the new shift buffer (B,1,d)."""
        cfg = self.cfg
        B, S, d = x.shape
        H, D = cfg.num_heads, cfg.head_dim
        xs = jnp.concatenate([x_prev, x[:, :-1]], axis=1)     # shifted by 1
        mix = pl["mix"].astype(x.dtype)                        # (5,d)

        def mixed(i):
            return x + (xs - x) * mix[i]

        r = linear(mixed(0), pl["wr"]).reshape(B, S, H, D)
        k = linear(mixed(1), pl["wk"]).reshape(B, S, H, D)
        v = linear(mixed(2), pl["wv"]).reshape(B, S, H, D)
        g = linear(mixed(4), pl["wg"]).reshape(B, S, H, D)
        # data-dependent decay (Finch): per-token, per-channel
        ww = pl["w_base"].astype(jnp.float32) + \
            linear(jnp.tanh(linear(mixed(3), pl["dd_a"])),
                   pl["dd_b"]).astype(jnp.float32)
        w = jnp.exp(-jnp.exp(jnp.clip(ww, -20.0, 8.0))).reshape(B, S, H, D)
        return r, k, v, g, w, x[:, -1:]

    @staticmethod
    def _wkv_chunked(r, k, v, w, u, state):
        """Chunked linear-recurrence. r,k,v,w (B,S,H,D) f32; u (H,D);
        state (B,H,D,D). Returns (out (B,S,H,D), new state).

        Within a chunk: decay-weighted quadratic form + inherited-state matmul.
        """
        B, S, H, D = r.shape
        C = _CHUNK if S % _CHUNK == 0 else S
        N = S // C
        r = r.reshape(B, N, C, H, D)
        k = k.reshape(B, N, C, H, D)
        v = v.reshape(B, N, C, H, D)
        w = w.reshape(B, N, C, H, D)
        logw = jnp.log(jnp.maximum(w, 1e-20))

        def chunk(state, xs):
            rc, kc, vc, wc, lwc = xs                          # (B,C,H,D)
            cum = jnp.cumsum(lwc, axis=1)                     # prod of w up to t (incl)
            # decay from chunk start to just BEFORE t: cum_{t-1}. All
            # exponents used below are true non-positive log-decays, so exp
            # never overflows and underflow-to-zero is the exact limit — no
            # clamping (a clamp breaks RELATIVE decays between nearby tokens
            # once the cumulative passes it).
            before = cum - lwc                                # <= 0
            r_d = rc * jnp.exp(before)                        # r_t * W_{0..t-1}
            k_d = kc * jnp.exp(cum[:, -1:] - cum)             # <= 0 exponent
            # inter-chunk: state contribution
            inter = jnp.einsum("bchd,bhde->bche", r_d, state)
            # intra-chunk: pairwise decay computed DIRECTLY —
            # exponent(t, s) = cum_{t-1} - cum_s = sum_{s<u<t} logw_u <= 0
            # (k_s is decayed by w_{s+1}..w_{t-1}, same convention as the
            # sequential step). (B,C,C,H,D) is bounded by _CHUNK=32.
            pair = before[:, :, None] - cum[:, None, :]       # (B,C,C,H,D)
            att = jnp.einsum("bthd,bshd,btshd->bhts", rc, kc,
                             jnp.exp(jnp.minimum(pair, 0.0)))
            tri = jnp.tril(jnp.ones((C, C)), -1)
            att = att * tri[None, None]
            intra = jnp.einsum("bhts,bshd->bthd", att, vc)
            # current-token bonus u
            bonus = jnp.einsum("bchd,bchd->bch", rc, u[None, None] * kc)
            out = inter + intra + bonus[..., None] * vc
            # state update
            new_state = state * jnp.exp(cum[:, -1])[..., None] + \
                jnp.einsum("bchd,bche->bhde", k_d, vc)
            return new_state, out

        # nested remat: without it the backward stashes the (B,C,C,H,D)
        # pairwise-decay tensor for every chunk-scan trip (~17 GiB/dev on
        # train_4k); recomputing it per chunk is two cheap einsums
        state, out = jax.lax.scan(
            jax.checkpoint(chunk), state,
            (jnp.moveaxis(r, 1, 0), jnp.moveaxis(k, 1, 0),
             jnp.moveaxis(v, 1, 0), jnp.moveaxis(w, 1, 0),
             jnp.moveaxis(logw, 1, 0)))
        out = jnp.moveaxis(out, 0, 1).reshape(B, S, H, D)
        return out, state

    @staticmethod
    def _wkv_step(r, k, v, w, u, state):
        """One-token recurrence. r,k,v,w (B,H,D); state (B,H,D,D)."""
        kv = jnp.einsum("bhd,bhe->bhde", k, v)
        out = jnp.einsum("bhd,bhde->bhe", r, state + u[None, :, :, None] * kv)
        state = state * w[..., None] + kv
        return out, state

    def _time_mix(self, pl, x, shift, state, valid=None, last_pos=None):
        """x (B,S,d) -> (out, new_shift, new_state). ``valid`` (B,S) freezes
        the recurrence on padding tokens (w=1, k=0 — the state passes
        through untouched, exactly as if the token were never fed)."""
        cfg = self.cfg
        B, S, d = x.shape
        H, D = cfg.num_heads, cfg.head_dim
        r, k, v, g, w, new_shift = self._proj(pl, x, shift)
        if valid is not None:
            vmask = valid[:, :, None, None]
            w = jnp.where(vmask, w, 1.0)
            k = k * vmask.astype(k.dtype)
        if last_pos is not None:
            new_shift = jnp.take_along_axis(
                x, last_pos[:, None, None].astype(jnp.int32), axis=1)
        rf, kf, vf, wf = (t.astype(jnp.float32) for t in (r, k, v, w))
        u = pl["u"].astype(jnp.float32)
        if S == 1:
            o, state = self._wkv_step(rf[:, 0], kf[:, 0], vf[:, 0], wf[:, 0],
                                      u, state)
            o = o[:, None]
        else:
            o, state = self._wkv_chunked(rf, kf, vf, wf, u, state)
        # group-norm over each head then gate (Finch uses GroupNorm(H))
        o = o.reshape(B, S, H, D)
        mu = jnp.mean(o, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(o - mu), axis=-1, keepdims=True)
        o = (o - mu) * jax.lax.rsqrt(var + 1e-5)
        o = (o.reshape(B, S, H * D) * pl["gn"].astype(jnp.float32))
        o = (o.reshape(B, S, H, D) * jax.nn.silu(g.astype(jnp.float32)))
        out = linear(o.reshape(B, S, H * D).astype(x.dtype), pl["wo"])
        return out, new_shift, state

    def _channel_mix(self, pl, x, shift, last_pos=None):
        """relu²-keyed FFN with sigmoid receptance gate."""
        xs = jnp.concatenate([shift, x[:, :-1]], axis=1)
        mix = pl["mix"].astype(x.dtype)
        xk = x + (xs - x) * mix[1]
        xr = x + (xs - x) * mix[0]
        k = jnp.square(jax.nn.relu(linear(xk, pl["ck"])))
        new_shift = (x[:, -1:] if last_pos is None else jnp.take_along_axis(
            x, last_pos[:, None, None].astype(jnp.int32), axis=1))
        return jax.nn.sigmoid(linear(xr, pl["cr"])) * linear(k, pl["cv"]), \
            new_shift

    # ------------------------------------------------------------- forward --
    def _run(self, params, tokens, state, valid=None, last_pos=None):
        """Shared trunk. state = None (train: zeros, discarded) or pytree.
        Returns (h_final (B,S,d), new_state)."""
        cfg = self.cfg
        B, S = tokens.shape
        h = params["embed"][tokens].astype(jnp.bfloat16)
        h = shard_act(h, ("batch", "seq", None))
        if state is None:
            state = self.init_state(B)

        def body(carry, xs):
            hh = carry
            pl, wkv, sh_t, sh_c = xs
            x = rmsnorm(hh, pl["ln1"], cfg.norm_eps)
            a, sh_t, wkv = self._time_mix(pl, x, sh_t, wkv, valid, last_pos)
            hh = hh + a
            x = rmsnorm(hh, pl["ln2"], cfg.norm_eps)
            f, sh_c = self._channel_mix(pl, x, sh_c, last_pos)
            hh = shard_act(hh + f, ("batch", "seq", None))
            return hh, (wkv, sh_t, sh_c)

        body = jax.checkpoint(body) if S > 1 else body
        h, (wkv, sh_t, sh_c) = jax.lax.scan(
            body, h, (params["layers"], state["wkv"], state["shift_t"],
                      state["shift_c"]))
        added = S if valid is None else jnp.sum(valid, axis=1)
        new_state = {"wkv": wkv, "shift_t": sh_t, "shift_c": sh_c,
                     "length": (state["length"] + added).astype(jnp.int32)}
        return rmsnorm(h, params["final_norm"], cfg.norm_eps), new_state

    def forward(self, params, batch, coopt: CoOptConfig = COOPT):
        h, _ = self._run(params, batch["tokens"], None)
        return linear(h, params["lm_head"]), {}

    def prefill(self, params, batch, cache, coopt: CoOptConfig = COOPT,
                long_window: int = 0):
        """Prompt prefill / chunked continuation (the unified ragged step
        path): the state pytree in ``cache`` is the state after the previous
        chunk and threads straight through — paged-cache plumbing
        (positions/slots/page_table/long_window) is accepted and ignored."""
        valid = batch.get("pad_mask")
        last_pos = batch.get("last_pos")
        h, cache = self._run(params, batch["tokens"], cache, valid, last_pos)
        if "cache_len" in batch:
            cache["length"] = batch["cache_len"].astype(jnp.int32)
        if last_pos is not None:
            h_last = jnp.take_along_axis(
                h, last_pos[:, None, None].astype(jnp.int32), axis=1)[:, 0]
        else:
            h_last = h[:, -1]
        return linear(h_last, params["lm_head"]), cache, no_counters()

    def decode_step(self, params, batch, cache, coopt: CoOptConfig = COOPT,
                    long_window: int = 0):
        h, cache = self._run(params, batch["token"], cache)
        return linear(h[:, 0], params["lm_head"]), cache, no_counters()

    # ------------------------------------------------------------- caching --
    def cache_shape(self, batch: int, max_len: int, coopt: CoOptConfig,
                    num_shards: int = 1, cache_cfg=None):
        # attention-free: no paged KV pool, so ``num_shards`` / ``cache_cfg``
        # (accepted for engine-call uniformity) size nothing here
        cfg = self.cfg
        L, d, H, D = cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.head_dim
        return {
            "wkv": ((L, batch, H, D, D), jnp.float32,
                    ("layers", "batch", "heads", None, None)),
            "shift_t": ((L, batch, 1, d), jnp.bfloat16,
                        ("layers", "batch", None, "d_model")),
            "shift_c": ((L, batch, 1, d), jnp.bfloat16,
                        ("layers", "batch", None, "d_model")),
            "length": ((batch,), jnp.int32, ("batch",)),
        }

    def init_cache(self, batch: int, max_len: int, coopt: CoOptConfig,
                   num_shards: int = 1, cache_cfg=None):
        return {k: jnp.zeros(sh, dt)
                for k, (sh, dt, _) in
                self.cache_shape(batch, max_len, coopt).items()}

    def init_state(self, batch: int):
        return self.init_cache(batch, 0, COOPT)

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
