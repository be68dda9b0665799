"""Mixture-of-Experts FFN (mixtral, deepseek-v2): dropless, sorted by expert.

Every real token of the step is routed over all E experts by the published
rule (``_moe_route``): p = softmax(x W_r) in float32, the top k by p, and
as weights those p as they are (``norm_topk_prob`` False, DeepSeek-V2) or
divided by their sum (True, Mixtral). Padded token slots (``valid`` False)
are not routed. The step's (token, expert) pairs are sorted by expert
across all rows into one buffer in which each expert's pairs start on a
tile of ``tm`` rows, and one grouped SwiGLU runs over it (``_moe_experts``,
the Pallas kernel ``kernels/moe_experts.py``, in serving steps;
``jax.lax.ragged_dot`` in the training forward). Each output row is then gathered back
to its (token, k) slot, weighted and summed over k. FLOPs scale with the k
pairs of each real token, an expert that is hit streams its weights once
per tile, and nothing is dropped at any chunk length.

On a mesh whose batch axes shard the tokens (an ``activation_sharding``
context, as training and the mesh step programs set) each batch shard
sorts and runs its own tokens' pairs inside a ``shard_map``: a sort over
the whole batch would gather every shard's tokens to every device and
all-reduce the whole buffer each layer. There the experts' ff dimension
stays on ``model`` as the weights hold it, and the partial
down-projections are summed over it.

Aux outputs: the load-balance loss and router z-loss over the routed
tokens (train_step), and two counters of the routing, read by the serving
step log: the most pairs any expert received, and how many experts
received any. They are computed from routing sums (``RouteStats``) that
add over batch shards, so the per-shard path gives those of the whole
batch.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.moe_experts import moe_experts_ref
from repro.models.layers import act_mesh_axes, linear


class MoEAux(NamedTuple):
    load_balance_loss: jax.Array
    router_z_loss: jax.Array
    expert_tokens_max: jax.Array    # most pairs routed to one expert
    experts_hit: jax.Array          # experts that received any pair


class RouteStats(NamedTuple):
    """Sums over the routed tokens, from which ``MoEAux`` follows."""
    counts: jax.Array        # (E,) int32 pairs routed to each expert
    prob_sum: jax.Array      # (E,) f32 router probabilities summed
    z_sum: jax.Array         # () f32 squared router logsumexp summed
    tokens: jax.Array        # () f32 routed tokens


def _aux(st: RouteStats) -> MoEAux:
    """Switch-style load-balance loss and router z-loss, and the counters."""
    E = st.counts.shape[0]
    n = jnp.maximum(st.tokens, 1.0)
    frac_tokens = st.counts.astype(jnp.float32) / n
    lb = E * jnp.sum(frac_tokens * st.prob_sum / n)
    return MoEAux(lb, st.z_sum / n, jnp.max(st.counts),
                  jnp.sum(st.counts > 0))


class Dispatch(NamedTuple):
    """Where each (token, k) pair goes in the buffer sorted by expert."""
    weights: jax.Array       # (T, K) f32 router weight of each pair
    dest: jax.Array          # (T, K) int32 buffer row; M for a pad token
    src: jax.Array           # (M,) int32 token of each buffer row
    tile_expert: jax.Array   # (M // tm,) int32 expert of each tile
    num_tiles: jax.Array     # (1,) int32 tiles that carry pairs
    group_sizes: jax.Array   # (E,) int32 rows each expert occupies


def tile_rows(pairs: int) -> int:
    """Rows per tile of the sorted buffer: 16 while a step carries few
    pairs (decode: each expert that is hit fills one tile), 128 for
    prefill steps, whose experts see tens of tokens each."""
    return 16 if pairs <= 2048 else 128


def buffer_rows(pairs: int, experts: int, tm: int) -> int:
    """Rows of the sorted buffer: every pair, plus up to ``tm - 1`` rows
    of padding per expert to start the next one on a tile."""
    return -(-(pairs + experts * (tm - 1)) // tm) * tm


@partial(jax.jit, static_argnames=("top_k", "norm_topk_prob", "tm"))
def _moe_route(x, wr, valid, *, top_k: int, norm_topk_prob: bool, tm: int):
    """x (T, d) tokens, wr (d, E), valid (T,) bool. Returns (Dispatch,
    RouteStats)."""
    T = x.shape[0]
    E = wr.shape[-1]
    P = T * top_k
    M = buffer_rows(P, E, tm)
    logits = jnp.dot(x, wr, preferred_element_type=jnp.float32)   # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, top_k)                    # (T, K)
    if norm_topk_prob:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

    # pairs of pad tokens go to expert E, which sorts last and is not run
    e = jnp.where(valid[:, None], top_e, E).reshape(P).astype(jnp.int32)
    counts = jnp.zeros(E + 1, jnp.int32).at[e].add(1)[:E]
    rows = -(-counts // tm) * tm                       # padded to tiles
    start = jnp.cumsum(rows) - rows
    order = jnp.argsort(e, stable=True)
    se = e[order]
    sec = jnp.minimum(se, E - 1)
    rank = jnp.arange(P, dtype=jnp.int32) - (jnp.cumsum(counts) - counts)[sec]
    dest = jnp.zeros(P, jnp.int32).at[order].set(
        jnp.where(se < E, start[sec] + rank, M))
    src = jnp.zeros(M, jnp.int32).at[dest].set(
        jnp.arange(P, dtype=jnp.int32) // top_k, mode="drop")
    tiles = jnp.arange(M // tm, dtype=jnp.int32) * tm
    tile_expert = jnp.minimum(
        jnp.searchsorted(jnp.cumsum(rows), tiles, side="right"), E - 1)
    plan = Dispatch(weights=top_p, dest=dest.reshape(T, top_k), src=src,
                    tile_expert=tile_expert.astype(jnp.int32),
                    num_tiles=(jnp.sum(rows) // tm).reshape(1),
                    group_sizes=rows)

    vf = valid.astype(jnp.float32)
    st = RouteStats(
        counts, jnp.sum(probs * vf[:, None], axis=0),
        jnp.sum(jnp.square(jax.nn.logsumexp(logits, axis=-1)) * vf),
        jnp.sum(vf))
    return plan, st


def _routed(xf, wr, wg, wu, wd, layer, valid, *, top_k: int,
            norm_topk_prob: bool, kernel: bool, out_dtype=None):
    """Route the real tokens of xf (T, d) over wr's experts and run their
    pairs: (the weighted sum over each token's k pairs (T, d) f32,
    RouteStats)."""
    tm = tile_rows(xf.shape[0] * top_k)
    plan, st = _moe_route(xf, wr, valid, top_k=top_k,
                          norm_topk_prob=norm_topk_prob, tm=tm)
    xs = xf[plan.src]                                      # (M, d)
    if kernel:
        from repro.kernels import ops
        ys = ops.moe_experts(xs, wg, wu, wd, layer, plan.tile_expert,
                             plan.num_tiles, plan.group_sizes, tm=tm)
    else:
        ys = moe_experts_ref(xs, wg, wu, wd, layer, plan.group_sizes,
                             out_dtype=out_dtype)
    M = ys.shape[0]
    routed = plan.dest < M                                 # (T, K)
    y = ys[jnp.minimum(plan.dest, M - 1)].astype(jnp.float32)
    # select, not multiply: rows past the real tiles are never written
    y = jnp.where(routed[..., None], y * plan.weights[..., None], 0.0)
    return jnp.sum(y, axis=1), st


def _routed_per_shard(xf, wr, wg, wu, wd, layer, valid, mesh, batch_axes,
                      *, top_k: int, norm_topk_prob: bool):
    """``_routed`` with each batch shard's tokens sorted and run on their
    own shard (``batch_axes`` of ``mesh`` shard the T axis). Layer
    ``layer``'s experts are taken out of the stack first, where they lie,
    so only they are gathered. Their ff dimension is split over ``model``
    where it divides: each device then holds its slice of every expert,
    and the float32 partial down-projections of a shard's buffer are
    summed over ``model``."""
    wg, wu, wd = (w[layer][None] for w in (wg, wu, wd))
    ff = wg.shape[-1]
    mdl = ("model" if "model" in mesh.shape and "model" not in batch_axes
           and ff % mesh.shape["model"] == 0 else None)
    rows = P(batch_axes, None)

    def body(x, v, wr, wg, wu, wd):
        y, st = _routed(x, wr, wg, wu, wd, 0, v, top_k=top_k,
                        norm_topk_prob=norm_topk_prob, kernel=False,
                        out_dtype=jnp.float32)
        if mdl:
            y = jax.lax.psum(y, mdl)
        return y, jax.tree.map(lambda a: jax.lax.psum(a, batch_axes), st)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(rows, P(batch_axes), P(), P(None, None, None, mdl),
                  P(None, None, None, mdl), P(None, None, mdl, None)),
        out_specs=(rows, RouteStats(P(), P(), P(), P())), check_vma=False,
    )(xf, valid, wr, wg, wu, wd)


def moe_ffn(x, wr, wg, wu, wd, *, top_k: int, norm_topk_prob: bool = True,
            layer=None, valid=None, shared: Optional[tuple] = None,
            kernel: bool = False):
    """x (..., d); wr (d,E); wg/wu (E,d,ff); wd (E,ff,d), or with ``layer``
    (an int32 scalar) the experts of every layer of a segment stacked,
    (L,E,d,ff) / (L,E,ff,d), of which layer ``layer`` runs (no layer's
    experts are sliced out); valid (...) bool, the real tokens (default:
    all).

    shared: optional (wg_s, wu_s, wd_s) always-on shared-expert SwiGLU,
    added unweighted. ``kernel``: the Pallas grouped kernel runs the experts
    (``kernels.ops.moe_experts``: compiled on a TPU, interpreted elsewhere),
    as serving steps ask; otherwise its ``ragged_dot`` reference, which
    autodiff can differentiate (the training forward), as on a mesh that
    shards the batch (``_routed_per_shard``). Returns (out (..., d),
    MoEAux).
    """
    if layer is None:
        wg, wu, wd, layer = wg[None], wu[None], wd[None], 0
    shape = x.shape
    d = shape[-1]
    xf = x.reshape(-1, d)
    T = xf.shape[0]
    valid = (jnp.ones(T, bool) if valid is None
             else valid.reshape(T).astype(bool))
    part = act_mesh_axes("batch", shape[0])
    if part is None:
        out, st = _routed(xf, wr, wg, wu, wd, layer, valid, top_k=top_k,
                          norm_topk_prob=norm_topk_prob, kernel=kernel)
    else:
        out, st = _routed_per_shard(xf, wr, wg, wu, wd, layer, valid, *part,
                                    top_k=top_k,
                                    norm_topk_prob=norm_topk_prob)
    out = out.reshape(shape)
    if shared is not None:
        wg_s, wu_s, wd_s = shared
        out = out + linear(jax.nn.silu(linear(x, wg_s)) * linear(x, wu_s),
                           wd_s).astype(jnp.float32)
    return out.astype(x.dtype), _aux(st)
