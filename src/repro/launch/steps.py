"""Step builders for the multi-pod dry-run and the real launchers.

For every (architecture x input shape) this module produces:
  * the pure step function  — train_step / prefill_step / serve_step,
  * abstract inputs         — ShapeDtypeStructs (no allocation),
  * in/out shardings        — NamedShardings from the logical-axis rules.

Sharding rules (DESIGN.md §7):
  weights      d_in -> data, d_out -> model, vocab -> data, experts -> data
  activations  batch -> (pod, data), seq -> model (sequence parallelism)
  cache        batch -> (pod, data); pages -> (pod, data) when batch is 1
               (long_500k); kv_heads/head_dim/latent/heads -> model
Any rule whose dim is not divisible by its mesh axes is dropped per-tensor
(handles kv=1 MQA, 56-head yi, whisper's odd vocab, 8-expert mixtral...).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS

from repro.configs import get_config, get_shape
from repro.configs.base import ModelConfig
from repro.configs.shapes import InputShape
from repro.core.coopt import CoOptConfig, COOPT
from repro.models import get_model
from repro.models.layers import (activation_sharding, make_shardings,
                                 shapes_tree)
from repro.training.train import loss_fn
from repro.training.optimizer import adamw_update, AdamWState

# block-sparse window for dense archs on long_500k (DESIGN.md §5)
LONG_WINDOW = 8192


class ShapeSkipped(Exception):
    """(arch x shape) cell excluded by DESIGN.md §5 (e.g. whisper long_500k)."""


# ---------------------------------------------------------------- rules ----
WEIGHT_RULES = {"d_in": "data", "d_out": "model", "vocab": "data",
                "experts": "data", "moe_d_in": "data"}
CACHE_RULES = {"batch": ("pod", "data"), "pages": ("pod", "data"),
               "kv_heads": "model", "head_dim": "model", "heads": "model",
               "latent": "model", "d_model": "model", "layers": None}
# kernel (shard_map) hot path: the pool leaves are partitioned ONLY along
# the pages axes — each shard streams its own page range through the
# unchanged Pallas kernels (kernels.sharded); heads/latent stay replicated
# on the pool (weights/activations keep their model parallelism), so no
# KV/latent bytes ever cross the interconnect.
KERNEL_CACHE_RULES = {"batch": ("pod", "data"), "pages": ("pod", "data")}
ACT_RULES_SEQ = {"batch": ("pod", "data"), "seq": "model", "ffn": "model",
                 "experts": None}
ACT_RULES_DECODE = {"batch": ("pod", "data"), "ffn": "model",
                    "latent": "model", "head_dim": "model"}
# serving keeps tensor-parallel-only weights: there is no optimizer state to
# shard away, so d_in -> data (ZeRO) would only add per-layer weight
# all-gathers to every decode step (§Perf P3.2)
WEIGHT_RULES_DECODE = {"d_in": None, "d_out": "model", "vocab": "model",
                       "experts": "data", "moe_d_in": "data"}


def axes_pspec(shape: Tuple[int, ...], axes, mesh: Mesh, rules) -> PS:
    """Logical axes -> PartitionSpec with divisibility + used-axis checks.
    Rule values may be a mesh axis name or a tuple of them."""
    entries, used = [], set()
    for dim, ax in zip(shape, axes):
        m = rules.get(ax) if ax else None
        if m is None:
            entries.append(None)
            continue
        ms = (m,) if isinstance(m, str) else tuple(m)
        ms = tuple(a for a in ms if a in mesh.shape and a not in used)
        size = math.prod(mesh.shape[a] for a in ms) if ms else 1
        if ms and dim % size == 0:
            entries.append(ms if len(ms) > 1 else ms[0])
            used.update(ms)
        else:
            entries.append(None)
    return PS(*entries)


def cache_shardings(model, batch: int, max_len: int, coopt: CoOptConfig,
                    mesh: Mesh, rules=CACHE_RULES, num_shards: int = 1):
    shapes = model.cache_shape(batch, max_len, coopt, num_shards=num_shards)
    return ({k: jax.ShapeDtypeStruct(sh, dt)
             for k, (sh, dt, _) in shapes.items()},
            {k: NamedSharding(mesh, axes_pspec(sh, ax, mesh, rules))
             for k, (sh, dt, ax) in shapes.items()})


def batch_shardings(specs: Dict[str, jax.ShapeDtypeStruct], mesh: Mesh):
    out = {}
    for k, s in specs.items():
        axes = ("batch",) + (None,) * (len(s.shape) - 1)
        out[k] = NamedSharding(
            mesh, axes_pspec(s.shape, axes, mesh,
                             {"batch": ("pod", "data")}))
    return out


# ---------------------------------------------------------------- steps ----
@dataclass
class StepBundle:
    kind: str                       # train | prefill | decode
    fn: Callable                    # pure step function
    args: Tuple[Any, ...]           # abstract ShapeDtypeStruct pytrees
    in_shardings: Tuple[Any, ...]
    out_shardings: Any
    cfg: ModelConfig
    shape: InputShape
    coopt: CoOptConfig
    long_window: int = 0

    def jitted(self):
        # donate the mutated state: train updates (params, opt), serving
        # updates the cache — halves the resident footprint of each
        donate = (0, 1) if self.kind == "train" else (2,)
        return jax.jit(self.fn, in_shardings=self.in_shardings,
                       out_shardings=self.out_shardings,
                       donate_argnums=donate)

    def lower(self):
        return self.jitted().lower(*self.args)


def effective_config(cfg: ModelConfig, shape: InputShape) -> ModelConfig:
    """Apply the long_500k policy (DESIGN.md §5)."""
    if shape.name != "long_500k":
        return cfg
    if cfg.family == "whisper":
        raise ShapeSkipped(
            "whisper-small x long_500k skipped: full-attention decoder, "
            "448-token native context (DESIGN.md §5)")
    return cfg


def long_window_for(cfg: ModelConfig, shape: InputShape) -> int:
    """Window for the block-sparse SkipSet policy on long_500k decode."""
    if shape.name != "long_500k":
        return 0
    if cfg.family in ("rwkv6", "griffin"):
        return 0            # natively sub-quadratic (O(1)/O(window) state)
    if cfg.attn_window:
        return 0            # mixtral: native SWA already windowed
    return LONG_WINDOW      # dense/mla/vlm: Opt-KV SkipSet as block sparsity


def default_microbatches(cfg: ModelConfig) -> int:
    """Gradient-accumulation depth for train_4k (§Perf P0/P4): each extra
    microbatch costs one grad cross-data reduction, so use the fewest that
    fit 16 GiB HBM. MoE dispatch tensors are the hungriest."""
    if cfg.num_experts:
        return 8
    if cfg.family == "griffin":
        return 8        # associative-scan pyramid is the peak, scales ~1/n
    n = get_model(cfg).param_count()
    if n > 6e10:
        return 16       # deepseek-67b: 20.3 GiB at 8 -> 9.2 GiB at 16
    if n > 3e10:
        return 8
    if n > 8e9:
        return 4
    if n > 5e9:
        return 2
    return 1


def make_step(arch_id: str, shape_name: str, mesh: Mesh,
              coopt: CoOptConfig = COOPT, *, lr: float = 3e-4,
              num_microbatches: Optional[int] = None) -> StepBundle:
    kctx = None
    if coopt.use_kernel:
        # a mesh with sharded pages axes gets the shard_map kernel layer
        from repro.kernels import ops
        kctx = ops.make_mesh_ctx(mesh)
    cfg = get_config(arch_id)
    shape = get_shape(shape_name)
    cfg = effective_config(cfg, shape)
    model = get_model(cfg)
    lw = long_window_for(cfg, shape)

    params_abs = shapes_tree(model.param_specs())
    wrules = WEIGHT_RULES_DECODE if shape.kind == "decode" else WEIGHT_RULES
    params_sh = make_shardings(model.param_specs(), mesh, wrules)
    batch_abs = model.input_specs(shape)
    batch_sh = batch_shardings(batch_abs, mesh)
    act_rules = ACT_RULES_DECODE if shape.kind == "decode" else ACT_RULES_SEQ

    if shape.kind == "train":
        mu_abs = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), params_abs)
        opt_abs = AdamWState(jax.ShapeDtypeStruct((), jnp.int32),
                             mu_abs, mu_abs)
        f32_sh = params_sh  # same pspecs; dtype lives in the avals
        opt_sh = AdamWState(NamedSharding(mesh, PS()), f32_sh, f32_sh)

        from repro.training.train import make_train_step
        nm = (num_microbatches if num_microbatches is not None
              else default_microbatches(cfg))
        inner = make_train_step(cfg, coopt, lr=lr, num_microbatches=nm)

        def train_step(params, opt_state, batch):
            with activation_sharding(mesh, act_rules):
                return inner(params, opt_state, batch)

        return StepBundle(
            "train", train_step, (params_abs, opt_abs, batch_abs),
            (params_sh, opt_sh, batch_sh), (params_sh, opt_sh, None),
            cfg, shape, coopt)

    # kernel path: pool pages axis padded to tile the mesh's KV shards and
    # partitioned ONLY along the pages axes (the shard_map layer's layout)
    if coopt.use_kernel:
        from repro.launch.mesh import kv_shard_count
        crules, ns = KERNEL_CACHE_RULES, kv_shard_count(mesh)
    else:
        crules, ns = CACHE_RULES, 1
    cache_abs, cache_sh = cache_shardings(
        model, shape.global_batch, shape.seq_len, coopt, mesh, rules=crules,
        num_shards=ns)

    if shape.kind == "prefill":

        def prefill_step(params, batch, cache):
            from repro.kernels import ops
            with ops.mesh_ctx_scope(kctx), \
                    activation_sharding(mesh, act_rules):
                return model.prefill(params, batch, cache, coopt)

        return StepBundle(
            "prefill", prefill_step, (params_abs, batch_abs, cache_abs),
            (params_sh, batch_sh, cache_sh), (None, cache_sh),
            cfg, shape, coopt)

    # decode: ONE new token against a cache of seq_len (serve_step)
    def serve_step(params, batch, cache):
        from repro.kernels import ops
        with ops.mesh_ctx_scope(kctx), \
                activation_sharding(mesh, act_rules):
            return model.decode_step(params, batch, cache, coopt,
                                     long_window=lw)

    return StepBundle(
        "decode", serve_step, (params_abs, batch_abs, cache_abs),
        (params_sh, batch_sh, cache_sh), (None, cache_sh),
        cfg, shape, coopt, long_window=lw)


# ------------------------------------------------ serving AOT warmup ----
def serving_warmup(engine) -> Dict[str, Any]:
    """AOT-compile the serving engine's whole step-shape lattice at launch
    time (``Engine.warmup``: prefill buckets x packed row buckets x decode,
    ``lower().compile()`` per shape) and return a summary for the launch
    report — after this, steady-state serving performs ZERO new traces
    (``engine.aot_misses`` stays 0)."""
    import time as _time
    t0 = _time.perf_counter()
    built = engine.warmup()
    kinds: Dict[str, int] = {}
    for key in engine._aot:
        kinds[key[0]] = kinds.get(key[0], 0) + 1
    return {"aot_executables": built,
            "aot_by_kind": kinds,
            "warmup_s": round(_time.perf_counter() - t0, 3)}
