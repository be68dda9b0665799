"""LLM-CoOpt serving engine: continuous batching over ONE shared, refcounted,
prefix-cached paged-KV pool, with the paper's three techniques selected by a
``CoOptConfig``.

The engine is the "vLLM migration target" of the paper: the Original mode
reproduces unmodified-vLLM semantics (bf16 cache, every allocated page
loaded, per-head KV expansion) and each Opt-* flag turns on one technique,
so Figs. 6-7's five modes are one constructor argument apart.

Design (hardware adaptation, DESIGN.md §3): the device cache is a GLOBAL
paged pool — leaves ``(L, 2, P_total, Hkv, ps, D)`` of every layer with no
batch dimension, ``P_total = num_lanes * pages(max_len)`` padded to tile evenly
over ``num_shards`` KV shards (the final page reserved). The pool's page
range is partitioned along the mesh ``(pod, data)`` axes — the axes
CACHE_RULES shard the pages axis over — and every request is pinned to
ONE shard at admission, so its page gathers
stay shard-local. All dynamic paging state (per-shard free lists, refcounts,
per-shard prefix-cache hash tables, slot indices, SkipSets) lives host-side
in the Scheduler/BlockManager; the device sees only static-shape index
arrays: global ``slot_idx``, per-lane ``page_table``, per-lane
``cache_len``. Lane isolation is enforced by slot disjointness — a lane can
only write pages it exclusively owns (shared prefix pages are read-only by
refcount construction) — so cache updates need no batch masking; only
batch-major leaves (per-lane lengths, recurrent state, whisper cross-KV) are
masked with the admitted-lane mask.

Scheduling (Sarathi-style): each step is composed under a token budget,
mixing decode tokens and chunked-prefill chunks, and EVERY family executes
the whole step as ONE device call through the chunked-continuation prefill
path (a decode lane is a chunk of length 1; a step with only decode lanes
takes the one-token decode kernel). The Opt-Pa two-step strategy — "segment
long sequences into manageable chunks, then apply lazy memory mapping and
computation" (paper §3.3) — therefore applies uniformly: dense/moe/vlm
attend the gathered paged history with true positions, MLA in absorbed
latent form, whisper over its decoder self-KV (cross-KV computed once, on
the first chunk), and griffin/rwkv6 thread their recurrent state across
chunks (the state after chunk k is the input state of chunk k+1), with
state snapshots at committed page boundaries backing their prefix cache.
Admission is shard-affine (prefix-affinity first, least-loaded fallback).
Shard exhaustion preempts the youngest running request ON THE PRESSURED
SHARD (freed pages, front-of-queue requeue, greedy-exact resume) instead of
crashing; impossible requests are REJECTED and surfaced.
"""
from __future__ import annotations

import dataclasses
import time
from collections import OrderedDict
from dataclasses import dataclass, field
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.profiler import TraceAnnotation

from repro.cache.block_manager import (OutOfBlocks, PageResidency,
                                       PrefixMatch, chain_hash_tokens,
                                       extend_chain_hash)
from repro.cache.quant import (HostPage, dequantize_fp8, encode_host_page)
from repro.kernels.visits import sharing_stats
from repro.configs.base import CacheConfig, ModelConfig
from repro.core.coopt import CoOptConfig, COOPT
from repro.models import get_model
from repro.serving import steplog
from repro.serving.request import FinishReason, Request, RequestState
from repro.serving.sampler import SamplingParams, sample
from repro.serving.scheduler import (DecodeItem, PrefillChunk, Scheduler,
                                     StepPlan, bucket_len, chunk_pages,
                                     pack_rows)


# --------------------------------------------- host-tier page transfers ----
# One compiled executable per (leaf shape, axis): the page index is a TRACED
# scalar (lax.dynamic_*_in_dim), so spilling/prefetching page 7 vs page 900
# never recompiles. Both directions are fully asynchronous — dispatch-order
# execution on the device stream sequences them against the surrounding
# steps without any host sync (COOPT001 stays clean).
@partial(jax.jit, static_argnames=("axis",))
def _read_pool_page(leaf, page, axis: int):
    return lax.dynamic_index_in_dim(leaf, page, axis, keepdims=False)


@partial(jax.jit, static_argnames=("axis",), donate_argnums=(0,))
def _write_pool_page(leaf, data, page, axis: int):
    return lax.dynamic_update_index_in_dim(
        leaf, data.astype(leaf.dtype), page, axis)


@partial(jax.jit, static_argnames=("axis",), donate_argnums=(0,))
def _write_pool_page_q(leaf, q, scale, page, axis: int):
    """fp8-encoded host page (CacheConfig.host_quant): dequantize on device
    during the staging write."""
    data = dequantize_fp8(q, scale, axis=-1, dtype=leaf.dtype)
    return lax.dynamic_update_index_in_dim(leaf, data, page, axis)


def place_cache(cache, shapes, mesh, use_kernel: bool):
    """Shard the device cache leaves onto the mesh (``shapes``: the model's
    ``cache_shape``): the kernel path partitions the pool ONLY along its
    pages axes (the shard_map layer's layout — heads/latent replicated);
    the jnp reference path uses the full CACHE_RULES (GSPMD handles the
    rest)."""
    from jax.sharding import NamedSharding
    from repro.launch.steps import (CACHE_RULES, KERNEL_CACHE_RULES,
                                    axes_pspec)
    rules = KERNEL_CACHE_RULES if use_kernel else CACHE_RULES
    return {k: jax.device_put(
                leaf, NamedSharding(mesh, axes_pspec(
                    shapes[k][0], shapes[k][2], mesh, rules)))
            for k, leaf in cache.items()}


@dataclass
class _Flight:
    """One dispatched host->HBM prefetch upload, committed to the device
    prefix table once the scheduler turn counter passes ``lands`` (dispatch
    order already sequences the upload before any step planned after the
    commit — the turn delay models the overlap window, it is not a wait)."""
    hash: int
    turn: int                      # dispatch turn
    lands: int                     # first turn the commit may happen
    ok: bool = True                # fault injection: False -> abort instead


@dataclass(frozen=True)
class EngineConfig:
    num_lanes: int = 4
    max_len: int = 512
    prefill_buckets: Tuple[int, ...] = (64, 128, 256, 512)
    long_window: int = 0            # >0: block-sparse long-context decode
    sampling: SamplingParams = SamplingParams()
    seed: int = 0
    token_budget: int = 0           # 0 => max(prefill_buckets)
    enable_prefix_cache: bool = True
    num_shards: int = 1             # KV-pool page-range shards; matches the
                                    # mesh (pod, data) extent the cache
                                    # pages axis is sharded over
                                    # (launch.mesh.kv_shard_count)
    state_cache_entries: int = 128  # recurrent-state snapshots retained
                                    # (griffin/rwkv6 prefix-cache resume)
    pack_prefill: bool = False      # concat-prefill packing: several
                                    # prompts' chunks share one row through
                                    # the segment-aware chunk kernels
                                    # (dense/moe/mla families)
    pack_slots: int = 4             # sampled-logit slots per packed row
                                    # (max final chunks packed together)
    max_preemptions: int = 32       # preemption bound per request: past it
                                    # the request is rejected
                                    # (PREEMPTION_LIMIT) instead of
                                    # livelocking the pool
    cache: Optional[CacheConfig] = None
                                    # consolidated cache knobs (pool size,
                                    # shards, prefix cache, host-DRAM spill
                                    # tier). None = derive a CacheConfig
                                    # from the legacy enable_prefix_cache /
                                    # num_shards fields above.

    def cache_config(self, page_size: int) -> CacheConfig:
        """Resolve the effective :class:`CacheConfig`.

        Legacy knobs (``num_shards`` / ``enable_prefix_cache``) remain the
        deprecation shim: with ``cache=None`` they are folded into a fresh
        CacheConfig; with an explicit ``cache`` they must not CONFLICT
        (non-default values in both places raise)."""
        cc = self.cache
        if cc is None:
            cc = CacheConfig(num_shards=self.num_shards,
                             enable_prefix_cache=self.enable_prefix_cache)
        else:
            if self.num_shards != 1 and self.num_shards != cc.num_shards:
                raise ValueError(
                    f"EngineConfig.num_shards={self.num_shards} conflicts "
                    f"with EngineConfig.cache.num_shards={cc.num_shards}; "
                    "set the shard count in ONE place (CacheConfig "
                    "preferred)")
            if not self.enable_prefix_cache and cc.enable_prefix_cache:
                cc = cc.replace(enable_prefix_cache=False)
        ps = cc.page_size or page_size
        pages_per_lane = -(-self.max_len // ps)
        return cc.resolve(page_size=ps,
                          num_pages=self.num_lanes * pages_per_lane)


@dataclass
class EngineStats:
    prefill_calls: int = 0
    decode_steps: int = 0
    mixed_steps: int = 0            # decode + prefill fused in one call
    generated_tokens: int = 0
    prefill_time: float = 0.0       # mixed-step wall time is split by
    decode_time: float = 0.0        # planned token share (Eq. 12 fairness)
    packed_steps: int = 0           # steps run through the packed row path
    packed_rows_saved: int = 0      # lane-rows eliminated by packing
    # ------------------------------------- cross-lane prefix sharing -----
    # Accounted per decode step from the step's page table (the same array
    # kernels.visits.plan_visits dedups on-device), so the numbers describe
    # exactly what the visit grid batches: a (slot, page) entry held by k>1
    # lanes streams once instead of k times.
    shared_page_visits: int = 0     # deduped visits with >1 member lane
    dup_page_streams_saved: int = 0 # per-lane page streams eliminated:
                                    # sum over shared visits of (k - 1)
    lanes_per_shared_page: Dict[int, int] = field(default_factory=dict)
                                    # histogram: k lanes -> visit count
    # ------------------------------------------------ per-request latency --
    ttft_s: List[float] = field(default_factory=list)   # submit->1st token
                                                        # (queue wait incl.)
    tpot_s: List[float] = field(default_factory=list)   # mean s/token after
    queue_wait_s: List[float] = field(default_factory=list)  # submit->admit
    # ----------------------------------------------------- pool health ----
    pool_pages: int = 0
    pages_in_use: int = 0           # referenced by live sequences (now)
    peak_pages_in_use: int = 0
    fresh_pages_allocated: int = 0  # pages handed out over the run
    prefix_cache_queries: int = 0
    prefix_cache_hits: int = 0      # pages reused, not recomputed
                                    # (= device + host hits; legacy total)
    prefix_device_hits: int = 0     # hit pages that were HBM-resident
    prefix_host_hits: int = 0       # hit pages restored from the host tier
                                    # (spilled, then prefetched back)
    preemptions: int = 0
    rejected: int = 0
    # ------------------------------------------------ host-DRAM KV tier ----
    host_pages: int = 0             # host tier capacity (0 = tier off)
    host_pages_resident: int = 0    # spilled pages currently host-resident
    spilled_pages: int = 0          # device evictions rescued to host DRAM
    host_evictions: int = 0         # pages dropped off the host LRU (gone)
    prefetch_begun: int = 0         # host->HBM uploads dispatched
    prefetch_committed: int = 0     # ..that landed and re-registered
    prefetch_aborted: int = 0       # ..that failed / lost a registration race
    prefetches_planned: int = 0     # queued requests the scheduler planned
                                    # prefetch for
    prefetch_held_turns: int = 0    # admission turns spent gated on an
                                    # IN_FLIGHT upload (overlap window)
    prefetch_replans: int = 0       # landed prefixes stolen by allocation
                                    # pressure pre-admission, fetched again
    # ----------------------------------------------------- resilience ----
    shed: int = 0                   # fast-rejected at submit (overload
                                    # watermark; AsyncEngine only)
    deadline_shed: int = 0          # queued requests shed TIMED_OUT
    preemption_limit_rejects: int = 0  # rejected past max_preemptions
    errors: int = 0                 # requests terminated by a pipeline
                                    # fault (step exception, worker death,
                                    # stall watchdog)
    # --------------------------------------------------- sharded pool ----
    num_shards: int = 1
    shard_pages: Tuple[int, ...] = ()          # page-range size per shard
    shard_pages_in_use: Tuple[int, ...] = ()
    peak_shard_pages_in_use: Tuple[int, ...] = ()
    shard_preemptions: Tuple[int, ...] = ()    # per-shard pressure evictions
    placement_prefix_hits: int = 0  # admitted on the prefix-affine shard
    placement_misses: int = 0       # prefix lived on an unusable shard ->
                                    # cross-shard CoW reuse lost

    @property
    def total_time(self) -> float:
        return self.prefill_time + self.decode_time

    def throughput(self) -> float:
        """Paper Eq. 12: generated tokens / generation time (decode's
        token-share of mixed steps, not whole mixed-step wall clock)."""
        return self.generated_tokens / self.decode_time \
            if self.decode_time else 0.0

    @staticmethod
    def _pct(xs: List[float], q: float) -> float:
        # xs is a host-side Python list of floats — no device value is
        # synced here, the pattern just looks like one to the linter
        return float(np.percentile(np.asarray(xs), q)) if xs else 0.0  # coopt: allow[COOPT001]

    def ttft(self, q: float = 50.0) -> float:
        """Time-to-first-token percentile (s) over finished requests,
        measured from SUBMISSION — queue wait included."""
        return self._pct(self.ttft_s, q)

    def tpot(self, q: float = 50.0) -> float:
        """Per-request mean time-per-output-token percentile (s)."""
        return self._pct(self.tpot_s, q)

    def queue_wait(self, q: float = 50.0) -> float:
        """Submission -> first lane admission percentile (s)."""
        return self._pct(self.queue_wait_s, q)

    def latency_summary(self) -> Dict[str, float]:
        return {"ttft_p50_s": round(self.ttft(50), 4),
                "ttft_p95_s": round(self.ttft(95), 4),
                "tpot_p50_s": round(self.tpot(50), 4),
                "tpot_p95_s": round(self.tpot(95), 4),
                "queue_wait_p50_s": round(self.queue_wait(50), 4),
                "queue_wait_p95_s": round(self.queue_wait(95), 4),
                # host-side Python int counters, not device values
                "shared_page_visits":
                    float(self.shared_page_visits),  # coopt: allow[COOPT001]
                "dup_page_streams_saved":
                    float(self.dup_page_streams_saved),  # coopt: allow[COOPT001]
                "shed":
                    float(self.shed),  # coopt: allow[COOPT001]
                "deadline_shed":
                    float(self.deadline_shed),  # coopt: allow[COOPT001]
                "preemption_limit_rejects":
                    float(self.preemption_limit_rejects),  # coopt: allow[COOPT001]
                "errors":
                    float(self.errors),  # coopt: allow[COOPT001]
                "prefix_device_hits":
                    float(self.prefix_device_hits),  # coopt: allow[COOPT001]
                "prefix_host_hits":
                    float(self.prefix_host_hits),  # coopt: allow[COOPT001]
                "prefix_misses":
                    float(self.prefix_cache_queries  # coopt: allow[COOPT001]
                          - self.prefix_cache_hits),
                "spilled_pages":
                    float(self.spilled_pages),  # coopt: allow[COOPT001]
                "prefetch_committed":
                    float(self.prefetch_committed),  # coopt: allow[COOPT001]
                }

    def pool_utilization(self) -> float:
        return self.pages_in_use / self.pool_pages if self.pool_pages else 0.0

    def shard_utilization(self) -> Tuple[float, ...]:
        return tuple(u / p if p else 0.0
                     for u, p in zip(self.shard_pages_in_use,
                                     self.shard_pages))

    def prefix_hit_rate(self) -> float:
        return self.prefix_cache_hits / self.prefix_cache_queries \
            if self.prefix_cache_queries else 0.0

    def prefix_device_hit_rate(self) -> float:
        return self.prefix_device_hits / self.prefix_cache_queries \
            if self.prefix_cache_queries else 0.0

    def prefix_host_hit_rate(self) -> float:
        return self.prefix_host_hits / self.prefix_cache_queries \
            if self.prefix_cache_queries else 0.0

    def prefix_miss_rate(self) -> float:
        return 1.0 - self.prefix_hit_rate() \
            if self.prefix_cache_queries else 0.0


@dataclass
class StepBatch:
    """One fully-built device step: the static-shape arrays plus the host
    metadata needed to route the sampled tokens back to requests. Built by
    ``Engine._build_step`` and consumed by BOTH the synchronous loop and
    the async pipeline (``serving.frontend``) — one step-construction path.

    ``samples`` maps each sampled logit slot to (request, is_first_token,
    index into the sampled-token array) — ``(lane,)`` for the per-lane
    kinds, ``(row, slot)`` for the packed kind. ``feed``/``row_lane``/
    ``scatter_lane`` carry the async device-token plumbing: column 0 of a
    decode row can take its input token from the device-resident per-lane
    ``lane_tok`` feed (-1) instead of a host value (-2 = keep the host
    token), and every sampled token is scattered back into ``lane_tok`` at
    ``scatter_lane`` (``num_lanes`` = drop), so planning step N+1 never
    waits on step N's host sync."""
    kind: str                      # "prefill" | "decode" | "packed"
    batch: Dict[str, jnp.ndarray]
    lane_mask: np.ndarray          # (num_lanes,) bool; unused for packed
    plan: StepPlan
    samples: List[Tuple[Request, bool, Tuple[int, ...]]]
    tp: int                        # planned prefill tokens
    td: int                        # planned decode tokens
    feed: np.ndarray               # (R,) int32 col-0 token source
    row_lane: np.ndarray           # (R,) int32 lane backing each row
    scatter_lane: np.ndarray       # (n_slots,) int32 lane per sample slot
    record: Optional[steplog.StepRecord] = None   # set once dispatched


class Engine:
    def __init__(self, model_cfg: ModelConfig, coopt: CoOptConfig = COOPT,
                 engine_cfg: EngineConfig = EngineConfig(),
                 params=None, mesh=None):
        """``mesh``: optional ``jax.sharding.Mesh``. When given, the KV-pool
        shard count is DERIVED from the mesh's pages axes
        (``launch.mesh.kv_shard_count``) — a default ``num_shards=1`` config
        is upgraded to match, and a conflicting explicit value raises (the
        host page ranges and the device pages-axis partition must coincide).
        The parameters are placed on the mesh once, replicated; the cache
        leaves are sharded along their pages axis, and with
        ``coopt.use_kernel`` the pooled Pallas kernels run through the
        ``kernels.sharded`` shard_map layer — one kernel hot path, single-
        host and distributed."""
        self.cfg = model_cfg
        self.coopt = coopt
        ccfg = engine_cfg.cache_config(coopt.page_size)
        if mesh is not None:
            from repro.launch.mesh import kv_shard_count
            ns = kv_shard_count(mesh)
            if ccfg.num_shards == 1:
                # config built before the mesh: derive the shard count
                ccfg = ccfg.replace(num_shards=ns)
            elif ccfg.num_shards != ns:
                raise ValueError(
                    f"EngineConfig.num_shards={ccfg.num_shards} "
                    f"disagrees with the mesh's KV shard count {ns} "
                    f"(pages axes {tuple(mesh.shape)}); build the config "
                    "from launch.mesh.kv_shard_count(mesh) or leave it at "
                    "the default to derive it")
        # keep the legacy EngineConfig mirrors in sync with the resolved
        # CacheConfig — downstream code reads either
        if (engine_cfg.num_shards != ccfg.num_shards
                or engine_cfg.enable_prefix_cache != ccfg.enable_prefix_cache):
            engine_cfg = dataclasses.replace(
                engine_cfg, num_shards=ccfg.num_shards,
                enable_prefix_cache=ccfg.enable_prefix_cache)
        self.ccfg = ccfg
        self.mesh = mesh
        self.ecfg = engine_cfg
        self.model = get_model(model_cfg)
        if params is None:
            params = self.model.init(jax.random.PRNGKey(engine_cfg.seed))
        if mesh is not None:
            # once, here: uncommitted weights would be copied to the mesh
            # again by every step
            from jax.sharding import NamedSharding, PartitionSpec
            params = jax.device_put(params,
                                    NamedSharding(mesh, PartitionSpec()))
        self.params = params
        self.key = jax.random.PRNGKey(engine_cfg.seed + 1)

        B, M = engine_cfg.num_lanes, engine_cfg.max_len
        # the device pool's pages axis is padded so it tiles evenly over the
        # KV shards (host page ids == device page ids, see opt_kv helpers)
        self.cache = self.model.init_cache(B, M, coopt,
                                           num_shards=engine_cfg.num_shards,
                                           cache_cfg=ccfg)
        # pages-axis shard_map dispatch for the pooled kernels (None for no
        # mesh / an unsharded mesh: identical single-host code path)
        from repro.kernels import ops
        self._kernel_ctx = (ops.make_mesh_ctx(mesh)
                            if coopt.use_kernel else None)
        if mesh is not None:
            self.cache = self._place_cache(self.cache, mesh)
        self._patch_offset = (model_cfg.num_patches
                              if model_cfg.family == "vlm" else 0)
        # recurrent-state families: chunk boundaries land on page boundaries
        # so the cross-chunk state can be snapshotted as the prefix cache's
        # resume artifact (KV pages alone cannot resume a recurrence)
        self._rec_leaves = tuple(getattr(self.model, "recurrent_leaves", ()))
        self.scheduler = Scheduler(
            B, M, coopt.page_size, list(engine_cfg.prefill_buckets),
            extra_tokens=self._patch_offset,
            token_budget=engine_cfg.token_budget or None,
            page_aligned=bool(self._rec_leaves),
            max_preemptions=engine_cfg.max_preemptions,
            cache_cfg=ccfg)
        # deterministic fault-injection hook layer (serving.faults); None in
        # production — the chaos suite installs a seeded FaultInjector here
        self.faults = None
        # chain-hash(prefix pages) -> per-lane state slices; the manager's
        # prefix_gate makes page matching stop at the last boundary we can
        # actually restore
        self._state_cache: "OrderedDict[int, Dict[str, np.ndarray]]" = \
            OrderedDict()
        if self._rec_leaves:
            self.scheduler.manager.prefix_gate = self._state_cache.__contains__
        self.stats = EngineStats()
        self.stats.pool_pages = self.scheduler.manager.num_pages

        # only batch-major leaves (length, recurrent state, whisper x-KV)
        # need lane masking; global-pool leaves are isolated by slot
        # disjointness.
        shapes = self.model.cache_shape(B, M, coopt, cache_cfg=ccfg)
        self._batch_axis = {k: axes.index("batch")
                            for k, (_, _, axes) in shapes.items()
                            if "batch" in axes}

        # ---------------------------------------- host-DRAM KV spill tier --
        # Pool leaves are addressed page-wise along their "pages" axis; the
        # batch-major leaves (recurrent state, whisper cross-KV) have no
        # page identity and never spill.
        self._pool_axis = {k: axes.index("pages")
                           for k, (_, _, axes) in shapes.items()
                           if "pages" in axes}
        self._prefetch_flights: List[_Flight] = []
        self._sched_turn = 0
        self._host_dev = None
        if ccfg.host_pages > 0 and self._pool_axis:
            try:
                self._host_dev = jax.devices("cpu")[0]
            except RuntimeError as e:
                raise RuntimeError(
                    "CacheConfig.host_pages needs JAX's CPU backend to hold "
                    "spilled pages in host memory; none is available (is "
                    "JAX_PLATFORMS set without 'cpu'?)") from e
            mgr = self.scheduler.manager
            mgr.spill_sink = self._spill_page
            self.scheduler.prefetcher = self._start_prefetch
            self.scheduler.prefetch_tick = self._tick_prefetch
        self.stats.host_pages = ccfg.host_pages

        # cache donation (argnum 2 of every step impl): the pool is
        # threaded through each step and immediately rebound to the
        # output, so XLA may update pages in place instead of copying the
        # whole pool per step
        self._prefill_fn = jax.jit(self._prefill_impl, donate_argnums=(2,))
        self._decode_fn = jax.jit(self._decode_impl, donate_argnums=(2,))
        self._packed_fn = jax.jit(self._prefill_packed_impl,
                                  donate_argnums=(2,))
        # async two-stage pipeline step (sample-on-device), one jit per
        # step kind so each kind's program carries its own name in a
        # device trace (jit_serve_step_<kind>); lazily traced,
        # AOT-compiled by AsyncEngine.warmup over the step-shape lattice.
        # lane_tok (argnum 4) is donated too — it is device-resident state
        # owned by the pipeline, rebound on every dispatch.
        self._step_fns = {
            "decode": jax.jit(self.serve_step_decode, donate_argnums=(2, 4)),
            "prefill": jax.jit(self.serve_step_prefill,
                               donate_argnums=(2, 4)),
            "packed": jax.jit(self.serve_step_packed, donate_argnums=(2, 4)),
        }
        self._aot: Dict[tuple, object] = {}   # shape key -> Compiled
        self._dev_cache: Dict[tuple, jnp.ndarray] = {}  # small recurring
        # host arrays (lane masks, token-feed plumbing) memoized on device
        # — steady-state decode reuses them every step, skipping the
        # per-step device_put that would otherwise eat the pipeline win
        self.aot_misses = 0                   # async steps that re-traced
        self.engine_id = next(steplog.ENGINE_IDS)
        self.steps_dispatched = 0             # step-log sequence numbers
        self.trace_counts: Dict[str, int] = {}  # impl traces (trace-time
                                                # side effect — retraces
                                                # show up here)
        # concat-prefill packing works where "length" is the ONLY
        # batch-major leaf (rows decouple from lanes; the packed impl
        # restores it): dense/moe/mla. vlm (patch stubs), whisper
        # (cross-KV) and the recurrent families keep per-lane state.
        self._pack_ok = (model_cfg.family in ("dense", "moe", "mla")
                         and not self._rec_leaves)
        if engine_cfg.pack_prefill and not self._pack_ok:
            raise ValueError(
                f"pack_prefill unsupported for family {model_cfg.family!r}"
                " (per-lane batch-major cache state)")

    # ------------------------------------------------------- mesh placement --
    def _place_cache(self, cache, mesh):
        shapes = self.model.cache_shape(self.ecfg.num_lanes,
                                        self.ecfg.max_len, self.coopt,
                                        num_shards=self.ecfg.num_shards,
                                        cache_cfg=self.ccfg)
        return place_cache(cache, shapes, mesh, self.coopt.use_kernel)

    # ---------------------------------------------------------- jit bodies --
    def _mask_lanes(self, new_cache, old_cache, lane_mask):
        out = {}
        for name, leaf in new_cache.items():
            ax = self._batch_axis.get(name)
            if ax is None:
                out[name] = leaf
                continue
            m = lane_mask.reshape((1,) * ax + (-1,) +
                                  (1,) * (leaf.ndim - ax - 1))
            out[name] = jnp.where(m, leaf, old_cache[name])
        return out

    def _count_trace(self, kind: str) -> None:
        # runs at TRACE time only: steady-state (cached or AOT-compiled)
        # steps never touch it, so any increment after warmup IS a retrace
        self.trace_counts[kind] = self.trace_counts.get(kind, 0) + 1

    def _run_model(self, fn, params, batch, cache):
        """The model's step ``fn`` (its ``prefill`` or ``decode_step``):
        (logits, new cache, the step's (2,) f32 expert counters: the most
        tokens one expert received in any MoE layer and the mean over MoE
        layers of the experts hit; zeros for a model without experts)."""
        from repro.kernels import ops
        with ops.mesh_ctx_scope(self._kernel_ctx):   # trace-scoped
            return fn(params, batch, cache, self.coopt,
                      long_window=self.ecfg.long_window)

    def _prefill_impl(self, params, batch, cache, lane_mask):
        self._count_trace("prefill")
        logits, new_cache, ctr = self._run_model(self.model.prefill, params,
                                                 batch, cache)
        return logits, self._mask_lanes(new_cache, cache, lane_mask), ctr

    def _decode_impl(self, params, batch, cache, lane_mask):
        self._count_trace("decode")
        logits, new_cache, ctr = self._run_model(self.model.decode_step,
                                                 params, batch, cache)
        return logits, self._mask_lanes(new_cache, cache, lane_mask), ctr

    def _prefill_packed_impl(self, params, batch, cache, lane_mask):
        """Packed rows are DECOUPLED from lanes (R != num_lanes), so no
        lane-shaped masking applies. Pool leaves are isolated by slot
        disjointness as ever; the only batch-major leaf in the packable
        families is ``length``, which is restored from the input cache
        (the engine passes explicit ``cache_len`` every step, so the leaf
        is bookkeeping only)."""
        self._count_trace("packed")
        logits, new_cache, ctr = self._run_model(self.model.prefill, params,
                                                 batch, cache)
        new_cache["length"] = cache["length"]
        return logits, new_cache, ctr

    def _async_step_impl(self, params, batch, cache, lane_mask, lane_tok,
                         key, feed, row_lane, scatter_lane, *, kind: str):
        """One async-pipeline device step: substitute device-resident input
        tokens, run the model, SAMPLE ON DEVICE, and scatter the sampled
        tokens back into the per-lane ``lane_tok`` feed — so the host can
        build and dispatch step N+1 from metadata alone while step N
        executes, deferring the host sync to the emit worker. Returns
        ((sampled tokens, expert counters), new cache, lane_tok): the
        counters (``_run_model``) come back with the tokens, in the same
        host sync."""
        self._count_trace("async_" + kind)
        tok_key = "token" if kind == "decode" else "tokens"
        batch = dict(batch)
        if "dmeta" in batch:
            # decode fast path: per-step metadata shipped as ONE (3, B)
            # host->device transfer instead of three
            dm = batch.pop("dmeta")
            batch["positions"] = dm[0][:, None]
            batch["slot_idx"] = dm[1][:, None]
            batch["cache_len"] = dm[2]
        t0 = batch[tok_key][:, 0]
        t0 = jnp.where(feed == -1, lane_tok[row_lane],
                       jnp.where(feed >= 0, feed, t0))
        batch[tok_key] = batch[tok_key].at[:, 0].set(t0)
        body = {"decode": self._decode_impl,
                "packed": self._prefill_packed_impl,
                "prefill": self._prefill_impl}[kind]
        logits, new_cache, ctr = body(params, batch, cache, lane_mask)
        sp = self.ecfg.sampling
        toks = sample(logits, key, temperature=sp.temperature,
                      top_k=sp.top_k, top_p=sp.top_p)
        lane_tok = lane_tok.at[scatter_lane].set(
            toks.reshape(-1).astype(jnp.int32), mode="drop")
        return (toks, ctr), new_cache, lane_tok

    def serve_step_decode(self, *args):
        return self._async_step_impl(*args, kind="decode")

    def serve_step_prefill(self, *args):
        return self._async_step_impl(*args, kind="prefill")

    def serve_step_packed(self, *args):
        return self._async_step_impl(*args, kind="packed")

    # -------------------------------------------------------------- common --
    def _sample(self, logits) -> np.ndarray:
        self.key, sub = jax.random.split(self.key)
        sp = self.ecfg.sampling
        return np.asarray(sample(logits, sub, temperature=sp.temperature,
                                 top_k=sp.top_k, top_p=sp.top_p))

    def _emit(self, req: Request, tok: int, now: float,
              first: bool) -> bool:
        """Deliver one sampled token. Returns False when the token is
        DROPPED: the request already terminated (cancelled, rejected, shed,
        errored) or is done (the async pipeline's <= 1-step EOS overrun)."""
        if req.inflight > 0:
            req.inflight -= 1
        if req.is_terminal or req.done():
            return False
        req.output.append(tok)
        self.stats.generated_tokens += 1
        if first and req.prefill_time < 0:
            req.prefill_time = now          # TTFT anchor survives preemption
        return True

    @staticmethod
    def _anchor(req: Request) -> float:
        """TTFT / queue-wait anchor: client submission when stamped, else
        scheduler-queue arrival."""
        return req.submit_time if req.submit_time >= 0 else req.enqueue_time

    def _finish_done(self, reqs: List[Request]) -> None:
        now = time.perf_counter()
        for r in reqs:
            if not r.done():
                continue
            if r.state is RequestState.PREEMPTED:
                # async pipeline edge: preempted while its LAST tokens were
                # still in flight — their emission just completed it, so it
                # must never re-admit. Its pages were already freed.
                if r in self.scheduler.waiting:
                    self.scheduler.waiting.remove(r)
                r.state = RequestState.FINISHED
                r.finish(FinishReason.FINISHED)
            elif r.state is RequestState.RUNNING:
                self.scheduler.finish(r)
            else:
                continue
            r.finish_time = now
            t0 = self._anchor(r)
            if r.prefill_time >= 0 and t0 >= 0:
                self.stats.ttft_s.append(r.prefill_time - t0)
                if r.num_generated > 1:
                    self.stats.tpot_s.append(
                        (r.finish_time - r.prefill_time)
                        / (r.num_generated - 1))
            if r.admit_time >= 0 and t0 >= 0:
                self.stats.queue_wait_s.append(r.admit_time - t0)

    def _update_pool_stats(self) -> None:
        mgr = self.scheduler.manager
        s = self.stats
        s.pool_pages = mgr.num_pages
        s.pages_in_use = mgr.pages_in_use
        s.peak_pages_in_use = max(s.peak_pages_in_use, mgr.pages_in_use)
        s.fresh_pages_allocated = mgr.fresh_pages_allocated
        s.prefix_cache_queries = mgr.prefix_queries
        s.prefix_cache_hits = mgr.prefix_hits
        s.preemptions = self.scheduler.preemptions
        s.rejected = len(self.scheduler.rejected)
        s.deadline_shed = self.scheduler.deadline_shed
        s.preemption_limit_rejects = self.scheduler.preemption_limit_rejects
        # per-shard health (page-range ownership along the mesh data/pod axes)
        n = mgr.num_shards
        s.num_shards = n
        s.shard_pages = tuple(mgr.shard_capacity(i) for i in range(n))
        s.shard_pages_in_use = tuple(mgr.pages_in_use_in(i)
                                     for i in range(n))
        peak = s.peak_shard_pages_in_use or (0,) * n
        s.peak_shard_pages_in_use = tuple(
            max(p, u) for p, u in zip(peak, s.shard_pages_in_use))
        s.shard_preemptions = tuple(self.scheduler.preemptions_by_shard)
        s.placement_prefix_hits = self.scheduler.placement_prefix_hits
        s.placement_misses = self.scheduler.placement_misses
        # host-DRAM tier
        s.prefix_device_hits = mgr.prefix_device_hits
        s.prefix_host_hits = mgr.prefix_host_hits
        s.host_pages = mgr.host_pages
        s.host_pages_resident = mgr.host_resident_pages
        s.spilled_pages = mgr.spilled_pages
        s.host_evictions = mgr.host_evictions
        s.prefetch_begun = mgr.prefetch_begun
        s.prefetch_committed = mgr.prefetch_committed
        s.prefetch_aborted = mgr.prefetch_aborted
        s.prefetches_planned = self.scheduler.prefetches_planned
        s.prefetch_held_turns = self.scheduler.prefetch_held_turns
        s.prefetch_replans = self.scheduler.prefetch_replans

    # ----------------------------------------------- host-DRAM spill tier --
    def _spill_page(self, h: int, page: int, shard: int):
        """BlockManager spill sink: rescue an LRU-evicted prefix page to the
        host store. Returns the host payload (or None to let the page die —
        fault injection). The pool slice is dispatched BEFORE any later step
        that could reuse ``page``, so device-order execution reads the old
        contents even though the pool leaves are donated per step; the
        ``device_put`` to the CPU backend is asynchronous — no host sync."""
        hook = (getattr(self.faults, "on_spill", None)
                if self.faults is not None else None)
        if hook is not None and not hook():
            return None
        leaves = {k: _read_pool_page(self.cache[k], page, axis=ax)
                  for k, ax in self._pool_axis.items()}
        hp = encode_host_page(leaves, quantize=self.ccfg.host_quant)
        if self._host_dev is not None:
            hp = hp.to_device(self._host_dev)
        return hp

    def _upload_page(self, hp: HostPage, page: int) -> None:
        """Write a host payload into reserved staging page ``page`` via the
        donated dynamic-update jit (rebind-at-call, pages updated in place)."""
        for k, ax in self._pool_axis.items():
            if k in hp.scales:
                self.cache[k] = _write_pool_page_q(
                    self.cache[k], hp.leaves[k], hp.scales[k], page, axis=ax)
            else:
                self.cache[k] = _write_pool_page(
                    self.cache[k], hp.leaves[k], page, axis=ax)

    def _start_prefetch(self, req: Request, match: PrefixMatch) -> List[int]:
        """Scheduler prefetcher hook: start host->HBM uploads for the
        non-device-resident pages of a queued request's matched prefix.
        Returns the chain hashes whose landing gates the request's
        admission (existing IN_FLIGHT uploads are ridden, not repeated)."""
        mgr = self.scheduler.manager
        keys: List[int] = []
        for mp in match.pages:
            if mp.residency is PageResidency.DEVICE:
                continue
            if mp.residency is PageResidency.IN_FLIGHT:
                keys.append(mp.hash)      # ride the existing upload
                continue
            try:
                page, payload = mgr.begin_prefetch(mp.hash, match.shard)
            except OutOfBlocks:
                break   # no staging page free: admit on what already landed
            except KeyError:
                break   # raced off the host store since match_prefix
            ok, delay = True, 0
            hook = (getattr(self.faults, "on_prefetch", None)
                    if self.faults is not None else None)
            if hook is not None:
                ok, delay = hook()
            self._upload_page(payload, page)
            self._prefetch_flights.append(_Flight(
                hash=mp.hash, turn=self._sched_turn,
                lands=self._sched_turn + 1 + max(int(delay), 0), ok=ok))
            keys.append(mp.hash)
        return keys

    def _tick_prefetch(self) -> None:
        """Scheduler prefetch_tick hook, called at the top of every
        schedule_step: advance the turn clock and settle landed flights.
        A flight dispatched on turn T commits no earlier than turn T+1 —
        the upload overlaps the step(s) dispatched in between; dispatch
        order guarantees it has executed before any step planned AFTER the
        commit can read the staged page."""
        self._sched_turn += 1
        if not self._prefetch_flights:
            return
        mgr = self.scheduler.manager
        still: List[_Flight] = []
        for f in self._prefetch_flights:
            if self._sched_turn < f.lands:
                still.append(f)
                continue
            if f.ok:
                mgr.commit_prefetch(f.hash)
            else:
                mgr.abort_prefetch(f.hash)
        self._prefetch_flights = still

    def _abort_prefetch_flights(self) -> None:
        """Return every in-flight staging page to the free list (payloads
        go back to the host store — the upload is abandoned, not lost)."""
        mgr = self.scheduler.manager
        for f in self._prefetch_flights:
            mgr.abort_prefetch(f.hash)
        self._prefetch_flights = []

    # ------------------------------------------------- recurrent snapshots --
    def _lane_index(self, leaf: str, lane: int):
        ax = self._batch_axis[leaf]
        return (slice(None),) * ax + (lane,)

    def _reset_or_restore_state(self, chunks: List[PrefillChunk]) -> None:
        """First chunk of a (re)admitted request on a recurrent-state
        family: the lane's state leaves hold the PREVIOUS occupant's state —
        zero them, or restore the snapshot matching the prefix-cache hit
        (``start > 0`` implies the manager's prefix_gate verified one)."""
        ps = self.coopt.page_size
        for c in chunks:
            if not c.first:
                continue
            lane = c.req.lane
            snap = None
            # (re)seed the request's running chain hash at its resume point
            c.req.prefix_hash_pages = c.start // ps
            c.req.prefix_hash = chain_hash_tokens(
                c.req.effective_prompt(), c.req.prefix_hash_pages, ps)
            if c.start > 0:
                snap = self._state_cache[c.req.prefix_hash]
                self._state_cache.move_to_end(c.req.prefix_hash)
            for leaf in self._rec_leaves:
                idx = self._lane_index(leaf, lane)
                cur = self.cache[leaf]
                val = 0 if snap is None else jnp.asarray(snap[leaf],
                                                         cur.dtype)
                self.cache[leaf] = cur.at[idx].set(val)

    def _snapshot_state(self, c: PrefillChunk) -> None:
        """A chunk that ended exactly on a page boundary leaves the lane's
        recurrent state at a committed-prefix resume point: snapshot it
        under the same chain hash the pages were registered with."""
        ps = self.coopt.page_size
        end = c.start + c.n
        if end % ps or not self.ecfg.enable_prefix_cache:
            return
        # extend the request's running hash — never rehash from page 0
        key = extend_chain_hash(c.req.prefix_hash, c.req.effective_prompt(),
                                c.req.prefix_hash_pages, end // ps, ps)
        c.req.prefix_hash, c.req.prefix_hash_pages = key, end // ps
        if key in self._state_cache:
            self._state_cache.move_to_end(key)
            return
        self._state_cache[key] = {
            leaf: np.asarray(self.cache[leaf][self._lane_index(leaf,
                                                               c.req.lane)])
            for leaf in self._rec_leaves}
        while len(self._state_cache) > self.ecfg.state_cache_entries:
            self._state_cache.popitem(last=False)

    # --------------------------------------------------- the ONE step path --
    def _should_pack(self, plan: StepPlan) -> bool:
        return (self.ecfg.pack_prefill and self._pack_ok
                and bool(plan.prefill))

    def _note_sharing(self, rows: np.ndarray) -> None:
        """Accumulate cross-lane prefix-sharing stats for one decode step
        from the decode lanes' page-table rows (the exact dedup the visit
        grid performs on-device, counted host-side for observability)."""
        st = sharing_stats(rows)
        self.stats.shared_page_visits += st["shared_page_visits"]
        self.stats.dup_page_streams_saved += st["dup_page_streams_saved"]
        hist = self.stats.lanes_per_shared_page
        for k, n in st["lanes_per_shared_page"].items():
            hist[k] = hist.get(k, 0) + n

    def _build_step(self, plan: StepPlan,
                    device_feed: bool = False) -> StepBatch:
        """Build the whole step's static-shape arrays from the plan — ONE
        construction path shared by the sync loop and the async pipeline.
        With ``device_feed`` decode rows take their input token from the
        device-resident lane feed (-1) instead of a host value, so the
        plan can be built before the previous step's tokens reach the
        host."""
        if self._rec_leaves and plan.prefill:
            self._reset_or_restore_state(plan.prefill)
        if self._should_pack(plan):
            return self._build_packed(plan, device_feed)

        B = self.ecfg.num_lanes
        NP = self.scheduler.pages_per_lane
        mgr = self.scheduler.manager
        off = self._patch_offset

        page_table = np.full((B, NP), -1, np.int32)
        cache_len = np.zeros(B, np.int32)
        lane_mask = np.zeros(B, bool)
        S = (bucket_len(max(c.n for c in plan.prefill),
                        self.scheduler.prefill_buckets) or
             max(c.n for c in plan.prefill)) if plan.prefill else 1
        tokens = np.zeros((B, S), np.int32)
        positions = np.zeros((B, S), np.int32)
        slot_idx = np.full((B, S), -1, np.int32)      # Eq. 5 SkipSet: pads
        pad_mask = np.zeros((B, S), bool)
        last_pos = np.zeros(B, np.int32)
        feed = np.full(B, -2, np.int32)
        scatter_lane = np.full(B, B, np.int32)        # B = drop
        samples: List[Tuple[Request, bool, Tuple[int, ...]]] = []

        for c in plan.prefill:
            lane, n = c.req.lane, c.n
            # token column j holds position start+j; columns inside the
            # vlm patch-stub prefix carry a placeholder id (the model
            # swaps in the patch embedding by position)
            pcols = min(max(off - c.start, 0), n)
            tokens[lane, pcols:pcols + len(c.tokens)] = c.tokens
            positions[lane] = np.minimum(c.start + np.arange(S),
                                         c.start + n - 1)
            slot_idx[lane, :n] = mgr.slot_indices(
                c.req.pool_id, np.arange(c.start, c.start + n))
            page_table[lane] = self.scheduler.page_table(c.req)
            cache_len[lane] = c.start + n
            pad_mask[lane, :n] = True
            last_pos[lane] = n - 1
            lane_mask[lane] = True
            if c.final:
                samples.append((c.req, True, (lane,)))
                scatter_lane[lane] = lane
        for d in plan.decode:                          # a chunk of length 1
            lane = d.req.lane
            tokens[lane, 0] = d.req.output[-1] if d.req.output else 0
            positions[lane] = d.pos
            slot_idx[lane, 0] = d.slot
            page_table[lane] = self.scheduler.page_table(d.req)
            cache_len[lane] = d.pos + 1
            pad_mask[lane, 0] = True
            last_pos[lane] = 0
            lane_mask[lane] = True
            samples.append((d.req, False, (lane,)))
            scatter_lane[lane] = lane
            if device_feed:
                feed[lane] = -1        # device lane feed, never host-sync
        if len(plan.decode) > 1:
            self._note_sharing(page_table[[d.req.lane
                                           for d in plan.decode]])

        if device_feed and not plan.prefill:
            # decode fast path: one fused metadata upload (unpacked in
            # _async_step_impl) + constant zero tokens (device lane feed)
            batch = {"dmeta": jnp.asarray(np.stack(
                         [positions[:, 0], slot_idx[:, 0], cache_len])),
                     "page_table": self._dev_const(page_table),
                     "token": self._dev_const(np.zeros_like(tokens))}
            return StepBatch(kind="decode", batch=batch,
                             lane_mask=lane_mask, plan=plan,
                             samples=samples, tp=0, td=len(plan.decode),
                             feed=feed,
                             row_lane=np.arange(B, dtype=np.int32),
                             scatter_lane=scatter_lane)
        batch = {"positions": jnp.asarray(positions),
                 "slot_idx": jnp.asarray(slot_idx),
                 "page_table": self._dev_const(page_table),
                 "cache_len": jnp.asarray(cache_len)}
        if plan.prefill:
            batch.update(tokens=jnp.asarray(tokens),
                         pad_mask=jnp.asarray(pad_mask),
                         last_pos=jnp.asarray(last_pos))
            if self.cfg.family == "vlm":
                batch["patches"] = jnp.zeros((B, off, self.cfg.d_model),
                                             jnp.bfloat16)
            if self.cfg.family == "whisper":
                firsts = np.zeros(B, bool)
                for c in plan.prefill:
                    firsts[c.req.lane] |= c.first
                if firsts.any():
                    # cross-KV is computed ONCE per request, on its first
                    # chunk; steps without one skip the encoder entirely
                    batch["frames"] = jnp.zeros(
                        (B, self.cfg.num_frames, self.cfg.d_model),
                        jnp.bfloat16)
                    batch["cross_mask"] = jnp.asarray(firsts)
            kind = "prefill"
        else:
            batch["token"] = jnp.asarray(tokens)
            kind = "decode"

        return StepBatch(kind=kind, batch=batch, lane_mask=lane_mask,
                         plan=plan, samples=samples,
                         tp=sum(c.n for c in plan.prefill),
                         td=len(plan.decode), feed=feed,
                         row_lane=np.arange(B, dtype=np.int32),
                         scatter_lane=scatter_lane)

    def _build_packed(self, plan: StepPlan,
                      device_feed: bool = False) -> StepBatch:
        """Concat-prefill packing: several prompts' chunks share one row as
        SEGMENTS, with per-row segment ids (``seg_q``/``page_seg``) and
        per-segment logical page indices (``page_base``) threaded to the
        segment-aware chunk kernels so attention cannot leak across packed
        prompts. Decode items keep one row each (their token feeds the
        async lane plumbing); rows are padded to a power-of-two bucket, so
        short-prompt steps run with FEWER rows than lanes — the packed
        win."""
        ps = self.coopt.page_size
        NP = self.scheduler.pages_per_lane
        G = self.ecfg.pack_slots
        mgr = self.scheduler.manager

        S = (bucket_len(max(c.n for c in plan.prefill),
                        self.scheduler.prefill_buckets) or
             max(c.n for c in plan.prefill))
        rows = pack_rows(plan.prefill, S, G, NP, ps)
        n_rows = len(plan.decode) + len(rows)
        R = 1
        while R < n_rows:
            R *= 2
        R = min(R, max(self.ecfg.num_lanes, n_rows))
        B = self.ecfg.num_lanes

        tokens = np.zeros((R, S), np.int32)
        positions = np.zeros((R, S), np.int32)
        seg_q = np.full((R, S), -1, np.int32)        # -1 matches no page
        slot_idx = np.full((R, S), -1, np.int32)
        page_table = np.full((R, NP), -1, np.int32)
        page_seg = np.zeros((R, NP), np.int32)
        page_base = np.zeros((R, NP), np.int32)
        cache_len = np.zeros(R, np.int32)
        pad_mask = np.zeros((R, S), bool)
        last_pos = np.zeros((R, G), np.int32)
        feed = np.full(R, -2, np.int32)
        row_lane = np.zeros(R, np.int32)
        scatter_lane = np.full(R * G, B, np.int32)   # num_lanes = drop
        samples: List[Tuple[Request, bool, Tuple[int, ...]]] = []

        for i, d in enumerate(plan.decode):          # one row per decode
            tokens[i, 0] = d.req.output[-1] if d.req.output else 0
            positions[i] = d.pos
            seg_q[i, 0] = 0
            slot_idx[i, 0] = d.slot
            pt = self.scheduler.page_table(d.req)
            page_table[i] = pt
            page_base[i] = np.arange(NP)
            cache_len[i] = d.pos + 1
            pad_mask[i, 0] = True
            row_lane[i] = d.req.lane
            scatter_lane[i * G] = d.req.lane
            samples.append((d.req, False, (i, 0)))
            if device_feed:
                feed[i] = -1
        if len(plan.decode) > 1:
            self._note_sharing(page_table[:len(plan.decode)])

        for j, row in enumerate(rows):
            r = len(plan.decode) + j
            t = pcur = g = 0
            for k, c in enumerate(row.chunks):
                n, npg = c.n, chunk_pages(c, ps)
                tokens[r, t:t + n] = c.tokens
                positions[r, t:t + n] = c.start + np.arange(n)
                seg_q[r, t:t + n] = k
                slot_idx[r, t:t + n] = mgr.slot_indices(
                    c.req.pool_id, np.arange(c.start, c.start + n))
                page_table[r, pcur:pcur + npg] = \
                    self.scheduler.page_table(c.req)[:npg]
                page_seg[r, pcur:pcur + npg] = k
                page_base[r, pcur:pcur + npg] = np.arange(npg)
                pad_mask[r, t:t + n] = True
                if c.final:
                    last_pos[r, g] = t + n - 1
                    scatter_lane[r * G + g] = c.req.lane
                    samples.insert(g + sum(x.finals for x in rows[:j]),
                                   (c.req, True, (r, g)))
                    g += 1
                t += n
                pcur += npg
            cache_len[r] = t
            row_lane[r] = row.chunks[0].req.lane

        # prefill finals emit BEFORE decode tokens (matches the unpacked
        # emission order exactly)
        samples.sort(key=lambda s: not s[1])

        batch = {"positions": jnp.asarray(positions),
                 "slot_idx": jnp.asarray(slot_idx),
                 "page_table": jnp.asarray(page_table),
                 "cache_len": jnp.asarray(cache_len),
                 "tokens": jnp.asarray(tokens),
                 "pad_mask": jnp.asarray(pad_mask),
                 "last_pos": jnp.asarray(last_pos),
                 "seg_q": jnp.asarray(seg_q),
                 "page_seg": jnp.asarray(page_seg),
                 "page_base": jnp.asarray(page_base)}
        self.stats.packed_steps += 1
        self.stats.packed_rows_saved += max(
            len(plan.decode) + len(plan.prefill) - R, 0)
        return StepBatch(kind="packed", batch=batch,
                         lane_mask=np.ones(B, bool), plan=plan,
                         samples=samples,
                         tp=sum(c.n for c in plan.prefill),
                         td=len(plan.decode), feed=feed, row_lane=row_lane,
                         scatter_lane=scatter_lane)

    def _execute(self, sb: StepBatch):
        """Synchronous dispatch: run the step, block, attribute wall time
        by planned token share (a prefill-heavy mixed step must not book
        its whole wall time under decode — Eq. 12), and log the step (a
        trace during the call means it compiled; fetched once
        ``block_until_ready`` returns)."""
        if self.faults is not None:
            self.faults.before_execute(sb)
        fn = {"prefill": self._prefill_fn, "decode": self._decode_fn,
              "packed": self._packed_fn}[sb.kind]
        traces = sum(self.trace_counts.values())
        t0 = time.perf_counter()
        logits, self.cache, _ = fn(self.params, sb.batch, self.cache,
                                   self._dev_const(sb.lane_mask))
        t_dispatched = time.perf_counter()
        logits.block_until_ready()
        t_fetched = time.perf_counter()
        self._count_step(sb)
        self._book_time(sb, t_fetched - t0)
        rec = self._log_step(
            sb, sum(self.trace_counts.values()) != traces, t_dispatched)
        rec.t_fetched = t_fetched
        return logits

    def _count_step(self, sb: StepBatch) -> None:
        if sb.tp:
            self.stats.prefill_calls += 1
        if sb.td:
            self.stats.decode_steps += 1
        if sb.tp and sb.td:
            self.stats.mixed_steps += 1

    def _book_time(self, sb: StepBatch, dt: float) -> None:
        share = dt / max(sb.tp + sb.td, 1)
        self.stats.prefill_time += share * sb.tp
        self.stats.decode_time += share * sb.td

    def _log_step(self, sb: StepBatch, compiled: bool,
                  t_dispatched: float) -> steplog.StepRecord:
        """Append the step's record to the step log; host values only."""
        rows, bucket = sb.batch["tokens" if "tokens" in sb.batch
                                else "token"].shape
        plan = sb.plan
        rec = steplog.StepRecord(
            engine=self.engine_id, seq=self.steps_dispatched, kind=sb.kind,
            rows=rows, bucket=bucket, real_tokens=sb.tp + sb.td,
            chunks=tuple((c.start, c.n) for c in plan.prefill),
            decode_ctx=tuple(d.pos + 1 for d in plan.decode),
            req_ids=tuple(c.req.req_id for c in plan.prefill)
            + tuple(d.req.req_id for d in plan.decode),
            compiled=compiled, t_dispatched=t_dispatched)
        self.steps_dispatched += 1
        sb.record = rec
        steplog.RECENT.append(rec)
        return rec

    def _note_executed(self, sb: StepBatch) -> None:
        """Host metadata updates that must land before the NEXT plan is
        built (they do not depend on sampled token VALUES): advance
        prefill progress, register prefix pages, snapshot recurrent
        state."""
        for c in sb.plan.prefill:
            self.scheduler.note_prefilled(c.req, c.n)
            if self._rec_leaves:
                self._snapshot_state(c)

    def _postprocess(self, sb: StepBatch, toks: np.ndarray,
                     now: float) -> None:
        """Route host-visible sampled tokens back to their requests and
        retire the finished ones."""
        for req, first, idx in sb.samples:
            self._emit(req, int(toks[idx]), now, first=first)
        self._finish_done([req for req, _, _ in sb.samples])

    def _run_mixed(self, plan: StepPlan, schedule_s: float = 0.0) -> None:
        """One device call for the whole step, for EVERY model family:
        prefill chunks + decode tokens through the chunked-continuation
        path (a decode lane is a chunk of length 1). A step with only
        decode lanes takes the one-token decode kernel — same composition,
        S == 1, with the block-sparse ``long_window`` policy available.
        With ``pack_prefill`` the prefill chunks run through the packed
        concat-prefill layout instead."""
        t0 = time.perf_counter()
        with TraceAnnotation("serve.build"):
            sb = self._build_step(plan)
        t1 = time.perf_counter()
        with TraceAnnotation("serve.dispatch", seq=self.steps_dispatched):
            logits = self._execute(sb)
        t2 = time.perf_counter()
        with TraceAnnotation("serve.apply"):
            toks = self._sample(logits)
            self._note_executed(sb)
            self._postprocess(sb, toks, time.perf_counter())
        rec = sb.record
        rec.schedule_s, rec.build_s = schedule_s, t1 - t0
        rec.dispatch_s = rec.t_dispatched - t1
        rec.apply_s = time.perf_counter() - t2

    # ------------------------------------------------- async step dispatch --
    def _async_key(self, kind: str, batch: Dict[str, jnp.ndarray]) -> tuple:
        """AOT executable key: the step kind plus every batch array's
        (name, shape, dtype). ``lane_tok``/``feed``/``row_lane``/
        ``scatter_lane`` shapes are functions of these, and params/cache
        shapes are fixed per engine, so this pins the whole signature."""
        return (kind,) + tuple(sorted(
            (k, tuple(v.shape), str(v.dtype)) for k, v in batch.items()))

    def _dev_const(self, arr: np.ndarray) -> jnp.ndarray:
        """Device-memoized small host array (recurs across steps)."""
        k = (arr.dtype.str, arr.shape, arr.tobytes())
        v = self._dev_cache.get(k)
        if v is None:
            if len(self._dev_cache) > 512:
                self._dev_cache.clear()
            v = self._dev_cache[k] = jnp.asarray(arr)
        return v

    def _async_args(self, sb: StepBatch, lane_tok, key):
        return (self.params, sb.batch, self.cache,
                self._dev_const(sb.lane_mask), lane_tok, key,
                self._dev_const(sb.feed), self._dev_const(sb.row_lane),
                self._dev_const(sb.scatter_lane))

    def _dispatch_async(self, sb: StepBatch, lane_tok):
        """Dispatch one pipeline step WITHOUT blocking: prefer the AOT
        executable warmed up for this shape (zero traces in steady state);
        fall back to the jit path and count the miss. Books the step
        counters and logs the step (``sb.record``); its time is booked
        when its tokens are applied. Returns ((tokens, expert counters),
        lane_tok), still on the device."""
        if self.faults is not None:
            self.faults.before_execute(sb)
        if self.ecfg.sampling.temperature > 0:
            self.key, sub = jax.random.split(self.key)
        else:
            sub = self.key               # greedy: argmax ignores the key
        args = self._async_args(sb, lane_tok, sub)
        fn = self._aot.get(self._async_key(sb.kind, sb.batch))
        if fn is not None:
            toks, self.cache, lane_tok = fn(*args)
        else:
            self.aot_misses += 1
            with TraceAnnotation("serve.compile"):
                toks, self.cache, lane_tok = self._step_fns[sb.kind](*args)
        self._log_step(sb, fn is None, time.perf_counter())
        self._count_step(sb)
        return toks, lane_tok

    # ------------------------------------------------------- AOT warmup ----
    def _dummy_batch(self, kind: str, R: int, S: int,
                     whisper_first: bool = True) -> Dict[str, jnp.ndarray]:
        """A shape-exact stand-in for one step's batch (values never run —
        ``lower().compile()`` only reads shapes/dtypes)."""
        B = self.ecfg.num_lanes
        NP = self.scheduler.pages_per_lane
        if kind == "decode":       # fused-dmeta schema (device-feed path)
            return {"dmeta": jnp.zeros((3, R), jnp.int32),
                    "page_table": jnp.full((R, NP), -1, jnp.int32),
                    "token": jnp.zeros((R, S), jnp.int32)}
        batch = {"positions": jnp.zeros((R, S), jnp.int32),
                 "slot_idx": jnp.full((R, S), -1, jnp.int32),
                 "page_table": jnp.full((R, NP), -1, jnp.int32),
                 "cache_len": jnp.zeros((R,), jnp.int32)}
        batch.update(tokens=jnp.zeros((R, S), jnp.int32),
                     pad_mask=jnp.zeros((R, S), bool))
        if kind == "packed":
            G = self.ecfg.pack_slots
            batch.update(last_pos=jnp.zeros((R, G), jnp.int32),
                         seg_q=jnp.full((R, S), -1, jnp.int32),
                         page_seg=jnp.zeros((R, NP), jnp.int32),
                         page_base=jnp.zeros((R, NP), jnp.int32))
            return batch
        batch["last_pos"] = jnp.zeros((R,), jnp.int32)
        if self.cfg.family == "vlm":
            batch["patches"] = jnp.zeros((B, self._patch_offset,
                                          self.cfg.d_model), jnp.bfloat16)
        if self.cfg.family == "whisper" and whisper_first:
            batch["frames"] = jnp.zeros(
                (B, self.cfg.num_frames, self.cfg.d_model), jnp.bfloat16)
            batch["cross_mask"] = jnp.zeros((B,), bool)
        return batch

    def _warmup_lattice(self) -> List[Tuple[str, Dict[str, jnp.ndarray]]]:
        """Every steady-state step shape the async pipeline can dispatch:
        one decode shape, one prefill shape per bucket (whisper: with and
        without the first-chunk encoder), and — when packing — every
        (row-bucket x prefill-bucket) packed shape."""
        B = self.ecfg.num_lanes
        buckets = self.scheduler.prefill_buckets
        lattice = [("decode", self._dummy_batch("decode", B, 1))]
        for S in buckets:
            lattice.append(("prefill", self._dummy_batch("prefill", B, S)))
            if self.cfg.family == "whisper":
                lattice.append(("prefill", self._dummy_batch(
                    "prefill", B, S, whisper_first=False)))
        if self.ecfg.pack_prefill and self._pack_ok:
            row_buckets = []
            r = 1
            while r < B:
                row_buckets.append(r)
                r *= 2
            row_buckets.append(B)
            for R in row_buckets:
                for S in buckets:
                    lattice.append(("packed",
                                    self._dummy_batch("packed", R, S)))
        return lattice

    def _lattice_args(self):
        """Yield ``(kind, AOT key, step arguments)`` for every shape in
        the bucket lattice (``_warmup_lattice``)."""
        B = self.ecfg.num_lanes
        lane_tok = jnp.zeros((B,), jnp.int32)
        key = jax.random.PRNGKey(0)
        for kind, batch in self._warmup_lattice():
            R = batch["page_table"].shape[0]
            n_slots = batch["last_pos"].size if kind == "packed" else R
            sb = StepBatch(kind=kind, batch=batch,
                           lane_mask=np.ones(B, bool), plan=StepPlan(),
                           samples=[], tp=0, td=0,
                           feed=np.full(R, -2, np.int32),
                           row_lane=np.zeros(R, np.int32),
                           scatter_lane=np.full(n_slots, B, np.int32))
            yield (kind, self._async_key(kind, batch),
                   self._async_args(sb, lane_tok, key))

    def warmup(self) -> int:
        """AOT-compile (``lower().compile()``) the async step executable
        for EVERY shape in the bucket lattice, so steady-state serving
        never traces or compiles. Returns the number of executables built.
        Compiled executables bypass the jit call cache entirely — dispatch
        looks them up by shape key (``_dispatch_async``)."""
        built = 0
        for kind, akey, args in self._lattice_args():
            if akey in self._aot:
                continue
            self._aot[akey] = self._step_fns[kind].lower(*args).compile()
            built += 1
        return built

    # ---------------------------------------------------------------- API --
    def add_request(self, req: Request) -> None:
        req.enqueue_time = time.perf_counter()
        self.scheduler.add_request(req)

    def abort_all(self, exc: Optional[BaseException] = None
                  ) -> List[Request]:
        """Fault drain: terminate every live request with ERROR, returning
        the pool to zero pages in use. Returns the drained requests so the
        caller (sync loop re-raise, async ``_fail``) can surface the fault
        per stream."""
        drained = self.scheduler.abort_all(FinishReason.ERROR, exc)
        self.stats.errors += len(drained)
        self._abort_prefetch_flights()
        self._update_pool_stats()
        return drained

    def step(self) -> None:
        t0 = time.perf_counter()
        with TraceAnnotation("serve.schedule"):
            plan = self.scheduler.schedule_step()
        if plan.empty:
            self._update_pool_stats()       # rejections still count
            return
        try:
            self._run_mixed(plan, time.perf_counter() - t0)
        except Exception as exc:
            # a step fault must not leak pool pages or strand requests:
            # drain everything as ERROR, then surface the fault
            self.abort_all(exc)
            raise
        self._update_pool_stats()

    def run(self, max_steps: int = 100_000) -> None:
        steps = 0
        while self.scheduler.has_work and steps < max_steps:
            self.step()
            steps += 1

    def generate(self, prompts: Sequence[np.ndarray], max_new_tokens: int = 32,
                 eos_token: Optional[int] = None,
                 return_requests: bool = False):
        """Serve ``prompts`` to completion. Returns the per-prompt output
        token lists (or the full Request objects with ``return_requests`` —
        inspect ``state`` to distinguish FINISHED from REJECTED; rejected
        requests surface with empty output and are counted in
        ``stats.rejected``). Requests are stamped with REAL submission
        times (monotonic clock, submission order preserved by ``req_id``
        tie-break), so ``stats.latency_summary()`` reports TTFT and queue
        wait measured from submission."""
        reqs = []
        for i, p in enumerate(prompts):
            now = time.perf_counter()
            reqs.append(Request(req_id=1000 + i,
                                prompt=np.asarray(p, np.int32),
                                max_new_tokens=max_new_tokens,
                                eos_token=eos_token,
                                arrival_time=now, submit_time=now))
        for r in reqs:
            self.add_request(r)
        self.run()
        if return_requests:
            return reqs
        return [r.output for r in reqs]
