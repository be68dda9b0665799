"""Production meshes (DESIGN.md §7).

Single pod: TPU v5e-256, mesh (data=16, model=16).
Multi-pod:  2 pods = 512 chips, mesh (pod=2, data=16, model=16) — pods are
data-parallel replicas; the "pod" axis only ever shards batch-like dims (or
KV pages for batch-1 long-context), so no tensor-parallel collective crosses
the inter-pod DCN link.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before any jax import).
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    # Auto axes: the models place activations with with_sharding_constraint
    # and the kernels run under shard_map, both of which want GSPMD-style
    # axes (jax.make_mesh defaults to Explicit ones)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh():
    """Degenerate 1-device mesh for smoke tests on the CPU container."""
    return _mesh((1, 1), ("data", "model"))


def make_sim_mesh(data: int = 4, model: int = 2, pod: int = 1):
    """Small mesh: (data, model) over the first data*model devices — four
    chips of one host, or simulated CPU devices (which need
    ``XLA_FLAGS=--xla_force_host_platform_device_count>=pod*data*model``
    set before the first jax import — see the CI mesh-matrix job)."""
    if pod > 1:
        return _mesh((pod, data, model), ("pod", "data", "model"))
    return _mesh((data, model), ("data", "model"))


def kv_shard_count(mesh) -> int:
    """Number of KV-pool page-range shards a mesh implies: the product of
    the mesh axes the cache ``pages`` axis is sharded over
    (``core.opt_kv.PAGES_AXES``, the same partition CACHE_RULES and the
    ``kernels.sharded`` shard_map layer use). Feed this to
    ``EngineConfig.num_shards`` so the host allocator's page ranges coincide
    with device shard boundaries — ``serving.Engine`` derives/checks this
    itself when handed a mesh."""
    from repro.core.opt_kv import PAGES_AXES
    return math.prod(mesh.shape[a] for a in PAGES_AXES if a in mesh.shape)


@dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peaks — the roofline denominators."""
    bf16_flops: float               # FLOP/s
    hbm_bw: float                   # B/s
    hbm_bytes: float
    ici_bw: float                   # B/s per chip-to-chip link
    source: str


# keyed by jax ``Device.device_kind``; a kind not listed has no peaks
PEAKS = {
    "TPU v5 lite": ChipPeaks(
        bf16_flops=197e12, hbm_bw=819e9, hbm_bytes=16e9,
        ici_bw=1600e9 / 8 / 4,      # 1,600 Gbit/s over the chip's 4 links
        source='Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
               "16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI per chip"),
}


def chip_peaks(device_kind: str) -> ChipPeaks:
    """Peaks of a chip by its ``device_kind``; raises for an unknown kind
    (a roofline against a guessed chip is no roofline)."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") \
            from None


# the chip the production meshes and dry-run cells are sized for
TARGET_DEVICE_KIND = "TPU v5 lite"
