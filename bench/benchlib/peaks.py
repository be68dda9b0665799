"""Published per-chip peaks, keyed by JAX's ``Device.device_kind``.

A device kind that is not listed has no peaks, and the benchmark refuses to
run on it: a share of a guessed peak is no share.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,        # FLOP/s
        "hbm_bw": 819e9,             # B/s
        "hbm_bytes": 16e9,
        "source": 'Google Cloud documentation, "TPU v5e": 197 TFLOP/s '
                  "bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s",
    },
}


def chip_peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None
