"""Published peaks of one chip, keyed by JAX's ``device_kind``.

A device that is not in the table is an error, never a default.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peak:
    flops_bf16: float      # FLOP/s
    hbm_bytes_per_s: float
    hbm_bytes: float
    source: str


PEAKS = {
    "TPU v5 lite": Peak(197e12, 819e9, 16e9,
                        'Google Cloud documentation, "TPU v5e": 197 TFLOP/s '
                        "bf16, 16 GB HBM2e at 819 GB/s per chip"),
}


def lookup(device_kind: str) -> Peak:
    if device_kind not in PEAKS:
        raise SystemExit(
            f"no published peaks for device kind {device_kind!r}; the table "
            f"has {sorted(PEAKS)}")
    return PEAKS[device_kind]
