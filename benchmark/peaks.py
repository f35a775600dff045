"""Published per-chip peaks, keyed by what JAX reports as ``device_kind``.

Copied from ``paddle_tpu/observability/perf.py`` ``DEVICE_SPECS`` (PR 21) so
that a later PR to the program cannot move the yardstick. Source: Google
Cloud TPU documentation, "System architecture" page of each version
("TPU v5e": 197 TFLOP/s bf16, 16 GB of HBM at 819 GB/s; "TPU v4": 275, 32,
1,200; "TPU v5p": 459, 95, 2,765; "TPU v6e": 918, 32, 1,640). A device kind
the table lacks is an error, never another chip's peak.
"""
from __future__ import annotations

from typing import NamedTuple


class Peak(NamedTuple):
    flops: float        # bf16 FLOP/s
    hbm_bytes: float    # bytes of HBM
    hbm_bw: float       # bytes/s


PEAKS = {
    "v4": Peak(275e12, 32e9, 1.20e12),
    "v5p": Peak(459e12, 95e9, 2.765e12),
    "v5e": Peak(197e12, 16e9, 8.19e11),
    "v5 lite": Peak(197e12, 16e9, 8.19e11),     # what a v5e chip reports
    "v6e": Peak(918e12, 32e9, 1.64e12),
    "v6 lite": Peak(918e12, 32e9, 1.64e12),     # what a v6e chip reports
}


def peak(device_kind: str) -> Peak:
    kind = (device_kind or "").lower()
    for key, val in PEAKS.items():
        if key in kind:
            return val
    raise KeyError(f"no published peak for device kind {device_kind!r}; "
                   "add it to benchmark/peaks.py with its source")
