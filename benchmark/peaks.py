"""Published per-chip peaks, keyed by jax's ``device_kind``.

Source: Google Cloud TPU documentation, one page per generation
("TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s;
"TPU v4": 275 TFLOP/s bf16, 32 GB at 1228 GB/s; "TPU v5p": 459 TFLOP/s
bf16, 95 GB at 2765 GB/s; "TPU v6e": 918 TFLOP/s bf16, 32 GB at
1640 GB/s).  Copied from ``paddle_tpu/observability/profile.py``
``CHIP_SPECS`` so that a later PR to the program cannot move the yardstick.
A device that is not in the table is an error, never a default.
"""
from __future__ import annotations

from typing import NamedTuple


class Peak(NamedTuple):
    bf16_flops: float      # FLOP/s
    hbm_bytes_s: float     # bytes/s
    hbm_bytes: float       # bytes of device memory


PEAKS = {
    "TPU v4": Peak(275e12, 1228e9, 32e9),
    "TPU v5 lite": Peak(197e12, 819e9, 16e9),
    "TPU v5e": Peak(197e12, 819e9, 16e9),
    "TPU v5": Peak(459e12, 2765e9, 95e9),
    "TPU v5p": Peak(459e12, 2765e9, 95e9),
    "TPU v6 lite": Peak(918e12, 1640e9, 32e9),
    "TPU v6e": Peak(918e12, 1640e9, 32e9),
}


def peak_for(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise LookupError(
            f"device kind {device_kind!r} is not in benchmark/peaks.py; "
            f"add its published peaks with their source") from None
