"""Peak rates of the chips the benchmark runs on, keyed by ``device_kind``.

A device that is not in the table is an error: a roofline against a
guessed peak is no roofline.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float      # FLOP/s
    hbm_bytes_per_s: float
    hbm_bytes: float
    source: str


TABLE = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s
    # int8, 16 GB HBM2 at 819 GB/s per chip.  JAX names the chip
    # "TPU v5 lite".
    "TPU v5 lite": Peaks(197e12, 819e9, 16e9, "Google Cloud documentation, TPU v5e"),
}


def for_kind(kind: str) -> Peaks:
    if kind not in TABLE:
        raise KeyError(f"no peaks for device kind {kind!r}; known: {sorted(TABLE)}")
    return TABLE[kind]
