"""Published peaks of the chips the benchmark runs on, keyed by
``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture
table): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GiB of HBM2 at 819 GB/s
per chip.  A device missing from the table is an error, never a
default.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peak:
    bf16_flops: float        # FLOP/s
    hbm_bytes_s: float       # bytes/s
    hbm_bytes: float         # bytes of device memory


PEAKS = {
    "TPU v5 lite": Peak(bf16_flops=197e12, hbm_bytes_s=819e9,
                        hbm_bytes=16 * 2**30),
}


def peak_for(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peak for device kind "
                       f"{device_kind!r}; add it to bench/peaks.py "
                       f"with its source") from None
