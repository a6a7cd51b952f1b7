"""Published per-chip peaks, keyed by ``jax.devices()[0].device_kind``.

A copy kept with the benchmark, so that a change to the program cannot
move the yardstick. "TPU v5 lite" is the TPU v5e. Source: Google Cloud
documentation, "TPU v5e": 197 TFLOP/s in bf16, 394 TOP/s in int8, 16 GB of
HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    """The peaks of one chip of ``device_kind``; an unknown kind raises."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       "add them to bench/lib/peaks.py with their source")
    return PEAKS[device_kind]
