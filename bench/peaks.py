"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``.  A device that is not in the table is an error.

Source: Google Cloud documentation, "TPU v5e" (per chip).
"""
from __future__ import annotations

SOURCE = "Google Cloud documentation, TPU v5e"

_V5E = {
    "bf16_flops_per_s": 197e12,
    "int8_ops_per_s": 393e12,
    "hbm_bytes_per_s": 819e9,
    "hbm_bytes": 16e9,
    "ici_bits_per_s": 1600e9,
}

PEAKS = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def peaks(device_kind: str) -> dict:
    try:
        return dict(PEAKS[device_kind])
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add them "
            f"with their source to bench/peaks.py") from None
