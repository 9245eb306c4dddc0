"""Published peaks of the chips the benchmark runs on, keyed by
``jax.Device.device_kind``.  A kind that is missing is an error, never a
default: a number divided by the wrong peak is worse than none."""
from __future__ import annotations

from typing import Dict

__all__ = ["PEAKS", "peak"]

PEAKS: Dict[str, Dict[str, object]] = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "ici_bits_per_s": 1600e9,
        "source": "Google Cloud documentation, 'TPU v5e' (system architecture: "
                  "peak compute per chip, HBM2 capacity and bandwidth, ICI)",
    },
}


def peak(device_kind: str, key: str) -> float:
    """``PEAKS[device_kind][key]``; raises ``KeyError`` naming the kind."""
    try:
        return float(PEAKS[device_kind][key])
    except KeyError:
        raise KeyError(f"no published peak {key!r} for device kind "
                       f"{device_kind!r}; known kinds: {sorted(PEAKS)}") from None
