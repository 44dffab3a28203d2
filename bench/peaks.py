"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``. A device that is not listed is an error, not a default.
"""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict] = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,      # FLOP/s per chip
        "hbm_bytes_per_s": 819e9,  # bytes/s per chip
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e'",
    },
}


def peaks(device_kind: str) -> Dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device_kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
