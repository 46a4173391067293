"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports. A kind that is not here is an error: a
roofline share against a guessed peak means nothing."""
from __future__ import annotations

from typing import Dict

# Google Cloud documentation, "TPU v5e" (system architecture): per chip
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s.
V5E = {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes_per_s": 819e9,
       "hbm_bytes": 16e9, "source": "Google Cloud documentation, TPU v5e"}

PEAKS: Dict[str, Dict[str, object]] = {
    "TPU v5 lite": V5E,
    "TPU v5e": V5E,
}


def peaks_for(device_kind: str) -> Dict[str, object]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
