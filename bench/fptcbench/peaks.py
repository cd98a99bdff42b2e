"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``.  Source: Google Cloud documentation, "TPU v5e" (system
architecture table): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at
819 GB/s per chip.  A kind that is not in the table is an error."""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}
PEAKS["TPU v5e"] = PEAKS["TPU v5 lite"]


def peaks_for(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}"
        ) from None
