"""Published peaks of the chips the benchmark may run on, keyed by the
``device_kind`` JAX reports.  A kind that is not here is an error: a
roofline or MFU against a guessed peak means nothing."""
from __future__ import annotations

from typing import Dict

#: Google Cloud documentation, "TPU v5e" (system architecture page):
#: 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s per chip.
_V5E = {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "source": "cloud.google.com/tpu/docs/v5e (TPU v5e per-chip peaks)"}

PEAKS: Dict[str, Dict] = {
    "TPU v5 lite": _V5E,   # what JAX reports as device_kind on a v5e
    "TPU v5e": _V5E,
}


def peaks_for(device_kind: str) -> Dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"(have {sorted(PEAKS)}); add them with their "
                       f"source to bench/peaks.py") from None
