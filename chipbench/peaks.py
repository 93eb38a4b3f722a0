"""Published peaks of each chip the benchmark may run on, keyed by
``device_kind`` as JAX reports it.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 16 GB HBM at 819 GB/s per chip.  A device that is not
in the table is an error: no ratio is ever taken against a guessed peak.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flop_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud, TPU v5e system architecture",
    },
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}") from None
