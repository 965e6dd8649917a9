"""Published peaks of the accelerators a cell may run on, keyed by the
``device_kind`` string JAX reports. A device that is not in the table is an
error, never a default: a roofline or utilization against a guessed peak is
not a measurement."""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e' system architecture "
                  "page: 197 TFLOP/s bf16, 16 GB HBM2 at 819 GB/s per chip",
    },
}


def peaks(device_kind: str) -> dict:
    """The peak table row of one device kind; ``KeyError`` for a device the
    table does not know."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device_kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
