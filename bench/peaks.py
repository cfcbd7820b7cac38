"""Published peaks of each accelerator, keyed by ``device.device_kind``.

Source: Google Cloud documentation, "TPU v5e" (per-chip figures).  A
device that is not in the table is an error, never a default: a roofline
share against a guessed peak reads as a measurement.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks_for(device_kind: str) -> dict:
    """The peak table row of ``device_kind``; raises ``KeyError`` for a
    device the table does not know."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"add a row with its source to bench/peaks.py") from None
