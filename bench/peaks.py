"""Published peaks per chip, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (per chip: 197 TFLOP/s
bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s).  A device that is not in
the table is an error: a share of a peak is never taken against a guess.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flop_per_s": 197e12,
        "int8_op_per_s": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
    },
}


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; raises KeyError for an unknown chip."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device_kind {device_kind!r}; known: "
                       f"{sorted(PEAKS)}") from None
