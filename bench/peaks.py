"""Per-chip peaks, keyed by ``jax.devices()[i].device_kind``.

A kind that is not in the table is an error, never a default: another
chip's peaks would make every share of a peak wrong.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,  # FLOP/s
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e' system "
                  "architecture: 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, "
                  "1,600 Gbit/s ICI per chip",
    },
}


def peaks(device_kind: str) -> dict:
    """The `PEAKS` row for ``device_kind``; raises KeyError for a chip that
    is not in the table."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak table for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)} (add a row with its "
                       "source)") from None
