"""Published peaks of the chips the benchmark runs on, keyed by
``device_kind`` as JAX reports it.

Source: Google Cloud documentation, "TPU v5e" (per chip: 197 TFLOP/s
bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI).
A device kind that is not in the table is an error, never a default.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peak_for(device_kind: str) -> dict:
    """The peaks of ``device_kind``; raises ``KeyError`` for a chip the
    table does not describe."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak table entry for device kind {device_kind!r} "
                       f"(known: {sorted(PEAKS)})") from None
