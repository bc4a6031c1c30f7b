"""Published peaks of the chips the benchmark knows, keyed by ``device_kind``
exactly as JAX reports it.  A device that is not in the table is an error,
never a default: a share of an assumed peak is a number about no machine.

Source for "TPU v5 lite": Google Cloud documentation, "TPU v5e" (one chip:
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of
chip-to-chip interconnect).
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bits_per_s": 1600e9,
        "source": "Google Cloud documentation, 'TPU v5e'",
    },
}


def peaks_for(device_kind: str) -> dict:
    """The peaks of one chip of ``device_kind``; KeyError names the table."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peaks known for device_kind={device_kind!r}; add a row with "
            f"its source to benchmark/lib/peaks.py (known: {sorted(PEAKS)})"
        ) from None
