"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` that JAX reports.

A kind that is not in the table is an error, never a default: a share of
a peak is only as good as the peak it is taken of.
"""
from __future__ import annotations

SOURCE_V5E = "Google Cloud documentation, 'TPU v5e' (Cloud TPU system architecture)"

_V5E = {
    "bf16_flops_per_s": 197e12,
    "int8_ops_per_s": 393e12,
    "hbm_bytes": 16e9,
    "hbm_bytes_per_s": 819e9,
    "ici_bits_per_s": 1600e9,
    "source": SOURCE_V5E,
}

PEAKS = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; KeyError if the table lacks it."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
