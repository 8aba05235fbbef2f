"""Published peaks of each chip, keyed by the ``device_kind`` JAX reports.

A device that is not in the table is an error, not a default: a share of a
peak is only as true as the peak.
"""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s
    # int8, 16 GB of HBM at 819 GB/s per chip
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


class UnknownDevice(RuntimeError):
    """The chip has no entry in the peaks table."""


def peaks_for(kind: str) -> dict:
    if kind not in PEAKS:
        raise UnknownDevice(f"no published peaks for device kind {kind!r}; "
                            f"known: {sorted(PEAKS)}")
    return PEAKS[kind]
