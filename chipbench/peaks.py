"""Published peaks per chip, keyed by the ``device_kind`` JAX reports.

Source: Google Cloud documentation, "TPU v5e" (per chip): 197 TFLOP/s
bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s.  The table gives no peak for
the vector unit, where both kernels of the forest query do their
arithmetic (elementwise compares and distances), so their rooflines are
taken against the HBM bound alone.  A device that is not listed is an
error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
