"""Published peaks of each accelerator the benchmark may run on, keyed by
JAX's `device_kind`.  Source: Google Cloud documentation, "TPU v5e"
(per chip: 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s).
"""
from __future__ import annotations

_V5E = dict(bf16_flops_per_s=197e12, int8_ops_per_s=393e12,
            hbm_bytes_per_s=819e9, hbm_bytes=16e9,
            source="Google Cloud documentation, TPU v5e")

PEAKS = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def peaks_for(device_kind: str) -> dict:
    """The peaks row of `device_kind`; an unknown device is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
