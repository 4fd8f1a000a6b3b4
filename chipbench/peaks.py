"""Published peaks of the devices the benchmark runs on, keyed by JAX's
``device_kind``.  A kind that is not here is an error, never a default.

Source: Google Cloud documentation, "TPU v5e" (Cloud TPU system
architecture): per chip 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM2 at
819 GB/s, 1,600 Gbit/s of inter-chip interconnect."""
from __future__ import annotations

_V5E = dict(hbm_bytes=16 * 10**9, hbm_bytes_per_s=819e9,
            bf16_flops_per_s=197e12, int8_ops_per_s=393e12,
            ici_bits_per_s=1600e9,
            source='Google Cloud documentation, "TPU v5e"')

PEAKS = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"chipbench/peaks.py knows {sorted(PEAKS)}") from None
