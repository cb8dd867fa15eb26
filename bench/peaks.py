"""Published peaks per chip, keyed by JAX's `device_kind`.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
393 TOP/s int8, 16 GB of HBM at 819 GB/s per chip.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12,
                    "int8_ops_per_s": 393e12, "hbm_bytes": 16e9},
}


def peak(device_kind: str, what: str) -> float:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to bench/peaks.py")
    return PEAKS[device_kind][what]


def sampler_output_bytes(batch: int) -> int:
    """Bytes a sampler batch must write, whatever computes it: four int32
    or float32 outputs (key ranks, op kinds, value sizes, gaps) per op."""
    return 4 * 4 * batch
