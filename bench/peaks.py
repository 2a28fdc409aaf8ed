"""Peak rates of the chips the benchmark runs on, keyed by
``jax.Device.device_kind``.

TPU v5e: Google Cloud documentation, "TPU v5e" (system architecture):
per chip 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s.
JAX reports a v5e chip as ``"TPU v5 lite"``.

A kind that is not in the table is an error: a share of a peak is never
computed against another chip's numbers.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes": 16e9, "hbm_bytes_per_s": 819e9,
                    "source": 'Google Cloud documentation, "TPU v5e"'},
}


def peak(kind: str) -> dict:
    try:
        return PEAKS[kind]
    except KeyError:
        raise KeyError(f"no peak rates for device kind {kind!r}; known: "
                       f"{sorted(PEAKS)}") from None
