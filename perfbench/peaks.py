"""Published peaks of each accelerator, keyed by `device_kind`.

Source: Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/
v5e): per chip 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s.
JAX reports a v5e as "TPU v5 lite".  A kind missing here is an error.
"""
from __future__ import annotations

V5E = {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes_s": 819e9,
       "hbm_bytes": 16e9}

PEAKS = {"TPU v5 lite": V5E, "TPU v5e": V5E}


def for_kind(kind: str) -> dict:
    try:
        return PEAKS[kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {kind!r}; known: "
                       f"{sorted(PEAKS)}") from None
