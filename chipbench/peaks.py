"""Published peaks of one chip, keyed by `jax.Device.device_kind`.

TPU v5e (Google Cloud documentation, "TPU v5e"): 197 TFLOP/s in bf16 and
819 GB/s of HBM bandwidth per chip; JAX reports the chip as "TPU v5 lite".
An f32 matmul at the TPU's default precision runs bf16 passes on the matrix
unit, so the bf16 peak is the ceiling of the served programs.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9},
}


def peaks(device_kind: str) -> dict:
    """The peaks of `device_kind`; a kind the table does not hold is an
    error, never priced as another chip."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
