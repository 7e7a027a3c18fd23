"""Per-chip peaks, keyed by ``jax.Device.device_kind``.

TPU v5e: Google Cloud documentation, "TPU v5e" — 197 TFLOP/s in bf16
(float32 matrix multiplications run on the same units), 16 GB of HBM at
819 GB/s.  A device kind that is not here is an error, not a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no peaks for device kind {device_kind!r} "
                         f"(known: {sorted(PEAKS)})") from None
