"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

The benchmark keeps its own table so that the yardstick does not move when
the program's copy does.  A device kind that is not here is an error.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "hbm_bytes_s": 819e9,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
                  "393 TOP/s int8, 16 GB HBM at 819 GB/s",
    },
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}") from None
