"""Published peaks of each accelerator the benchmark may run on.

Keyed by ``jax.Device.device_kind``. A device that is not in the table is an
error, never a default: a roofline or utilization share against a guessed
peak means nothing.
"""
from __future__ import annotations

#: Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e):
#: 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks_for(device_kind: str) -> dict:
    """The peaks of ``device_kind``; raises ``KeyError`` naming the kinds
    the table holds when it is missing."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"device kind {device_kind!r} is not in the peaks table "
            f"(known: {sorted(PEAKS)})"
        ) from None
