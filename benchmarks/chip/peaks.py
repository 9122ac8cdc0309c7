"""Published peaks of the chips the benchmark runs on, by ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s
interconnect per chip. ``flops_per_s`` is the bf16 peak, the highest the
chip's matrix unit reaches; float32 work at ``highest`` runs below it, so a
share of it is never above the true one. A device that is not here is an
error, not a default.
"""

from __future__ import annotations

_V5E = {
    "flops_per_s": 197e12,
    "hbm_bytes_per_s": 819e9,
    "hbm_bytes": 16e9,
    "ici_bits_per_s": 1600e9,
    "source": "Google Cloud documentation, TPU v5e",
}

PEAKS = {
    "TPU v5 lite": _V5E,  # a v5e chip's device_kind
}


def peak_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peak for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
