"""Published peaks of one chip, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 819 GB/s of HBM bandwidth, 16 GB of HBM per chip.
A kind that is not in the table is an error, never a default.
"""

from __future__ import annotations

from typing import NamedTuple


class Peaks(NamedTuple):
    flops_per_s: float      # bf16 matrix units
    hbm_bytes_per_s: float
    hbm_bytes: float


_V5E = Peaks(197e12, 819e9, 16e9)

PEAKS = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


def peaks(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}: add a "
            f"sourced row to benchmark/lib/peaks.py") from None
