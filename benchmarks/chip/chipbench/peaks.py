"""Published peaks of the chips the benchmark may run on, keyed by the
``device_kind`` JAX reports. A device that is not in the table is an error.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16 and 819 GB/s of HBM bandwidth per chip, 16 GB of HBM.
"""

from __future__ import annotations

import dataclasses

__all__ = ["Peaks", "PEAKS", "UnknownDevice", "peaks_for"]


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops_per_s: float      # dense bf16 matrix peak
    hbm_bytes_per_s: float
    hbm_bytes: int
    source: str


_V5E = Peaks(flops_per_s=197e12, hbm_bytes_per_s=819e9, hbm_bytes=16 * 10**9,
             source='Google Cloud documentation, "TPU v5e"')

PEAKS = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


class UnknownDevice(KeyError):
    """The device kind has no entry in the peaks table."""


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}") from None
