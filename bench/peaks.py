"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``.  A device that is not in the table is an error, never a
default."""
from __future__ import annotations

from typing import NamedTuple


class Peaks(NamedTuple):
    flops_bf16: float    # FLOP/s, dense bf16 matrix units
    hbm_bytes_s: float   # B/s
    hbm_bytes: float     # device memory
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(
        flops_bf16=197e12, hbm_bytes_s=819e9, hbm_bytes=16e9,
        source='Google Cloud documentation, "TPU v5e" (per chip: 197 '
               'TFLOP/s bf16, 16 GB HBM at 819 GB/s)'),
}


class UnknownDevice(KeyError):
    """The device kind has no entry in :data:`PEAKS`."""


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}") from None
