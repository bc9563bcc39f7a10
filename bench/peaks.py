"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16 and 819 GB/s HBM bandwidth per chip, 16 GB of HBM.
A device that is not in the table is an error, never a default.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops_per_s: float  # dense bf16 matrix peak of one chip
    hbm_bytes_per_s: float
    hbm_bytes: int
    source: str


PEAKS: dict[str, Peaks] = {
    "TPU v5 lite": Peaks(
        flops_per_s=197e12,
        hbm_bytes_per_s=819e9,
        hbm_bytes=16 * 10**9,
        source='Google Cloud documentation, "TPU v5e"',
    ),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; add them to "
            f"bench/peaks.py (known: {sorted(PEAKS)})"
        ) from None
