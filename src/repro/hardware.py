"""Published per-chip peaks, keyed by the ``device_kind`` JAX reports.

Source: Google Cloud documentation, "TPU v5e" (system architecture): per
chip 197 TFLOP/s in bf16, 16 GB of HBM at 819 GB/s, and 1,600 Gbit/s of
inter-chip interconnect over four links (50 GB/s per link).

The kernel cost model (:mod:`repro.kernels.spmv.autotune`), the roofline
records (:mod:`repro.launch.roofline`) and the dry-run
(:mod:`repro.launch.dryrun`) all read their peaks here.  They model
:data:`TARGET_DEVICE_KIND` unless told the kind of the chip they run on.
A kind that is not in :data:`PEAKS` is an error, never a default.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict


@dataclass(frozen=True)
class ChipPeaks:
    """Peak rates of one chip."""

    flops_bf16: float          # FLOP/s, bf16 matmul
    hbm_bytes_per_s: float     # HBM bandwidth
    ici_bytes_per_s_link: float  # inter-chip interconnect, per link


PEAKS: Dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(flops_bf16=197e12, hbm_bytes_per_s=819e9,
                             ici_bytes_per_s_link=50e9),
}

#: the chip this repo's deployments are sized for (TPU v5e)
TARGET_DEVICE_KIND = "TPU v5 lite"


def chip_peaks(device_kind: str = TARGET_DEVICE_KIND) -> ChipPeaks:
    """Peaks of ``device_kind``; raises ``ValueError`` for a kind that is
    not in :data:`PEAKS`."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind {device_kind!r}; "
            f"known kinds: {sorted(PEAKS)}") from None


__all__ = ["ChipPeaks", "PEAKS", "TARGET_DEVICE_KIND", "chip_peaks"]
