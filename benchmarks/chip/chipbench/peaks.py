"""Published per-chip peaks, keyed by JAX's ``device_kind``.

A copy of ``repro.exec.peaks``, kept with the benchmark so that the
yardstick cannot move.  Source: Google Cloud documentation, "TPU v5e"
(system architecture, chip specifications): 197 TFLOP/s bf16 and 819 GB/s
of HBM bandwidth per chip.  No f32 peak is published; the bf16 rate
bounds f32 work from above.  A kind missing from the table is an error.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": dict(bf16_flops=197e12, hbm_Bps=819e9),
}


def peaks_for(device_kind: str) -> dict:
    """The peaks of a device kind; ``KeyError`` for one with no entry."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for {device_kind!r}; add them "
                       f"to chipbench.peaks.PEAKS with their source") from None
