"""Published per-chip peaks, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture,
chip specifications): 197 TFLOP/s bf16 and 819 GB/s of HBM bandwidth per
chip.  No f32 peak is published; the MXU's bf16 rate bounds f32 work
from above, so a share of it is a lower bound on the f32 share.

A TPU kind missing from the table is an error, not a default: its peaks
would be guessed.  Off the TPU there is no roofline to report.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": dict(bf16_flops=197e12, hbm_Bps=819e9),
}


def device_peaks(device) -> dict | None:
    """The peaks of ``device`` (a ``jax.Device``); ``None`` off the TPU.

    Raises ``KeyError`` for a TPU kind that has no published entry.
    """
    if device.platform != "tpu":
        return None
    try:
        return PEAKS[device.device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for TPU kind {device.device_kind!r}; "
            f"add them to repro.exec.peaks.PEAKS with their source") from None
