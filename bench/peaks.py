"""Published peaks of the chips the benchmark may run on, keyed by the
``device_kind`` JAX reports. A kind that is not here is an error, never a
default: a roofline or utilization against a guessed peak means nothing.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16 and 819 GB/s of HBM bandwidth per chip, 16 GiB of HBM.
"""
from __future__ import annotations

PEAKS = {
    # device_kind: bf16 FLOP/s, HBM bytes/s, HBM bytes
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16 * 2**30,
                    "source": "Google Cloud TPU v5e documentation"},
}


def peaks_for(device_kind: str) -> dict:
    """The peaks of ``device_kind``; raises KeyError for an unlisted chip."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; known: {sorted(PEAKS)}"
        ) from None
