"""Peak rates of one chip, keyed by ``device_kind`` as JAX reports it."""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud documentation, TPU v5e: 197 TFLOP/s bf16, 819 GB/s HBM",
    },
}


def peak_for(kind: str) -> dict:
    """The peaks of ``kind``; a kind that is not in the table is an error."""
    if kind not in PEAKS:
        raise KeyError(f"no peak rates for device kind {kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[kind]
