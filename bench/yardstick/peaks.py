"""Published peaks per chip, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.  No
float32 vector-unit peak is published, so a min-plus kernel's compute
bound is taken at the bf16 peak; its roofline is then the HBM bound,
which is the larger of the two for every kernel measured here.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud TPU v5e documentation"},
}


def peak(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a kind not in the table is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to PEAKS with their "
                       f"source") from None


def least_seconds(flops: float, nbytes: float, device_kind: str) -> float:
    """The least time the chip could take: the larger of the compute
    bound and the memory bound."""
    p = peak(device_kind)
    return max(flops / p["flops_per_s"], nbytes / p["hbm_bytes_per_s"])
