"""Published per-chip peaks, keyed by ``jax.devices()[0].device_kind``.

A device that is not in the table has no peak to be compared with: asking
for it raises, it does not default to another chip's numbers.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

PEAKS: Dict[str, Dict[str, object]] = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197.0e12,
        "hbm_bytes_per_s": 819.0e9,
        "hbm_bytes": 16.0e9,
        "source": 'Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
                  "16 GB HBM2e at 819 GB/s per chip",
    },
}


def peaks(device_kind: str) -> Dict[str, object]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}") from None


def least_seconds(flops: float, bytes_: float,
                  peak: Dict[str, Any]) -> Tuple[float, str]:
    """(seconds, which bound sets them) on a chip with these peaks."""
    by_compute = flops / peak["bf16_flops_per_s"]
    by_bandwidth = bytes_ / peak["hbm_bytes_per_s"]
    if by_compute >= by_bandwidth:
        return by_compute, "compute"
    return by_bandwidth, "bandwidth"
