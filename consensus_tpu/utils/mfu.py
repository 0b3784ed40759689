"""Shared model-FLOPs-utilization accounting.

One implementation for the reporting surfaces (chip_smoke.py, bench.py,
the north-star timing report, and the scoring microbench) so the formula
and the peaks table cannot drift apart.  Accounting convention: useful FLOPs =
``2 * params * useful_token`` where useful tokens are generated + scored
tokens actually consumed by a caller — bucket padding, KV/weight HBM
traffic and host time all show up as LOST utilization, which is the
point of the number.  The embedding matrix counts once (it
is a gather on the way in and the head matmul on the way out).
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    """Published per-chip peaks of one accelerator."""

    bf16_tflops: float
    hbm_gb_per_s: float
    hbm_gb: float
    source: str


#: Keyed by ``jax.devices()[0].device_kind``.  A device that is not here has
#: no peak to be compared with: :func:`device_peaks` raises, it does not
#: default to some other chip's numbers.
DEVICE_PEAKS = {
    "TPU v5 lite": DevicePeaks(
        bf16_tflops=197.0,
        hbm_gb_per_s=819.0,
        hbm_gb=16.0,
        source='Google Cloud documentation, "TPU v5e" (int8 peak is 2x bf16)',
    ),
}


def device_peaks(device_kind: str) -> DevicePeaks:
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(DEVICE_PEAKS)}. Add its row to DEVICE_PEAKS with a "
            "source before reporting a share of peak on it."
        ) from None


def param_count(config) -> int:
    """Logical parameter count from a ModelConfig (quantization-agnostic)."""
    c = config
    attn = c.d_model * (c.n_heads * c.head_dim) * 2  # wq + wo
    attn += c.d_model * (c.n_kv_heads * c.head_dim) * 2  # wk + wv
    ffn = 3 * c.d_model * c.ffn_hidden  # gate, up, down
    norms = (4 if c.use_post_norms else 2) * c.d_model
    per_layer = attn + ffn + norms
    total = c.n_layers * per_layer + c.vocab_size * c.d_model + c.d_model
    if not c.tie_lm_head:
        total += c.vocab_size * c.d_model
    return int(total)


def useful_tflops_per_sec(n_params: int, tokens: int, wall_s: float) -> float:
    if wall_s <= 0:
        return 0.0
    return 2.0 * n_params * tokens / wall_s / 1e12


def pct_of_peak(tflops: float, device_kind: str, n_devices: int = 1) -> float:
    """Percent of the aggregate bf16 peak of ``n_devices`` chips of
    ``device_kind``.  ``n_devices`` scales the denominator to the mesh: a
    dp=4,tp=2 slice has 8 chips' worth of peak FLOPs, and quoting a
    multichip run against one chip's peak would flatter the number 8x."""
    peak = device_peaks(device_kind).bf16_tflops
    return 100.0 * tflops / (peak * max(1, int(n_devices)))
