"""The least work the algorithm needs, from shapes and counts alone.

Every function takes the ``model`` block of a configuration file.  The work
counted is what a statement needs whatever the implementation does: useful
tokens only (no padding, no recomputation), weights read once per launch, a
cache position read once.  Nothing here looks at what the program ran, so no
implementation can read over 100% of a peak, and the same count holds when a
kernel replaces an einsum.
"""

from __future__ import annotations

from typing import Any, Dict

BF16_BYTES = 2


def layer_matmul_params(model: Dict[str, Any]) -> int:
    d, hd = model["d_model"], model["head_dim"]
    attn = 2 * d * model["n_heads"] * hd + 2 * d * model["n_kv_heads"] * hd
    return attn + 3 * d * model["ffn_hidden"]


def matmul_params(model: Dict[str, Any]) -> int:
    """Parameters a token is multiplied by: every layer's matrices and the
    output head (the input embedding is a gather)."""
    return (model["n_layers"] * layer_matmul_params(model)
            + model["vocab_size"] * model["d_model"])


def param_count(model: Dict[str, Any]) -> int:
    """Parameters held: matrices, norms, the embedding, and the output head
    where it is not tied to the embedding."""
    d = model["d_model"]
    total = (model["n_layers"] * (layer_matmul_params(model) + 2 * d)
             + model["vocab_size"] * d + d)
    if not model["tie_lm_head"]:
        total += model["vocab_size"] * d
    return total


def weight_bytes(model: Dict[str, Any]) -> int:
    return param_count(model) * BF16_BYTES


def kv_bytes_per_token(model: Dict[str, Any]) -> int:
    """Keys and values of one position over all layers, in bfloat16."""
    return (2 * model["n_layers"] * model["n_kv_heads"] * model["head_dim"]
            * BF16_BYTES)


def span_flops(model: Dict[str, Any], start: int, count: int,
               with_head: int = 0) -> float:
    """FLOPs to run ``count`` new positions that follow ``start`` cached
    ones: 2 per matrix parameter per position, attention's 4 x context x
    heads x head size per position per layer (causal: position p sees p + 1
    keys), and the vocabulary projection at ``with_head`` of them."""
    layers = model["n_layers"] * layer_matmul_params(model)
    context = count * start + count * (count + 1) // 2
    attention = (4 * context * model["n_heads"] * model["head_dim"]
                 * model["n_layers"])
    head = 2 * with_head * model["vocab_size"] * model["d_model"]
    return 2.0 * layers * count + attention + head


def step_bytes(model: Dict[str, Any], cached_positions: int) -> float:
    """Bytes one decode launch must read: every weight once and every
    distinct cached position once."""
    return float(weight_bytes(model)
                 + cached_positions * kv_bytes_per_token(model))


def least_seconds(flops: float, bytes_: float, peak: Dict[str, Any]):
    """(seconds, which bound sets them) on a chip with these peaks."""
    by_compute = flops / peak["bf16_flops_per_s"]
    by_bandwidth = bytes_ / peak["hbm_bytes_per_s"]
    if by_compute >= by_bandwidth:
        return by_compute, "compute"
    return by_bandwidth, "bandwidth"
