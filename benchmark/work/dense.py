"""The least work a dense decoder-only transformer needs, from shapes and
counts alone: the work count of every configuration file that names no
``"work"`` of its own.

Every function takes the ``model`` block of a configuration file.  The work
counted is what a statement needs whatever the implementation does: useful
tokens only (no padding, no recomputation), weights read once per launch, a
cache position read once.  Nothing here looks at what the program ran, so no
implementation can read over 100% of a peak, and the same count holds when a
kernel replaces an einsum.

What a work file gives (``harness.load_work`` holds every one to it):
``param_count``, ``weight_bytes``, ``span_flops``, ``step_bytes`` and
``TERMS``, the names of the parts the work falls into.  The last three
functions take ``term``: ``None`` counts the whole, a name of ``TERMS`` that
part alone, and the parts sum to the whole.  A kernel's roofline is read
against its own term (a metric file's ``"term"``).  Here:

* ``attention``: 4 x context x heads x head size FLOPs a position a layer,
  and the cache's bytes;
* ``head``: the vocabulary projection where a position is scored or sampled,
  and the head's table once a launch;
* ``matrix``: 2 FLOPs per layer-matrix parameter per position, and the rest
  of the weights.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

BF16_BYTES = 2

TERMS = ("attention", "head", "matrix")


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


def kv_bytes_per_token(model: Dict[str, Any]) -> int:
    """Keys and values of one position over all layers, in bfloat16."""
    return (2 * model["n_layers"] * model["n_kv_heads"] * model["head_dim"]
            * BF16_BYTES)


def head_bytes(model: Dict[str, Any]) -> int:
    """What a launch reads of the head: its table once."""
    return model["vocab_size"] * model["d_model"] * BF16_BYTES


def weight_bytes(model: Dict[str, Any], term: Optional[str] = None) -> int:
    whole = param_count(model) * BF16_BYTES
    if term is None:
        return whole
    return {"attention": 0, "head": head_bytes(model),
            "matrix": whole - head_bytes(model)}[term]


def span_flops(model: Dict[str, Any], start: int, count: int,
               with_head: int = 0, term: Optional[str] = None) -> float:
    """FLOPs to run ``count`` new positions that follow ``start`` cached
    ones: 2 per matrix parameter per position, attention's 4 x context x
    heads x head size per position per layer (causal: position p sees p + 1
    keys), and the vocabulary projection at ``with_head`` of them."""
    layers = model["n_layers"] * layer_matmul_params(model)
    context = count * start + count * (count + 1) // 2
    attention = (4 * context * model["n_heads"] * model["head_dim"]
                 * model["n_layers"])
    head = 2 * with_head * model["vocab_size"] * model["d_model"]
    if term is None:
        return 2.0 * layers * count + attention + head
    return float({"attention": attention, "head": head,
                  "matrix": 2.0 * layers * count}[term])


def step_bytes(model: Dict[str, Any], cached_positions: int, rows: int,
               term: Optional[str] = None) -> float:
    """Bytes one decode launch must read: every weight once and every
    distinct cached position once, however many ``rows`` decode in it (a
    recurrent layer's state would be read once a row; here nothing is)."""
    cache = cached_positions * kv_bytes_per_token(model)
    if term is None:
        return float(weight_bytes(model) + cache)
    return float(cache if term == "attention" else weight_bytes(model, term))
