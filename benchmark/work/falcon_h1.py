"""The least work a Falcon-H1 block needs, from shapes and counts alone: a
Mamba-2 mixer beside grouped-query attention in every layer, an untied head.

The same contract as ``work/dense.py`` (``harness.load_work`` holds it):
every function takes the ``model`` block of a configuration file, counts
useful tokens only, reads weights once a launch and a cached position once,
and looks at nothing the program ran.  ``TERMS`` has a fourth name:

* ``attention``: 4 x context x heads x head size FLOPs a position a layer,
  and the key-value cache's bytes;
* ``head``: the vocabulary projection where a position is scored or sampled,
  and the (untied) head's table once a launch;
* ``matrix``: 2 FLOPs per layer-matrix parameter per position, the mixer's
  input and output products among them, and the rest of the weights;
* ``ssm``: the recurrence and its convolution.  Per position, layer and
  head the (head size x state) matrix H is decayed, takes the outer product
  dt x (x) B and is read out against C, and the skip term D x is added; per
  position and layer a causal depthwise convolution of ``ssm_conv`` taps runs
  over the x, B and C columns.  The recurrence is counted once, in the form
  that needs fewer operations at the configuration's sizes
  (``recurrence_flops``): sequentially that is 5 x P x N + 3 x P a head a
  position (decay, outer product, add, and 2 for the read-out); in chunks of
  Q positions (Mamba-2's duality, Q = ``ssm_chunk`` as published) it is
  4 x P x N for the chunk states and their read-out, Q x P and, a group,
  Q x N for the causal half of the products inside the chunk, and
  3 x P x N / Q for carrying the state from chunk to chunk.  At the published
  sizes (P 128, N 256, Q 128) the chunked form needs 150,656 a head against
  164,224, so that is the count, whether a chunked einsum, a sequential scan
  or a kernel computes it, and for a decode step too (a lower bound stays
  one).  Per decode launch and row the state is read and written once:
  2 x (4 x heads x P x N + the convolution's window) bytes a layer, which no
  sharing of a prefix takes away; ``step_bytes`` reads ``rows`` for it.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

BF16_BYTES = 2
F32_BYTES = 4

TERMS = ("attention", "head", "matrix", "ssm")


def conv_dim(model: Dict[str, Any]) -> int:
    return model["ssm_inner"] + 2 * model["ssm_groups"] * model["ssm_state"]


def in_dim(model: Dict[str, Any]) -> int:
    """Columns of the mixer's input product: [z | x | B | C | dt]."""
    return model["ssm_inner"] + conv_dim(model) + model["ssm_heads"]


def layer_matmul_params(model: Dict[str, Any]) -> int:
    d, hd = model["d_model"], model["head_dim"]
    attn = 2 * d * model["n_heads"] * hd + 2 * d * model["n_kv_heads"] * hd
    mixer = d * in_dim(model) + model["ssm_inner"] * d
    return attn + mixer + 3 * d * model["ffn_hidden"]


def layer_ssm_params(model: Dict[str, Any]) -> int:
    """What the convolution and the recurrence read: taps, bias, and the
    three float32 vectors a head (A, the step's bias, D)."""
    return (model["ssm_conv"] + 1) * conv_dim(model) + 3 * model["ssm_heads"]


def param_count(model: Dict[str, Any]) -> int:
    """Parameters held: matrices, the mixer's small leaves and its gated
    norm, two norms a layer, the embedding, the untied head, the last norm."""
    d = model["d_model"]
    layer = (layer_matmul_params(model) + layer_ssm_params(model)
             + model["ssm_inner"] + 2 * d)
    total = model["n_layers"] * layer + model["vocab_size"] * d + d
    if not model["tie_lm_head"]:
        total += model["vocab_size"] * d
    return total


def kv_bytes_per_token(model: Dict[str, Any]) -> int:
    return (2 * model["n_layers"] * model["n_kv_heads"] * model["head_dim"]
            * BF16_BYTES)


def state_bytes_per_row(model: Dict[str, Any]) -> int:
    """One row's recurrent state over all layers: H in float32 and the
    convolution's window of ``ssm_conv`` - 1 columns in bfloat16."""
    h = F32_BYTES * model["ssm_heads"] * model["ssm_head_dim"] * model["ssm_state"]
    window = BF16_BYTES * (model["ssm_conv"] - 1) * conv_dim(model)
    return model["n_layers"] * (h + window)


def head_bytes(model: Dict[str, Any]) -> int:
    return model["vocab_size"] * model["d_model"] * BF16_BYTES


def ssm_weight_bytes(model: Dict[str, Any]) -> int:
    taps = (model["ssm_conv"] + 1) * conv_dim(model) * BF16_BYTES
    return model["n_layers"] * (taps + 3 * model["ssm_heads"] * F32_BYTES)


def weight_bytes(model: Dict[str, Any], term: Optional[str] = None) -> int:
    # bfloat16 but for the three float32 vectors a head a layer.
    whole = (param_count(model) * BF16_BYTES
             + model["n_layers"] * 3 * model["ssm_heads"] * (F32_BYTES - BF16_BYTES))
    if term is None:
        return whole
    return {"attention": 0, "head": head_bytes(model),
            "ssm": ssm_weight_bytes(model),
            "matrix": whole - head_bytes(model) - ssm_weight_bytes(model)}[term]


def recurrence_flops(model: Dict[str, Any]) -> Dict[str, float]:
    """FLOPs of the recurrence a position a layer, all heads, in both forms
    (the module's text says what each counts)."""
    h, p, n = model["ssm_heads"], model["ssm_head_dim"], model["ssm_state"]
    g, q = model["ssm_groups"], model["ssm_chunk"]
    skip = 3 * p  # dt x, and D x added
    return {
        "sequential": float(h * (5 * p * n + skip)),
        "chunked": float(h * (4 * p * n + q * p + 3 * p * n / q + skip)
                         + g * q * n),
    }


def ssm_position_flops(model: Dict[str, Any]) -> float:
    """The ``ssm`` term a position a layer: the recurrence in its cheaper
    form, and the convolution (a multiply and an add a tap a column)."""
    return (min(recurrence_flops(model).values())
            + 2.0 * model["ssm_conv"] * conv_dim(model))


def span_flops(model: Dict[str, Any], start: int, count: int,
               with_head: int = 0, term: Optional[str] = None) -> float:
    """FLOPs to run ``count`` new positions that follow ``start`` cached
    ones (the recurrent state stands for them at no cost)."""
    layers = model["n_layers"] * layer_matmul_params(model)
    context = count * start + count * (count + 1) // 2
    parts = {
        "attention": float(4 * context * model["n_heads"] * model["head_dim"]
                           * model["n_layers"]),
        "head": float(2 * with_head * model["vocab_size"] * model["d_model"]),
        "matrix": 2.0 * layers * count,
        "ssm": model["n_layers"] * ssm_position_flops(model) * count,
    }
    if term is None:
        return sum(parts[name] for name in TERMS)
    return parts[term]


def step_bytes(model: Dict[str, Any], cached_positions: int, rows: int,
               term: Optional[str] = None) -> float:
    """Bytes one decode launch must read (and, of the state, write): every
    weight once, every distinct cached position once, and every decoding
    row's recurrent state in and out."""
    parts = {
        "attention": float(cached_positions * kv_bytes_per_token(model)),
        "head": float(weight_bytes(model, "head")),
        "matrix": float(weight_bytes(model, "matrix")),
        "ssm": float(weight_bytes(model, "ssm")
                     + 2 * rows * state_bytes_per_row(model)),
    }
    if term is None:
        return sum(parts[name] for name in TERMS)
    return parts[term]
