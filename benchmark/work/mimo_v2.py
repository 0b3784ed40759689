"""The least work MiMo-V2-Flash's layers need, from shapes and counts alone:
full and window attention with their own key-value heads, key heads wider
than value heads, a dense feed-forward where ``moe_layer_freq`` is 0 and
routed experts, of which this chip holds ``experts_held``, where it is 1.

The same contract as ``work/dense.py`` (``harness.load_work`` holds it):
every function takes the ``model`` block of a configuration file, counts
useful tokens only, reads weights once a launch and a cached position once,
and looks at nothing the program ran.  ``TERMS``:

* ``attention``: the full layers' attention.  2 x context x heads x (key
  width + value width) FLOPs a position a layer (the logits' product and the
  values'), and the full layers' keys and values of every cached position.
* ``attention_window``: the window layers' attention at the window's least
  work: a query sees at most ``sliding_window`` keys, itself among them,
  whatever the program gathers or masks; a decode step reads the window's
  positions of the window layers' cache and no more.
* ``experts``: the router (2 x hidden x ``n_experts`` a position a routed
  layer, float32 weights) and the held experts' three products at the
  EXPECTED number of assignments a position, ``experts_per_token`` x held /
  ``n_experts`` (0.5 at 8 x 16 / 256): the router is near uniform on seeded
  weights, and a count of what was really routed would be a reading of the
  program.  A span reads every held expert; a decode step of ``rows`` rows
  reads the experts expected to be hit, held x (1 - (1 - k / n)^rows), so
  that a kernel which skips idle experts cannot read over 100%.
* ``head``: the vocabulary projection where a position is scored or sampled,
  and the (untied) head's table once a launch.
* ``matrix``: 2 FLOPs per parameter of the attention matrices and the dense
  feed-forward per position, and the rest of the weights.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

BF16_BYTES = 2
F32_BYTES = 4

TERMS = ("attention", "attention_window", "experts", "head", "matrix")


def layers_of(model: Dict[str, Any]) -> Dict[str, int]:
    """How many layers are full, window, dense and routed."""
    window = sum(1 for kind in model["hybrid_layer_pattern"] if kind)
    routed = sum(1 for kind in model["moe_layer_freq"] if kind)
    n = model["n_layers"]
    return {"full": n - window, "window": window, "dense": n - routed,
            "routed": routed}


def value_dim(model: Dict[str, Any]) -> int:
    return model.get("v_head_dim") or model["head_dim"]


def attention_params(model: Dict[str, Any], window: bool) -> int:
    """Wq, Wk, Wv and Wo of one layer of the kind."""
    d, h, hd, vd = model["d_model"], model["n_heads"], model["head_dim"], value_dim(model)
    kv = model["swa_kv_heads"] if window else model["n_kv_heads"]
    return d * h * hd + d * kv * hd + d * kv * vd + h * vd * d


def expert_params(model: Dict[str, Any]) -> int:
    """One expert's gate, up and down matrices."""
    return 3 * model["d_model"] * model["expert_hidden"]


def router_params(model: Dict[str, Any]) -> int:
    """The router's matrix and its selection bias, one routed layer."""
    return model["d_model"] * model["n_experts"] + model["n_experts"]


def matrix_params(model: Dict[str, Any]) -> int:
    """Parameters of the ``matrix`` term a position is multiplied by: every
    layer's attention matrices and the dense layers' feed-forward."""
    n = layers_of(model)
    return (n["full"] * attention_params(model, False)
            + n["window"] * attention_params(model, True)
            + n["dense"] * 3 * model["d_model"] * model["ffn_hidden"])


def sink_params(model: Dict[str, Any]) -> int:
    if not model.get("swa_sink"):
        return 0
    return layers_of(model)["window"] * model["n_heads"]


def param_count(model: Dict[str, Any]) -> int:
    """Parameters held: the matrices, the routers with their bias, the held
    experts, the sinks, two norms a layer, the embedding, the untied head,
    the last norm."""
    n, d = layers_of(model), model["d_model"]
    held = model["experts_held"][1]
    total = (matrix_params(model)
             + n["routed"] * (router_params(model) + held * expert_params(model))
             + sink_params(model) + model["n_layers"] * 2 * d
             + model["vocab_size"] * d + d)
    if not model["tie_lm_head"]:
        total += model["vocab_size"] * d
    return total


def kv_bytes_per_token(model: Dict[str, Any], kind: Optional[str] = None) -> int:
    """Keys and values of one position in bfloat16, over the layers of one
    kind of attention (``"full"``, ``"window"``) or over all."""
    n, width = layers_of(model), model["head_dim"] + value_dim(model)
    full = n["full"] * model["n_kv_heads"] * width * BF16_BYTES
    window = n["window"] * model["swa_kv_heads"] * width * BF16_BYTES
    return {"full": full, "window": window, None: full + window}[kind]


def head_bytes(model: Dict[str, Any]) -> int:
    return model["vocab_size"] * model["d_model"] * BF16_BYTES


def router_bytes(model: Dict[str, Any]) -> int:
    """Every routed layer's router and bias, float32."""
    return layers_of(model)["routed"] * router_params(model) * F32_BYTES


def expert_bytes(model: Dict[str, Any]) -> int:
    """One expert's matrices, bfloat16."""
    return expert_params(model) * BF16_BYTES


def weight_bytes(model: Dict[str, Any], term: Optional[str] = None) -> int:
    # bfloat16 but for the routers, their bias and the sinks (float32).
    f32 = layers_of(model)["routed"] * router_params(model) + sink_params(model)
    whole = param_count(model) * BF16_BYTES + f32 * (F32_BYTES - BF16_BYTES)
    if term is None:
        return whole
    experts = router_bytes(model) + (
        layers_of(model)["routed"] * model["experts_held"][1] * expert_bytes(model))
    return {"attention": 0, "attention_window": 0, "head": head_bytes(model),
            "experts": experts,
            "matrix": whole - head_bytes(model) - experts}[term]


def held_assignments_per_position(model: Dict[str, Any]) -> float:
    """Expected assignments of one position to experts held here."""
    return (model["experts_per_token"] * model["experts_held"][1]
            / model["n_experts"])


def experts_hit(model: Dict[str, Any], rows: int) -> float:
    """Held experts that ``rows`` rows are expected to reach, a layer."""
    miss = 1.0 - model["experts_per_token"] / model["n_experts"]
    return model["experts_held"][1] * (1.0 - miss ** rows)


def window_context(model: Dict[str, Any], start: int, count: int) -> int:
    """Keys that ``count`` new positions after ``start`` cached ones see in a
    window layer: position p sees min(p + 1, window)."""
    window = model["sliding_window"]
    short = min(max(window - start, 0), count)  # positions not yet a window in
    return short * start + short * (short + 1) // 2 + (count - short) * window


def span_flops(model: Dict[str, Any], start: int, count: int,
               with_head: int = 0, term: Optional[str] = None) -> float:
    """FLOPs to run ``count`` new positions that follow ``start`` cached
    ones."""
    n = layers_of(model)
    per_key = 2 * model["n_heads"] * (model["head_dim"] + value_dim(model))
    context = count * start + count * (count + 1) // 2
    routed_position = (
        2.0 * model["d_model"] * model["n_experts"]
        + 2.0 * held_assignments_per_position(model) * expert_params(model))
    parts = {
        "attention": float(per_key * context * n["full"]),
        "attention_window": float(
            per_key * window_context(model, start, count) * n["window"]),
        "experts": n["routed"] * routed_position * count,
        "head": float(2 * with_head * model["vocab_size"] * model["d_model"]),
        "matrix": 2.0 * matrix_params(model) * count,
    }
    if term is None:
        return sum(parts[name] for name in TERMS)
    return parts[term]


def step_bytes(model: Dict[str, Any], cached_positions: int, rows: int,
               term: Optional[str] = None) -> float:
    """Bytes one decode launch must read: the head and the matrices once,
    the routers and the experts its rows are expected to reach, every
    distinct cached position of the full layers, and the window's positions
    of the window layers."""
    n = layers_of(model)
    parts = {
        "attention": float(cached_positions * kv_bytes_per_token(model, "full")),
        "attention_window": float(
            min(cached_positions, model["sliding_window"])
            * kv_bytes_per_token(model, "window")),
        "experts": float(router_bytes(model) + n["routed"]
                         * experts_hit(model, rows) * expert_bytes(model)),
        "head": float(weight_bytes(model, "head")),
        "matrix": float(weight_bytes(model, "matrix")),
    }
    if term is None:
        return sum(parts[name] for name in TERMS)
    return parts[term]
