"""The least work JoyAI-LLM-Flash's layers need, from shapes and counts alone:
latent attention (MLA) in every layer, a dense feed-forward where
``moe_layer_freq`` is 0 and, where it is 1, routed experts of which this chip
holds ``experts_held``, with a shared expert beside them.

The same contract as ``work/dense.py`` (``harness.load_work`` holds it):
every function takes the ``model`` block of a configuration file, counts
useful tokens only, reads weights once a launch and a cached position once,
and looks at nothing the program ran.  ``TERMS``:

* ``attention_latent``: latent attention, whichever form serves it.  There
  are two ways to attend over cached [latent | rotary key] and a span's FLOPs
  are **the lesser of the two for that call**, so that the share means the
  same whichever the program took.  *Absorbed*: a query's part without
  position is folded through its head's key matrix and the weighted latents
  through its value matrix (2 x rank x (nope + value) a query a head), and a
  query-key-head costs 2 x (rank + rope) for the score and 2 x rank for the
  value.  *Expanded*: every head's keys and values are made of every key
  position once (2 x rank x heads x (nope + value) a key), and a
  query-key-head costs 2 x (nope + rope) + 2 x value.  A decode step reads
  every cached position once, (rank + rope) numbers a layer, and ``W_kvb``
  once a layer: both belong to this term, in bytes and in FLOPs, and not to
  ``matrix``.
* ``experts``: the router (2 x hidden x ``n_experts`` a position a routed
  layer, float32 weights), the shared expert's three products and the held
  experts' three products at the EXPECTED number of assignments a position,
  ``experts_per_token`` x held / ``n_experts`` (8 with all 256 held).  A span
  reads every held expert; a decode step of ``rows`` rows reads the router,
  the shared expert and the experts expected to be hit, held x (1 - (1 - k /
  n)^rows), so that a kernel which skips idle experts cannot read over 100%.
* ``head``: the vocabulary projection where a position is scored or sampled,
  and the (untied) head's table once a launch.
* ``matrix``: 2 FLOPs per parameter of the attention matrices but ``W_kvb``
  and of the dense feed-forward per position, and the rest of the weights.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

BF16_BYTES = 2
F32_BYTES = 4

TERMS = ("attention_latent", "experts", "head", "matrix")


def layers_of(model: Dict[str, Any]) -> Dict[str, int]:
    """How many layers are latent (all), dense and routed."""
    routed = sum(1 for kind in model["moe_layer_freq"] if kind)
    n = model["n_layers"]
    return {"latent": n, "dense": n - routed, "routed": routed}


def latent_dim(model: Dict[str, Any]) -> int:
    """What a token leaves behind a layer: [latent | rotary key]."""
    return model["kv_lora_rank"] + model["qk_rope_dim"]


def kvb_params(model: Dict[str, Any]) -> int:
    """``W_kvb`` of one layer: latent -> heads x [keys without position |
    values]."""
    return model["kv_lora_rank"] * model["n_heads"] * (
        model["qk_nope_dim"] + model["v_head_dim"])


def attention_params(model: Dict[str, Any]) -> int:
    """One layer's attention matrices but ``W_kvb``: into and out of the
    queries' bottleneck, into the latent and the rotary key, and Wo."""
    d, h = model["d_model"], model["n_heads"]
    return (d * model["q_lora_rank"] + model["q_lora_rank"] * h * model["head_dim"]
            + d * latent_dim(model) + h * model["v_head_dim"] * d)


def expert_params(model: Dict[str, Any]) -> int:
    """One expert's gate, up and down matrices."""
    return 3 * model["d_model"] * model["expert_hidden"]


def shared_params(model: Dict[str, Any]) -> int:
    """The shared expert of one routed layer."""
    return (model.get("n_shared_experts") or 0) * expert_params(model)


def router_params(model: Dict[str, Any]) -> int:
    """The router's matrix and its selection bias, one routed layer."""
    return model["d_model"] * model["n_experts"] + model["n_experts"]


def matrix_params(model: Dict[str, Any]) -> int:
    """Parameters of the ``matrix`` term a position is multiplied by: every
    layer's attention matrices but ``W_kvb`` and the dense layers'
    feed-forward."""
    n = layers_of(model)
    return (n["latent"] * attention_params(model)
            + n["dense"] * 3 * model["d_model"] * model["ffn_hidden"])


def norm_params(model: Dict[str, Any]) -> int:
    """Four norms a layer (input, queries' bottleneck, latent, feed-forward)
    and the last one."""
    d = model["d_model"]
    return model["n_layers"] * (
        2 * d + model["q_lora_rank"] + model["kv_lora_rank"]) + d


def param_count(model: Dict[str, Any]) -> int:
    """Parameters held: the matrices, ``W_kvb``, the routers with their bias,
    the shared and the held experts, the norms, the embedding and the untied
    head."""
    n, d = layers_of(model), model["d_model"]
    held = model["experts_held"][1]
    total = (matrix_params(model) + n["latent"] * kvb_params(model)
             + n["routed"] * (router_params(model) + shared_params(model)
                              + held * expert_params(model))
             + norm_params(model) + model["vocab_size"] * d)
    if not model["tie_lm_head"]:
        total += model["vocab_size"] * d
    return total


def kv_bytes_per_token(model: Dict[str, Any], kind: Optional[str] = None) -> int:
    """One position's latent cache in bfloat16 over all layers: one buffer,
    counted once (``kind``: ``"latent"`` or None, the same)."""
    if kind not in (None, "latent"):
        return 0
    return layers_of(model)["latent"] * latent_dim(model) * BF16_BYTES


def head_bytes(model: Dict[str, Any]) -> int:
    return model["vocab_size"] * model["d_model"] * BF16_BYTES


def router_bytes(model: Dict[str, Any]) -> int:
    """Every routed layer's router and bias, float32."""
    return layers_of(model)["routed"] * router_params(model) * F32_BYTES


def expert_bytes(model: Dict[str, Any]) -> int:
    """One expert's matrices, bfloat16."""
    return expert_params(model) * BF16_BYTES


def kvb_bytes(model: Dict[str, Any]) -> int:
    """Every layer's ``W_kvb``, bfloat16."""
    return layers_of(model)["latent"] * kvb_params(model) * BF16_BYTES


def weight_bytes(model: Dict[str, Any], term: Optional[str] = None) -> int:
    # bfloat16 but for the routers and their bias (float32).
    f32 = layers_of(model)["routed"] * router_params(model)
    whole = param_count(model) * BF16_BYTES + f32 * (F32_BYTES - BF16_BYTES)
    if term is None:
        return whole
    n = layers_of(model)
    experts = router_bytes(model) + n["routed"] * BF16_BYTES * (
        shared_params(model) + model["experts_held"][1] * expert_params(model))
    return {"attention_latent": kvb_bytes(model), "head": head_bytes(model),
            "experts": experts,
            "matrix": whole - head_bytes(model) - experts - kvb_bytes(model)}[term]


def held_assignments_per_position(model: Dict[str, Any]) -> float:
    """Expected assignments of one position to experts held here."""
    return (model["experts_per_token"] * model["experts_held"][1]
            / model["n_experts"])


def experts_hit(model: Dict[str, Any], rows: int) -> float:
    """Held experts that ``rows`` rows are expected to reach, a layer."""
    miss = 1.0 - model["experts_per_token"] / model["n_experts"]
    return model["experts_held"][1] * (1.0 - miss ** rows)


def latent_attention_flops(model: Dict[str, Any], start: int, count: int
                           ) -> Dict[str, float]:
    """One layer's latent attention for ``count`` new positions after
    ``start`` cached ones, in each form."""
    h, rank, rope = model["n_heads"], model["kv_lora_rank"], model["qk_rope_dim"]
    nope, vd = model["qk_nope_dim"], model["v_head_dim"]
    pairs = count * start + count * (count + 1) // 2  # query-key pairs a head
    through = 2.0 * rank * h * (nope + vd)  # W_kvb, once a query or once a key
    return {
        "absorbed": count * through + pairs * h * 2.0 * (rank + rope + rank),
        "expanded": (start + count) * through + pairs * h * 2.0 * (nope + rope + vd),
    }


def span_flops(model: Dict[str, Any], start: int, count: int,
               with_head: int = 0, term: Optional[str] = None) -> float:
    """FLOPs to run ``count`` new positions that follow ``start`` cached
    ones."""
    n = layers_of(model)
    routed_position = (
        2.0 * model["d_model"] * model["n_experts"] + 2.0 * shared_params(model)
        + 2.0 * held_assignments_per_position(model) * expert_params(model))
    parts = {
        "attention_latent": n["latent"] * min(
            latent_attention_flops(model, start, count).values()),
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
    the routers, the shared experts and the routed experts its rows are
    expected to reach, every distinct cached position's latent and ``W_kvb``
    once a layer."""
    n = layers_of(model)
    parts = {
        "attention_latent": float(
            cached_positions * kv_bytes_per_token(model) + kvb_bytes(model)),
        "experts": float(router_bytes(model) + n["routed"] * (
            shared_params(model) * BF16_BYTES
            + experts_hit(model, rows) * expert_bytes(model))),
        "head": float(weight_bytes(model, "head")),
        "matrix": float(weight_bytes(model, "matrix")),
    }
    if term is None:
        return sum(parts[name] for name in TERMS)
    return parts[term]
