"""Load HuggingFace safetensors checkpoints into the runtime's param pytree.

Maps HF Gemma-2 / Llama-3 parameter names onto the stacked-layer layout of
:func:`consensus_tpu.models.transformer.init_params`.  Works fully offline —
it only ever reads local files (zero-egress environment); when no checkpoint
is available callers fall back to random init (bench/tests).

HF layouts handled:
  Gemma-2:  model.layers.{i}.self_attn.{q,k,v,o}_proj.weight,
            .mlp.{gate,up,down}_proj.weight,
            .input_layernorm / .post_attention_layernorm /
            .pre_feedforward_layernorm / .post_feedforward_layernorm,
            model.embed_tokens.weight (tied LM head), model.norm.weight
  Llama-3:  same attention/mlp names, input_layernorm /
            post_attention_layernorm only, untied lm_head.weight
"""

from __future__ import annotations

import json
import pathlib
from typing import Dict, Optional

import jax.numpy as jnp
import numpy as np

from consensus_tpu.models.config import ModelConfig


def _open_safetensors(model_dir: pathlib.Path):
    """Yield (name, numpy array) for every tensor across all shards."""
    try:
        from safetensors import safe_open  # type: ignore
    except ImportError as e:  # pragma: no cover - safetensors ships with transformers
        raise RuntimeError("safetensors is required to load checkpoints") from e

    files = sorted(model_dir.glob("*.safetensors"))
    if not files:
        raise FileNotFoundError(f"No .safetensors files under {model_dir}")
    for file in files:
        with safe_open(str(file), framework="numpy") as f:
            for name in f.keys():
                yield name, f.get_tensor(name)


def load_params(
    model_dir: str,
    config: ModelConfig,
    dtype: jnp.dtype = jnp.bfloat16,
) -> Dict:
    """Read a local HF checkpoint directory into the runtime pytree."""
    model_dir_path = pathlib.Path(model_dir)
    c = config
    if c.has_layer_kinds:
        from consensus_tpu.models.config import LayerKindsUnsupported

        raise LayerKindsUnsupported(
            "load_params (a HuggingFace checkpoint)",
            "it fills one stack of leaves for every layer, and no checkpoint "
            "of such a model is on this machine to hold a mapping to")
    h, kv, hd = c.n_heads, c.n_kv_heads, c.head_dim

    def blank(*shape):
        return np.zeros(shape, dtype=np.float32)

    layers: Dict[str, np.ndarray] = {
        "attn_norm": blank(c.n_layers, c.d_model),
        "wq": blank(c.n_layers, c.d_model, h * hd),
        "wk": blank(c.n_layers, c.d_model, kv * hd),
        "wv": blank(c.n_layers, c.d_model, kv * hd),
        "wo": blank(c.n_layers, h * hd, c.d_model),
        "ffn_norm": blank(c.n_layers, c.d_model),
        "w_gate": blank(c.n_layers, c.d_model, c.ffn_hidden),
        "w_up": blank(c.n_layers, c.d_model, c.ffn_hidden),
        "w_down": blank(c.n_layers, c.ffn_hidden, c.d_model),
    }
    if c.use_post_norms:
        layers["post_attn_norm"] = blank(c.n_layers, c.d_model)
        layers["post_ffn_norm"] = blank(c.n_layers, c.d_model)

    params: Dict = {"layers": layers}

    # HF stores projections as (out, in); the runtime right-multiplies, so
    # every matrix is transposed on the way in.
    proj_map = {
        "self_attn.q_proj.weight": ("wq", True),
        "self_attn.k_proj.weight": ("wk", True),
        "self_attn.v_proj.weight": ("wv", True),
        "self_attn.o_proj.weight": ("wo", True),
        "mlp.gate_proj.weight": ("w_gate", True),
        "mlp.up_proj.weight": ("w_up", True),
        "mlp.down_proj.weight": ("w_down", True),
        "input_layernorm.weight": ("attn_norm", False),
        "post_attention_layernorm.weight": (
            "post_attn_norm" if c.use_post_norms else "ffn_norm",
            False,
        ),
        "pre_feedforward_layernorm.weight": ("ffn_norm", False),
        "post_feedforward_layernorm.weight": ("post_ffn_norm", False),
    }

    for name, tensor in _open_safetensors(model_dir_path):
        tensor = np.asarray(tensor, dtype=np.float32)
        if name == "model.embed_tokens.weight":
            params["embed"] = tensor
            continue
        if name == "model.norm.weight":
            params["final_norm"] = tensor
            continue
        if name == "lm_head.weight":
            params["lm_head"] = tensor
            continue
        if not name.startswith("model.layers."):
            continue
        rest = name[len("model.layers."):]
        layer_str, suffix = rest.split(".", 1)
        layer_idx = int(layer_str)
        if suffix not in proj_map:
            continue
        target, transpose = proj_map[suffix]
        layers[target][layer_idx] = tensor.T if transpose else tensor

    if "embed" not in params:
        raise ValueError(f"Checkpoint at {model_dir} missing model.embed_tokens.weight")
    if "final_norm" not in params:
        raise ValueError(f"Checkpoint at {model_dir} missing model.norm.weight")
    if not c.tie_lm_head and "lm_head" not in params:
        raise ValueError(f"Checkpoint at {model_dir} missing lm_head.weight (untied head)")
    if c.tie_lm_head:
        params.pop("lm_head", None)

    return {
        key: jnp.asarray(value, dtype)
        if isinstance(value, np.ndarray)
        else {k: jnp.asarray(v, dtype) for k, v in value.items()}
        for key, value in params.items()
    }


def infer_config_name(model_dir: str) -> Optional[str]:
    """Guess the preset name from an HF config.json, if present."""
    config_file = pathlib.Path(model_dir) / "config.json"
    if not config_file.exists():
        return None
    hf = json.loads(config_file.read_text())
    model_type = hf.get("model_type", "")
    hidden = hf.get("hidden_size")
    if model_type == "gemma2":
        return {2304: "gemma2-2b", 3584: "gemma2-9b"}.get(hidden)
    if model_type == "llama":
        return {4096: "llama3-8b"}.get(hidden)
    return None
