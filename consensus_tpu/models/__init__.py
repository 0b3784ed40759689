from consensus_tpu.models.config import ModelConfig, get_model_config  # noqa: F401
from consensus_tpu.models.transformer import (  # noqa: F401
    forward,
    init_params,
    make_cache,
)

#: The outer scopes of a generation program: the shared prompt's prefill and
#: the decode loop.  An operation carries one of them beside its own scope.
MODEL_PHASES = ("prefill", "decode_step")

#: Every name a ``jax.named_scope`` under ``consensus_tpu/`` gives: what a
#: profile shows an operation under, and what the benchmark's kernel metrics
#: group device time by.  ``attn_qkv`` is the norm, the three projections and
#: the rotary embedding; ``attention`` the logits, mask, softmax and values
#: (with the page gather where there is one); ``vocab_projection`` the head
#: product; ``logsumexp`` the log-softmax and the target's gather; ``sample``
#: temperature, bias, penalties, Gumbel noise and the top-k or argmax.
#: ``layers`` is the scan over the layers, and names what no scope of the
#: layer body covers: the loop's own slicing of a layer's weights and cache
#: out of the stacked arrays, and the stacking of what the layer returns.
#: A block with a recurrent mixer (``transformer.ssm_mixer``) adds ``ssm_in``
#: (the input product and its multipliers), ``ssm_conv`` (the causal
#: convolution and its window), ``ssm_scan`` (step sizes, decays, the chunked
#: or one-step recurrence, the skip term) and ``ssm_out`` (gate, grouped
#: norm, output product); ``state_fork`` is a program copying one recurrent
#: state to many rows (``transformer.fork_ssm``).
#: A configuration with layers of more than one kind names a window layer's
#: attention ``attention_window`` (a full layer's stays ``attention``), and
#: its routed feed-forward (``transformer.moe_block``) ``moe_router`` (norm,
#: the router's float32 product, sigmoid, selection, weights),
#: ``moe_dispatch`` (the rows' assignments sorted and gathered by expert, or
#: the mask of a decode step's few rows), ``moe_experts`` (the held experts'
#: three products) and ``moe_combine`` (each row's weighted sum of what its
#: assignments returned, and the residual); ``moe_shared`` is the expert every
#: row passes, where the configuration has one.
#: A latent layer's attention (logits, mask, softmax, values, with the page
#: gather where there is one) is ``attention_latent``, in either form;
#: ``mla_absorb`` is the absorbed form's two products with the heads' key and
#: value matrices (the queries folded before, the weighted latents unfolded
#: after) and ``mla_expand`` the expanded form's product that makes every
#: head's keys and values of the gathered latents.  ``kv_write`` stays one
#: name: a latent layer writes one buffer there.
MODEL_SCOPES = (
    "embed", "layers", "attn_qkv", "kv_write", "attention", "attn_out", "ffn",
    "final_norm", "vocab_projection", "logsumexp", "sample",
    "ssm_in", "ssm_conv", "ssm_scan", "ssm_out", "state_fork",
    "attention_window", "moe_router", "moe_dispatch", "moe_experts",
    "moe_combine",
    "mla_absorb", "mla_expand", "attention_latent", "moe_shared",
) + MODEL_PHASES
