"""Certify the JAX transformer's numerics against HuggingFace ``transformers``.

The box has no real checkpoint (zero egress), so quality parity vs the
API baseline can't be measured directly.  The strongest evidence available
is architectural: build a tiny-but-faithful Gemma-2 / Llama-3 model, load
*identical* random weights into torch ``Gemma2ForCausalLM`` /
``LlamaForCausalLM`` (CPU, float32, eager attention) and into our runtime
via the production HF-checkpoint path (``models/loader.py:load_params`` on
a ``save_pretrained`` directory), and assert logit agreement.

This certifies every architectural detail the reference's scoring
semantics depend on (reference scores via API logprobs,
/root/reference/src/utils.py:201-281): RoPE theta + Llama-3.1 rope
scaling, attn/final logit softcaps, sliding-window layer alternation,
GQA head grouping, RMSNorm style (Gemma 1+w vs Llama w), embedding
scaling, tied vs untied LM heads, and the activation functions.
"""

import numpy as np
import pytest

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

import jax.numpy as jnp  # noqa: E402

from consensus_tpu.models.config import get_model_config  # noqa: E402
from consensus_tpu.models.loader import load_params  # noqa: E402
from consensus_tpu.models import transformer  # noqa: E402

# Sequence longer than the sliding window (16) so local layers actually clip.
BATCH, SEQ = 2, 48


def _save_hf_model(model, tmp_path):
    d = tmp_path / "ckpt"
    model.save_pretrained(str(d), safe_serialization=True)
    return str(d)


def _hf_tiny_gemma2():
    cfg = transformers.Gemma2Config(
        vocab_size=268,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=4,
        num_attention_heads=4,
        num_key_value_heads=2,
        head_dim=16,
        query_pre_attn_scalar=16,
        sliding_window=16,
        attn_logit_softcapping=50.0,
        final_logit_softcapping=30.0,
        rope_theta=10_000.0,
        rms_norm_eps=1e-6,
        hidden_activation="gelu_pytorch_tanh",
        max_position_embeddings=256,
        tie_word_embeddings=True,
        attention_dropout=0.0,
    )
    cfg._attn_implementation = "eager"
    torch.manual_seed(0)
    model = transformers.Gemma2ForCausalLM(cfg)
    model.eval()
    return model


def _hf_tiny_llama3(rope_scaling=None):
    cfg = transformers.LlamaConfig(
        vocab_size=268,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        rope_theta=500_000.0,
        rms_norm_eps=1e-5,
        max_position_embeddings=256,
        tie_word_embeddings=False,
        attention_bias=False,
        mlp_bias=False,
        rope_scaling=rope_scaling,
    )
    cfg._attn_implementation = "eager"
    torch.manual_seed(1)
    model = transformers.LlamaForCausalLM(cfg)
    model.eval()
    return model


def _jax_logits(ckpt_dir, config, tokens, positions, valid):
    params = load_params(ckpt_dir, config, dtype=jnp.float32)
    logits, _ = transformer.forward(
        params,
        config,
        jnp.asarray(tokens, jnp.int32),
        jnp.asarray(positions, jnp.int32),
        jnp.asarray(valid, bool),
    )
    return np.asarray(logits)


def _hf_logits(model, tokens, positions, valid):
    with torch.no_grad():
        out = model(
            input_ids=torch.tensor(tokens, dtype=torch.long),
            attention_mask=torch.tensor(valid, dtype=torch.long),
            position_ids=torch.tensor(positions, dtype=torch.long),
        )
    return out.logits.float().numpy()


def _full_valid_inputs(vocab):
    rng = np.random.default_rng(42)
    tokens = rng.integers(0, vocab, size=(BATCH, SEQ))
    positions = np.broadcast_to(np.arange(SEQ), (BATCH, SEQ)).copy()
    valid = np.ones((BATCH, SEQ), dtype=bool)
    return tokens, positions, valid


def _left_pad_inputs(vocab, pad=7):
    tokens, positions, valid = _full_valid_inputs(vocab)
    valid[0, :pad] = False
    tokens[0, :pad] = 0
    # Positions restart at 0 on the first real token (the runtime's
    # left-padded layout); HF gets the same explicit position_ids.
    positions[0] = np.concatenate([np.zeros(pad, int), np.arange(SEQ - pad)])
    return tokens, positions, valid


def test_gemma2_logits_match_hf(tmp_path):
    model = _hf_tiny_gemma2()
    ckpt = _save_hf_model(model, tmp_path)
    config = get_model_config("tiny-gemma2")
    tokens, positions, valid = _full_valid_inputs(config.vocab_size)

    ours = _jax_logits(ckpt, config, tokens, positions, valid)
    theirs = _hf_logits(model, tokens, positions, valid)

    np.testing.assert_allclose(ours, theirs, atol=2e-4, rtol=2e-4)


def test_gemma2_logits_match_hf_left_padded(tmp_path):
    model = _hf_tiny_gemma2()
    ckpt = _save_hf_model(model, tmp_path)
    config = get_model_config("tiny-gemma2")
    tokens, positions, valid = _left_pad_inputs(config.vocab_size)

    ours = _jax_logits(ckpt, config, tokens, positions, valid)
    theirs = _hf_logits(model, tokens, positions, valid)

    np.testing.assert_allclose(
        ours[valid], theirs[valid], atol=2e-4, rtol=2e-4
    )


def test_llama3_logits_match_hf(tmp_path):
    model = _hf_tiny_llama3()
    ckpt = _save_hf_model(model, tmp_path)
    config = get_model_config("tiny-llama3")
    tokens, positions, valid = _full_valid_inputs(config.vocab_size)

    ours = _jax_logits(ckpt, config, tokens, positions, valid)
    theirs = _hf_logits(model, tokens, positions, valid)

    np.testing.assert_allclose(ours, theirs, atol=2e-4, rtol=2e-4)


def test_llama31_rope_scaling_matches_hf(tmp_path):
    """Llama-3.1 'llama3' rope frequency scaling (the reference's main-body
    generation model is Meta-Llama-3.1-8B-Instruct-Turbo)."""
    scaling = {
        "rope_type": "llama3",
        "factor": 8.0,
        "low_freq_factor": 1.0,
        "high_freq_factor": 4.0,
        "original_max_position_embeddings": 64,
    }
    model = _hf_tiny_llama3(rope_scaling=scaling)
    ckpt = _save_hf_model(model, tmp_path)
    config = get_model_config(
        "tiny-llama3", rope_scaling=(8.0, 1.0, 4.0, 64)
    )
    tokens, positions, valid = _full_valid_inputs(config.vocab_size)

    ours = _jax_logits(ckpt, config, tokens, positions, valid)
    theirs = _hf_logits(model, tokens, positions, valid)

    np.testing.assert_allclose(ours, theirs, atol=2e-4, rtol=2e-4)


def test_gemma2_decode_path_matches_hf(tmp_path):
    """The KV-cache prefill+decode path (what generation actually runs)
    must agree with HF on the decoded positions, not just the
    teacher-forced path."""
    model = _hf_tiny_gemma2()
    ckpt = _save_hf_model(model, tmp_path)
    config = get_model_config("tiny-gemma2")
    params = load_params(ckpt, config, dtype=jnp.float32)

    rng = np.random.default_rng(3)
    prompt_len, decode_len = 20, 6
    total = prompt_len + decode_len
    tokens = rng.integers(0, config.vocab_size, size=(1, total))

    # HF: one full forward, take the last decode_len logits.
    positions = np.arange(total)[None, :]
    valid = np.ones((1, total), dtype=bool)
    theirs = _hf_logits(model, tokens, positions, valid)[0, prompt_len - 1 : -1]

    # Ours: prefill the prompt into a cache, then decode token by token.
    cache = transformer.make_cache(config, batch=1, max_len=total, dtype=jnp.float32)
    logits, cache = transformer.forward(
        params,
        config,
        jnp.asarray(tokens[:, :prompt_len], jnp.int32),
        jnp.asarray(positions[:, :prompt_len], jnp.int32),
        jnp.ones((1, prompt_len), bool),
        cache=cache,
        write_index=0,
    )
    steps = [np.asarray(logits[:, -1])]
    for i in range(prompt_len, total - 1):
        logits, cache = transformer.forward(
            params,
            config,
            jnp.asarray(tokens[:, i : i + 1], jnp.int32),
            jnp.asarray([[i]], jnp.int32),
            jnp.ones((1, 1), bool),
            cache=cache,
            write_index=i,
        )
        steps.append(np.asarray(logits[:, -1]))
    ours = np.concatenate(steps, axis=0)

    np.testing.assert_allclose(ours, theirs, atol=2e-4, rtol=2e-4)
