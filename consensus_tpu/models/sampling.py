"""Token sampling: temperature / top-k / top-p / logit-bias, batched.

Replaces the sampling surface the reference gets from the Together API
(``temperature``, ``seed``, ``logit_bias``, ``stop`` params of
src/utils.py:77-198).  Logit bias maps of {token_id: bias} become a dense
additive vector so banning junk tokens (beam_search.py:38-56) is one add.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp


def ban_undecodable(logits: jax.Array, config) -> jax.Array:
    """``-inf`` at the ids the serving tokenizer cannot decode
    (``config.sample_vocab`` and above), so that no sampler or proposer
    ever picks one.  Traces to nothing when the whole vocabulary is
    decodable.  Only sampling sites call this: teacher-forced scoring
    normalizes over the full vocabulary."""
    n = config.sample_vocab
    if n is None or n >= logits.shape[-1]:
        return logits
    with jax.named_scope("sample"):
        ids = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
        return jnp.where(ids < n, logits, -jnp.inf)


def _top_k_filter(logits: jax.Array, k: int) -> jax.Array:
    if k <= 0 or k >= logits.shape[-1]:
        return logits
    threshold = jax.lax.top_k(logits, k)[0][..., -1:]
    return jnp.where(logits < threshold, -jnp.inf, logits)


def _top_p_filter(logits: jax.Array, p: float) -> jax.Array:
    if p >= 1.0:  # static: top_p is a static argname of sample_tokens
        return logits
    sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cumulative = jnp.cumsum(probs, axis=-1)
    # Keep tokens until cumulative prob exceeds p (always keep the first).
    keep_sorted = jnp.roll(cumulative < p, 1, axis=-1).at[..., 0].set(True)
    threshold = jnp.min(
        jnp.where(keep_sorted, sorted_logits, jnp.inf), axis=-1, keepdims=True
    )
    return jnp.where(logits < threshold, -jnp.inf, logits)


def apply_repetition_penalty(
    logits: jax.Array,  # (B, V) float32
    presence: jax.Array,  # (B, V) bool — token ids seen in prompt/output
    penalty: jax.Array,  # (B,) or scalar float32, 1.0 = no-op
) -> jax.Array:
    """HF-style repetition penalty: for already-seen tokens, positive
    logits divide by the penalty and negative logits multiply by it
    (the reference forwards the same-named Together param,
    src/utils.py:88,156,184 — identical semantics server-side)."""
    penalty = jnp.asarray(penalty, jnp.float32)
    if penalty.ndim == 1:
        penalty = penalty[:, None]
    penalized = jnp.where(logits > 0, logits / penalty, logits * penalty)
    return jnp.where(presence, penalized, logits)


@functools.partial(jax.jit, static_argnames=("top_k", "top_p"))
def sample_tokens(
    key: jax.Array,  # single key (2,) or per-row keys (B, 2)
    logits: jax.Array,  # (B, V) float32
    temperature: float | jax.Array = 1.0,
    top_k: int = 0,
    top_p: float = 1.0,
    logit_bias: Optional[jax.Array] = None,  # (V,) or (B, V) additive
    presence: Optional[jax.Array] = None,  # (B, V) bool seen-token mask
    rep_penalty: Optional[jax.Array] = None,  # (B,) float32
) -> jax.Array:
    """Sample one token id per row; temperature<=0 means greedy argmax.

    With per-row keys (B, 2), each row's draw depends only on its own key —
    a request's output is then independent of batch composition, matching
    the reference's per-request seed semantics (SURVEY §7.4).
    """
    with jax.named_scope("sample"):
        logits = logits.astype(jnp.float32)
        if presence is not None and rep_penalty is not None:
            logits = apply_repetition_penalty(logits, presence, rep_penalty)
        if logit_bias is not None:
            logits = logits + logit_bias

        greedy = jnp.argmax(logits, axis=-1)

        filtered = _top_k_filter(logits, top_k)
        filtered = _top_p_filter(filtered, top_p)
        temp = jnp.asarray(temperature, jnp.float32)
        if temp.ndim == 1:  # per-row temperatures (B,) -> broadcast over vocab
            temp = temp[:, None]
        safe_temp = jnp.maximum(temp, 1e-6)
        scaled = filtered / safe_temp
        if key.ndim == 2:
            sampled = jax.vmap(jax.random.categorical)(key, scaled)
        else:
            sampled = jax.random.categorical(key, scaled, axis=-1)

        use_greedy = jnp.any(temp <= 0.0, axis=-1) if temp.ndim else temp <= 0.0
        return jnp.where(use_greedy, greedy, sampled).astype(jnp.int32)
