"""The plain reference of a dense decoder-only transformer, in float32: the
reference of every configuration file that names no ``"reference"`` of its
own.

A full teacher-forced forward with no cache, no paging and no batching
tricks: float32 activations, every matrix product at ``HIGHEST`` precision.
It runs layer by layer under one ``lax.scan`` with each layer's bfloat16
weights widened to float32 inside the step, and row block by row block
(``lib/reference.py`` ``score_by_width``), so that it fits beside nothing
else on one chip.  It imports nothing of ``consensus_tpu``: the weights are
the same draws the program's ``init_params`` makes, written out again here.

What a reference file gives (``harness.load_cell`` refuses one that lacks
any): ``ref_config(model)``, hashable, which refuses every key of the
``model`` block that this forward does not compute; ``make_weights(cfg,
seed)``, bfloat16 leaves under the tree paths the program serves;
``score_rows(cfg, weights, rows, precision)``, ``Scored`` rows, where
``precision="fp8"`` is the control of the output check: the same forward
with every weight and every matrix-product input rounded to float8 (e4m3),
the nearest precision below the bfloat16 the configurations state.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp

from benchmark.lib.reference import (BYTE_VOCAB, Scored, fp8, score_by_width,
                                     seed_key)

HIGHEST = jax.lax.Precision.HIGHEST


class RefConfig(NamedTuple):
    """The sizes the forward needs, hashable so that ``jit`` can take it."""

    vocab_size: int
    d_model: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    ffn_hidden: int
    activation: str
    rope_theta: float
    rms_eps: float
    rmsnorm_style: str
    scale_embeddings: bool
    tie_lm_head: bool
    sample_vocab: int


#: Keys this forward knows and computes only at the value given here: soft
#: caps, windows, post-norms and rope scaling are refused, not ignored.
_OFF = {"attn_softcap": None, "final_softcap": None, "sliding_window": None,
        "rope_scaling": None, "use_post_norms": False,
        "query_pre_attn_scalar": None}


def ref_config(model: Dict[str, Any]) -> RefConfig:
    """From the ``model`` block of a configuration file.  The list of keys is
    closed: one that is not known here belongs to another architecture's
    reference (the configuration file's ``"reference"``)."""
    fields = [f for f in RefConfig._fields if f != "sample_vocab"]
    known = set(fields) | set(_OFF) | {"local_layer_pattern"}
    unknown = sorted(set(model) - known)
    if unknown:
        raise ValueError(
            f"the dense reference does not compute {', '.join(unknown)}: the "
            "configuration needs a reference of its own")
    for key, off in _OFF.items():
        if model.get(key, off) != off:
            raise ValueError(f"the dense reference has no {key}")
    if any(model.get("local_layer_pattern", ())):
        raise ValueError("the dense reference has no local_layer_pattern")
    if model["activation"] not in ("swiglu", "geglu"):
        raise ValueError(f"unknown activation {model['activation']!r}")
    values = {f: model[f] for f in fields}
    values["rope_theta"] = float(values["rope_theta"])
    values["rms_eps"] = float(values["rms_eps"])
    values["sample_vocab"] = min(BYTE_VOCAB, model["vocab_size"])
    return RefConfig(**values)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _make_weights(cfg: RefConfig, key: jax.Array) -> Dict[str, Any]:
    dtype = jnp.bfloat16
    keys = jax.random.split(key, 8)
    n, d, f = cfg.n_layers, cfg.d_model, cfg.ffn_hidden
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

    def dense(k, *shape, scale=None):
        scale = scale if scale is not None else shape[-2] ** -0.5
        return (jax.random.normal(k, shape) * scale).astype(dtype)

    unit = jnp.zeros if cfg.rmsnorm_style == "gemma" else jnp.ones
    layers = {
        "attn_norm": unit((n, d), dtype),
        "wq": dense(keys[0], n, d, h * hd),
        "wk": dense(keys[1], n, d, kv * hd),
        "wv": dense(keys[2], n, d, kv * hd),
        "wo": dense(keys[3], n, h * hd, d),
        "ffn_norm": unit((n, d), dtype),
        "w_gate": dense(keys[4], n, d, f),
        "w_up": dense(keys[5], n, d, f),
        "w_down": dense(keys[6], n, f, d),
    }
    weights = {
        "embed": (jax.random.normal(keys[7], (cfg.vocab_size, d)) * 0.02
                  ).astype(dtype),
        "layers": layers,
        "final_norm": unit((d,), dtype),
    }
    if not cfg.tie_lm_head:
        weights["lm_head"] = dense(
            jax.random.fold_in(keys[7], 1), cfg.vocab_size, d, scale=d ** -0.5)
    return weights


def make_weights(cfg: RefConfig, seed: int) -> Dict[str, Any]:
    """Seeded random weights in the type they are served in (bfloat16):
    normal draws scaled by fan-in**-0.5, 0.02 for the embedding, unit norms."""
    return _make_weights(cfg, seed_key(seed))


def rms_norm(x, weight, eps, style):
    normed = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    w = weight.astype(jnp.float32)
    return normed * ((1.0 + w) if style == "gemma" else w)


def rope(x, positions, theta):
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freq
    cos, sin = jnp.cos(angles)[:, :, None, :], jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


@functools.partial(jax.jit, static_argnames=("cfg", "n_scored", "precision"))
def _forward(cfg: RefConfig, weights, tokens, lengths, targets, *,
             n_scored: int, precision: str):
    """``tokens`` (B, S) right-padded, ``lengths`` (B,), ``targets`` (B, T):
    the ids scored at each row's last T real positions.  Returns, for each of
    those positions, the target's log-probability over the whole vocabulary,
    the target's logit, the best logit among sampleable ids, and that id."""
    low = precision == "fp8"
    q_in = fp8 if low else (lambda x: x)

    def mm(x, w):
        return jnp.matmul(q_in(x), q_in(w.astype(jnp.float32)), precision=HIGHEST)

    B, S = tokens.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    x = q_in(weights["embed"].astype(jnp.float32))[tokens]
    if cfg.scale_embeddings:
        x = x * jnp.float32(cfg.d_model ** 0.5)
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]  # (S query, S key)

    def layer(x, lp):
        a = rms_norm(x, lp["attn_norm"], cfg.rms_eps, cfg.rmsnorm_style)
        q = rope(mm(a, lp["wq"]).reshape(B, S, h, hd), positions, cfg.rope_theta)
        k = rope(mm(a, lp["wk"]).reshape(B, S, kv, hd), positions, cfg.rope_theta)
        v = mm(a, lp["wv"]).reshape(B, S, kv, hd)
        k = jnp.repeat(k, h // kv, axis=2)
        v = jnp.repeat(v, h // kv, axis=2)
        logits = jnp.einsum("bshd,bthd->bhst", q_in(q), q_in(k),
                            precision=HIGHEST) * (hd ** -0.5)
        logits = jnp.where(causal[None, None], logits, -jnp.inf)
        probs = jax.nn.softmax(logits, axis=-1)
        attn = jnp.einsum("bhst,bthd->bshd", q_in(probs), q_in(v),
                          precision=HIGHEST)
        x = x + mm(attn.reshape(B, S, h * hd), lp["wo"])
        f = rms_norm(x, lp["ffn_norm"], cfg.rms_eps, cfg.rmsnorm_style)
        gate = mm(f, lp["w_gate"])
        gate = (jax.nn.silu(gate) if cfg.activation == "swiglu"
                else jax.nn.gelu(gate, approximate=True))
        return x + mm(gate * mm(f, lp["w_up"]), lp["w_down"]), None

    x, _ = jax.lax.scan(layer, x, weights["layers"])
    x = rms_norm(x, weights["final_norm"], cfg.rms_eps, cfg.rmsnorm_style)
    # The hidden state that predicts position p sits at p - 1.
    at = lengths[:, None] - n_scored - 1 + jnp.arange(n_scored)[None, :]
    hidden = jnp.take_along_axis(x, jnp.maximum(at, 0)[:, :, None], axis=1)
    head = weights["embed"] if cfg.tie_lm_head else weights["lm_head"]
    logits = jnp.einsum("btd,vd->btv", q_in(hidden),
                        q_in(head.astype(jnp.float32)), precision=HIGHEST)
    lse = jax.nn.logsumexp(logits, axis=-1)
    target_logit = jnp.take_along_axis(logits, targets[:, :, None], axis=-1)[..., 0]
    sampleable = logits[..., : cfg.sample_vocab]
    return (target_logit - lse, target_logit, jnp.max(sampleable, axis=-1),
            jnp.argmax(sampleable, axis=-1))


def score_rows(cfg: RefConfig, weights, rows: Sequence[Tuple],
               precision: str = "float32") -> List[Scored]:
    return score_by_width(_forward, cfg, weights, rows, precision)
