"""A reference of its own, as a later PR's configuration brings one: the
dense forward with an RMSNorm after the attention's output projection and
after the feed-forward, each before its residual (``use_post_norms``), which
the dense reference refuses.  For the harness's test on the CPU only.

It gives the three functions a reference file has to give, and takes from
``benchmark/lib/reference.py`` what is no architecture's, and from the dense
reference the two equations it shares with it.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple

import jax
import jax.numpy as jnp

from benchmark.lib.reference import BYTE_VOCAB, fp8, score_by_width, seed_key
from benchmark.references.dense import rms_norm, rope

HIGHEST = jax.lax.Precision.HIGHEST


class RefConfig(NamedTuple):
    vocab_size: int
    d_model: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    ffn_hidden: int
    rope_theta: float
    rms_eps: float
    sample_vocab: int


#: Beside the sizes, the keys of a ``model`` block this forward knows, each
#: with the one value it computes.
_ONLY = {"activation": "swiglu", "rope_scaling": None, "attn_softcap": None,
         "final_softcap": None, "sliding_window": None,
         "local_layer_pattern": [False], "query_pre_attn_scalar": None,
         "scale_embeddings": False, "tie_lm_head": False,
         "use_post_norms": True, "rmsnorm_style": "llama"}


def ref_config(model: Dict[str, Any]) -> RefConfig:
    sizes = [f for f in RefConfig._fields if f != "sample_vocab"]
    unknown = sorted(set(model) - set(sizes) - set(_ONLY))
    if unknown:
        raise ValueError(f"the post-norm reference does not compute "
                         f"{', '.join(unknown)}")
    for key, only in _ONLY.items():
        if model[key] != only:
            raise ValueError(f"the post-norm reference has {key} {only!r} only")
    return RefConfig(**{**{f: model[f] for f in sizes},
                        "rope_theta": float(model["rope_theta"]),
                        "rms_eps": float(model["rms_eps"]),
                        "sample_vocab": min(BYTE_VOCAB, model["vocab_size"])})


@functools.partial(jax.jit, static_argnames=("cfg",))
def _make_weights(cfg: RefConfig, key: jax.Array) -> Dict[str, Any]:
    keys = jax.random.split(key, 8)
    n, d, f = cfg.n_layers, cfg.d_model, cfg.ffn_hidden
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

    def dense(k, *shape, scale=None):
        scale = scale if scale is not None else shape[-2] ** -0.5
        return (jax.random.normal(k, shape) * scale).astype(jnp.bfloat16)

    def ones(*shape):
        return jnp.ones(shape, jnp.bfloat16)

    return {
        "embed": (jax.random.normal(keys[7], (cfg.vocab_size, d)) * 0.02
                  ).astype(jnp.bfloat16),
        "layers": {
            "attn_norm": ones(n, d), "wq": dense(keys[0], n, d, h * hd),
            "wk": dense(keys[1], n, d, kv * hd),
            "wv": dense(keys[2], n, d, kv * hd),
            "wo": dense(keys[3], n, h * hd, d), "post_attn_norm": ones(n, d),
            "ffn_norm": ones(n, d), "w_gate": dense(keys[4], n, d, f),
            "w_up": dense(keys[5], n, d, f), "w_down": dense(keys[6], n, f, d),
            "post_ffn_norm": ones(n, d)},
        "final_norm": ones(d),
        "lm_head": dense(jax.random.fold_in(keys[7], 1), cfg.vocab_size, d,
                         scale=d ** -0.5),
    }


def make_weights(cfg: RefConfig, seed: int) -> Dict[str, Any]:
    return _make_weights(cfg, seed_key(seed))


def _norm(x, weight, eps):
    return rms_norm(x, weight, eps, "llama")


@functools.partial(jax.jit, static_argnames=("cfg", "n_scored", "precision"))
def _forward(cfg: RefConfig, weights, tokens, lengths, targets, *,
             n_scored: int, precision: str):
    q_in = fp8 if precision == "fp8" else (lambda x: x)

    def mm(x, w):
        return jnp.matmul(q_in(x), q_in(w.astype(jnp.float32)), precision=HIGHEST)

    B, S = tokens.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    x = q_in(weights["embed"].astype(jnp.float32))[tokens]
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]

    def layer(x, lp):
        a = _norm(x, lp["attn_norm"], cfg.rms_eps)
        q = rope(mm(a, lp["wq"]).reshape(B, S, h, hd), positions, cfg.rope_theta)
        k = rope(mm(a, lp["wk"]).reshape(B, S, kv, hd), positions, cfg.rope_theta)
        v = mm(a, lp["wv"]).reshape(B, S, kv, hd)
        k, v = (jnp.repeat(t, h // kv, axis=2) for t in (k, v))
        logits = jnp.einsum("bshd,bthd->bhst", q_in(q), q_in(k),
                            precision=HIGHEST) * (hd ** -0.5)
        probs = jax.nn.softmax(
            jnp.where(causal[None, None], logits, -jnp.inf), axis=-1)
        attn = jnp.einsum("bhst,bthd->bshd", q_in(probs), q_in(v),
                          precision=HIGHEST).reshape(B, S, h * hd)
        x = x + _norm(mm(attn, lp["wo"]), lp["post_attn_norm"], cfg.rms_eps)
        f = _norm(x, lp["ffn_norm"], cfg.rms_eps)
        ffn = mm(jax.nn.silu(mm(f, lp["w_gate"])) * mm(f, lp["w_up"]),
                 lp["w_down"])
        return x + _norm(ffn, lp["post_ffn_norm"], cfg.rms_eps), None

    x, _ = jax.lax.scan(layer, x, weights["layers"])
    x = _norm(x, weights["final_norm"], cfg.rms_eps)
    at = lengths[:, None] - n_scored - 1 + jnp.arange(n_scored)[None, :]
    hidden = jnp.take_along_axis(x, jnp.maximum(at, 0)[:, :, None], axis=1)
    logits = jnp.einsum("btd,vd->btv", q_in(hidden),
                        q_in(weights["lm_head"].astype(jnp.float32)),
                        precision=HIGHEST)
    lse = jax.nn.logsumexp(logits, axis=-1)
    target = jnp.take_along_axis(logits, targets[:, :, None], axis=-1)[..., 0]
    sampleable = logits[..., : cfg.sample_vocab]
    return (target - lse, target, jnp.max(sampleable, axis=-1),
            jnp.argmax(sampleable, axis=-1))


def score_rows(cfg: RefConfig, weights, rows, precision: str = "float32"):
    return score_by_width(_forward, cfg, weights, rows, precision)
