"""The plain reference: a decoder-only transformer forward in float32.

It imports nothing of ``consensus_tpu`` and takes nothing the program made.
It makes its own weights from the seed (the same draws the program's
``init_params`` makes, written out again here), renders and tokenizes its own
prompts (a copy of the byte tokenizer's rules), and computes a full
teacher-forced forward with no cache, no paging and no batching tricks:
float32 activations, every matrix product at ``HIGHEST`` precision.  It runs
layer by layer under one ``lax.scan`` with each layer's bfloat16 weights
widened to float32 inside the step, and row block by row block, so that it
fits beside nothing else on one chip.

``precision="fp8"`` is the control of the output check: the same forward
with every weight and every matrix-product input rounded to float8 (e4m3),
the nearest precision below the bfloat16 the configurations state.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST

# -- tokenizer and prompt rendering (the serving tokenizer's rules) ------------

SPECIAL_TOKENS = (
    "<pad>", "<bos>", "<eos>", "<|eot_id|>", "<|end_of_text|>",
    "<end_of_turn>", "<start_of_turn>", "[SYS]", "[/SYS]", "[USER]",
    "[/USER]", "[ASSISTANT]",
)
N_SPECIAL = len(SPECIAL_TOKENS)
#: 256 bytes and the special strings: ids at or above this are never sampled.
BYTE_VOCAB = N_SPECIAL + 256
BOS_ID = 1
_MATCH_ORDER = sorted((s for s in SPECIAL_TOKENS if s != "<pad>"),
                      key=len, reverse=True)
_SPECIAL_ID = {s: i for i, s in enumerate(SPECIAL_TOKENS)}


def encode(text: str, add_bos: bool = False) -> List[int]:
    """UTF-8 bytes shifted past the specials; a special string is one id."""
    ids = [BOS_ID] if add_bos else []
    i = 0
    while i < len(text):
        for special in _MATCH_ORDER:
            if text.startswith(special, i):
                ids.append(_SPECIAL_ID[special])
                i += len(special)
                break
        else:
            ids.extend(N_SPECIAL + b for b in text[i].encode("utf-8"))
            i += 1
    return ids


def chat_prompt(user: str, system: Optional[str]) -> str:
    if system:
        return f"[SYS]{system}[/SYS]\n[USER]{user}[/USER]\n[ASSISTANT]"
    return f"[USER]{user}[/USER]\n[ASSISTANT]"


def raw_prompt(user: str, system: Optional[str]) -> str:
    return f"{system}\n\n{user}" if system else user


def score_prefix(context: str, system: Optional[str], chat: bool, role: str) -> str:
    """The text a continuation is scored after: as a reply to a chat turn,
    inside the user turn (the evaluator's layout), or after raw text."""
    if chat and role == "user":
        parts = [p for p in (system, context) if p]
        joined = "\n\n".join(parts)
        return f"[SYS]{joined}[/SYS]\n[USER]" if joined else "[USER]"
    if chat:
        return chat_prompt(context, system)
    return raw_prompt(context, system)


# -- weights -------------------------------------------------------------------


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative whole number (seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _make_weights(cfg: "RefConfig", key: jax.Array) -> Dict[str, Any]:
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


def make_weights(cfg: "RefConfig", seed: int) -> Dict[str, Any]:
    """Seeded random weights in the type they are served in (bfloat16):
    normal draws scaled by fan-in**-0.5, 0.02 for the embedding, unit norms."""
    return _make_weights(cfg, seed_key(seed))


@jax.jit
def _leaf_checksum(x: jax.Array) -> jax.Array:
    bits = jax.lax.bitcast_convert_type(x, jnp.uint16).astype(jnp.uint32)
    weight = (jnp.arange(bits.size, dtype=jnp.uint32).reshape(bits.shape)
              % jnp.uint32(65521)) + jnp.uint32(1)
    return jnp.sum(bits * weight, dtype=jnp.uint32)


def weights_checksum(weights: Dict[str, Any]) -> Dict[str, int]:
    """A position-weighted sum of every bfloat16 leaf's bits, by leaf path:
    two weight trees agree in these only if they are the same numbers."""
    flat, _ = jax.tree_util.tree_flatten_with_path(weights)
    return {jax.tree_util.keystr(path): int(_leaf_checksum(leaf))
            for path, leaf in flat}


# -- configuration ---------------------------------------------------------------


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


def ref_config(model: Dict[str, Any]) -> RefConfig:
    """From the ``model`` block of a configuration file.  What this forward
    does not compute (soft caps, windows, post-norms, rope scaling) is
    refused, not ignored."""
    for key, off in (("attn_softcap", None), ("final_softcap", None),
                     ("sliding_window", None), ("rope_scaling", None),
                     ("use_post_norms", False),
                     ("query_pre_attn_scalar", None)):
        if model.get(key, off) != off:
            raise ValueError(f"the reference forward has no {key}")
    if model["activation"] not in ("swiglu", "geglu"):
        raise ValueError(f"unknown activation {model['activation']!r}")
    fields = {f: model[f] for f in RefConfig._fields if f != "sample_vocab"}
    fields["rope_theta"] = float(fields["rope_theta"])
    fields["rms_eps"] = float(fields["rms_eps"])
    fields["sample_vocab"] = min(BYTE_VOCAB, model["vocab_size"])
    return RefConfig(**fields)


# -- forward ---------------------------------------------------------------------


def _fp8(x: jax.Array) -> jax.Array:
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def _rms_norm(x, weight, eps, style):
    normed = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    w = weight.astype(jnp.float32)
    return normed * ((1.0 + w) if style == "gemma" else w)


def _rope(x, positions, theta):
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
    q_in = _fp8 if low else (lambda x: x)

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
        a = _rms_norm(x, lp["attn_norm"], cfg.rms_eps, cfg.rmsnorm_style)
        q = _rope(mm(a, lp["wq"]).reshape(B, S, h, hd), positions, cfg.rope_theta)
        k = _rope(mm(a, lp["wk"]).reshape(B, S, kv, hd), positions, cfg.rope_theta)
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
        f = _rms_norm(x, lp["ffn_norm"], cfg.rms_eps, cfg.rmsnorm_style)
        gate = mm(f, lp["w_gate"])
        gate = (jax.nn.silu(gate) if cfg.activation == "swiglu"
                else jax.nn.gelu(gate, approximate=True))
        return x + mm(gate * mm(f, lp["w_up"]), lp["w_down"]), None

    x, _ = jax.lax.scan(layer, x, weights["layers"])
    x = _rms_norm(x, weights["final_norm"], cfg.rms_eps, cfg.rmsnorm_style)
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


#: Sequence widths the forward is compiled at, and the rows one call takes at
#: each, so that the (rows, heads, S, S) float32 attention logits stay near a
#: gigabyte.  A handful of programs whatever the prompts' lengths.
_WIDTHS = ((512, 8), (1024, 8), (2048, 2), (3072, 1), (4096, 1), (8192, 1))
_SCORED_BUCKET = 64


class Scored:
    """One row's readings at its scored positions (numpy, float32)."""

    def __init__(self, logprob, target_logit, best_logit, best_id):
        self.logprob = logprob
        self.target_logit = target_logit
        self.best_logit = best_logit
        self.best_id = best_id


def score_rows(cfg: RefConfig, weights, rows: Sequence[Tuple],
               precision: str = "float32") -> List[Scored]:
    """``rows`` are (token ids, how many trailing ids are scored) or, to read
    other ids' logits at those same positions, (ids, count, target ids).
    Each row's whole sequence runs through the model once, in its width's
    block."""
    out: List[Optional[Scored]] = [None] * len(rows)
    by_shape: Dict[Tuple[int, int, int], List[int]] = {}
    for index, row in enumerate(rows):
        ids, n_scored = row[0], row[1]
        if not 0 < n_scored < len(ids):
            raise ValueError(f"row {index}: {n_scored} scored of {len(ids)} ids")
        for width, block in _WIDTHS:
            if len(ids) <= width:
                break
        else:
            raise ValueError(f"row {index}: {len(ids)} ids pass {_WIDTHS[-1][0]}")
        scored = -(-n_scored // _SCORED_BUCKET) * _SCORED_BUCKET
        by_shape.setdefault((width, block, scored), []).append(index)
    for (width, block, scored), indices in by_shape.items():
        for start in range(0, len(indices), block):
            chunk = indices[start:start + block]
            tokens = np.zeros((block, width), np.int32)
            lengths = np.full((block,), scored + 1, np.int32)
            targets = np.zeros((block, scored), np.int32)
            for slot, index in enumerate(chunk):
                ids, n_scored = rows[index][0], rows[index][1]
                tokens[slot, :len(ids)] = ids
                lengths[slot] = len(ids)
                targets[slot, scored - n_scored:] = (
                    rows[index][2] if len(rows[index]) > 2
                    else ids[len(ids) - n_scored:])
            result = _forward(cfg, weights, jnp.asarray(tokens),
                              jnp.asarray(lengths), jnp.asarray(targets),
                              n_scored=scored, precision=precision)
            logprob, target, best, best_id = (np.asarray(a) for a in result)
            for slot, index in enumerate(chunk):
                n_scored = rows[index][1]
                out[index] = Scored(logprob[slot, -n_scored:],
                                    target[slot, -n_scored:],
                                    best[slot, -n_scored:],
                                    best_id[slot, -n_scored:])
    return out  # type: ignore[return-value]
