"""The plain reference of MiMo-V2-Flash's layers, in float32: full and window
attention with their own key-value heads, key heads wider than value heads,
rotary on the leading part of a head, a learned sink logit a head in the
window layers' softmax, and a routed-expert feed-forward that is told which
experts it holds.  The reference of every configuration file that says
``"reference": "mimo_v2"``; ``tests/reference_mimo_v2.py`` is the same
mathematics on one unpadded sequence, and a test holds this file to it.

A full teacher-forced forward with no cache and no paging: float32
activations, every matrix product at ``HIGHEST`` precision, a Python loop
over the layers, attention over the whole sequence one key-value group at a
time, the experts a plain loop over the held ones with a mask on every row
(no grouping, no sort).  The weights stay in the types they are served in
and are widened a matrix at a time, the head a block of rows at a time, so
that it fits beside 6.9 GB of weights on one chip.  It imports nothing of
``consensus_tpu``: ``make_weights`` writes the program's draws out again.

The equations (RMSNorm is ``x * w``; layer ``l`` is a window layer where
``hybrid_layer_pattern[l]`` is 1 and routed where ``moe_layer_freq[l]`` is 1):

    x = Embed[tokens]
    u = RMSNorm(x; w_in)
    q = u Wq -> (H, hd);  k = u Wk -> (KV, hd);  v = (u Wv) * value_scale -> (KV, vd)
    rotary, half-split, on dims [0, rotary_dim) of q and k; theta by kind
    s_ij = q_i . k_j / sqrt(hd);  j <= i;  window layers also i - j < window
    full:    p_ij = exp(s_ij) / sum_j' exp(s_ij')
    window:  p_ij = exp(s_ij) / (exp(sink_h) + sum_j' exp(s_ij'))
    o_i = sum_j p_ij v_j;  x = x + o Wo
    t = RMSNorm(x; w_ff)
    dense:   f = (silu(t Wg) * (t Wu)) Wd
    routed:  g = sigmoid(t Wr);  S = top-k of (g + b);  w_e = g_e / (sum_S g + 1e-20)
             f = sum_{e in S, e held} w_e (silu(t Wg_e) * (t Wu_e)) Wd_e
    x = x + f
    logits = RMSNorm(x; w_final) W_head

What the absent experts would have added is left out, as in the program.  Not
computed: the model's multi-token-prediction layers (no key of the catalog's
config names them, and they are no part of the main forward pass).

``precision="fp8"`` is the control of the output check: the same forward with
every weight and every product's inputs (the router's among them) rounded to
float8 (e4m3), the nearest precision below bfloat16.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp

from benchmark.lib.reference import (BYTE_VOCAB, Scored, fp8, score_by_width,
                                     seed_key)

HIGHEST = jax.lax.Precision.HIGHEST

#: ``fold_in`` data of a kind's key, as ``init_params`` has it.
_KIND_KEY_BASE = 200
#: Rows of the head widened to float32 at a time.
_HEAD_BLOCK = 16384


class RefConfig(NamedTuple):
    """The sizes the forward needs, hashable so that ``jit`` can take it."""

    vocab_size: int
    d_model: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    ffn_hidden: int
    rope_theta: float
    rms_eps: float
    sliding_window: int
    hybrid_layer_pattern: Tuple[int, ...]
    moe_layer_freq: Tuple[int, ...]
    swa_kv_heads: int
    swa_rope_theta: float
    swa_sink: bool
    v_head_dim: int
    rotary_dim: int
    value_scale: float
    n_experts: int
    experts_per_token: int
    expert_hidden: int
    experts_held: Tuple[int, int]
    sample_vocab: int


#: Keys this forward knows and computes only at the value given here.
_FIXED = {"attn_softcap": None, "final_softcap": None, "rope_scaling": None,
          "use_post_norms": False, "query_pre_attn_scalar": None,
          "scale_embeddings": False, "tie_lm_head": False,
          "rmsnorm_style": "llama", "activation": "swiglu"}


def ref_config(model: Dict[str, Any]) -> RefConfig:
    """From the ``model`` block of a configuration file.  The list of keys is
    closed: any other is refused before anything runs."""
    fields = [f for f in RefConfig._fields if f != "sample_vocab"]
    known = set(fields) | set(_FIXED) | {"local_layer_pattern"}
    unknown = sorted(set(model) - known)
    if unknown:
        raise ValueError(
            f"the mimo_v2 reference does not compute {', '.join(unknown)}")
    missing = sorted(f for f in fields if model.get(f) is None)
    if missing:
        raise ValueError(f"the mimo_v2 reference needs {', '.join(missing)}")
    for key, fixed in _FIXED.items():
        if key in model and model[key] != fixed:
            raise ValueError(f"the mimo_v2 reference has {key} = {fixed!r} only")
    if any(model.get("local_layer_pattern", ())):
        raise ValueError("the mimo_v2 reference has no local_layer_pattern")
    values = {f: model[f] for f in fields}
    for key in ("rope_theta", "rms_eps", "swa_rope_theta", "value_scale"):
        values[key] = float(values[key])
    for key in ("hybrid_layer_pattern", "moe_layer_freq", "experts_held"):
        values[key] = tuple(int(v) for v in values[key])
    n = values["n_layers"]
    if len(values["hybrid_layer_pattern"]) != n or len(values["moe_layer_freq"]) != n:
        raise ValueError("hybrid_layer_pattern and moe_layer_freq have one "
                         "entry a layer")
    first, count = values["experts_held"]
    if not (0 <= first and count > 0 and first + count <= values["n_experts"]
            and 0 < values["experts_per_token"] <= values["n_experts"]):
        raise ValueError("experts_held = (first, count) lies inside the "
                         "router's n_experts, experts_per_token too")
    values["sample_vocab"] = min(BYTE_VOCAB, model["vocab_size"])
    return RefConfig(**values)


class _Kind(NamedTuple):
    name: str
    window: bool
    routed: bool
    kv_heads: int
    theta: float


def _layer_kinds(cfg: RefConfig) -> List[Tuple[_Kind, int]]:
    """(the layer's kind, its index in the kind's stack), a layer."""
    seen: Dict[str, int] = {}
    out = []
    for window, routed in zip(cfg.hybrid_layer_pattern, cfg.moe_layer_freq):
        name = f"{'window' if window else 'full'}_{'moe' if routed else 'dense'}"
        kind = _Kind(name, bool(window), bool(routed),
                     cfg.swa_kv_heads if window else cfg.n_kv_heads,
                     cfg.swa_rope_theta if window else cfg.rope_theta)
        out.append((kind, seen.get(name, 0)))
        seen[name] = seen.get(name, 0) + 1
    return out


@functools.partial(jax.jit, static_argnames=("cfg",))
def _make_weights(cfg: RefConfig, key: jax.Array) -> Dict[str, Any]:
    dtype = jnp.bfloat16
    d, h, hd, vd = cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.v_head_dim
    first, count = cfg.experts_held

    def dense(k, *shape):
        return (jax.random.normal(k, shape) * shape[-2] ** -0.5).astype(dtype)

    def by_expert(k, n, *shape):
        # Expert e's matrices come from fold_in(k, e), e over the whole router.
        drawn = jax.vmap(lambda e: dense(jax.random.fold_in(k, e), n, *shape))(
            first + jnp.arange(count))
        return jnp.moveaxis(drawn, 0, 1)

    counts: Dict[_Kind, int] = {}
    for kind, _ in _layer_kinds(cfg):
        counts[kind] = counts.get(kind, 0) + 1
    layers = {}
    for index, (kind, n) in enumerate(counts.items()):
        keys = jax.random.split(jax.random.fold_in(key, _KIND_KEY_BASE + index), 12)
        kv = kind.kv_heads
        leaves = {
            "attn_norm": jnp.ones((n, d), dtype),
            "wq": dense(keys[0], n, d, h * hd),
            "wk": dense(keys[1], n, d, kv * hd),
            "wv": dense(keys[2], n, d, kv * vd),
            "wo": dense(keys[3], n, h * vd, d),
            "ffn_norm": jnp.ones((n, d), dtype),
        }
        if kind.window and cfg.swa_sink:
            leaves["attn_sink"] = jax.random.normal(keys[4], (n, h))
        if kind.routed:
            f = cfg.expert_hidden
            leaves.update({
                "router": jax.random.normal(keys[5], (n, d, cfg.n_experts))
                * d ** -0.5,
                "router_bias": jax.random.normal(keys[6], (n, cfg.n_experts)) * 0.1,
                "experts_gate": by_expert(keys[7], n, d, f),
                "experts_up": by_expert(keys[8], n, d, f),
                "experts_down": by_expert(keys[9], n, f, d),
            })
        else:
            leaves.update({
                "w_gate": dense(keys[7], n, d, cfg.ffn_hidden),
                "w_up": dense(keys[8], n, d, cfg.ffn_hidden),
                "w_down": dense(keys[9], n, cfg.ffn_hidden, d),
            })
        layers[kind.name] = leaves
    top = jax.random.split(key, 8)[7]
    return {
        "embed": (jax.random.normal(top, (cfg.vocab_size, d)) * 0.02).astype(dtype),
        "layers": layers,
        "final_norm": jnp.ones((d,), dtype),
        "lm_head": (jax.random.normal(jax.random.fold_in(top, 1),
                                      (cfg.vocab_size, d)) * d ** -0.5).astype(dtype),
    }


def make_weights(cfg: RefConfig, seed: int) -> Dict[str, Any]:
    """Seeded random weights in the types they are served in: bfloat16, and
    float32 for the router, its selection bias (normal at 0.1) and the sinks
    (unit normal).  Each matrix is a normal draw at fan-in scale, the
    embedding at 0.02; a kind's leaves come from the twelve keys that
    ``fold_in(key, 200 + the kind's number)`` splits into."""
    return _make_weights(cfg, seed_key(seed))


def rms_norm(x, weight, eps):
    normed = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return normed * weight.astype(jnp.float32)


def rope(x, positions, theta, rotary):
    """Half-split rotation of the leading ``rotary`` dims of (B, S, H, hd)."""
    half = rotary // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freq
    cos, sin = jnp.cos(angles)[:, :, None, :], jnp.sin(angles)[:, :, None, :]
    x1, x2, rest = x[..., :half], x[..., half:rotary], x[..., rotary:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def _attention(cfg: RefConfig, kind: _Kind, lp, u, positions, mm, q_in):
    B, S, _ = u.shape
    h, kv, hd, vd = cfg.n_heads, kind.kv_heads, cfg.head_dim, cfg.v_head_dim
    reps = h // kv
    q = rope(mm(u, lp["wq"]).reshape(B, S, h, hd), positions, kind.theta,
             cfg.rotary_dim)
    k = rope(mm(u, lp["wk"]).reshape(B, S, kv, hd), positions, kind.theta,
             cfg.rotary_dim)
    v = (mm(u, lp["wv"]) * cfg.value_scale).reshape(B, S, kv, vd)
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    seen = j <= i  # (S query, S key)
    if kind.window:
        seen = seen & (i - j < cfg.sliding_window)
    with_sink = kind.window and cfg.swa_sink
    sinks = (lp["attn_sink"] if with_sink else jnp.zeros((h,), jnp.float32)
             ).reshape(kv, reps)

    def one_group(group):
        qg, kg, vg, sink = group  # (B, S, reps, hd), (B, S, hd), (B, S, vd), (reps,)
        logits = jnp.einsum("bsrd,btd->brst", q_in(qg), q_in(kg),
                            precision=HIGHEST) * (hd ** -0.5)
        logits = jnp.where(seen[None, None], logits, -jnp.inf)
        if with_sink:  # a column with no value
            column = jnp.broadcast_to(sink[None, :, None, None], (B, reps, S, 1))
            probs = jax.nn.softmax(
                jnp.concatenate([logits, column], axis=-1), axis=-1)[..., :-1]
        else:
            probs = jax.nn.softmax(logits, axis=-1)
        return jnp.einsum("brst,btd->bsrd", q_in(probs), q_in(vg),
                          precision=HIGHEST)

    groups = (jnp.moveaxis(q.reshape(B, S, kv, reps, hd), 2, 0),
              jnp.moveaxis(k, 2, 0), jnp.moveaxis(v, 2, 0), sinks)
    out = jax.lax.map(one_group, groups)  # (kv, B, S, reps, vd)
    out = jnp.moveaxis(out, 0, 2).reshape(B, S, h * vd)
    return mm(out, lp["wo"])


def _experts(cfg: RefConfig, lp, t, mm, q_in):
    """The held experts' part of the routed layer: a loop over them, each on
    every row, under the weight the row gave it (0 where it was not chosen)."""
    first, count = cfg.experts_held
    g = jax.nn.sigmoid(mm(t, lp["router"]))
    _, chosen = jax.lax.top_k(g + lp["router_bias"], cfg.experts_per_token)
    picked = jnp.take_along_axis(g, chosen, axis=-1)
    weights = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)

    def one_expert(out, expert):
        w_gate, w_up, w_down, number = expert
        weight = jnp.sum(jnp.where(chosen == number, weights, 0.0), axis=-1)
        hidden = jax.nn.silu(mm(t, w_gate)) * mm(t, w_up)
        return out + weight[..., None] * mm(hidden, w_down), None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(t),
        (lp["experts_gate"], lp["experts_up"], lp["experts_down"],
         first + jnp.arange(count)))
    return out


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
    x = q_in(weights["embed"][tokens].astype(jnp.float32))
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    for kind, at in _layer_kinds(cfg):
        lp = jax.tree.map(lambda a: a[at], weights["layers"][kind.name])
        u = rms_norm(x, lp["attn_norm"], cfg.rms_eps)
        x = x + _attention(cfg, kind, lp, u, positions, mm, q_in)
        t = rms_norm(x, lp["ffn_norm"], cfg.rms_eps)
        if kind.routed:
            x = x + _experts(cfg, lp, t, mm, q_in)
        else:
            x = x + mm(jax.nn.silu(mm(t, lp["w_gate"])) * mm(t, lp["w_up"]),
                       lp["w_down"])
    x = rms_norm(x, weights["final_norm"], cfg.rms_eps)
    # The hidden state that predicts position p sits at p - 1.
    at = lengths[:, None] - n_scored - 1 + jnp.arange(n_scored)[None, :]
    hidden = q_in(jnp.take_along_axis(x, jnp.maximum(at, 0)[:, :, None], axis=1))

    # The head a block of rows at a time: a streamed logsumexp, the target's
    # logit where its block passes, the best sampleable logit in the first.
    head, vocab = weights["lm_head"], cfg.vocab_size
    block = min(_HEAD_BLOCK, vocab)
    n_blocks = -(-vocab // block)

    def head_block(carry, i):
        run_max, run_sum, target_logit = carry
        start = jnp.minimum(i * block, vocab - block)
        rows = jax.lax.dynamic_slice_in_dim(head, start, block, axis=0)
        logits = jnp.einsum("btd,vd->btv", hidden, q_in(rows.astype(jnp.float32)),
                            precision=HIGHEST)
        ids = start + jnp.arange(block)
        fresh = ids >= i * block  # the last block overlaps the one before
        hit = (ids[None, None, :] == targets[:, :, None]) & fresh[None, None, :]
        target_logit = target_logit + jnp.sum(jnp.where(hit, logits, 0.0), axis=-1)
        masked = jnp.where(fresh[None, None, :], logits, -jnp.inf)
        new_max = jnp.maximum(run_max, jnp.max(masked, axis=-1))
        run_sum = run_sum * jnp.exp(run_max - new_max) + jnp.sum(
            jnp.exp(masked - new_max[..., None]), axis=-1)
        return (new_max, run_sum, target_logit), None

    zeros = jnp.zeros((B, n_scored), jnp.float32)
    (run_max, run_sum, target_logit), _ = jax.lax.scan(
        head_block, (jnp.full((B, n_scored), -jnp.inf), zeros, zeros),
        jnp.arange(n_blocks))
    lse = run_max + jnp.log(run_sum)
    sampleable = jnp.einsum(
        "btd,vd->btv", hidden,
        q_in(head[: cfg.sample_vocab].astype(jnp.float32)), precision=HIGHEST)
    return (target_logit - lse, target_logit, jnp.max(sampleable, axis=-1),
            jnp.argmax(sampleable, axis=-1))


def score_rows(cfg: RefConfig, weights, rows: Sequence[Tuple],
               precision: str = "float32") -> List[Scored]:
    return score_by_width(_forward, cfg, weights, rows, precision)
