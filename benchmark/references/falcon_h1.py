"""The plain reference of the Falcon-H1 block, in float32: a Mamba-2 mixer
beside grouped-query attention in every layer, muP multipliers where they
stand, an untied head.  The reference of every configuration file that says
``"reference": "falcon_h1"``; ``tests/reference_falcon_h1.py`` is the same
mathematics on one unpadded sequence, and a test holds this file to it.

A full teacher-forced forward with no cache, no paging and no state handed
on: float32 activations, every matrix product at ``HIGHEST`` precision, the
recurrence a sequential ``lax.scan`` over positions (no chunks), attention
over the whole sequence one key-value group at a time.  The weights stay in
the bfloat16 they are served in and are widened a matrix at a time inside a
``lax.scan`` over the layers, and the head a block of rows at a time, so that
it fits beside nothing else on one chip (8.8 GB of weights, a 1.7 GB layer
and a few GB of activations at 4,096 positions).  It imports nothing of
``consensus_tpu``: ``make_weights`` writes the program's draws out again.

The equations, with e = ``embedding_multiplier``, RMSNorm as ``x * w``:

    x = Embed[tokens] * e;  per layer  u = RMSNorm(x; w_in)
    attention: q = (u a_in) Wq, k = (u a_in) Wk * key_multiplier, v = (u a_in)
        Wv; rotary on q, k; causal softmax(q k^T / sqrt(head_dim)) v;
        a = (. Wo) * attention_out_multiplier
    mixer: p = ((u ssm_in) W_in) * m over [z | x | B | C | dt];
        xBC = silu(conv(xBC)), causal depthwise with bias, zeros before 0;
        dt = softplus(dt + dt_bias); A = -exp(a_log);
        H_t = exp(dt_t A) H_{t-1} + dt_t x_t (x) B_t;  y_t = H_t C_t + D x_t;
        gate then grouped RMSNorm (or norm then gate); s = (y W_out) * ssm_out
    x = x + a + s;  v = RMSNorm(x; w_ff)
    f = (silu((v W_gate) * mlp[0]) * (v W_up)) W_down * mlp[1];  x = x + f
    logits = (RMSNorm(x; w_final) W_head) * lm_head_multiplier

``precision="fp8"`` is the control of the output check: the same forward with
every weight and every product's inputs (the recurrence's x, B and C among
them) rounded to float8 (e4m3), the nearest precision below bfloat16.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp

from benchmark.lib.reference import (BYTE_VOCAB, Scored, fp8, score_by_width,
                                     seed_key)

HIGHEST = jax.lax.Precision.HIGHEST

#: ``fold_in`` data of the mixer's keys, as ``init_params`` has it.
_SSM_KEY_BASE = 100
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
    ssm_heads: int
    ssm_head_dim: int
    ssm_state: int
    ssm_groups: int
    ssm_conv: int
    ssm_chunk: int
    ssm_inner: int
    ssm_norm_before_gate: bool
    embedding_multiplier: float
    attention_in_multiplier: float
    attention_out_multiplier: float
    key_multiplier: float
    ssm_in_multiplier: float
    ssm_slice_multipliers: Tuple[float, ...]
    ssm_out_multiplier: float
    mlp_multipliers: Tuple[float, float]
    lm_head_multiplier: float
    sample_vocab: int


#: Keys this forward knows and computes only at the value given here.
_FIXED = {"attn_softcap": None, "final_softcap": None, "sliding_window": None,
          "rope_scaling": None, "use_post_norms": False,
          "query_pre_attn_scalar": None, "scale_embeddings": False,
          "tie_lm_head": False, "rmsnorm_style": "llama",
          "activation": "swiglu"}


def ref_config(model: Dict[str, Any]) -> RefConfig:
    """From the ``model`` block of a configuration file.  The list of keys is
    closed, and every multiplier has to be there: one that is left out would
    be a 1 in the program and nothing here."""
    fields = [f for f in RefConfig._fields if f != "sample_vocab"]
    known = set(fields) | set(_FIXED) | {"local_layer_pattern"}
    unknown = sorted(set(model) - known)
    if unknown:
        raise ValueError(
            f"the falcon_h1 reference does not compute {', '.join(unknown)}")
    missing = sorted(f for f in fields if model.get(f) is None)
    if missing:
        raise ValueError(
            f"the falcon_h1 reference needs {', '.join(missing)}")
    for key, fixed in _FIXED.items():
        if key in model and model[key] != fixed:
            raise ValueError(
                f"the falcon_h1 reference has {key} = {fixed!r} only")
    if any(model.get("local_layer_pattern", ())):
        raise ValueError("the falcon_h1 reference has no local_layer_pattern")
    if model["ssm_inner"] != model["ssm_heads"] * model["ssm_head_dim"]:
        raise ValueError("ssm_inner is not ssm_heads x ssm_head_dim")
    values = {f: model[f] for f in fields}
    for key in ("rope_theta", "rms_eps"):
        values[key] = float(values[key])
    for key in ("ssm_slice_multipliers", "mlp_multipliers"):
        values[key] = tuple(float(m) for m in values[key])
    if len(values["ssm_slice_multipliers"]) != 5:
        raise ValueError("ssm_slice_multipliers is one a slice of [z|x|B|C|dt]")
    values["sample_vocab"] = min(BYTE_VOCAB, model["vocab_size"])
    return RefConfig(**values)


def _conv_dim(cfg: RefConfig) -> int:
    return cfg.ssm_inner + 2 * cfg.ssm_groups * cfg.ssm_state


@functools.partial(jax.jit, static_argnames=("cfg",))
def _make_weights(cfg: RefConfig, key: jax.Array) -> Dict[str, Any]:
    dtype = jnp.bfloat16
    keys = jax.random.split(key, 8)
    n, d, f = cfg.n_layers, cfg.d_model, cfg.ffn_hidden
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

    def dense(k, *shape, scale=None, over=()):
        # Fan-in scale, divided by the multipliers between the matrix and
        # its branch's output, so that the branch is of unit order.
        scale = scale if scale is not None else shape[-2] ** -0.5
        for m in over:
            scale = scale / m
        return (jax.random.normal(k, shape) * scale).astype(dtype)

    a_in = cfg.attention_in_multiplier
    layers = {
        "attn_norm": jnp.ones((n, d), dtype),
        "wq": dense(keys[0], n, d, h * hd, over=(a_in,)),
        "wk": dense(keys[1], n, d, kv * hd, over=(a_in, cfg.key_multiplier)),
        "wv": dense(keys[2], n, d, kv * hd, over=(a_in,)),
        "wo": dense(keys[3], n, h * hd, d, over=(cfg.attention_out_multiplier,)),
        "ffn_norm": jnp.ones((n, d), dtype),
        "w_gate": dense(keys[4], n, d, f, over=(cfg.mlp_multipliers[0],)),
        "w_up": dense(keys[5], n, d, f),
        "w_down": dense(keys[6], n, f, d, over=(cfg.mlp_multipliers[1],)),
    }
    k_in, k_conv, k_bias, k_a, k_dt, k_out = (
        jax.random.fold_in(key, _SSM_KEY_BASE + i) for i in range(6))
    gn = cfg.ssm_groups * cfg.ssm_state
    widths = (cfg.ssm_inner, cfg.ssm_inner, gn, gn, cfg.ssm_heads)
    col_scale = jnp.concatenate([
        jnp.full((w,), d ** -0.5 / (m * cfg.ssm_in_multiplier), jnp.float32)
        for w, m in zip(widths, cfg.ssm_slice_multipliers)])
    step = jnp.exp(
        jax.random.uniform(k_dt, (n, cfg.ssm_heads))
        * (jnp.log(0.1) - jnp.log(0.001)) + jnp.log(0.001))
    in_dim = cfg.ssm_inner + _conv_dim(cfg) + cfg.ssm_heads
    layers.update({
        "ssm_in": (jax.random.normal(k_in, (n, d, in_dim)) * col_scale
                   ).astype(dtype),
        "ssm_conv_w": (jax.random.normal(k_conv, (n, cfg.ssm_conv, _conv_dim(cfg)))
                       * cfg.ssm_conv ** -0.5).astype(dtype),
        "ssm_conv_b": (jax.random.normal(k_bias, (n, _conv_dim(cfg))) * 0.1
                       ).astype(dtype),
        "ssm_a_log": jnp.log(jax.random.uniform(
            k_a, (n, cfg.ssm_heads), minval=1.0, maxval=16.0)),
        "ssm_dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "ssm_d": jnp.ones((n, cfg.ssm_heads), jnp.float32),
        "ssm_norm": jnp.ones((n, cfg.ssm_inner), dtype),
        "ssm_out": (jax.random.normal(k_out, (n, cfg.ssm_inner, d))
                    * (cfg.ssm_inner ** -0.5 / cfg.ssm_out_multiplier)
                    ).astype(dtype),
    })
    return {
        "embed": (jax.random.normal(keys[7], (cfg.vocab_size, d))
                  * (1.0 / cfg.embedding_multiplier)).astype(dtype),
        "layers": layers,
        "final_norm": jnp.ones((d,), dtype),
        "lm_head": dense(jax.random.fold_in(keys[7], 1), cfg.vocab_size, d,
                         scale=d ** -0.5, over=(cfg.lm_head_multiplier,)),
    }


def make_weights(cfg: RefConfig, seed: int) -> Dict[str, Any]:
    """Seeded random weights in the types they are served in: bfloat16, and
    float32 for the three vectors a head (A's logarithm in [0, log 16], the
    step's bias the inverse softplus of a log-uniform step in [0.001, 0.1],
    D = 1).  Each matrix is a normal draw at fan-in scale over its branch's
    multipliers; the embedding at 1 / ``embedding_multiplier``."""
    return _make_weights(cfg, seed_key(seed))


def rms_norm(x, weight, eps):
    normed = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return normed * weight.astype(jnp.float32)


def rope(x, positions, theta):
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freq
    cos, sin = jnp.cos(angles)[:, :, None, :], jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _mixer(cfg: RefConfig, lp, u, mm, q_in):
    """The Mamba-2 branch on ``u`` (B, S, D), every row from a zero state.
    Right padding is harmless: position t reads nothing after t."""
    B, S, _ = u.shape
    heads, p, n, g = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups
    inner, gn, k = cfg.ssm_inner, cfg.ssm_groups * cfg.ssm_state, cfg.ssm_conv
    proj = mm(u * cfg.ssm_in_multiplier, lp["ssm_in"])
    m = cfg.ssm_slice_multipliers
    z = proj[..., :inner] * m[0]
    xbc = jnp.concatenate([
        proj[..., inner:2 * inner] * m[1],
        proj[..., 2 * inner:2 * inner + gn] * m[2],
        proj[..., 2 * inner + gn:2 * inner + 2 * gn] * m[3]], axis=-1)
    dt = proj[..., 2 * inner + 2 * gn:] * m[4]
    padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    taps = q_in(lp["ssm_conv_w"].astype(jnp.float32))
    conv = q_in(lp["ssm_conv_b"].astype(jnp.float32))[None, None, :]
    for j in range(k):
        conv = conv + padded[:, j:j + S] * taps[j][None, None, :]
    xbc = jax.nn.silu(conv)
    x = xbc[..., :inner].reshape(B, S, heads, p)
    b = jnp.repeat(xbc[..., inner:inner + gn].reshape(B, S, g, n), heads // g, axis=2)
    c = jnp.repeat(xbc[..., inner + gn:].reshape(B, S, g, n), heads // g, axis=2)
    dt = jax.nn.softplus(dt + lp["ssm_dt_bias"][None, None, :])
    a = -jnp.exp(lp["ssm_a_log"])

    def step(h, at):
        x_t, b_t, c_t, dt_t = at  # (B, H, P), (B, H, N), (B, H, N), (B, H)
        h = (jnp.exp(dt_t * a[None, :])[:, :, None, None] * h
             + (dt_t[:, :, None] * q_in(x_t))[..., None] * q_in(b_t)[:, :, None, :])
        return h, jnp.sum(h * q_in(c_t)[:, :, None, :], axis=-1)

    _, y = jax.lax.scan(
        step, jnp.zeros((B, heads, p, n), jnp.float32),
        tuple(jnp.moveaxis(t, 1, 0) for t in (x, b, c, dt)))
    y = jnp.moveaxis(y, 0, 1) + lp["ssm_d"][None, None, :, None] * x
    y = y.reshape(B, S, inner)
    gate = jax.nn.silu(z)
    if not cfg.ssm_norm_before_gate:
        y = y * gate
    yg = y.reshape(B, S, g, inner // g)
    yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, axis=-1, keepdims=True) + cfg.rms_eps)
    y = yg.reshape(B, S, inner) * lp["ssm_norm"].astype(jnp.float32)
    if cfg.ssm_norm_before_gate:
        y = y * gate
    return mm(y, lp["ssm_out"]) * cfg.ssm_out_multiplier


def _attention(cfg: RefConfig, lp, u, positions, mm, q_in):
    B, S, _ = u.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    reps = h // kv
    ua = u * cfg.attention_in_multiplier
    q = rope(mm(ua, lp["wq"]).reshape(B, S, h, hd), positions, cfg.rope_theta)
    k = rope((mm(ua, lp["wk"]) * cfg.key_multiplier).reshape(B, S, kv, hd),
             positions, cfg.rope_theta)
    v = mm(ua, lp["wv"]).reshape(B, S, kv, hd)
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]  # (S query, S key)

    def one_group(group):
        qg, kg, vg = group  # (B, S, reps, hd), (B, S, hd), (B, S, hd)
        logits = jnp.einsum("bsrd,btd->brst", q_in(qg), q_in(kg),
                            precision=HIGHEST) * (hd ** -0.5)
        probs = jax.nn.softmax(
            jnp.where(causal[None, None], logits, -jnp.inf), axis=-1)
        return jnp.einsum("brst,btd->bsrd", q_in(probs), q_in(vg),
                          precision=HIGHEST)

    groups = (jnp.moveaxis(q.reshape(B, S, kv, reps, hd), 2, 0),
              jnp.moveaxis(k, 2, 0), jnp.moveaxis(v, 2, 0))
    out = jax.lax.map(one_group, groups)  # (kv, B, S, reps, hd)
    out = jnp.moveaxis(out, 0, 2).reshape(B, S, h * hd)
    return mm(out, lp["wo"]) * cfg.attention_out_multiplier


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
    x = x * jnp.float32(cfg.embedding_multiplier)
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))

    def layer(x, lp):
        u = rms_norm(x, lp["attn_norm"], cfg.rms_eps)
        x = (x + _attention(cfg, lp, u, positions, mm, q_in)
             + _mixer(cfg, lp, u, mm, q_in))
        v = rms_norm(x, lp["ffn_norm"], cfg.rms_eps)
        gate = jax.nn.silu(mm(v, lp["w_gate"]) * cfg.mlp_multipliers[0])
        f = mm(gate * mm(v, lp["w_up"]), lp["w_down"]) * cfg.mlp_multipliers[1]
        return x + f, None

    x, _ = jax.lax.scan(layer, x, weights["layers"])
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
                            precision=HIGHEST) * cfg.lm_head_multiplier
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
        q_in(head[: cfg.sample_vocab].astype(jnp.float32)),
        precision=HIGHEST) * cfg.lm_head_multiplier
    return (target_logit - lse, target_logit, jnp.max(sampleable, axis=-1),
            jnp.argmax(sampleable, axis=-1))


def score_rows(cfg: RefConfig, weights, rows: Sequence[Tuple],
               precision: str = "float32") -> List[Scored]:
    return score_by_width(_forward, cfg, weights, rows, precision)
