"""The plain reference of MiMo-V2-Flash's layers: full and window attention
with their own key-value heads, key heads wider than value heads, rotary on
the leading part of a head, a learned sink logit a head in the window layers'
softmax, and a routed-expert feed-forward that is told which experts it holds.

A full teacher-forced forward of ONE unpadded sequence in ``jax.numpy`` and
float32 with ``jax.default_matmul_precision("highest")``: a Python loop over
the layers, attention over the whole sequence, the experts a plain loop over
the held ones with a mask (no grouping, no sort), no cache and no batching
trick.  It imports nothing of ``consensus_tpu``; the configuration is any
object with the fields read here (a ``ModelConfig`` has them) and the weights
are the tree that ``init_params`` makes.

The equations (RMSNorm is ``x * w``; ``kind(l)`` is ``hybrid_layer_pattern[l]``,
0 full and 1 window; layer ``l`` is routed where ``moe_layer_freq[l]`` is 1):

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

The planted faults of the tests are arguments: ``sink=False`` drops the sink,
``bias_in_weights=True`` takes the weights from g + b, ``router_dtype`` runs
the router's product in a lower precision, and ``held`` replaces the experts
held (another chip's share, or all of them with that tree's weights).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta, rotary):
    """Half-split rotation of the leading ``rotary`` dims of (S, H, hd)."""
    seq, half = x.shape[0], rotary // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(seq, dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half:rotary], x[..., rotary:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def kinds(c):
    """(name of the layer's stack, its index in it, window?, routed?) a layer."""
    seen, out = {}, []
    routed = c.moe_layer_freq or (0,) * c.n_layers
    for window, moe in zip(c.hybrid_layer_pattern, routed):
        name = f"{'window' if window else 'full'}_{'moe' if moe else 'dense'}"
        out.append((name, seen.get(name, 0), bool(window), bool(moe)))
        seen[name] = seen.get(name, 0) + 1
    return out


def attention(c, lp, u, window, sink=True):
    seq = u.shape[0]
    h, hd = c.n_heads, c.head_dim
    vd = c.v_head_dim or hd
    kv = (c.swa_kv_heads or c.n_kv_heads) if window else c.n_kv_heads
    theta = (c.swa_rope_theta or c.rope_theta) if window else c.rope_theta
    rotary = c.rotary_dim or hd
    q = _rope((u @ lp["wq"]).reshape(seq, h, hd), theta, rotary)
    k = _rope((u @ lp["wk"]).reshape(seq, kv, hd), theta, rotary)
    v = (u @ lp["wv"]).reshape(seq, kv, vd)
    if c.value_scale is not None:
        v = v * c.value_scale
    k = jnp.repeat(k, h // kv, axis=1)  # head h reads key-value head h // reps
    v = jnp.repeat(v, h // kv, axis=1)
    scores = jnp.einsum("ihd,jhd->hij", q, k) * hd ** -0.5
    i, j = jnp.arange(seq)[:, None], jnp.arange(seq)[None, :]
    seen = j <= i
    if window:
        seen = seen & (i - j < c.sliding_window)
    scores = jnp.where(seen[None], scores, -jnp.inf)
    if window and c.swa_sink and sink:
        column = jnp.broadcast_to(lp["attn_sink"][:, None, None], (h, seq, 1))
        probs = jax.nn.softmax(
            jnp.concatenate([scores, column], axis=-1), axis=-1)[..., :-1]
    else:
        probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("hij,jhd->ihd", probs, v).reshape(seq, h * vd)
    return out @ lp["wo"]


def routing(c, lp, t, bias_in_weights=False, router_dtype=jnp.float32):
    """(the experts chosen (S, k), their weights (S, k))."""
    product = (t.astype(router_dtype) @ lp["router"].astype(router_dtype))
    g = jax.nn.sigmoid(product.astype(jnp.float32))
    biased = g + lp["router_bias"]
    _, chosen = jax.lax.top_k(biased, c.experts_per_token)
    picked = jnp.take_along_axis(biased if bias_in_weights else g, chosen, axis=-1)
    return chosen, picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)


def experts(c, lp, t, held=None, **faults):
    """The part of the routed layer's result that the experts ``held`` =
    (first, count) give, their matrices ``lp["experts_*"][j]`` for expert
    ``first + j``: a loop over them, each on every row under a mask."""
    first, count = held or c.experts_held
    chosen, weights = routing(c, lp, t, **faults)
    out = jnp.zeros_like(t)
    for j in range(count):
        weight = jnp.sum(jnp.where(chosen == first + j, weights, 0.0), axis=-1)
        hidden = jax.nn.silu(t @ lp["experts_gate"][j]) * (t @ lp["experts_up"][j])
        out = out + weight[:, None] * (hidden @ lp["experts_down"][j])
    return out


def forward(c, params, tokens, sink=True, **faults):
    """Logits (S, V) float32 of one unpadded sequence ``tokens`` (S,)."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(jnp.float32)
        for name, at, window, routed in kinds(c):
            lp = jax.tree.map(
                lambda a: a[at].astype(jnp.float32), params["layers"][name])
            u = _rms(x, lp["attn_norm"], c.rms_eps)
            x = x + attention(c, lp, u, window, sink)
            t = _rms(x, lp["ffn_norm"], c.rms_eps)
            if routed:
                x = x + experts(c, lp, t, **faults)
            else:
                x = x + (jax.nn.silu(t @ lp["w_gate"]) * (t @ lp["w_up"])
                         ) @ lp["w_down"]
        x = _rms(x, params["final_norm"].astype(jnp.float32), c.rms_eps)
        return x @ params["lm_head"].astype(jnp.float32).T


def token_logprobs(c, params, tokens, **faults):
    """log p(tokens[t] | tokens[:t]) for t >= 1, and 0.0 at t = 0."""
    logprobs = jax.nn.log_softmax(forward(c, params, tokens, **faults), axis=-1)
    picked = jnp.take_along_axis(logprobs[:-1], tokens[1:, None], axis=-1)[:, 0]
    return jnp.concatenate([jnp.zeros((1,), jnp.float32), picked])
