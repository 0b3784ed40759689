"""The plain reference of JoyAI-LLM-Flash's layers: latent attention (MLA) in
every layer, a dense feed-forward in the leading layer and, in the others,
routed experts that are told which of them are held, with a shared expert
beside them and a factor on the routed sum.

A full teacher-forced forward of ONE unpadded sequence in ``jax.numpy`` and
float32 with ``jax.default_matmul_precision("highest")``: a Python loop over
the layers, attention in the expanded form only (every head's own keys and
values made from the latents of the whole sequence), the experts a plain loop
over the held ones with a mask (no grouping, no sort), no cache and no
batching trick.  It imports nothing of ``consensus_tpu``; the configuration is
any object with the fields read here (a ``ModelConfig`` has them) and the
weights are the tree that ``init_params`` makes.

The equations (RMSNorm is ``x * w``, eps ``rms_eps``; ``u`` a layer's normed
input; H heads; layer ``l`` is routed where ``moe_layer_freq[l]`` is 1):

    x  = Embed[tokens]                                          (no scaling)
    u  = RMSNorm(x; w_in)
    cq = RMSNorm(u W_qa; w_qn)                                  q_lora_rank
    q  = cq W_qb -> (H, nope + rope) = [q_nope | q_rope]
    a  = u W_kva -> [c_raw (kv_lora_rank) | k_rope_raw (rope)]
    c  = RMSNorm(c_raw; w_kvn)                                  THE LATENT
    k_rope = rope(k_rope_raw), one for all heads; q_rope = rope(q_rope) a head:
             adjacent pairs (2i, 2i+1) turned by pos * theta^(-2i/rope)
    [k_nope_h | v_h] = c W_kvb -> (H, nope + vd);  k_h = [k_nope_h | k_rope]
    s_ij^h = q_i^h . k_j^h / sqrt(nope + rope);  j <= i;  p = softmax_j
    o_i^h = sum_j p_ij^h v_j^h;  x = x + concat_h(o^h) W_o
    t  = RMSNorm(x; w_ff)
    dense:   f = (silu(t Wg) * (t Wu)) Wd
    routed:  g = sigmoid(t Wr);  S = top-k of (g + b);  w_e = g_e / (sum_S g + 1e-20)
             f = factor * sum_{e in S, e held} w_e E_e(t)  +  E_shared(t)
             E(t) = (silu(t Wg) * (t Wu)) Wd
    x  = x + f
    logits = RMSNorm(x; w_final) W_head

Departures from the published model: none in the layers computed.  Not
computed: the multi-token-prediction block behind the last layer (no part of
the main forward pass).

The planted faults of the tests are arguments: ``shared=False`` drops the
shared expert, ``factor=`` replaces the factor on the routed sum,
``interleave=False`` turns the pairs (i, i + rope/2) instead, ``kv_norm=False``
drops the latent's norm, ``latent_dtype`` rounds the latent and the rotary key
a token leaves behind (the cache's type), and ``held`` replaces the experts
held (another chip's share).  ``shared_only`` and ``routed_only`` give the two
parts of a routed layer's feed-forward apart, for the test that adds shares.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope_pairs(x, theta, interleave=True):
    """The rotary turn of (S, H, rope): pair i is columns (2i, 2i+1), or
    (i, i + rope/2) without ``interleave``; it turns by pos * theta^(-2i/rope)
    and stays where it was."""
    seq, rope = x.shape[0], x.shape[-1]
    half = rope // 2
    freq = theta ** (-2.0 * jnp.arange(half, dtype=jnp.float32) / rope)
    ang = jnp.arange(seq, dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    if interleave:
        x1, x2 = x[..., 0::2], x[..., 1::2]
        turned = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
        return turned.reshape(x.shape)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def kinds(c):
    """(name of the layer's stack, its index in it, routed?) a layer."""
    seen, out = {}, []
    for moe in c.moe_layer_freq:
        name = f"latent_{'moe' if moe else 'dense'}"
        out.append((name, seen.get(name, 0), bool(moe)))
        seen[name] = seen.get(name, 0) + 1
    return out


def attention(c, lp, u, interleave=True, kv_norm=True, latent_dtype=None):
    """Latent attention in the expanded form on the normed rows ``u`` (S, D)."""
    seq = u.shape[0]
    h, nope, rope, vd = c.n_heads, c.qk_nope_dim, c.qk_rope_dim, c.v_head_dim
    rank = c.kv_lora_rank
    q = (_rms(u @ lp["w_qa"], lp["q_norm"], c.rms_eps) @ lp["w_qb"]).reshape(
        seq, h, nope + rope)
    q_rope = _rope_pairs(q[..., nope:], c.rope_theta, interleave)
    left = u @ lp["w_kva"]
    latent = left[:, :rank]
    if kv_norm:
        latent = _rms(latent, lp["kv_norm"], c.rms_eps)
    k_rope = _rope_pairs(left[:, None, rank:], c.rope_theta, interleave)
    if latent_dtype is not None:  # what the cache keeps of a token
        latent = latent.astype(latent_dtype).astype(jnp.float32)
        k_rope = k_rope.astype(latent_dtype).astype(jnp.float32)
    made = (latent @ lp["w_kvb"]).reshape(seq, h, nope + vd)
    k_nope, v = made[..., :nope], made[..., nope:]
    scores = (jnp.einsum("ihd,jhd->hij", q[..., :nope], k_nope)
              + jnp.einsum("ihd,jd->hij", q_rope, k_rope[:, 0])
              ) * (nope + rope) ** -0.5
    i, j = jnp.arange(seq)[:, None], jnp.arange(seq)[None, :]
    scores = jnp.where((j <= i)[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("hij,jhd->ihd", probs, v).reshape(seq, h * vd)
    return out @ lp["wo"]


def routing(c, lp, t):
    """(the experts chosen (S, k), their weights (S, k))."""
    g = jax.nn.sigmoid(t @ lp["router"])
    _, chosen = jax.lax.top_k(g + lp["router_bias"], c.experts_per_token)
    picked = jnp.take_along_axis(g, chosen, axis=-1)
    return chosen, picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)


def routed_only(c, lp, t, held=None, factor=None):
    """``factor`` times the part of the routed sum that the experts ``held`` =
    (first, count) give, their matrices ``lp["experts_*"][j]`` for expert
    ``first + j``: a loop over them, each on every row under a mask."""
    first, count = held or c.experts_held
    factor = c.routed_scaling_factor if factor is None else factor
    chosen, weights = routing(c, lp, t)
    out = jnp.zeros_like(t)
    for j in range(count):
        weight = jnp.sum(jnp.where(chosen == first + j, weights, 0.0), axis=-1)
        hidden = jax.nn.silu(t @ lp["experts_gate"][j]) * (t @ lp["experts_up"][j])
        out = out + weight[:, None] * (hidden @ lp["experts_down"][j])
    return (factor or 1.0) * out


def shared_only(c, lp, t):
    """The shared expert on the same normed rows."""
    return (jax.nn.silu(t @ lp["shared_gate"]) * (t @ lp["shared_up"])
            ) @ lp["shared_down"]


def experts(c, lp, t, held=None, shared=True, factor=None):
    out = routed_only(c, lp, t, held, factor)
    if c.n_shared_experts and shared:
        out = out + shared_only(c, lp, t)
    return out


def layer_params(params, name, at):
    return jax.tree.map(
        lambda a: a[at].astype(jnp.float32), params["layers"][name])


def forward(c, params, tokens, held=None, shared=True, factor=None,
            **attention_faults):
    """Logits (S, V) float32 of one unpadded sequence ``tokens`` (S,)."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(jnp.float32)
        for name, at, routed in kinds(c):
            lp = layer_params(params, name, at)
            u = _rms(x, lp["attn_norm"], c.rms_eps)
            x = x + attention(c, lp, u, **attention_faults)
            t = _rms(x, lp["ffn_norm"], c.rms_eps)
            if routed:
                x = x + experts(c, lp, t, held, shared, factor)
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
