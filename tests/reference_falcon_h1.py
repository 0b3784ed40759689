"""The plain reference of the Falcon-H1 block: a Mamba-2 mixer beside
grouped-query attention in every block, muP multipliers where they stand.

A full teacher-forced forward in ``jax.numpy`` and float32 with
``jax.default_matmul_precision("highest")``: the recurrence is a sequential
``lax.scan`` over positions (no chunks), attention runs over the whole
sequence, there is no cache and no batching trick.  It imports nothing of
``consensus_tpu``; the configuration is any object with the fields read
here (a ``ModelConfig`` has them) and the weights are the tree that
``init_params`` makes.

The equations, with e = ``embedding_multiplier`` and RMSNorm in the ``x * w``
style:

    x = Embed[tokens] * e;  per layer  u = RMSNorm(x; w_in)
    attention: q = (u a_in) Wq, k = (u a_in) Wk * key_multiplier, v = (u a_in)
        Wv; rotary on q, k; causal softmax(q k^T / sqrt(head_dim)) v;
        a = (. Wo) * attention_out_multiplier
    mixer: p = ((u ssm_in) W_in) * m over [z | x | B | C | dt];
        xBC = silu(conv(xBC)) causal depthwise with bias; dt = softplus(dt +
        dt_bias); A = -exp(a_log); H_t = exp(dt_t A) H_{t-1} + dt_t x_t (x) B_t;
        y_t = H_t C_t + D x_t; gate then grouped RMSNorm (or the other order);
        s = (y W_out) * ssm_out_multiplier
    x = x + a + s;  v = RMSNorm(x; w_ff);
    f = (silu((v W_gate) * mlp[0]) * (v W_up)) W_down * mlp[1];  x = x + f
    logits = (RMSNorm(x; w_final) W_head) * lm_head_multiplier
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    seq, half = x.shape[0], x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(seq, dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _one(m):
    return 1.0 if m is None else m


def mixer(c, lp, u, state_dtype=jnp.float32):
    """The Mamba-2 branch on one sequence ``u`` (S, D), from a zero state.
    ``state_dtype`` is the planted fault of the tests: the state held in a
    lower precision."""
    seq = u.shape[0]
    heads, p, n, g = c.ssm_heads, c.ssm_head_dim, c.ssm_state, c.ssm_groups
    inner, gn = c.ssm_inner, c.ssm_groups * c.ssm_state
    proj = (u * _one(c.ssm_in_multiplier)) @ lp["ssm_in"]
    m = c.ssm_slice_multipliers or (1.0,) * 5
    z = proj[:, :inner] * m[0]
    x = proj[:, inner:2 * inner] * m[1]
    b = proj[:, 2 * inner:2 * inner + gn] * m[2]
    cc = proj[:, 2 * inner + gn:2 * inner + 2 * gn] * m[3]
    dt = proj[:, 2 * inner + 2 * gn:] * m[4]
    xbc = jnp.concatenate([x, b, cc], axis=-1)
    k = c.ssm_conv
    padded = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1])), xbc], axis=0)
    conv = lp["ssm_conv_b"][None, :] + sum(
        padded[j:j + seq] * lp["ssm_conv_w"][j][None, :] for j in range(k))
    xbc = jax.nn.silu(conv)
    x = xbc[:, :inner].reshape(seq, heads, p)
    b = jnp.repeat(xbc[:, inner:inner + gn].reshape(seq, g, n), heads // g, axis=1)
    cc = jnp.repeat(xbc[:, inner + gn:].reshape(seq, g, n), heads // g, axis=1)
    dt = jax.nn.softplus(dt + lp["ssm_dt_bias"][None, :])  # (S, H)
    a = -jnp.exp(lp["ssm_a_log"])  # (H,)

    def step(h, at):
        x_t, b_t, c_t, dt_t = at
        h = h.astype(jnp.float32)
        h = (jnp.exp(dt_t * a)[:, None, None] * h
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        h = h.astype(state_dtype)
        y = jnp.einsum("hpn,hn->hp", h.astype(jnp.float32), c_t)
        return h, y

    _, y = jax.lax.scan(step, jnp.zeros((heads, p, n), state_dtype),
                        (x, b, cc, dt))
    y = y + lp["ssm_d"][None, :, None] * x
    y = y.reshape(seq, inner)
    gate = jax.nn.silu(z)
    if not c.ssm_norm_before_gate:
        y = y * gate
    yg = y.reshape(seq, g, inner // g)
    yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, axis=-1, keepdims=True) + c.rms_eps)
    y = yg.reshape(seq, inner) * lp["ssm_norm"][None, :]
    if c.ssm_norm_before_gate:
        y = y * gate
    return (y @ lp["ssm_out"]) * _one(c.ssm_out_multiplier)


def attention(c, lp, u):
    seq = u.shape[0]
    h, kv, hd = c.n_heads, c.n_kv_heads, c.head_dim
    ua = u * _one(c.attention_in_multiplier)
    q = _rope((ua @ lp["wq"]).reshape(seq, h, hd), c.rope_theta)
    k = _rope(((ua @ lp["wk"]) * _one(c.key_multiplier)).reshape(seq, kv, hd),
              c.rope_theta)
    v = (ua @ lp["wv"]).reshape(seq, kv, hd)
    k = jnp.repeat(k, h // kv, axis=1)
    v = jnp.repeat(v, h // kv, axis=1)
    logits = jnp.einsum("shd,thd->hst", q, k) * hd ** -0.5
    causal = jnp.arange(seq)[:, None] >= jnp.arange(seq)[None, :]
    probs = jax.nn.softmax(jnp.where(causal[None], logits, -jnp.inf), axis=-1)
    out = jnp.einsum("hst,thd->shd", probs, v).reshape(seq, h * hd)
    return (out @ lp["wo"]) * _one(c.attention_out_multiplier)


def forward(c, params, tokens, state_dtype=jnp.float32):
    """Logits (S, V) of one unpadded sequence ``tokens`` (S,)."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
        x = params["embed"][tokens] * _one(c.embedding_multiplier)
        mlp = c.mlp_multipliers or (1.0, 1.0)
        for layer in range(c.n_layers):
            lp = jax.tree.map(lambda a: a[layer], params["layers"])
            u = _rms(x, lp["attn_norm"], c.rms_eps)
            x = x + attention(c, lp, u) + mixer(c, lp, u, state_dtype)
            v = _rms(x, lp["ffn_norm"], c.rms_eps)
            gate = jax.nn.silu((v @ lp["w_gate"]) * mlp[0])
            x = x + ((gate * (v @ lp["w_up"])) @ lp["w_down"]) * mlp[1]
        x = _rms(x, params["final_norm"], c.rms_eps)
        return (x @ params["lm_head"].T) * _one(c.lm_head_multiplier)


def token_logprobs(c, params, tokens, state_dtype=jnp.float32):
    """(S,) float32: entry t is log p(tokens[t] | tokens[:t]); entry 0 is 0."""
    logits = forward(c, params, tokens, state_dtype)
    lp = jax.nn.log_softmax(logits[:-1], axis=-1)
    got = jnp.take_along_axis(lp, tokens[1:, None], axis=-1)[:, 0]
    return jnp.concatenate([jnp.zeros((1,)), got])
