"""The plain reference of JoyAI-LLM-Flash's layers, in float32: latent
attention (MLA) in every layer, a dense feed-forward in the leading layer
and, in the others, routed experts that are told which of them are held, with
a shared expert beside them and a factor on the routed sum.  The reference of
every configuration file that says ``"reference": "joyai_flash"``;
``tests/reference_joyai_flash.py`` is the same mathematics on one unpadded
sequence, and a test holds this file to it.

A full teacher-forced forward with no cache and no paging: float32
activations, every matrix product at ``HIGHEST`` precision, a Python loop
over the layers, latent attention **in the expanded form only** (every head's
own keys and values made of the latents of the whole sequence, a head at a
time over it), no batching trick.  The weights stay in the types they are
served in, beside 11.1 GB of them on one chip, and are widened a matrix at a
time, the head a block of rows at a time, the experts **an expert at a time,
each sent only the rows routed to it** (a masked loop over 256 experts on
every row would be 256 times the experts' work at the slowest precision): the
rows that chose the expert are found by a count (``nonzero`` at a fixed size)
and run through it in blocks of ``_EXPERT_ROWS``, as many blocks as its rows
need.  It imports nothing of ``consensus_tpu``: ``make_weights`` writes the
program's draws out again.

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

What absent experts would have added is left out, as in the program (this
cut holds them all).  Not computed: the model's multi-token-prediction block
behind the last layer (no part of the main forward pass).

``precision="fp8"`` is the control of the output check: the same forward with
every weight and every product's inputs (the router's among them, and so the
latent that a head's keys and values are made of) rounded to float8 (e4m3),
the nearest precision below bfloat16.
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
#: Rows an expert multiplies at a time.
_EXPERT_ROWS = 256
#: A routed layer's leaves that hold the experts, (held, ...) a layer.
_EXPERTS = ("experts_gate", "experts_up", "experts_down")


class RefConfig(NamedTuple):
    """The sizes the forward needs, hashable so that ``jit`` can take it."""

    vocab_size: int
    d_model: int
    n_layers: int
    n_heads: int
    head_dim: int
    ffn_hidden: int
    rope_theta: float
    rms_eps: float
    moe_layer_freq: Tuple[int, ...]
    v_head_dim: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_dim: int
    qk_rope_dim: int
    n_experts: int
    experts_per_token: int
    expert_hidden: int
    experts_held: Tuple[int, int]
    n_shared_experts: int
    routed_scaling_factor: float
    sample_vocab: int


#: Keys this forward knows and computes only at the value given here.
_FIXED = {"attn_softcap": None, "final_softcap": None, "rope_scaling": None,
          "use_post_norms": False, "query_pre_attn_scalar": None,
          "scale_embeddings": False, "tie_lm_head": False,
          "rmsnorm_style": "llama", "activation": "swiglu",
          "sliding_window": None, "rope_interleave": True}


def ref_config(model: Dict[str, Any]) -> RefConfig:
    """From the ``model`` block of a configuration file.  The list of keys is
    closed: any other is refused before anything runs."""
    fields = [f for f in RefConfig._fields if f != "sample_vocab"]
    known = (set(fields) | set(_FIXED)
             | {"local_layer_pattern", "hybrid_layer_pattern", "n_kv_heads"})
    unknown = sorted(set(model) - known)
    if unknown:
        raise ValueError(
            f"the joyai_flash reference does not compute {', '.join(unknown)}")
    missing = sorted(f for f in fields if model.get(f) is None)
    if missing:
        raise ValueError(f"the joyai_flash reference needs {', '.join(missing)}")
    for key, fixed in _FIXED.items():
        if key in model and model[key] != fixed:
            raise ValueError(
                f"the joyai_flash reference has {key} = {fixed!r} only")
    if "rope_interleave" not in model:
        raise ValueError("the joyai_flash reference needs rope_interleave")
    if any(model.get("local_layer_pattern", ())) or any(
            model.get("hybrid_layer_pattern", ())):
        raise ValueError("the joyai_flash reference has latent layers only: "
                         "no local_layer_pattern, a hybrid_layer_pattern of zeros")
    values = {f: model[f] for f in fields}
    for key in ("rope_theta", "rms_eps", "routed_scaling_factor"):
        values[key] = float(values[key])
    for key in ("moe_layer_freq", "experts_held"):
        values[key] = tuple(int(v) for v in values[key])
    if len(values["moe_layer_freq"]) != values["n_layers"] or len(
            model.get("hybrid_layer_pattern", ())) != values["n_layers"]:
        raise ValueError("hybrid_layer_pattern and moe_layer_freq have one "
                         "entry a layer")
    if values["head_dim"] != values["qk_nope_dim"] + values["qk_rope_dim"]:
        raise ValueError("head_dim is qk_nope_dim + qk_rope_dim")
    if model.get("n_kv_heads", values["n_heads"]) != values["n_heads"]:
        raise ValueError("latent attention makes keys and values for every "
                         "head: n_kv_heads is n_heads")
    first, count = values["experts_held"]
    if not (0 <= first and count > 0 and first + count <= values["n_experts"]
            and 0 < values["experts_per_token"] <= values["n_experts"]):
        raise ValueError("experts_held = (first, count) lies inside the "
                         "router's n_experts, experts_per_token too")
    values["sample_vocab"] = min(BYTE_VOCAB, model["vocab_size"])
    return RefConfig(**values)


def _layer_kinds(cfg: RefConfig) -> List[Tuple[str, int, bool]]:
    """(the layer's stack of weights, its index in it, routed?), a layer."""
    seen: Dict[str, int] = {}
    out = []
    for routed in cfg.moe_layer_freq:
        name = f"latent_{'moe' if routed else 'dense'}"
        out.append((name, seen.get(name, 0), bool(routed)))
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
        # Expert e's matrices come from fold_in(k, e), e over the whole
        # router; one at a time into its place, as the program draws them.
        def place(e, stack):
            drawn = dense(jax.random.fold_in(k, first + e), n, *shape)
            return jax.lax.dynamic_update_index_in_dim(stack, drawn, e, 1)

        return jax.lax.fori_loop(
            0, count, place, jnp.zeros((n, count) + shape, dtype))

    counts: Dict[str, int] = {}
    for name, _, _ in _layer_kinds(cfg):
        counts[name] = counts.get(name, 0) + 1
    layers = {}
    for index, (name, n) in enumerate(counts.items()):
        keys = jax.random.split(jax.random.fold_in(key, _KIND_KEY_BASE + index), 12)
        leaves = {
            "attn_norm": jnp.ones((n, d), dtype),
            "w_qa": dense(keys[0], n, d, cfg.q_lora_rank),
            "q_norm": jnp.ones((n, cfg.q_lora_rank), dtype),
            "w_qb": dense(keys[1], n, cfg.q_lora_rank, h * hd),
            "w_kva": dense(keys[2], n, d, cfg.kv_lora_rank + cfg.qk_rope_dim),
            "kv_norm": jnp.ones((n, cfg.kv_lora_rank), dtype),
            "w_kvb": dense(keys[10], n, cfg.kv_lora_rank,
                           h * (cfg.qk_nope_dim + vd)),
            "wo": dense(keys[3], n, h * vd, d),
            "ffn_norm": jnp.ones((n, d), dtype),
        }
        if name.endswith("_moe"):
            f = cfg.expert_hidden
            leaves.update({
                "router": jax.random.normal(keys[5], (n, d, cfg.n_experts))
                * d ** -0.5,
                "router_bias": jax.random.normal(keys[6], (n, cfg.n_experts)) * 0.1,
                "experts_gate": by_expert(keys[7], n, d, f),
                "experts_up": by_expert(keys[8], n, d, f),
                "experts_down": by_expert(keys[9], n, f, d),
            })
            if cfg.n_shared_experts:
                fs = cfg.n_shared_experts * f
                gate, up, down = (jax.random.fold_in(keys[11], i) for i in range(3))
                leaves.update({
                    "shared_gate": dense(gate, n, d, fs),
                    "shared_up": dense(up, n, d, fs),
                    "shared_down": dense(down, n, fs, d),
                })
        else:
            leaves.update({
                "w_gate": dense(keys[7], n, d, cfg.ffn_hidden),
                "w_up": dense(keys[8], n, d, cfg.ffn_hidden),
                "w_down": dense(keys[9], n, cfg.ffn_hidden, d),
            })
        layers[name] = leaves
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
    float32 for the router and its selection bias (normal at 0.1).  Each
    matrix is a normal draw at fan-in scale, the embedding at 0.02; a kind's
    leaves come from the twelve keys that ``fold_in(key, 200 + the kind's
    number)`` splits into (``W_kvb`` from the eleventh, the shared expert's
    three matrices from folds of the twelfth)."""
    return _make_weights(cfg, seed_key(seed))


def rms_norm(x, weight, eps):
    normed = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return normed * weight.astype(jnp.float32)


def rope_pairs(x, positions, theta):
    """The rotary turn of (B, S, H, rope): adjacent pairs (2i, 2i+1), by
    pos * theta^(-2i/rope); each pair stays where it was."""
    rope = x.shape[-1]
    freq = theta ** (-2.0 * jnp.arange(rope // 2, dtype=jnp.float32) / rope)
    angles = positions[..., None].astype(jnp.float32) * freq
    cos, sin = jnp.cos(angles)[:, :, None, :], jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    turned = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return turned.reshape(x.shape)


def _attention(cfg: RefConfig, lp, u, positions, mm, q_in):
    """Latent attention in the expanded form, a head at a time."""
    B, S, _ = u.shape
    h, nope, rope, vd = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    rank = cfg.kv_lora_rank
    q = mm(rms_norm(mm(u, lp["w_qa"]), lp["q_norm"], cfg.rms_eps), lp["w_qb"]
           ).reshape(B, S, h, nope + rope)
    q = jnp.concatenate(
        [q[..., :nope], rope_pairs(q[..., nope:], positions, cfg.rope_theta)],
        axis=-1)
    left = mm(u, lp["w_kva"])
    latent = rms_norm(left[..., :rank], lp["kv_norm"], cfg.rms_eps)
    k_rope = rope_pairs(left[:, :, None, rank:], positions, cfg.rope_theta)[:, :, 0]
    made = mm(latent, lp["w_kvb"]).reshape(B, S, h, nope + vd)
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    seen = j <= i  # (S query, S key)

    def one_head(head):
        qh, kh, vh = head  # (B, S, nope + rope), (B, S, nope), (B, S, vd)
        keys = jnp.concatenate([kh, k_rope], axis=-1)
        logits = jnp.einsum("bsd,btd->bst", q_in(qh), q_in(keys),
                            precision=HIGHEST) * ((nope + rope) ** -0.5)
        probs = jax.nn.softmax(jnp.where(seen[None], logits, -jnp.inf), axis=-1)
        return jnp.einsum("bst,btd->bsd", q_in(probs), q_in(vh), precision=HIGHEST)

    out = jax.lax.map(one_head, (jnp.moveaxis(q, 2, 0),
                                 jnp.moveaxis(made[..., :nope], 2, 0),
                                 jnp.moveaxis(made[..., nope:], 2, 0)))
    out = jnp.moveaxis(out, 0, 2).reshape(B, S, h * vd)  # (h, B, S, vd) back
    return mm(out, lp["wo"])


def _gated(x, w_gate, w_up, w_down, mm):
    return mm(jax.nn.silu(mm(x, w_gate)) * mm(x, w_up), w_down)


def _experts(cfg: RefConfig, lp, t, mm):
    """``factor`` x the held experts' part of the routed sum, and the shared
    expert beside it.  An expert at a time: the rows that chose it, in blocks
    of ``_EXPERT_ROWS``, as many blocks as it has rows; what they return
    added to their rows under the weight each gave it."""
    shape = t.shape
    t = t.reshape(-1, shape[-1])
    n = t.shape[0]
    first, count = cfg.experts_held
    g = jax.nn.sigmoid(mm(t, lp["router"]))
    _, chosen = jax.lax.top_k(g + lp["router_bias"], cfg.experts_per_token)
    picked = jnp.take_along_axis(g, chosen, axis=-1)
    weights = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    block = min(_EXPERT_ROWS, n)
    # One spare row past the last: where an expert's list of rows is padded.
    rows_in = jnp.concatenate([t, jnp.zeros((1, shape[-1]), t.dtype)])

    stacks, layer = lp["experts"]  # (layers of the kind, held, ...), at layer

    def one_expert(out, held):
        # The expert's matrices read where they lie: no layer's experts are
        # sliced out of the stack (2.4 GB a layer at the published sizes).
        w_gate, w_up, w_down = (
            jax.lax.dynamic_slice(
                stack, (layer, held, 0, 0), (1, 1) + stack.shape[2:])[0, 0]
            for stack in stacks)
        sent = chosen == first + held  # (n, k)
        weight = jnp.concatenate(
            [jnp.sum(jnp.where(sent, weights, 0.0), axis=-1), jnp.zeros((1,))])
        mine = jnp.any(sent, axis=-1)
        rows = jnp.concatenate([
            jnp.nonzero(mine, size=n, fill_value=n)[0],
            jnp.full((block,), n, jnp.int32)])

        def one_block(b, out):
            at = jax.lax.dynamic_slice_in_dim(rows, b * block, block)
            made = _gated(rows_in[at], w_gate, w_up, w_down, mm)
            return out.at[at].add(weight[at][:, None] * made)

        blocks = (jnp.sum(mine, dtype=jnp.int32) + block - 1) // block
        return jax.lax.fori_loop(0, blocks, one_block, out), None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros((n + 1, shape[-1]), jnp.float32),
        jnp.arange(count))
    out = cfg.routed_scaling_factor * out[:n]
    if cfg.n_shared_experts:
        out = out + _gated(t, lp["shared_gate"], lp["shared_up"],
                           lp["shared_down"], mm)
    return out.reshape(shape)


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
    for name, at, routed in _layer_kinds(cfg):
        stack = weights["layers"][name]
        lp = {leaf: a[at] for leaf, a in stack.items() if leaf not in _EXPERTS}
        if routed:
            lp["experts"] = tuple(stack[leaf] for leaf in _EXPERTS), at
        u = rms_norm(x, lp["attn_norm"], cfg.rms_eps)
        x = x + _attention(cfg, lp, u, positions, mm, q_in)
        t = rms_norm(x, lp["ffn_norm"], cfg.rms_eps)
        if routed:
            x = x + _experts(cfg, lp, t, mm)
        else:
            x = x + _gated(t, lp["w_gate"], lp["w_up"], lp["w_down"], mm)
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
