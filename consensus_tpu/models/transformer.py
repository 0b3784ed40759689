"""Pure-JAX decoder-only transformer runtime (Gemma-2 / Llama-3 families).

This is the component the reference outsources to the Together API — there is
no model-execution code anywhere in the reference (SURVEY §0); every decoder
"forward pass" is an HTTPS call (src/utils.py:70).  Here the model is a
functional program over a parameter pytree, designed TPU-first:

* layers are *stacked* along a leading axis and executed with ``lax.scan`` —
  one layer gets traced/compiled regardless of depth;
* static shapes everywhere: prompts are left-padded into a fixed context
  window for generation (so every decode step writes the same cache slot for
  all rows) and right-padded for teacher-forced scoring;
* grouped-query attention, RoPE, RMSNorm, GeGLU/SwiGLU, Gemma-2 logit
  softcaps and alternating sliding-window layers;
* a preallocated KV cache pytree threaded through ``forward`` so prefill and
  decode share one code path.

Everything here is shape-polymorphic in batch only; wrap calls in ``jax.jit``
(the TPU backend does) and XLA sees a single static program.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from consensus_tpu.models.config import (
    KERNEL_NEEDS_PLAIN_HEADS,
    NEEDS_ONE_KIND,
    LayerKind,
    LayerKindsUnsupported,
    ModelConfig,
    RecurrentStateUnsupported,
)
from consensus_tpu.models.quant import (
    gather_target_logits,
    head_matmul,
    matmul,
    slice_rows,
    take_rows,
)
from consensus_tpu.ops.decode_attention import softmax_with_sink

Params = Dict[str, Any]


MASK_FILL = -1e9  # finite fill: pad query rows softmax to uniform, not NaN


# ---------------------------------------------------------------------------
# Parameter initialization
# ---------------------------------------------------------------------------


def init_params(
    config: ModelConfig, key: jax.Array, dtype: jnp.dtype = jnp.float32
) -> Params:
    """Random-normal params, stacked over layers on the leading axis."""
    c = config
    keys = jax.random.split(key, 8)

    def dense(k, *shape, scale=None, over=None):
        # ``over``: the multipliers that stand between this matrix and its
        # branch's output.  The draw is divided by them, so that the branch
        # comes out of unit order (a checkpoint's weights are as large).
        scale = scale if scale is not None else shape[-2] ** -0.5
        for m in over or ():
            if m is not None:
                scale = scale / m
        return (jax.random.normal(k, shape) * scale).astype(dtype)

    h, kv, hd = c.n_heads, c.n_kv_heads, c.head_dim
    a_in = c.attention_in_multiplier
    mlp = c.mlp_multipliers or (None, None)
    layers = _init_kind_params(c, key, dtype) if c.has_layer_kinds else {
        "attn_norm": jnp.zeros((c.n_layers, c.d_model), dtype)
        if c.rmsnorm_style == "gemma"
        else jnp.ones((c.n_layers, c.d_model), dtype),
        "wq": dense(keys[0], c.n_layers, c.d_model, h * hd, over=(a_in,)),
        "wk": dense(keys[1], c.n_layers, c.d_model, kv * hd,
                    over=(a_in, c.key_multiplier)),
        "wv": dense(keys[2], c.n_layers, c.d_model, kv * hd, over=(a_in,)),
        "wo": dense(keys[3], c.n_layers, h * hd, c.d_model,
                    over=(c.attention_out_multiplier,)),
        "ffn_norm": jnp.zeros((c.n_layers, c.d_model), dtype)
        if c.rmsnorm_style == "gemma"
        else jnp.ones((c.n_layers, c.d_model), dtype),
        "w_gate": dense(keys[4], c.n_layers, c.d_model, c.ffn_hidden,
                        over=(mlp[0],)),
        "w_up": dense(keys[5], c.n_layers, c.d_model, c.ffn_hidden),
        "w_down": dense(keys[6], c.n_layers, c.ffn_hidden, c.d_model,
                        over=(mlp[1],)),
    }
    if c.has_ssm:
        layers.update(_init_ssm_params(c, key, dtype))
    if c.use_post_norms:
        # Distinct buffers per leaf — aliased leaves break donation
        # (e.g. the quantization jit donates the whole pytree).
        def norm_init():
            return (
                jnp.zeros((c.n_layers, c.d_model), dtype)
                if c.rmsnorm_style == "gemma"
                else jnp.ones((c.n_layers, c.d_model), dtype)
            )

        layers["post_attn_norm"] = norm_init()
        layers["post_ffn_norm"] = norm_init()

    embed_scale = (
        0.02 if c.embedding_multiplier is None else 1.0 / c.embedding_multiplier
    )
    params: Params = {
        "embed": (
            jax.random.normal(keys[7], (c.vocab_size, c.d_model)) * embed_scale
        ).astype(dtype),
        "layers": layers,
        "final_norm": jnp.zeros((c.d_model,), dtype)
        if c.rmsnorm_style == "gemma"
        else jnp.ones((c.d_model,), dtype),
    }
    if not c.tie_lm_head:
        params["lm_head"] = dense(
            jax.random.fold_in(keys[7], 1), c.vocab_size, c.d_model,
            scale=c.d_model**-0.5, over=(c.lm_head_multiplier,),
        )
    return params


#: ``fold_in`` data of the mixer's leaves, beside the eight keys that
#: ``init_params`` splits (a split's keys are no fold of its parent).
_SSM_KEY_BASE = 100
#: ``fold_in`` data of a kind's key, for a configuration with layers of more
#: than one kind: kind ``i`` (in order of first appearance) draws its leaves
#: from the twelve keys that ``fold_in(key, 200 + i)`` splits into.
_KIND_KEY_BASE = 200


def _init_kind_params(c: ModelConfig, key: jax.Array, dtype) -> Params:
    """A stack of leaves a kind of layer, ``{kind's name: {leaf: (layers of
    the kind, ...)}}``: every matrix a normal draw at fan-in scale, so that a
    branch and the logits come out of unit order.  A window kind's sinks are
    unit normal draws and a routed kind's selection bias a normal draw at
    0.1, both float32 like the router, so that a sink takes a share of a
    softmax and the bias decides the selection near a tie.  Expert ``e``'s
    matrices are drawn from ``fold_in(leaf's key, e)`` with ``e`` counted over
    the whole router: the experts held here are the same numbers whichever
    share of them a program holds."""
    d, h, hd, vd = c.d_model, c.n_heads, c.head_dim, c.value_dim
    norm_init = jnp.zeros if c.rmsnorm_style == "gemma" else jnp.ones

    def dense(k, *shape):
        return (jax.random.normal(k, shape) * shape[-2] ** -0.5).astype(dtype)

    def by_expert(k, n, *shape):
        # An expert at a time into its place in (layers of the kind, held,
        # ...): the float32 draw that lives at once is one expert's, whatever
        # is held.  (All of them under one ``vmap`` and a ``moveaxis`` after
        # it were 6.4 GB of float32 and a 3.2 GB copy a leaf at 256 held
        # experts of 4 x 2,048 x 768.)  The bits are the ``vmap``'s.
        first, count = c.experts_held

        def place(e, stack):
            drawn = dense(jax.random.fold_in(k, first + e), n, *shape)
            return jax.lax.dynamic_update_index_in_dim(stack, drawn, e, 1)

        return jax.lax.fori_loop(
            0, count, place, jnp.zeros((n, count) + shape, dtype))

    layers = {}
    for index, (kind, n) in enumerate(c.kind_layers):
        keys = jax.random.split(
            jax.random.fold_in(key, _KIND_KEY_BASE + index), 12)
        kv = kind.kv_heads
        if kind.attention == "latent":
            # Queries through a bottleneck with a norm inside it; one latent
            # and one rotary key a token (``w_kva``), the latent normed; a
            # head's [keys without position | values] made from the latent
            # (``w_kvb``).  The last two of the twelve keys are this kind's.
            leaves = {
                "attn_norm": norm_init((n, d), dtype),
                "w_qa": dense(keys[0], n, d, c.q_lora_rank),
                "q_norm": norm_init((n, c.q_lora_rank), dtype),
                "w_qb": dense(keys[1], n, c.q_lora_rank, h * hd),
                "w_kva": dense(keys[2], n, d, c.latent_dim),
                "kv_norm": norm_init((n, c.kv_lora_rank), dtype),
                "w_kvb": dense(keys[10], n, c.kv_lora_rank,
                               h * (c.qk_nope_dim + vd)),
                "wo": dense(keys[3], n, h * vd, d),
                "ffn_norm": norm_init((n, d), dtype),
            }
        else:
            leaves = {
                "attn_norm": norm_init((n, d), dtype),
                "wq": dense(keys[0], n, d, h * hd),
                "wk": dense(keys[1], n, d, kv * hd),
                "wv": dense(keys[2], n, d, kv * vd),
                "wo": dense(keys[3], n, h * vd, d),
                "ffn_norm": norm_init((n, d), dtype),
            }
        if kind.sink:
            leaves["attn_sink"] = jax.random.normal(keys[4], (n, h))
        if kind.routed:
            f = c.expert_hidden
            leaves.update({
                "router": jax.random.normal(keys[5], (n, d, c.n_experts))
                * d ** -0.5,
                "router_bias": jax.random.normal(keys[6], (n, c.n_experts))
                * 0.1,
                "experts_gate": by_expert(keys[7], n, d, f),
                "experts_up": by_expert(keys[8], n, d, f),
                "experts_down": by_expert(keys[9], n, f, d),
            })
            if c.n_shared_experts:
                fs = c.n_shared_experts * f
                gate, up, down = (
                    jax.random.fold_in(keys[11], i) for i in range(3))
                leaves.update({
                    "shared_gate": dense(gate, n, d, fs),
                    "shared_up": dense(up, n, d, fs),
                    "shared_down": dense(down, n, fs, d),
                })
        else:
            leaves.update({
                "w_gate": dense(keys[7], n, d, c.ffn_hidden),
                "w_up": dense(keys[8], n, d, c.ffn_hidden),
                "w_down": dense(keys[9], n, c.ffn_hidden, d),
            })
        layers[kind.name] = leaves
    return layers


def _init_ssm_params(c: ModelConfig, key: jax.Array, dtype) -> Params:
    """The mixer's leaves, stacked over layers, with Mamba-2's own ranges so
    that state carries: ``A = -exp(a_log)`` in [-16, -1], a step size whose
    bias is the inverse softplus of a log-uniform draw in [0.001, 0.1],
    ``D = 1``.  The three per-head vectors stay float32 whatever ``dtype``."""
    n, d = c.n_layers, c.d_model
    k_in, k_conv, k_bias, k_a, k_dt, k_out = (
        jax.random.fold_in(key, _SSM_KEY_BASE + i) for i in range(6)
    )
    # One scale a column: each slice of [z | x | B | C | dt] is divided by
    # its own multiplier (and all by the input's), so each is of unit order.
    slices = c.ssm_slice_multipliers or (1.0,) * 5
    col_scale = jnp.concatenate([
        jnp.full((w,), d**-0.5 / (m * (c.ssm_in_multiplier or 1.0)), jnp.float32)
        for w, m in zip(c.ssm_slice_widths, slices)
    ])
    step = jnp.exp(
        jax.random.uniform(k_dt, (n, c.ssm_heads))
        * (jnp.log(0.1) - jnp.log(0.001)) + jnp.log(0.001)
    )
    return {
        "ssm_in": (
            jax.random.normal(k_in, (n, d, c.ssm_in_dim)) * col_scale
        ).astype(dtype),
        "ssm_conv_w": (
            jax.random.normal(k_conv, (n, c.ssm_conv, c.ssm_conv_dim))
            * c.ssm_conv**-0.5
        ).astype(dtype),
        "ssm_conv_b": (
            jax.random.normal(k_bias, (n, c.ssm_conv_dim)) * 0.1
        ).astype(dtype),
        "ssm_a_log": jnp.log(
            jax.random.uniform(k_a, (n, c.ssm_heads), minval=1.0, maxval=16.0)
        ),
        # softplus(x) = step  <=>  x = step + log(1 - exp(-step))
        "ssm_dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "ssm_d": jnp.ones((n, c.ssm_heads), jnp.float32),
        "ssm_norm": jnp.ones((n, c.ssm_inner), dtype),
        "ssm_out": (
            jax.random.normal(k_out, (n, c.ssm_inner, d))
            * (c.ssm_inner**-0.5 / (c.ssm_out_multiplier or 1.0))
        ).astype(dtype),
    }


# ---------------------------------------------------------------------------
# Building blocks
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, weight: jax.Array, eps: float, style: str) -> jax.Array:
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    normed = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    scale = (1.0 + weight.astype(jnp.float32)) if style == "gemma" else weight.astype(
        jnp.float32
    )
    return (normed * scale).astype(dtype)


def _rope_angles(
    positions: jax.Array,
    head_dim: int,
    theta: float,
    scaling: Optional[Tuple[float, float, float, int]] = None,
) -> Tuple[jax.Array, jax.Array]:
    half = head_dim // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if scaling is not None:
        # Llama-3.1 "llama3" rope scaling: long wavelengths are divided by
        # ``factor``, short ones kept, mid-band smoothly interpolated.
        # (The reference's main-body generation model is
        # Meta-Llama-3.1-8B-Instruct-Turbo, configs/main_body/*.yaml.)
        factor, low_freq_factor, high_freq_factor, original_max = scaling
        wavelen = 2.0 * jnp.pi / freq
        low_freq_wavelen = original_max / low_freq_factor
        high_freq_wavelen = original_max / high_freq_factor
        smooth = (original_max / wavelen - low_freq_factor) / (
            high_freq_factor - low_freq_factor
        )
        interp = (1.0 - smooth) * freq / factor + smooth * freq
        freq = jnp.where(
            wavelen > low_freq_wavelen,
            freq / factor,
            jnp.where(wavelen < high_freq_wavelen, freq, interp),
        )
    angles = positions[..., None].astype(jnp.float32) * freq  # (..., half)
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(
    x: jax.Array,
    positions: jax.Array,
    theta: float,
    scaling: Optional[Tuple[float, float, float, int]] = None,
) -> jax.Array:
    """Rotate (B, S, H, hd) by per-token positions (B, S). Half-split layout."""
    cos, sin = _rope_angles(positions, x.shape[-1], theta, scaling)
    cos = cos[:, :, None, :]  # (B, S, 1, half)
    sin = sin[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    rotated = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return rotated.astype(x.dtype)


def rope_heads(
    c: ModelConfig, x: jax.Array, positions: jax.Array, theta: float
) -> jax.Array:
    """:func:`apply_rope` on the leading ``rotary_dim`` dimensions of every
    head of (B, S, H, hd); the rest pass.  All of them where the
    configuration names no ``rotary_dim``."""
    if c.rotary_dim is None or c.rotary_dim == x.shape[-1]:
        return apply_rope(x, positions, theta, c.rope_scaling)
    turned = apply_rope(x[..., : c.rotary_dim], positions, theta, c.rope_scaling)
    return jnp.concatenate([turned, x[..., c.rotary_dim :]], axis=-1)


def rope_latent(
    c: ModelConfig, x: jax.Array, positions: jax.Array, theta: float
) -> jax.Array:
    """The rotary turn of a latent layer's rotary columns, (B, S, H,
    ``qk_rope_dim``).  With ``rope_interleave`` the pairs turned are adjacent
    columns (2i, 2i+1): they are brought side by side first, (i, i + half),
    and stay so.  A permutation of the rotary columns that queries and keys
    share changes no score, and nothing but a score reads them."""
    if c.rope_interleave:
        x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    return apply_rope(x, positions, theta, c.rope_scaling)


#: Query positions a row up to which a latent layer attends in the absorbed
#: form; a call with more takes the expanded form.  Absorbed is 2 x (2 x 512 +
#: 64) = 2,176 FLOPs a query-key-head after 8.4 MFLOP a *query* and reads a
#: key's 576 cached numbers as they lie; expanded is 2 x (192 + 128) = 640
#: FLOPs a query-key-head after 8.4 MFLOP a *key*, 20 KB a key written and
#: read back.  One query a row (a decode step, ``forward_trunk_tail``'s tail):
#: absorbed, 0.15 GFLOP a row a layer at 2k keys against 16.8 to expand them.
#: A paged chunk of 128 or 256 queries over the 1-4k keys of its tables
#: (the score chunk, the paged prefill): by FLOPs the expanded form (27 GFLOP
#: a row a layer against 36 at 256 over 2k), and on a v5e the absorbed one is
#: the faster by 5% of the whole program a layer: the einsum attention is
#: bound by what it writes, and the expanded keys and values of 16 rows x
#: 2,048 positions are 1.3 GB written and read a layer.  A prefill over its
#: own keys (the 4,096-wide trunk, an embedded text of 1,024): as many keys as
#: queries, so expanding costs what absorbing does and every pair is 3.4 times
#: cheaper: expanded, by 19% and 11%.  (``scripts/mla_form_bench.py``, one
#: layer at the published widths; PERF.md 5 has the readings.)
_MLA_ABSORBED_QUERIES = 256


def latent_form(queries: int) -> str:
    """``"absorbed"`` or ``"expanded"``: the form in which a latent layer
    attends for a call of ``queries`` query positions a row.  From the
    call's shapes alone, here and nowhere else."""
    return "absorbed" if queries <= _MLA_ABSORBED_QUERIES else "expanded"


def _kvb_heads(c: ModelConfig, w_kvb: jax.Array) -> jax.Array:
    """``w_kvb`` a head: (latent, H, [keys without position | values])."""
    return w_kvb.reshape(
        c.kv_lora_rank, c.n_heads, c.qk_nope_dim + c.value_dim)


def mla_absorb(c: ModelConfig, w_kvb: jax.Array, q: jax.Array) -> jax.Array:
    """The absorbed form's queries: (..., H, ``head_dim``) -> (..., H,
    ``latent_dim``), a head's part without position folded through the
    head's key matrix, ``q_nope (W_UK)^T``, so that its product with a cached
    [latent | rotary key] is the score; the rotary part passes."""
    with jax.named_scope("mla_absorb"):
        w_uk = _kvb_heads(c, w_kvb)[..., : c.qk_nope_dim]
        folded = jnp.einsum("...hd,rhd->...hr", q[..., : c.qk_nope_dim], w_uk)
        return jnp.concatenate([folded, q[..., c.qk_nope_dim :]], axis=-1)


def mla_absorb_out(c: ModelConfig, w_kvb: jax.Array, attn: jax.Array) -> jax.Array:
    """The absorbed form's values: a head's weighted sum of latents (..., H,
    ``kv_lora_rank``) through the head's value matrix, ``o' W_UV``."""
    with jax.named_scope("mla_absorb"):
        w_uv = _kvb_heads(c, w_kvb)[..., c.qk_nope_dim :]
        return jnp.einsum("...hr,rhd->...hd", attn, w_uv)


def mla_expand(c: ModelConfig, w_kvb: jax.Array, latents: jax.Array):
    """The expanded form's keys and values of the positions a call gathered:
    (..., 1, ``latent_dim``) -> ((..., H, ``head_dim``), (..., H, value
    width)): ``c W_kvb`` a head, the one rotary key beside every head's
    keys."""
    with jax.named_scope("mla_expand"):
        cached = latents[..., 0, :]
        made = jnp.einsum(
            "...r,rhd->...hd", cached[..., : c.kv_lora_rank],
            _kvb_heads(c, w_kvb))
        rotary = jnp.broadcast_to(
            cached[..., None, c.kv_lora_rank :],
            made.shape[:-1] + (c.qk_rope_dim,))
        keys = jnp.concatenate([made[..., : c.qk_nope_dim], rotary], axis=-1)
        return keys, made[..., c.qk_nope_dim :]


def latent_view(c: ModelConfig, latents: jax.Array):
    """The absorbed form's keys and values of the positions a call gathered:
    the cached [latent | rotary key] themselves, one head, and as values
    their first ``kv_lora_rank`` columns.  A view, not a second copy."""
    return latents, latents[..., : c.kv_lora_rank]


def _softcap(x: jax.Array, cap: Optional[float]) -> jax.Array:
    if cap is None:
        return x
    return cap * jnp.tanh(x / cap)


# ---------------------------------------------------------------------------
# Layer pieces every layer body shares.  Each runs under the
# ``jax.named_scope`` that names it in a profile (models.MODEL_SCOPES): a
# scope changes the operations' metadata and nothing else.  The scan over
# the layers runs under ``layers``, which is left to what the loop itself
# does: slicing a layer's weights and read-only operands out of the stacked
# arrays and stacking what a layer merely produces.  What a layer reads AND
# updates (a cache, the recurrent state) is not sliced: it rides the loop's
# carry whole and the layer addresses its part by the layer's index.
# ---------------------------------------------------------------------------


def layer_of(stack: jax.Array, layer: jax.Array) -> jax.Array:
    """Layer ``layer`` of a buffer whose layer axis leads: a read at an index,
    for the consumer to fuse, not a slice handed out by the loop."""
    return jax.lax.dynamic_index_in_dim(stack, layer, 0, keepdims=False)


def put_layer(stack: jax.Array, layer: jax.Array, new: jax.Array) -> jax.Array:
    """``stack`` with layer ``layer`` replaced by ``new``, in place where the
    stack is a loop's carry."""
    return jax.lax.dynamic_update_index_in_dim(stack, new, layer, 0)


def put_columns(
    stack: jax.Array, layer: jax.Array, column, new: jax.Array
) -> jax.Array:
    """A (L, B, T, ...) cache with ``new`` (B, S, ...) written from column
    ``column`` of layer ``layer`` on: the columns a call adds, in place."""
    return jax.lax.dynamic_update_slice(
        stack, new[None], (layer, 0, column) + (0,) * (new.ndim - 2))


def _times(x: jax.Array, multiplier: Optional[float]) -> jax.Array:
    """``x`` times a muP multiplier, as a multiplication in the program where
    the configuration has one; ``x`` itself where it has none."""
    if multiplier is None:
        return x
    return x * jnp.asarray(multiplier, x.dtype)


def embed_tokens(params: Params, c: ModelConfig, tokens: jax.Array) -> jax.Array:
    with jax.named_scope("embed"):
        x = take_rows(params["embed"], tokens)
        if c.scale_embeddings:
            x = x * jnp.asarray(c.d_model**0.5, x.dtype)
        return _times(x, c.embedding_multiplier)


def attn_out_block(
    c: ModelConfig, lp, x: jax.Array, attn: jax.Array,
    mixed: Optional[jax.Array] = None,
) -> jax.Array:
    """Output projection of the heads' values (..., H*hd), and the residual;
    ``mixed`` is the recurrent mixer's branch of the same block, added with
    it."""
    with jax.named_scope("attn_out"):
        attn = _times(matmul(attn, lp["wo"]), c.attention_out_multiplier)
        if c.use_post_norms:
            attn = rms_norm(attn, lp["post_attn_norm"], c.rms_eps, c.rmsnorm_style)
        if mixed is not None:
            return x + attn + mixed
        return x + attn


def ffn_block(c: ModelConfig, lp, x: jax.Array) -> jax.Array:
    """Norm, gated feed-forward, and the residual."""
    with jax.named_scope("ffn"):
        ffn_in = rms_norm(x, lp["ffn_norm"], c.rms_eps, c.rmsnorm_style)
        gate_m, down_m = c.mlp_multipliers or (None, None)
        gate = _times(matmul(ffn_in, lp["w_gate"]), gate_m)
        if c.activation == "geglu":
            gate = jax.nn.gelu(gate, approximate=True)
        else:
            gate = jax.nn.silu(gate)
        ffn = _times(
            matmul(gate * matmul(ffn_in, lp["w_up"]), lp["w_down"]), down_m)
        if c.use_post_norms:
            ffn = rms_norm(ffn, lp["post_ffn_norm"], c.rms_eps, c.rmsnorm_style)
        return x + ffn


#: What a routed layer counts, summed over layers and launches: assignments
#: to experts held here, rows routed (each makes ``experts_per_token``
#: assignments), passes through a routed layer, and held experts that a pass
#: reached (at least one of its rows was sent there: only their matrices are
#: read).
MOE_TALLY = ("held", "rows", "layer_passes", "reached")

#: Rows a grouped product takes at a time: ``experts_per_token`` assignments
#: a row are gathered (every one may be to an expert held here), so the
#: gathered rows of a block are 8 x 4,096 x 4,096 wide x 2 bytes = 268 MB.
#: The sort's one-hot is (8 x 4,096, held + 1) int32: 34 MB at 256 held.
_MOE_BLOCK_ROWS = 4096


def route(c: ModelConfig, lp, t: jax.Array):
    """The router on normed rows ``t`` (N, D), in float32 whatever ``t`` is:
    sigmoid scores over all ``n_experts``, the ``experts_per_token`` largest
    of score + bias chosen (the bias selects and nothing else), their scores
    renormalised over all that were chosen, held here or not.  Returns
    (chosen (N, k) int32, weights (N, k) float32)."""
    scores = jax.nn.sigmoid(jnp.dot(
        t.astype(jnp.float32), lp["router"].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _, chosen = jax.lax.top_k(
        scores + lp["router_bias"].astype(jnp.float32), c.experts_per_token)
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    return chosen, weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)


#: A routed layer's leaves that hold the experts, (held, ...) a layer.
EXPERT_LEAVES = ("experts_gate", "experts_up", "experts_down")


#: Most rows, contracted columns and produced columns of one tile of a
#: span's grouped product (a 512-row tile of 1,024 x 1,024 weights is 341
#: FLOPs a byte, past a v5e's 240, in 9 MB of its fast memory).
_GROUPED_TILE = (512, 1024, 1024)
#: Rows of a tile where an expert expects fewer rows than this from the call
#: (a decode step: 32 rows x 8 of 256 experts give one a row): the fewest
#: megablox takes for bfloat16 rows (8 compiles too, and was no faster).  A
#: span's tile would multiply each expert it visits by 512 rows to keep one
#: or two of them.  At 1-256 rows this is no slower than a product of every
#: held expert under a mask (32 rows of JoyAI-LLM-Flash's layer: 2.23 ms
#: against 3.33; PERF.md 5, PR 37), which it replaced.
_FEW_ROWS_TILE = 16
#: Bytes that two buffers of a few-rows tile's weight block may take: half of
#: the 16 MiB of fast memory a v5e kernel is given.
_WEIGHT_BLOCK_BYTES = 8 << 20


def grouped_tiling(m: int, k: int, n: int, run: float, itemsize: int = 2):
    """The (rows, contracted, produced) tile of a grouped product of ``m``
    rows of ``k`` into ``n`` columns whose groups expect ``run`` rows each.

    A span's groups fill tiles of ``_GROUPED_TILE``.  Where a group expects
    fewer than ``_FEW_ROWS_TILE`` rows, the tile has that many rows and the
    contracted axis whole, with as many produced columns of a power of two's
    share as let two buffers of the weight block fit ``_WEIGHT_BLOCK_BYTES``
    (JoyAI-LLM-Flash's 2,048 x 768 whole, 3 MB; MiMo-V2-Flash's 4,096 x 2,048
    a quarter at a time): the kernel then walks each expert's tiles of rows
    with the same block of weights, which it does not fetch again, and an
    expert's matrices are read once a call.  Where no such block fits, the
    span's columns at the few rows."""
    if run >= _FEW_ROWS_TILE:
        return (min(_GROUPED_TILE[0], m), min(_GROUPED_TILE[1], k),
                min(_GROUPED_TILE[2], n))
    columns = n
    while 2 * k * columns * itemsize > _WEIGHT_BLOCK_BYTES and columns % 256 == 0:
        columns //= 2
    if 2 * k * columns * itemsize > _WEIGHT_BLOCK_BYTES:
        return (_FEW_ROWS_TILE, min(_GROUPED_TILE[1], k), min(_GROUPED_TILE[2], n))
    return (_FEW_ROWS_TILE, k, columns)


def _grouped_dot(rows: jax.Array, stack: jax.Array, layer, sizes: jax.Array,
                 run: float):
    """``rows[group g's run] @ stack[layer, g]`` for every group: ``rows`` (M,
    K) sorted by group, ``stack`` (layers, G, K, N), ``sizes`` (G,) int32 the
    runs' lengths in order, ``run`` the rows a group expects
    (``grouped_tiling``); rows past the last run come back undefined.  Only
    the tiles that hold a run's rows are computed, and an empty group's
    matrices are never read.

    megablox's grouped matrix product, a Pallas kernel, called from here so
    that its operations carry this call's scope in a profile: what XLA's TPU
    compiler makes of ``lax.ragged_dot`` is the same kind of kernel under an
    operation name of its own making (``ragged-dot-none``) and no scope, so
    that no trace could say what the experts cost.  The kernel reads the
    layer's matrices where they lie: it is handed every layer's as layers x G
    groups, all empty but this layer's, and visits no empty group.  (Handed a
    slice, it had the slice copied out for it first: 0.8 GB a layer a call at
    the published sizes, 1.6 ms, PERF.md 6, PR 32.)"""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    m, k = rows.shape
    layers, groups = stack.shape[:2]
    tile = grouped_tiling(m, k, stack.shape[3], run, stack.dtype.itemsize)
    pad = -m % tile[0]
    if pad:
        rows = jnp.pad(rows, ((0, pad), (0, 0)))
    every = jax.lax.dynamic_update_slice(
        jnp.zeros((layers * groups,), jnp.int32), sizes, (layer * groups,))
    out = gmm(rows, stack.reshape((layers * groups,) + stack.shape[2:]), every,
              preferred_element_type=rows.dtype, tiling=tile,
              interpret=jax.default_backend() == "cpu")
    return out[:m] if pad else out


def _experts_grouped(stacks, layer, run, t, local, held, weights):
    """The rows' assignments sorted by expert (those to absent experts last,
    in no group), each held expert's gated product over its own run of rows
    (``_grouped_dot``; ``run`` the rows an expert expects), and each row's
    weighted sum of what its assignments returned.  No capacity: a group is
    as long as its expert was chosen often.  Returns (that sum, the groups'
    sizes)."""
    n, k = local.shape
    count = stacks["experts_gate"].shape[1]
    with jax.named_scope("moe_dispatch"):
        expert = jnp.where(held, local, count).reshape(-1)  # (N * k,)
        # The stable sort by expert, counted and not sorted (the experts are
        # few): an assignment goes to its group's start plus the number of
        # its group's assignments before it.  (The TPU compiler takes 14 s
        # for one ``argsort`` of 32,768 and 2 s for this, PERF.md 6, PR 32.)
        sent = (expert[:, None] == jnp.arange(count + 1)[None, :]).astype(
            jnp.int32)
        before = jnp.cumsum(sent, axis=0) - sent
        every = jnp.sum(sent, axis=0)
        starts = jnp.cumsum(every) - every
        went = jnp.sum(sent * (before + starts[None, :]), axis=1)
        order = jnp.zeros_like(went).at[went].set(
            jnp.arange(n * k, dtype=went.dtype), unique_indices=True)
        sizes = every[:count]
        rows = t[order // k]  # (N * k, D): assignment a's row is a // k
    with jax.named_scope("moe_experts"):
        gate = jax.nn.silu(
            _grouped_dot(rows, stacks["experts_gate"], layer, sizes, run))
        up = _grouped_dot(rows, stacks["experts_up"], layer, sizes, run)
        out = _grouped_dot(gate * up, stacks["experts_down"], layer, sizes, run)
    with jax.named_scope("moe_combine"):
        back = went.reshape(n, k)  # where each assignment went
        share = jnp.where(held, weights, 0.0)
        # Rows past the last group are no expert's: what the product left
        # there is dropped by the mask, not multiplied by zero.
        returned = jnp.where(held[:, :, None], out[back].astype(jnp.float32), 0.0)
        return jnp.sum(returned * share[:, :, None], axis=1).astype(
            t.dtype), sizes


def moe_block(c: ModelConfig, lp, x: jax.Array):
    """Norm, the routed experts, and the residual: the feed-forward of a
    routed layer.  The program holds experts ``experts_held = (first,
    count)`` of the router's ``n_experts``: it routes every row over all of
    them and adds the part of the result that its own experts give; an
    assignment to an absent expert is skipped (what it would have added is
    another chip's to add), none to a held expert is dropped.  The rows'
    assignments are grouped by expert and each held expert that any row was
    sent to multiplies its own (``_experts_grouped``), a block of rows at a
    time past ``_MOE_BLOCK_ROWS``; the tile follows the rows an expert
    expects from the call (``grouped_tiling``), so that a decode step's few
    rows read the experts they reached and no other.  The sum of the routed
    parts is multiplied by ``routed_scaling_factor`` where the configuration
    has one, and a shared expert (``shared_*`` of ``lp``) is added beside
    it, unscaled.

    ``lp`` holds the layer's own norm, router and bias, and the experts
    either as the layer's own leaves (``EXPERT_LEAVES``, (held, ...)) or, from
    the layer loop, as ``lp["experts"]`` = (every layer of the kind's, the
    layer's index): the products read the layer's where they lie.

    Returns (x + the held experts' part, the layer's tally: int32
    (assignments to held experts, rows routed, 1, held experts reached),
    which the layer loop sums over the routed layers)."""
    shape = x.shape
    first, count = c.experts_held
    stacks, layer = lp.get("experts") or (
        {leaf: lp[leaf][None] for leaf in EXPERT_LEAVES}, 0)
    with jax.named_scope("moe_router"):
        t = rms_norm(x, lp["ffn_norm"], c.rms_eps, c.rmsnorm_style).reshape(
            -1, shape[-1])
        chosen, weights = route(c, lp, t)
        local = chosen - first
        held = (local >= 0) & (local < count)
    if c.routed_scaling_factor is not None:
        # On the routed sum alone, as a factor of the float32 weights that
        # make it.
        with jax.named_scope("moe_combine"):
            weights = weights * c.routed_scaling_factor
    n = t.shape[0]
    block = min(n, _MOE_BLOCK_ROWS)
    run = block * c.experts_per_token / c.n_experts
    if n <= _MOE_BLOCK_ROWS:
        part, sizes = _experts_grouped(stacks, layer, run, t, local, held, weights)
    else:
        blocks = -(-n // _MOE_BLOCK_ROWS)
        pad = blocks * _MOE_BLOCK_ROWS - n  # padding rows are sent nowhere

        def blocked(a, fill=0):
            a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1),
                        constant_values=fill)
            return a.reshape((blocks, _MOE_BLOCK_ROWS) + a.shape[1:])

        part, sizes = jax.lax.map(
            lambda block: _experts_grouped(stacks, layer, run, *block),
            (blocked(t), blocked(local), blocked(held, False), blocked(weights)))
        part = part.reshape(-1, shape[-1])[:n]
        sizes = jnp.sum(sizes, axis=0)
    if c.n_shared_experts:
        # The expert every row passes, on the same normed rows; every chip
        # computes it alike, whatever share of the routed ones it holds.
        with jax.named_scope("moe_shared"):
            gate = jax.nn.silu(matmul(t, lp["shared_gate"]))
            part = part + matmul(gate * matmul(t, lp["shared_up"]),
                                 lp["shared_down"])
    with jax.named_scope("moe_combine"):
        return x + part.reshape(shape), jnp.stack(
            [jnp.sum(held, dtype=jnp.int32), jnp.int32(n), jnp.int32(1),
             jnp.sum(sizes > 0, dtype=jnp.int32)])


def final_norm(params: Params, c: ModelConfig, x: jax.Array) -> jax.Array:
    with jax.named_scope("final_norm"):
        return rms_norm(x, params["final_norm"], c.rms_eps, c.rmsnorm_style)



# ---------------------------------------------------------------------------
# The recurrent mixer (Mamba-2) of a block that has one beside attention
# ---------------------------------------------------------------------------


def _ssm_slice_vector(c: ModelConfig) -> Optional[jax.Array]:
    """``ssm_slice_multipliers`` spread over [z | x | B | C | dt]."""
    if c.ssm_slice_multipliers is None:
        return None
    return jnp.concatenate([
        jnp.full((w,), m, jnp.float32)
        for w, m in zip(c.ssm_slice_widths, c.ssm_slice_multipliers)
    ])


def _ssm_conv(c: ModelConfig, lp, xbc, window, valid):
    """Causal depthwise convolution over each row's one run of valid
    positions, which ``window`` (B, K-1, C) precedes: the last K-1 inputs
    before this span (zeros at a sequence's start).  Returns (silu of the
    convolution (B, S, C), the window after the run's last position).  A row
    with no valid position keeps its window."""
    b, s, _ = xbc.shape
    k = c.ssm_conv
    n_valid = jnp.sum(valid.astype(jnp.int32), axis=1)
    first = jnp.argmax(valid, axis=1).astype(jnp.int32)  # 0 where none
    xbc = jnp.where(valid[:, :, None], xbc, jnp.zeros((), xbc.dtype))
    cat = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))  # index t + k - 1 = t
    # The window lands right before the run: in the left pad, or in the
    # k - 1 columns put in front.
    at = jnp.arange(s + k - 1)[None, :]
    for j in range(k - 1):
        cat = jnp.where(
            (at == (first + j)[:, None])[:, :, None],
            window[:, j : j + 1, :].astype(cat.dtype), cat,
        )
    w = lp["ssm_conv_w"].astype(jnp.float32)
    out = lp["ssm_conv_b"].astype(jnp.float32)[None, None, :]
    for j in range(k):
        out = out + cat[:, j : j + s, :].astype(jnp.float32) * w[j][None, None, :]
    tail = (first + n_valid)[:, None] + jnp.arange(k - 1)[None, :]
    new_window = jnp.take_along_axis(cat, tail[:, :, None], axis=1)
    return jax.nn.silu(out).astype(xbc.dtype), new_window.astype(window.dtype)


def _ssm_scan_step(x, dt, a, bm, cm, h):
    """One position of the recurrence: ``x`` (B, H, P), ``dt`` (B, H)
    float32, ``a`` (H,), ``bm``/``cm`` (B, H, N) by head, ``h`` (B, H, P, N)
    float32.  The state is read and written once, elementwise."""
    decay = jnp.exp(dt * a[None, :])[:, :, None, None]
    dbx = (dt[:, :, None] * x.astype(jnp.float32))[..., None] * bm.astype(
        jnp.float32)[:, :, None, :]
    h = decay * h + dbx
    y = jnp.sum(h * cm.astype(jnp.float32)[:, :, None, :], axis=-1)
    return y, h


def _ssm_scan_chunked(c: ModelConfig, x, dt, a, bm, cm, h0):
    """The recurrence over a span in chunks of ``ssm_chunk`` (Mamba-2's
    state-space duality): within a chunk a masked product of the decays,
    between chunks the state, carried in float32.  ``x`` (B, S, H, P),
    ``dt`` (B, S, H) float32 and zero where nothing may change the state,
    ``bm``/``cm`` (B, S, G, N), ``h0`` (B, H, P, N).  Returns (y (B, S, H,
    P) float32, the state after the span)."""
    b, s, h, p = x.shape
    g, n = bm.shape[2], bm.shape[3]
    r = h // g
    q = min(c.ssm_chunk, s)
    pad = -s % q
    if pad:  # dt = 0: the padding neither moves the state nor is read
        x, dt, bm, cm = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (x, dt, bm, cm)
        )
    nc = (s + pad) // q
    f32 = jnp.float32
    x = x.reshape(b, nc, q, g, r, p)
    dt = dt.reshape(b, nc, q, g, r)
    bm = bm.reshape(b, nc, q, g, n)
    cm = cm.reshape(b, nc, q, g, n)
    da = dt * a.reshape(g, r)[None, None, None]
    cum = jnp.cumsum(da, axis=2)  # (B, nc, Q, G, R), <= 0 and falling
    # Within the chunk: y_i += sum_{j<=i} (C_i.B_j) exp(cum_i - cum_j) dt_j x_j
    cb = jnp.einsum("bcign,bcjgn->bcgij", cm, bm, preferred_element_type=f32)
    seg = cum[:, :, :, None] - cum[:, :, None, :]  # (B, nc, i, j, G, R)
    causal = jnp.tril(jnp.ones((q, q), bool))[None, None, :, :, None, None]
    weights = jnp.where(causal, jnp.exp(jnp.where(causal, seg, 0.0)), 0.0)
    weights = weights * dt[:, :, None] * jnp.moveaxis(cb, 2, 4)[..., None]
    y = jnp.einsum("bcijgr,bcjgrp->bcigrp", weights, x.astype(f32),
                   preferred_element_type=f32)
    # What each chunk adds to the state, decayed to the chunk's end.
    to_end = jnp.exp(cum[:, :, -1:] - cum) * dt  # (B, nc, Q, G, R)
    added = jnp.einsum("bcjgrp,bcjgn->bcgrpn", to_end[..., None] * x.astype(f32),
                       bm.astype(f32), preferred_element_type=f32)
    chunk_decay = jnp.exp(cum[:, :, -1])  # (B, nc, G, R)

    def carry_state(state, per_chunk):
        decay, add = per_chunk
        return decay[..., None, None] * state + add, state

    h_last, h_before = jax.lax.scan(
        carry_state, h0.reshape(b, g, r, p, n),
        (jnp.moveaxis(chunk_decay, 1, 0), jnp.moveaxis(added, 1, 0)),
    )
    h_before = jnp.moveaxis(h_before, 0, 1)  # (B, nc, G, R, P, N)
    # What the state before the chunk gives each position of it.
    y = y + jnp.einsum("bcign,bcgrpn->bcigrp", cm.astype(f32), h_before,
                       preferred_element_type=f32) * jnp.exp(cum)[..., None]
    y = y.reshape(b, nc * q, h, p)[:, :s]
    return y, h_last.reshape(b, h, p, n)


def ssm_mixer(
    c: ModelConfig, lp, u: jax.Array, state: Optional["SSMState"],
    valid: jax.Array, layer: Optional[jax.Array] = None,
):
    """The Mamba-2 branch of a block, on the block's normed input ``u`` (B,
    S, D): returns (its contribution to the residual (B, S, D), the state
    after the span).  ``state`` is one layer's ``SSMState`` (conv window (B,
    K-1, C), h (B, H, P, N) float32), or None at a sequence's start; with
    ``layer`` it is every layer's (the layer axis leading, as the layer loop
    carries it): this layer's window and ``h`` are read at ``layer`` and
    written back there under the scopes that made them, and the whole comes
    back.  ``valid`` (B, S)
    is one run of real positions a row; the others leave the state and the
    window as they found them, and what is returned at them is not read.  A
    span of one position takes the recurrence's one-step form, a longer one
    the chunked form."""
    b, s, _ = u.shape
    heads, p, n, g = c.ssm_heads, c.ssm_head_dim, c.ssm_state, c.ssm_groups
    inner, gn = c.ssm_inner, c.ssm_groups * c.ssm_state
    stacked = state is not None and layer is not None
    if state is None:
        window = jnp.zeros((b, c.ssm_conv - 1, c.ssm_conv_dim), u.dtype)
        h = jnp.zeros((b, heads, p, n), jnp.float32)
    else:
        window, h = state
    with jax.named_scope("ssm_in"):
        proj = matmul(_times(u, c.ssm_in_multiplier), lp["ssm_in"])
        slices = _ssm_slice_vector(c)
        if slices is not None:
            proj = proj * slices.astype(proj.dtype)
        z = proj[..., :inner]
        xbc = proj[..., inner : inner + c.ssm_conv_dim]
        dt = proj[..., inner + c.ssm_conv_dim :]
    # The stacked state is read and written inside the scopes that use it,
    # so that the state's traffic is the mixer's in a profile.
    with jax.named_scope("ssm_conv"):
        xbc, after = _ssm_conv(
            c, lp, xbc, layer_of(window, layer) if stacked else window, valid)
        window = put_layer(window, layer, after) if stacked else after
    with jax.named_scope("ssm_scan"):
        before = layer_of(h, layer) if stacked else h
        x = xbc[..., :inner].reshape(b, s, heads, p)
        bm = xbc[..., inner : inner + gn].reshape(b, s, g, n)
        cm = xbc[..., inner + gn :].reshape(b, s, g, n)
        dt = jax.nn.softplus(
            dt.astype(jnp.float32) + lp["ssm_dt_bias"].astype(jnp.float32))
        dt = jnp.where(valid[:, :, None], dt, 0.0)
        a = -jnp.exp(lp["ssm_a_log"].astype(jnp.float32))
        if s == 1:
            by_head = lambda t: jnp.repeat(t[:, 0], heads // g, axis=1)
            y, after = _ssm_scan_step(
                x[:, 0], dt[:, 0], a, by_head(bm), by_head(cm), before)
            y = y[:, None]
        else:
            y, after = _ssm_scan_chunked(c, x, dt, a, bm, cm, before)
        h = put_layer(h, layer, after) if stacked else after
        y = y + lp["ssm_d"].astype(jnp.float32)[None, None, :, None] * x.astype(
            jnp.float32)
    with jax.named_scope("ssm_out"):
        y = y.reshape(b, s, inner)
        gate = jax.nn.silu(z.astype(jnp.float32))
        if not c.ssm_norm_before_gate:
            y = y * gate
        # RMSNorm over each group's columns.
        yg = y.reshape(b, s, g, inner // g)
        yg = yg * jax.lax.rsqrt(
            jnp.mean(yg * yg, axis=-1, keepdims=True) + c.rms_eps)
        y = yg.reshape(b, s, inner) * lp["ssm_norm"].astype(jnp.float32)
        if c.ssm_norm_before_gate:
            y = y * gate
        out = _times(matmul(y.astype(u.dtype), lp["ssm_out"]), c.ssm_out_multiplier)
    return out, SSMState(window, h)


# ---------------------------------------------------------------------------
# KV cache, and the recurrent state that rides beside it
# ---------------------------------------------------------------------------


class SSMState(NamedTuple):
    """The recurrent state of a configuration with a mixer, by row, the
    layer axis leading: the other kind of state beside keys and values by
    position.  Its size does not grow with the prefix; a shared prefix is a
    copy of it at the prefix's end (``fork_ssm``), not a run of pages."""

    conv: jax.Array  # (L, B, K-1, conv_dim): the last K-1 inputs of the conv
    h: jax.Array  # (L, B, heads, head size, state) float32


def make_ssm_state(
    config: ModelConfig, batch: int, dtype: jnp.dtype = jnp.float32
) -> Optional[SSMState]:
    """A zero state for ``batch`` rows (a sequence's start); None where the
    configuration has no recurrent layer."""
    c = config
    if not c.has_ssm:
        return None
    return SSMState(
        conv=jnp.zeros((c.n_layers, batch, c.ssm_conv - 1, c.ssm_conv_dim), dtype),
        h=jnp.zeros(
            (c.n_layers, batch, c.ssm_heads, c.ssm_head_dim, c.ssm_state),
            jnp.float32,
        ),
    )


def fork_ssm(state: SSMState, rows: jax.Array | int) -> SSMState:
    """Copy a state to many rows: ``rows`` is the index of the source row
    for each new row, or a count (every new row starts from row 0).  This
    is what a shared prefix costs a recurrent layer."""
    with jax.named_scope("state_fork"):
        if isinstance(rows, int):
            return jax.tree.map(
                lambda a: jnp.broadcast_to(
                    a[:, :1], a.shape[:1] + (rows,) + a.shape[2:]),
                state,
            )
        return jax.tree.map(lambda a: jnp.take(a, rows, axis=1), state)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class KVCache:
    #: (L, B, T, KV, hd) keys and (L, B, T, KV, value width) values.  With
    #: layers of more than one kind, a cache a kind of attention: ``{"full":
    #: (its layers, B, T, its KV, hd), "window": ...}``, the two dictionaries
    #: alike.  A latent kind keeps one buffer: ``k["latent"]`` is (its
    #: layers, B, T, 1, latent + rotary key) and ``v["latent"]`` is None (the
    #: values are the keys' first ``kv_lora_rank`` columns).
    k: Any
    v: Any
    key_positions: jax.Array  # (B, T) int32
    key_valid: jax.Array  # (B, T) bool
    #: The rows' recurrent state after the last position written; None for
    #: a configuration without recurrent layers.
    ssm: Optional[SSMState] = None

    def tree_flatten(self):
        return (
            self.k, self.v, self.key_positions, self.key_valid, self.ssm
        ), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def kv_buffers(
    config: ModelConfig, middle: Tuple[int, ...], dtype, make=jnp.zeros
):
    """(keys, values) of zeros, each ``(layers,) + middle + (KV heads, head
    width)``: the shape of every cache layout (``middle`` is (rows, columns)
    of a dense cache or a tail, (pages, page size) of a pool).  With layers
    of more than one kind each is a dictionary by kind of attention, at that
    kind's layers, heads and widths (``ModelConfig.cache_kinds``,
    ``cache_widths``); a latent kind's values are None: one buffer, whose
    first ``kv_lora_rank`` columns are the values."""
    c = config

    def pair(name, layers, heads):
        return tuple(
            make((layers,) + middle + (heads, width), dtype) if width else None
            for width in c.cache_widths(name))

    if not c.has_layer_kinds:
        return pair(None, c.n_layers, c.n_kv_heads)
    pairs = {name: pair(name, n, heads) for name, n, heads in c.cache_kinds}
    return ({name: kv[0] for name, kv in pairs.items()},
            {name: kv[1] for name, kv in pairs.items()})


def by_kind(c: ModelConfig, *trees):
    """What a caller hands ``scan_layers`` of several caches that are each a
    dictionary by kind of attention (or each one array, for a configuration
    of one kind): ``{kind: (tree[kind], ...)}``, or the trees as a tuple."""
    if not c.has_layer_kinds:
        return trees
    return {name: tuple(tree[name] for tree in trees)
            for name, _, _ in c.cache_kinds}


def from_kinds(c: ModelConfig, written, n: int = 2):
    """The inverse of :func:`by_kind`: ``n`` trees back from what
    ``scan_layers`` returned."""
    if not c.has_layer_kinds:
        return tuple(written)
    return tuple({name: pair[i] for name, pair in written.items()}
                 for i in range(n))


def make_cache(
    config: ModelConfig, batch: int, max_len: int, dtype: jnp.dtype = jnp.float32
) -> KVCache:
    c = config
    k, v = kv_buffers(c, (batch, max_len), dtype)
    return KVCache(
        k=k,
        v=v,
        key_positions=jnp.zeros((batch, max_len), jnp.int32),
        key_valid=jnp.zeros((batch, max_len), jnp.bool_),
        ssm=make_ssm_state(c, batch, dtype),
    )


# ---------------------------------------------------------------------------
# Forward pass
# ---------------------------------------------------------------------------


def _attention_masks(
    config: ModelConfig,
    q_positions: jax.Array,  # (B, S)
    q_valid: jax.Array,  # (B, S)
    k_positions: jax.Array,  # (B, T)
    k_valid: jax.Array,  # (B, T)
) -> Tuple[jax.Array, jax.Array]:
    """(global_mask, local_mask), each (B, 1, S, T) boolean."""
    qp = q_positions[:, :, None]  # (B, S, 1)
    kp = k_positions[:, None, :]  # (B, 1, T)
    causal = (kp <= qp) & k_valid[:, None, :] & q_valid[:, :, None]
    global_mask = causal[:, None, :, :]
    if config.sliding_window is not None:
        local = causal & (qp - kp < config.sliding_window)
        local_mask = local[:, None, :, :]
    else:
        local_mask = global_mask
    return global_mask, local_mask


def attention_scope(is_local, latent=None):
    """The scope of a layer's attention.  A run of window layers, whose window
    is known when it is traced, has a name of its own, and so has a run of
    latent layers (``latent``: what ``layer_block`` hands their ``attend``)."""
    if latent is not None:
        return jax.named_scope("attention_latent")
    if is_local is True:
        return jax.named_scope("attention_window")
    return jax.named_scope("attention")


def _layer_mask(is_local, local_mask, global_mask) -> jax.Array:
    """This layer's mask of the einsum paths: ``is_local`` is a traced scan
    input, so both masks are built outside the loop and one is picked here.
    (A run of layers of one kind knows its window when it is traced: a
    Python bool picks the mask there.)"""
    if isinstance(is_local, bool):
        return local_mask if is_local else global_mask
    return jnp.where(is_local, local_mask, global_mask)


def windowed(c: ModelConfig, is_local: jax.Array, call: Callable, *operands):
    """``call(window, *operands)`` under this layer's window, for the kernels
    that take the window as a static argument: ``is_local`` is a traced scan
    input, so the choice is a ``lax.cond`` between two statically windowed
    calls (and no choice at all for a configuration without a window)."""
    if c.sliding_window is None:
        return call(None, *operands)
    if isinstance(is_local, bool):  # a run of layers of one kind
        return call(c.sliding_window if is_local else None, *operands)
    return jax.lax.cond(
        is_local,
        functools.partial(call, c.sliding_window),
        functools.partial(call, None),
        *operands,
    )


def layer_block(
    c: ModelConfig, lp, x: jax.Array, positions: jax.Array, attend: Callable,
    operands_l, written, ssm, layer: jax.Array, is_local,
    valid: Optional[jax.Array], kind: Optional[LayerKind] = None,
):
    """One transformer layer, the only one written out: norm, the three
    products, rope; the recurrent mixer where the configuration has one;
    ``attend``; the output product and the feed-forward with their residuals.

    ``x`` is (B, S, D) with ``positions`` (B, S), or a decode step's (B, D)
    with ``positions`` (B,): a span of one without the axis.  ``attend(q, k,
    v, operands_l, written, layer, is_local)`` is the caller's cache layout:
    it gets the roped (B, S, heads, hd) queries, keys and values of this
    call, the layer's slice of what the caller only reads, and ``written``,
    every layer's part of what the caller reads and updates, which it
    addresses by ``layer``: it writes K/V where the layout keeps them and
    attends over what it holds, and returns (the heads' values, any shape
    that flattens to ``x``'s rows x H*hd; what the caller wants stacked over
    the layers; ``written`` updated).  ``ssm`` is every layer's recurrent
    state, or None for rows that start a sequence.  ``valid`` (B, S) marks the
    positions that move the mixer's state; None is all of them.

    ``kind``: what this layer is, where the configuration has layers of more
    than one kind (``lp``, ``operands_l`` and ``written`` are then its
    kind's, and ``layer`` its index among them): its key-value heads, its
    rotary base, its window as a Python bool in ``is_local``, its sinks
    (handed to ``attend`` as ``sink=``), and routed experts in the
    feed-forward's place, whose count of assignments to held experts is what
    the layer produces.

    A latent kind hands ``attend`` what its layout keeps, as ``k``: a token's
    [normed latent | rotary key], one head of ``latent_dim``, and None as
    ``v``; and ``latent=``, which makes the keys and values of the cached
    positions the call gathered, in the form the call's shapes choose
    (``latent_form``): ``latent_view`` beside absorbed queries (one head of
    ``latent_dim``, the values its first columns, ``mla_absorb_out`` after
    the softmax), ``mla_expand`` beside the heads' own queries.

    Returns (x, what ``attend`` produced, ``written``, ``ssm``)."""
    h, kv, hd, vd = c.n_heads, c.n_kv_heads, c.head_dim, c.value_dim
    theta = c.rope_theta
    if kind is not None:
        kv, theta = kind.kv_heads, kind.rope_theta
    latent = kind is not None and kind.attention == "latent"
    one = x.ndim == 2
    span_of = (lambda t: t[:, None]) if one else (lambda t: t)
    rows = x.shape[:-1] + ((1,) if one else ())  # (B, S)

    with jax.named_scope("attn_qkv"):
        attn_in = rms_norm(x, lp["attn_norm"], c.rms_eps, c.rmsnorm_style)
        qkv_in = _times(attn_in, c.attention_in_multiplier)
        if latent:
            q = matmul(
                rms_norm(matmul(qkv_in, lp["w_qa"]), lp["q_norm"], c.rms_eps,
                         c.rmsnorm_style),
                lp["w_qb"]).reshape(rows + (h, hd))
            left = matmul(qkv_in, lp["w_kva"]).reshape(rows + (1, c.latent_dim))
            q = jnp.concatenate([
                q[..., : c.qk_nope_dim],
                rope_latent(c, q[..., c.qk_nope_dim :], span_of(positions),
                            theta)], axis=-1)
            k = jnp.concatenate([
                rms_norm(left[..., : c.kv_lora_rank], lp["kv_norm"], c.rms_eps,
                         c.rmsnorm_style),
                rope_latent(c, left[..., c.kv_lora_rank :], span_of(positions),
                            theta)], axis=-1)
            v = None
        else:
            q = matmul(qkv_in, lp["wq"]).reshape(rows + (h, hd))
            k = _times(matmul(qkv_in, lp["wk"]), c.key_multiplier).reshape(
                rows + (kv, hd))
            v = _times(matmul(qkv_in, lp["wv"]), c.value_scale).reshape(
                rows + (kv, vd))
            q = rope_heads(c, q, span_of(positions), theta)
            k = rope_heads(c, k, span_of(positions), theta)
    mixed = None
    if c.has_ssm:
        mixed, after = ssm_mixer(
            c, lp, span_of(attn_in), ssm,
            jnp.ones(rows, bool) if valid is None else valid, layer)
        if ssm is not None:  # a state is handed on only where one came in
            ssm = after
        if one:
            mixed = mixed[:, 0]
    own = {"sink": lp["attn_sink"]} if kind is not None and kind.sink else {}
    absorbed = latent and latent_form(rows[1]) == "absorbed"
    if absorbed:
        q = mla_absorb(c, lp["w_kvb"], q)
        own["latent"] = functools.partial(latent_view, c)
    elif latent:
        own["latent"] = functools.partial(mla_expand, c, lp["w_kvb"])
    attn, produced, written = attend(
        q, k, v, operands_l, written, layer, is_local, **own)
    if absorbed:
        attn = mla_absorb_out(
            c, lp["w_kvb"], attn.reshape(rows + (h, c.kv_lora_rank)))
    x = attn_out_block(c, lp, x, attn.reshape(x.shape[:-1] + (h * vd,)), mixed)
    if kind is not None and kind.routed:
        x, produced = moe_block(c, lp, x)
        return x, produced, written, ssm
    return ffn_block(c, lp, x), produced, written, ssm


def scan_layers(
    params: Params, c: ModelConfig, x: jax.Array, positions: jax.Array,
    attend: Callable, operands, written, ssm: Optional["SSMState"],
    valid: Optional[jax.Array],
):
    """The one loop over the layers: ``layer_block`` under a ``lax.scan``.

    What a layer reads AND updates rides the loop's carry whole, beside
    ``x``, and each layer addresses its part by the layer's index:
    ``written`` (the caller's cache: page pools, a dense cache, a decode
    tail; any pytree with the layer axis leading, or None) and ``ssm`` (the
    recurrent state).  Handed to the scan as an input and taken back as an
    output, such a buffer is sliced out of the stack and stacked back whole
    every layer for the few rows a layer writes.  What a layer only reads is
    scanned over: the stacked weights, the caller's ``operands`` (a trunk's
    K/V, frozen blocks; or None), the window flags, and the layer's index.

    Returns (x, what ``attend`` produced stacked over the layers, ``written``
    and ``ssm`` after the span).  A state is handed on only where one was
    handed in: rows that start a sequence (``ssm`` None) drop it.

    A configuration with layers of more than one kind (``c.layer_runs``)
    runs each run of equal layers as one ``lax.scan`` of the same
    ``layer_block``, one after the other, with the run's kind as static
    data.  ``params["layers"]``, ``operands`` and ``written`` are then
    dictionaries: a stack of weights by the kind's name, and what the caller
    reads or writes by the kind of attention (``"full"``, ``"window"``), each
    with its own layers leading.  A run reads its weights and operands at
    the layer's index in its kind's stack and carries its kind's ``written``;
    nothing is sliced out for a run.  What comes back as produced is the
    routed layers' tally, ``MOE_TALLY``: int32 (assignments to held experts,
    rows routed, routed layers passed), zeros without routed layers."""
    if c.has_layer_kinds:
        held = jnp.zeros((len(MOE_TALLY),), jnp.int32)
        for run in c.layer_runs:
            kind = run.kind
            stack = params["layers"][kind.name]
            operands_k = None if operands is None else operands[kind.attention]

            def run_step(carry, index, kind=kind, stack=stack,
                         operands_k=operands_k, run=run):
                x, cache, held = carry
                # The layer's place in its kind's weights, and in its kind of
                # attention's cache: read where they lie, nothing sliced out
                # (the experts not even named here: ``moe_block`` reads them).
                lp = {leaf: layer_of(a, run.at + index)
                      for leaf, a in stack.items() if leaf not in EXPERT_LEAVES}
                if kind.routed:
                    lp["experts"] = (
                        {leaf: stack[leaf] for leaf in EXPERT_LEAVES},
                        run.at + index)
                layer = run.cache_at + index
                operands_l = jax.tree.map(
                    lambda a: layer_of(a, layer), operands_k)
                x, produced, cache, _ = layer_block(
                    c, lp, x, positions, attend, operands_l, cache, None,
                    layer, kind.window is not None, valid, kind)
                return (x, cache, held + produced if kind.routed else held), None

            cache = None if written is None else written[kind.attention]
            with jax.named_scope("layers"):
                (x, cache, held), _ = jax.lax.scan(
                    run_step, (x, cache, held),
                    jnp.arange(run.count, dtype=jnp.int32))
            if written is not None:
                written = {**written, kind.attention: cache}
        return x, held, written, None

    def step(carry, scanned):
        x, written, ssm = carry
        lp, operands_l, is_local, layer = scanned
        x, produced, written, ssm = layer_block(
            c, lp, x, positions, attend, operands_l, written, ssm, layer,
            is_local, valid)
        return (x, written, ssm), produced

    with jax.named_scope("layers"):
        (x, written, ssm), produced = jax.lax.scan(
            step, (x, written, ssm),
            (params["layers"], operands, jnp.asarray(c.local_flags),
             jnp.arange(c.n_layers, dtype=jnp.int32)),
        )
    return x, produced, written, ssm


def forward(
    params: Params,
    config: ModelConfig,
    tokens: jax.Array,  # (B, S) int32
    positions: jax.Array,  # (B, S) int32 RoPE positions
    valid: jax.Array,  # (B, S) bool — real (non-pad) tokens
    cache: Optional[KVCache] = None,
    write_index: int | jax.Array = 0,
    return_hidden: bool = False,
) -> Tuple[jax.Array, Optional[KVCache]]:
    """Run the transformer. Returns (logits (B, S, V) float32, updated cache).

    Without a cache, attention runs over this call's own keys (full
    teacher-forced forward).  With a cache, this call's k/v are written at
    ``write_index`` (same slot for every row — callers left-pad prompts) and
    attention runs over the whole cache buffer.

    ``return_hidden=True`` returns the final-norm hidden states (B, S, D)
    instead of logits — used by the streaming scorer, which must never
    materialize a full (B, S, V) logits tensor for 256k-vocab models.
    """
    c = config
    x = embed_tokens(params, c, tokens)

    if cache is None:
        k_positions, k_valid = positions, valid
    else:
        span = tokens.shape[1]
        k_positions = jax.lax.dynamic_update_slice(
            cache.key_positions, positions, (0, write_index)
        )
        k_valid = jax.lax.dynamic_update_slice(cache.key_valid, valid, (0, write_index))

    global_mask, local_mask = _attention_masks(c, positions, valid, k_positions, k_valid)
    if c.use_flash_attention and (
            c.swa_sink or c.value_dim != c.head_dim or c.has_latent):
        raise LayerKindsUnsupported("flash_attention", KERNEL_NEEDS_PLAIN_HEADS)

    def call_flash(window, q, keys, values):
        # Pallas blockwise kernel: no (B, H, S, S) logits in HBM.  The
        # kernel's masking model is one contiguous valid span per row,
        # described by (start, length) scalars — start=0 covers the
        # right-padded scoring layout, start=argmax(valid) the
        # left-padded next-token/embed layout (rows with no valid token
        # get length 0 and an empty mask either way).
        from consensus_tpu.ops.flash_attention import flash_attention

        return flash_attention(
            q, keys, values,
            jnp.sum(valid.astype(jnp.int32), axis=1),
            jnp.argmax(valid, axis=1).astype(jnp.int32),
            scale=c.q_scale, softcap=c.attn_softcap, window=window,
            causal=True, interpret=jax.default_backend() == "cpu",
        )

    def attend(q, k, v, _, kv_cache, layer, is_local, sink=None, latent=None):
        """Own keys without a cache; with one, this call's K/V written at
        ``(layer, 0, write_index)`` and the layer's whole buffer attended.
        (A latent layer's ``v`` and value buffer are None: ``latent`` makes
        keys and values of the latents held.)"""
        if kv_cache is None:
            keys, values = k, v
        else:
            with jax.named_scope("kv_write"):
                kv_cache = tuple(
                    None if buffer is None
                    else put_columns(buffer, layer, write_index, new)
                    for buffer, new in zip(kv_cache, (k, v)))
            keys, values = (None if buffer is None else layer_of(buffer, layer)
                            for buffer in kv_cache)
        if latent is not None:
            keys, values = latent(keys)
        groups = keys.shape[2]
        reps = q.shape[2] // groups
        with attention_scope(is_local, latent):
            if c.use_flash_attention and cache is None:
                # The pallas kernel takes equal q/kv head counts; expand here.
                attn = windowed(
                    c, is_local, call_flash, q,
                    jnp.repeat(keys, reps, axis=2),  # (B, T, H, hd)
                    jnp.repeat(values, reps, axis=2),
                ).astype(x.dtype)
            else:
                # GQA without materializing repeated KV: group q heads by their
                # kv head — on the decode path jnp.repeat would re-write the
                # whole (B, T, H, hd) cache expansion every layer every step,
                # doubling HBM traffic for nothing.
                qg = q.reshape(q.shape[:2] + (groups, reps, q.shape[-1]))

                def attend_groups(qg, keys, values, sink):
                    """Logits, mask, softmax and values of the key-value
                    groups handed in: (B, S, g, reps, hd) queries over (B, T,
                    g, hd) keys; ``sink`` (g, reps) or None."""
                    logits = jnp.einsum(
                        "bsgrd,btgd->bgrst", qg, keys).astype(jnp.float32)
                    logits = logits * c.q_scale
                    logits = _softcap(logits, c.attn_softcap)
                    mask = _layer_mask(is_local, local_mask, global_mask)
                    logits = jnp.where(mask[:, :, None], logits, MASK_FILL)
                    if sink is not None:  # one a query head
                        sink = sink[:, :, None, None]
                    weights = softmax_with_sink(logits, sink).astype(x.dtype)
                    return jnp.einsum("bgrst,btgd->bsgrd", weights, values)

                if sink is not None:
                    sink = sink.reshape(groups, reps)
                if c.has_layer_kinds and q.shape[1] > 1:
                    # Many query heads over a long span: the float32 logits
                    # of all of them at once pass what the weights leave
                    # (64 heads x 4,096 x 4,096 x 4 B = 4.3 GB a row), so a
                    # key-value group's heads at a time.
                    by_group = [jnp.moveaxis(t, 2, 0)[:, :, :, None]
                                for t in (qg, keys, values)]
                    if sink is not None:
                        by_group.append(sink[:, None])
                    attn = jax.lax.map(
                        lambda group: attend_groups(
                            *group, *(() if sink is not None else (None,))),
                        tuple(by_group))  # (g, B, S, 1, reps, vd)
                    attn = jnp.moveaxis(attn[:, :, :, 0], 0, 2)
                else:
                    attn = attend_groups(qg, keys, values, sink)
        return attn, None, kv_cache

    if cache is None:
        x, _, _, _ = scan_layers(
            params, c, x, positions, attend, None, None, None, valid)
        new_cache = None
    else:
        x, _, written, new_ssm = scan_layers(
            params, c, x, positions, attend, None, by_kind(c, cache.k, cache.v),
            cache.ssm, valid)
        new_k, new_v = from_kinds(c, written)
        new_cache = KVCache(k=new_k, v=new_v, key_positions=k_positions,
                            key_valid=k_valid, ssm=new_ssm)

    x = final_norm(params, c, x)
    if return_hidden:
        return x, new_cache
    return project_logits(params, c, x), new_cache


def quantize_kv(arr: jax.Array):
    """Symmetric absmax int8 over the head (last) dim, shape-agnostic:
    (..., hd) -> (int8 same shape, float32 scale (..., 1)).

    The SINGLE quantizer for every generated-KV surface — per-step tail
    writes here, whole prompt trunks and frozen blocks via
    generate._quantize_kv (an alias of this function) — so the
    per-(token, head) scale layout can never drift between the tail and
    the frozen blocks it turns into."""
    amax = jnp.max(jnp.abs(arr.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = amax / 127.0
    q = jnp.round(arr.astype(jnp.float32) / jnp.maximum(scale, 1e-12))
    return q.astype(jnp.int8), scale


def forward_trunk_tail(
    params: Params,
    config: ModelConfig,
    tokens: jax.Array,  # (Rows,) int32 — one new token per (slot x role) row
    positions: jax.Array,  # (Rows,) int32 — RoPE position of the new token
    trunk: KVCache,  # (L, R0, W0, ...) shared read-only prefix, R0 = n_roles
    tail_k,  # (L, Rows, Ts, KV, hd) per-row generated keys — or (int8, scale)
    tail_v,
    tail_positions: jax.Array,  # (Rows, Ts) int32
    write_col: jax.Array,  # () int32 — tail column for this step's token
    n_slots: int,
    n_roles: int,
    frozen_k=(),  # sequence of (L, Rows, F_i, KV, hd) blocks / (int8, scale)
    frozen_v=(),
    frozen_positions=(),  # sequence of (Rows, F_i) int32, one per block
    use_decode_kernel: bool = True,
    ssm: Optional[SSMState] = None,  # (L, Rows, ...) recurrent state by row
    moe_held: Optional[jax.Array] = None,  # MOE_TALLY int32, so far
):
    """One-token decode step where every search slot shares ONE trunk cache.

    Beam-search slots all contain the identical prompt prefix — replicating
    it per (slot x role) row (5+ GB for a wide beam on a 2B model) is pure
    waste, and gathering those replicas on every beam reorder doubles peak
    HBM whenever the gather is not done in place.  Here the prefix lives
    ONCE per role and
    broadcasts against all slots inside the attention einsum; only the
    <=max_steps-column per-row TAIL (the generated tokens) is slot-local
    state.  Tail columns <= ``write_col`` are visible (the current token
    writes there first).

    ``frozen_*``: optional read-only KV blocks holding tokens the row
    generated in EARLIER decode segments (models/generate.py's segmented
    decode), one block per frozen segment, in chronological order.  The
    live tail rides the while_loop carry and the layer loop's carry inside
    it, whole: a step writes one column of it in place
    (``generate._decode_segment`` has what the carry cost while it was
    sliced and stacked a layer).  Frozen blocks are plain operands: read
    once per step by attention, never copied, never concatenated (the
    per-block list replaces round 3's single concatenated block, whose
    append transient dominated the segmented HBM row allowance), and always
    fully visible (segments append whole seg_len blocks).

    A block — and the live tail itself — may be an (int8 values, float32
    per-(token, head) scales) pair: read traffic and carry bytes halve, and
    the int8->compute convert fuses into the attention dot's operand read,
    mirroring the weight path (quant.py MATMUL_LOWERING="astype").  A
    quantized tail is written quantized (one absmax round per step) so
    freezing a segment is a free list append.

    ``use_decode_kernel=False`` forces the einsum path: the pallas kernel's
    masking model assumes the trunk block is valid on [start_r, W0), which a
    SCRATCH trunk ([session trunk | session tail] with interior invalid
    columns — stepper.rollout_scored_many) violates; the einsum path masks
    by ``trunk.key_valid`` and handles any validity pattern.

    ``ssm``: a configuration with recurrent layers keeps each row's state
    here (forked from the trunk's where the rows share one); this step
    advances it by the one position.

    With layers of more than one kind the trunk's ``k`` and ``v``, the tails
    and every frozen block are dictionaries by kind of attention
    (``KVCache``, ``kv_buffers``), plain arrays all: the int8 forms and the
    Pallas kernel are refused by name.  ``moe_held``: the routed layers'
    tally (``MOE_TALLY``); handed one, the step adds its own and returns it
    fifth.

    Returns (final-norm hidden (Rows, D), new tail_k, new tail_v, new ssm)
    with the tail structure preserved; ``ssm`` is None where there is none.
    """
    c = config
    if c.has_ssm and ssm is None:
        raise RecurrentStateUnsupported(
            "forward_trunk_tail", "rows without their recurrent state")
    frozen_k = tuple(frozen_k)
    frozen_v = tuple(frozen_v)
    frozen_positions = tuple(frozen_positions)
    tail_quantized = isinstance(tail_k, tuple)
    trunk_quantized = isinstance(trunk.k, tuple)
    if c.has_layer_kinds:
        if tail_quantized or trunk_quantized:
            raise LayerKindsUnsupported("an int8 key-value tail", NEEDS_ONE_KIND)
        if c.use_decode_attention and use_decode_kernel:
            raise LayerKindsUnsupported(
                "decode_attention", KERNEL_NEEDS_PLAIN_HEADS)

    def block_width(block) -> int:
        if isinstance(block, dict):  # by kind of attention: alike in width
            block = next(iter(block.values()))
        return (block[0] if isinstance(block, tuple) else block).shape[2]

    t_tail = block_width(tail_k)

    x = embed_tokens(params, c, tokens)  # (Rows, D)

    qp = positions.reshape(n_slots, n_roles)  # (P, R)
    # Trunk masks: (P, R, W0) — every valid trunk key precedes the query.
    trunk_kp = trunk.key_positions[None, :, :]  # (1, R, W0)
    trunk_mask = jnp.broadcast_to(
        trunk.key_valid[None], (n_slots,) + trunk.key_valid.shape
    )
    # Tail masks: (P, R, Ts) — columns up to and including write_col.
    tail_cols = jnp.arange(t_tail)
    tail_fill = (tail_cols <= write_col)[None, None, :]
    tail_kp = tail_positions.reshape(n_slots, n_roles, t_tail)
    if c.sliding_window is not None:
        trunk_local = trunk_mask & (qp[:, :, None] - trunk_kp < c.sliding_window)
        tail_local = tail_fill & (qp[:, :, None] - tail_kp < c.sliding_window)
    else:
        trunk_local = trunk_mask
        tail_local = jnp.broadcast_to(tail_fill, (n_slots, n_roles, t_tail))
    tail_mask = jnp.broadcast_to(tail_fill, (n_slots, n_roles, t_tail))
    # Frozen columns are always fully valid — segments append exactly
    # seg_len columns each (generate.py) — so only the sliding window
    # ever masks them.  Widths come from the UNsliced (L, Rows, F, ...)
    # blocks here; inside the layer scan the leading layer axis is gone.
    frozen_widths = [block_width(b) for b in frozen_k]
    frozen_masks = []
    frozen_locals = []
    for width, fp in zip(frozen_widths, frozen_positions):
        mask = jnp.ones((n_slots, n_roles, width), bool)
        frozen_masks.append(mask)
        if c.sliding_window is not None:
            fkp = fp.reshape(n_slots, n_roles, width)
            frozen_locals.append(qp[:, :, None] - fkp < c.sliding_window)
        else:
            frozen_locals.append(mask)

    def call_decode(window, q, k_trunk, v_trunk, k_tail, v_tail, layer):
        # Fused pallas kernel (ops/decode_attention.py): one VMEM pass
        # per (role, kv-head) instead of four einsums with an fp32
        # logits intermediate.  Session call sites guarantee per-role
        # query positions (slots advance in lockstep) — qpos from slot
        # 0's rows; trunk spans from key_valid (left-padded prefills).
        # The tails come whole, as the loop carries them (through
        # ``windowed``'s cond too); the kernel takes this layer's.
        from consensus_tpu.ops.decode_attention import decode_attention

        return decode_attention(
            q, k_trunk, v_trunk, layer_of(k_tail, layer),
            layer_of(v_tail, layer),
            jnp.argmax(trunk.key_valid, axis=1).astype(jnp.int32),
            positions.reshape(n_slots, n_roles)[0], write_col,
            n_slots=n_slots, n_roles=n_roles, scale=c.q_scale,
            softcap=c.attn_softcap, window=window,
            interpret=jax.default_backend() == "cpu",
        )

    def attend(q, k, v, operands_l, tails, layer, is_local, sink=None,
               latent=None):
        """This step's K/V written (quantised where the tail is) at the
        tails' ``(layer, 0, write_col)``, one column of the carried
        buffers; then [trunk | frozen blocks | this layer's tail] attended,
        the trunk broadcast over the slots.  (A latent layer's trunk, blocks
        and tail are one buffer each, their value sides None: ``latent``
        makes the keys and values of each, the one query a row's absorbed
        form.)"""
        k_trunk, v_trunk, froz_k, froz_v = operands_l

        def write_column(tail, new):
            """``new`` (Rows, 1, KV, hd) into every layer's ``tail`` (or its
            (int8, scale) pair) at this layer's column."""
            if tail_quantized:
                new = quantize_kv(new)
            return jax.tree.map(
                lambda buffer, column: put_columns(
                    buffer, layer, write_col, column),
                tail, new)

        with jax.named_scope("kv_write"):
            tails = (write_column(tails[0], k),
                     None if v is None else write_column(tails[1], v))
        # This layer's tail, read where it lies: the index goes into the
        # einsums' operand reads.
        new_k_tail, new_v_tail = jax.tree.map(
            lambda buffer: layer_of(buffer, layer), tails)
        if latent is not None:
            k_trunk, v_trunk = latent(k_trunk)
            new_k_tail, new_v_tail = latent(new_k_tail)
            froz_k, froz_v = zip(*map(latent, froz_k)) if froz_k else ((), ())
        kv, hd = k_trunk.shape[-2:] if latent is not None else k.shape[-2:]
        reps = q.shape[-2] // kv

        with attention_scope(is_local, latent):
            if (
                c.use_decode_attention
                and use_decode_kernel
                and not frozen_k
                and not tail_quantized
                and not trunk_quantized
            ):
                attn = windowed(
                    c, is_local, call_decode,
                    q[:, 0], k_trunk, v_trunk, tails[0], tails[1], layer,
                ).astype(x.dtype)
            else:
                qg = q.reshape(n_slots, n_roles, kv, reps, hd)

                def key_logits(block, width):
                    """(P,R,g,m,width) attention logits for one generated-KV
                    block, dequantizing int8 via the per-(token, head) scale."""
                    quantized = isinstance(block, tuple)
                    values = block[0] if quantized else block
                    kg = values.astype(x.dtype).reshape(
                        n_slots, n_roles, width, kv, hd
                    )
                    lg = jnp.einsum("prgmd,prtgd->prgmt", qg, kg).astype(jnp.float32)
                    if quantized:
                        # Scales are per (row, token, head): (Rows, F, g, 1) ->
                        # (P, R, g, 1, F) against lg's (p, r, g, m, t).
                        s = block[1].reshape(n_slots, n_roles, width, kv)
                        lg = lg * s.transpose(0, 1, 3, 2)[:, :, :, None, :]
                    return lg

                def value_attend(block, width, w):
                    """Weighted value sum for one generated-KV block; value
                    scales fold into the f32 weights, the dot runs int8."""
                    quantized = isinstance(block, tuple)
                    values = block[0] if quantized else block
                    vg = values.astype(x.dtype).reshape(
                        n_slots, n_roles, width, kv, values.shape[-1]
                    )
                    if quantized:
                        s = block[1].reshape(n_slots, n_roles, width, kv)
                        w = (
                            w.astype(jnp.float32)
                            * s.transpose(0, 1, 3, 2)[:, :, :, None, :]
                        ).astype(x.dtype)
                    return jnp.einsum("prgmt,prtgd->prgmd", w, vg)

                # Trunk attention broadcasts the shared (R, W0) keys over slots.
                # A quantized trunk (classic-layout segmented decodes under
                # kv_quant: the per-row prompt cache is the dominant per-step
                # read) dequantizes exactly like the generated-KV blocks, with
                # the (R, W0, kv) scales broadcast over slots.
                if trunk_quantized:
                    lt = jnp.einsum(
                        "prgmd,rtgd->prgmt", qg, k_trunk[0].astype(x.dtype)
                    ).astype(jnp.float32)
                    st = k_trunk[1][..., 0]  # (R, W0, kv)
                    lt = lt * st.transpose(0, 2, 1)[None, :, :, None, :]
                else:
                    lt = jnp.einsum(
                        "prgmd,rtgd->prgmt", qg, k_trunk
                    ).astype(jnp.float32)
                # Chronological key order [trunk, frozen blocks..., tail].
                widths = frozen_widths + [t_tail]
                blocks = [lt] + [
                    key_logits(b, w) for b, w in zip(froz_k, frozen_widths)
                ] + [key_logits(new_k_tail, t_tail)]
                masks = [
                    _layer_mask(is_local, local, everywhere)
                    for local, everywhere in zip(
                        [trunk_local] + frozen_locals + [tail_local],
                        [trunk_mask] + frozen_masks + [tail_mask])
                ]
                logits = jnp.concatenate(blocks, axis=-1) * c.q_scale
                logits = _softcap(logits, c.attn_softcap)
                mask = jnp.concatenate(masks, axis=-1)[:, :, None, None]
                logits = jnp.where(mask, logits, MASK_FILL)
                if sink is not None:  # one a query head: (KV, reps, 1)
                    sink = sink.reshape(kv, reps)[:, :, None]
                weights = softmax_with_sink(logits, sink).astype(x.dtype)
                w0 = (k_trunk[0] if trunk_quantized else k_trunk).shape[1]
                wt = weights[..., :w0]
                if trunk_quantized:
                    sv = v_trunk[1][..., 0]  # (R, W0, kv)
                    wt = (
                        wt.astype(jnp.float32)
                        * sv.transpose(0, 2, 1)[None, :, :, None, :]
                    ).astype(x.dtype)
                    attn = jnp.einsum(
                        "prgmt,rtgd->prgmd", wt, v_trunk[0].astype(x.dtype)
                    )
                else:
                    attn = jnp.einsum("prgmt,rtgd->prgmd", wt, v_trunk)
                offset = w0
                for block, width in zip(tuple(froz_v) + (new_v_tail,), widths):
                    attn = attn + value_attend(
                        block, width, weights[..., offset : offset + width]
                    )
                    offset += width
        return attn, None, tails

    # One pytree a kind serves every variant: lax.scan slices each read-only
    # leaf along the layer axis, including nested (int8, scale) pairs and
    # the per-block frozen tuples, and carries the tails as they come.
    if c.has_layer_kinds:
        operands = {
            name: (trunk.k[name], trunk.v[name],
                   tuple(block[name] for block in frozen_k),
                   tuple(block[name] for block in frozen_v))
            for name, _, _ in c.cache_kinds}
    else:
        operands = (trunk.k, trunk.v, frozen_k, frozen_v)
    x, held, written, new_ssm = scan_layers(
        params, c, x, positions, attend, operands,
        by_kind(c, tail_k, tail_v), ssm, None)
    new_tail_k, new_tail_v = from_kinds(c, written)
    x = final_norm(params, c, x)
    if moe_held is not None:
        return x, new_tail_k, new_tail_v, new_ssm, moe_held + held
    return x, new_tail_k, new_tail_v, new_ssm


def forward_shared_trunk(
    params: Params,
    config: ModelConfig,
    suffix_tokens: jax.Array,  # (P, L) int32 — per-path suffix token ids
    cache: KVCache,  # R-row trunk cache (one row per role), read-only
    cur_pos: jax.Array,  # (R,) int32 — last written trunk position per role
    return_all_positions: bool = False,
    return_suffix_kv: bool = False,
) -> jax.Array:
    """Forward P path suffixes over ONE shared R-row trunk cache.

    Every lookahead-tree path shares the trunk (prompt + statement so far);
    only its <=`L`-token suffix differs.  Materializing the trunk cache per
    (path x role) row would cost P x the HBM of the trunk — instead the
    trunk keys/values keep their (R, T, ...) shape and broadcast against
    (P, R, ...) suffix queries inside the attention einsums, so the only
    per-path state is the L-token suffix itself.  The cache is not written.

    Returns final-norm hidden states of the LAST suffix position, (P, R, D).
    Replaces the per-node API walk of the reference's `_generate_tree_paths`
    (finite_lookahead.py:225-422) at zero cache duplication.

    ``return_suffix_kv``: additionally return the per-layer ROPED suffix
    keys and plain values, each (n_layers, P, R, L, KV, hd) — exactly the
    entries a per-(path x role) tail cache would hold, so a batched rollout
    (stepper.rollout_scored_many) can seed its decode tails from this one
    shared prefill instead of re-running the suffixes row-replicated.
    """
    c = config
    n_paths, span = suffix_tokens.shape
    n_roles = cache.key_valid.shape[0]
    if c.has_layer_kinds and return_suffix_kv:
        raise LayerKindsUnsupported(
            "forward_shared_trunk with suffix keys", NEEDS_ONE_KIND)

    x = embed_tokens(params, c, suffix_tokens)  # (P, L, D)
    # One row a (path, role), as the products and the mixer take them.
    x = jnp.broadcast_to(
        x[:, None], (n_paths, n_roles) + x.shape[1:]
    ).reshape(n_paths * n_roles, span, -1)  # (P*R, L, D)

    # Suffix positions continue each role's trunk: (R, L).
    positions = cur_pos[:, None] + 1 + jnp.arange(span)[None, :]

    # Masks are path-independent. Trunk: every suffix position sees every
    # valid trunk key (trunk positions always precede the suffix), windowed
    # for local layers. Suffix: causal within the path, same window.
    qp = positions[:, :, None]  # (R, L, 1)
    trunk_kp = cache.key_positions[:, None, :]  # (R, 1, T)
    trunk_mask = cache.key_valid[:, None, :] & jnp.ones(
        (1, span, 1), bool
    )  # (R, L, T)
    suffix_mask = jnp.broadcast_to(
        jnp.arange(span)[:, None] >= jnp.arange(span)[None, :],
        (n_roles, span, span),
    )  # (R, L, L)
    if c.sliding_window is not None:
        trunk_local = trunk_mask & (qp - trunk_kp < c.sliding_window)
        suffix_kp = positions[:, None, :]  # (R, 1, L)
        suffix_local = suffix_mask & (qp - suffix_kp < c.sliding_window)
    else:
        trunk_local, suffix_local = trunk_mask, suffix_mask

    ssm_rows = None
    if c.has_ssm:
        if return_suffix_kv:
            raise RecurrentStateUnsupported(
                "forward_shared_trunk", "suffix keys without the suffix's state")
        # Every (path, role) row starts from its role's state at the trunk's
        # end; what the suffix makes of it is not kept.
        ssm_rows = fork_ssm(cache.ssm, jnp.tile(jnp.arange(n_roles), n_paths))

    def attend(q, ks, vs, trunk_l, _, layer, is_local, sink=None, latent=None):
        """The trunk's (R, T) keys broadcast over the paths, beside each
        path's own suffix; nothing is written, so nothing is carried, and
        the suffix's K/V are produced: stacked by the loop.  (``latent``
        makes a latent layer's keys and values, of the trunk's latents and
        of the suffix's.)"""
        k_trunk, v_trunk = trunk_l  # (R, T, kv, hd)
        if latent is not None:
            k_trunk, v_trunk = latent(k_trunk)
            ks, vs = latent(ks)
        kv, hd = ks.shape[-2:]
        reps = q.shape[-2] // kv
        qg = q.reshape(n_paths, n_roles, span, kv, reps, hd)
        ks = ks.reshape(n_paths, n_roles, span, kv, hd)
        vs = vs.reshape(n_paths, n_roles, span, kv, vs.shape[-1])
        with attention_scope(is_local, latent):
            lt = jnp.einsum("prsgmd,rtgd->prgmst", qg, k_trunk).astype(jnp.float32)
            ls = jnp.einsum("prsgmd,prtgd->prgmst", qg, ks).astype(jnp.float32)
            logits = jnp.concatenate([lt, ls], axis=-1) * c.q_scale
            logits = _softcap(logits, c.attn_softcap)
            mask = jnp.concatenate(
                [_layer_mask(is_local, trunk_local, trunk_mask),
                 _layer_mask(is_local, suffix_local, suffix_mask)], axis=-1
            )[None, :, None, None]  # (1, R, 1, 1, L, T+L)
            logits = jnp.where(mask, logits, MASK_FILL)
            if sink is not None:  # one a query head: (KV, reps, 1, 1)
                sink = sink.reshape(kv, reps)[:, :, None, None]
            weights = softmax_with_sink(logits, sink).astype(x.dtype)
            t_len = k_trunk.shape[1]
            attn = jnp.einsum(
                "prgmst,rtgd->prsgmd", weights[..., :t_len], v_trunk
            ) + jnp.einsum(
                "prgmst,prtgd->prsgmd", weights[..., t_len:], vs
            )
        return attn, ((ks, vs) if return_suffix_kv else None), None

    x, suffix_kv, _, _ = scan_layers(
        params, c, x, jnp.tile(positions, (n_paths, 1)), attend,
        by_kind(c, cache.k, cache.v), None, ssm_rows, None)
    x = final_norm(params, c, x).reshape(n_paths, n_roles, span, -1)
    if return_all_positions:
        out = x  # (P, R, L, D) — the shared-context scorer needs every slot
    else:
        out = x[:, :, -1, :]  # (P, R, D)
    if return_suffix_kv:
        return out, suffix_kv[0], suffix_kv[1]
    return out


# ---------------------------------------------------------------------------
# Teacher-forced scoring
# ---------------------------------------------------------------------------


def project_logits(params: Params, config: ModelConfig, hidden: jax.Array) -> jax.Array:
    """Head-project hidden states (..., D) -> float32 logits (..., V), with
    the model's final softcap.  Callers slice hidden down (e.g. to the last
    position) BEFORE projecting so a (B, S, 256k) tensor never materializes."""
    with jax.named_scope("vocab_projection"):
        head = params["embed"] if config.tie_lm_head else params["lm_head"]
        logits = _times(head_matmul(hidden, head), config.lm_head_multiplier)
        return _softcap(logits, config.final_softcap)


@functools.partial(jax.jit, static_argnames=("config",))
def token_logprobs(
    params: Params,
    config: ModelConfig,
    tokens: jax.Array,  # (B, S) right-padded
    valid: jax.Array,  # (B, S)
) -> jax.Array:
    """Per-position logprob of tokens[:, t] given tokens[:, :t].

    Returns (B, S) float32; position 0 gets 0.0 (no conditioning context).
    This is the on-device replacement for the reference's echo'd-prompt
    logprob extraction (src/utils.py:201-373): one forward, gather.

    Materializes the full (B, S, V) logits — fine for small vocabs/tests;
    use :func:`token_logprobs_streamed` for 256k-vocab production models.
    """
    positions = jnp.maximum(jnp.cumsum(valid.astype(jnp.int32), axis=1) - 1, 0)
    logits, _ = forward(params, config, tokens, positions, valid)
    with jax.named_scope("logsumexp"):
        logprobs = jax.nn.log_softmax(logits, axis=-1)
        gathered = jnp.take_along_axis(
            logprobs[:, :-1, :], tokens[:, 1:, None], axis=-1
        )[..., 0]
        return jnp.pad(gathered, ((0, 0), (1, 0)))


@functools.partial(jax.jit, static_argnames=("config", "vocab_chunk"))
def token_logprobs_streamed(
    params: Params,
    config: ModelConfig,
    tokens: jax.Array,  # (B, S) right-padded
    valid: jax.Array,  # (B, S)
    vocab_chunk: int = 8192,
) -> jax.Array:
    """Memory-bounded teacher-forced scoring for huge vocabularies.

    A (B, S, 256k) float32 logits tensor for a Gemma-2 scoring batch is tens
    of GB — over HBM.  Instead: one forward to final hidden states, then a
    ``lax.scan`` over vocab tiles maintaining a streaming logsumexp
    (running max + rescaled sum), plus a direct gather of the target-token
    logits.  Peak extra memory is one (B, S, vocab_chunk) tile.  Gemma-2's
    final logit softcap (tanh) is applied per-tile, so semantics match
    :func:`token_logprobs` exactly.
    """
    c = config
    positions = jnp.maximum(jnp.cumsum(valid.astype(jnp.int32), axis=1) - 1, 0)
    x, _ = forward(params, c, tokens, positions, valid, return_hidden=True)
    gathered = _streamed_target_logprobs(
        params, c, x[:, :-1, :], tokens[:, 1:], vocab_chunk
    )
    return jnp.pad(gathered, ((0, 0), (1, 0)))


def _streamed_target_logprobs(
    params: Params,
    config: ModelConfig,
    x: jax.Array,  # (B, S, D) final-norm hidden states
    targets: jax.Array,  # (B, S) int32 — token whose logprob each slot yields
    vocab_chunk: int,
    constrain_tile: Optional[Callable[[jax.Array], jax.Array]] = None,
) -> jax.Array:
    """log p(targets[b, s] | hidden x[b, s]) with a streaming logsumexp over
    vocab tiles — the memory-bounded core shared by the full-sequence and
    shared-context scorers and the fused score chunk (never materializes
    (B, S, V); the head's table is read once whatever B and S are).
    ``constrain_tile`` is the caller's sharding constraint on a
    (B, S, vocab_chunk) tile, for a program lowered under a mesh."""
    c = config
    head = params["embed"] if c.tie_lm_head else params["lm_head"]
    vocab = head.shape[0]
    n_chunks = -(-vocab // vocab_chunk)
    batch, span = targets.shape

    def tile_step(carry, i):
        run_max, run_sum = carry
        with jax.named_scope("vocab_projection"):
            start = jnp.maximum(jnp.minimum(i * vocab_chunk, vocab - vocab_chunk), 0)
            rows, row_scales = slice_rows(head, start, min(vocab_chunk, vocab))
            tile = jnp.einsum(
                "bsd,vd->bsv",
                x,
                rows.astype(x.dtype) if row_scales is not None else rows,
                preferred_element_type=jnp.float32,
            )
            if row_scales is not None:
                tile = tile * row_scales[:, 0][None, None, :]
            tile = _softcap(_times(tile, c.lm_head_multiplier), c.final_softcap)
            if constrain_tile is not None:
                tile = constrain_tile(tile)
        with jax.named_scope("logsumexp"):
            row_ids = start + jnp.arange(rows.shape[0])
            fresh = (row_ids >= i * vocab_chunk) & (row_ids < vocab)
            tile = jnp.where(fresh[None, None, :], tile, -jnp.inf)
            tile_max = jnp.max(tile, axis=-1)
            new_max = jnp.maximum(run_max, tile_max)
            run_sum = run_sum * jnp.exp(run_max - new_max) + jnp.sum(
                jnp.exp(tile - new_max[..., None]), axis=-1
            )
        return (new_max, run_sum), None

    init = (
        jnp.full((batch, span), -jnp.inf, jnp.float32),
        jnp.zeros((batch, span), jnp.float32),
    )
    (run_max, run_sum), _ = jax.lax.scan(tile_step, init, jnp.arange(n_chunks))
    with jax.named_scope("logsumexp"):
        lse = run_max + jnp.log(run_sum)
        target_logits = _softcap(
            _times(gather_target_logits(x, head, targets), c.lm_head_multiplier),
            c.final_softcap,
        )
        return target_logits - lse


@functools.partial(jax.jit, static_argnames=("config", "vocab_chunk"))
def shared_context_token_logprobs(
    params: Params,
    config: ModelConfig,
    ctx_tokens: jax.Array,  # (1, C) int32, RIGHT-padded shared context
    ctx_valid: jax.Array,  # (1, C) bool
    cont_tokens: jax.Array,  # (P, L) int32, RIGHT-padded continuations
    cont_valid: jax.Array,  # (P, L) bool
    vocab_chunk: int = 8192,
) -> jax.Array:
    """Score P continuations of ONE shared context: (P, L) float32 where
    slot [p, j] = log p(cont[p, j] | ctx, cont[p, :j]).  Invalid slots are 0.

    Scoring a batch of candidates that share their prompt (best_of_n scores
    every candidate under every agent context — reference best_of_n.py:266-
    321) through :func:`token_logprobs` repeats the full context forward per
    candidate: O(P·(C+L)) token-forwards.  Here the context prefills ONCE
    into a trunk cache and only the continuations run, with the trunk
    broadcast against all candidates inside the attention einsums
    (:func:`forward_shared_trunk`): O(C + P·L).  For the AAMAS workload
    (C≈1k context, L≈0.2k statements) that is a 4-5x compute cut on the
    scoring phase that dominates best-of-n cells.

    Semantics match :func:`token_logprobs` on the concatenated sequence
    (numerically equivalent; accumulation order differs, so not bitwise):
    continuation token 0 is conditioned on the context's last hidden
    state; token j>0 on the suffix forward at j-1; causality, RoPE
    positions, and sliding windows all continue the context's coordinates.
    """
    trunk, ctx_len, last_hidden = shared_context_prefill(
        params, config, ctx_tokens, ctx_valid
    )
    return shared_context_cont_logprobs(
        params, config, trunk, ctx_len, last_hidden,
        cont_tokens, cont_valid, vocab_chunk,
    )


@functools.partial(jax.jit, static_argnames=("config",))
def shared_context_prefill(
    params: Params,
    config: ModelConfig,
    ctx_tokens: jax.Array,  # (1, C) int32, RIGHT-padded shared context
    ctx_valid: jax.Array,  # (1, C) bool
) -> Tuple[KVCache, jax.Array, jax.Array]:
    """Prefill ONE shared context into a trunk cache; returns (trunk,
    ctx_len (1,), last_hidden (1, 1, D)).

    Split out of :func:`shared_context_token_logprobs` so a >max_batch_rows
    scoring group prefills its context ONCE and scores every row chunk
    against the same resident trunk (round 2 re-prefilled per 32-row chunk
    — VERDICT r2 #5)."""
    c = config
    ctx_width = ctx_tokens.shape[1]
    trunk = make_cache(c, 1, ctx_width, params["embed"].dtype)
    positions = jnp.maximum(jnp.cumsum(ctx_valid.astype(jnp.int32), axis=1) - 1, 0)
    hidden_ctx, trunk = forward(
        params, c, ctx_tokens, positions, ctx_valid, trunk, 0, return_hidden=True
    )
    ctx_len = jnp.sum(ctx_valid.astype(jnp.int32), axis=1)  # (1,)
    last_hidden = jnp.take_along_axis(
        hidden_ctx, (ctx_len - 1)[:, None, None], axis=1
    )  # (1, 1, D)
    return trunk, ctx_len, last_hidden


@functools.partial(jax.jit, static_argnames=("config", "vocab_chunk"))
def shared_context_cont_logprobs(
    params: Params,
    config: ModelConfig,
    trunk: KVCache,
    ctx_len: jax.Array,  # (1,)
    last_hidden: jax.Array,  # (1, 1, D)
    cont_tokens: jax.Array,  # (P, L) int32, RIGHT-padded continuations
    cont_valid: jax.Array,  # (P, L) bool
    vocab_chunk: int = 8192,
) -> jax.Array:
    """Score P continuations against an already-prefilled shared trunk."""
    c = config
    n_cont, span = cont_tokens.shape

    # First continuation token: conditioned on the context only.
    first_lp = _streamed_target_logprobs(
        params, c,
        jnp.broadcast_to(last_hidden[:, 0], (n_cont, last_hidden.shape[-1]))[
            :, None, :
        ],
        cont_tokens[:, :1],
        vocab_chunk,
    )  # (P, 1)

    if span > 1:
        # Suffix forward: feed cont[:-1]; hidden j predicts cont[j+1].
        suffix = cont_tokens[:, :-1]
        hidden = forward_shared_trunk(
            params, c, suffix, trunk, ctx_len - 1, return_all_positions=True
        )  # (P, 1, L-1, D)
        rest_lp = _streamed_target_logprobs(
            params, c, hidden[:, 0], cont_tokens[:, 1:], vocab_chunk
        )  # (P, L-1)
        logprobs = jnp.concatenate([first_lp, rest_lp], axis=1)
    else:
        logprobs = first_lp
    return jnp.where(cont_valid, logprobs, 0.0)
