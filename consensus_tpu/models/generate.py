"""Batched autoregressive generation with a preallocated KV cache.

The decode loop is a ``lax.while_loop`` over step index — one compiled
program per (batch, context, max_new_tokens) shape bucket, exiting as soon
as every row has hit EOS (each skipped step saves a full weight read).
Prompts must be LEFT-padded so every row's next token writes the same cache
slot and the last prompt column is always a real token.

Replaces the reference's per-call HTTPS text generation
(``generate_text``, src/utils.py:77-198): temperature/seed/stop/logit-bias
semantics live here and in :mod:`consensus_tpu.models.sampling`; stop-*string*
truncation stays host-side in the backend (tokenizer-dependent).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from consensus_tpu.models.config import (
    NEEDS_ONE_KIND,
    LayerKindsUnsupported,
    ModelConfig,
)
from consensus_tpu.models.sampling import ban_undecodable, sample_tokens
from consensus_tpu.models.transformer import (
    MOE_TALLY,
    KVCache,
    fork_ssm,
    forward,
    forward_trunk_tail,
    kv_buffers,
    make_cache,
    project_logits,
)
from consensus_tpu.models.transformer import quantize_kv as transformer_quantize_kv


class GenerateOutput(NamedTuple):
    """Decode results.

    Residency contract: the monolithic jitted entry points return DEVICE
    arrays; the ``*_segmented`` host loops return HOST numpy arrays (their
    per-segment buffers are already fetched — shipping them back to the
    device would be a pointless round trip).  Consumers
    must treat the fields as array-likes (``np.asarray`` is always safe)
    and must NOT assume device residency.
    """

    tokens: jax.Array  # (B, max_new_tokens) int32; pad_id after EOS
    num_generated: jax.Array  # (B,) int32 — tokens before (excluding) EOS
    hit_eos: jax.Array  # (B,) bool
    #: With routed experts: the decode steps' tally (int32
    #: ``transformer.MOE_TALLY``); None without.
    moe_held: Optional[jax.Array] = None


def _assemble_output(tokens_buf, emitted_buf, max_new_tokens, pad_id,
                     moe_held=None):
    """(T, B) step buffers -> GenerateOutput (works traced or concrete)."""
    tokens = tokens_buf.T  # (B, T)
    emitted = emitted_buf.T
    num_generated = jnp.sum(emitted.astype(jnp.int32), axis=1)
    hit_eos = num_generated < max_new_tokens
    tokens = jnp.where(emitted, tokens, pad_id)
    return GenerateOutput(
        tokens=tokens, num_generated=num_generated, hit_eos=hit_eos,
        moe_held=moe_held,
    )


def left_pad_positions(valid: jax.Array) -> jax.Array:
    """RoPE positions for a left-padded valid mask: pads clamp to 0."""
    return jnp.maximum(jnp.cumsum(valid.astype(jnp.int32), axis=1) - 1, 0)


#: Shared absmax-int8 KV quantizer (transformer.quantize_kv): one
#: implementation serves the per-step tail writes, the classic prompt
#: trunk, and (by construction) the frozen blocks a quantized tail
#: freezes into — the scale layouts cannot drift apart.
_quantize_kv = jax.jit(transformer_quantize_kv)


def _prompt_presence(
    prompt_tokens: jax.Array,  # (R, S) int32
    prompt_valid: jax.Array,  # (R, S) bool
    vocab_size: int,
) -> jax.Array:
    """(R, V) bool mask of the prompt's token ids.

    Seeds the repetition-penalty seen-token mask: HF semantics (and the
    Together param the reference forwards, src/utils.py:88) penalize
    tokens from the prompt as well as prior generations."""
    rows = prompt_tokens.shape[0]
    pres = jnp.zeros((rows, vocab_size), jnp.bool_)
    return pres.at[jnp.arange(rows)[:, None], prompt_tokens].max(prompt_valid)


def _take_rows_keep_sharding(array, idx, axis):
    """Row gather that PRESERVES the input's named sharding.

    ``jnp.take`` with an index vector returns a fully REPLICATED result on
    a mesh (verified on an 8-device CPU mesh) — a compaction gather would
    silently de-shard the frozen KV and trunk for every later segment,
    losing the dp split and exceeding the per-device HBM the row allowance
    models.  Re-placing with the source's NamedSharding keeps batch rows on
    the ``data`` axis (the halved batch stays dp-divisible by the
    ``dp_align`` guard).
    """
    out = jnp.take(array, idx, axis=axis)
    sharding = getattr(array, "sharding", None)
    if sharding is not None and getattr(sharding, "spec", None) is not None:
        out = jax.device_put(out, sharding)
    return out


@functools.partial(
    jax.jit,
    static_argnames=("config", "max_new_tokens", "top_k", "top_p", "pad_id"),
)
def generate_tokens(
    params,
    config: ModelConfig,
    prompt_tokens: jax.Array,  # (B, S_ctx) int32, LEFT-padded
    prompt_valid: jax.Array,  # (B, S_ctx) bool
    key: jax.Array,
    max_new_tokens: int,
    temperature: float | jax.Array = 1.0,
    top_k: int = 0,
    top_p: float = 1.0,
    eos_ids: Optional[jax.Array] = None,  # (E,) int32; None/empty = no EOS stop
    logit_bias: Optional[jax.Array] = None,  # (V,) or (B, V) additive
    bias_table: Optional[jax.Array] = None,  # (U, V) unique bias vectors
    bias_index: Optional[jax.Array] = None,  # (B,) int32 row -> table index
    pad_id: int = 0,
    rep_penalty: Optional[jax.Array] = None,  # (B,) float32; None = off
) -> GenerateOutput:
    """Single-dispatch decode: prefill + ONE full-budget ``_decode_segment``
    (nested jit inlines, so this stays one compiled program).

    The decode loop is a while_loop (not scan) so the whole batch EXITS as
    soon as every row has hit EOS — real statements end at a fraction of
    the token budget, and each skipped step saves a full weight read.
    Bucket-padding dummy rows (no valid prompt tokens) start done: their
    outputs are never read, but left not-done they would almost never
    sample an EOS id and so would pin the early exit at the full budget.
    """
    batch = prompt_tokens.shape[0]
    next_logits, trunk, cur_pos = _prefill_classic(
        params, config, prompt_tokens, prompt_valid
    )
    init_done = ~jnp.any(prompt_valid, axis=1)
    presence = (
        _prompt_presence(prompt_tokens, prompt_valid, config.vocab_size)
        if rep_penalty is not None
        else None
    )
    tokens_buf, emitted_buf, *_, moe_held = _decode_segment(
        params, config, trunk, None, None, cur_pos,
        jnp.asarray(0, jnp.int32), next_logits, key, init_done,
        n_slots=1, n_roles=batch, seg_len=max_new_tokens,
        temperature=temperature, top_k=top_k, top_p=top_p, eos_ids=eos_ids,
        logit_bias=logit_bias, bias_table=bias_table, bias_index=bias_index,
        pad_id=pad_id, presence=presence, rep_penalty=rep_penalty,
    )
    return _assemble_output(
        tokens_buf, emitted_buf, max_new_tokens, pad_id, moe_held)


@functools.partial(
    jax.jit,
    static_argnames=("config", "batch", "max_new_tokens", "top_k", "top_p", "pad_id"),
)
def generate_tokens_shared_trunk(
    params,
    config: ModelConfig,
    prompt_tokens: jax.Array,  # (1, S_ctx) int32 — ONE shared prompt
    prompt_valid: jax.Array,  # (1, S_ctx) bool
    batch: int,  # rows to decode from the shared prompt
    key: jax.Array,  # (B, 2) per-row PRNG keys
    max_new_tokens: int,
    temperature: float | jax.Array = 1.0,
    top_k: int = 0,
    top_p: float = 1.0,
    eos_ids: Optional[jax.Array] = None,
    bias_table: Optional[jax.Array] = None,
    bias_index: Optional[jax.Array] = None,
    pad_id: int = 0,
    init_done: Optional[jax.Array] = None,  # (B,) bool — bucket-pad rows
    rep_penalty: Optional[jax.Array] = None,  # (B,) float32; None = off
) -> GenerateOutput:
    """``generate_tokens`` for B rows sharing ONE identical prompt.

    The workloads that dominate the sweep decode many rows from the same
    prompt: best_of_n's N drafts share the reference prompt
    (/root/reference/src/methods/best_of_n.py:101-142 — n calls, same
    prompt, seeds seed+i) and every habermas phase reuses one prompt per
    batch (habermas_machine.py:530-583).  The classic path prefills the
    prompt B times and each decode step re-reads B full prompt KV caches —
    at a 30-run cell's widths the per-step cache read is GBs and dominates
    the statement time.  Here the prompt prefills ONCE into a 1-row trunk
    and every decode row broadcast-attends it inside the attention einsum
    (transformer.forward_trunk_tail with n_slots=B, n_roles=1): per-step
    HBM traffic drops from B·(ctx+t) to ctx + B·t key/value rows, and
    prefill compute drops B-fold.

    Sampling semantics are identical to ``generate_tokens`` — per-row keys
    drive distinct rows; logits are row-independent of batch composition.
    """
    c = config
    # One logits row, broadcast to every decode row (_prefill_shared and
    # _decode_segment inline under this jit — still one compiled program;
    # the segmented host loop calls them standalone).
    next_logits_1, trunk, last_pos = _prefill_shared(
        params, config, prompt_tokens, prompt_valid
    )
    next_logits = jnp.broadcast_to(next_logits_1, (batch, c.vocab_size))
    cur_pos = jnp.broadcast_to(last_pos, (batch,))
    if init_done is None:
        init_done = jnp.zeros((batch,), jnp.bool_)
    presence = (
        jnp.broadcast_to(
            _prompt_presence(prompt_tokens, prompt_valid, c.vocab_size),
            (batch, c.vocab_size),
        )
        if rep_penalty is not None
        else None
    )
    tokens_buf, emitted_buf, *_, moe_held = _decode_segment(
        params, config, trunk, None, None, cur_pos,
        jnp.asarray(0, jnp.int32), next_logits, key, init_done,
        n_slots=batch, n_roles=1, seg_len=max_new_tokens,
        temperature=temperature, top_k=top_k, top_p=top_p, eos_ids=eos_ids,
        bias_table=bias_table, bias_index=bias_index, pad_id=pad_id,
        presence=presence, rep_penalty=rep_penalty,
    )
    return _assemble_output(
        tokens_buf, emitted_buf, max_new_tokens, pad_id, moe_held)


@functools.partial(jax.jit, static_argnames=("config",))
def _prefill_shared(
    params,
    config: ModelConfig,
    prompt_tokens: jax.Array,  # (1, S_ctx) int32 — ONE shared prompt
    prompt_valid: jax.Array,  # (1, S_ctx) bool
):
    """Prefill one shared prompt row: (next_logits (1, V), trunk, last_pos)."""
    with jax.named_scope("prefill"):
        trunk = make_cache(
            config, 1, prompt_tokens.shape[1], params["embed"].dtype)
        positions = left_pad_positions(prompt_valid)
        hidden, trunk = forward(
            params, config, prompt_tokens, positions, prompt_valid, trunk, 0,
            return_hidden=True,
        )
        next_logits = project_logits(params, config, hidden[:, -1, :])
        return next_logits, trunk, positions[0, -1]


@functools.partial(
    jax.jit,
    static_argnames=(
        "config", "n_slots", "n_roles", "seg_len", "top_k", "top_p", "pad_id",
        "quantize_tail",
    ),
)
def _decode_segment(
    params,
    config: ModelConfig,
    trunk,  # KVCache with n_roles rows (1 shared row, or one per request)
    frozen_k,  # tuple of (L, B, F_i, KV, hd) blocks (or (int8, scale) pairs)
    frozen_v,
    base_pos: jax.Array,  # (B,) int32 — per-row last prompt position
    seg_start: jax.Array,  # () int32 — tokens decoded before this segment
    next_logits: jax.Array,  # (B, V) float32
    keys: jax.Array,  # (B, 2) per-row PRNG keys
    done: jax.Array,  # (B,) bool
    n_slots: int,
    n_roles: int,
    seg_len: int,
    temperature: jax.Array,  # (B,) float32 (or scalar)
    top_k: int = 0,
    top_p: float = 1.0,
    eos_ids: Optional[jax.Array] = None,
    logit_bias: Optional[jax.Array] = None,  # (V,) or (B, V) additive
    bias_table: Optional[jax.Array] = None,
    bias_index: Optional[jax.Array] = None,
    pad_id: int = 0,
    quantize_tail: bool = False,
    presence: Optional[jax.Array] = None,  # (B, V) bool seen-token mask
    rep_penalty: Optional[jax.Array] = None,  # (B,) float32
    ssm=None,  # (L, B, ...) recurrent state a later segment starts from
    moe_held=None,  # transformer.MOE_TALLY, which a later segment adds to
):
    """One ``seg_len``-step slice of a decode, B = n_slots * n_roles rows.

    The live KV tail in the while_loop carry is only ``seg_len`` columns —
    a carry is state the compiler may copy every step, where an operand is
    only read.  Measured on one v5e (PERF.md 5 and 6, PR 31): while the
    layer loop took the tail as a scanned input and gave it back as a
    stacked output, the compiler copied the carried tail twice a step
    (0.2 GB a side at 32 rows x 64 columns, 0.4 as the device pads a head
    size of 64: 2.45 ms) and sliced and stacked it a layer (1.12 and
    1.24 ms), 4.8 of a 13.7 ms step on SmolLM2-1.7B; and the carried
    recurrent state of Falcon-H1 (537 MB) once a step beside its stacking,
    0.055 s of a 0.40 s generation call.  Since the layer loop carries the
    tail and the state whole (``transformer.scan_layers``) a step writes
    one column of the tail and one layer's state at a time, in place, and
    nothing of the carry is copied: 8.3 ms a step on SmolLM2-1.7B.
    Earlier segments ride in ``frozen_k/v``: read-only operand BLOCKS, one
    per frozen segment, never copied or concatenated.  With
    ``quantize_tail`` the live tail itself is int8+scale — the carry bytes
    halve again, and freezing a segment is a free list append.  Sampling
    math, PRNG folds, and masking are identical to the monolithic loops —
    per-step logits see the same key set [trunk, frozen..., tail] in
    chronological order.

    Serves both decode layouts: shared-trunk (n_slots=B, n_roles=1 — every
    row broadcast-attends trunk row 0) and classic per-row trunks
    (n_slots=1, n_roles=B).

    A configuration with recurrent layers carries each row's state through
    the loop beside the tail.  The first segment takes it from the trunk:
    row for row in the classic layout, one row forked to all B in the shared
    one (there is no broadcasting a state that every row then changes).  A
    later segment is handed ``ssm``, what the one before returned last.

    With layers of more than one kind the trunk's K/V, the tail and every
    frozen block are dictionaries by kind of attention (``kv_buffers``), and
    with routed experts the loop carries the routed layers' tally last
    (``transformer.MOE_TALLY``), which a later segment is handed as
    ``moe_held`` and every segment returns tenth.
    """
    c = config
    batch = n_slots * n_roles
    if c.has_layer_kinds and quantize_tail:
        raise LayerKindsUnsupported("an int8 key-value tail", NEEDS_ONE_KIND)
    if c.has_moe and moe_held is None:
        moe_held = jnp.zeros((len(MOE_TALLY),), jnp.int32)
    if c.has_ssm and ssm is None:
        ssm = trunk.ssm if n_roles == batch else fork_ssm(trunk.ssm, batch)
    if eos_ids is None:
        eos_ids = jnp.zeros((0,), jnp.int32)
    if bias_table is not None:
        # Dedup table shipped from host; per-row bias rows gather ON device.
        logit_bias = bias_table[bias_index]

    frozen_k = tuple(frozen_k) if frozen_k else ()
    frozen_v = tuple(frozen_v) if frozen_v else ()
    frozen_positions = []
    offset = 0
    for block in frozen_k:
        if isinstance(block, dict):  # by kind of attention: alike in width
            block = next(iter(block.values()))
        width = (block[0] if isinstance(block, tuple) else block).shape[2]
        frozen_positions.append(
            base_pos[:, None] + 1 + offset + jnp.arange(width)[None, :]
        )
        offset += width
    cur_pos = base_pos + seg_start
    tail_positions = cur_pos[:, None] + 1 + jnp.arange(seg_len)[None, :]
    tail_shape = (c.n_layers, batch, seg_len, c.n_kv_heads, c.head_dim)
    if quantize_tail:
        scale_shape = tail_shape[:-1] + (1,)
        tail_k = (
            jnp.zeros(tail_shape, jnp.int8), jnp.zeros(scale_shape, jnp.float32)
        )
        tail_v = (
            jnp.zeros(tail_shape, jnp.int8), jnp.zeros(scale_shape, jnp.float32)
        )
    else:
        tail_k, tail_v = kv_buffers(c, (batch, seg_len), params["embed"].dtype)

    def is_eos(token: jax.Array) -> jax.Array:
        if eos_ids.shape[0] == 0:
            return jnp.zeros_like(token, dtype=jnp.bool_)
        return jnp.any(token[:, None] == eos_ids[None, :], axis=-1)

    tokens_buf = jnp.full((seg_len, batch), pad_id, jnp.int32)
    emitted_buf = jnp.zeros((seg_len, batch), jnp.bool_)
    # Repetition penalty needs the seen-token mask in the carry (it grows
    # with each sampled token).  The default path (no penalty) must trace
    # EXACTLY as before — same carry tuple, same HLO — so the mask rides as
    # an optional tenth element, present only when the feature is on.
    use_rp = presence is not None and rep_penalty is not None

    def cond(carry):
        return (carry[0] < seg_len) & ~jnp.all(carry[4])

    def body(carry):
        (i, next_logits, tail_k, tail_v, done, key, cur_pos, tokens_buf,
         emitted_buf) = carry[:9]
        pres = carry[9] if use_rp else None
        state = carry[-1] if c.has_ssm else None
        held = carry[-1] if c.has_moe else None
        if key.ndim == 2:  # per-row keys: rows draw independently
            pairs = jax.vmap(jax.random.split)(key)
            key, sub = pairs[:, 0], pairs[:, 1]
        else:
            key, sub = jax.random.split(key)
        token = sample_tokens(
            sub, ban_undecodable(next_logits, config),
            temperature=temperature, top_k=top_k, top_p=top_p,
            logit_bias=logit_bias,
            presence=pres, rep_penalty=rep_penalty if use_rp else None,
        )
        token = jnp.where(done, pad_id, token)
        if use_rp:
            # Done rows re-mark pad_id — harmless, keeps the scatter dense.
            pres = pres.at[jnp.arange(batch), token].set(True)
        token_is_eos = is_eos(token) & ~done
        emitted = ~done & ~token_is_eos
        new_done = done | token_is_eos

        pos = cur_pos + 1
        hidden, tail_k, tail_v, state, *step_held = forward_trunk_tail(
            params, config, token, pos, trunk, tail_k, tail_v,
            tail_positions, i, n_slots, n_roles,
            frozen_k=frozen_k, frozen_v=frozen_v,
            frozen_positions=tuple(frozen_positions),
            ssm=state, moe_held=held,
        )
        logits = project_logits(params, config, hidden)
        tokens_buf = jax.lax.dynamic_update_slice(tokens_buf, token[None], (i, 0))
        emitted_buf = jax.lax.dynamic_update_slice(
            emitted_buf, emitted[None], (i, 0)
        )
        out = (
            i + 1, logits, tail_k, tail_v, new_done, key, pos,
            tokens_buf, emitted_buf,
        )
        return out + ((pres,) if use_rp else ()) + (
            (state,) if c.has_ssm else ()) + (
            tuple(step_held))

    init = (
        jnp.asarray(0, jnp.int32), next_logits, tail_k, tail_v,
        done, keys, cur_pos, tokens_buf, emitted_buf,
    ) + ((presence,) if use_rp else ()) + ((ssm,) if c.has_ssm else ()) + (
        (moe_held,) if c.has_moe else ())
    with jax.named_scope("decode_step"):
        final = jax.lax.while_loop(cond, body, init)
    (_, next_logits, tail_k, tail_v, done, keys, _, tokens_buf, emitted_buf) = final[:9]
    presence = final[9] if use_rp else None
    return (
        tokens_buf, emitted_buf, next_logits, tail_k, tail_v, done, keys,
        presence, final[-1] if c.has_ssm else None,
        final[-1] if c.has_moe else None,
    )


def _segmented_loop(
    params,
    config: ModelConfig,
    trunk,
    base_pos: jax.Array,  # (B,) int32 per-row last prompt position
    next_logits: jax.Array,  # (B, V)
    keys: jax.Array,
    done: jax.Array,
    n_slots: int,
    n_roles: int,
    max_new_tokens: int,
    seg_len: int,
    temperature: jax.Array,
    top_k: int,
    top_p: float,
    eos_ids: jax.Array,
    bias_table,
    bias_index,
    pad_id: int,
    logit_bias=None,
    dp_align: int = 1,
    kv_quant: bool = False,
    presence: Optional[jax.Array] = None,  # (B, V) bool seen-token mask
    rep_penalty: Optional[jax.Array] = None,  # (B,) float32
) -> GenerateOutput:
    """Host loop over ``_decode_segment`` calls shared by both layouts.

    Between segments the host checks whether every row is done — real
    statements finish at a fraction of the 700-token habermas budget, so
    whole segments are skipped where a monolithic loop only skips steps.

    Completed segments append to a LIST of frozen operand blocks — never
    concatenated, so there is no append copy and no 2x frozen transient in
    the HBM peak (round 3's single-block design made that transient the
    row-allowance bound).  With ``kv_quant`` the live tail is written int8
    (carry bytes halve) and freezing is a free list append.

    Rows that finish COMPACT away at segment boundaries — but only by
    HALVING the batch: every per-row array (and, in the classic layout,
    the per-row trunk) gathers down to the survivors, so later segments
    pay weights+KV traffic only for rows still decoding.  Halving-only
    keeps the compiled-program space bounded (log2 row variants per
    frozen-width family, vs one per ladder bucket) and each halving
    guarantees >=2x per-step tail savings.  ``dp_align`` preserves the
    backend's dp-divisibility invariant: a halved batch that no longer
    divides the data mesh axis would silently lose the dp sharding.
    Per-row PRNG keys make each row's stream independent of batch
    composition (the invariant tests/test_batching.py already pins), so
    compaction changes no tokens — only traffic.
    """
    import numpy as np

    batch = n_slots * n_roles
    shared_layout = n_roles == 1
    orig_batch = batch
    row_map = np.arange(batch)  # current row -> original row
    # Scalar-key streams are batch-coupled (one draw feeds all rows), so
    # row gathers would change them; compact only with per-row keys.
    can_compact = getattr(keys, "ndim", 0) == 2 and jnp.ndim(temperature) == 1

    frozen_k: list = []
    frozen_v: list = []
    ssm = None  # the first segment takes the rows' state from the trunk
    moe_held = None  # and the first starts the tally of held assignments
    tokens = np.full((orig_batch, max_new_tokens), pad_id, np.int32)
    emitted = np.zeros((orig_batch, max_new_tokens), bool)
    n_segs = max_new_tokens // seg_len
    for seg in range(n_segs):
        (tokens_buf, emitted_buf, next_logits, tail_k, tail_v, done, keys,
         presence, ssm, moe_held) = (
            _decode_segment(
                params, config, trunk, tuple(frozen_k), tuple(frozen_v),
                base_pos, jnp.asarray(seg * seg_len, jnp.int32),
                next_logits, keys, done,
                n_slots=batch if shared_layout else 1,
                n_roles=1 if shared_layout else batch,
                seg_len=seg_len,
                temperature=temperature,
                top_k=top_k, top_p=top_p, eos_ids=eos_ids,
                logit_bias=logit_bias,
                bias_table=bias_table, bias_index=bias_index, pad_id=pad_id,
                quantize_tail=kv_quant,
                presence=presence, rep_penalty=rep_penalty, ssm=ssm,
                moe_held=moe_held,
            )
        )
        col = seg * seg_len
        tokens[row_map, col:col + seg_len] = np.asarray(tokens_buf).T
        emitted[row_map, col:col + seg_len] = np.asarray(emitted_buf).T
        if seg + 1 == n_segs:
            break
        done_host = np.asarray(done)
        if done_host.all():
            break
        # The finished segment's tail freezes as-is (already int8+scale
        # under kv_quant) — a list append, no copy, no quantize dispatch.
        frozen_k.append(tail_k)
        frozen_v.append(tail_v)
        if can_compact:
            alive = np.flatnonzero(~done_host)
            target = batch
            while (
                target // 2 >= len(alive)
                and target // 2 >= max(8, dp_align)
                and (target // 2) % dp_align == 0
            ):
                target //= 2
            if target < batch:
                # Pad the survivor set with done rows up to the bucket
                # (their outputs are discarded; they start done).
                pad_rows = np.flatnonzero(done_host)[: target - len(alive)]
                idx_host = np.concatenate([alive, pad_rows])
                idx = jnp.asarray(idx_host)
                row_map = row_map[idx_host]
                take = _take_rows_keep_sharding
                frozen_k = jax.tree.map(
                    lambda a: take(a, idx, axis=1), frozen_k
                )
                frozen_v = jax.tree.map(
                    lambda a: take(a, idx, axis=1), frozen_v
                )
                next_logits = take(next_logits, idx, axis=0)
                keys = take(keys, idx, axis=0)
                done = take(done, idx, axis=0)
                base_pos = take(base_pos, idx, axis=0)
                temperature = take(temperature, idx, axis=0)
                if bias_index is not None:
                    bias_index = take(bias_index, idx, axis=0)
                if logit_bias is not None and jnp.ndim(logit_bias) == 2:
                    logit_bias = take(logit_bias, idx, axis=0)
                if presence is not None:
                    presence = take(presence, idx, axis=0)
                if rep_penalty is not None:
                    rep_penalty = take(rep_penalty, idx, axis=0)
                if ssm is not None:
                    ssm = jax.tree.map(lambda a: take(a, idx, axis=1), ssm)
                if not shared_layout:
                    # Classic layout: the trunk is per-row too.
                    trunk = jax.tree.map(
                        lambda a: take(a, idx, axis=1)
                        if a.ndim >= 3 else take(a, idx, axis=0),
                        trunk,
                    )
                batch = target

    num_generated = emitted.sum(axis=1).astype(np.int32)
    hit_eos = num_generated < max_new_tokens
    tokens = np.where(emitted, tokens, pad_id)
    # Host arrays, deliberately: every consumer (backend _finish_generation,
    # tests) immediately np.asarray()s the fields — shipping them back to
    # the device would be a pointless round trip.
    return GenerateOutput(
        tokens=tokens, num_generated=num_generated, hit_eos=hit_eos,
        moe_held=moe_held,
    )


def generate_tokens_shared_trunk_segmented(
    params,
    config: ModelConfig,
    prompt_tokens: jax.Array,  # (1, S_ctx) int32 — ONE shared prompt
    prompt_valid: jax.Array,  # (1, S_ctx) bool
    batch: int,
    key: jax.Array,  # (B, 2) per-row PRNG keys
    max_new_tokens: int,
    seg_len: int = 128,
    temperature: float | jax.Array = 1.0,
    top_k: int = 0,
    top_p: float = 1.0,
    eos_ids: Optional[jax.Array] = None,
    bias_table: Optional[jax.Array] = None,
    bias_index: Optional[jax.Array] = None,
    pad_id: int = 0,
    init_done: Optional[jax.Array] = None,
    dp_align: int = 1,
    kv_quant: bool = False,
    rep_penalty: Optional[jax.Array] = None,  # (B,) float32; None = off
) -> GenerateOutput:
    """``generate_tokens_shared_trunk`` as a host loop over short segments.

    Semantics are identical (same per-step sampling math and PRNG stream);
    only the HBM traffic shape changes: the while_loop carries a
    ``seg_len``-column live tail instead of the full ``max_new_tokens``
    window, and completed segments move to read-only frozen operands
    (scripts/decode_step_bench.py times both; not measured on this
    toolchain).
    """
    c = config
    if config.use_decode_attention:
        # The fused pallas decode-attention kernel has no frozen-operand
        # variant: segment 0 would use the kernel and later segments the
        # einsum path, quietly breaking the token-exact contract.
        raise ValueError(
            "segmented decode is incompatible with use_decode_attention; "
            "use the monolithic decode path instead"
        )
    if max_new_tokens % seg_len:
        raise ValueError(
            f"max_new_tokens={max_new_tokens} must be a multiple of "
            f"seg_len={seg_len} (bucketed widths are)"
        )
    if eos_ids is None:
        eos_ids = jnp.zeros((0,), jnp.int32)
    temperature = jnp.broadcast_to(
        jnp.asarray(temperature, jnp.float32), (batch,)
    )

    next_logits_1, trunk, last_pos = _prefill_shared(
        params, config, prompt_tokens, prompt_valid
    )
    next_logits = jnp.broadcast_to(next_logits_1, (batch, c.vocab_size))
    done = (
        jnp.zeros((batch,), jnp.bool_) if init_done is None else init_done
    )
    presence = (
        jnp.broadcast_to(
            _prompt_presence(prompt_tokens, prompt_valid, c.vocab_size),
            (batch, c.vocab_size),
        )
        if rep_penalty is not None
        else None
    )
    return _segmented_loop(
        params, config, trunk, jnp.broadcast_to(last_pos, (batch,)),
        next_logits, key, done,
        n_slots=batch, n_roles=1,
        max_new_tokens=max_new_tokens, seg_len=seg_len,
        temperature=temperature, top_k=top_k, top_p=top_p, eos_ids=eos_ids,
        bias_table=bias_table, bias_index=bias_index, pad_id=pad_id,
        dp_align=dp_align, kv_quant=kv_quant,
        presence=presence, rep_penalty=rep_penalty,
    )


@functools.partial(jax.jit, static_argnames=("config",))
def _prefill_classic(
    params,
    config: ModelConfig,
    prompt_tokens: jax.Array,  # (B, S_ctx) int32, LEFT-padded
    prompt_valid: jax.Array,  # (B, S_ctx) bool
):
    """Prefill per-row prompts: (next_logits (B, V), trunk, last_pos (B,))."""
    with jax.named_scope("prefill"):
        trunk = make_cache(
            config, prompt_tokens.shape[0], prompt_tokens.shape[1],
            params["embed"].dtype,
        )
        positions = left_pad_positions(prompt_valid)
        hidden, trunk = forward(
            params, config, prompt_tokens, positions, prompt_valid, trunk, 0,
            return_hidden=True,
        )
        next_logits = project_logits(params, config, hidden[:, -1, :])
        return next_logits, trunk, positions[:, -1]


def generate_tokens_segmented(
    params,
    config: ModelConfig,
    prompt_tokens: jax.Array,  # (B, S_ctx) int32, LEFT-padded
    prompt_valid: jax.Array,  # (B, S_ctx) bool
    key: jax.Array,  # (B, 2) per-row PRNG keys
    max_new_tokens: int,
    seg_len: int = 128,
    temperature: float | jax.Array = 1.0,
    top_k: int = 0,
    top_p: float = 1.0,
    eos_ids: Optional[jax.Array] = None,
    logit_bias: Optional[jax.Array] = None,
    bias_table: Optional[jax.Array] = None,
    bias_index: Optional[jax.Array] = None,
    pad_id: int = 0,
    dp_align: int = 1,
    kv_quant: bool = False,
    rep_penalty: Optional[jax.Array] = None,  # (B,) float32; None = off
) -> GenerateOutput:
    """``generate_tokens`` (per-row prompts) as a host loop over segments.

    Same carry-size argument as the shared variant; the per-row trunk stays
    a read-only operand (n_slots=1, n_roles=B) and earlier segments move to
    frozen operands.  Habermas' ranking/critique phases decode long CoT
    budgets from per-agent prompts — the shapes this path serves.
    """
    batch = prompt_tokens.shape[0]
    if config.use_decode_attention:
        # The fused pallas decode-attention kernel has no frozen-operand
        # variant: segment 0 would use the kernel and later segments the
        # einsum path, quietly breaking the token-exact contract.
        raise ValueError(
            "segmented decode is incompatible with use_decode_attention; "
            "use the monolithic decode path instead"
        )
    if max_new_tokens % seg_len:
        raise ValueError(
            f"max_new_tokens={max_new_tokens} must be a multiple of "
            f"seg_len={seg_len} (bucketed widths are)"
        )
    if eos_ids is None:
        eos_ids = jnp.zeros((0,), jnp.int32)
    temperature = jnp.broadcast_to(
        jnp.asarray(temperature, jnp.float32), (batch,)
    )

    next_logits, trunk, last_pos = _prefill_classic(
        params, config, prompt_tokens, prompt_valid
    )
    if kv_quant and config.has_layer_kinds:
        raise LayerKindsUnsupported("an int8 key-value trunk", NEEDS_ONE_KIND)
    if kv_quant:
        # The per-row prompt cache is the dominant per-step read of a
        # classic-layout decode (B rows x ctx columns, re-read every step);
        # it is written once at prefill and read-only after, so int8 halves
        # the read with the same per-(token, head) scale scheme as the
        # frozen blocks.  (The shared-trunk layout skips this: its trunk is
        # ONE row — quantizing it saves ~nothing and would add a program
        # variant.)
        trunk = KVCache(
            k=_quantize_kv(trunk.k),
            v=_quantize_kv(trunk.v),
            key_positions=trunk.key_positions,
            key_valid=trunk.key_valid,
            ssm=trunk.ssm,
        )
    # Bucket-padding dummy rows (no valid prompt tokens) start done —
    # matches generate_tokens' init_done.
    done = ~jnp.any(prompt_valid, axis=1)
    presence = (
        _prompt_presence(prompt_tokens, prompt_valid, config.vocab_size)
        if rep_penalty is not None
        else None
    )
    return _segmented_loop(
        params, config, trunk, last_pos,
        next_logits, key, done,
        n_slots=1, n_roles=batch,
        max_new_tokens=max_new_tokens, seg_len=seg_len,
        temperature=temperature, top_k=top_k, top_p=top_p, eos_ids=eos_ids,
        logit_bias=logit_bias,
        bias_table=bias_table, bias_index=bias_index, pad_id=pad_id,
        dp_align=dp_align, kv_quant=kv_quant,
        presence=presence, rep_penalty=rep_penalty,
    )


@functools.partial(jax.jit, static_argnames=("config", "k", "with_gumbel"))
def next_token_topk(
    params,
    config: ModelConfig,
    prompt_tokens: jax.Array,  # (B, S) LEFT-padded
    prompt_valid: jax.Array,  # (B, S) bool
    keys: jax.Array,  # (B, 2) per-row PRNG keys (Gumbel perturbation)
    k: int,
    temperature: jax.Array,  # (B,) float32
    use_gumbel: jax.Array,  # (B,) bool — False rows take deterministic top-k
    bias_table: Optional[jax.Array] = None,  # (U, V) unique bias vectors
    bias_index: Optional[jax.Array] = None,  # (B,) int32 row -> table index
    with_gumbel: bool = True,  # static: skip (B, V) noise for pure-topk batches
) -> tuple[jax.Array, jax.Array]:
    """Top-k next-token candidates per row, selected ON DEVICE.

    Returns (ids (B, k) int32, logprobs (B, k) float32) — the host transfer
    is O(B·k), never the (B, 256k) logit matrix (VERDICT r1 #6; replaces the
    reference's rejection sampling, beam_search.py:199-333).

    Selection: scores = logprobs / max(temp, eps) + gumbel·use_gumbel; for
    deterministic rows the Gumbel term is zeroed and positive-temperature
    scaling is order-preserving, so top-k by score == top-k by logprob.
    Results come back in SCORE order (Gumbel-top-k = sampling without
    replacement, so a caller wanting fewer candidates takes a prefix);
    logprobs are the true (biased, untempered) log-softmax values.
    """
    positions = left_pad_positions(prompt_valid)
    hidden, _ = forward(
        params, config, prompt_tokens, positions, prompt_valid, return_hidden=True
    )
    logits = project_logits(params, config, hidden[:, -1, :])  # (B, V) f32
    logits = ban_undecodable(logits, config)
    if bias_table is not None:
        logits = logits + bias_table[bias_index]
    logprobs = jax.nn.log_softmax(logits, axis=-1)

    temp = jnp.maximum(temperature, 1e-6)[:, None]
    scores = logprobs / temp
    if with_gumbel:
        gumbel = jax.vmap(lambda kk: jax.random.gumbel(kk, (logits.shape[-1],)))(keys)
        scores = scores + gumbel * use_gumbel[:, None].astype(jnp.float32)
    _, ids = jax.lax.top_k(scores, k)  # (B, k)
    picked = jnp.take_along_axis(logprobs, ids, axis=-1)
    return ids.astype(jnp.int32), picked


@functools.partial(jax.jit, static_argnames=("config",))
def next_token_logits(
    params,
    config: ModelConfig,
    prompt_tokens: jax.Array,  # (B, S) LEFT-padded
    prompt_valid: jax.Array,
) -> jax.Array:
    """Full next-token logit rows (B, V) — one forward, no cache.

    The primitive behind ``Backend.next_token_logprobs``: the reference needed
    up to ``max_sampling_attempts`` API calls to see k distinct next tokens
    (beam_search.py:253-333); on device the whole distribution is free.
    """
    positions = left_pad_positions(prompt_valid)
    hidden, _ = forward(
        params, config, prompt_tokens, positions, prompt_valid, return_hidden=True
    )
    return project_logits(params, config, hidden[:, -1, :])
