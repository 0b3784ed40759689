"""Incremental token-search stepper: shared trunk + per-(slot x role) tails.

The token-level decoders (beam search `src/methods/beam_search.py:408-693`,
finite lookahead, MCTS) need, at every emitted token, (a) k proposed next
tokens from the reference policy and (b) every proposal's logprob under
every agent-conditioned policy.  The reference pays one HTTPS round-trip
per (beam, attempt) and per (beam, token, agent) — 4 000+ s/statement.
Round 1 of this framework batched those into two full-prefix forwards per
step, which is still O(T^2) total FLOPs: every step re-runs the whole
prefix.

This module makes each search step ONE fused device program, O(T) total,
with memory O(prefix + slots x steps) instead of O(slots x prefix):

  - The PREFIX KV cache (prompt + issue + opinions — the bulk) lives ONCE
    per role (role 0 = reference policy, roles 1..A = agent policies: same
    weights, different prompt, the reference's core trick, SURVEY §0) and
    broadcasts against every search slot inside the attention einsums
    (transformer.forward_trunk_tail).
  - Only the <=max_steps-column TAIL of generated tokens is per-(slot x
    role) state; beam reorders gather megabytes of tail, never gigabytes
    of replicated prefix.

  step(parents, token):
    1. gather TAIL rows of surviving parent beams (beams reorder/die),
    2. append the chosen token id to every role-row of its beam,
    3. forward ONE position for all rows over [shared trunk | own tail],
    4. ref rows:   (gumbel-)top-k over biased logits -> k proposals/beam,
    5. agent rows: log-softmax gathered at those k proposal ids.

The same logits serve proposal and scoring — an agent's reward for token c
after sequence s is its next-token logprob at the end of s (reference
`_get_agent_token_logprob`, beam_search.py:335-405), which is exactly what
step t's forward just produced.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from consensus_tpu.models.config import (
    NEEDS_ONE_KIND,
    SEARCH_NEEDS_STATE,
    STREAM_NEEDS_STATE,
    LayerKindsUnsupported,
    ModelConfig,
)
from consensus_tpu.models.generate import left_pad_positions
from consensus_tpu.models.transformer import (
    MOE_TALLY,
    KVCache,
    RecurrentStateUnsupported,
    SSMState,
    _streamed_target_logprobs,
    attention_scope,
    embed_tokens,
    final_norm,
    forward,
    forward_shared_trunk,
    forward_trunk_tail,
    fork_ssm,
    from_kinds,
    by_kind,
    kv_buffers,
    layer_of,
    make_cache,
    make_ssm_state,
    project_logits,
    scan_layers,
    windowed,
)
from consensus_tpu.models.sampling import ban_undecodable, sample_tokens
from consensus_tpu.ops.decode_attention import paged_attention
from consensus_tpu.ops.welfare import (
    DEFAULT_REWARD,
    WELFARE_RULES,
    sanitize_utilities,
)


def refuse_recurrent(config: ModelConfig, what: str, why: str) -> None:
    """Programs that reorder, roll back or page rows by position alone have
    nowhere to keep a recurrent layer's state: they say so by name, when
    they are traced, for a configuration that has one.  The same programs
    gather and build one cache of one shape for every layer: they refuse a
    configuration with layers of more than one kind too, by its own error."""
    if config.has_ssm:
        raise RecurrentStateUnsupported(what, why)
    if config.has_layer_kinds:
        raise LayerKindsUnsupported(what, NEEDS_ONE_KIND)


class SearchState(NamedTuple):
    """Device-resident search state: one shared trunk, per-row tails."""

    trunk: KVCache  # (L, n_roles, W0, KV, hd) — read-only after prefill
    tail_k: jax.Array  # (L, n_slots * n_roles, Ts, KV, hd)
    tail_v: jax.Array
    tail_positions: jax.Array  # (n_slots * n_roles, Ts) int32
    cur_pos: jax.Array  # (n_slots * n_roles,) int32 — last written position


class StepOutput(NamedTuple):
    packed: jax.Array  # (B, k, 2 + A) f32: [id, ref_logprob, agent_logprobs...]
    state: SearchState


def _propose_and_score(
    params,
    config: ModelConfig,
    hidden_last: jax.Array,  # (Rows, D) final-norm hidden of the last position
    n_beams: int,
    n_roles: int,
    base_key: jax.Array,  # (2,) — per-(family, step, slot) keys fold in-device
    step_index: jax.Array,  # () int32
    temperature: jax.Array,  # () f32
    k: int,
    sample: bool,
    ref_bias: Optional[jax.Array],  # (V,) additive bias for ref rows only
    key_family: int = 0,  # disjoint PRNG stream per call family (trunk=0,
    # suffix-tree=1, rollout=2): nested folds keep streams collision-free
    # even when a trunk step index equals a suffix salt.
) -> jax.Array:
    logits = project_logits(params, config, hidden_last)  # (Rows, V) f32
    per_beam = logits.reshape(n_beams, n_roles, -1)
    ref_logits = ban_undecodable(per_beam[:, 0, :], config)  # (B, V)
    if ref_bias is not None:
        ref_logits = ref_logits + ref_bias[None, :]
    ref_lp = jax.nn.log_softmax(ref_logits, axis=-1)

    # Proposal selection mirrors generate.next_token_topk: Gumbel-top-k at
    # temperature == sampling k distinct tokens without replacement;
    # sample=False is deterministic top-k.
    scores = ref_lp / jnp.maximum(temperature, 1e-6)
    if sample:
        step_key = jax.random.fold_in(
            jax.random.fold_in(base_key, key_family), step_index
        )
        slot_keys = jax.vmap(
            lambda slot: jax.random.fold_in(step_key, slot)
        )(jnp.arange(n_beams))
        gumbel = jax.vmap(lambda kk: jax.random.gumbel(kk, ref_lp.shape[-1:]))(
            slot_keys
        )
        scores = scores + gumbel
    _, ids = jax.lax.top_k(scores, k)  # (B, k)
    ref_picked = jnp.take_along_axis(ref_lp, ids, axis=-1)

    agent_lp = jax.nn.log_softmax(per_beam[:, 1:, :], axis=-1)  # (B, A, V)
    agent_picked = jnp.take_along_axis(
        agent_lp, jnp.broadcast_to(ids[:, None, :], agent_lp.shape[:2] + (k,)), axis=-1
    )
    # Pack into ONE f32 array so the host needs a single device fetch per
    # step (ids are exact in f32 up to 2^24 >> any vocab).
    return jnp.concatenate(
        [
            ids.astype(jnp.float32)[..., None],
            ref_picked[..., None],
            jnp.moveaxis(agent_picked, 1, 2),  # (B, k, A)
        ],
        axis=-1,
    )


def _scratch_cache(
    state: SearchState, t_filled: jax.Array, extra: int
) -> Tuple[KVCache, jax.Array]:
    """Materialize [trunk | tail | extra zero columns] as one KVCache for the
    n_slots=1 (trunk-session) read paths — tree expansion and rollouts.
    Tail columns >= ``t_filled`` are masked invalid.  Returns the cache and
    the column index where new writes should land (W0 + t_filled)."""
    trunk, tail_k = state.trunk, state.tail_k
    layers, rows = tail_k.shape[0], tail_k.shape[1]
    t_tail = tail_k.shape[2]
    pad_kv = ((0, 0), (0, 0), (0, extra), (0, 0), (0, 0))
    cache = KVCache(
        k=jnp.pad(jnp.concatenate([trunk.k, tail_k], axis=2), pad_kv),
        v=jnp.pad(jnp.concatenate([trunk.v, state.tail_v], axis=2), pad_kv),
        key_positions=jnp.pad(
            jnp.concatenate(
                [trunk.key_positions, state.tail_positions], axis=1
            ),
            ((0, 0), (0, extra)),
        ),
        key_valid=jnp.pad(
            jnp.concatenate(
                [
                    trunk.key_valid,
                    jnp.broadcast_to(
                        jnp.arange(t_tail)[None, :] < t_filled,
                        (rows, t_tail),
                    ),
                ],
                axis=1,
            ),
            ((0, 0), (0, extra)),
        ),
    )
    return cache, trunk.k.shape[2] + t_filled


@functools.partial(
    jax.jit, static_argnames=("config", "n_beams", "n_roles", "k", "sample", "max_steps")
)
def search_prefill(
    params,
    config: ModelConfig,
    prefix_tokens: jax.Array,  # (n_roles, W0) int32, LEFT-padded
    prefix_valid: jax.Array,  # (n_roles, W0) bool
    n_beams: int,
    n_roles: int,
    base_key: jax.Array,  # (2,)
    temperature: jax.Array,
    k: int,
    sample: bool,
    max_steps: int,
    ref_bias: Optional[jax.Array] = None,
) -> StepOutput:
    """Prefill the (ref + agents) prefixes ONCE into the shared trunk,
    allocate empty per-(slot x role) tails, and return the root proposals
    (every slot starts identical)."""
    refuse_recurrent(config, "search_prefill", SEARCH_NEEDS_STATE)
    w0 = prefix_tokens.shape[1]
    c = config
    positions = left_pad_positions(prefix_valid)
    trunk = make_cache(config, n_roles, w0, params["embed"].dtype)
    hidden, trunk = forward(
        params, config, prefix_tokens, positions, prefix_valid, trunk, 0,
        return_hidden=True,
    )

    rows = n_beams * n_roles
    state = SearchState(
        trunk=trunk,
        tail_k=jnp.zeros(
            (c.n_layers, rows, max_steps, c.n_kv_heads, c.head_dim),
            params["embed"].dtype,
        ),
        tail_v=jnp.zeros(
            (c.n_layers, rows, max_steps, c.n_kv_heads, c.head_dim),
            params["embed"].dtype,
        ),
        tail_positions=jnp.zeros((rows, max_steps), jnp.int32),
        cur_pos=jnp.tile(positions[:, -1], (n_beams,)),
    )
    hidden_last = jnp.tile(hidden[:, -1, :], (n_beams, 1))

    packed = _propose_and_score(
        params, config, hidden_last, n_beams, n_roles, base_key,
        jnp.asarray(0, jnp.int32), temperature, k, sample, ref_bias,
    )
    return StepOutput(packed, state)


@functools.partial(
    jax.jit,
    static_argnames=("config", "n_beams", "n_roles", "k", "sample"),
    # Donate the tail buffers — megabytes, and replaced every step.
    donate_argnums=(2,),
)
def search_step(
    params,
    config: ModelConfig,
    state: SearchState,
    advance: jax.Array,  # (2, B) int32: row 0 = parent beam, row 1 = token id
    step_meta: jax.Array,  # (2,) int32: [step_index (1-based), write_col]
    n_beams: int,
    n_roles: int,
    base_key: jax.Array,  # (2,)
    temperature: jax.Array,
    k: int,
    sample: bool,
    ref_bias: Optional[jax.Array] = None,
) -> StepOutput:
    """Advance every beam slot from its parent by one token; propose + score.
    Only the per-row TAILS are gathered on beam reorders — the shared trunk
    is untouched."""
    refuse_recurrent(config, "search_step", SEARCH_NEEDS_STATE)
    parents, tokens = advance[0], advance[1]
    step_index, write_col = step_meta[0], step_meta[1]
    rows = jnp.arange(n_beams * n_roles)
    parent_rows = parents[rows // n_roles] * n_roles + (rows % n_roles)

    tail_k = state.tail_k[:, parent_rows]
    tail_v = state.tail_v[:, parent_rows]
    tail_positions = state.tail_positions[parent_rows]
    cur_pos = state.cur_pos[parent_rows] + 1
    row_tokens = tokens[rows // n_roles]  # same token for every role of a beam

    tail_positions = jax.lax.dynamic_update_slice(
        tail_positions, cur_pos[:, None], (0, write_col)
    )
    hidden, tail_k, tail_v, _ = forward_trunk_tail(
        params, config, row_tokens, cur_pos,
        state.trunk, tail_k, tail_v, tail_positions, write_col,
        n_beams, n_roles,
    )
    packed = _propose_and_score(
        params, config, hidden, n_beams, n_roles, base_key,
        step_index, temperature, k, sample, ref_bias,
    )
    new_state = SearchState(
        trunk=state.trunk,
        tail_k=tail_k,
        tail_v=tail_v,
        tail_positions=tail_positions,
        cur_pos=cur_pos,
    )
    return StepOutput(packed, new_state)


@functools.partial(
    jax.jit, static_argnames=("config", "n_roles", "k", "sample")
)
def suffix_propose(
    params,
    config: ModelConfig,
    state: SearchState,  # n_slots=1 trunk session (NOT consumed)
    t_filled: jax.Array,  # () int32 — tail columns already generated
    suffix_tokens: jax.Array,  # (P, L) int32 — one row per frontier path
    salt: jax.Array,  # () int32 — folds into per-path proposal keys
    n_roles: int,
    base_key: jax.Array,  # (2,)
    temperature: jax.Array,
    k: int,
    sample: bool,
    ref_bias: Optional[jax.Array] = None,
) -> jax.Array:
    """Propose + score k next tokens for every tree path over the SHARED
    trunk+tail cache (models/transformer.py:forward_shared_trunk).  Returns
    the packed (P, k, 2 + A) candidate array; the session state is
    untouched, so a lookahead tree costs one call per LEVEL and zero cache
    duplication."""
    refuse_recurrent(config, "suffix_propose", SEARCH_NEEDS_STATE)
    n_paths = suffix_tokens.shape[0]
    cache, _ = _scratch_cache(state, t_filled, extra=0)
    hidden = forward_shared_trunk(
        params, config, suffix_tokens, cache, state.cur_pos
    )
    return _propose_and_score(
        params, config, hidden.reshape(n_paths * n_roles, -1),
        n_paths, n_roles, base_key, salt, temperature, k, sample, ref_bias,
        key_family=1,
    )


@functools.partial(
    jax.jit,
    static_argnames=("config", "n_roles", "suffix_len", "depth"),
)
def rollout_scored(
    params,
    config: ModelConfig,
    state: SearchState,  # n_slots=1 trunk session (NOT consumed)
    t_filled: jax.Array,  # () int32
    suffix_tokens: jax.Array,  # (suffix_len,) int32 — the node's path
    salt: jax.Array,  # () int32
    n_roles: int,
    suffix_len: int,
    depth: int,
    base_key: jax.Array,  # (2,)
    temperature: jax.Array,
    eos_ids: jax.Array,  # (E,) int32
) -> jax.Array:
    """MCTS rollout valued in ONE device call: continue ``depth`` tokens from
    the reference policy past trunk+tail+suffix, scoring each sampled token
    under every agent from the same logits.  Returns packed (depth, 2 + A)
    f32 rows [token_id, counted, agent_logprobs...]; ``counted`` is 0 from
    the first EOS on (matching generate()'s EOS-excluded text).  The session
    state is copied into a widened scratch, so it stays untouched.  Replaces
    the reference's rollout + per-agent full-statement scoring
    (mcts.py:470-651) — the call that its own NameError bug aborts."""
    refuse_recurrent(config, "rollout_scored", SEARCH_NEEDS_STATE)
    scratch, write_index = _scratch_cache(
        state, t_filled, extra=suffix_len + depth
    )
    cur_pos = state.cur_pos

    tokens = jnp.tile(suffix_tokens[None, :], (n_roles, 1))
    positions = cur_pos[:, None] + 1 + jnp.arange(suffix_len)[None, :]
    logits, scratch = forward(
        params, config, tokens, positions,
        jnp.ones((n_roles, suffix_len), jnp.bool_), scratch, write_index,
    )
    rollout_key = jax.random.fold_in(jax.random.fold_in(base_key, 2), salt)

    def step(carry, t):
        logits_last, cache_t, pos, done = carry
        lp = jax.nn.log_softmax(logits_last.astype(jnp.float32), axis=-1)
        key = jax.random.fold_in(rollout_key, t)
        ref_lp = ban_undecodable(lp[0], config)
        sampled = jax.random.categorical(
            key, ref_lp / jnp.maximum(temperature, 1e-6)
        )
        token = jnp.where(temperature <= 0.0, jnp.argmax(ref_lp), sampled)
        is_eos = (
            jnp.any(token == eos_ids)
            if eos_ids.shape[0]
            else jnp.asarray(False)
        )
        counted = ~done & ~is_eos
        agent_lps = lp[1:, token]  # (A,)
        new_done = done | is_eos

        pos = pos + 1
        step_logits, new_cache = forward(
            params, config,
            jnp.full((n_roles, 1), token, jnp.int32),
            pos[:, None],
            jnp.broadcast_to(~done, (n_roles,))[:, None],
            cache_t,
            write_index + suffix_len + t,
        )
        out_row = jnp.concatenate(
            [
                token.astype(jnp.float32)[None],
                counted.astype(jnp.float32)[None],
                jnp.where(counted, agent_lps, 0.0),
            ]
        )
        return (step_logits[:, -1, :], new_cache, pos, new_done), out_row

    init = (
        logits[:, -1, :],
        scratch,
        positions[:, -1],
        jnp.asarray(False),
    )
    _, rows = jax.lax.scan(step, init, jnp.arange(depth))
    return rows  # (depth, 2 + A)


@functools.partial(
    jax.jit,
    static_argnames=("config", "n_roles", "suffix_len", "depth", "mesh"),
)
def rollout_scored_many(
    params,
    config: ModelConfig,
    state: SearchState,  # n_slots=1 trunk session (NOT consumed)
    t_filled: jax.Array,  # () int32
    suffix_tokens: jax.Array,  # (P, suffix_len) int32 — one row per path
    salts: jax.Array,  # (P,) int32 — one rollout PRNG salt per path
    n_roles: int,
    suffix_len: int,
    depth: int,
    base_key: jax.Array,  # (2,)
    temperature: jax.Array,
    eos_ids: jax.Array,  # (E,) int32
    mesh: Optional[Mesh] = None,  # static: shard rollout paths over data
) -> jax.Array:
    """A whole WAVE of MCTS rollouts in ONE device call: ``P`` equal-length
    tree paths each continue ``depth`` reference-policy tokens past
    trunk+tail+suffix, scoring every sampled token under every agent from
    the same logits.  Returns packed (P, depth, 2 + A) f32 rows
    [token_id, counted, agent_logprobs...] per path.

    Data flow: the suffixes prefill over the SHARED scratch trunk in one
    ``forward_shared_trunk`` pass whose per-layer roped keys/values seed
    per-(path x role) decode tails (width suffix_len + depth); the rollout
    loop then runs ``forward_trunk_tail`` with n_slots=P — the trunk stays
    one copy per role, so per-path HBM is just the narrow tail.  Per-path
    keys fold (family 2, salts[p]), making path p's token stream identical
    to a singleton ``rollout_scored`` call with the same salt modulo
    post-EOS cache writes (rollout_scored stops writing after EOS; here
    done paths keep writing uncounted tokens that only their own uncounted
    steps ever attend).  The einsum attention path is forced because the
    scratch trunk has interior invalid columns (see forward_trunk_tail).
    """
    refuse_recurrent(config, "rollout_scored_many", SEARCH_NEEDS_STATE)
    c = config
    n_paths = suffix_tokens.shape[0]
    rows = n_paths * n_roles
    suffix_tokens = _constrain(suffix_tokens, mesh, "data", None)
    salts = _constrain(salts, mesh, "data")
    scratch, _ = _scratch_cache(state, t_filled, extra=0)
    hidden, suf_k, suf_v = forward_shared_trunk(
        params, config, suffix_tokens, scratch, state.cur_pos,
        return_suffix_kv=True,
    )  # hidden (P, R, D); suf_k/v (L, P, R, suffix_len, KV, hd)

    pad = ((0, 0), (0, 0), (0, depth), (0, 0), (0, 0))
    tail_k = jnp.pad(
        suf_k.reshape(c.n_layers, rows, suffix_len, c.n_kv_heads, c.head_dim),
        pad,
    )
    tail_v = jnp.pad(
        suf_v.reshape(c.n_layers, rows, suffix_len, c.n_kv_heads, c.head_dim),
        pad,
    )
    suffix_pos = state.cur_pos[:, None] + 1 + jnp.arange(suffix_len)[None, :]
    tail_positions = jnp.pad(
        jnp.tile(suffix_pos, (n_paths, 1)), ((0, 0), (0, depth))
    )  # (rows, suffix_len + depth)
    pos0 = jnp.tile(state.cur_pos, (n_paths,)) + suffix_len  # last written
    rollout_keys = jax.vmap(
        lambda s: jax.random.fold_in(jax.random.fold_in(base_key, 2), s)
    )(salts)  # (P, 2)

    def step(carry, t):
        hidden_last, k_tail, v_tail, kp_tail, pos, done = carry
        logits = project_logits(params, config, hidden_last)  # (rows, V) f32
        lp = jax.nn.log_softmax(
            logits.reshape(n_paths, n_roles, -1).astype(jnp.float32), axis=-1
        )
        keys = jax.vmap(lambda kk: jax.random.fold_in(kk, t))(rollout_keys)
        ref_lp = ban_undecodable(lp[:, 0, :], config)
        sampled = jax.vmap(jax.random.categorical)(
            keys, ref_lp / jnp.maximum(temperature, 1e-6)
        )
        token = jnp.where(
            temperature <= 0.0, jnp.argmax(ref_lp, axis=-1), sampled
        ).astype(jnp.int32)  # (P,)
        is_eos = (
            jnp.any(token[:, None] == eos_ids[None, :], axis=-1)
            if eos_ids.shape[0]
            else jnp.zeros((n_paths,), bool)
        )
        counted = ~done & ~is_eos  # (P,)
        agent_lps = jnp.take_along_axis(
            lp[:, 1:, :],
            jnp.broadcast_to(
                token[:, None, None], (n_paths, n_roles - 1, 1)
            ),
            axis=-1,
        )[..., 0]  # (P, A)
        new_done = done | is_eos

        pos = pos + 1
        write_col = suffix_len + t
        kp_tail = jax.lax.dynamic_update_slice(
            kp_tail, pos[:, None], (0, write_col)
        )
        row_tokens = jnp.repeat(token, n_roles)  # path-major (rows,)
        hidden2, k_tail, v_tail, _ = forward_trunk_tail(
            params, config, row_tokens, pos,
            scratch, k_tail, v_tail, kp_tail, write_col,
            n_paths, n_roles,
            use_decode_kernel=False,
        )
        out = jnp.concatenate(
            [
                token.astype(jnp.float32)[:, None],
                counted.astype(jnp.float32)[:, None],
                jnp.where(counted[:, None], agent_lps, 0.0),
            ],
            axis=1,
        )  # (P, 2 + A)
        return (hidden2, k_tail, v_tail, kp_tail, pos, new_done), out

    init = (
        hidden.reshape(rows, -1),
        tail_k,
        tail_v,
        tail_positions,
        pos0,
        jnp.zeros((n_paths,), bool),
    )
    _, out_rows = jax.lax.scan(step, init, jnp.arange(depth))
    return jnp.moveaxis(out_rows, 0, 1)  # (P, depth, 2 + A)


@functools.partial(
    jax.jit,
    static_argnames=("config", "n_roles", "suffix_len", "depth", "mesh"),
)
def rollout_verify_many(
    params,
    config: ModelConfig,
    state: SearchState,  # n_slots=1 trunk session (NOT consumed)
    t_filled: jax.Array,  # () int32
    suffix_tokens: jax.Array,  # (P, suffix_len) int32 — one row per path
    draft_tokens: jax.Array,  # (P, depth) int32 — teacher-forced drafts
    salts: jax.Array,  # (P,) int32 — SAME salts rollout_scored_many takes
    n_roles: int,
    suffix_len: int,
    depth: int,
    base_key: jax.Array,  # (2,)
    temperature: jax.Array,
    eos_ids: jax.Array,  # (E,) int32
    mesh: Optional[Mesh] = None,  # static: shard verify paths over data
) -> jax.Array:
    """Speculative verification of whole rollout drafts in ONE parallel
    forward (Leviathan et al.: draft cheap, verify wide).  Teacher-forces
    each path's ``depth``-token draft past trunk+tail+suffix via a single
    ``forward_shared_trunk`` pass over [suffix ++ draft] and replays the
    EXACT per-step sampling decisions of :func:`rollout_scored_many`: the
    choice at rollout step ``t`` reads hidden column ``suffix_len - 1 + t``
    (conditioned on ``draft[:t]``), folds the same (family-2, salt, t)
    PRNG key, and applies the same f32 log-softmax + categorical/argmax.

    Returns packed (P, depth, 2 + A) f32 rows
    [chosen_token, is_eos, agent_logprobs_of_chosen...].  Row ``t`` is
    valid iff ``draft[:t]`` matches the chosen tokens before it — the host
    accepts the longest matched prefix plus the first correction (standard
    rejection), so accepted token STREAMS replay the sequential scan
    exactly: position ``t`` attends the same trunk/suffix entries in the
    same order (later draft columns are masked to exactly-zero softmax
    terms — the argument rollout_many == rollout_from already leans on)
    and folds the identical PRNG key, so the categorical/argmax decision
    agrees everywhere the logits aren't ulp-tied.  Agent logprob TOTALS
    carry float-tolerance wiggle (~1e-6): the one-pass verify projects
    logits at a different matmul shape than the step-by-step scan, so row
    reductions tile differently.  Same contract the batched rollout tests
    already pin (exact ids, allclose totals) — re-pinned for this program
    on tiny models in tests/test_speculative.py.  The session state is
    untouched."""
    refuse_recurrent(config, "rollout_verify_many", SEARCH_NEEDS_STATE)
    n_paths = suffix_tokens.shape[0]
    suffix_tokens = _constrain(suffix_tokens, mesh, "data", None)
    draft_tokens = _constrain(draft_tokens, mesh, "data", None)
    salts = _constrain(salts, mesh, "data")
    scratch, _ = _scratch_cache(state, t_filled, extra=0)
    ext = jnp.concatenate([suffix_tokens, draft_tokens], axis=1)
    hidden = forward_shared_trunk(
        params, config, ext, scratch, state.cur_pos,
        return_all_positions=True,
    )  # (P, R, suffix_len + depth, D)
    h = jax.lax.dynamic_slice_in_dim(hidden, suffix_len - 1, depth, axis=2)
    logits = project_logits(
        params, config, h.reshape(n_paths * n_roles * depth, -1)
    ).reshape(n_paths, n_roles, depth, -1)
    lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    rollout_keys = jax.vmap(
        lambda s: jax.random.fold_in(jax.random.fold_in(base_key, 2), s)
    )(salts)  # (P, 2)
    keys = jax.vmap(
        lambda kk: jax.vmap(lambda t: jax.random.fold_in(kk, t))(
            jnp.arange(depth)
        )
    )(rollout_keys)  # (P, depth, 2)
    ref_lp = ban_undecodable(lp[:, 0, :, :], config)  # (P, depth, V)
    sampled = jax.vmap(jax.vmap(jax.random.categorical))(
        keys, ref_lp / jnp.maximum(temperature, 1e-6)
    )
    token = jnp.where(
        temperature <= 0.0, jnp.argmax(ref_lp, axis=-1), sampled
    ).astype(jnp.int32)  # (P, depth)
    is_eos = (
        jnp.any(token[..., None] == eos_ids[None, None, :], axis=-1)
        if eos_ids.shape[0]
        else jnp.zeros((n_paths, depth), bool)
    )
    agent_lps = jnp.take_along_axis(
        lp[:, 1:, :, :],
        jnp.broadcast_to(
            token[:, None, :, None], (n_paths, n_roles - 1, depth, 1)
        ),
        axis=-1,
    )[..., 0]  # (P, A, depth)
    return jnp.concatenate(
        [
            token.astype(jnp.float32)[..., None],
            is_eos.astype(jnp.float32)[..., None],
            jnp.moveaxis(agent_lps, 1, 2),
        ],
        axis=-1,
    )  # (P, depth, 2 + A)


# ---------------------------------------------------------------------------
# Paged slot programs (continuous-batching engine)
# ---------------------------------------------------------------------------
#
# The decode engine (backends/engine.py) holds every resident request's KV
# in fixed-size pages (ops/kv_pages.py); the two programs below are the
# engine's device-side primitives, both compiled to ONE fixed shape per
# (n_slots, chunk, max_blocks, num_pages) — a slot's ACTUAL length only
# enters as data (block tables, lengths, write cursors), never as a shape,
# so ragged-length serving load causes zero recompiles.
#
# Page arrays carry one extra SINK page at index num_pages: inactive slots
# and invalid chunk columns write their K/V there (scatter needs somewhere
# to land under fixed shapes), and nothing ever reads it — block tables
# only name pool pages 0..num_pages-1.


class PagedSlotState(NamedTuple):
    """Device page pool: K/V for every resident slot, owned by block tables
    host-side.  Shape (L, num_pages + 1, page_size, KV, hd); the final page
    is the write sink.

    ``ssm``: the other kind of state, for a configuration with recurrent
    layers: one state a row (``transformer.SSMState``, (L, rows, ...)),
    whatever the row's length.  The chunked prefill carries each row's state
    from chunk to chunk here; the score chunk reads the same table as its
    rows' snapshots (``ssm_rows``) and hands it back untouched."""

    #: With layers of more than one kind, a pool a kind of attention:
    #: ``{"full": (its layers, pages + 1, page, its KV, hd), "window": ...}``
    #: (values at the value heads' width), one table of page ids for all.  A
    #: latent kind's pool is one buffer: ``k_pages["latent"]`` is (its layers,
    #: pages + 1, page, 1, latent + rotary key) and ``v_pages["latent"]`` is
    #: None, not a second copy.
    k_pages: jax.Array
    v_pages: jax.Array
    ssm: Optional[SSMState] = None
    #: With routed experts: the routed layers' tally over the programs run
    #: on this state so far (int32 ``transformer.MOE_TALLY``).
    moe_held: Optional[jax.Array] = None


def _constrain(x: jax.Array, mesh: Optional[Mesh], *axes) -> jax.Array:
    """``with_sharding_constraint`` under a ``(data, model)`` mesh; identity
    when no mesh is in play.  A mesh axis is silently dropped for any array
    dim it does not divide (e.g. kv-heads < tp, or a slot count that is not
    a multiple of dp) — the dim stays replicated rather than erroring, so
    one program text serves every (dp, tp) width.  Axis names are string
    literals ("data"/"model") to keep this module import-cycle-free from
    ``consensus_tpu.parallel``."""
    if mesh is None:
        return x
    resolved = tuple(
        axis if axis is not None and dim % mesh.shape[axis] == 0 else None
        for dim, axis in zip(x.shape, axes)
    )
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, PartitionSpec(*resolved))
    )


def _constrain_state(
    state: PagedSlotState, mesh: Optional[Mesh]
) -> PagedSlotState:
    """Page pool sharding: kv-head axis over ``model`` (Megatron attention
    shards heads, so each model shard holds its own heads' pages), all other
    axes replicated — pages are addressed by slot block tables host-side,
    never by a device axis."""
    if mesh is None:
        return state
    return state._replace(
        k_pages=_constrain(state.k_pages, mesh, None, None, None, "model", None),
        v_pages=_constrain(state.v_pages, mesh, None, None, None, "model", None),
    )


def make_page_state(
    config: ModelConfig,
    num_pages: int,
    page_size: int,
    dtype=jnp.float32,
    mesh: Optional[Mesh] = None,
    ssm_rows: int = 0,
) -> PagedSlotState:
    """``ssm_rows``: rows of zero recurrent state to hold beside the pages
    (one a context the pool's prefill will run); a configuration without
    recurrent layers holds none whatever is asked."""
    c = config
    state = PagedSlotState(
        *kv_buffers(c, (num_pages + 1, page_size), dtype),
        make_ssm_state(c, ssm_rows, dtype) if ssm_rows else None,
        jnp.zeros((len(MOE_TALLY),), jnp.int32) if c.has_moe else None,
    )
    if mesh is not None:
        if c.has_layer_kinds:
            raise LayerKindsUnsupported("a mesh of several chips", NEEDS_ONE_KIND)
        kv_axis = "model" if c.n_kv_heads % mesh.shape["model"] == 0 else None
        sharding = NamedSharding(
            mesh, PartitionSpec(None, None, None, kv_axis, None)
        )
        state = PagedSlotState(
            jax.device_put(state.k_pages, sharding),
            jax.device_put(state.v_pages, sharding),
            state.ssm,
        )
    return state


def _paged_forward(
    params,
    c: ModelConfig,
    tokens: jax.Array,  # (B, S) int32
    positions: jax.Array,  # (B, S) int32
    state: PagedSlotState,
    block_tables: jax.Array,  # (B, max_blocks) int32, -1 padded
    lengths: jax.Array,  # (B,) int32 — INCLUDING this call's tokens
    write_pages: jax.Array,  # (B, S) int32 — sink for invalid columns
    write_offsets: jax.Array,  # (B, S) int32
    valid: Optional[jax.Array] = None,  # (B, S) bool — real columns
    ssm: Optional[SSMState] = None,  # (L, B, ...) the rows' recurrent state
):
    """Shared body of chunked prefill and the decode step: write this
    call's K/V into the pages the cursors name, then attend every query
    through its slot's block table.  Returns (hidden (B, S, D), state); the
    state's ``ssm`` is the rows' recurrent state after their ``valid``
    columns (a configuration with recurrent layers has to be handed both)."""
    if c.has_ssm and (ssm is None or valid is None):
        raise RecurrentStateUnsupported(
            "this paged program", "its rows carry pages and no recurrent state")
    x = embed_tokens(params, c, tokens)

    # The pools ride the layer loop's carry whole, (L, pages + 1, page, KV,
    # hd), and a layer addresses its pages by its index: the layer and page
    # axes read as one axis of L * (pages + 1) pages, layer ``l``'s page
    # ``p`` at ``l * (pages + 1) + p``.  One scatter over (layer, page) in
    # place; no layer's pool is stacked back.  (With layers of more than one
    # kind: a pool a kind of attention, each with its own layers leading and
    # the same pages; a layer's index is its place in its kind's pool.)
    pages_a_layer = jax.tree.leaves(state.k_pages)[0].shape[1]
    # Rows that share context pages gather each of them many times: more
    # pages gathered than a layer's pool holds.  On a v5e the gather reads
    # them twice as fast from a copy of the layer's pool as from inside the
    # carried buffer (21.8 against 43.6 ms over the 24 layers of a 64-row
    # score chunk, PERF.md 6, PR 31), which pays for the copy; a prefill's
    # few rows gather less than the pool holds, and read it where it lies.
    gather_from_a_copy = block_tables.size > pages_a_layer

    def as_pages(pool):
        return pool.reshape((-1,) + pool.shape[2:])

    def call_paged(window, q, k_pages, v_pages, layer, sink=None, latent=None):
        if gather_from_a_copy:
            pools = [None if pool is None else layer_of(pool, layer)
                     for pool in (k_pages, v_pages)]
            tables = block_tables
        else:
            pools = [None if pool is None else as_pages(pool)
                     for pool in (k_pages, v_pages)]
            tables = jnp.maximum(block_tables, 0) + layer * pages_a_layer
        own = {} if sink is None else {"sink": sink}
        if latent is not None:
            own["latent"] = latent
        return paged_attention(
            q, *pools, tables, lengths, positions,
            scale=c.q_scale, softcap=c.attn_softcap, window=window, **own)

    def attend(q, k, v, _, pools, layer, is_local, sink=None, latent=None):
        """This call's K/V scattered into the pages the cursors name; every
        query attends through its slot's block table.  (A latent layer's
        pool is one buffer, its value side None: ``latent`` makes keys and
        values of the pages gathered.)"""
        # Cursor pairs are unique across rows (slots own disjoint pages)
        # except the sink, which is never read, so duplicate-index order
        # doesn't matter.
        with jax.named_scope("kv_write"):
            at = write_pages + layer * pages_a_layer
            k_pages, v_pages = (
                None if pool is None else
                as_pages(pool).at[at, write_offsets].set(new).reshape(pool.shape)
                for pool, new in zip(pools, (k, v)))
        with attention_scope(is_local, latent):
            if latent is not None:  # no window, no sink: nothing to choose
                attn = call_paged(None, q, k_pages, None, layer, latent=latent)
            else:
                attn = windowed(
                    c, is_local, call_paged, q, k_pages, v_pages, layer,
                    *(() if sink is None else (sink,)))
        return attn, None, (k_pages, v_pages)

    x, held, written, new_ssm = scan_layers(
        params, c, x, positions, attend, None,
        by_kind(c, state.k_pages, state.v_pages),
        ssm if c.has_ssm else None, valid)
    new_k, new_v = from_kinds(c, written)
    moe_held = None if state.moe_held is None else state.moe_held + held
    return final_norm(params, c, x), PagedSlotState(
        new_k, new_v, new_ssm, moe_held)


@functools.partial(
    jax.jit, static_argnames=("config", "mesh"), donate_argnums=(4,)
)
def paged_prefill_chunk(
    params,
    config: ModelConfig,
    tokens: jax.Array,  # (B, C) int32 — one prompt chunk per slot
    chunk_valid: jax.Array,  # (B, C) bool — real tokens of this chunk
    state: PagedSlotState,
    block_tables: jax.Array,  # (B, max_blocks)
    lengths: jax.Array,  # (B,) int32 — stream length AFTER this chunk
    write_pages: jax.Array,  # (B, C)
    write_offsets: jax.Array,  # (B, C)
    mesh: Optional[Mesh] = None,  # static: shard slots over data, KV over model
) -> Tuple[jax.Array, PagedSlotState]:
    """Ingest one prompt chunk per slot into the page pool.

    Chunk token j of slot b sits at stream position lengths[b] - valid_count
    + j, attending everything the slot already holds plus the chunk's own
    earlier tokens — so a prompt prefills in ceil(W / C) fixed-shape calls
    interleaved between decode iterations instead of one W-bucketed
    program.  Returns the final-norm hidden of each slot's LAST valid chunk
    position (B, D) — callers project logits only when the prompt is
    complete — and the updated page state.

    With recurrent layers ``state.ssm`` holds one state a row of this call:
    each row's goes in at its stream position ``lengths - valid_count`` and
    comes out after its valid columns, so a prompt's chunks hand it on, and a
    row with no valid column keeps what it has.
    """
    b, chunk = tokens.shape
    tokens = _constrain(tokens, mesh, "data", None)
    chunk_valid = _constrain(chunk_valid, mesh, "data", None)
    block_tables = _constrain(block_tables, mesh, "data", None)
    lengths = _constrain(lengths, mesh, "data")
    write_pages = _constrain(write_pages, mesh, "data", None)
    write_offsets = _constrain(write_offsets, mesh, "data", None)
    state = _constrain_state(state, mesh)
    n_valid = jnp.sum(chunk_valid.astype(jnp.int32), axis=1)  # (B,)
    start = lengths - n_valid
    positions = start[:, None] + jnp.arange(chunk, dtype=jnp.int32)[None, :]
    hidden, state = _paged_forward(
        params, config, tokens, positions, state,
        block_tables, lengths, write_pages, write_offsets,
        valid=chunk_valid, ssm=state.ssm,
    )
    state = _constrain_state(state, mesh)
    last = jnp.maximum(n_valid - 1, 0)
    hidden_last = jnp.take_along_axis(
        hidden, last[:, None, None], axis=1
    )[:, 0, :]
    return _constrain(hidden_last, mesh, "data", None), state


@functools.partial(
    jax.jit, static_argnames=("config", "mesh"), donate_argnums=(3,)
)
def paged_decode_step(
    params,
    config: ModelConfig,
    tokens: jax.Array,  # (B,) int32 — one token per slot
    state: PagedSlotState,
    block_tables: jax.Array,  # (B, max_blocks)
    lengths: jax.Array,  # (B,) int32 — stream length INCLUDING this token
    write_pages: jax.Array,  # (B,) int32 — sink page for inactive slots
    write_offsets: jax.Array,  # (B,) int32
    mesh: Optional[Mesh] = None,  # static: shard slots over data, KV over model
) -> Tuple[jax.Array, PagedSlotState]:
    """One decode iteration for the whole slot table: every active slot
    advances one position, reading K/V through its own block table.  One
    compiled shape regardless of slot lengths.  Returns (logits (B, V)
    f32, updated page state); under a mesh the logits come out sharded
    (slots over ``data``, vocab over ``model`` — the embedding's row shards
    produce vocab-sharded logits and argmax reductions ride ICI)."""
    refuse_recurrent(config, "paged_decode_step", STREAM_NEEDS_STATE)
    tokens = _constrain(tokens, mesh, "data")
    block_tables = _constrain(block_tables, mesh, "data", None)
    lengths = _constrain(lengths, mesh, "data")
    write_pages = _constrain(write_pages, mesh, "data")
    write_offsets = _constrain(write_offsets, mesh, "data")
    state = _constrain_state(state, mesh)
    positions = (lengths - 1)[:, None]
    hidden, state = _paged_forward(
        params, config, tokens[:, None], positions, state,
        block_tables, lengths, write_pages[:, None], write_offsets[:, None],
    )
    state = _constrain_state(state, mesh)
    logits = project_logits(params, config, hidden[:, 0, :])
    return _constrain(logits, mesh, "data", "model"), state


@functools.partial(
    jax.jit,
    static_argnames=("config", "num_steps", "top_k", "top_p", "pad_id", "mesh"),
    donate_argnums=(3,),
)
def paged_decode_steps(
    params,
    config: ModelConfig,
    logits: jax.Array,  # (B, V) f32 — sampling logits carried IN (prefill out)
    state: PagedSlotState,
    block_tables: jax.Array,  # (B, max_blocks) int32, -1 padded
    lengths: jax.Array,  # (B,) int32 — tokens WRITTEN so far (excl. this window)
    keys: jax.Array,  # (B, 2) per-row PRNG keys
    done: jax.Array,  # (B,) bool — frozen rows (EOS'd / budget-spent / pads)
    budgets: jax.Array,  # (B,) int32 — remaining emit budget (max_tokens left)
    hit_eos: jax.Array,  # (B,) bool — row sampled EOS within budget
    temperature: jax.Array,  # (B,) float32 (or scalar)
    eos_ids: Optional[jax.Array] = None,  # (E,) int32
    num_steps: int = 1,
    top_k: int = 0,
    top_p: float = 1.0,
    logit_bias: Optional[jax.Array] = None,  # (V,) or (B, V) additive
    bias_table: Optional[jax.Array] = None,
    bias_index: Optional[jax.Array] = None,
    pad_id: int = 0,
    presence: Optional[jax.Array] = None,  # (B, V) bool seen-token mask
    rep_penalty: Optional[jax.Array] = None,  # (B,) float32
    mesh: Optional[Mesh] = None,  # static: slots over data, KV/vocab over model
):
    """Decode up to ``num_steps`` tokens per slot in ONE dispatch.

    A ``lax.scan`` over ``paged_decode_step``'s body: each step samples from
    the carried logits with the SAME per-row key-split schedule as the
    sequential loops (``_decode_segment`` splits every row's key once per
    step, done rows included, and a row's t-th emitted token is always drawn
    from its t-th split — so K=8 is byte-identical to K=1 and to the dense
    paths, up to forward numerics), then advances one position through the
    paged forward.

    Early exit is a MASK, not a loop break: a row freezes when it samples
    EOS or when its budget was already spent at step start.  Frozen rows
    keep splitting keys (schedule replay), sample pad ids, write K/V only to
    the sink page, and stop advancing ``lengths`` — so their block-table
    pages beyond the frozen cursor are never touched.  The one extra sample
    at ``budgets == 0`` is the eos-check step: it decides ``hit_eos`` (stop
    vs length finish) exactly like the sequential path, whose bucketed
    windows also sample past the request budget before the host truncates.

    Page cursors advance IN-SCAN: step writes go to
    ``block_tables[b, lengths[b] // page_size]`` at offset ``lengths[b] %
    page_size``, so a window may cross page boundaries mid-scan — every
    page it can reach was reserved at dispatch time (the engine books
    ``max_tokens`` worth of pages at cohort admission) and the eos-check
    token itself lands in the sink, never in a pool page.

    Returns ``(tokens (B, K), emitted (B, K), logits, state, lengths, keys,
    done, budgets, hit_eos, presence)`` — the trailing tuple re-enters the
    next window's dispatch unchanged, so the host only ever fetches
    ``tokens``/``emitted``/``done`` (small int/bool arrays) and the KV state
    never crosses the device boundary.
    """
    refuse_recurrent(config, "paged_decode_steps", STREAM_NEEDS_STATE)
    batch = logits.shape[0]
    page_size = state.k_pages.shape[2]
    sink = state.k_pages.shape[1] - 1
    max_blocks = block_tables.shape[1]
    if eos_ids is None:
        eos_ids = jnp.zeros((0,), jnp.int32)
    if bias_table is not None:
        logit_bias = bias_table[bias_index]
    logits = _constrain(logits, mesh, "data", "model")
    block_tables = _constrain(block_tables, mesh, "data", None)
    lengths = _constrain(lengths, mesh, "data")
    keys = _constrain(keys, mesh, "data", None)
    done = _constrain(done, mesh, "data")
    budgets = _constrain(budgets, mesh, "data")
    hit_eos = _constrain(hit_eos, mesh, "data")
    state = _constrain_state(state, mesh)
    use_rp = presence is not None and rep_penalty is not None

    def is_eos(token: jax.Array) -> jax.Array:
        if eos_ids.shape[0] == 0:
            return jnp.zeros_like(token, dtype=jnp.bool_)
        return jnp.any(token[:, None] == eos_ids[None, :], axis=-1)

    def step(carry, _):
        (logits, state, lengths, keys, done, budgets, hit_eos) = carry[:7]
        pres = carry[7] if use_rp else None
        pairs = jax.vmap(jax.random.split)(keys)
        keys, sub = pairs[:, 0], pairs[:, 1]
        token = sample_tokens(
            sub, ban_undecodable(logits, config),
            temperature=temperature, top_k=top_k, top_p=top_p,
            logit_bias=logit_bias,
            presence=pres, rep_penalty=rep_penalty if use_rp else None,
        )
        token = jnp.where(done, pad_id, token)
        if use_rp:
            pres = pres.at[jnp.arange(batch), token].set(True)
        token_is_eos = is_eos(token) & ~done
        emitted = ~done & ~token_is_eos & (budgets > 0)
        new_done = done | token_is_eos | (budgets <= 0)
        hit_eos = hit_eos | token_is_eos
        budgets = budgets - emitted.astype(jnp.int32)

        page_idx = jnp.minimum(lengths // page_size, max_blocks - 1)
        page = jnp.take_along_axis(
            block_tables, page_idx[:, None], axis=1
        )[:, 0]
        write_pages = jnp.where(new_done | (page < 0), sink, page)
        write_offsets = jnp.where(new_done, 0, lengths % page_size)
        attn_lengths = jnp.where(new_done, lengths, lengths + 1)
        hidden, state = _paged_forward(
            params, config, token[:, None], lengths[:, None], state,
            block_tables, attn_lengths,
            write_pages[:, None], write_offsets[:, None],
        )
        state = _constrain_state(state, mesh)
        logits = project_logits(params, config, hidden[:, 0, :])
        logits = _constrain(logits, mesh, "data", "model")
        out = (logits, state, attn_lengths, keys, new_done, budgets, hit_eos)
        return out + ((pres,) if use_rp else ()), (token, emitted)

    init = (logits, state, lengths, keys, done, budgets, hit_eos) + (
        (presence,) if use_rp else ()
    )
    with jax.named_scope("decode_step"):
        final, (tokens_steps, emitted_steps) = jax.lax.scan(
            step, init, None, length=num_steps
        )
    (logits, state, lengths, keys, done, budgets, hit_eos) = final[:7]
    presence = final[7] if use_rp else None
    return (
        jnp.swapaxes(tokens_steps, 0, 1),  # (B, K) int32
        jnp.swapaxes(emitted_steps, 0, 1),  # (B, K) bool
        logits, state, lengths, keys, done, budgets, hit_eos, presence,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "config", "num_steps", "has_pending", "top_k", "top_p", "pad_id",
        "mesh",
    ),
    donate_argnums=(3,),
)
def paged_verify_steps(
    params,
    config: ModelConfig,
    logits: jax.Array,  # (B, V) f32 — first-decision logits (prefill out);
    #                     read only when ``has_pending`` is False
    state: PagedSlotState,
    block_tables: jax.Array,  # (B, max_blocks) int32, -1 padded
    lengths: jax.Array,  # (B,) int32 — tokens whose K/V is WRITTEN (the
    #                      pending token, when present, is NOT counted)
    keys: jax.Array,  # (B, 2) per-row PRNG keys
    done: jax.Array,  # (B,) bool
    budgets: jax.Array,  # (B,) int32 — remaining emit budget
    hit_eos: jax.Array,  # (B,) bool
    temperature: jax.Array,  # (B,) float32 (or scalar)
    draft_tokens: jax.Array,  # (B, K) int32 — per-row self-draft proposal
    pending: jax.Array,  # (B,) int32 — last emitted token, K/V unwritten
    eos_ids: Optional[jax.Array] = None,  # (E,) int32
    num_steps: int = 1,
    top_k: int = 0,
    top_p: float = 1.0,
    logit_bias: Optional[jax.Array] = None,
    bias_table: Optional[jax.Array] = None,
    bias_index: Optional[jax.Array] = None,
    pad_id: int = 0,
    presence: Optional[jax.Array] = None,  # (B, V) bool seen-token mask
    rep_penalty: Optional[jax.Array] = None,  # (B,) float32
    has_pending: bool = False,
    mesh: Optional[Mesh] = None,
):
    """Draft-and-verify variant of :func:`paged_decode_steps`: ONE window
    emits ``1 + accepted`` real tokens instead of 1 per scan step.

    The K per-row draft tokens are teacher-forced through ONE parallel
    ``_paged_forward`` (S = K, or K+1 with the pending column), then K+1
    sampling DECISIONS replay the sequential per-row key-split schedule
    exactly — decision t splits the row's key and samples from the logits
    the sequential scan would have carried at that step, so the accepted
    prefix plus the first correction token reproduce the sequential
    sampling decisions bit-for-bit (Leviathan et al. rejection, the same
    contract ``rollout_verify_many`` pins for score-only rollouts).  A row
    stops deciding the moment it diverges from its draft (the
    teacher-forced context past that column is wrong); its key state has
    then consumed exactly as many splits as decisions made, so the NEXT
    window resumes the sequential schedule unchanged — keys only advance
    on real decisions, which IS the rewind.

    Pending-token protocol: a correction (or the bonus token sampled after
    a fully-accepted draft) is emitted without its K/V being written — the
    next window forwards it as column 0 (``has_pending=True``) and derives
    the first decision's logits from its hidden, so ``lengths`` always
    counts exactly the K/V-written tokens and the conservative
    ``ceil((prompt + max_tokens) / page_size)`` reservation stays valid
    under variable emission: every position a REAL decision's logits
    depend on is < prompt + max_tokens.

    Write discipline: draft columns write their K/V optimistically into
    the pages the cursors name (later columns must attend earlier ones),
    but a column is routed to the SINK when its row was done at entry or
    its position falls past the block table (never clamp-and-write — the
    decode path's clamp would wrap a past-table position into the LAST
    page at a low offset and corrupt live K/V).  Rejected-tail writes that
    did land in pool pages sit past the row's final ``lengths``, masked
    out of every attention read and overwritten when those positions go
    live.

    Returns ``(tokens (B, K+1), emitted (B, K+1), accepted (B,) int32,
    pending, state, lengths, keys, done, budgets, hit_eos, presence)`` —
    ``accepted`` counts emitted draft matches (excluding the correction /
    bonus token), and the trailing tuple re-enters the next window's
    dispatch with ``has_pending=True``.
    """
    refuse_recurrent(config, "paged_verify_steps", STREAM_NEEDS_STATE)
    batch = draft_tokens.shape[0]
    assert draft_tokens.shape[1] == num_steps, (
        "draft_tokens must carry num_steps columns"
    )
    page_size = state.k_pages.shape[2]
    sink = state.k_pages.shape[1] - 1
    max_blocks = block_tables.shape[1]
    if eos_ids is None:
        eos_ids = jnp.zeros((0,), jnp.int32)
    if bias_table is not None:
        logit_bias = bias_table[bias_index]
    if logits is not None:
        logits = _constrain(logits, mesh, "data", "model")
    block_tables = _constrain(block_tables, mesh, "data", None)
    lengths = _constrain(lengths, mesh, "data")
    keys = _constrain(keys, mesh, "data", None)
    done = _constrain(done, mesh, "data")
    budgets = _constrain(budgets, mesh, "data")
    hit_eos = _constrain(hit_eos, mesh, "data")
    draft_tokens = _constrain(draft_tokens, mesh, "data", None)
    pending = _constrain(pending, mesh, "data")
    state = _constrain_state(state, mesh)
    use_rp = presence is not None and rep_penalty is not None
    done_entry = done

    # ---- one teacher-forced forward over the window's columns ----------
    if has_pending:
        cols = jnp.concatenate([pending[:, None], draft_tokens], axis=1)
    else:
        cols = draft_tokens
    s = cols.shape[1]
    positions = lengths[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
    page_idx = positions // page_size
    in_table = page_idx < max_blocks
    page = jnp.take_along_axis(
        block_tables, jnp.minimum(page_idx, max_blocks - 1), axis=1
    )
    write_pages = jnp.where(
        done_entry[:, None] | ~in_table | (page < 0), sink, page
    )
    write_offsets = jnp.where(done_entry[:, None], 0, positions % page_size)
    attn_lengths = jnp.where(
        done_entry, lengths,
        jnp.minimum(lengths + s, max_blocks * page_size),
    )
    hidden, state = _paged_forward(
        params, config, cols, positions, state,
        block_tables, attn_lengths, write_pages, write_offsets,
    )
    state = _constrain_state(state, mesh)

    # Decision t (t = 0..K) samples the token at new position t.  Its
    # context is the written stream plus [pending?, d_0..d_{t-1}] — with a
    # pending column that is hidden column t, without one it is column
    # t-1 (decision 0 then samples from the CARRIED prefill logits, the
    # exact first sample of the sequential path).
    if has_pending:
        first_logits = project_logits(params, config, hidden[:, 0, :])
        dec_hidden = hidden[:, 1:, :]  # (B, K, D)
    else:
        first_logits = logits
        dec_hidden = hidden  # (B, K, D)
    first_logits = _constrain(first_logits, mesh, "data", "model")

    def is_eos(token: jax.Array) -> jax.Array:
        if eos_ids.shape[0] == 0:
            return jnp.zeros_like(token, dtype=jnp.bool_)
        return jnp.any(token[:, None] == eos_ids[None, :], axis=-1)

    def decision(carry, logits_t, draft_t):
        (keys, done, budgets, hit_eos, ok, accepted, pending) = carry[:7]
        pres = carry[7] if use_rp else None
        real = ok & ~done
        pairs = jax.vmap(jax.random.split)(keys)
        keys = jnp.where(real[:, None], pairs[:, 0], keys)
        token = sample_tokens(
            pairs[:, 1], ban_undecodable(logits_t, config),
            temperature=temperature, top_k=top_k,
            top_p=top_p, logit_bias=logit_bias,
            presence=pres, rep_penalty=rep_penalty if use_rp else None,
        )
        token = jnp.where(real, token, pad_id)
        if use_rp:
            updated = pres.at[jnp.arange(batch), token].set(True)
            pres = jnp.where(real[:, None], updated, pres)
        token_is_eos = is_eos(token) & real
        emit = real & ~token_is_eos & (budgets > 0)
        done = done | (real & (token_is_eos | (budgets <= 0)))
        hit_eos = hit_eos | token_is_eos
        budgets = budgets - emit.astype(jnp.int32)
        # A row keeps deciding only while every emitted token matched its
        # draft; the correction / bonus token (draft -1 never matches)
        # ends the row's window with that token left pending.
        matched = emit & (token == draft_t)
        accepted = accepted + matched.astype(jnp.int32)
        pending = jnp.where(emit, token, pending)
        out = (keys, done, budgets, hit_eos, matched, accepted, pending)
        return out + ((pres,) if use_rp else ()), (token, emit)

    carry = (
        keys, done, budgets, hit_eos,
        jnp.ones((batch,), jnp.bool_), jnp.zeros((batch,), jnp.int32),
        pending,
    ) + ((presence,) if use_rp else ())
    carry, (tok0, emit0) = decision(carry, first_logits, draft_tokens[:, 0])

    drafts_rest = jnp.concatenate(
        [draft_tokens[:, 1:], jnp.full((batch, 1), -1, jnp.int32)], axis=1
    )  # (B, K): d_1..d_{K-1} then the bonus sentinel

    def scan_step(carry, xs):
        h_col, d_col = xs  # (B, D), (B,)
        logits_t = project_logits(params, config, h_col)
        logits_t = _constrain(logits_t, mesh, "data", "model")
        return decision(carry, logits_t, d_col)

    carry, (tok_rest, emit_rest) = jax.lax.scan(
        scan_step, carry,
        (jnp.moveaxis(dec_hidden, 0, 1), jnp.moveaxis(drafts_rest, 0, 1)),
    )
    (keys, done, budgets, hit_eos, _, accepted, pending) = carry[:7]
    presence = carry[7] if use_rp else None
    written = accepted
    if has_pending:
        # The carried pending token's K/V went live this window (done-at-
        # entry rows wrote sink and stay frozen).
        written = written + (~done_entry).astype(jnp.int32)
    lengths = lengths + written
    tokens_out = jnp.concatenate(
        [tok0[:, None], jnp.swapaxes(tok_rest, 0, 1)], axis=1
    )  # (B, K+1) int32
    emitted_out = jnp.concatenate(
        [emit0[:, None], jnp.swapaxes(emit_rest, 0, 1)], axis=1
    )  # (B, K+1) bool
    return (
        tokens_out, emitted_out, accepted, pending, state, lengths,
        keys, done, budgets, hit_eos, presence,
    )


#: The most one float32 tile of a score chunk's head may take, and the fewest
#: and the most vocabulary columns a tile has.  A tiny test vocabulary is one
#: tile; past 4,096 columns a tile was no faster on a v5e (32 x 256 positions
#: x 5,120: 131.6 ms a chunk at 4,096, 143.9 at 8,192; 64 x 256 x 2,048: 24.3
#: and 27.7; chip run, PR 28) and holds more.
_SCORE_TILE_BYTES = 256 * 1024**2
_SCORE_TILE_COLUMNS = (1024, 4096)


def score_vocab_tile(positions: int) -> int:
    """Vocabulary columns a tile of :func:`paged_score_chunk`'s head has for
    a chunk of ``positions`` = rows x columns: the largest power of two that
    keeps the (positions, tile) float32 logits within ``_SCORE_TILE_BYTES``,
    held to ``_SCORE_TILE_COLUMNS`` (4,096 up to 64 x 256 positions, 1,024
    from 64 x 1,024).  From the shapes alone: the backend's budget counts the
    same tile."""
    fewest, most = _SCORE_TILE_COLUMNS
    columns = min(max(_SCORE_TILE_BYTES // (4 * positions), fewest), most)
    return 1 << (columns.bit_length() - 1)


@functools.partial(
    jax.jit, static_argnames=("config", "mesh"), donate_argnums=(6,)
)
def paged_score_chunk(
    params,
    config: ModelConfig,
    tokens: jax.Array,  # (B, S) int32 — query block per matrix row
    targets: jax.Array,  # (B, S) int32 — stream token AFTER each query pos
    score_mask: jax.Array,  # (B, S) bool — continuation positions only
    chunk_valid: jax.Array,  # (B, S) bool — real columns of this chunk
    state: PagedSlotState,
    block_tables: jax.Array,  # (B, max_blocks) — shared ctx + private pages
    lengths: jax.Array,  # (B,) int32 — stream length AFTER this call
    write_pages: jax.Array,  # (B, S) int32 — private pages / sink
    write_offsets: jax.Array,  # (B, S) int32
    mesh: Optional[Mesh] = None,  # static: rows over data, heads over model
    ssm_rows: Optional[jax.Array] = None,  # (B,) int32 — row of state.ssm
) -> Tuple[Tuple[jax.Array, jax.Array, jax.Array, jax.Array], PagedSlotState]:
    """Teacher-forced scoring of one (candidates x agents) row chunk over
    shared context pages, reduced ON DEVICE.

    Each row's query block is the tail of its agent context (the tokens
    past the last full shared page — at least one, so the hidden at the
    final context position exists to teacher-force the first candidate
    token) followed by all but the last candidate token.  The block table
    names the agent's READ-ONLY shared context pages first and the row's
    private tail pages after; writes land only in the private region (or
    the sink for padding columns), so many rows attend the same agent
    prefill bytes without copying them — the PagedAttention sharing trick
    applied to scoring.

    The logprob of stream token p+1 is taken at query position p by
    :func:`transformer._streamed_target_logprobs`: a ``lax.scan`` over
    vocabulary tiles, every row and column of the chunk against one tile at
    a time (:func:`score_vocab_tile` columns of the head), a running
    maximum and a rescaled sum for each position — the head's table is read
    once a chunk and no (B, S, V) array exists.  Returns the per-row
    reductions ``(sum_lp, last_lp, sum_exp_lp, count)`` over the masked
    columns (``last_lp`` is the value at the row's last one, 0.0 where it
    has none) — enough for every consumer statistic (mean / sum / last /
    moments) — and the updated page state.  No per-token vector survives to
    be fetched.

    With recurrent layers the shared pages are half of a context: the other
    half is the state at the page boundary where the row's query block
    starts.  ``state.ssm`` holds those snapshots, one a context as the
    prefill left them, and ``ssm_rows`` names each row's; the row's copy
    runs on through its block and is dropped, the snapshots stay.
    """
    tokens = _constrain(tokens, mesh, "data", None)
    targets = _constrain(targets, mesh, "data", None)
    score_mask = _constrain(score_mask, mesh, "data", None)
    chunk_valid = _constrain(chunk_valid, mesh, "data", None)
    block_tables = _constrain(block_tables, mesh, "data", None)
    lengths = _constrain(lengths, mesh, "data")
    write_pages = _constrain(write_pages, mesh, "data", None)
    write_offsets = _constrain(write_offsets, mesh, "data", None)
    state = _constrain_state(state, mesh)
    b, s = tokens.shape
    n_valid = jnp.sum(chunk_valid.astype(jnp.int32), axis=1)  # (B,)
    start = lengths - n_valid
    positions = start[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
    snapshots = state.ssm
    hidden, state = _paged_forward(
        params, config, tokens, positions, state,
        block_tables, lengths, write_pages, write_offsets,
        valid=chunk_valid,
        ssm=fork_ssm(snapshots, ssm_rows) if config.has_ssm else None,
    )
    state = _constrain_state(state._replace(ssm=snapshots), mesh)
    mask = score_mask & chunk_valid

    lp = _streamed_target_logprobs(
        params, config, hidden, targets, score_vocab_tile(b * s),
        constrain_tile=lambda tile: _constrain(tile, mesh, "data", None, "model"),
    )  # (B, S) f32
    with jax.named_scope("logsumexp"):
        sum_lp = jnp.sum(jnp.where(mask, lp, 0.0), axis=1)
        sum_exp = jnp.sum(jnp.where(mask, jnp.exp(lp), 0.0), axis=1)
        counts = jnp.sum(mask.astype(jnp.int32), axis=1)
        cols = jnp.arange(s, dtype=jnp.int32)[None, :]
        last_col = jnp.max(jnp.where(mask, cols, -1), axis=1, keepdims=True)
        last_lp = jnp.sum(jnp.where(cols == last_col, lp, 0.0), axis=1)
    return (sum_lp, last_lp, sum_exp, counts), state


def utility_matrix(
    stats: Tuple[jax.Array, jax.Array, jax.Array, jax.Array],
    n_candidates: int,
    n_agents: int,
    stat: str = "mean",
    rule: str = "egalitarian",
    default: float = DEFAULT_REWARD,
) -> Tuple[jax.Array, jax.Array, Optional[jax.Array]]:
    """Fold flattened (C*A,) per-row reductions into the (C, A) utility
    matrix and its welfare vector, entirely on device: sanitize -> welfare
    rule over the agent axis.  Rows with zero scored tokens (empty
    continuation) take ``default`` — the per-call ``ScoreResult`` empty
    semantics.  Returns ``(utilities (C, A) f32, welfare (C,), aux)``
    where ``aux`` is the per-cell mean probability for ``stat="moments"``
    (the evaluator's perplexity accounting) and ``None`` otherwise.  The
    caller fetches only these — the welfare argmax stays a host
    ``np.argmax`` so tie-breaking is pinned to numpy first-max."""
    sum_lp, last_lp, sum_exp, counts = stats
    counts_f = jnp.maximum(counts, 1).astype(jnp.float32)
    if stat in ("mean", "moments"):
        value = sum_lp / counts_f
    elif stat == "sum":
        value = sum_lp
    elif stat == "last":
        value = last_lp
    else:
        raise ValueError(f"unknown stat {stat!r}")
    scored = counts > 0
    value = jnp.where(scored, value, jnp.asarray(default, jnp.float32))
    utilities = value.reshape(n_candidates, n_agents)
    welfare_vals = WELFARE_RULES[rule](sanitize_utilities(utilities), axis=1)
    aux = None
    if stat == "moments":
        aux = jnp.where(scored, sum_exp / counts_f, 0.0).reshape(
            n_candidates, n_agents
        )
    return utilities, welfare_vals, aux


@functools.partial(
    jax.jit, static_argnames=("config", "mesh"), donate_argnums=(3,)
)
def paged_gather_step(
    params,
    config: ModelConfig,
    tokens: jax.Array,  # (B,) int32 — each slot's LAST cached token
    state: PagedSlotState,
    block_tables: jax.Array,  # (B, max_blocks) — may name SHARED pages
    lengths: jax.Array,  # (B,) int32 — cached stream length
    mesh: Optional[Mesh] = None,  # static: shard slots over data, KV over model
) -> Tuple[jax.Array, PagedSlotState]:
    """Read-only decode step over shared prefix pages (the prefix cache's
    gather path).  When a slot adopts a fully cached prompt it still needs
    the logits at the last prompt position to start decoding — this
    re-forwards that one token, gathering K/V through the block table
    exactly like :func:`paged_decode_step`, but routes the recomputed K/V
    to the write SINK: pages another slot (or the cache) owns are read in
    place, never copied and never mutated.  Attention reads the STORED
    page for the query's own position (the bytes the owner's prefill
    wrote), so the logits match the owning slot's dense/prefill logits at
    that position to float tolerance — pinned against the dense forward
    in tests/test_engine.py.  Returns (logits (B, V) f32, state) — only
    the sink page changed."""
    refuse_recurrent(config, "paged_gather_step", STREAM_NEEDS_STATE)
    num_pages = state.k_pages.shape[1] - 1
    b = tokens.shape[0]
    tokens = _constrain(tokens, mesh, "data")
    block_tables = _constrain(block_tables, mesh, "data", None)
    lengths = _constrain(lengths, mesh, "data")
    state = _constrain_state(state, mesh)
    sink = jnp.full((b, 1), num_pages, jnp.int32)
    positions = (lengths - 1)[:, None]
    hidden, state = _paged_forward(
        params, config, tokens[:, None], positions, state,
        block_tables, lengths, sink, jnp.zeros((b, 1), jnp.int32),
    )
    state = _constrain_state(state, mesh)
    logits = project_logits(params, config, hidden[:, 0, :])
    return _constrain(logits, mesh, "data", "model"), state
