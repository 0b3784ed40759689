"""Pallas TPU kernel for the session hot loop's 1-position decode attention.

Every fused token-search step (``models/stepper.py`` — beam/MCTS/lookahead
sessions) runs ``transformer.forward_trunk_tail``: one new query position
per (slot x role) row attending a SHARED per-role trunk cache plus a
per-row generated-token tail.  Under stock XLA that is four einsums with a
(P, R, g, m, W0+Ts) fp32 logits intermediate between them; this kernel
fuses score -> softcap -> mask -> streaming-softmax -> value-accumulate
into one VMEM-resident pass per (role, kv-head), reading the trunk ONCE
per role (broadcast over slots, like the einsum) and the tail once.

Layout (one grid step = one K block):

* grid = (R · KV, k_steps) where the k axis first walks the trunk's
  W0-blocks and then the folded (P·Ts) tail rows;
* q block: all slots' query heads for one (role, kv-group) —
  (P·reps, hd) rows, contiguous because the wrapper rearranges
  (P, R, KV, reps) -> (R, KV, P·reps);
* tail keys fold to (P·Ts, hd); block-diagonal slot masking is pure iota
  arithmetic (slot_of_q = row // reps, slot_of_k = row // Ts);
* masking model mirrors the flash kernel's contiguous-span model: a trunk
  row is valid on [start_r, W0) with RoPE position ``iota - start_r``; a
  tail column j is valid for j <= write_col with position
  ``qpos - write_col + j``.

Restriction: query positions are uniform across SLOTS (one scalar per
role, ``qpos_r``).  Every session call site satisfies this — all slots
advance in lockstep off one trunk, so a row's position is its role's
prefix length plus the shared step counter — and the wrapper is only used
on that path; the general ``forward_trunk_tail`` einsum stays the
fallback.

Numerics are pinned against the einsum path in tests (CPU interpret mode).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

DEFAULT_BLOCK_K = 256


def _kernel(
    scalar_ref,  # (2 + 2R,) int32 SMEM: [write_col, Ts, qpos_0.., start_0..]
    q_ref,  # (1, QP, hd) — QP = P·reps padded
    k_ref,  # (1, BK, hd) — trunk blocks then folded tail rows
    v_ref,  # (1, BK, hd)
    out_ref,  # (1, QP, hd)
    m_scratch,  # (QP, 128) f32
    l_scratch,  # (QP, 128) f32
    acc_scratch,  # (QP, hd) f32
    *,
    scale: float,
    softcap: Optional[float],
    window: Optional[int],
    n_roles: int,
    reps: int,
    block_k: int,
    k_steps: int,
    w0: int,
    w0_padded: int,
):
    rg = pl.program_id(0)  # role * KV + kv_head
    ki = pl.program_id(1)
    role = rg // (pl.num_programs(0) // n_roles)

    @pl.when(ki == 0)
    def _init():
        m_scratch[...] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[...] = jnp.zeros_like(l_scratch)
        acc_scratch[...] = jnp.zeros_like(acc_scratch)

    write_col = scalar_ref[0]
    t_tail = scalar_ref[1]
    qpos = scalar_ref[2 + role]
    start = scalar_ref[2 + n_roles + role]

    q = q_ref[0].astype(jnp.float32)  # (QP, hd)
    k = k_ref[0].astype(jnp.float32)  # (BK, hd)
    v = v_ref[0].astype(jnp.float32)

    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # (QP, BK)
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)

    qp = q_ref.shape[1]
    qrow = jax.lax.broadcasted_iota(jnp.int32, (qp, 1), 0)
    krow = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)

    in_trunk = krow < w0_padded
    # Trunk keys: valid span [start, W0) — the padded columns [W0, W0p) are
    # zeros and MUST be masked or they add softmax mass.  All trunk
    # positions precede the query (written before any tail token) so
    # causality is automatic.
    trunk_ok = (krow < w0) & (krow >= start)
    if window is not None:
        trunk_pos = krow - start
        trunk_ok = trunk_ok & (qpos - trunk_pos < window)
    # Tail keys: folded (P·Ts) rows; key row j of slot p sits at
    # w0_padded + p·Ts + j.  Valid when j <= write_col and the slot matches
    # the query's slot (block-diagonal).
    tail_row = krow - w0_padded
    tail_slot = tail_row // t_tail
    tail_col = tail_row - tail_slot * t_tail
    q_slot = qrow // reps
    tail_ok = (
        ~in_trunk
        & (tail_col <= write_col)
        & (tail_slot == q_slot)
    )
    if window is not None:
        tail_ok = tail_ok & (write_col - tail_col < window)
    mask = trunk_ok | tail_ok

    logits = jnp.where(mask, logits, NEG_INF)

    m_prev = m_scratch[:, :1]
    block_max = jnp.max(logits, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, block_max)
    p = jnp.exp(logits - m_new)
    p = jnp.where(mask, p, 0.0)
    alpha = jnp.exp(m_prev - m_new)

    l_new = l_scratch[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = acc_scratch[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    m_scratch[...] = jnp.broadcast_to(m_new, m_scratch.shape)
    l_scratch[...] = jnp.broadcast_to(l_new, l_scratch.shape)
    acc_scratch[...] = acc_new

    @pl.when(ki == k_steps - 1)
    def _finalize():
        denom = jnp.maximum(l_scratch[:, :1], 1e-30)
        out_ref[0, :, :] = (acc_scratch[...] / denom).astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "n_slots", "n_roles", "scale", "softcap", "window", "block_k", "interpret",
    ),
)
def decode_attention(
    q: jax.Array,  # (Rows, H, hd) — Rows = n_slots·n_roles, slot-major
    trunk_k: jax.Array,  # (R, W0, KV, hd)
    trunk_v: jax.Array,
    tail_k: jax.Array,  # (Rows, Ts, KV, hd)
    tail_v: jax.Array,
    starts: jax.Array,  # (R,) int32 — trunk valid-span starts (left-padded)
    qpos: jax.Array,  # (R,) int32 — per-role query position (uniform across slots)
    write_col: jax.Array,  # () int32 — current tail column
    n_slots: int,
    n_roles: int,
    scale: float,
    softcap: Optional[float] = None,
    window: Optional[int] = None,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
) -> jax.Array:
    """Fused 1-position GQA decode attention over shared trunk + tails.

    Returns (Rows, H, hd) in q's dtype.
    """
    rows, h, hd = q.shape
    r, w0, kv, _ = trunk_k.shape
    ts = tail_k.shape[1]
    reps = h // kv
    assert rows == n_slots * n_roles and r == n_roles

    # q: (P, R, KV, reps, hd) -> (R·KV, P·reps, hd)
    qr = (
        q.reshape(n_slots, n_roles, kv, reps, hd)
        .transpose(1, 2, 0, 3, 4)
        .reshape(n_roles * kv, n_slots * reps, hd)
    )
    qp = n_slots * reps
    qp_pad = max(8, -(-qp // 8) * 8)
    if qp_pad != qp:
        qr = jnp.pad(qr, ((0, 0), (0, qp_pad - qp), (0, 0)))

    # trunk: (R, W0, KV, hd) -> (R·KV, W0p, hd)
    w0_pad = -(-w0 // block_k) * block_k
    def fold_trunk(x):
        x = x.transpose(0, 2, 1, 3).reshape(n_roles * kv, w0, hd)
        if w0_pad != w0:
            x = jnp.pad(x, ((0, 0), (0, w0_pad - w0), (0, 0)))
        return x

    # tail: (P, R, Ts, KV, hd) -> (R·KV, P·Ts, hd), padded to a block multiple
    pt = n_slots * ts
    pt_pad = -(-pt // block_k) * block_k
    def fold_tail(x):
        x = (
            x.reshape(n_slots, n_roles, ts, kv, hd)
            .transpose(1, 3, 0, 2, 4)
            .reshape(n_roles * kv, pt, hd)
        )
        if pt_pad != pt:
            x = jnp.pad(x, ((0, 0), (0, pt_pad - pt), (0, 0)))
        return x

    kf = jnp.concatenate([fold_trunk(trunk_k), fold_tail(tail_k)], axis=1)
    vf = jnp.concatenate([fold_trunk(trunk_v), fold_tail(tail_v)], axis=1)

    k_steps = (w0_pad + pt_pad) // block_k

    scalars = jnp.concatenate(
        [
            jnp.stack(
                [
                    jnp.asarray(write_col, jnp.int32),
                    jnp.asarray(ts, jnp.int32),
                ]
            ),
            jnp.broadcast_to(jnp.asarray(qpos, jnp.int32), (n_roles,)),
            starts.astype(jnp.int32),
        ]
    )

    kernel = functools.partial(
        _kernel,
        scale=float(scale),
        softcap=softcap,
        window=window,
        n_roles=n_roles,
        reps=reps,
        block_k=block_k,
        k_steps=k_steps,
        w0=w0,
        w0_padded=w0_pad,
    )

    out = pl.pallas_call(
        kernel,
        grid=(n_roles * kv, k_steps),
        in_specs=[
            pl.BlockSpec(
                (2 + 2 * n_roles,), lambda rg, ki: (0,), memory_space=pltpu.SMEM
            ),
            pl.BlockSpec((1, qp_pad, hd), lambda rg, ki: (rg, 0, 0)),
            pl.BlockSpec((1, block_k, hd), lambda rg, ki: (rg, ki, 0)),
            pl.BlockSpec((1, block_k, hd), lambda rg, ki: (rg, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, qp_pad, hd), lambda rg, ki: (rg, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_roles * kv, qp_pad, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((qp_pad, 128), jnp.float32),
            pltpu.VMEM((qp_pad, 128), jnp.float32),
            pltpu.VMEM((qp_pad, hd), jnp.float32),
        ],
        interpret=interpret,
        name="decode_attention",
    )(scalars, qr, kf, vf)

    # (R·KV, P·reps, hd) -> (Rows, H, hd)
    out = out[:, :qp]
    out = (
        out.reshape(n_roles, kv, n_slots, reps, hd)
        .transpose(2, 0, 1, 3, 4)
        .reshape(rows, h, hd)
    )
    return out


def softmax_with_sink(logits: jax.Array, sink: Optional[jax.Array]) -> jax.Array:
    """Softmax over the last axis of float32 ``logits`` with one more column
    of logit ``sink`` (broadcast against ``logits[..., :1]``) in the
    denominator: the sink takes mass and adds no value, so the weights sum to
    less than one.  The plain softmax where there is no sink."""
    if sink is None:
        return jax.nn.softmax(logits, axis=-1)
    sink = sink.astype(jnp.float32)
    top = jnp.maximum(jnp.max(logits, axis=-1, keepdims=True), sink)
    shares = jnp.exp(logits - top)
    return shares / (jnp.sum(shares, axis=-1, keepdims=True) + jnp.exp(sink - top))


# ---------------------------------------------------------------------------
# Paged attention: gather K/V through per-slot block tables
# ---------------------------------------------------------------------------


def paged_attention(
    q: jax.Array,  # (B, S, H, hd) — S query positions per slot (decode: S=1)
    k_pages: jax.Array,  # (num_pages[+sink], page_size, KV, hd)
    v_pages: jax.Array,
    block_tables: jax.Array,  # (B, max_blocks) int32, -1 padded
    lengths: jax.Array,  # (B,) int32 — valid tokens in the slot's stream
    q_positions: jax.Array,  # (B, S) int32 — query RoPE positions
    scale: float,
    softcap: Optional[float] = None,
    window: Optional[int] = None,
    sink: Optional[jax.Array] = None,  # (H,) float32: a logit a query head
    latent=None,  # gathered (B, T, 1, width) latents -> (keys, values)
) -> jax.Array:
    """GQA attention over a PAGED KV cache (Kwon et al., SOSP '23 layout).

    Each slot's K/V live in the fixed page pool at the pages its block
    table names; the gather materializes a (B, max_blocks·page_size, ...)
    view, so ragged-length slots coexist in ONE fixed-shape program — the
    compiled shape is (B, S, max_blocks) and never depends on any slot's
    actual length.  A slot's token t sits at page ``table[t // page_size]``
    offset ``t % page_size`` with RoPE position t (streams are contiguous
    from 0), so causality is plain position arithmetic.

    Padding rows of the block table (-1) gather page 0 but are masked by
    ``lengths``; rows past a slot's length inside its last page are masked
    the same way.  Pure jnp on purpose: the engine's slot programs must run
    (and be pinned) under JAX_PLATFORMS=cpu; the pallas fusion of this
    gather is a later optimization behind the same signature.

    ``sink``: one more column in every softmax's denominator, of that logit
    for the query's head; it takes mass and adds no value.  The value pages
    may be narrower than the key pages.

    A latent pool is one buffer: ``v_pages`` is None, ``k_pages`` holds one
    head of [latent | rotary key] a token, and ``latent`` makes the keys and
    values of what was gathered (``transformer.latent_view``: the latents
    themselves and their first columns, for absorbed queries;
    ``transformer.mla_expand``: every head's own).

    Returns (B, S, H, value width) in q's dtype.
    """
    b, s, h, hd = q.shape
    page_size = k_pages.shape[1]
    max_blocks = block_tables.shape[1]
    t_len = max_blocks * page_size

    safe_tables = jnp.maximum(block_tables, 0)
    keys = k_pages[safe_tables].reshape((b, t_len) + k_pages.shape[2:])
    if v_pages is None:
        keys, values = latent(keys)
    else:
        values = v_pages[safe_tables].reshape((b, t_len) + v_pages.shape[2:])
    kv = keys.shape[2]
    reps = h // kv

    kpos = jnp.arange(t_len, dtype=jnp.int32)[None, :]  # (1, T)
    k_valid = kpos < lengths[:, None]  # (B, T)
    causal = kpos[:, None, :] <= q_positions[:, :, None]  # (B, S, T)
    mask = causal & k_valid[:, None, :]
    if window is not None:
        mask = mask & (q_positions[:, :, None] - kpos[:, None, :] < window)

    # Grouped-query einsum without materializing repeated KV (mirrors the
    # transformer.forward einsum path).
    qg = q.reshape(b, s, kv, reps, hd)
    logits = jnp.einsum("bsgrd,btgd->bgrst", qg, keys).astype(jnp.float32)
    logits = logits * scale
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)
    logits = jnp.where(mask[:, None, None], logits, NEG_INF)
    if sink is not None:  # one a query head: (KV, reps, 1, 1)
        sink = sink.reshape(kv, reps)[:, :, None, None]
    weights = softmax_with_sink(logits, sink).astype(q.dtype)
    attn = jnp.einsum("bgrst,btgd->bsgrd", weights, values)
    return attn.reshape(b, s, h, values.shape[-1])
