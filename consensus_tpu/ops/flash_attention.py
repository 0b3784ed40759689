"""Pallas TPU flash-attention kernel for the teacher-forced scoring path.

The welfare pipeline's FLOPs concentrate in full-sequence self-attention:
every decoder scores (candidates × agents) sequences teacher-forced
(SURVEY §3.3), and each scoring forward materializes (B, H, S, S) attention
logits in HBM under stock XLA.  This kernel computes attention blockwise in
VMEM with the streaming-softmax (flash) recurrence: per (batch·head,
Q-block) it iterates K-blocks keeping running (max, sum, accumulator)
scratch, so HBM traffic is O(S·hd) instead of O(S²).

Masking model: rows hold ONE contiguous valid span ``[start, start+length)``
— right-padded scoring batches have ``start == 0``; left-padded generation/
next-token/embed batches have ``start == S - length``.  Two per-row scalars
(SMEM) define validity, and positions are the global iota; because the span
is contiguous, iota-based causal/window tests equal the RoPE-position tests
(position == iota - start inside the span).  This keeps every VMEM operand
3-D with Mosaic-legal tiles ((block, hd) with block a multiple of 8 and hd
a lane multiple); the wrapper pads the sequence up to a block multiple and
slices the padding back off.

Supports causal masking, Gemma-2's sliding-window local layers
(``window``), and the attention logit softcap.  Numerics are pinned against
the XLA reference in tests (CPU interpret mode); on TPU the same kernel
compiles via Mosaic.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128


def _kernel(
    len_ref,  # (BH,) int32 in SMEM — all rows' valid-span lengths
    start_ref,  # (BH,) int32 in SMEM — all rows' valid-span start offsets
    q_ref,  # (1, BQ, hd)
    k_ref,  # (1, BK, hd)
    v_ref,  # (1, BK, hd)
    out_ref,  # (1, BQ, hd)
    m_scratch,  # (BQ, 128) f32
    l_scratch,  # (BQ, 128) f32
    acc_scratch,  # (BQ, hd) f32
    *,
    scale: float,
    softcap: Optional[float],
    window: Optional[int],
    causal: bool,
    block_q: int,
    block_k: int,
    k_steps: int,
):
    bh = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scratch[...] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[...] = jnp.zeros_like(l_scratch)
        acc_scratch[...] = jnp.zeros_like(acc_scratch)

    length = len_ref[bh]
    start = start_ref[bh]
    q = q_ref[0].astype(jnp.float32)  # (BQ, hd)
    k = k_ref[0].astype(jnp.float32)  # (BK, hd)
    v = v_ref[0].astype(jnp.float32)

    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # (BQ, BK)
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)

    # Positions are the global iota; validity is the contiguous span
    # [start, start+length) — start==0 for right-padded scoring rows,
    # start==S-length for left-padded generation/next-token/embed rows.
    qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
    kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
    end = start + length
    mask = (qpos >= start) & (qpos < end) & (kpos >= start) & (kpos < end)
    if causal:
        mask = mask & (kpos <= qpos)
    if window is not None:
        mask = mask & (qpos - kpos < window)
    logits = jnp.where(mask, logits, NEG_INF)

    m_prev = m_scratch[:, :1]  # (BQ, 1)
    block_max = jnp.max(logits, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, block_max)
    p = jnp.exp(logits - m_new)
    p = jnp.where(mask, p, 0.0)
    alpha = jnp.exp(m_prev - m_new)  # (BQ, 1)

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
        "scale", "softcap", "window", "causal", "block_q", "block_k", "interpret",
    ),
)
def flash_attention(
    q: jax.Array,  # (B, S, H, hd)
    k: jax.Array,  # (B, S, H, hd) — post-GQA-repeat, same head count as q
    v: jax.Array,
    lengths: jax.Array,  # (B,) int32 — valid-span length per row
    starts: Optional[jax.Array] = None,  # (B,) int32 — span start (default 0)
    scale: Optional[float] = None,
    softcap: Optional[float] = None,
    window: Optional[int] = None,
    causal: bool = True,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
) -> jax.Array:
    """Blockwise-streaming attention over rows with one contiguous valid span.

    ``starts=None`` (all zeros) is the right-padded scoring layout;
    ``starts = S - lengths`` is the left-padded generation layout.
    Returns (B, S, H, hd) in q's dtype; positions outside the span are zero.
    """
    batch, seq, heads, head_dim = q.shape
    if scale is None:
        scale = head_dim ** -0.5

    block_q = min(block_q, max(seq, 8))
    block_k = min(block_k, max(seq, 8))
    pad_to = max(block_q, block_k)
    padded = -(-seq // pad_to) * pad_to
    if padded != seq:
        grow = ((0, 0), (0, padded - seq), (0, 0), (0, 0))
        q, k, v = jnp.pad(q, grow), jnp.pad(k, grow), jnp.pad(v, grow)

    # Fold heads into batch: attention is independent per (batch, head).
    def fold(x):
        return x.transpose(0, 2, 1, 3).reshape(batch * heads, padded, head_dim)

    qf, kf, vf = fold(q), fold(k), fold(v)
    lens = jnp.repeat(lengths.astype(jnp.int32), heads, axis=0)  # (BH,)
    if starts is None:
        starts = jnp.zeros_like(lengths)
    offs = jnp.repeat(starts.astype(jnp.int32), heads, axis=0)  # (BH,)

    q_steps = padded // block_q
    k_steps = padded // block_k

    kernel = functools.partial(
        _kernel,
        scale=float(scale),
        softcap=softcap,
        window=window,
        causal=causal,
        block_q=block_q,
        block_k=block_k,
        k_steps=k_steps,
    )

    out = pl.pallas_call(
        kernel,
        grid=(batch * heads, q_steps, k_steps),
        in_specs=[
            # SMEM rank-1 blocks must be whole-array; index by program_id.
            pl.BlockSpec(
                (batch * heads,), lambda b, qi, ki: (0,), memory_space=pltpu.SMEM
            ),
            pl.BlockSpec(
                (batch * heads,), lambda b, qi, ki: (0,), memory_space=pltpu.SMEM
            ),
            pl.BlockSpec((1, block_q, head_dim), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_k, head_dim), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, block_k, head_dim), lambda b, qi, ki: (b, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, head_dim), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((batch * heads, padded, head_dim), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, head_dim), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention",
    )(lens, offs, qf, kf, vf)

    out = out.reshape(batch, heads, padded, head_dim).transpose(0, 2, 1, 3)
    return out[:, :seq]
