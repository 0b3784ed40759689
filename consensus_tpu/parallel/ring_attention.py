"""Ring attention: sequence-parallel exact attention over an ICI ring.

The reference has no long-context machinery at all (SURVEY §5.7 — prompts
are a few hundred tokens); this framework treats long context as first-
class.  For sequences too long for one chip's HBM, shard the sequence axis
across devices and compute EXACT attention by rotating K/V blocks around
the ring with ``lax.ppermute`` while each device keeps only its local Q
block — a streaming-softmax accumulation identical in spirit to
:func:`consensus_tpu.models.transformer.token_logprobs_streamed`'s vocab
tiling, but over the sequence axis and across devices (Ring Attention,
Liu et al. 2023).

Per ring step each device holds one (B, S/K, H, hd) K/V block; peak memory
is O(S/K) per device and the K-1 rotations ride ICI neighbour links.
Causality is enforced with GLOBAL positions, so the result is bitwise
independent of how the sequence was sharded — pinned by tests against
single-device full attention on the 8-virtual-device CPU mesh.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

SEQ_AXIS = "sequence"

_NEG_INF = -1e30


def _attend_block(
    q: jax.Array,  # (B, Sq, H, hd)
    k: jax.Array,  # (B, Skv, H, hd)
    v: jax.Array,  # (B, Skv, H, hd)
    q_pos: jax.Array,  # (B, Sq)
    kv_pos: jax.Array,  # (B, Skv)
    q_valid: jax.Array,  # (B, Sq)
    kv_valid: jax.Array,  # (B, Skv)
    scale: float,
    causal: bool,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One block's (logits-max, sum-exp, weighted-V) contributions."""
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    mask = kv_valid[:, None, None, :] & q_valid[:, None, :, None]
    if causal:
        mask = mask & (kv_pos[:, None, None, :] <= q_pos[:, None, :, None])
    logits = jnp.where(mask, logits, _NEG_INF)
    block_max = jnp.max(logits, axis=-1)  # (B, H, Sq)
    p = jnp.exp(logits - block_max[..., None])
    p = jnp.where(mask, p, 0.0)  # kill exp(-1e30 - max) residue exactly
    block_sum = jnp.sum(p, axis=-1)
    block_out = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)
    return block_max, block_sum, block_out


def _ring_attention_local(
    q, k, v, q_pos, kv_pos, q_valid, kv_valid, *, axis_name: str, scale: float,
    causal: bool, n_shards: int,
):
    """Per-shard body: rotate K/V around the ring, stream the softmax.

    ``n_shards`` is threaded in statically from the mesh (it sizes the
    ppermute ring and the scan length; ``jax.lax.axis_size`` only exists
    on newer jax, and the mesh knows the answer anyway)."""
    batch, s_q, heads, _ = q.shape

    run_max = jnp.full((batch, heads, s_q), _NEG_INF, jnp.float32)
    run_sum = jnp.zeros((batch, heads, s_q), jnp.float32)
    run_out = jnp.zeros(q.shape, jnp.float32)

    perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]

    def step(carry, _):
        run_max, run_sum, run_out, k_blk, v_blk, kv_pos_blk, kv_valid_blk = carry
        blk_max, blk_sum, blk_out = _attend_block(
            q, k_blk, v_blk, q_pos, kv_pos_blk, q_valid, kv_valid_blk,
            scale, causal,
        )
        new_max = jnp.maximum(run_max, blk_max)
        old_scale = jnp.exp(run_max - new_max)
        blk_scale = jnp.exp(blk_max - new_max)
        run_sum = run_sum * old_scale + blk_sum * blk_scale
        run_out = (
            run_out * old_scale.transpose(0, 2, 1)[..., None]
            + blk_out.astype(jnp.float32)
            * blk_scale.transpose(0, 2, 1)[..., None]
        )
        # Rotate K/V (+ their positions/masks) one hop around the ring.
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        kv_pos_blk = jax.lax.ppermute(kv_pos_blk, axis_name, perm)
        kv_valid_blk = jax.lax.ppermute(kv_valid_blk, axis_name, perm)
        return (new_max, run_sum, run_out, k_blk, v_blk, kv_pos_blk, kv_valid_blk), None

    carry = (run_max, run_sum, run_out, k, v, kv_pos, kv_valid)
    (run_max, run_sum, run_out, *_), _ = jax.lax.scan(
        step, carry, None, length=n_shards
    )
    out = run_out / jnp.maximum(run_sum, 1e-30).transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def ring_self_attention(
    mesh: Mesh,
    q: jax.Array,  # (B, S, H, hd) — S divisible by the sequence-axis size
    k: jax.Array,
    v: jax.Array,
    positions: jax.Array,  # (B, S) global positions
    valid: jax.Array,  # (B, S)
    scale: Optional[float] = None,
    causal: bool = True,
    axis_name: str = SEQ_AXIS,
) -> jax.Array:
    """Exact attention with the sequence axis sharded over ``axis_name``.

    Inputs/outputs are global arrays; shard_map splits them over the mesh's
    sequence axis and XLA lays the ppermute hops on ICI neighbours.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5

    spec_qkv = P(None, axis_name, None, None)
    spec_2d = P(None, axis_name)

    body = functools.partial(
        _ring_attention_local, axis_name=axis_name, scale=scale,
        causal=causal, n_shards=int(mesh.shape[axis_name]),
    )
    # The replication check stays off: the ring accumulator is deliberately
    # unreplicated.
    sharded = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(spec_qkv, spec_qkv, spec_qkv, spec_2d, spec_2d, spec_2d, spec_2d),
        out_specs=spec_qkv,
        check_vma=False,
    )
    return sharded(q, k, v, positions, positions, valid, valid)


def make_sequence_mesh(n_devices: Optional[int] = None) -> Mesh:
    """1-D mesh over the sequence axis (context parallelism)."""
    devices = jax.devices()[: n_devices or len(jax.devices())]
    import numpy as np

    return Mesh(np.array(devices), (SEQ_AXIS,))


def full_attention_reference(
    q, k, v, positions, valid, scale: Optional[float] = None, causal: bool = True
):
    """Single-device exact attention used as the numerical oracle in tests."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    blk_max, blk_sum, blk_out = _attend_block(
        q, k, v, positions, positions, valid, valid, scale, causal
    )
    out = blk_out.astype(jnp.float32) / jnp.maximum(
        blk_sum, 1e-30
    ).transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)
