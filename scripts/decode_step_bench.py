"""Microbench: shared-trunk decode step time vs batch / quant / sampling.

The habermas cell profile (scripts/profile_habermas_cell.py) shows the
64-row x 768-step shared-trunk decode dispatch running at ~44 ms/step
against a ~6.5 ms HBM roofline (int8 weights 2.6 GB + avg tail KV ~2.6 GB
+ trunk 0.1 GB at 820 GB/s).  This script isolates the per-step cost
drivers by timing generate_tokens_shared_trunk with pinned budget (no
early exit) across arms:

- batch in {8, 32, 64}
- int8 vs bf16 weights
- greedy-ish sampling (top_k=1) vs full categorical (the production arm)
- short vs long tails (max_new 128 vs 768)

Usage: PYTHONPATH=. python scripts/decode_step_bench.py   (on the chip)
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from consensus_tpu.models.config import get_model_config
from consensus_tpu.models.generate import generate_tokens_shared_trunk
from consensus_tpu.models.quant import quantize_params
from consensus_tpu.models.transformer import init_params

CTX = 1024
MODEL = "gemma2-2b"


def run_segmented_arm(params, config, batch, max_new, seg_len, label,
                      kv_quant=False):
    from consensus_tpu.models.generate import (
        generate_tokens_shared_trunk_segmented,
    )

    tokens = np.zeros((1, CTX), np.int32)
    valid = np.ones((1, CTX), bool)
    keys = jax.vmap(lambda i: jax.random.fold_in(jax.random.PRNGKey(0), i))(
        jnp.arange(batch)
    )
    args = dict(
        batch=batch,
        key=keys,
        max_new_tokens=max_new,
        seg_len=seg_len,
        temperature=jnp.ones((batch,), jnp.float32),
        eos_ids=jnp.asarray([-1], jnp.int32),
        pad_id=0,
        kv_quant=kv_quant,
    )
    out = generate_tokens_shared_trunk_segmented(
        params, config, jnp.asarray(tokens), jnp.asarray(valid), **args
    )
    np.asarray(out.tokens)
    t0 = time.perf_counter()
    out = generate_tokens_shared_trunk_segmented(
        params, config, jnp.asarray(tokens), jnp.asarray(valid), **args
    )
    np.asarray(out.tokens)
    wall = time.perf_counter() - t0
    print(
        f"{label:44s} B={batch:3d} T={max_new:4d} "
        f"wall={wall:7.2f}s  {1000 * wall / max_new:7.2f} ms/step"
    )


def run_arm(params, config, batch, max_new, top_k, label):
    tokens = np.zeros((1, CTX), np.int32)
    valid = np.ones((1, CTX), bool)
    keys = jax.vmap(lambda i: jax.random.fold_in(jax.random.PRNGKey(0), i))(
        jnp.arange(batch)
    )
    args = dict(
        batch=batch,
        key=keys,
        max_new_tokens=max_new,
        temperature=jnp.ones((batch,), jnp.float32),
        eos_ids=jnp.asarray([-1], jnp.int32),  # pinned: no early exit
        pad_id=0,
    )
    if top_k:
        args["top_k"] = top_k
    out = generate_tokens_shared_trunk(
        params, config, jnp.asarray(tokens), jnp.asarray(valid), **args
    )
    np.asarray(out.tokens)  # wait for the device
    t0 = time.perf_counter()
    out = generate_tokens_shared_trunk(
        params, config, jnp.asarray(tokens), jnp.asarray(valid), **args
    )
    np.asarray(out.tokens)
    wall = time.perf_counter() - t0
    print(
        f"{label:44s} B={batch:3d} T={max_new:4d} "
        f"wall={wall:7.2f}s  {1000 * wall / max_new:7.2f} ms/step"
    )


def main() -> None:
    config = get_model_config(MODEL)
    params_bf16 = init_params(config, jax.random.PRNGKey(0), dtype=jnp.bfloat16)
    params_int8 = quantize_params(params_bf16)
    del params_bf16  # holding both param sets + the tail OOMs a 16 GB chip

    import os

    arms = os.environ.get("BENCH_ARMS", "all")
    if arms in ("all", "mono"):
        run_arm(params_int8, config, 64, 768, 0, "int8, categorical (production)")
        run_arm(params_int8, config, 64, 768, 1, "int8, top_k=1")
        run_arm(params_int8, config, 32, 768, 0, "int8, categorical")
        run_arm(params_int8, config, 8, 768, 0, "int8, categorical")
        run_arm(params_int8, config, 64, 128, 0, "int8, categorical, short tail")
        run_arm(params_int8, config, 1, 128, 0, "int8, categorical, B=1")
    if arms in ("all", "seg"):
        run_segmented_arm(params_int8, config, 64, 768, 128, "int8, SEGMENTED s=128")
        run_segmented_arm(params_int8, config, 64, 768, 96, "int8, SEGMENTED s=96")
        # Round 3's frozen-concat transient OOMed raw B=96 at T=768; the
        # round-4 block-list design (no concat) lifts the bf16 allowance to
        # ~96 and the int8-KV allowance to ~192 on a 16 GB chip.
        run_segmented_arm(params_int8, config, 48, 768, 128, "int8, SEGMENTED s=128")
    if arms in ("all", "kvq"):
        run_segmented_arm(params_int8, config, 64, 768, 128,
                          "int8, SEGMENTED s=128, int8 KV", kv_quant=True)
        run_segmented_arm(params_int8, config, 96, 768, 128,
                          "int8, SEGMENTED s=128, int8 KV", kv_quant=True)
    if arms == "r4c":
        # Classic layout (per-row prompt trunks — habermas ranking/critique
        # phases): the B x ctx trunk is the dominant per-step read; under
        # kv_quant it is int8 after prefill.
        from consensus_tpu.models.generate import generate_tokens_segmented

        def run_classic(batch, kv_quant, label):
            tokens = np.asarray(
                jax.random.randint(
                    jax.random.PRNGKey(2), (batch, CTX), 1, 255, jnp.int32
                )
            )
            valid = np.ones((batch, CTX), bool)
            keys = jax.vmap(
                lambda i: jax.random.fold_in(jax.random.PRNGKey(0), i)
            )(jnp.arange(batch))
            args = dict(
                key=keys, max_new_tokens=768, seg_len=128,
                temperature=jnp.ones((batch,), jnp.float32),
                eos_ids=jnp.asarray([-1], jnp.int32), pad_id=0,
                kv_quant=kv_quant,
            )
            out = generate_tokens_segmented(
                params_int8, config, jnp.asarray(tokens), jnp.asarray(valid), **args
            )
            np.asarray(out.tokens)
            t0 = time.perf_counter()
            out = generate_tokens_segmented(
                params_int8, config, jnp.asarray(tokens), jnp.asarray(valid), **args
            )
            np.asarray(out.tokens)
            wall = time.perf_counter() - t0
            print(
                f"{label:44s} B={batch:3d} T= 768 "
                f"wall={wall:7.2f}s  {1000 * wall / 768:7.2f} ms/step"
            )

        run_classic(32, False, "int8, CLASSIC SEGMENTED s=128")
        run_classic(32, True, "int8, CLASSIC SEGMENTED s=128, int8 KV+trunk")
        run_classic(48, True, "int8, CLASSIC SEGMENTED s=128, int8 KV+trunk")
    if arms == "r4":
        # Round-4 arms: per-ROW throughput is the metric that moves the
        # sweep (weights amortize over rows); the block-list + int8-tail
        # allowance admits 192 rows at the 768 budget.
        run_segmented_arm(params_int8, config, 64, 768, 128,
                          "int8, SEGMENTED s=128 (r4 blocks)")
        run_segmented_arm(params_int8, config, 64, 768, 128,
                          "int8, SEGMENTED s=128, int8 KV", kv_quant=True)
        run_segmented_arm(params_int8, config, 96, 768, 128,
                          "int8, SEGMENTED s=128, int8 KV", kv_quant=True)
        run_segmented_arm(params_int8, config, 128, 768, 128,
                          "int8, SEGMENTED s=128, int8 KV", kv_quant=True)
        run_segmented_arm(params_int8, config, 192, 768, 128,
                          "int8, SEGMENTED s=128, int8 KV", kv_quant=True)
    if arms in ("all", "bf16"):
        del params_int8
        params_bf16 = init_params(config, jax.random.PRNGKey(1), dtype=jnp.bfloat16)
        run_arm(params_bf16, config, 32, 768, 0, "bf16, categorical")


if __name__ == "__main__":
    main()
