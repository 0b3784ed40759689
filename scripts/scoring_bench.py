"""Scoring-phase microbench: where does teacher-forced scoring lose its 10x?

VERDICT r3 #3: scoring is prefill-shaped and should run at 30-50% MFU, but
the sweep's combined cells clock ~0.35-0.5 s per 1k scored tokens (~5% of
v5e bf16 peak).  This script times the two production scorers warm at
sweep shapes and splits model-forward cost from the streamed-logsumexp
cost (the vocab projection sweeps the full 256k x 2304 head per call):

- token_logprobs_streamed (classic: B rows x S columns)
- shared_context_token_logprobs (shared: 1 ctx row + P x L continuations)
- forward-only arms (return_hidden, no head sweep) isolate the logsumexp.

Prints achieved TFLOP/s against the model-forward FLOPs (2 * params *
tokens) and against total useful FLOPs (incl. the head sweep), so the
padding/compute split is explicit.

Usage: PYTHONPATH=. python scripts/scoring_bench.py   (on the chip)
"""

from __future__ import annotations

import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from consensus_tpu.models.config import get_model_config
from consensus_tpu.models.quant import quantize_params
from consensus_tpu.models.transformer import (
    forward,
    init_params,
    shared_context_token_logprobs,
    token_logprobs_streamed,
)

from consensus_tpu.utils.mfu import device_peaks, param_count  # noqa: E402

MODEL = "gemma2-2b"


def bench(label, fn, flops_model=0.0, flops_total=0.0, repeats=3):
    out = fn()
    np.asarray(out[0] if isinstance(out, tuple) else out)  # warm compile
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn()
        np.asarray(out[0] if isinstance(out, tuple) else out)  # wait for it
        best = min(best, time.perf_counter() - t0)
    # Raises on a device whose peak is not published: no default chip.
    PEAK_TFLOPS = device_peaks(jax.devices()[0].device_kind).bf16_tflops
    mfu_m = flops_model / best / 1e12 / PEAK_TFLOPS * 100 if flops_model else 0
    mfu_t = flops_total / best / 1e12 / PEAK_TFLOPS * 100 if flops_total else 0
    print(
        f"{label:52s} {best:7.3f}s  model-MFU {mfu_m:5.1f}%  "
        f"total-MFU {mfu_t:5.1f}%"
    )
    return best


def main() -> None:
    config = get_model_config(MODEL)
    params = quantize_params(init_params(config, jax.random.PRNGKey(0), jnp.bfloat16))
    import dataclasses

    config = dataclasses.replace(config, use_flash_attention=True)
    n_params = param_count(config)
    # 2*n_params includes the head matmul ONCE (utils/mfu.py convention) —
    # split it out so the forward-only arms (no head sweep) are credited
    # only the body FLOPs and the streamed arms don't double-count it.
    head_flops_per_slot = 2 * config.d_model * config.vocab_size
    body_flops_per_slot = 2 * n_params - head_flops_per_slot

    key = jax.random.PRNGKey(1)

    def classic_arm(batch, width):
        tokens = jax.random.randint(key, (batch, width), 1, 255, jnp.int32)
        valid = jnp.ones((batch, width), bool)
        slots = batch * width
        fwd = body_flops_per_slot * slots
        tot = 2 * n_params * slots
        bench(
            f"classic streamed B={batch} S={width}",
            lambda: token_logprobs_streamed(params, config, tokens, valid),
            flops_model=fwd, flops_total=tot,
        )
        bench(
            f"classic forward-only B={batch} S={width}",
            lambda: forward(
                params, config, tokens,
                jnp.maximum(jnp.cumsum(valid.astype(jnp.int32), axis=1) - 1, 0),
                valid, return_hidden=True,
            )[0],
            flops_model=fwd, flops_total=fwd,
        )

    def shared_arm(p, l, ctx):
        ctx_tokens = jax.random.randint(key, (1, ctx), 1, 255, jnp.int32)
        ctx_valid = jnp.ones((1, ctx), bool)
        cont = jax.random.randint(key, (p, l), 1, 255, jnp.int32)
        cont_valid = jnp.ones((p, l), bool)
        slots = p * l
        fwd = body_flops_per_slot * (slots + ctx)
        tot = fwd + head_flops_per_slot * slots
        bench(
            f"shared-context P={p} L={l} ctx={ctx}",
            lambda: shared_context_token_logprobs(
                params, config, ctx_tokens, ctx_valid, cont, cont_valid
            ),
            flops_model=fwd, flops_total=tot,
        )

    arms = os.environ.get("BENCH_ARMS", "all")
    if arms in ("all", "classic"):
        classic_arm(32, 1024)
        classic_arm(32, 384)
        classic_arm(64, 384)
    if arms in ("all", "shared"):
        shared_arm(32, 192, 1024)
        shared_arm(64, 192, 1024)
        shared_arm(32, 64, 1024)


if __name__ == "__main__":
    main()
