"""Microbench: CLASSIC-layout segmented decode step time vs batch rows.

The pinned habermas profile shows ranking/critique phases (per-agent
prompts -> classic layout, per-row 1024-col trunks) decoding 768-token
budgets in 32-row dispatches at ~12.6 ms/step — while per-step cost is
dominated by the weight read, i.e. nearly flat in rows.  If a 64- or
96-row classic decode holds (HBM: per-row int8 trunk 54 MB) the phase
cost per row-token drops accordingly.  This script measures it directly:
prefill + segmented decode at B in {32, 48, 64, 96}, ctx 1024, budget
768, int8 weights + kv_quant (the production config).

Usage: PYTHONPATH=. python scripts/classic_decode_bench.py   (on the chip)
"""

from __future__ import annotations

import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from consensus_tpu.models.config import get_model_config
from consensus_tpu.models.generate import generate_tokens_segmented
from consensus_tpu.models.quant import quantize_params
from consensus_tpu.models.transformer import init_params

CTX = int(os.environ.get("BENCH_CTX", "1024"))
MAX_NEW = int(os.environ.get("BENCH_MAX_NEW", "768"))
SEG_LEN = int(os.environ.get("BENCH_SEG_LEN", "128"))
MODEL = os.environ.get("BENCH_MODEL", "gemma2-2b")
BATCHES = tuple(
    int(b) for b in os.environ.get("BENCH_BATCHES", "32,48,64,96").split(",")
)


def run_arm(params, config, batch):
    rng = np.random.default_rng(0)
    tokens = rng.integers(1, 1000, size=(batch, CTX)).astype(np.int32)
    valid = np.ones((batch, CTX), bool)
    keys = jax.vmap(lambda i: jax.random.fold_in(jax.random.PRNGKey(0), i))(
        jnp.arange(batch)
    )
    args = dict(
        key=keys,
        max_new_tokens=MAX_NEW,
        seg_len=SEG_LEN,
        temperature=jnp.zeros((batch,), jnp.float32),  # greedy: ranking shape
        eos_ids=jnp.asarray([-1], jnp.int32),  # pinned budget: no early exit
        pad_id=0,
        kv_quant=True,
    )
    out = generate_tokens_segmented(
        params, config, jnp.asarray(tokens), jnp.asarray(valid), **args
    )
    np.asarray(out.tokens)  # warm (compile)
    t0 = time.perf_counter()
    out = generate_tokens_segmented(
        params, config, jnp.asarray(tokens), jnp.asarray(valid), **args
    )
    np.asarray(out.tokens)
    wall = time.perf_counter() - t0
    print(
        f"classic-seg int8+kvq B={batch:3d} ctx={CTX} T={MAX_NEW} "
        f"wall={wall:7.2f}s  {1000 * wall / MAX_NEW:6.2f} ms/step  "
        f"{1000 * wall / (MAX_NEW * batch):6.3f} ms/row-token",
        flush=True,
    )


def main():
    config = get_model_config(MODEL)
    print(f"model={MODEL} devices={jax.devices()}", flush=True)
    host = jax.devices("cpu")[0]
    with jax.default_device(host):
        params = init_params(config, jax.random.PRNGKey(0), jnp.bfloat16)
        params = quantize_params(params)
    params = jax.device_put(params)
    for batch in BATCHES:
        try:
            run_arm(params, config, batch)
        except Exception as exc:  # OOM arms report and continue
            print(f"classic-seg B={batch}: FAILED: {type(exc).__name__}: {exc}")


if __name__ == "__main__":
    main()
