"""BENCH_MESH cell: statements/sec scaling of the mesh serving path.

Runs the continuous-batching engine over an emulated 8-device CPU mesh
(``--xla_force_host_platform_device_count``) at dp=1 and dp=4 and prints
ONE JSON object:

* ``mesh_scaling_efficiency`` — statements/sec at dp=4 over 4x the dp=1
  rate.  Both widths run inside the SAME 8-virtual-device topology (dp=1
  is one emulated device of the eight), so the comparison isolates what
  the mesh actually buys the engine: dp pools carry dp× the aggregate KV
  capacity, so the decode cohort runs dp× wider at the same per-iteration
  dispatch cost.  (Emulated devices share the host's silicon — raw-FLOP
  scaling is only observable on real chips; capacity/batch-width scaling,
  the serving bottleneck this cell pins, is observable here.)
* ``texts_match_dp`` — dp=1 and dp=4 statements are identical (the
  MULTICHIP dryrun invariant, promoted to the bench + pytest).
* ``dp1_byte_identical_to_engine`` — the dp=1/tp=1 mesh path returns the
  exact bytes of the plain single-device engine path (PR 6).

Runs in a SUBPROCESS of bench.py (BENCH_MESH cell): the parent process
has already initialized the real TPU platform, and a JAX process cannot
re-initialize as 8 virtual CPU devices — so this module is also a
standalone CLI:

    JAX_PLATFORMS=cpu python -m consensus_tpu.cli.bench_mesh
"""

from __future__ import annotations

import json
import os
import time
from concurrent.futures import ThreadPoolExecutor

N_DEVICES = 8
N_REQUESTS = int(os.environ.get("BENCH_MESH_REQUESTS", "16"))
MAX_TOKENS = int(os.environ.get("BENCH_MESH_TOKENS", "8"))
N_TRIALS = max(1, int(os.environ.get("BENCH_MESH_TRIALS", "3")))
PAGE_SIZE = 16
DP_WIDE = 4


def _force_cpu_devices(n: int) -> None:
    """8 virtual CPU devices, dryrun_multichip-style: must run before the
    first backend initialization."""
    import jax

    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}"
        ).strip()
    jax.config.update("jax_platforms", "cpu")
    if len(jax.devices()) < n:
        raise RuntimeError(
            f"need {n} devices, have {len(jax.devices())} — set XLA_FLAGS "
            "before the first JAX backend initialization"
        )


def _requests():
    from consensus_tpu.backends.base import GenerationRequest

    return [
        GenerationRequest(
            user_prompt=f"Draft a one-line consensus statement on issue {i}.",
            max_tokens=MAX_TOKENS,
            temperature=0.8,
            seed=100 + i,
            chat=False,
        )
        for i in range(N_REQUESTS)
    ]


def _run_engine(backend, mesh, num_pages, registry):
    """Drive N_REQUESTS one-per-session through the engine; returns
    (texts, wall_s, generate_dispatches)."""
    from consensus_tpu.backends.batching import BatchingBackend

    batching = BatchingBackend(
        backend,
        registry=registry,
        engine=True,
        engine_options={
            "slots": N_DEVICES,
            "page_size": PAGE_SIZE,
            "num_pages": num_pages,
            **({"mesh": mesh} if mesh is not None else {}),
        },
    )
    reqs = _requests()

    def drive():
        with ThreadPoolExecutor(max_workers=N_REQUESTS) as pool:
            futures = [pool.submit(batching.generate, [r]) for r in reqs]
            return [f.result()[0].text for f in futures]

    try:
        drive()  # warmup: compiles every cohort-width bucket
        # min over trials: host scheduling noise only ever ADDS wall, so
        # the fastest trial is the cleanest capacity measurement.
        wall, texts, dispatches = None, None, None
        for _ in range(N_TRIALS):
            before = batching.engine.dispatch_counts["generate"]
            start = time.perf_counter()
            trial_texts = drive()
            trial_wall = time.perf_counter() - start
            if wall is None or trial_wall < wall:
                wall = trial_wall
                texts = trial_texts
                dispatches = (
                    batching.engine.dispatch_counts["generate"] - before
                )
            assert trial_texts == texts  # determinism across trials
    finally:
        batching.close()
    return texts, wall, dispatches


def main() -> int:
    _force_cpu_devices(N_DEVICES)

    from consensus_tpu.backends.tpu import TPUBackend
    from consensus_tpu.obs.metrics import Registry

    base = TPUBackend(model="tiny-gemma2", max_context=256)
    # Per-shard pool sized to exactly ONE resident row: capacity — and with
    # it the decode cohort width — then scales 1:1 with dp, which is the
    # mesh's serving story.  (Every pool is per-shard, mirroring per-chip
    # HBM: dp chips really do carry dp x the pages.)
    tok = base.tokenizer
    prompt_tokens = max(
        len(tok.encode(r.user_prompt)) for r in _requests()
    )
    pages_per_row = -(-(prompt_tokens + MAX_TOKENS) // PAGE_SIZE)
    num_pages = pages_per_row

    # PR 6 single-device engine path — the byte-identity reference.
    plain_texts, _, _ = _run_engine(base, None, num_pages, Registry())

    # dp=1/tp=1 mesh path on the same backend/params.
    dp1_texts, dp1_wall, dp1_disp = _run_engine(
        base, {"dp": 1, "tp": 1}, num_pages, Registry()
    )

    # dp=4: backend sharded over 4 of the 8 emulated devices (params
    # replicate over data; batch rows shard), engine partitioned 4-ways.
    wide = TPUBackend(
        model="tiny-gemma2", max_context=256, dp=DP_WIDE,
        params=base.params, config=base.config,
    )
    dp4_texts, dp4_wall, dp4_disp = _run_engine(
        wide, {"dp": DP_WIDE, "tp": 1}, num_pages, Registry()
    )

    sps1 = N_REQUESTS / dp1_wall
    sps4 = N_REQUESTS / dp4_wall
    print(json.dumps({
        "bench_mesh": {
            "model": "tiny-gemma2",
            "emulated_devices": N_DEVICES,
            "requests": N_REQUESTS,
            "max_tokens": MAX_TOKENS,
            "trials": N_TRIALS,
            "kv_pages_per_shard": num_pages,
            "dp1_statements_per_sec": round(sps1, 3),
            "dp4_statements_per_sec": round(sps4, 3),
            "dp1_wall_s": round(dp1_wall, 3),
            "dp4_wall_s": round(dp4_wall, 3),
            "dp1_generate_dispatches": dp1_disp,
            "dp4_generate_dispatches": dp4_disp,
            "mesh_scaling_efficiency": round(sps4 / (DP_WIDE * sps1), 3),
            "texts_match_dp": dp1_texts == dp4_texts,
            "dp1_byte_identical_to_engine": dp1_texts == plain_texts,
            "note": (
                "efficiency = sps(dp=4) / (4 * sps(dp=1)), min wall over "
                f"{N_TRIALS} trials per width, both inside the "
                "same 8-virtual-device CPU topology; per-shard pools hold "
                "one row, so the decode cohort is dp-wide and the win is "
                "capacity/batch-width scaling (per-iteration dispatch cost "
                "is ~width-independent, as on real HBM-bound decode)"
            ),
        }
    }))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
