"""Benchmark: REAL-stack consensus-statement throughput on device.

Drives the production pipeline end-to-end — ``BestOfNGenerator`` /
``BeamSearchGenerator`` over ``TPUBackend`` — including tokenization,
prompt templating, host<->device round-trips, per-request PRNG folds, and
the egalitarian-welfare selection, on the paper's scenario-2 text (5
agents).  This measures the framework, not a hand-rolled kernel loop.

TWO regimes, labeled explicitly in the JSON (VERDICT r2 weak #5):

* ``throughput`` (HEADLINE): N_CONCURRENT best-of-N statements co-batched
  through ``BatchingBackend`` — the sweep regime the north star is judged
  on (a sweep cell's 25-30 runs co-batch the same way).
* ``latency``: one statement at a time — the interactive
  single-statement cost.

Headline (BASELINE.json): best-of-N statements/sec, Gemma-2B, 5 agents,
N=32 candidates, 50 new tokens.  API baseline: 61-77 s/statement
(BASELINE.md) -> ~1/70 st/s.  The ``extra`` field reports token-level beam
search (beam 4, 50 tokens), the reference's worst case: 4019-5117
s/statement on the API.

Weights are random (no checkpoint ships with the repo) — throughput/shapes
are real, statement text is noise.  Runs the production fast path
(weight-only int8 + shared-context scoring, models/quant.py) unless
BENCH_QUANT=none / BENCH_SHARED_SCORING=0.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "extra"}.

NOTE: every timed call ends in a fetch of its results to the host
(np.asarray), which waits for the device.  This file runs on any platform
and stamps no device; ``chip_smoke.py`` is the proof that the path runs on
the chip, and the chip benchmark replaces this file (ROADMAP A0, D8).
"""

from __future__ import annotations

import json
import logging
import os
import statistics
import time
from concurrent.futures import ThreadPoolExecutor

logging.disable(logging.WARNING)  # keep the single-JSON-line contract

N_CANDIDATES = int(os.environ.get("BENCH_N", "32"))
NEW_TOKENS = int(os.environ.get("BENCH_TOKENS", "50"))
N_CONCURRENT = int(os.environ.get("BENCH_CONCURRENT", "8"))  # throughput regime
#: Headline trials: report the median of >=3 trials with min/max so
#: regression and noise are distinguishable.
N_TRIALS = max(1, int(os.environ.get("BENCH_TRIALS", "3")))
BON_LATENCY_ROUNDS = 2
BASELINE_BON_STATEMENTS_PER_SEC = 1.0 / 70.0
BASELINE_BEAM_STATEMENTS_PER_SEC = 1.0 / 4019.0
BASELINE_LOOKAHEAD_STATEMENTS_PER_SEC = 1.0 / 944.0

# Paper scenario 2 (5 agents) — consensus_tpu/data/aamas_scenarios.py.
from consensus_tpu.data.aamas_scenarios import SCENARIOS  # noqa: E402

SCENARIO = SCENARIOS[2]


def main() -> None:
    from consensus_tpu.backends.batching import BatchingBackend
    from consensus_tpu.backends.tpu import TPUBackend
    from consensus_tpu.methods import get_method_generator

    quantization = os.environ.get("BENCH_QUANT", "int8")  # production fast path
    shared_scoring = os.environ.get("BENCH_SHARED_SCORING", "1") != "0"
    backend = TPUBackend(
        model=os.environ.get("BENCH_MODEL", "gemma2-2b"),  # tiny-gemma2: CI smoke
        dtype="bfloat16",
        max_context=1024,
        use_flash_attention=True,
        base_seed=0,
        max_batch_rows=32,
        quantization=None if quantization in ("", "none") else quantization,
        shared_context_scoring=shared_scoring,
    )
    issue = SCENARIO["issue"]
    opinions = dict(SCENARIO["agent_opinions"])

    def one_bon(seed: int, engine) -> str:
        generator = get_method_generator(
            "best_of_n",
            engine,
            {"n": N_CANDIDATES, "max_tokens": NEW_TOKENS, "seed": seed,
             "temperature": 1.0},
        )
        return generator.generate_statement(issue, opinions)

    # ---- throughput regime (HEADLINE): co-batched statements ---------
    def bon_cobatched(seed0: int) -> float:
        """Run N_CONCURRENT statements through one BatchingBackend (the
        sweep regime, experiment.py's concurrent path); returns wall s."""
        batching = BatchingBackend(
            backend,
            flush_ms=float(os.environ.get("BENCH_FLUSH_MS", "10")),
            expected_sessions=N_CONCURRENT,
        )

        def worker(i: int) -> str:
            with batching.session():
                return one_bon(seed0 + i, batching)

        start = time.perf_counter()
        with ThreadPoolExecutor(max_workers=N_CONCURRENT) as pool:
            statements = list(pool.map(worker, range(N_CONCURRENT)))
        elapsed = time.perf_counter() - start
        assert all(isinstance(s, str) for s in statements)
        return elapsed

    from consensus_tpu.obs import (
        bucket_recompiles,
        diff_snapshots,
        get_registry,
        padding_efficiency,
    )

    bon_cobatched(7000)  # warmup / compile (wide co-batched shapes)
    tokens_before = dict(backend.token_counts)  # after warmup: timed runs only
    metrics_before = get_registry().snapshot()
    trial_walls = [bon_cobatched(100 + 1000 * t) for t in range(N_TRIALS)]
    tokens_after = dict(backend.token_counts)
    metrics_timed = diff_snapshots(metrics_before, get_registry().snapshot())
    throughput_wall = statistics.median(trial_walls)
    throughput_sps = N_CONCURRENT / throughput_wall
    # min wall = max st/s and vice versa: spread bounds for the headline.
    throughput_sps_max = N_CONCURRENT / min(trial_walls)
    throughput_sps_min = N_CONCURRENT / max(trial_walls)

    # ---- continuous-batching engine cell (PR 6 tentpole) -------------
    # The SAME co-batched best-of-N workload, but through
    # BatchingBackend(engine=True): iteration-level slot scheduling over
    # the paged KV pool instead of the flush-snapshot barrier.  Results
    # are byte-identical (tests/test_engine.py); the deltas worth
    # reporting are statements/sec, slot occupancy, and padding
    # efficiency.  Goal (ROADMAP): >=3x legacy bon throughput
    # (0.15 -> >=0.45 st/s) at >=15% of v5e bf16 peak.  BENCH_ENGINE=0
    # skips; BENCH_ENGINE_SLOTS resizes the slot table.
    engine_extra = {}
    if os.environ.get("BENCH_ENGINE", "1") != "0":
        engine_slots = int(
            os.environ.get("BENCH_ENGINE_SLOTS", str(max(8, N_CONCURRENT))))

        def bon_engine(seed0: int):
            batching = BatchingBackend(
                backend, engine=True,
                engine_options={"slots": engine_slots},
            )
            try:
                def worker(i: int) -> str:
                    with batching.session():
                        return one_bon(seed0 + i, batching)

                start = time.perf_counter()
                with ThreadPoolExecutor(max_workers=N_CONCURRENT) as pool:
                    statements = list(pool.map(worker, range(N_CONCURRENT)))
                elapsed = time.perf_counter() - start
                assert all(isinstance(s, str) for s in statements)
                stats = batching.engine.stats()
            finally:
                batching.close()
            return elapsed, stats

        # Per-trial compile warmup is reported, not hidden: the engine's
        # paged programs compile once per slot-table shape, and that wall
        # belongs in the record even though steady-state trials skip it.
        warmup_start = time.perf_counter()
        bon_engine(9000)
        engine_warmup_wall_s = time.perf_counter() - warmup_start
        engine_before = get_registry().snapshot()
        engine_trials = []
        engine_stats = {}
        for t in range(N_TRIALS):
            wall, engine_stats = bon_engine(200 + 1000 * t)
            engine_trials.append(wall)
        engine_delta = diff_snapshots(engine_before, get_registry().snapshot())
        engine_wall = statistics.median(engine_trials)
        engine_sps = N_CONCURRENT / engine_wall
        engine_pad = padding_efficiency(engine_delta)
        # Where the engine's wall time actually went (ISSUE 14 ledger):
        # device dispatch vs host bookkeeping vs idle, over the last trial's
        # iterations.  host_fraction is the ROADMAP-3 number — the share of
        # engine wall the per-iteration host round-trip costs.
        engine_mfu = engine_stats.get("mfu_attribution") or {}
        # KV-page accounting: capacity is the pool SIZE, high-water the
        # most pages ever simultaneously in use — report both plus the
        # ratio, clearly named (a raw capacity next to a high-water number
        # reads like a 5-orders-of-magnitude leak).
        kv_capacity = engine_stats.get("kv_pages")
        kv_high_water = engine_stats.get("kv_pages_high_water")
        kv_util = (
            round(kv_high_water / kv_capacity, 4)
            if kv_capacity and kv_high_water is not None else None
        )

        # ---- multi-token decode comparison (PR 15) -------------------
        # The same workload with decode_steps=8: one K-step on-device
        # dispatch per cohort instead of one host round-trip per token.
        # On this CPU CI-smoke regime device work is host-synchronous, so
        # the overlap win is structural (host iterations per token), not
        # wall clock — the throughput ratio means nothing off the chip.
        k1_sps = engine_sps
        wall_k8, stats_k8 = None, {}

        def bon_engine_k(seed0: int, decode_steps: int):
            batching = BatchingBackend(
                backend, engine=True,
                engine_options={"slots": engine_slots,
                                "decode_steps": decode_steps},
            )
            try:
                def worker(i: int) -> str:
                    with batching.session():
                        return one_bon(seed0 + i, batching)

                start = time.perf_counter()
                with ThreadPoolExecutor(max_workers=N_CONCURRENT) as pool:
                    statements = list(pool.map(worker, range(N_CONCURRENT)))
                elapsed = time.perf_counter() - start
                assert all(isinstance(s, str) for s in statements)
                stats = batching.engine.stats()
            finally:
                batching.close()
            return elapsed, stats

        if os.environ.get("BENCH_ENGINE_MULTITOKEN", "1") != "0":
            bon_engine_k(9000, 8)  # warmup the K=8 program shapes
            wall_k8, stats_k8 = bon_engine_k(200, 8)
        mfu_k8 = stats_k8.get("mfu_attribution") or {}
        k8_tokens = mfu_k8.get("tokens") or 0
        engine_extra = {
            "engine_statements_per_sec": round(engine_sps, 4),
            "engine_mfu_device_fraction": engine_mfu.get("device_fraction"),
            "engine_mfu_host_fraction": engine_mfu.get("host_fraction"),
            "engine_mfu_idle_fraction": engine_mfu.get("idle_fraction"),
            "engine_mfu_dispatch_fraction": engine_mfu.get(
                "dispatch_fraction"),
            "engine_mfu_block_fraction": engine_mfu.get("block_fraction"),
            "engine_mfu_host_breakdown": engine_mfu.get("host_breakdown"),
            "engine_mfu_coverage": engine_mfu.get("coverage"),
            "engine_trial_walls_s": [round(w, 2) for w in engine_trials],
            "warmup_wall_s": round(engine_warmup_wall_s, 2),
            "engine_slots": engine_slots,
            "engine_slot_occupancy_mean": round(
                engine_stats.get("slot_occupancy_mean", 0.0), 4),
            "engine_kv_pages_capacity": kv_capacity,
            "engine_kv_pages_high_water": kv_high_water,
            "engine_kv_pages_utilization": kv_util,
            "engine_padding_efficiency": (
                round(engine_pad, 4) if engine_pad is not None else None),
            "engine_bucket_recompiles_timed_window": bucket_recompiles(
                engine_delta),
            "engine_vs_legacy_throughput": round(
                engine_sps / throughput_sps, 2),
            "engine_goal": ">=3x legacy bon throughput (0.15 -> >=0.45 "
                           "st/s) and throughput_pct_of_bf16_peak "
                           ">= 15",
        }
        if wall_k8 is not None:
            engine_extra.update({
                "engine_k8_statements_per_sec": round(
                    N_CONCURRENT / wall_k8, 4),
                "engine_k8_vs_k1_throughput": round(
                    (N_CONCURRENT / wall_k8) / k1_sps, 2),
                "engine_k8_host_iterations_per_token": (
                    round(stats_k8.get("iterations", 0) / k8_tokens, 4)
                    if k8_tokens else None),
                "engine_k8_tokens_per_dispatch": round(
                    stats_k8.get("tokens_per_dispatch_mean", 0.0), 2),
                "engine_k1_tokens_per_dispatch": round(
                    engine_stats.get("tokens_per_dispatch_mean", 0.0), 2),
                "engine_k8_mfu_dispatch_fraction": mfu_k8.get(
                    "dispatch_fraction"),
                "engine_k8_mfu_block_fraction": mfu_k8.get("block_fraction"),
                "engine_k8_note": (
                    "CPU CI-smoke regime: device execution is "
                    "host-synchronous, so the K=8 async-dispatch overlap "
                    "shows as fewer host iterations per token, not wall "
                    "clock; the >=20%-of-peak throughput check needs a "
                    "chip."),
            })

    # ---- latency regime: one statement at a time ---------------------
    # The latency / beam / lookahead cells compile the narrow single-cell
    # and token-search session shapes, which dominates wall time on CPU
    # smoke runs.  BENCH_LATENCY=0 skips all three (their report keys are
    # omitted); default stays on.
    latency_extra = {}
    if os.environ.get("BENCH_LATENCY", "1") != "0":
        one_bon(7, backend)  # warmup (narrow single-cell shapes)
        start = time.perf_counter()
        for i in range(BON_LATENCY_ROUNDS):
            one_bon(500 + i, backend)
        bon_latency_s = (time.perf_counter() - start) / BON_LATENCY_ROUNDS

        # ---- token-level beam search (reference worst case) ----------
        def one_beam(seed: int) -> str:
            generator = get_method_generator(
                "beam_search",
                backend,
                {"beam_width": 4, "max_tokens": NEW_TOKENS, "seed": seed},
            )
            return generator.generate_statement(issue, opinions)

        one_beam(11)  # warmup / compile
        start = time.perf_counter()
        beam_statement = one_beam(12)
        beam_elapsed = time.perf_counter() - start
        assert isinstance(beam_statement, str)
        beam_sps = 1.0 / beam_elapsed

        # ---- finite lookahead (bf=3, depth=3: the deepest grid) ------
        def one_lookahead(seed: int) -> str:
            generator = get_method_generator(
                "finite_lookahead",
                backend,
                {"branching_factor": 3, "max_depth": 3,
                 "max_tokens": NEW_TOKENS, "seed": seed},
            )
            return generator.generate_statement(issue, opinions)

        one_lookahead(21)  # warmup / compile
        start = time.perf_counter()
        lookahead_statement = one_lookahead(22)
        lookahead_elapsed = time.perf_counter() - start
        assert isinstance(lookahead_statement, str)
        lookahead_sps = 1.0 / lookahead_elapsed

        latency_extra = {
            "bon_latency_seconds_per_statement": round(bon_latency_s, 2),
            "bon_latency_statements_per_sec": round(1.0 / bon_latency_s, 4),
            "bon_latency_vs_baseline": round(
                (1.0 / bon_latency_s) / BASELINE_BON_STATEMENTS_PER_SEC, 2
            ),
            "beam_search_statements_per_sec_latency": round(beam_sps, 4),
            "beam_search_vs_baseline": round(
                beam_sps / BASELINE_BEAM_STATEMENTS_PER_SEC, 2
            ),
            "beam_search_seconds_per_statement": round(beam_elapsed, 2),
            "finite_lookahead_seconds_per_statement": round(
                lookahead_elapsed, 2
            ),
            "finite_lookahead_vs_baseline": round(
                lookahead_sps / BASELINE_LOOKAHEAD_STATEMENTS_PER_SEC, 2
            ),
        }

    # ---- wave-parallel MCTS (de-RTT'd slowest decoder) ---------------
    # Reference-default search scale (num_simulations=50, width=5,
    # rollout_depth=10) with pin_budget so every simulation issues real
    # device work — the same workload the >=4x dispatch-reduction
    # acceptance test pins on the fake backend (tests/test_mcts_wave.py).
    # BENCH_MCTS=0 skips; BENCH_MCTS_WAVE / BENCH_MCTS_SIMS rescale.
    mcts_extra = {}
    if os.environ.get("BENCH_MCTS", "1") != "0":
        mcts_wave = int(os.environ.get("BENCH_MCTS_WAVE", "8"))
        mcts_sims = int(os.environ.get("BENCH_MCTS_SIMS", "50"))

        def one_mcts(seed: int):
            generator = get_method_generator(
                "mcts",
                backend,
                {
                    "num_simulations": mcts_sims,
                    "expansion_sample_width": 5,
                    "max_tokens": NEW_TOKENS,
                    "rollout_depth": 10,
                    "seed": seed,
                    "pin_budget": True,
                    "mcts_wave_size": mcts_wave,
                },
            )
            statement = generator.generate_statement(issue, opinions)
            assert isinstance(statement, str)
            return generator

        one_mcts(31)  # warmup / compile (wave-width padded shapes)
        start = time.perf_counter()
        mcts_gen = one_mcts(32)
        mcts_elapsed = time.perf_counter() - start
        stats = mcts_gen.search_stats
        mcts_steps = max(1, len(stats["visit_log"]))
        mcts_extra = {
            "mcts_seconds_per_statement": round(mcts_elapsed, 2),
            "mcts_device_dispatches_per_statement": stats["device_dispatches"],
            "mcts_device_dispatches_per_token": round(
                stats["device_dispatches"] / mcts_steps, 1
            ),
            "mcts_wave_size": mcts_wave,
            "mcts_num_simulations": mcts_sims,
            "mcts_virtual_loss_collisions": stats["collisions"],
        }

    # ---- online serving cell (fake backend, scheduler + HTTP stack) --
    # Short fixed-rate open-loop run through the full serve path
    # (admission -> worker pool -> shared BatchingBackend): throughput,
    # tail latency, and rejection rate of the subsystem itself, decoupled
    # from device speed.  BENCH_SERVE=0 skips; BENCH_SERVE_REQUESTS /
    # BENCH_SERVE_RATE rescale.
    serve_extra = {}
    if os.environ.get("BENCH_SERVE", "1") != "0":
        from consensus_tpu.serve import create_server
        from consensus_tpu.serve.loadgen import run_loadgen, scenario_requests

        serve_requests = int(os.environ.get("BENCH_SERVE_REQUESTS", "32"))
        serve_rate = float(os.environ.get("BENCH_SERVE_RATE", "50"))
        server = create_server(backend="fake", port=0, max_inflight=4).start()
        try:
            serve_report = run_loadgen(
                server.base_url,
                scenario_requests(serve_requests, params={
                    "n": 8, "max_tokens": NEW_TOKENS}),
                rate_rps=serve_rate,
            )
        finally:
            server.stop()
        serve_extra = {
            "serve_throughput_rps": serve_report["throughput_rps"],
            "serve_p50_ms": serve_report["latency_ms"]["p50"],
            "serve_p99_ms": serve_report["latency_ms"]["p99"],
            "serve_rejected_frac": serve_report["rejection_rate"],
            "serve_offered_rate_rps": serve_report["offered_rate_rps"],
            "serve_requests": serve_requests,
            "serve_backend": "fake (subsystem cost, not device speed)",
        }

    # ---- chaos cell: the serve stack under a transient-fault plan ----
    # Same fixed-rate open-loop workload, but the fake engine sits under
    # supervisor(faults(engine)) with a 5% seeded transient-fault plan:
    # what fraction of requests still succeed, what the fault retries do
    # to tail latency, and how many retries the stack absorbed per
    # request.  BENCH_CHAOS=0 skips; BENCH_CHAOS_RATE_FAULTS rescales the
    # injected fault rate.
    chaos_extra = {}
    if os.environ.get("BENCH_CHAOS", "1") != "0":
        from consensus_tpu.serve import create_server
        from consensus_tpu.serve.loadgen import run_loadgen, scenario_requests

        chaos_requests = int(os.environ.get("BENCH_CHAOS_REQUESTS", "32"))
        chaos_rate = float(os.environ.get("BENCH_CHAOS_RATE", "50"))
        chaos_fault_rate = float(
            os.environ.get("BENCH_CHAOS_RATE_FAULTS", "0.05"))
        chaos_plan = {"seed": 7, "faults": [
            {"kind": "transient_error", "op": "*", "rate": chaos_fault_rate}]}
        chaos_before = get_registry().snapshot()
        server = create_server(
            backend="fake", port=0, max_inflight=4, fault_plan=chaos_plan,
        ).start()
        try:
            chaos_report = run_loadgen(
                server.base_url,
                scenario_requests(chaos_requests, params={
                    "n": 8, "max_tokens": NEW_TOKENS}),
                rate_rps=chaos_rate,
            )
        finally:
            server.stop()
        chaos_delta = diff_snapshots(chaos_before, get_registry().snapshot())

        def _family_total(name: str) -> float:
            family = (chaos_delta.get("families") or {}).get(name) or {}
            return sum(s.get("value", 0) for s in family.get("series", []))

        chaos_retries = _family_total("supervisor_retries_total") \
            + _family_total("serve_retried_total")
        chaos_extra = {
            "chaos_success_frac": chaos_report["availability"],
            "chaos_p99_ms": chaos_report["latency_ms"]["p99"],
            "chaos_retries_per_request": round(
                chaos_retries / chaos_requests, 4) if chaos_requests else 0.0,
            "chaos_fault_rate": chaos_fault_rate,
            "chaos_faults_injected": _family_total("faults_injected_total"),
            "chaos_requests": chaos_requests,
            # Time-bucketed availability/p95 over the run: the shape of the
            # degradation, not just the blended fraction.
            "chaos_recovery_curve": chaos_report.get("recovery_curve"),
        }

    # ---- fleet-chaos cell: transport-seam faults vs a live fleet -----
    # The PR 19 conformance surface measured: the standard seeded seam
    # schedule (5% ship/fetch drops, 1% corruption, one 2s partition of
    # r1) against a 3-replica elastic fleet whose PageStore traffic
    # crosses a FaultyTransport.  Availability should hold >= 0.99 (the
    # request path never crosses the seam; the seam degrades gracefully
    # to cold prefill), and chaos_recovery_time_s is how long after the
    # scheduled partition window ended the manager's probes cleared the
    # partitioned replica.  BENCH_CHAOS=0 skips this cell too.
    chaos_fleet_extra = {}
    if os.environ.get("BENCH_CHAOS", "1") != "0":
        import json as _json
        import time as _time

        from consensus_tpu.serve import create_server
        from consensus_tpu.serve.loadgen import run_loadgen, scenario_requests

        seam_requests = int(os.environ.get("BENCH_CHAOS_REQUESTS", "32"))
        seam_rate = float(os.environ.get("BENCH_CHAOS_RATE", "50"))
        seam_plan = _json.dumps({"seed": 7, "faults": [
            {"kind": "drop", "op": "ship", "rate": 0.05},
            {"kind": "drop", "op": "fetch", "rate": 0.05},
            {"kind": "bit_flip", "op": "*", "rate": 0.01},
            {"kind": "partition", "op": "*", "peer": "r1",
             "after_s": 1.0, "duration_s": 2.0},
        ]})
        server = create_server(
            backend="fake", port=0, max_inflight=4, fleet_size=3,
            fleet_options={"elastic": True,
                           "transport_fault_plan": seam_plan},
        ).start()
        try:
            seam_report = run_loadgen(
                server.base_url,
                scenario_requests(seam_requests, params={
                    "n": 8, "max_tokens": NEW_TOKENS}),
                rate_rps=seam_rate,
                transport_fault_plan=seam_plan,
            )
            # Recovery time: wait (bounded) for the manager's probes to
            # clear the scheduled partition, then measure heal lag past
            # the window end on the transport's own clock.
            manager = getattr(server.scheduler, "manager", None)
            recovery_s = None
            if manager is not None:
                deadline = _time.monotonic() + 15.0
                while _time.monotonic() < deadline:
                    if manager.snapshot().get("partition_events"):
                        break
                    _time.sleep(0.1)
                events = manager.snapshot().get("partition_events") or []
                transport = getattr(manager.page_store, "transport", None)
                windows = (
                    transport.partition_windows()
                    if hasattr(transport, "partition_windows") else []
                )
                if events and windows:
                    recovery_s = max(0.0, round(
                        events[-1]["cleared_s"] - windows[0][2], 3))
        finally:
            server.stop()
        chaos_fleet_extra = {
            "chaos_fleet_availability": seam_report["availability"],
            "chaos_fleet_p99_ms": seam_report["latency_ms"]["p99"],
            "chaos_recovery_time_s": recovery_s,
            "chaos_fleet_requests": seam_requests,
            "chaos_fleet_seam_degradation": seam_report.get(
                "seam_degradation"),
        }

    # ---- brownout cell: the serve stack under deliberate overload ----
    # Open-loop load at roughly 2x the worker pool's drain rate with the
    # brownout controller ON and tight per-request deadlines: the graceful-
    # degradation claim measured — availability should hold near 1.0 while
    # degraded_fraction reports how many answers paid for it with a
    # shrunken search budget.  BENCH_BROWNOUT=0 skips.
    brownout_extra = {}
    if os.environ.get("BENCH_BROWNOUT", "1") != "0":
        from consensus_tpu.serve import create_server
        from consensus_tpu.serve.loadgen import run_loadgen, scenario_requests

        brownout_requests = int(os.environ.get("BENCH_BROWNOUT_REQUESTS", "32"))
        brownout_rate = float(os.environ.get("BENCH_BROWNOUT_RATE", "100"))
        server = create_server(
            backend="fake", port=0, max_inflight=2, max_queue_depth=64,
            brownout=True, default_timeout_s=30.0,
        ).start()
        try:
            brownout_report = run_loadgen(
                server.base_url,
                scenario_requests(
                    brownout_requests,
                    params={"n": 8, "max_tokens": NEW_TOKENS},
                    timeout_s=10.0,
                ),
                rate_rps=brownout_rate,
            )
            brownout_tiers = server.scheduler.stats().get("brownout", {})
        finally:
            server.stop()
        brownout_extra = {
            "brownout_availability": brownout_report["availability"],
            "brownout_degraded_fraction": brownout_report["degraded_fraction"],
            "brownout_p99_ms": brownout_report["latency_ms"]["p99"],
            "brownout_peak_tier": max(
                (int(t) for t, c in brownout_tiers.get(
                    "tier_request_counts", {}).items() if c), default=0),
            "brownout_requests": brownout_requests,
            "brownout_offered_rate_rps": brownout_rate,
        }

    # ---- fleet cell: N replicas + mid-run replica kill ----------------
    # The PR 7 acceptance surface measured: the same open-loop workload
    # against (a) one capacity-constrained scheduler and (b) a 3-replica
    # fleet with one replica killed mid-run.  The GOAL of this cell is
    # availability-under-kill: it should hold at 1.0 through the kill
    # (failed-over requests re-dispatch under their original deadline,
    # byte-identical).  fleet_scaling_efficiency = fleet_rps /
    # (replicas * single_rps) rides along as an honest same-regime
    # capacity number — both arms pin engine=True explicitly so a future
    # default flip can't silently change one arm's regime.  History: the
    # r05 baseline read 1.86 because the single arm ran the legacy flush
    # path while the fleet arm predated PR 11's engine-default flip; with
    # both arms on the engine (r06+) the small fake-backend workload
    # amortizes nothing across replicas and the honest number is ~0.3-0.5
    # — a >1.0 reading here means the arms are in different regimes, not
    # that the router manufactured capacity.  BENCH_FLEET=0 skips.
    fleet_extra = {}
    if os.environ.get("BENCH_FLEET", "1") != "0":
        import threading as _threading

        from consensus_tpu.serve import create_server
        from consensus_tpu.serve.loadgen import run_loadgen, scenario_requests

        fleet_requests = int(os.environ.get("BENCH_FLEET_REQUESTS", "48"))
        fleet_rate = float(os.environ.get("BENCH_FLEET_RATE", "100"))
        fleet_n = int(os.environ.get("BENCH_FLEET_REPLICAS", "3"))
        fleet_payloads = scenario_requests(
            fleet_requests, params={"n": 8, "max_tokens": NEW_TOKENS},
            timeout_s=30.0,
        )
        capacity = {"max_inflight": 2, "max_queue_depth": 8,
                    "default_timeout_s": 30.0}

        server = create_server(
            backend="fake", port=0, engine=True, **capacity).start()
        try:
            single_report = run_loadgen(
                server.base_url, fleet_payloads, rate_rps=fleet_rate)
        finally:
            server.stop()
        single_rps = single_report["throughput_rps"]

        server = create_server(
            backend="fake", port=0, engine=True, fleet_size=fleet_n,
            **capacity).start()
        kill_at_s = 0.4 * fleet_requests / fleet_rate
        killer = _threading.Timer(
            kill_at_s, server.scheduler.kill_replica, args=("r0",))
        killer.daemon = True
        try:
            killer.start()
            fleet_report = run_loadgen(
                server.base_url, fleet_payloads, rate_rps=fleet_rate)
        finally:
            killer.cancel()
            server.stop()
        fleet_rps = fleet_report["throughput_rps"]
        fleet_extra = {
            "fleet_replicas": fleet_n,
            "fleet_availability": fleet_report["availability"],
            "fleet_failovers": fleet_report.get("fleet", {}).get(
                "failovers", 0),
            "fleet_failover_fraction": fleet_report.get(
                "failover_fraction", 0.0),
            "fleet_throughput_rps": fleet_rps,
            "fleet_single_replica_rps": single_rps,
            "fleet_scaling_efficiency": round(
                fleet_rps / (fleet_n * single_rps), 4
            ) if single_rps else None,
            "fleet_replica_request_counts": fleet_report.get(
                "replica_request_counts", {}),
            "fleet_kill_at_s": round(kill_at_s, 3),
            "fleet_requests": fleet_requests,
            "fleet_offered_rate_rps": fleet_rate,
            # Availability/p95 per time bucket across the kill: the dip and
            # the climb back, not one blended number.
            "fleet_recovery_curve": fleet_report.get("recovery_curve"),
            "fleet_goal": "availability 1.0 through the mid-run kill (the "
                          "headline); scaling efficiency is a same-regime "
                          "capacity report (both arms engine=True), not a "
                          "target — see cell comment for the r05 1.86 -> "
                          "r06 0.34 regime-flip history",
        }

    # ---- prefix cache cell: repeated-scenario load, cache on vs off ---
    # The SAME open-loop workload twice through the decode engine — with
    # the cross-request prefix KV cache on, then off — against a
    # repeated-scenario mix (the --scenario-repeat shape production
    # consensus traffic has).  The honest prefill-work series is
    # engine_prefill_tokens_total: tokens chunked prefill actually
    # ingested (prefix-cache hits skip theirs), so the on/off ratio IS
    # the prefill-FLOPs reduction at any fixed model.  Acceptance
    # (ROADMAP): >=5x prefill work per statement on repeated-scenario
    # load, statements byte-identical either way.  Skipped prefill is
    # never credited as useful work — mfu_accounting stays useful-token-
    # only.  Also times speculative rollout verification on the real
    # backend: rollout_many plain vs speculative over the same paths
    # (identical token streams), reporting wall speedup and draft
    # acceptance.  BENCH_PREFIX=0 skips; BENCH_PREFIX_MIX reshapes the
    # scenario mix; BENCH_PREFIX_SPEC=0 skips the rollout sub-cell.
    prefix_extra = {}
    if os.environ.get("BENCH_PREFIX", "1") != "0":
        from consensus_tpu.obs.metrics import Registry
        from consensus_tpu.serve import create_server
        from consensus_tpu.serve.loadgen import run_loadgen, scenario_requests
        from consensus_tpu.utils.mfu import param_count as _param_count

        prefix_requests = int(os.environ.get("BENCH_PREFIX_REQUESTS", "24"))
        prefix_rate = float(os.environ.get("BENCH_PREFIX_RATE", "100"))
        prefix_mix = os.environ.get("BENCH_PREFIX_MIX", "fixed:2")
        prefix_payloads = scenario_requests(
            prefix_requests, params={"n": 8, "max_tokens": NEW_TOKENS},
            scenario_repeat=prefix_mix,
        )

        def prefix_run(enabled: bool):
            reg = Registry()
            engine_options = {"slots": 4, "num_pages": 1024}
            if enabled:
                engine_options["prefix_cache"] = True
            server = create_server(
                backend="fake", port=0, max_inflight=4,
                engine=True, engine_options=engine_options, registry=reg,
            ).start()
            try:
                report = run_loadgen(
                    server.base_url, prefix_payloads, rate_rps=prefix_rate)
            finally:
                server.stop()
            fam = reg.snapshot()["families"].get(
                "engine_prefill_tokens_total") or {}
            prefill_tokens = sum(
                s.get("value", 0) for s in fam.get("series", []))
            return report, prefill_tokens

        on_report, on_prefill = prefix_run(True)
        off_report, off_prefill = prefix_run(False)
        prefix_n_params = _param_count(backend.config)
        prefix_extra = {
            "prefix_requests": prefix_requests,
            "prefix_scenario_mix": prefix_mix,
            "prefix_availability": on_report["availability"],
            "prefix_hit_fraction": on_report.get("prefix_hit_fraction"),
            "prefix_tokens_saved": on_report.get(
                "prefix_cache", {}).get("tokens_saved"),
            "prefill_tokens_per_statement": {
                "cache_off": round(off_prefill / prefix_requests, 1),
                "cache_on": round(on_prefill / prefix_requests, 1),
            },
            "prefill_flops_per_statement": {
                "cache_off": round(
                    2 * prefix_n_params * off_prefill / prefix_requests),
                "cache_on": round(
                    2 * prefix_n_params * on_prefill / prefix_requests),
                "note": "2*params*prefill_tokens at the headline model "
                        "size; the serve cell runs the fake backend, so "
                        "the on/off RATIO is the measurement",
            },
            "prefill_work_reduction_x": round(
                off_prefill / max(on_prefill, 1), 2),
            "prefix_statements_byte_identical": (
                {o.request_id: o.statement for o in on_report["outcomes"]}
                == {o.request_id: o.statement for o in off_report["outcomes"]}
            ),
            "prefix_goal": ">=5x prefill work per statement on "
                           "repeated-scenario load, byte-identical "
                           "statements",
        }

        if os.environ.get("BENCH_PREFIX_SPEC", "1") != "0":
            from consensus_tpu.backends.session import SearchSpec
            from consensus_tpu.backends.tpu import TPUTokenSearchSession

            spec_depth = int(os.environ.get("BENCH_SPEC_DEPTH", "10"))
            agent_prompts = tuple(
                ("You judge consensus statements for one participant.",
                 f"Opinion: {op}\nStatement:")
                for op in opinions.values()
            )

            def rollout_wall(speculative: bool):
                sess = TPUTokenSearchSession(backend, SearchSpec(
                    ref_system="You draft consensus statements.",
                    ref_user=f"Issue: {issue}\nStatement:",
                    agent_prompts=agent_prompts,
                    n_slots=1, k=4, temperature=1.0, seed=17, sample=False,
                    max_steps=spec_depth + 2, speculative=speculative,
                ))
                try:
                    root = sess.propose()[0]
                    suffixes = [[c] for c in root] + [[root[0], root[1]]]
                    salts = list(range(1, len(suffixes) + 1))
                    sess.rollout_many(suffixes, spec_depth, salts)  # warmup
                    start = time.perf_counter()
                    results = sess.rollout_many(suffixes, spec_depth, salts)
                    wall = time.perf_counter() - start
                finally:
                    sess.close()
                return wall, [r[0] for r in results]

            plain_wall, plain_ids = rollout_wall(False)
            spec_before = get_registry().snapshot()
            spec_wall, spec_ids = rollout_wall(True)
            spec_delta = diff_snapshots(spec_before, get_registry().snapshot())

            def _spec_total(name: str) -> float:
                family = (spec_delta.get("families") or {}).get(name) or {}
                return sum(
                    s.get("value", 0) for s in family.get("series", []))

            spec_proposed = _spec_total("spec_draft_proposed_tokens_total")
            spec_verified = _spec_total("spec_draft_verified_tokens_total")
            prefix_extra.update({
                "spec_rollout_speedup": round(plain_wall / spec_wall, 2)
                    if spec_wall else None,
                "spec_rollout_depth": spec_depth,
                "spec_rollout_plain_wall_s": round(plain_wall, 3),
                "spec_rollout_spec_wall_s": round(spec_wall, 3),
                "spec_draft_acceptance": round(
                    spec_verified / spec_proposed, 4) if spec_proposed else 0.0,
                "spec_token_streams_identical": plain_ids == spec_ids,
                "spec_note": "speedup needs accepted drafts, which need "
                             "self-similar rollout text — with the repo's "
                             "random weights acceptance is ~0 and speedup "
                             "<1 is expected; the equivalence (identical "
                             "streams) is the part pinned in CI",
            })

    # ---- corpus-driven load cell (PR 18) -----------------------------
    # The serve + prefix cells above replay the 4 AAMAS scenarios; this
    # cell drives the versioned scenario corpus (data/scenarios_v2)
    # through the same engine-backed serve stack with a weighted family
    # mix, and pins the headline fairness number: the egalitarian price
    # of utilitarian selection on the 500-agent polarized scenario
    # (mean_prob channel — the same table tests/golden/fairness pins).
    # BENCH_CORPUS=0 skips; BENCH_CORPUS_REQUESTS / BENCH_CORPUS_RATE /
    # BENCH_CORPUS_MIX rescale.
    corpus_extra = {}
    if os.environ.get("BENCH_CORPUS", "1") != "0":
        from consensus_tpu.backends.fake import FakeBackend
        from consensus_tpu.data.scenarios.fairness import welfare_gap_table
        from consensus_tpu.data.scenarios.registry import (
            resolve_scenario_ref,
        )
        from consensus_tpu.obs.metrics import Registry
        from consensus_tpu.serve import create_server
        from consensus_tpu.serve.loadgen import corpus_requests, run_loadgen

        corpus_count = int(os.environ.get("BENCH_CORPUS_REQUESTS", "24"))
        corpus_rate = float(os.environ.get("BENCH_CORPUS_RATE", "50"))
        corpus_mix = os.environ.get(
            "BENCH_CORPUS_MIX", "polarized=2,sybil=1,holdout=1")
        corpus_payloads = corpus_requests(
            "v2", corpus_count,
            params={"n": 8, "max_tokens": NEW_TOKENS}, mix=corpus_mix,
        )
        server = create_server(
            backend="fake", port=0, max_inflight=4, engine=True,
            engine_options={
                "slots": 4, "num_pages": 4096, "prefix_cache": True},
            registry=Registry(),
        ).start()
        try:
            corpus_report = run_loadgen(
                server.base_url, corpus_payloads, rate_rps=corpus_rate)
        finally:
            server.stop()
        gap_table = welfare_gap_table(
            FakeBackend(), resolve_scenario_ref("corpus:v2:polarized-500"),
            n_candidates=6, max_tokens=16, seed=0,
        )
        gaps = gap_table["channels"]["mean_prob"]["gaps"]
        corpus_extra = {
            "corpus_requests": corpus_count,
            "corpus_scenario_mix": corpus_report["scenario_mix"],
            "corpus_statements_per_sec": corpus_report["throughput_rps"],
            "corpus_prefix_hit_fraction": corpus_report.get(
                "prefix_hit_fraction"),
            "corpus_availability": corpus_report["availability"],
            "welfare_gap_polarized": gaps[
                "egalitarian_price_of_utilitarian"],
            "welfare_gap_note": "egalitarian welfare forfeited by the "
                                "utilitarian winner on corpus:v2:"
                                "polarized-500 (mean_prob channel; fake "
                                "backend — the fairness-suite golden)",
        }

    # ---- BENCH_MESH: dp scaling of the mesh serving path -----------------
    # Statements/sec efficiency of the engine partitioned over a dp=4 mesh
    # vs one device, plus the two identity invariants (dp=1 byte-identical
    # to the plain engine path; texts identical across dp widths).  Runs as
    # a SUBPROCESS: this process already initialized the real device
    # platform and cannot re-init as 8 emulated CPU devices.  BENCH_MESH=0
    # skips.
    mesh_extra = {}
    if os.environ.get("BENCH_MESH", "1") != "0":
        import subprocess
        import sys as _sys

        mesh_env = dict(os.environ)
        mesh_env["JAX_PLATFORMS"] = "cpu"
        mesh_env.pop("XLA_FLAGS", None)  # cell sets its own device count
        mesh_proc = subprocess.run(
            [_sys.executable, "-m", "consensus_tpu.cli.bench_mesh"],
            env=mesh_env, capture_output=True, text=True, timeout=600,
        )
        if mesh_proc.returncode == 0:
            mesh_extra = json.loads(mesh_proc.stdout.splitlines()[-1])
            mesh_extra["bench_mesh"]["goal"] = (
                ">=0.7 scaling efficiency at dp=4 with both identity "
                "invariants true"
            )
        else:
            mesh_extra = {"bench_mesh": {
                "error": (mesh_proc.stderr or mesh_proc.stdout)[-2000:],
            }}

    # ---- BENCH_SCORE: fused utility-matrix scoring vs per-call -----------
    # The 5-agent reference workload (scenario-2 agents x freshly generated
    # candidates) scored both ways on the SAME backend: the flat per-call
    # ScoreRequest batch (ships every per-token logprob D2H) vs ONE
    # score_matrix call (welfare folded on device; only the (C, A) matrix
    # crosses).  Goals (ISSUE 10): >=3x scored_tokens_per_sec, >=10x D2H
    # reduction per statement, and a 64-agent matrix that chunks under the
    # same HBM session budget.  BENCH_SCORE=0 skips.
    score_extra = {}
    if os.environ.get("BENCH_SCORE", "1") != "0":
        from consensus_tpu.backends.base import GenerationRequest
        from consensus_tpu.backends.score_matrix import (
            AgentContext,
            ScoreMatrixRequest,
        )
        from consensus_tpu.methods.prompts import (
            agent_prompt,
            clean_statement,
            reference_prompt,
        )

        ref_system, ref_user = reference_prompt(issue, opinions)
        gen_results = backend.generate([
            GenerationRequest(
                user_prompt=ref_user, system_prompt=ref_system,
                max_tokens=NEW_TOKENS, temperature=1.0,
                seed=9000 + i, chat=True,
            )
            for i in range(8)
        ])
        cands = [
            clean_statement(r.text) or f"consensus statement draft {i}"
            for i, r in enumerate(gen_results)
        ]
        # The full scenario opinions render to 780-1090-token prefixes,
        # past the bench backend's max_context=1024 — rows that long are
        # the per-call scorer's truncation territory by contract, so the
        # fused path would (correctly) fall back and the cell would time
        # the fallback against itself.  Trim the opinions so every row
        # fits and the device matrix is what gets measured.
        short_opinions = {
            name: opinion[:280] for name, opinion in opinions.items()
        }
        contexts = []
        for _, opinion in short_opinions.items():
            a_system, a_user = agent_prompt(issue, opinion)
            contexts.append(
                AgentContext(context=a_user, system_prompt=a_system, chat=True)
            )
        matrix_req = ScoreMatrixRequest(
            agents=tuple(contexts), candidates=tuple(cands), stat="mean",
        )
        cell_reqs = matrix_req.cell_requests()
        n_stmt = len(cands)

        def timed_percall():
            t0 = time.perf_counter()
            s0 = backend.token_counts["scored"]
            results = backend.score(cell_reqs)
            wall = time.perf_counter() - t0
            toks = backend.token_counts["scored"] - s0
            d2h = sum(len(r.logprobs) * 8 for r in results)
            return wall, toks, d2h

        def timed_matrix():
            t0 = time.perf_counter()
            s0 = backend.token_counts["scored"]
            result = backend.score_matrix([matrix_req])[0]
            wall = time.perf_counter() - t0
            return wall, backend.token_counts["scored"] - s0, result

        timed_percall()  # warmup/compile both paths before timing
        timed_matrix()
        pc_wall, pc_toks, pc_d2h = timed_percall()
        mx_wall, mx_toks, mx_result = timed_matrix()
        pc_tps = pc_toks / pc_wall if pc_wall else 0.0
        mx_tps = mx_toks / mx_wall if mx_wall else 0.0

        # 64-agent regime (AAMAS 50-200 agent scaling): contexts are made
        # textually distinct so prefix-page sharing can't flatter the
        # chunked run — it must stream (C x 64) rows through the SAME HBM
        # session budget.
        base_opinions = list(short_opinions.values())
        many_agents = []
        for i in range(64):
            opinion = base_opinions[i % len(base_opinions)]
            variant = (
                f"{opinion} Restated by panel member {i}: the same position, "
                f"emphasis variant {i // len(base_opinions)}."
            )
            a_system, a_user = agent_prompt(issue, variant)
            many_agents.append(
                AgentContext(context=a_user, system_prompt=a_system, chat=True)
            )
        chunks0 = backend.matrix_stats["chunks"]
        fallbacks0 = backend.matrix_stats["fallbacks"]
        t0 = time.perf_counter()
        many_result = backend.score_matrix([
            ScoreMatrixRequest(
                agents=tuple(many_agents), candidates=tuple(cands[:4]),
                stat="mean",
            )
        ])[0]
        many_wall = time.perf_counter() - t0

        score_extra = {"bench_score": {
            "scored_tokens_per_sec": {
                "matrix": round(mx_tps, 1),
                "per_call": round(pc_tps, 1),
            },
            "matrix_vs_per_call_speedup": round(mx_tps / pc_tps, 2)
                if pc_tps else None,
            "d2h_bytes_per_statement": {
                "matrix": round(mx_result.d2h_bytes / n_stmt, 1),
                "per_call": round(pc_d2h / n_stmt, 1),
            },
            "d2h_reduction": round(pc_d2h / mx_result.d2h_bytes, 1)
                if mx_result.d2h_bytes else None,
            "matrix_path": mx_result.path,
            "matrix_cells": mx_result.cells,
            "agents_64": {
                "wall_s": round(many_wall, 3),
                "chunks": backend.matrix_stats["chunks"] - chunks0,
                "fell_back": backend.matrix_stats["fallbacks"] > fallbacks0,
                "path": many_result.path,
                "cells": many_result.cells,
                "hbm_session_budget_bytes": backend._session_budget.cap,
            },
            "goal": ">=3x scored_tokens_per_sec and >=10x D2H reduction "
                    "per statement vs per-call on the 5-agent reference "
                    "workload; 64 agents chunk under the same HBM budget",
        }}

    # ---- BENCH_ELASTIC: full elasticity cycle on the fake fleet ----------
    # The PR 11 acceptance surface measured: a 3-replica elastic fleet
    # under repeated-scenario load takes a kill -> ladder loss -> same-name
    # respawn (warm PageStore pre-seed) -> rejoin, then an autoscaler-driven
    # scale-up to 4 and back to 3.  Reported: availability through the
    # cycle, per-kill time-to-recover, warm-vs-cold respawn prefill tokens
    # (the PageStore's latency floor, as a fleet-wide counter delta over
    # the post-respawn replay), the respawned replica's first-pass prefix
    # hit fraction, and scale-cycle monotonicity (replica count + tier
    # changes never oscillate within a phase).  BENCH_ELASTIC=0 skips.
    elastic_extra = {}
    if os.environ.get("BENCH_ELASTIC", "1") != "0":
        from consensus_tpu.obs.metrics import Registry as _Registry
        from consensus_tpu.serve import Autoscaler, create_server
        from consensus_tpu.serve.loadgen import run_loadgen, scenario_requests

        el_requests = int(os.environ.get("BENCH_ELASTIC_REQUESTS", "36"))
        el_rate = float(os.environ.get("BENCH_ELASTIC_RATE", "60"))
        el_payloads = scenario_requests(
            el_requests, params={"n": 4, "max_tokens": NEW_TOKENS},
            timeout_s=30.0, scenario_repeat="fixed:2",
        )

        def _counter_total(registry, name):
            family = registry.snapshot()["families"].get(name) or {}
            return sum(s.get("value", 0)
                       for s in family.get("series", []))

        def _wait(predicate, timeout_s):
            deadline = time.perf_counter() + timeout_s
            while time.perf_counter() < deadline:
                if predicate():
                    return True
                time.sleep(0.02)
            return predicate()

        def _elastic_cycle(warm):
            registry = _Registry()
            server = create_server(
                backend="fake", port=0, registry=registry,
                max_inflight=2, max_queue_depth=16,
                default_timeout_s=30.0,
                engine_options={"prefix_cache": True},
                fleet_size=3,
                fleet_options={
                    "elastic": True,
                    "elastic_options": {"check_interval_s": 0.05,
                                        "respawn_backoff_s": 0.05,
                                        "harvest_interval_s": 0.1},
                },
            ).start()
            router = server.scheduler
            manager = router.manager
            if not warm:
                manager.page_store = None  # cold respawns: no handoff
            try:
                steady = run_loadgen(
                    server.base_url, el_payloads, rate_rps=el_rate)
                if warm:
                    _wait(lambda: len(manager.page_store) > 0, 10.0)
                t_kill = time.perf_counter()
                router.kill_replica("r0")
                recovered = _wait(
                    lambda: manager.snapshot()["respawns"] >= 1
                    and len(router.replicas) == 3
                    and router.stats()["fleet"]["healthy"] == 3,
                    15.0,
                )
                recover_s = time.perf_counter() - t_kill
                prefill0 = _counter_total(
                    registry, "engine_prefill_tokens_total")
                replay = run_loadgen(
                    server.base_url, el_payloads, rate_rps=el_rate)
                prefill = _counter_total(
                    registry, "engine_prefill_tokens_total") - prefill0
                cache = router._replica(
                    "r0").scheduler.batching.engine.prefix_cache
                probes = cache.hits + cache.misses
                return {
                    "steady_availability": steady["availability"],
                    "replay_availability": replay["availability"],
                    "recovered": bool(recovered),
                    "time_to_recover_s": round(recover_s, 3),
                    "respawns": manager.snapshot()["respawns"],
                    "replay_prefill_tokens": prefill,
                    "respawn_hit_fraction": round(
                        cache.hits / probes, 4) if probes else 0.0,
                    "steady_hit_fraction": steady.get(
                        "prefix_hit_fraction", 0.0),
                }, server, router, manager
            except BaseException:
                server.stop(drain=False)
                raise

        warm_cycle, server, router, manager = _elastic_cycle(warm=True)
        # Scale cycle on the surviving warm server: a synthetic pressure
        # source drives the real autoscaler control law; replica count
        # must be monotone within each phase (no oscillation).
        pressure = [0.95]
        scaler = Autoscaler(
            manager, pressure_fn=lambda: pressure[0],
            min_replicas=1, max_replicas=4,
            up_dwell_s=0.1, down_dwell_s=0.2, cooldown_s=0.1,
            check_interval_s=0.05, registry=_Registry(),
        )
        try:
            sizes_up = []
            t_up = time.perf_counter()
            _wait(lambda: sizes_up.append(len(router.replicas)) or (
                len(router.replicas) == 4
                and router.stats()["fleet"]["healthy"] == 4), 10.0)
            scale_up_s = time.perf_counter() - t_up
            pressure[0] = 0.1
            sizes_down = []
            t_down = time.perf_counter()
            _wait(lambda: sizes_down.append(len(router.replicas)) or (
                len(router.replicas) == 3), 10.0)
            scale_down_s = time.perf_counter() - t_down
            monotone = (
                sizes_up == sorted(sizes_up)
                and sizes_down == sorted(sizes_down, reverse=True)
            )
            scale_snapshot = scaler.snapshot()
        finally:
            scaler.close()
            server.stop(drain=False)

        cold_cycle, server, _, _ = _elastic_cycle(warm=False)
        server.stop(drain=False)

        warm_prefill = warm_cycle["replay_prefill_tokens"]
        cold_prefill = cold_cycle["replay_prefill_tokens"]
        elastic_extra = {"bench_elastic": {
            "availability": min(warm_cycle["steady_availability"],
                                warm_cycle["replay_availability"]),
            "time_to_recover_s": warm_cycle["time_to_recover_s"],
            "respawns": warm_cycle["respawns"],
            "respawn_prefill_tokens": {
                "warm": warm_prefill, "cold": cold_prefill,
            },
            "warm_vs_cold_prefill_ratio": round(
                cold_prefill / warm_prefill, 2) if warm_prefill else None,
            "respawn_hit_fraction": {
                "warm": warm_cycle["respawn_hit_fraction"],
                "cold": cold_cycle["respawn_hit_fraction"],
            },
            "steady_hit_fraction": warm_cycle["steady_hit_fraction"],
            "scale_up_s": round(scale_up_s, 3),
            "scale_down_s": round(scale_down_s, 3),
            "scale_events": {"up": scale_snapshot["scale_ups"],
                             "down": scale_snapshot["scale_downs"]},
            "replica_count_monotone": monotone,
            "requests_per_phase": el_requests,
            "offered_rate_rps": el_rate,
            "goal": "availability 1.0 through kill->respawn->scale cycle; "
                    "warm respawn prefills less than cold (PageStore "
                    "handoff); replica count monotone per phase",
        }}

    # ---- BENCH_RESTART: zero-loss rolling restart of a durable fleet -----
    # The PR 20 acceptance surface measured: a 3-replica elastic fleet with
    # a state_dir (durable idempotency snapshot + disk-backed PageStore
    # spill) takes a full rolling restart — drain -> capture -> respawn ->
    # warm-seed -> health-gated rejoin, one replica at a time — while
    # open-loop load keeps arriving.  Reported: availability through the
    # cycle (goal >= 0.99), the fraction of respawns that warm-seeded at
    # least one run from the durable PageStore (goal: all of them), and
    # the slowest per-replica drain->rejoin time.  BENCH_RESTART=0 skips.
    restart_extra = {}
    if os.environ.get("BENCH_RESTART", "1") != "0":
        import tempfile as _tempfile
        import threading as _rthreading

        from consensus_tpu.serve import create_server
        from consensus_tpu.serve.loadgen import run_loadgen, scenario_requests

        restart_requests = int(os.environ.get("BENCH_RESTART_REQUESTS", "36"))
        restart_rate = float(os.environ.get("BENCH_RESTART_RATE", "60"))
        restart_payloads = scenario_requests(
            restart_requests, params={"n": 4, "max_tokens": NEW_TOKENS},
            timeout_s=30.0, scenario_repeat="fixed:2",
        )
        restart_state_dir = _tempfile.mkdtemp(prefix="bench-restart-")
        server = create_server(
            backend="fake", port=0, max_inflight=2, max_queue_depth=16,
            default_timeout_s=30.0, state_dir=restart_state_dir,
            engine_options={"prefix_cache": True},
            fleet_size=3,
            fleet_options={
                "elastic": True,
                "elastic_options": {"check_interval_s": 0.05,
                                    "respawn_backoff_s": 0.05,
                                    "harvest_interval_s": 0.1},
            },
        ).start()
        restart_manager = server.scheduler.manager
        restart_outcome = {}
        try:
            # Prime the PageStore (harvested prefix runs are what respawns
            # warm-seed from), then restart the fleet under fresh load.
            run_loadgen(server.base_url, restart_payloads,
                        rate_rps=restart_rate)
            prime_deadline = time.perf_counter() + 10.0
            while (time.perf_counter() < prime_deadline
                   and not len(restart_manager.page_store)):
                time.sleep(0.05)
            restarter = _rthreading.Timer(
                0.2,
                lambda: restart_outcome.update(
                    restart_manager.rolling_restart()),
            )
            restarter.daemon = True
            restarter.start()
            restart_report = run_loadgen(
                server.base_url, restart_payloads, rate_rps=restart_rate)
            restarter.join(timeout=60.0)
            restart_snap = restart_manager.snapshot()
        finally:
            server.stop(drain=False)
        restart_events = restart_snap.get("restart_events") or []
        restart_recover_times = [
            round(e["completed_s"] - e["started_s"], 3)
            for e in restart_events
            if e.get("completed_s") is not None
            and e.get("started_s") is not None
        ]
        restart_extra = {
            "restart_availability": restart_report["availability"],
            "restart_warm_seed_fraction": round(
                sum(1 for e in restart_events
                    if (e.get("warm_seeded") or 0) > 0)
                / len(restart_events), 4) if restart_events else None,
            "restart_recovery_time_s": (
                max(restart_recover_times)
                if restart_recover_times else None),
            "restart_recovery_times_s": restart_recover_times,
            "restart_replicas_cycled": restart_snap.get("restarts", 0),
            "restart_aborted": restart_outcome.get("aborted"),
            "restart_requests": restart_requests,
            "restart_offered_rate_rps": restart_rate,
            "restart_goal": "availability >= 0.99 while every replica is "
                            "drained, restarted, warm-seeded from the "
                            "durable PageStore, and health-gated back in, "
                            "one at a time",
        }

    # ---- BENCH_OBS: welfare telemetry plane cost + federation proof ------
    # Two claims measured: (1) the telemetry plane (latency + welfare
    # quantile sketches, drift detector, SLO engine) costs < 2% serve
    # throughput vs the same stack with it off; (2) the fleet-federated
    # /metrics p99 from merged per-replica sketches EQUALS the quantile of
    # one sketch fed the pooled observations (merge is exact integer
    # bucket addition, so this is equality, not approximation).
    # BENCH_OBS=0 skips.
    obs_extra = {}
    if os.environ.get("BENCH_OBS", "1") != "0":
        import copy as _copy

        from consensus_tpu.obs.metrics import Registry as _Registry
        from consensus_tpu.obs.sketch import (
            merge_sketch_series,
            quantile_from_series,
        )
        from consensus_tpu.obs.welfare import set_welfare_sink
        from consensus_tpu.serve import create_server
        from consensus_tpu.serve.loadgen import run_loadgen, scenario_requests

        obs_requests = int(os.environ.get("BENCH_OBS_REQUESTS", "32"))
        obs_rate = float(os.environ.get("BENCH_OBS_RATE", "50"))
        obs_payloads = scenario_requests(
            obs_requests, params={"n": 4, "max_tokens": NEW_TOKENS},
            evaluate=True,
        )

        def _obs_run(telemetry_on):
            registry = _Registry()
            server = create_server(
                backend="fake", port=0, registry=registry, max_inflight=4,
                telemetry=telemetry_on, slo=telemetry_on,
            ).start()
            try:
                report = run_loadgen(
                    server.base_url, obs_payloads, rate_rps=obs_rate)
            finally:
                server.stop()
                set_welfare_sink(None)
            return report

        report_off = _obs_run(False)
        report_on = _obs_run(True)
        overhead = (
            1.0 - report_on["throughput_rps"] / report_off["throughput_rps"]
            if report_off["throughput_rps"] else 0.0
        )

        # Federation proof on a 3-replica fleet: merged fleet p99 must
        # equal the pooled-observation p99 bit-for-bit.
        fleet_registry = _Registry()
        fleet_server = create_server(
            backend="fake", port=0, registry=fleet_registry, max_inflight=4,
            fleet_size=3, telemetry=True,
        ).start()
        try:
            run_loadgen(fleet_server.base_url, obs_payloads,
                        rate_rps=obs_rate)
            fed = fleet_server.scheduler.federated_metrics_snapshot()
        finally:
            fleet_server.stop()
            set_welfare_sink(None)
        family = fed["families"]["serve_latency_sketch_seconds"]
        pooled = None
        merged = None
        replicas_seen = set()
        for series in family["series"]:
            body = {k: v for k, v in series.items() if k != "labels"}
            if series["labels"].get("replica") == "fleet":
                if merged is None:
                    merged = _copy.deepcopy(body)
                else:
                    merge_sketch_series(merged, body, family["extreme"])
            else:
                replicas_seen.add(series["labels"].get("replica"))
                if pooled is None:
                    pooled = _copy.deepcopy(body)
                else:
                    merge_sketch_series(pooled, body, family["extreme"])
        ra = family["relative_accuracy"]
        p99_merged = quantile_from_series(merged, 0.99, ra)
        p99_pooled = quantile_from_series(pooled, 0.99, ra)
        obs_extra = {"bench_obs": {
            "throughput_off_rps": report_off["throughput_rps"],
            "throughput_on_rps": report_on["throughput_rps"],
            "telemetry_overhead_frac": round(overhead, 4),
            "within_2pct": overhead < 0.02,
            "fleet_replicas_observed": len(replicas_seen),
            "fleet_p99_merged_ms": round(p99_merged * 1e3, 3),
            "fleet_p99_pooled_ms": round(p99_pooled * 1e3, 3),
            "merged_equals_pooled": p99_merged == p99_pooled,
            "exemplars": len(merged.get("exemplars", [])),
            "requests_per_run": obs_requests,
            "offered_rate_rps": obs_rate,
            "goal": "telemetry plane < 2% throughput cost; fleet-merged "
                    "p99 exactly equals pooled-observation p99 (exact "
                    "sketch merge)",
        }}

    # ---- BENCH_SPEC: engine-native speculative decoding ------------------
    # Two surfaces: (1) the fake-serve path spec-on vs spec-off on a
    # self-similar (scenario_repeat=fixed:2) load — statements/sec plus the
    # engine's accepted-tokens/dispatch and draft acceptance rate from the
    # loadgen's /healthz delta; (2) the device verify kernel on the tiny
    # real model, a cyclic greedy prompt the n-gram self-draft can actually
    # learn, K in {1, 4} — tokens-per-dispatch floats with acceptance, and
    # the K=1 spec cell is the "exceeds fixed K" proof (a 1-draft window
    # emits up to 2 real tokens per dispatch).  HONEST CAVEAT: random
    # weights mean acceptance here measures the proposer against
    # random-model output self-similarity, not real-text draftability —
    # the acceptance rates below are a mechanism proof, not a speedup
    # claim; wall-clock wins need a real checkpoint and a chip.
    # BENCH_SPEC=0 skips.
    spec_extra = {}
    if os.environ.get("BENCH_SPEC", "1") != "0":
        from consensus_tpu.backends.base import GenerationRequest
        from consensus_tpu.serve import create_server
        from consensus_tpu.serve.loadgen import run_loadgen, scenario_requests

        spec_requests = int(os.environ.get("BENCH_SPEC_REQUESTS", "24"))
        spec_rate = float(os.environ.get("BENCH_SPEC_RATE", "50"))
        spec_payloads = scenario_requests(
            spec_requests, params={"n": 4, "max_tokens": NEW_TOKENS},
            timeout_s=30.0, scenario_repeat="fixed:2",
        )

        def _spec_serve(speculative):
            server = create_server(
                backend="fake", port=0, max_inflight=4,
                engine_options={"decode_steps": 4,
                                "speculative": speculative},
            ).start()
            try:
                report = run_loadgen(
                    server.base_url, spec_payloads, rate_rps=spec_rate)
            finally:
                server.stop()
            return report

        spec_off_report = _spec_serve(False)
        spec_on_report = _spec_serve(True)
        serve_spec = spec_on_report.get("speculative") or {}

        def _spec_stream_cell(k, speculative):
            reqs = [GenerationRequest(
                user_prompt="one two three one two three one two three "
                            "one two three",
                seed=1, max_tokens=48, temperature=0.0,
            )]
            stream = backend.generate_stream(
                reqs, decode_steps=k, speculative=speculative)
            results, windows = {}, 0
            while not stream.finished:
                stream.dispatch()
                _, finished = stream.collect()
                results.update(finished)
                windows += 1
                assert windows < 300, "spec bench stream failed to drain"
            proposed = getattr(stream, "spec_proposed", 0)
            accepted = getattr(stream, "spec_accepted", 0)
            stream.close()
            tokens = len(results[0].token_ids or ())
            return {
                "tokens_per_dispatch": round(tokens / windows, 3),
                "dispatches": windows,
                "draft_acceptance_rate": (
                    round(accepted / proposed, 4) if proposed else None),
            }

        stream_cells = {
            f"k{k}_{'spec' if on else 'plain'}": _spec_stream_cell(k, on)
            for k in (1, 4) for on in (False, True)
        }
        k1_spec_tpd = stream_cells["k1_spec"]["tokens_per_dispatch"]
        spec_extra = {
            "spec_statements_per_sec": spec_on_report["throughput_rps"],
            "spec_off_statements_per_sec": spec_off_report["throughput_rps"],
            "spec_accepted_tokens_per_dispatch": serve_spec.get(
                "accepted_tokens_per_dispatch"),
            "spec_draft_acceptance_rate": serve_spec.get(
                "draft_acceptance_rate"),
            "spec_serve_proposed_tokens": serve_spec.get("proposed_tokens"),
            "spec_serve_accepted_tokens": serve_spec.get("accepted_tokens"),
            "spec_stream_cells": stream_cells,
            # The acceptance-criteria cell: a K=1 draft window emitting
            # > 1.0 tokens per dispatch is throughput past the fixed-K
            # floor (spec-off K=1 is exactly 1.0 by construction).
            "spec_k1_tokens_per_dispatch": k1_spec_tpd,
            "spec_k1_exceeds_fixed_k": k1_spec_tpd > 1.0,
            "spec_note": (
                "random weights: acceptance measures the n-gram proposer "
                "against random-model output self-similarity (cyclic "
                "greedy prompt on the device cells, repeated fake "
                "scenarios on the serve cells), a mechanism proof rather "
                "than a real-text speedup claim; output is byte-identical "
                "spec on/off by construction, so the only cost risk is "
                "the wasted verify columns — wall-clock wins need a real "
                "checkpoint and a chip"
            ),
        }

    bench_tokens = {
        k: tokens_after[k] - tokens_before[k] for k in tokens_after
    }

    # Hardware utilization of the HEADLINE regime (VERDICT r3 #3: print
    # MFU from the harness, don't leave it to be estimated).  Shared
    # accounting: consensus_tpu/utils/mfu.py.
    from consensus_tpu.utils.mfu import (
        DEVICE_PEAKS,
        param_count,
        pct_of_peak,
        useful_tflops_per_sec,
    )

    n_params = param_count(backend.config)
    bench_total_tokens = sum(bench_tokens.values())
    padding_eff = padding_efficiency(metrics_timed)
    throughput_tflops = useful_tflops_per_sec(
        n_params, bench_total_tokens, sum(trial_walls)
    )
    # MFU split by work kind over the SAME wall: the scored and generated
    # components add up to throughput_tflops_per_sec, so readers can see
    # which side of the workload (candidate generation vs the utility
    # matrix) carries the useful FLOPs.
    score_tflops = useful_tflops_per_sec(
        n_params, bench_tokens.get("scored", 0), sum(trial_walls)
    )
    generate_tflops = useful_tflops_per_sec(
        n_params, bench_tokens.get("generated", 0), sum(trial_walls)
    )
    # Peak FLOPs scale with the mesh: a dp*tp slice has that many chips'
    # worth of silicon, and %-of-peak must divide by ALL of it or multichip
    # runs flatter themselves.  Single-chip runs: mesh_devices == 1,
    # numbers unchanged.
    mesh_devices = (
        backend.mesh_plan.n_devices if backend.mesh_plan is not None else 1
    )
    # A share of peak exists only on a device whose peak is published
    # (utils/mfu.py); anywhere else it was not measured.
    device_kind = backend.device_info["device_kind"]
    pct_peak = (
        round(pct_of_peak(throughput_tflops, device_kind, mesh_devices), 2)
        if device_kind in DEVICE_PEAKS
        else "not measured"
    )
    print(
        json.dumps(
            {
                "metric": "best_of_n_statements_per_sec",
                "value": round(throughput_sps, 4),
                "unit": "statements/sec (THROUGHPUT regime: "
                        f"{N_CONCURRENT} co-batched sweep-style statements; "
                        f"median of {N_TRIALS} trials; "
                        f"real stack, {os.environ.get('BENCH_MODEL', 'gemma2-2b')}, "
                        f"5-agent, N={N_CANDIDATES}, {NEW_TOKENS} tok)",
                "vs_baseline": round(
                    throughput_sps / BASELINE_BON_STATEMENTS_PER_SEC, 2
                ),
                "extra": {
                    "regimes": {
                        "throughput": "co-batched statements via "
                                      "BatchingBackend (sweep/north-star "
                                      "regime; the headline)",
                        "latency": "one statement at a time",
                    },
                    "bon_throughput_wall_s": round(throughput_wall, 2),
                    "bon_throughput_trial_walls_s": [
                        round(w, 2) for w in trial_walls
                    ],
                    "bon_throughput_walls_sum_s": round(sum(trial_walls), 2),
                    "bon_throughput_sps_spread": {
                        "median": round(throughput_sps, 4),
                        "min": round(throughput_sps_min, 4),
                        "max": round(throughput_sps_max, 4),
                        "n_trials": N_TRIALS,
                    },
                    # Renamed from bon_throughput_tokens (r1-r4: ONE timed
                    # run): now summed over all N_TRIALS timed runs — divide
                    # by walls_sum_s, not wall_s, for tokens/sec.
                    "bon_throughput_tokens_all_trials": bench_tokens,
                    # Derived here so r1-r4 vs r5+ token numbers compare
                    # directly without readers redoing the wall division.
                    "tokens_per_sec": round(
                        bench_total_tokens / sum(trial_walls), 1
                    ),
                    # obs-derived hardware-efficiency trajectory (timed
                    # throughput window): useful/allocated tokens across the
                    # padded device grids, and how many padded program
                    # shapes compiled.  Steady-state recompiles should be 0
                    # after warmup; total counts the whole process.
                    "padding_efficiency": (
                        round(padding_eff, 4) if padding_eff is not None else None
                    ),
                    "bucket_recompiles": bucket_recompiles(
                        get_registry().snapshot()
                    ),
                    "bucket_recompiles_timed_window": bucket_recompiles(
                        metrics_timed
                    ),
                    "throughput_tflops_per_sec": round(throughput_tflops, 2),
                    "score_tflops_per_sec": round(score_tflops, 2),
                    "generate_tflops_per_sec": round(generate_tflops, 2),
                    "throughput_pct_of_bf16_peak": pct_peak,
                    "device": backend.device_info,
                    "mesh_devices": mesh_devices,
                    "mfu_accounting": (
                        f"2*{n_params:.3g} params * {bench_total_tokens} "
                        "generated+scored tokens / wall; peak = the "
                        "device kind's published bf16 TFLOP/s x "
                        f"{mesh_devices} mesh device(s) — %-of-peak divides "
                        "by the WHOLE slice's silicon, so multichip runs "
                        "can't flatter themselves; "
                        "counts USEFUL tokens only — bucket padding, "
                        "KV/weight HBM traffic, and host overheads all "
                        "show up as lost MFU, which is the point; "
                        "prefix-cache-skipped prefill tokens are never "
                        "credited as useful work; score_/generate_"
                        "tflops_per_sec split the same accounting by work "
                        "kind over the same wall (they sum to the total)"
                    ),
                    **latency_extra,
                    **engine_extra,
                    **mcts_extra,
                    **serve_extra,
                    **chaos_extra,
                    **chaos_fleet_extra,
                    **brownout_extra,
                    **fleet_extra,
                    **prefix_extra,
                    **corpus_extra,
                    **mesh_extra,
                    **score_extra,
                    **elastic_extra,
                    **restart_extra,
                    **obs_extra,
                    **spec_extra,
                    "weights": "random",
                    "quantization": backend.quantization or "bf16",
                    "shared_context_scoring": backend.shared_context_scoring,
                },
            }
        )
    )


if __name__ == "__main__":
    main()
