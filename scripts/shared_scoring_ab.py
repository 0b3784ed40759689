"""In-situ A/B: shared-context scoring ON vs OFF on the real chip.

The end-to-end effect of shared-context scoring is not measured on this
toolchain.  This script measures it: repeated INTERLEAVED runs of the same real best_of_n statement (so ambient
service variance hits both arms equally), medians reported, scoring phase
timed separately from generation (generation is identical in both arms).

Usage: python scripts/shared_scoring_ab.py [--trials 5] [--n 32] [--quick]
(repo root, free chip — don't run during a timed sweep)
"""

from __future__ import annotations

import argparse
import json
import statistics
import time

from consensus_tpu.backends.tpu import TPUBackend
from consensus_tpu.data.aamas_scenarios import SCENARIOS
from consensus_tpu.methods import get_method_generator


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--trials", type=int, default=5)
    parser.add_argument("--n", type=int, default=32, help="best_of_n candidates")
    parser.add_argument("--max-tokens", type=int, default=50)
    parser.add_argument("--model", default="gemma2-2b")
    parser.add_argument("--quick", action="store_true", help="tiny model, CPU-ok smoke")
    args = parser.parse_args()

    model = "tiny-gemma2" if args.quick else args.model
    backend = TPUBackend(
        model=model,
        max_context=1024,
        base_seed=0,
        use_flash_attention=not args.quick,
        max_batch_rows=32,
        quantization=None if args.quick else "int8",
        shared_context_scoring=True,  # flipped per-arm below
    )

    scenario = SCENARIOS[1]
    issue, opinions = scenario["issue"], scenario["agent_opinions"]

    # Time the scoring phase separately: generation is identical in both
    # arms, so the score-call delta is the certified effect.
    score_time = {"t": 0.0}
    inner_score = backend.score

    def timed_score(requests):
        t0 = time.perf_counter()
        out = inner_score(requests)
        score_time["t"] += time.perf_counter() - t0
        return out

    backend.score = timed_score

    def run_once(shared: bool, seed: int):
        backend.shared_context_scoring = shared
        generator = get_method_generator(
            "best_of_n",
            backend,
            {"n": args.n, "max_tokens": args.max_tokens, "seed": seed},
            model,
        )
        score_time["t"] = 0.0
        t0 = time.perf_counter()
        generator.generate_statement(issue, opinions)
        return time.perf_counter() - t0, score_time["t"]

    print(f"warmup (compiles both arms, {model}, n={args.n}) ...", flush=True)
    run_once(True, seed=9000)
    run_once(False, seed=9000)

    totals = {True: [], False: []}
    scores = {True: [], False: []}
    for trial in range(args.trials):
        for shared in (True, False):
            total, score = run_once(shared, seed=100 + trial)
            totals[shared].append(total)
            scores[shared].append(score)
            print(
                f"trial {trial} shared={int(shared)}: "
                f"total {total:.2f}s score {score:.2f}s",
                flush=True,
            )

    med = statistics.median
    result = {
        "model": model,
        "n_candidates": args.n,
        "n_agents": len(opinions),
        "trials": args.trials,
        "total_s_shared": round(med(totals[True]), 3),
        "total_s_classic": round(med(totals[False]), 3),
        "score_s_shared": round(med(scores[True]), 3),
        "score_s_classic": round(med(scores[False]), 3),
        "score_speedup": round(med(scores[False]) / max(med(scores[True]), 1e-9), 2),
        "total_speedup": round(med(totals[False]) / max(med(totals[True]), 1e-9), 2),
    }
    print(json.dumps(result))


if __name__ == "__main__":
    main()
