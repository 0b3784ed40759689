#!/usr/bin/env python
"""Regenerate the fairness regression goldens under tests/golden/fairness/.

Each golden is one welfare-gap table (see
consensus_tpu/data/scenarios/fairness.py) for one corpus scenario on one
backend.  The fake-backend scores are hash-deterministic; the tiny-gemma2
tables come from PRNGKey(0) random weights, so they hold for a fixed jax
version.  tests/test_fairness_regression.py compares every float at rel
1e-4 / abs 1e-6 and everything else exactly.

Run from the repo root after any intentional change to the corpus, the
prompts, or the score-matrix numerics, and after a toolchain change that
moves what ``init_params`` draws from a key:

    JAX_PLATFORMS=cpu python scripts/gen_fairness_goldens.py
"""

import argparse
import json
import pathlib
import sys

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO_ROOT))

from consensus_tpu.data.scenarios.fairness import (  # noqa: E402
    BIG_SLATE,
    welfare_gap_table,
)
from consensus_tpu.data.scenarios.registry import (  # noqa: E402
    resolve_scenario_ref,
)

#: Scenarios whose fake-backend tables are pinned.  Chosen so that at
#: least three adversarial families separate all three welfare rules on
#: the mean_prob channel (asserted by the regression suite).
FAKE_SCENARIOS = (
    "polarized-0004",
    "sybil-0006",
    "holdout-0005",
    "contradictory-0003",
    "paraphrase-0004",
    "polarized-500",
)

#: Scenarios pinned on tiny-gemma2 through the FUSED score-matrix path.
#: The 500-agent table doubles as the chunked-under-budget demonstration.
TINY_SCENARIOS = ("polarized-0004", "polarized-500")

FAKE_TABLE_KWARGS = {"n_candidates": 6, "max_tokens": 16, "seed": 0}


def fake_tables():
    from consensus_tpu.backends.fake import FakeBackend

    backend = FakeBackend()
    for sid in FAKE_SCENARIOS:
        scenario = resolve_scenario_ref(f"corpus:v2:{sid}")
        yield f"fake_{sid}", welfare_gap_table(
            backend, scenario, **FAKE_TABLE_KWARGS)


def tiny_tables():
    from consensus_tpu.backends.tpu import TPUBackend

    # max_context must cover the agent-prompt prefixes (~670 tokens under
    # the near-char-level tiny tokenizer) or the fused gate falls back.
    backend = TPUBackend(model="tiny-gemma2", dtype="float32",
                         max_context=1024)
    for sid in TINY_SCENARIOS:
        scenario = resolve_scenario_ref(f"corpus:v2:{sid}")
        before = backend.matrix_stats["chunks"]
        table = welfare_gap_table(backend, scenario, candidates=BIG_SLATE)
        table["matrix_chunks"] = backend.matrix_stats["chunks"] - before
        yield f"tiny-gemma2_{sid}", table


def main():
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument(
        "--out", default=str(REPO_ROOT / "tests" / "golden" / "fairness"))
    parser.add_argument(
        "--skip-tiny", action="store_true",
        help="only regenerate the fake-backend tables")
    args = parser.parse_args()

    out = pathlib.Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    sources = [fake_tables()]
    if not args.skip_tiny:
        sources.append(tiny_tables())
    for source in sources:
        for name, table in source:
            path = out / f"{name}.json"
            path.write_text(json.dumps(table, indent=2, sort_keys=True)
                            + "\n")
            prob = table["channels"]["mean_prob"]
            print(f"{name}: path={table['matrix_path']} "
                  f"winners={prob['winners']} "
                  f"separated={prob['rules_separated']}")


if __name__ == "__main__":
    main()
