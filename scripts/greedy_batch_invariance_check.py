"""Validate the habermas retry-elision premise on hardware (ADVICE r4).

``methods/habermas.py`` elides temperature-0 ranking retries on backends
whose greedy decode is argmax: the retry would replay the identical
response.  The elided retry, however, would have run in a DIFFERENT batch
composition (fewer pending rows, possibly another padding bucket) than
attempt 0 — so the elision additionally assumes greedy argmax is invariant
to batch width on the real device, which XLA does not promise in general
(accumulation order may differ across shapes).

This script tests exactly that: the same greedy request decoded at batch
widths 1, 8, 9, 32, and 64 (padded with distinct sibling prompts, target
row first/last), asserting token-identical output across all compositions.
Writes ``reports/greedy_batch_invariance.md`` + ``.json``.

Usage: PYTHONPATH=. python scripts/greedy_batch_invariance_check.py
       [--quick]          (--quick: tiny model, CPU-ok)
       [--backend fake]   (no model at all: deterministic fake backend —
                           exercises the harness end-to-end and pins the
                           fake's own composition invariance; jax-free)
"""

from __future__ import annotations

import argparse
import json
import pathlib
from datetime import datetime

from consensus_tpu.backends.base import GenerationRequest
from consensus_tpu.data.aamas_scenarios import SCENARIOS


def build_backend(args):
    """Returns (backend, model_label, dtype, quantization, max_tokens)."""
    if args.backend == "fake":
        from consensus_tpu.backends.fake import FakeBackend

        return FakeBackend(), "fake", "none", None, min(args.max_tokens, 32)
    from consensus_tpu.backends.tpu import TPUBackend

    if args.quick:
        import jax

        jax.config.update("jax_platforms", "cpu")
        model, max_context, max_tokens = "tiny-gemma2", 256, 32
        dtype, quantization = "float32", None
    else:
        model, max_context = args.model, 1024
        max_tokens = args.max_tokens
        dtype, quantization = "bfloat16", "int8"

    backend = TPUBackend(
        model=model,
        dtype=dtype,
        quantization=quantization,
        max_context=max_context,
        base_seed=0,
        use_flash_attention=not args.quick,
    )
    return backend, model, dtype, quantization, max_tokens


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--model", default="gemma2-2b")
    parser.add_argument("--quick", action="store_true")
    parser.add_argument("--max-tokens", type=int, default=256)
    parser.add_argument(
        "--backend", choices=["tpu", "fake"], default="tpu",
        help="'fake' runs the identical harness on the deterministic fake "
        "backend (no jax, no weights) — CI-runnable end-to-end check.",
    )
    parser.add_argument(
        "--report-dir", default="reports",
        help="Directory for greedy_batch_invariance.{md,json}.",
    )
    args = parser.parse_args()

    backend, model, dtype, quantization, max_tokens = build_backend(args)

    scenario = SCENARIOS[1]
    opinions = list(scenario["agent_opinions"].values())
    target = (
        f"Issue: {scenario['issue']}\n\nOpinion: {opinions[0]}\n\n"
        "Rank the candidate statements from best to worst."
    )
    siblings = [
        f"Issue: {scenario['issue']}\n\nOpinion: {opinions[i % len(opinions)]}\n\n"
        f"Sibling prompt variant {i}: write a consensus statement."
        for i in range(63)
    ]

    def run(width: int, target_pos: int) -> str:
        prompts = list(siblings[: width - 1])
        prompts.insert(target_pos, target)
        requests = [
            GenerationRequest(
                user_prompt=p, max_tokens=max_tokens, temperature=0.0, seed=7
            )
            for p in prompts
        ]
        results = backend.generate(requests)
        return results[target_pos].text

    # Widths must STRADDLE padding-bucket boundaries, not just vary inside
    # one bucket: tpu.py buckets rows (minimum 8), so widths 1 and 4 would
    # execute the identical 8-row program.  1/8 share the smallest bucket;
    # 9 forces the next one; 32/64 are the shapes real sweep batches
    # (max_batch_rows up to 64) actually run — the compositions an elided
    # habermas retry would have landed in.
    compositions = [(1, 0), (8, 0), (9, 8), (32, 0), (32, 31), (64, 63)]
    outputs = {}
    for width, pos in compositions:
        key = f"width={width},pos={pos}"
        outputs[key] = run(width, pos)
        print(f"{key}: {len(outputs[key])} chars")

    baseline = outputs["width=1,pos=0"]
    mismatches = {k: v != baseline for k, v in outputs.items()}
    invariant = not any(mismatches.values())

    payload = {
        "generated": datetime.now().isoformat(timespec="seconds"),
        "backend": args.backend,
        "model": model,
        "dtype": dtype,
        "quantization": quantization,
        "max_tokens": max_tokens,
        "compositions": [f"width={w},pos={p}" for w, p in compositions],
        "token_identical": invariant,
        "mismatching_compositions": [k for k, bad in mismatches.items() if bad],
    }
    reports = pathlib.Path(args.report_dir)
    reports.mkdir(parents=True, exist_ok=True)
    (reports / "greedy_batch_invariance.json").write_text(
        json.dumps(payload, indent=2)
    )
    lines = [
        "# Greedy batch-composition invariance (habermas retry-elision premise)",
        "",
        f"- Generated: {payload['generated']}",
        f"- Backend: {args.backend}",
        f"- Model: {model} ({dtype}, quant={quantization}), greedy, "
        f"{max_tokens} tokens",
        "- Premise under test: argmax decode is invariant to batch width / "
        "row position, so a temperature-0 retry in a smaller batch would "
        "replay attempt 0 exactly (`methods/habermas.py` retry elision).",
        "",
        f"Result: **{'INVARIANT' if invariant else 'NOT invariant'}** across "
        f"compositions {', '.join(payload['compositions'])}.",
    ]
    if not invariant:
        lines += [
            "",
            "Mismatching compositions: "
            + ", ".join(payload["mismatching_compositions"]),
            "",
            "ACTION: the retry-elision `break` in "
            "`consensus_tpu/methods/habermas.py` rests on a premise this "
            "hardware violates — remove it or gate it per-model.",
        ]
    (reports / "greedy_batch_invariance.md").write_text("\n".join(lines) + "\n")
    print(f"token_identical={invariant}")


if __name__ == "__main__":
    main()
