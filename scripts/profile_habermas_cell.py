"""Profile one north-star habermas_only cell at device-dispatch granularity.

Round-3 continuation: the habermas-family cells dominate the timed sweep
(~65 of 92 min), yet a roofline estimate of their decode work is several
times smaller than the measured cell wall.  This script runs the exact
scenario-1 habermas_only cell (30 runs: nc {2,5,10} x rounds {1,2} x 5
seeds) with instrumentation on every level of the stack:

- BatchingBackend flushes (merged request counts per flush)
- TPUBackend.generate calls (rows, wall)
- generate_tokens_shared_trunk / generate_tokens device dispatches
  (rows, prompt width, max_new, wall)

so the gap between "roofline decode time" and "measured cell wall" is
attributed instead of guessed.
"""

from __future__ import annotations

import json
import os
import time

import numpy as np
import yaml

import consensus_tpu.models.generate as gen_mod
from consensus_tpu.backends import get_backend
from consensus_tpu.experiment import Experiment

CONFIG = os.environ.get("PROFILE_CONFIG", "configs/north_star/gemma/scenario_1/habermas_only.yaml")

dispatches = []


def wrap_dispatch(name, fn):
    def wrapped(params, config, prompt_tokens, prompt_valid, *args, **kwargs):
        t0 = time.perf_counter()
        out = fn(params, config, prompt_tokens, prompt_valid, *args, **kwargs)
        np.asarray(out.tokens)  # wait for the device (np fields: no-op)
        wall = time.perf_counter() - t0
        if name.startswith("shared"):
            batch = args[0]
        else:
            batch = prompt_tokens.shape[0]
        max_new = kwargs.get("max_new_tokens", "?")
        dispatches.append(
            {
                "kind": name,
                "rows": int(batch),
                "ctx_width": int(prompt_tokens.shape[1]),
                "max_new": max_new,
                "wall_s": round(wall, 3),
            }
        )
        return out

    return wrapped


gen_mod.generate_tokens_shared_trunk = wrap_dispatch(
    "shared", gen_mod.generate_tokens_shared_trunk
)
gen_mod.generate_tokens = wrap_dispatch("classic", gen_mod.generate_tokens)
# Segmented entry points (the default for long budgets) are whole host
# loops, not single dispatches — timed the same way for attribution.
gen_mod.generate_tokens_shared_trunk_segmented = wrap_dispatch(
    "shared-seg", gen_mod.generate_tokens_shared_trunk_segmented
)
gen_mod.generate_tokens_segmented = wrap_dispatch(
    "classic-seg", gen_mod.generate_tokens_segmented
)
# tpu.py binds generate_tokens at module import; patch its reference too.
import consensus_tpu.backends.tpu as tpu_mod  # noqa: E402

tpu_mod.generate_tokens = gen_mod.generate_tokens


def main() -> None:
    with open(CONFIG) as f:
        config = yaml.safe_load(f)

    if os.environ.get("PROFILE_PIN"):
        # Mirror run_sweep --timing-pin-budget in full: the method-side
        # pin_budget half is injected by Experiment._run_configs from this
        # flag, and the backend-side pin_generation_budget half (device
        # EOS early-exit disabled) is applied to the explicit backend below.
        config["timing_pin_budget"] = True

    backend_opts = dict(config.get("backend_options") or {})
    if config.get("timing_pin_budget") and config.get("backend") == "tpu":
        backend_opts["pin_generation_budget"] = True
    backend = get_backend(config.get("backend"), **backend_opts)

    # Instrument the inner generate (what each Batching flush calls).
    inner_calls = []
    orig_generate = backend.generate

    def timed_generate(requests):
        t0 = time.perf_counter()
        out = orig_generate(requests)
        inner_calls.append(
            {"rows": len(requests), "wall_s": round(time.perf_counter() - t0, 3)}
        )
        return out

    backend.generate = timed_generate

    config["output_dir"] = "/tmp/profile_habermas"
    t0 = time.perf_counter()
    experiment = Experiment(config, backend=backend)
    frame = experiment.run()
    total = time.perf_counter() - t0

    gen_time = sum(d["wall_s"] for d in dispatches)
    inner_time = sum(c["wall_s"] for c in inner_calls)
    print(json.dumps({
        "cell_wall_s": round(total, 1),
        "statements": len(frame),
        "device_dispatches": len(dispatches),
        "device_dispatch_s": round(gen_time, 1),
        "inner_generate_calls": len(inner_calls),
        "inner_generate_s": round(inner_time, 1),
        "host_overhead_s": round(total - inner_time, 1),
        "tokenize_etc_s": round(inner_time - gen_time, 1),
        "batch_counts": getattr(experiment, "last_batch_counts", None),
        "token_counts": dict(getattr(backend, "token_counts", {}) or {}),
    }, indent=2))
    print("\n-- inner generate calls (rows, wall) --")
    for c in inner_calls:
        print(f"  rows={c['rows']:4d}  wall={c['wall_s']:8.3f}s")
    print("\n-- device dispatches --")
    for d in dispatches:
        print(
            f"  {d['kind']:8s} rows={d['rows']:4d} ctx={d['ctx_width']:5d} "
            f"max_new={d['max_new']} wall={d['wall_s']:8.3f}s"
        )


if __name__ == "__main__":
    main()
