"""Read a cell's compared numbers over many seeds in one process.

    python3 benchmark/tools/seeds.py --workload <cell> --seconds <s> \
        --seeds 11,12,13 --control-seeds 11,12 --out chiprun_out/seeds.jsonl

Each seed is one whole run of ``benchmark/run.py`` (weights, server, warm-up,
window, reference), but in one process, so that only the first pays for
tracing and compiling.  For the seeds under ``--control-seeds`` the float8
control is read too, through the same comparison (``control_correct`` has to
be false).  A limit is set from these readings: the largest the program
gives, the smallest the control gives.  Never part of a measured
run: ``setup_s`` and the memory peak of any seed but the first mean nothing
here.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def main() -> int:
    from benchmark import run as bench

    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--control-seeds", default="")
    parser.add_argument("--platform", choices=("tpu", "cpu"), default="tpu")
    parser.add_argument("--bench-dir", action="append", default=[])
    parser.add_argument("--out", required=True)
    args = parser.parse_args()
    control = {int(s) for s in args.control_seeds.split(",") if s}
    out = pathlib.Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    with open(out, "a") as handle:
        for seed in (int(s) for s in args.seeds.split(",")):
            bench.START = time.perf_counter()
            result = bench.run(argparse.Namespace(
                workload=args.workload, seed=seed, seconds=args.seconds, trace=0,
                platform=args.platform, bench_dir=args.bench_dir,
                control=seed in control))
            line = {"seed": seed, "correct": result["correct"],
                    "attempted": result["attempted"], "failed": result["failed"],
                    "metrics": {k: v["value"] for k, v in result["metrics"].items()},
                    "phases": result["phases"], "sizes": result["sizes"],
                    "requests": result["requests"],
                    "compared": result["compared"],
                    "control_correct": result.get("control_correct"),
                    "control": result.get("control")}
            handle.write(json.dumps(line) + "\n")
            handle.flush()
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
