"""Run one cell of the benchmark once and print its result line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: it makes the weights on the device from the seed, builds the
server as ``python -m consensus_tpu.serve --backend tpu`` does, warms up what
the cell's traffic uses (set-up; ``harness.warm_up`` says how), drives ``POST
/v1/consensus`` over the loopback for ``--seconds`` seconds, frees the
program's state, and holds a sample of what was served against the plain
float32 reference that the cell's configuration names.  The last line
of standard output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, in a traced run ``breakdown``, and last ``compared``.

It needs a TPU whose kind is in the table of peaks: anywhere else it exits
with code 2 and prints no result.  ``--platform cpu`` exists for the
rehearsal only and stamps ``cpu`` into ``device``.
"""

from __future__ import annotations

import argparse
import functools
import gc
import json
import os
import pathlib
import shutil
import sys
import threading
import time
from typing import Any, Dict, List, Optional

START = time.perf_counter()
HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def log(message: str) -> None:
    print(f"[bench {time.perf_counter() - START:7.1f}s] {message}",
          file=sys.stderr, flush=True)


def require_device(platform: str, chips: int) -> Dict[str, Any]:
    """The devices as JAX reports them, or exit code 2 with no result."""
    try:
        import jax

        devices = jax.devices()
    except Exception as exc:  # no backend at all is also "no accelerator"
        print(f"benchmark: JAX found no device: {exc}", file=sys.stderr)
        raise SystemExit(2)
    found = devices[0].platform
    if found != platform:
        print(f"benchmark: needs {platform}, JAX reports {found!r} "
              f"({devices[0].device_kind}); nothing was measured", file=sys.stderr)
        raise SystemExit(2)
    if len(devices) < chips:
        print(f"benchmark: the cell needs {chips} chip(s), JAX reports "
              f"{len(devices)}; nothing was measured", file=sys.stderr)
        raise SystemExit(2)
    return {"platform": found, "kind": devices[0].device_kind,
            "count": len(devices)}


def trace_anchor(path: str, name: str) -> Optional[float]:
    """Start (ns) of the host annotation ``name`` in the trace: the point
    both clocks saw."""
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for event in line.events:
                if event.name == name:
                    return float(event.start_ns)
    return None


def label_gaps(gaps_ns, anchor_ns: Optional[float], anchor_clock: float,
               calls: List[Dict[str, Any]], sent: List[Any]) -> List[List[Any]]:
    """Each idle gap against the benchmark's own spans: which calls into the
    backend layer were open at its middle, and how many requests in flight."""
    out = []
    for start, stop in gaps_ns:
        label = "unplaced"
        if anchor_ns is not None:
            middle = anchor_clock + ((start + stop) / 2 - anchor_ns) / 1e9
            kinds = {call["kind"] for call in calls
                     if call["start"] <= middle <= call["end"]}
            flying = sum(1 for s in sent if s.sent <= middle <= (s.done or middle))
            label = ("+".join(sorted(kinds)) or "no_backend_call") + \
                f"|{flying}_in_flight"
        out.append([label, (stop - start) / 1e9])
    merged: Dict[str, float] = {}
    for label, seconds in out:
        merged[label] = merged.get(label, 0.0) + seconds
    return sorted(([k, v] for k, v in merged.items()), key=lambda kv: -kv[1])[:10]


def run(args: argparse.Namespace) -> Dict[str, Any]:
    from benchmark.lib import check, harness, useful
    from benchmark.lib import reference as ref
    from benchmark.lib.meter import CompileMeter
    from benchmark.lib.peaks import peaks
    from benchmark.lib.recorder import Recorder
    from benchmark.lib.trace_reduce import (describe, find_xplane, read_planes,
                                            reduce_planes)

    bench_dirs = [pathlib.Path(d).resolve() for d in args.bench_dir] + [HERE]
    cell = harness.load_cell(bench_dirs, args.workload)
    # Before anything runs: a key of the model block that the cell's
    # reference does not compute stops here, with the key in the message.
    cfg = cell.reference.ref_config(cell.model)
    device = require_device(args.platform, int(cell.workload["chips"]))
    peak = peaks(device["kind"]) if device["platform"] == "tpu" else None
    metrics = harness.load_metrics(bench_dirs) if args.trace else {}

    from consensus_tpu.utils.compile_cache import enable_compile_cache

    import jax

    cache_dir = enable_compile_cache()
    # Small programs too, wherever the cache lies: what set-up compiles in a
    # cell's first run, every later run reads.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    log(f"cell {cell.name} seed {args.seed} on {device}; compile cache at "
        f"{cache_dir}")

    out_dir = ROOT / ".bench_out"
    trace_dir = out_dir / "trace" / cell.name
    recorder = Recorder()
    with CompileMeter() as meter:
        config = harness.model_config(cell)
        params = harness.make_params(config, args.seed)
        phases = {"weights_s": time.perf_counter() - START}
        log(f"weights made: {meter.totals()}")
        with harness.serving(cell, params, config) as server:
            backend = server.scheduler.inner_backend
            recorder.attach(backend)
            phases["server_s"] = time.perf_counter() - START - phases["weights_s"]
            log("server up")
            warm = harness.warm_up(server, cell, meter, args.seed, log, recorder)
            recorder.clear()
            health_before = harness.traffic_lib.get_json(server.base_url, "/healthz")
            registry_before = harness.registry_now()
            compiled_before = meter.totals()
            named_before = len(meter.names)
            setup_s = time.perf_counter() - START
            phases["warm_up_s"] = setup_s - phases["weights_s"] - phases["server_s"]
            log(f"set-up done in {setup_s:.1f}s; window of {args.seconds}s")

            # The window runs in a thread of its own so that a traced run can
            # start and stop the profiler from this one, a few seconds into
            # the window: a trace of the whole window would be hundreds of
            # megabytes, and one from its first second would show the ramp.
            box: Dict[str, Any] = {}

            def drive_window() -> None:
                try:
                    box["sent"] = harness.window(server, cell, args.seed,
                                                args.seconds,
                                                warm["greedy_scenario"])
                except BaseException as exc:  # handed to the main thread
                    box["error"] = exc

            driver = threading.Thread(target=drive_window, name="window")
            driver.start()
            traced = None
            if args.trace:
                plan = cell.workload.get("trace", {})
                time.sleep(min(float(plan.get("start_s", 8.0)), args.seconds / 4))
                shutil.rmtree(trace_dir, ignore_errors=True)
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0  # no Python call stacks
                options.host_tracer_level = 1
                jax.profiler.start_trace(str(trace_dir), profiler_options=options)
                with jax.profiler.TraceAnnotation("bench_anchor"):
                    anchor_clock = time.perf_counter()
                time.sleep(min(float(plan.get("seconds", 24.0)), args.seconds / 2))
                traced = [anchor_clock, time.perf_counter()]
                jax.profiler.stop_trace()
            driver.join()
            if "error" in box:
                raise box["error"]
            sent = box["sent"]
            compiled = meter.since(compiled_before)
            compiled["names"] = meter.names[named_before:][:8]
            deltas = harness.registry_deltas(registry_before)
            health_after = harness.traffic_lib.get_json(server.base_url, "/healthz")
            truncated = int(backend.truncated_prompts)
            calls = recorder.snapshot()
            recorder.detach()
            memory_peak = harness.memory_peak_bytes()
            log(f"window closed: {len(sent)} requests, compiled {compiled}")
            served_sums = ref.weights_checksum(params)
            del backend
        # The program's state goes before the reference comes: the server is
        # stopped, its backend dropped from the program's cache, the weights
        # freed.
        del server, params
        gc.collect()

        problems = {s.index: harness.answer_problems(s) for s in sent}
        answered = sum(1 for p in problems.values() if not p)
        e2e = harness.end_to_end(cell, sent)

        numbers = check.Numbers()
        numbers.add("truncated", truncated)
        weights = cell.reference.make_weights(cfg, args.seed)
        numbers.add("weights", *check.differing_leaves(
            served_sums, ref.weights_checksum(weights)))
        jobs, sample = check.gather(
            cell, check.choose_sample(cell, sent, calls, args.seed), calls,
            numbers, args.seed)
        check_start = time.perf_counter()
        check.compare(cell.reference, cfg, weights, jobs, numbers)
        control = None
        if args.control:
            control = check.control_numbers(cell.reference, cfg, weights, jobs,
                                            numbers)
        del weights
        phases["window_s"] = e2e["window_span_s"]
        phases["check_s"] = time.perf_counter() - check_start
        log(f"reference ran over {len(sample)} request(s) in "
            f"{phases['check_s']:.1f}s")
        correct, compared = check.verdict(numbers, cell.workload.get("limits", {}))
        correct = correct and answered == len(sent) and bool(sample)

    lengths = useful.Lengths()
    result: Dict[str, Any] = {
        "correct": bool(correct),
        "attempted": len(sent),
        "failed": len(sent) - answered,
    }
    device["memory_peak_bytes"] = memory_peak
    if not args.trace:
        result["metrics"] = {
            "statements_per_s": {"value": e2e["statements_per_s"], "unit": "1/s"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
        if "time_to_statement_p90_s" in e2e:
            result["metrics"]["time_to_statement_p90_s"] = {
                "value": e2e["time_to_statement_p90_s"], "unit": "s"}
    else:
        xplane = find_xplane(str(trace_dir))
        reduced = None
        if xplane is not None:
            prefix = "/device:TPU" if device["platform"] == "tpu" else "/host:CPU"
            planes = read_planes(xplane, prefix)
            for plane_name, lines in planes:
                log(f"trace plane {plane_name}: " + ", ".join(
                    f"{line_name} ({len(events)} events)"
                    for line_name, events in lines))
            # The work is counted between the anchor and the stop, on the
            # host's clock; the device seconds are cut to the same stretch.
            anchor = trace_anchor(xplane, "bench_anchor")
            reduced = reduce_planes(
                planes, traced[1] - traced[0],
                None if anchor is None else
                (anchor, anchor + (traced[1] - traced[0]) * 1e9))
            if reduced is None:
                log("no device plane with events; the trace holds: "
                    + " | ".join(describe(xplane))[:3000])
            log(f"trace of {os.path.getsize(xplane) / 1e6:.0f} MB reduced")
        context = {
            "cell": cell, "peak": peak, "sent": sent, "answered": answered,
            "span_s": e2e["window_span_s"],
            "first_send": min(s.sent for s in sent),
            "last_done": max(s.done for s in sent),
            "deltas": deltas, "health_before": health_before,
            "health_after": health_after, "compiled": compiled, "calls": calls,
            "trace": reduced, "traced": traced, "memory_peak_bytes": memory_peak,
            # One cache of token counts for every reader's tally.
            "tally": functools.partial(useful.tally, cell.work, cell.model,
                                       lengths=lengths),
        }
        result["metrics"] = {}
        listed = {}
        if (ROOT / "BENCHMARK.json").exists():
            listed = {m["name"]: m for m in harness.load_json(
                ROOT / "BENCHMARK.json")["per_layer"]}
        for name, metric in sorted(metrics.items()):
            # A metric that BENCHMARK.json lists for other cells only is not
            # this cell's to report.
            if cell.name not in listed.get(name, {}).get("workloads", [cell.name]):
                continue
            value = metric["read"](context, metric)
            if isinstance(value, dict):  # a reading with what stands beside it
                result["metrics"][name] = {**value, "unit": metric["unit"]}
            elif value is not None:
                result["metrics"][name] = {"value": value, "unit": metric["unit"]}
        if reduced is not None:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            result["breakdown"] = {
                "device_ops": [[name, seconds] for name, seconds in sorted(
                    reduced["by_program_s"].items(), key=lambda kv: -kv[1])[:10]],
                "idle_gaps": label_gaps(reduced["gaps_ns"], anchor, traced[0],
                                        calls, sent),
            }
        shutil.rmtree(trace_dir, ignore_errors=True)
    result["device"] = device
    phases["total_s"] = time.perf_counter() - START
    first_send = min(s.sent for s in sent)
    result["requests"] = [[round(s.sent - first_send, 3), round(s.seconds, 3)]
                          for s in sent if s.seconds is not None]
    result["sizes"] = useful.matrix_sizes(calls, lengths)
    result["calls"] = {kind: sum(1 for c in calls if c["kind"] == kind)
                       for kind in sorted({c["kind"] for c in calls})}
    result["phases"] = phases
    result["setup"] = {"warm_up": warm, "jax": meter.totals()}
    if control is not None:
        # The control through the same comparison: it has to read not correct.
        result["control_correct"], result["control"] = check.verdict(
            control, cell.workload.get("limits", {}))
    result["compared"] = compared
    for name, entry in compared.items():
        print(f"compared {name}: {entry}", file=sys.stderr)
    print(f"correct: {result['correct']} (answered {answered} of {len(sent)})",
          file=sys.stderr)
    return result


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--platform", choices=("tpu", "cpu"), default="tpu",
                        help="cpu is for the rehearsal only")
    parser.add_argument("--bench-dir", action="append", default=[],
                        help="a further directory of configs/, traffic/, "
                             "workloads/, metrics/, readers/, references/ and "
                             "work/, searched first")
    parser.add_argument("--control", action="store_true",
                        help="also read the float8 control's numbers (for "
                             "setting limits; never part of a measured run)")
    args = parser.parse_args(argv)
    try:
        import consensus_tpu  # noqa: F401
    except ImportError as exc:
        print(f"benchmark: not in a checkout of the repository: {exc}",
              file=sys.stderr)
        return 2
    result = run(args)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # Every thread the server started has been joined by now; a stray
    # daemon thread of the profiler must not keep the process.
    os._exit(code)
