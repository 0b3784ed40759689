"""Record a small trace of a named probe program on whatever device JAX has
and write its device planes, as ``reduce_planes`` takes them, to a JSON file:
the recorded trace the reduction's test is checked on.

    python3 benchmark/tools/record_trace.py chiprun_out/probe_trace.json
"""

from __future__ import annotations

import json
import pathlib
import shutil
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def main(out: str) -> None:
    import jax
    import jax.numpy as jnp

    from benchmark.lib.trace_reduce import find_xplane, read_planes

    @jax.jit
    def bench_probe(x):
        return jnp.tanh(x @ x) * 0.5

    x = jnp.ones((1024, 1024), jnp.bfloat16)
    bench_probe(x).block_until_ready()
    trace_dir = ROOT / ".bench_out" / "trace" / "probe"
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(str(trace_dir))
    start = time.perf_counter()
    for _ in range(3):
        x = bench_probe(x)
        x.block_until_ready()
        time.sleep(0.01)
    window_s = time.perf_counter() - start
    jax.profiler.stop_trace()
    device = jax.devices()[0]
    prefix = "/device:TPU" if device.platform == "tpu" else "/host:CPU"
    planes = read_planes(find_xplane(str(trace_dir)), prefix)
    pathlib.Path(out).parent.mkdir(parents=True, exist_ok=True)
    pathlib.Path(out).write_text(json.dumps({
        "device": {"platform": device.platform, "kind": device.device_kind},
        "window_s": window_s, "planes": planes}, indent=0))
    shutil.rmtree(trace_dir, ignore_errors=True)
    print(f"wrote {out}: " + "; ".join(
        f"{name}: " + ", ".join(f"{line} ({len(events)})" for line, events in lines)
        for name, lines in planes))


if __name__ == "__main__":
    main(sys.argv[1])
