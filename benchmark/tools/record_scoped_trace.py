"""Record a small trace of a probe program that carries ``jax.named_scope``
names and host annotations, on whatever device JAX has; print what the trace
says about one device operation (every stat ``ProfileData`` gives for it,
and every stat that sits on its metadata), and write the planes, with the
scope path beside each operation and the host annotations kept, to a JSON
file: the recorded trace that ``benchmark/tests/test_xplane_spans.py`` reads.

    python3 benchmark/tools/record_scoped_trace.py chiprun_out/scoped_trace.json
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
    from jax.profiler import ProfileData

    from benchmark.lib import xplane_spans
    from benchmark.lib.trace_reduce import find_xplane

    @jax.jit
    def scoped_probe(x, w):
        def layer(carry, _):
            with jax.named_scope("attention"):
                y = jnp.tanh(carry @ w)
            with jax.named_scope("ffn"):
                z = jax.nn.silu(y @ w) * 0.5
            return z, None

        with jax.named_scope("decode_step"):
            x, _ = jax.lax.scan(layer, x, None, length=4)
        with jax.named_scope("vocab_projection"):
            return x @ w.T

    x = jnp.ones((1024, 1024), jnp.bfloat16)
    w = jnp.full((1024, 1024), 1e-3, jnp.bfloat16)
    scoped_probe(x, w).block_until_ready()
    trace_dir = ROOT / ".bench_out" / "trace" / "scoped_probe"
    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    start = time.perf_counter()
    for step in range(3):
        with jax.profiler.TraceAnnotation("engine.iteration", step=step):
            with jax.profiler.TraceAnnotation("backend.launch",
                                              program="scoped_probe"):
                y = scoped_probe(x, w)
            with jax.profiler.TraceAnnotation("backend.d2h"):
                y.block_until_ready()
        with jax.profiler.TraceAnnotation("engine.idle"):
            time.sleep(0.01)
    window_s = time.perf_counter() - start
    jax.profiler.stop_trace()
    device = jax.devices()[0]
    prefix = "/device:TPU" if device.platform == "tpu" else "/host:CPU"
    path = find_xplane(str(trace_dir))

    # What one operation carries, by both ways of reading the file.
    shown = False
    for plane in ProfileData.from_file(path).planes:
        print(f"plane {plane.name}: " + ", ".join(
            f"{line.name} ({sum(1 for _ in line.events)})"
            for line in plane.lines))
        if not plane.name.startswith(prefix) or shown:
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for event in line.events:
                if "fusion" in event.name:
                    print("ProfileData stats of", event.name[:120])
                    for key, value in event.stats:
                        print(f"    {key} = {str(value)[:200]!r}")
                    shown = True
                    break
    for prefix_, label in ((prefix, "device"), ("/host:metadata", "metadata")):
        for plane_name, table in xplane_spans.read_event_metadata(
                path, prefix_).items():
            print(f"metadata stats on {plane_name} ({label}): "
                  f"{len(table)} events with stats")
            for name, stats in list(table.items())[:400]:
                if "fusion" in name or label == "metadata":
                    print("   ", name[:120])
                    for key, value in stats.items():
                        print(f"        {key} = {str(value)[:200]!r}")
                    break
    planes = xplane_spans.read_scoped_planes(path, prefix)
    hosts = xplane_spans.read_host_spans(path)
    pathlib.Path(out).parent.mkdir(parents=True, exist_ok=True)
    pathlib.Path(out).write_text(json.dumps({
        "device": {"platform": device.platform, "kind": device.device_kind},
        "window_s": window_s, "planes": planes, "host_spans": hosts},
        indent=0))
    # The file itself, while it is small: what a reader is checked against.
    keep = pathlib.Path(out).with_suffix(".xplane.pb")
    if pathlib.Path(path).stat().st_size < 8e6:
        shutil.copy(path, keep)
    shutil.rmtree(trace_dir, ignore_errors=True)
    scoped = sum(1 for _, lines in planes for _, events in lines
                 for event in events if event[3])
    print(f"wrote {out}: {len(hosts)} host spans, {scoped} scoped operations; "
          + "; ".join(f"{name}: " + ", ".join(
              f"{line} ({len(events)})" for line, events in lines)
              for name, lines in planes))


if __name__ == "__main__":
    main(sys.argv[1])
