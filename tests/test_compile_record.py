"""The process's compile record (``consensus_tpu/obs/backends.py``).

* JAX's own events, by jitted function: a meeting (an executable compiled
  or read from the persistent cache) and each stage's seconds are counted
  once, and a function traced inside another's trace adds nothing.
* Each stage is a ``backend.compile`` span on the thread that compiles: a
  child of ``backend.launch`` in the request's tree (``GET
  /v1/trace/<id>``) and on the profiler's host plane, closed also when the
  stage raises.
* The benchmark's three readers of ``/healthz``'s ``compiles`` block read
  nothing from a program without the record.
"""

import importlib.util
import json
import pathlib
import threading
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from consensus_tpu.backends import FakeBackend
from consensus_tpu.obs.backends import COMPILE_STAGES, install_compile_record
from consensus_tpu.obs.metrics import Registry, get_registry
from consensus_tpu.obs.trace import TraceContext, span, trace_current, use_trace
from consensus_tpu.serve import create_server

REPO = pathlib.Path(__file__).resolve().parents[1]
BENCH = REPO / "benchmark"
METRICS = ("setup_compile_s", "setup_programs", "window_compile_s")
STAGE_KEYS = ("trace_s", "lower_s", "compile_s")


@pytest.fixture
def record():
    return install_compile_record()


class _RawStages:
    """JAX's stage intervals as JAX reports them, by thread, beside the
    record: their union is what the record's seconds have to sum to."""

    def __init__(self):
        self.intervals = []

    def _heard(self, event, start, end, **kwargs):
        if event in COMPILE_STAGES:
            self.intervals.append((threading.get_ident(), start, end,
                                   COMPILE_STAGES[event], kwargs["fun_name"]))

    def __enter__(self):
        jax.monitoring.register_event_time_span_listener(self._heard)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_time_span_listener(self._heard)

    def union_s(self):
        total = 0.0
        for thread in {i[0] for i in self.intervals}:
            end = None
            for _, a, b, _, _ in sorted(i for i in self.intervals
                                        if i[0] == thread):
                if end is None or a >= end:
                    total += b - a
                    end = b
                elif b > end:
                    total += b - end
                    end = b
        return total

    def meetings(self):
        return sum(1 for i in self.intervals if i[3] == "compile")


def _grown(before, after, program):
    was = before["by_program"].get(program, {})
    return {key: value - was.get(key, 0)
            for key, value in after["by_program"].get(program, {}).items()}


def _stage_seconds(before, after):
    return sum(after[key] - before[key] for key in STAGE_KEYS)


def test_meetings_stages_and_seconds_are_counted_once(record):
    def _record_twice(x):
        return x * 2 + 1

    program = jax.jit(_record_twice)
    small, large = jnp.ones(3), jnp.ones(5)
    before = record.snapshot()
    with _RawStages() as raw:
        program(small)
        program(large)
        program(small)
    after = record.snapshot()
    row = _grown(before, after, "_record_twice")
    assert row["meetings"] == 2 and row["cache_reads"] == 0
    assert all(row[key] > 0 for key in STAGE_KEYS)
    assert after["programs"] - before["programs"] == raw.meetings() == 2
    assert _stage_seconds(before, after) == pytest.approx(
        raw.union_s(), abs=1e-6)


def test_a_function_traced_inside_another_adds_nothing(record):
    @jax.jit
    def _record_inner(x):
        return x + 1

    def _record_outer(x):
        return _record_inner(x) * 3

    operand = jnp.ones(4)
    before = record.snapshot()
    with _RawStages() as raw:
        jax.jit(_record_outer)(operand)
    after = record.snapshot()
    # JAX traced the inner function, inside the outer's trace ...
    assert any(i[4] == "_record_inner" for i in raw.intervals)
    # ... and the record counts that as the outer program's tracing.
    assert "_record_inner" not in after["by_program"]
    assert _grown(before, after, "_record_outer")["meetings"] == 1
    assert _stage_seconds(before, after) == pytest.approx(
        raw.union_s(), abs=1e-6)


def test_a_second_meeting_from_the_persistent_cache_is_a_cache_read(
        record, tmp_path):
    from jax._src import compilation_cache

    keys = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {key: getattr(jax.config, key) for key in keys}
    for key, value in zip(keys, (True, str(tmp_path), 0.0, -1)):
        jax.config.update(key, value)
    compilation_cache.reset_cache()
    try:
        def _record_cached(x):
            return jnp.sin(x) * 2

        operand = jnp.ones(7)
        before = record.snapshot()
        jax.jit(_record_cached)(operand)
        jax.clear_caches()
        jax.jit(_record_cached)(operand)
        after = record.snapshot()
    finally:
        for key, value in saved.items():
            jax.config.update(key, value)
        compilation_cache.reset_cache()
    row = _grown(before, after, "_record_cached")
    assert row["meetings"] == 2 and row["cache_reads"] == 1
    assert row["cache_read_s"] > 0
    assert after["cache_reads"] - before["cache_reads"] >= 1
    outcomes = {s["labels"]["outcome"]: s["value"] for s in get_registry()
                .snapshot()["families"]["backend_compile_programs_total"]
                ["series"] if s["labels"]["program"] == "_record_cached"}
    assert outcomes == {"compiled": 1, "cache_read": 1}


def test_a_stage_that_raises_still_closes_its_span(record):
    def _record_raises(x):
        raise ValueError("no program")

    operand = jnp.ones(2)
    trace = TraceContext("raises")
    with use_trace(trace), span("backend.launch", program="_record_raises"):
        carried = trace_current()
        with pytest.raises(ValueError):
            jax.jit(_record_raises)(operand)
        assert trace_current() == carried
    spans = trace.to_dict()["spans"]
    launch = next(s for s in spans if s["name"] == "backend.launch")
    compiles = [s for s in spans if s["name"] == "backend.compile"
                and s["attrs"]["program"] == "_record_raises"]
    assert [s["attrs"]["stage"] for s in compiles] == ["trace"]
    assert not compiles[0]["in_flight"]
    assert compiles[0]["parent"] == launch["id"]


class _CompilingBackend:
    """FakeBackend whose generation launches a program it has never met."""

    name = "compiling-fake"

    def __init__(self):
        self.inner = FakeBackend()

    def __getattr__(self, attr):
        return getattr(self.inner, attr)

    def generate(self, requests):
        def _request_program(x):
            return x * 3.0

        with span("backend.launch", program="_request_program"):
            jax.jit(_request_program)(np.float32(len(requests)))
        return self.inner.generate(requests)


def _call(url, body=None):
    data = None if body is None else json.dumps(body).encode("utf-8")
    request = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"},
        method="GET" if body is None else "POST")
    with urllib.request.urlopen(request, timeout=60.0) as response:
        return response.status, json.loads(response.read().decode("utf-8"))


def test_a_request_that_meets_a_new_program_shows_it_under_backend_launch(
        record):
    server = create_server(backend=_CompilingBackend(), port=0,
                           registry=Registry()).start()
    try:
        status, _ = _call(server.base_url + "/v1/consensus", {
            "issue": "Should we invest in public transport?",
            "agent_opinions": {"Agent 1": "Yes, buses are vital.",
                               "Agent 2": "Only with congestion pricing."},
            "method": "best_of_n", "params": {"n": 2, "max_tokens": 8},
            "seed": 3, "request_id": "compile-tree-1", "trace": True})
        assert status == 200
        status, tree = _call(server.base_url + "/v1/trace/compile-tree-1")
        assert status == 200
        status, health = _call(server.base_url + "/healthz")
    finally:
        server.stop(drain=False, timeout=5.0)
    by_id = {s["id"]: s for s in tree["spans"]}
    compiles = [s for s in tree["spans"] if s["name"] == "backend.compile"
                and s["attrs"]["program"] == "_request_program"]
    assert {s["attrs"]["stage"] for s in compiles} == {
        "trace", "lower", "compile"}
    for row in compiles:
        assert by_id[row["parent"]]["name"] == "backend.launch"
        assert not row["in_flight"]
    assert [s["attrs"]["cache_read"] for s in compiles
            if s["attrs"]["stage"] == "compile"] == [False]
    # /healthz carries the record: what start-up and this request paid.
    assert health["compiles"]["by_program"]["_request_program"]["meetings"] >= 1
    assert set(STAGE_KEYS) <= set(health["compiles"])


def test_the_host_plane_nests_backend_compile_in_backend_launch(
        record, tmp_path):
    from benchmark.lib.trace_reduce import find_xplane
    from benchmark.lib.xplane_spans import read_host_spans

    def _record_profiled(x):
        return jnp.cos(x) + 1

    operand = jnp.ones(6)
    with jax.profiler.trace(str(tmp_path)):
        with span("backend.launch", program="_record_profiled"):
            jax.jit(_record_profiled)(operand).block_until_ready()
    host = read_host_spans(find_xplane(str(tmp_path)))
    launch = [s for s in host if s[0] == "backend.launch"]
    compiles = [s for s in host if s[0] == "backend.compile"
                and s[4].get("program") == "_record_profiled"]
    assert len(launch) == 1
    assert sorted(s[4]["stage"] for s in compiles) == [
        "compile", "lower", "trace"]
    for _, thread, start, end, _ in compiles:
        assert thread == launch[0][1]
        assert launch[0][2] <= start <= end <= launch[0][3]


# -- the benchmark's readers -------------------------------------------------


def _metric(name):
    metric = json.loads((BENCH / "metrics" / f"{name}.json").read_text())
    path = BENCH / "readers" / f"{metric['reader']}.py"
    spec = importlib.util.spec_from_file_location(
        f"compile_reader_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return metric, module.read


def _block(trace_s, lower_s, compile_s, programs, cache_reads, by_program):
    return {"trace_s": trace_s, "lower_s": lower_s, "compile_s": compile_s,
            "cache_read_s": 0.5 * cache_reads, "programs": programs,
            "cache_reads": cache_reads, "by_program": by_program}


def _row(seconds, meetings):
    return {"trace_s": seconds / 4, "lower_s": seconds / 4,
            "compile_s": seconds / 2, "cache_read_s": 0.0,
            "meetings": meetings, "cache_reads": 0}


@pytest.mark.parametrize("name", METRICS)
def test_a_program_without_the_record_reads_nothing(name):
    metric, read = _metric(name)
    for health in ({"status": "ok", "engine": {"iterations": 3}}, {}, None):
        context = {"health_before": health, "health_after": health,
                   "deltas": {}, "compiled": {"programs": 0}}
        assert read(context, metric) is None


@pytest.mark.parametrize("name", METRICS)
def test_the_readers_read_the_record(name):
    metric, read = _metric(name)
    before = _block(10.0, 20.0, 30.0, 200, 150, {
        "paged_score_chunk": _row(40.0, 50), "_embed_forward": _row(8.0, 70),
        "add": _row(0.5, 0)})
    after = _block(10.5, 20.5, 31.0, 203, 150, before["by_program"])
    value = read({"health_before": {"compiles": before},
                  "health_after": {"compiles": after}}, metric)
    if name == "setup_compile_s":
        assert value["value"] == 60.0
        assert value["cache_reads"] == 150 and value["cache_read_s"] == 75.0
        assert value["by_seconds"][:2] == [["paged_score_chunk", 40.0, 50],
                                           ["_embed_forward", 8.0, 70]]
    elif name == "setup_programs":
        assert value["value"] == 200
        assert (value["compiled"], value["cache_reads"]) == (50, 150)
        assert value["functions"] == 2
        assert value["by_meetings"][0] == ["_embed_forward", 8.0, 70]
    else:
        assert value == {"value": 2.0, "programs": 3}
    # a window that met nothing reads 0, not nothing
    if name == "window_compile_s":
        assert read({"health_before": {"compiles": before},
                     "health_after": {"compiles": before}},
                    metric) == {"value": 0.0, "programs": 0}
