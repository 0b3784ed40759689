"""The new per-layer readers on span trees and contexts made by hand, and on
the recorded trace: what each reads, and that each returns nothing (so that
the metric is left out of the line) where its spans or the trace are
missing.  The harness end to end on the CPU prints the three metrics that
come from the span trees."""

import functools
import importlib.util
import json
import pathlib
import types

import pytest

from benchmark.lib import useful, xplane_spans
from benchmark.lib.peaks import peaks
from benchmark.work import dense

TESTS = pathlib.Path(__file__).resolve().parent
BENCH = TESTS.parent
XPLANE = str(TESTS / "scoped_trace.xplane.pb")
MODEL = json.loads((BENCH / "configs" / "smollm2-1.7b.json").read_text())["model"]


def reader(name):
    spec = importlib.util.spec_from_file_location(
        f"reader_{name}", BENCH / "readers" / f"{name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def metric(name):
    return json.loads((BENCH / "metrics" / f"{name}.json").read_text())


def span(id, name, parent, start, duration):
    return {"id": id, "name": name, "parent": parent, "start_s": start,
            "duration_s": duration}


TREE = [
    span(1, "http_request", None, 0.0, 10.0),
    span(2, "serve.parse", 1, 0.0, 0.1),
    span(3, "queue_wait", 1, 0.1, 0.2),
    span(4, "handler", 1, 0.3, 9.6),
    span(5, "serve.method", 4, 0.4, 7.0),
    span(6, "method.render", 5, 0.4, 0.1),
    span(7, "method.generate", 5, 0.5, 4.0),
    span(8, "engine_generate", 7, 0.6, 3.8),
    span(9, "engine_row", 8, 0.6, 3.8),
    span(10, "engine.dispatch", 8, 2.6, 1.7),
    span(11, "method.score", 5, 4.5, 2.8),
    span(12, "engine_score_matrix", 11, 4.5, 2.8),
    span(13, "engine.dispatch", 12, 5.0, 1.0),
    span(14, "engine.dispatch", 12, 6.5, 0.5),
    span(15, "method.select", 5, 7.3, 0.05),
    span(16, "serve.evaluate", 4, 7.4, 2.4),
    span(17, "engine_embed", 16, 7.5, 2.0),
    span(18, "engine.dispatch", 17, 9.0, 0.5),
]


def test_span_tree_measures_by_hand():
    tree = reader("span_tree")
    assert tree.queue_wait(TREE) == pytest.approx(0.2)
    # calls 3.8 + 2.8 + 2.0 in the engine, 1.7 + 1.5 + 0.5 of them dispatched
    assert tree.engine_wait(TREE) == pytest.approx(8.6 - 3.7)
    # handler 9.6 - (7.0 + 2.4); serve.method 7.0 - (0.1 + 4.0 + 2.8 + 0.05);
    # render 0.1; generate 4.0 - 3.8; score 2.8 - 2.8; select 0.05;
    # serve.evaluate 2.4 - 2.0
    assert tree.method_host(TREE) == pytest.approx(
        0.2 + 0.05 + 0.1 + 0.2 + 0.0 + 0.05 + 0.4)
    # two of a request's calls in the engine at once wait once
    both = TREE + [span(19, "engine_score", 16, 7.5, 1.0)]
    assert tree.engine_wait(both) == pytest.approx(8.6 - 3.7)


def test_span_tree_measures_leave_out_what_the_program_does_not_write():
    tree = reader("span_tree")
    parent = [s for s in TREE if "." not in s["name"]]  # no span() yet
    assert tree.engine_wait(parent) is None
    assert tree.method_host(parent) is None
    assert tree.queue_wait(parent) == pytest.approx(0.2)
    assert tree.queue_wait([TREE[0]]) is None


def test_span_tree_reader_reads_the_store():
    from consensus_tpu.obs.trace import TraceContext, get_trace_store, span

    trace = TraceContext("readers-1")
    root = trace.begin("http_request")
    wait = trace.begin("queue_wait", parent=root)
    trace.end(wait)
    call = trace.begin("engine_embed", parent=root)
    with span("engine.dispatch", traces=[(trace, call)], kind="embed", rows=1):
        pass
    trace.end(call)
    trace.end(root)
    get_trace_store().put(trace)
    sent = [types.SimpleNamespace(payload={"request_id": "readers-1"},
                                  seconds=0.5),
            types.SimpleNamespace(payload={"request_id": "never-sent"},
                                  seconds=None)]
    tree = reader("span_tree")
    reading = tree.read({"sent": sent}, metric("engine_wait_ms"))
    assert reading["requests_read"] == 1 and reading["requests"] == 2
    assert reading["value"] >= 0.0
    # one request whole beside the mean: its phases sum to its root span
    example = reading["example"]
    assert example["client_s"] == 0.5
    assert sum(example["phases"].values()) == pytest.approx(
        example["http_request_s"], abs=1e-4)
    assert "example" not in tree.read({"sent": sent}, metric("queue_wait_ms"))
    assert tree.read({"sent": sent}, metric("method_host_ms")) is None
    assert tree.read({"sent": sent[1:]}, metric("queue_wait_ms")) is None


def context(**over):
    base = {"cell": types.SimpleNamespace(name="no-such-cell", model=MODEL,
                                          work=dense),
            "peak": peaks("TPU v5 lite"), "traced": None, "calls": [],
            "tally": functools.partial(useful.tally, dense, MODEL)}
    return {**base, **over}


@pytest.mark.parametrize("name", ["idle_attributed_pct", "attention_roofline",
                                  "vocab_projection_roofline",
                                  "scoped_device_pct"])
def test_trace_readers_return_nothing_without_a_trace(name):
    m = metric(name)
    read = reader(m["reader"]).read
    assert read(context(), m) is None  # no traced stretch
    assert read(context(traced=[1.0, 2.0]), m) is None  # no file


def recorded(host_spans=True):
    """A reader's context with the recorded trace as the run's own, the
    anchor put where the first launch starts (the probe has none)."""
    data = {"planes": xplane_spans.read_scoped_planes(XPLANE),
            "host": xplane_spans.read_host_spans(XPLANE) if host_spans else []}
    start = data["host"][0][2] - 2e6 if host_spans else 4.0e7
    data["host"] = [("bench_anchor", "main", start, start + 1.0, {})] + data["host"]
    return context(traced=[100.0, 100.04], xplane_spans=data)


def test_idle_attributed_on_the_recorded_trace():
    m = metric("idle_attributed_pct")
    reading = reader("idle_attributed").read(recorded(), m)
    # The probe sleeps inside engine.idle between its launches: most of the
    # idle time is nobody's, the rest is the launch's and the fetch's.
    assert 0.0 < reading["value"] < 50.0
    assert reading["by_span"][0][0] == "engine.idle"
    assert {name for name, _ in reading["by_span"]} <= {
        "engine.idle", "engine.iteration", "backend.launch", "backend.d2h",
        xplane_spans.UNNAMED}
    assert reading["idle_s"] > 0.02 and reading["gaps"] >= 2
    # A program that writes no span has nothing to attribute to.
    assert reader("idle_attributed").read(
        recorded(host_spans=False), m) is None


def test_scoped_device_on_the_recorded_trace():
    m = dict(metric("scoped_device_pct"), programs=["scoped_probe"])
    reading = reader("scoped_device").read(recorded(), m)
    # attention and vocab_projection are names of MODEL_SCOPES; the probe's
    # ffn is one too.
    assert reading["value"] > 80.0
    assert set(reading["by_scope_s"]) >= {
        "decode_step/attention", "decode_step/ffn", "-/vocab_projection"}
    assert sum(reading["by_scope_s"].values()) == pytest.approx(
        reading["device_s"])
    # Programs the metric does not name are not its to read.
    assert reader("scoped_device").read(
        recorded(), metric("scoped_device_pct")) is None


def test_scope_roofline_on_the_recorded_trace():
    m = dict(metric("vocab_projection_roofline"), programs=["scoped_probe"])
    ctx = recorded()
    request = types.SimpleNamespace(chat=False, user_prompt="p" * 10,
                                    system_prompt=None, seed=1)
    ctx["calls"] = [{"kind": "generate", "start": 100.0, "end": 100.04,
                     "requests": [request],
                     "results": [types.SimpleNamespace(token_ids=(5, 5))]}]
    reading = reader("scope_roofline").read(ctx, m)
    assert set(reading) == {"value", "bound", "least_s", "device_s"}
    # three (1024, 1024) x (1024, 1024) products under vocab_projection
    assert reading["device_s"] == pytest.approx(3 * 12.6e-6, rel=0.02)
    assert reading["bound"] == "bandwidth"  # the head's table, three times
    assert reading["least_s"] == pytest.approx(3 * 201_326_592 / 819e9, rel=1e-3)
    # No peak for the device, or no work in the stretch: nothing.
    assert reader("scope_roofline").read(dict(ctx, peak=None), m) is None
    assert reader("scope_roofline").read(dict(ctx, calls=[]), m) is None


def test_cpu_rehearsal_prints_the_span_tree_metrics():
    from benchmark.tests.test_harness_cpu import run_cell

    line, _ = run_cell("--workload", "tiny-dense.bon_small", "--seed", "77",
                       "--seconds", "2", "--trace", "1")
    assert line["correct"] is True
    for name in ("queue_wait_ms", "engine_wait_ms", "method_host_ms"):
        reading = line["metrics"][name]
        assert reading["unit"] == "ms" and reading["value"] >= 0.0
        assert reading["requests_read"] == reading["requests"] == line["attempted"]
    # What reads the device plane finds none in a CPU's trace.
    for name in ("idle_attributed_pct", "attention_roofline",
                 "vocab_projection_roofline", "scoped_device_pct"):
        assert name not in line["metrics"]
