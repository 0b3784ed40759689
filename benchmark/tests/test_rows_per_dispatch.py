"""``generate_rows_per_dispatch`` on span trees made by hand and on trees the
program's own span primitive wrote into the trace store: a request whose
generation went out in four dispatches of 8 rows reads 8, one of 32 reads
32, and a tree with no such span returns nothing, so that the metric is left
out of the line."""

import importlib.util
import json
import pathlib
import types

import pytest

BENCH = pathlib.Path(__file__).resolve().parent.parent
METRIC = json.loads(
    (BENCH / "metrics" / "generate_rows_per_dispatch.json").read_text())


def reader():
    spec = importlib.util.spec_from_file_location(
        "reader_rows_per_dispatch", BENCH / "readers" / "rows_per_dispatch.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def span(id, name, parent, **attrs):
    return {"id": id, "name": name, "parent": parent, "start_s": 0.1 * id,
            "duration_s": 0.05, "attrs": attrs}


def tree(generate_rows):
    """One best_of_n request: its generation call dispatched in cohorts of
    ``generate_rows``, then a score matrix and an embedding call."""
    spans = [span(1, "http_request", None), span(2, "handler", 1),
             span(3, "engine_generate", 2, rows=32)]
    spans += [span(10 + i, "engine.dispatch", 3, kind="generate", rows=n)
              for i, n in enumerate(generate_rows)]
    spans += [span(30, "engine_score_matrix", 2, rows=1),
              span(31, "engine.dispatch", 30, kind="score_matrix", rows=160),
              span(32, "engine_embed", 2, rows=6),
              span(33, "engine.dispatch", 32, kind="embed", rows=6)]
    return spans


@pytest.mark.parametrize("generate_rows, mean", [
    ([8, 8, 8, 8], 8.0),   # the engine's four cohorts of eight
    ([32], 32.0),          # one cohort a statement
    ([32, 16, 16], 64 / 3),
])
def test_rows_by_hand(generate_rows, mean):
    found = reader().rows(tree(generate_rows), METRIC["kind"])
    assert found == generate_rows
    assert sum(found) / len(found) == pytest.approx(mean)


def test_rows_leave_out_what_is_not_a_generate_dispatch():
    module = reader()
    assert module.rows(tree([]), "generate") == []
    assert module.rows(tree([8]), "score_matrix") == [160]
    # a stream's dispatch is another kind; a span without attributes (a
    # program before the span carried them) is nobody's
    spans = [span(1, "engine.dispatch", None, kind="generate_stream", rows=8),
             {"id": 2, "name": "engine.dispatch", "parent": None,
              "start_s": 0.0, "duration_s": 0.1}]
    assert module.rows(spans, "generate") == []


def stored(request_id, generate_rows):
    from consensus_tpu.obs.trace import TraceContext, get_trace_store, span

    trace = TraceContext(request_id)
    root = trace.begin("http_request")
    call = trace.begin("engine_generate", parent=root, rows=32)
    for n in generate_rows:
        with span("engine.dispatch", traces=[(trace, call)],
                  kind="generate", rows=n):
            pass
    trace.end(call)
    other = trace.begin("engine_embed", parent=root)
    with span("engine.dispatch", traces=[(trace, other)], kind="embed", rows=5):
        pass
    trace.end(other)
    trace.end(root)
    get_trace_store().put(trace)
    return types.SimpleNamespace(payload={"request_id": request_id})


def test_reader_reads_the_store():
    module = reader()
    parent = [stored("rows-parent-1", [8, 8, 8, 8]),
              stored("rows-parent-2", [8, 8, 8, 8])]
    assert module.read({"sent": parent}, METRIC) == {
        "value": 8.0, "dispatches": 8}
    change = [stored("rows-change-1", [32]), stored("rows-change-2", [32]),
              types.SimpleNamespace(payload={"request_id": "never-sent"})]
    assert module.read({"sent": change}, METRIC) == {
        "value": 32.0, "dispatches": 2}
    # no generate dispatch in any tree, or no tree: nothing, not a zero
    assert module.read({"sent": [stored("rows-none", [])]}, METRIC) is None
    assert module.read({"sent": change[2:]}, METRIC) is None
    assert module.read({"sent": []}, METRIC) is None


def test_metric_file_is_the_benchmarks_entry():
    listed = {m["name"]: m for m in json.loads(
        (BENCH.parent / "BENCHMARK.json").read_text())["per_layer"]}
    entry = listed[METRIC["name"]]
    for key in ("unit", "better", "source", "layer", "moves"):
        assert entry[key] == METRIC[key], key
    assert entry["workloads"] == ["smollm2-1.7b.bon_sweep"]
