"""The reading of spans and scopes from a trace: on the trace recorded on a
TPU v5e by ``benchmark/tools/record_scoped_trace.py`` (``scoped_trace.json``
and the file it came from, ``scoped_trace.xplane.pb``) and on planes made by
hand."""

import json
import pathlib
import sys

import pytest

TESTS = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(TESTS.parents[1]))

from benchmark.lib import xplane_spans  # noqa: E402

RECORDED = json.loads((TESTS / "scoped_trace.json").read_text())
XPLANE = str(TESTS / "scoped_trace.xplane.pb")
SCOPES = ("attention", "ffn", "vocab_projection", "prefill", "decode_step")
PHASES = ("prefill", "decode_step")


def recorded_planes():
    return [(name, [(line, [tuple(e) for e in events]) for line, events in lines])
            for name, lines in RECORDED["planes"]]


def test_self_time_with_a_while_round_two_children():
    events = [("%while", 0.0, 100.0, ""), ("a", 0.0, 40.0, "p/attention/dot"),
              ("b", 40.0, 50.0, "p/ffn/dot"), ("after", 120.0, 10.0, "")]
    own = {e[0]: s for e, s in xplane_spans.self_times(events)}
    assert own == {"%while": 10.0, "a": 40.0, "b": 50.0, "after": 10.0}
    # every instant once: the self times sum to the union of the intervals
    assert sum(own.values()) == 110.0
    cut = {e[0]: s for e, s in xplane_spans.self_times(events, clip=(20.0, 95.0))}
    assert cut == {"%while": 5.0, "a": 20.0, "b": 50.0}
    # a loop inside a loop
    nested = [("%outer", 0.0, 100.0, ""), ("%inner", 10.0, 60.0, ""),
              ("x", 20.0, 30.0, "")]
    own = {e[0]: s for e, s in xplane_spans.self_times(nested)}
    assert own == {"%outer": 40.0, "%inner": 30.0, "x": 30.0}


def test_scope_of_a_path():
    path = "jit(f)/jit(_decode_segment)/decode_step/while/body/attention/dot_general:"
    assert xplane_spans.scope_of(path, SCOPES, PHASES) == ("decode_step", "attention")
    assert xplane_spans.scope_of("jit(f)/decode_step/while/cond/lt", SCOPES,
                                 PHASES) == ("decode_step", "")
    assert xplane_spans.scope_of("", SCOPES, PHASES) == ("", "")
    # the innermost scope wins
    assert xplane_spans.scope_of("jit(f)/ffn/attention/mul", SCOPES,
                                 PHASES) == ("", "attention")


def test_recorded_trace_scopes_and_self_time():
    """Three launches of the probe: a scan of four (attention, ffn) layers
    under ``decode_step``, then a ``vocab_projection``."""
    planes = recorded_planes()
    seconds = xplane_spans.scoped_seconds(planes, None, SCOPES, PHASES)
    assert {key[0] for key in seconds} == {"jit_scoped_probe"}
    by = {}
    for key, s in seconds.items():
        by[key[1:3]] = by.get(key[1:3], 0.0) + s
    # what has no scope keeps its operation's name, what has one does not
    assert {key[3] for key in seconds if key[2]} == {""}
    assert {key[3] for key in seconds if not key[2]} >= {"%while", "%copy-done"}
    assert by[("decode_step", "attention")] == pytest.approx(12 * 11.56e-6, rel=0.02)
    assert by[("decode_step", "ffn")] == pytest.approx(12 * 11.6e-6, rel=0.02)
    assert by[("", "vocab_projection")] == pytest.approx(3 * 12.6e-6, rel=0.02)
    # the loop's own time is what its body does not cover: next to nothing,
    # where its span is eight operations long
    ops = [e for _, lines in planes for name, events in lines
           if name == "XLA Ops" for e in events]
    loops = [e for e in ops if e[0].startswith("%while")]
    assert len(loops) == 3 and all(e[2] > 90_000 for e in loops)
    assert by[("", "")] < 0.3 * sum(by.values())
    # every instant once: the sum is the union of the operations' intervals
    from benchmark.lib.trace_reduce import union_seconds

    assert sum(by.values()) == pytest.approx(
        union_seconds((e[1], e[1] + e[2]) for e in ops), rel=1e-6)
    scoped = sum(s for (_, scope), s in by.items() if scope)
    assert 100.0 * scoped / sum(by.values()) > 80.0


def test_all_gaps_not_ten():
    planes = [("/device:TPU:0", [("XLA Ops", [
        (f"op{i}", 100.0 * i, 60.0, "") for i in range(40)])])]
    gaps = xplane_spans.device_gaps(planes, (0.0, 4000.0))
    assert len(gaps) == 40 and gaps[0] == (60.0, 100.0)
    assert gaps[-1] == (3960.0, 4000.0)
    assert xplane_spans.device_gaps(
        [("/device:TPU:0", [("XLA Modules", [])])], (0.0, 1.0)) is None
    # the recorded trace: the device idles between its three launches
    planes = recorded_planes()
    ops = [e for _, lines in planes for name, events in lines
           if name == "XLA Ops" for e in events]
    clip = (ops[0][1], ops[-1][1] + ops[-1][2])
    long_gaps = [g for g in xplane_spans.device_gaps(planes, clip)
                 if g[1] - g[0] > 1e6]
    assert len(long_gaps) == 2


def test_precedence_on_two_threads():
    host = [
        ("serve.method", "request-1", 0.0, 1000.0, {}),
        ("method.score", "request-1", 100.0, 900.0, {}),
        ("engine.idle", "engine", 0.0, 300.0, {}),
        ("engine.dispatch", "engine", 300.0, 800.0, {}),
        ("backend.layout", "engine", 400.0, 500.0, {}),
        ("bench_anchor", "main", 0.0, 1.0, {}),
    ]
    gaps = [(50.0, 250.0), (350.0, 450.0), (850.0, 950.0), (1100.0, 1200.0),
            (10.0, 11.0)]
    found = xplane_spans.attribute_gaps(gaps, host, shortest_ns=5.0)
    by_span = dict(found["by_span"])
    assert by_span == {
        # the request thread's spans outrank the engine's idling
        "method.score": pytest.approx(200e-9),
        "serve.method": pytest.approx(100e-9),
        # the engine thread's work outranks the request thread's waiting, and
        # the backend's the engine's
        "engine.dispatch": pytest.approx(50e-9),
        "backend.layout": pytest.approx(50e-9),
        xplane_spans.UNNAMED: pytest.approx(100e-9),
    }
    assert found["gaps"] == 4 and found["idle_s"] == pytest.approx(500e-9)
    assert found["launch_gap_s"] == pytest.approx(1e-9)
    assert found["attributed_s"] == pytest.approx(400e-9)
    # a bare engine.idle blames nobody
    bare = xplane_spans.attribute_gaps(
        [(0.0, 100.0)], [("engine.idle", "engine", 0.0, 100.0, {})], 5.0)
    assert bare["attributed_s"] == 0.0
    assert dict(bare["by_span"]) == {"engine.idle": pytest.approx(100e-9)}


def test_recorded_host_spans_and_gaps():
    host = [tuple(span) for span in RECORDED["host_spans"]]
    assert [s[0] for s in host[:4]] == [
        "engine.iteration", "backend.launch", "backend.d2h", "engine.idle"]
    assert host[1][4] == {"program": "scoped_probe"}
    planes = recorded_planes()
    ops = [e for _, lines in planes for name, events in lines
           if name == "XLA Ops" for e in events]
    gaps = xplane_spans.device_gaps(planes, (ops[0][1], ops[-1][1] + ops[-1][2]))
    found = xplane_spans.attribute_gaps(gaps, host)
    # between its launches the probe slept inside engine.idle
    assert found["by_span"][0][0] == "engine.idle"
    assert found["gaps"] == 2 and found["launch_gap_s"] < 1e-4


def test_wire_reader_against_profile_data():
    """The file's own reader finds what ``ProfileData`` shows, and on the
    events' metadata the ``tf_op`` path that it does not."""
    from jax.profiler import ProfileData

    names = set()
    for plane in ProfileData.from_file(XPLANE).planes:
        if plane.name.startswith("/device:TPU"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for event in line.events:
                        names.add(event.name)
                        assert "tf_op" not in dict(event.stats)
    table = xplane_spans.read_event_metadata(XPLANE, "/device:TPU")["/device:TPU:0"]
    fusions = {name for name in names if "fusion" in name}
    assert fusions and fusions <= set(table)
    for name in fusions:
        assert table[name]["tf_op"].startswith("jit(scoped_probe)/")
        assert table[name]["flops"] > 0
    planes = xplane_spans.read_scoped_planes(XPLANE, "/device:TPU")
    assert json.loads(json.dumps(planes)) == RECORDED["planes"]
    assert json.loads(json.dumps(xplane_spans.read_host_spans(XPLANE))) == \
        RECORDED["host_spans"]


def test_span_names():
    assert xplane_spans.is_span_name("backend.launch")
    assert xplane_spans.is_span_name("engine.idle")
    for name in ("bench_anchor", "PjRtCpuExecutable::Execute", "Backend.launch",
                 "backend.", ".launch", "$core.py:123 f"):
        assert not xplane_spans.is_span_name(name)


def test_traced_stretch_needs_the_anchor():
    host = [("backend.launch", "t", 5.0, 6.0, {}),
            ("bench_anchor", "main", 100.0, 101.0, {})]
    assert xplane_spans.traced_stretch(host, [7.0, 9.5]) == (100.0, 100.0 + 2.5e9, 7.0)
    assert xplane_spans.traced_stretch(host[:1], [7.0, 9.5]) is None
