"""The trace reduction on a small recorded trace kept beside this file
(``probe_trace.json``: three launches of ``bench_probe`` recorded on a TPU v5e
by ``benchmark/tools/record_trace.py``) and on intervals worked by hand."""

import json
import pathlib

import pytest

from benchmark.lib import trace_reduce

RECORDED = pathlib.Path(__file__).with_name("probe_trace.json")


def test_union_and_gaps_by_hand():
    intervals = [(0, 10), (5, 20), (30, 40), (35, 36)]
    assert trace_reduce.union_seconds(intervals) == pytest.approx(30e-9)
    assert trace_reduce.gaps(intervals, 0, 50, 5) == [(20, 30), (40, 50)]
    assert trace_reduce.program_name("jit_search_step(1234)") == "jit_search_step"


def test_reduce_by_hand():
    planes = [("/device:TPU:0", [
        ("XLA Modules", [("jit_a(1)", 0.0, 4e9), ("jit_b(2)", 6e9, 2e9),
                         ("jit_a(3)", 8e9, 1e9)]),
        ("XLA Ops", [("fusion.1", 0.0, 3e9), ("copy.2", 3e9, 1e9),
                     ("fusion.1", 6e9, 3e9)]),
    ])]
    reduced = trace_reduce.reduce_planes(planes, 10.0)
    assert reduced["busy_s"] == pytest.approx(7.0)
    assert reduced["by_program_s"] == {"jit_a": pytest.approx(5.0),
                                       "jit_b": pytest.approx(2.0)}
    assert reduced["launches"] == {"jit_a": 2, "jit_b": 1}
    assert reduced["top_ops"][0] == ("fusion.1", pytest.approx(6.0))
    assert reduced["gaps_ns"] == [(4e9, 6e9)]
    # Cut to a stretch of the trace: events are cut at its ends, and the
    # gaps are those of the stretch.
    cut = trace_reduce.reduce_planes(planes, 6.0, clip=(2e9, 8e9))
    assert cut["busy_s"] == pytest.approx(4.0)
    assert cut["by_program_s"] == {"jit_a": pytest.approx(2.0),
                                   "jit_b": pytest.approx(2.0)}
    assert cut["gaps_ns"] == [(4e9, 6e9)]
    # Nothing to read is nothing, not a zero.
    assert trace_reduce.reduce_planes([("/device:TPU:0", [])], 10.0) is None


def test_recorded_trace():
    recorded = json.loads(RECORDED.read_text())
    planes = [(name, [(line, [tuple(e) for e in events]) for line, events in lines])
              for name, lines in recorded["planes"]]
    reduced = trace_reduce.reduce_planes(planes, recorded["window_s"])
    probe = [name for name in reduced["by_program_s"] if "bench_probe" in name]
    assert len(probe) == 1 and reduced["launches"][probe[0]] == 3
    # Three launches with the host asleep between them: busy well under the
    # window, no more than the programs' own time, and gaps between launches.
    assert 0 < reduced["busy_s"] <= reduced["by_program_s"][probe[0]] * 1.001
    assert reduced["busy_s"] < 0.5 * recorded["window_s"]
    idle_pct = 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])
    assert 50.0 < idle_pct < 100.0
    assert len(reduced["gaps_ns"]) >= 2
