"""What the program's own names say about a profiler trace.

Three things are read from an ``.xplane.pb`` here that ``trace_reduce`` does
not read: the host plane's ``<layer>.<what>`` annotations of every thread
(``consensus_tpu.obs.trace.span`` writes them), every idle gap of the first
device, and each device operation's self time with the ``jax.named_scope``
it was traced under.

An operation's scope is not among the stats ``jax.profiler.ProfileData``
iterates: on this libtpu it sits on the event's *metadata* (the ``tf_op``
stat of an ``XEventMetadata``).  So the file's ``XSpace`` message is read a
second time with a small wire-format reader, for the metadata alone (the
lines' events are skipped by length, which keeps a 70 MB trace to a second
or two), and joined to ``ProfileData``'s events by their name.
"""

from __future__ import annotations

import struct
from typing import Any, Dict, Iterable, Iterator, List, Optional, Sequence, Tuple

#: (name, thread, start ns, end ns, attrs)
HostSpan = Tuple[str, str, float, float, Dict[str, str]]
#: (event name, start ns, duration ns)
Op = Tuple[str, float, float]


# -- the wire format ---------------------------------------------------------


def _varint(buf: bytes, at: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, at
        shift += 7


def fields(buf: bytes) -> Iterator[Tuple[int, int, Any]]:
    """(field number, wire type, value) of one protobuf message: an int for
    a varint, bytes for a length-delimited or fixed field."""
    at, end = 0, len(buf)
    while at < end:
        key, at = _varint(buf, at)
        number, kind = key >> 3, key & 7
        if kind == 0:
            value, at = _varint(buf, at)
        elif kind == 2:
            size, at = _varint(buf, at)
            value, at = buf[at:at + size], at + size
        elif kind == 1:
            value, at = buf[at:at + 8], at + 8
        elif kind == 5:
            value, at = buf[at:at + 4], at + 4
        else:
            raise ValueError(f"wire type {kind} at byte {at}")
        yield number, kind, value


def _stat(buf: bytes) -> Tuple[int, Any]:
    """An ``XStat``: (metadata id, value); a ``ref_value`` comes back as
    ("ref", id) for the caller to look up."""
    key, value = 0, None
    for number, _, raw in fields(buf):
        if number == 1:
            key = raw
        elif number == 2:
            value = struct.unpack("<d", raw)[0]
        elif number in (3, 4):
            value = raw
        elif number == 5:
            value = raw.decode("utf-8", "replace")
        elif number == 6:
            value = raw
        elif number == 7:
            value = ("ref", raw)
    return key, value


def _map_entry(buf: bytes) -> Tuple[int, bytes]:
    key, value = 0, b""
    for number, _, raw in fields(buf):
        if number == 1:
            key = raw
        elif number == 2:
            value = raw
    return key, value


def read_event_metadata(path: str, plane_prefix: str = "/device:"
                        ) -> Dict[str, Dict[str, Dict[str, Any]]]:
    """``{plane name: {event name: {stat name: value}}}`` for the planes
    whose name starts with ``plane_prefix``: the stats that sit on an
    event's metadata, by the name ``ProfileData`` shows the event under."""
    with open(path, "rb") as handle:
        space = handle.read()
    out: Dict[str, Dict[str, Dict[str, Any]]] = {}
    for number, _, plane in fields(space):
        if number != 1:
            continue
        name, events, stat_names = "", [], {}
        for field, _, raw in fields(plane):
            if field == 2:
                name = raw.decode("utf-8", "replace")
            elif field == 4:
                events.append(_map_entry(raw)[1])
            elif field == 5:
                key, value = _map_entry(raw)
                for sub, _, text in fields(value):
                    if sub == 2:
                        stat_names[key] = text.decode("utf-8", "replace")
        if not name.startswith(plane_prefix):
            continue
        table = out.setdefault(name, {})
        for event in events:
            event_name, display, stats = "", "", {}
            for field, _, raw in fields(event):
                if field == 2:
                    event_name = raw.decode("utf-8", "replace")
                elif field == 4:
                    display = raw.decode("utf-8", "replace")
                elif field == 5:
                    key, value = _stat(raw)
                    if isinstance(value, tuple):
                        value = stat_names.get(value[1], "")
                    stats[stat_names.get(key, str(key))] = value
            if stats:
                table[event_name] = stats
                if display:
                    table.setdefault(display, stats)
    return out


# -- reading a trace -----------------------------------------------------------

#: The stat of an operation's metadata that holds its ``op_name`` path
#: (``jit(f)/decode_step/while/body/attention/dot_general:``), as the probe
#: of ``benchmark/tools/record_scoped_trace.py`` found it on this libtpu.
PATH_STAT = "tf_op"


def read_scoped_planes(path: str, device_prefix: str = "/device:TPU"
                       ) -> List[Tuple[str, List[Tuple[str, List[Tuple]]]]]:
    """The device planes' module and operation lines as plain tuples, an
    event being (name, start ns, duration ns, scope path): the path is ""
    where the trace holds none for that event."""
    from jax.profiler import ProfileData

    from benchmark.lib.trace_reduce import MODULE_LINES, OP_LINES

    metadata = read_event_metadata(path, device_prefix)
    planes = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith(device_prefix):
            continue
        table = metadata.get(plane.name, {})
        paths: Dict[str, str] = {}
        lines = []
        for line in plane.lines:
            if line.name not in MODULE_LINES + OP_LINES:
                continue
            events = []
            for event in line.events:
                name = event.name
                if name not in paths:
                    paths[name] = str(table.get(name, {}).get(PATH_STAT, ""))
                events.append((name, float(event.start_ns),
                               float(event.duration_ns), paths[name]))
            lines.append((line.name, events))
        planes.append((plane.name, lines))
    return planes


def is_span_name(name: str) -> bool:
    """``<layer>.<what>``, lower case: the form of every name in
    ``consensus_tpu.obs.trace.HOST_SPANS``."""
    layer, dot, what = name.partition(".")
    return bool(dot) and layer.isalpha() and layer.islower() and \
        what.replace("_", "").isalnum() and what.islower()


def read_host_spans(path: str, extra: Sequence[str] = ("bench_anchor",)
                    ) -> List[HostSpan]:
    """The host planes' ``<layer>.<what>`` annotations (and those named in
    ``extra``) of every thread, in order of their start."""
    from jax.profiler import ProfileData

    out: List[HostSpan] = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for event in line.events:
                name = event.name
                if name in extra or is_span_name(name):
                    start = float(event.start_ns)
                    out.append((name, line.name, start,
                                start + float(event.duration_ns),
                                {k: str(v) for k, v in event.stats}))
    return sorted(out, key=lambda span: span[2])


# -- the stretch both clocks saw ---------------------------------------------------


def traced_stretch(host: Sequence[HostSpan], traced: Sequence[float],
                   anchor: str = "bench_anchor"
                   ) -> Optional[Tuple[float, float, float]]:
    """(start ns, end ns, host clock at the start) of the stretch both
    clocks saw: from the anchor annotation for as long as ``traced`` (start
    and stop on the host clock) says.  None without the anchor."""
    for name, _, start, _, _ in host:
        if name == anchor:
            return start, start + (traced[1] - traced[0]) * 1e9, traced[0]
    return None


# -- the device's operations -----------------------------------------------------


def self_times(events: Iterable[Tuple], clip: Optional[Tuple[float, float]] = None
               ) -> List[Tuple[Tuple, float]]:
    """(event, self ns) for the events of one line: an event's duration less
    the events of the same line that lie inside it (``%while`` covers its
    body's operations), so that every instant belongs to the innermost
    operation that covers it.  Durations are cut to ``clip`` first."""
    cut = []
    for event in events:
        start, stop = event[1], event[1] + event[2]
        if clip is not None:
            start, stop = max(start, clip[0]), min(stop, clip[1])
        if stop > start:
            cut.append((start, stop, event))
    cut.sort(key=lambda item: (item[0], -item[1]))
    out: List[List[Any]] = []
    stack: List[Tuple[float, int]] = []  # (end, index into out)
    for start, stop, event in cut:
        while stack and stack[-1][0] <= start:
            stack.pop()
        if stack:  # a child: its time is not its parent's own
            out[stack[-1][1]][1] -= min(stop, stack[-1][0]) - start
        out.append([event, stop - start])
        stack.append((stop, len(out) - 1))
    return [(event, max(own, 0.0)) for event, own in out]


def scope_of(path: str, scopes: Sequence[str], phases: Sequence[str]
             ) -> Tuple[str, str]:
    """(phase, scope) of an operation's path: the innermost name of
    ``scopes`` that is no phase, and the name of ``phases`` beside it; ""
    where the path has none."""
    phase = scope = ""
    for part in path.split("/"):
        if part in phases:
            phase = part
        elif part in scopes:
            scope = part
    return phase, scope


def scoped_seconds(planes: Sequence[Tuple], clip: Optional[Tuple[float, float]],
                   scopes: Sequence[str], phases: Sequence[str]
                   ) -> Dict[Tuple[str, str, str, str], float]:
    """Self seconds of the first device's operations by (program, phase,
    scope, operation); the operation is named only where it has no scope
    (``%copy.5``: what a reader shows of the time no scope covers).  The
    program is the module whose span holds the operation's start, its name
    with the run id stripped."""
    import bisect

    from benchmark.lib.trace_reduce import MODULE_LINES, OP_LINES, program_name

    out: Dict[Tuple[str, str, str, str], float] = {}
    for _, lines in planes:
        modules = sorted((e[1], e[1] + e[2], program_name(e[0]))
                         for name, events in lines if name in MODULE_LINES
                         for e in events)
        starts = [m[0] for m in modules]
        found: Dict[Tuple[str, str], Tuple[str, str, str]] = {}
        for name, events in lines:
            if name not in OP_LINES:
                continue
            for event, own in self_times(events, clip):
                at = bisect.bisect_right(starts, event[1]) - 1
                program = modules[at][2] if at >= 0 and \
                    event[1] < modules[at][1] else ""
                if (event[0], event[3]) not in found:
                    phase, scope = scope_of(event[3], scopes, phases)
                    found[event[0], event[3]] = (
                        phase, scope, "" if scope else event[0].split(" = ")[0])
                key = (program,) + found[event[0], event[3]]
                out[key] = out.get(key, 0.0) + own / 1e9
        if out:
            break  # the first device that holds operations
    return out


def device_gaps(planes: Sequence[Tuple], clip: Tuple[float, float]
                ) -> Optional[List[Tuple[float, float]]]:
    """Every (start ns, end ns) stretch of ``clip`` in which the first
    device ran no operation, in order; None where no device holds one."""
    from benchmark.lib.trace_reduce import OP_LINES, gaps

    for _, lines in planes:
        intervals = [(e[1], e[1] + e[2]) for name, events in lines
                     if name in OP_LINES for e in events
                     if e[1] + e[2] > clip[0] and e[1] < clip[1]]
        if intervals:
            return sorted(gaps(intervals, clip[0], clip[1], len(intervals) + 1))
    return None


# -- whose fault an idle instant is ------------------------------------------------

#: Whose work an instant of host time is, most to blame first.  A bare
#: ``engine.idle`` (the engine waits for a call and no request thread is
#: inside a named span) blames nobody.
_LAYER_RANK = {"backend": 4, "engine": 3, "method": 2, "serve": 1}
IDLE, UNNAMED = "engine.idle", "(no span)"


def _rank(name: str) -> int:
    return 0 if name == IDLE else _LAYER_RANK.get(name.partition(".")[0], 0)


def attribute_gaps(gaps_ns: Sequence[Tuple[float, float]],
                   host: Sequence[HostSpan], shortest_ns: float = 2e5
                   ) -> Dict[str, Any]:
    """Each idle instant to the host span that was open then: over all
    threads the span of the layer nearest the device (backend, engine,
    method, serve), the innermost of that layer.  Gaps shorter than
    ``shortest_ns`` are the turn-around between two launches that were both
    enqueued: they are summed apart and nobody's."""
    long_gaps = [g for g in gaps_ns if g[1] - g[0] >= shortest_ns]
    spans = [s for s in host if _rank(s[0]) or s[0] == IDLE]
    # One sweep over the ends of gaps and spans; at one instant the ends
    # come before the starts.  A mark is (ns, opens, span index or -1 for a
    # gap).
    marks: List[Tuple[float, bool, int]] = []
    for start, stop in long_gaps:
        marks += [(start, True, -1), (stop, False, -1)]
    for index, span in enumerate(spans):
        marks += [(span[2], True, index), (span[3], False, index)]
    marks.sort(key=lambda mark: mark[:2])
    by_span: Dict[str, float] = {}
    open_spans: Dict[int, Tuple[int, float, str]] = {}  # (rank, start, name)
    in_gap, last = False, 0.0
    for at, opens, index in marks:
        if in_gap and at > last:
            name = max(open_spans.values())[2] if open_spans else UNNAMED
            by_span[name] = by_span.get(name, 0.0) + (at - last) / 1e9
        last = at
        if index < 0:
            in_gap = opens
        elif opens:
            name = spans[index][0]
            open_spans[index] = (_rank(name), spans[index][2], name)
        else:
            open_spans.pop(index, None)
    idle_s = sum(stop - start for start, stop in long_gaps) / 1e9
    blamed = sum(s for name, s in by_span.items() if name not in (IDLE, UNNAMED))
    return {
        "idle_s": idle_s,
        "launch_gap_s": sum(g[1] - g[0] for g in gaps_ns) / 1e9 - idle_s,
        "gaps": len(long_gaps),
        "attributed_s": blamed,
        "by_span": sorted(([k, v] for k, v in by_span.items()),
                          key=lambda kv: -kv[1])[:10],
    }


# -- from a reader's context -------------------------------------------------------


def load_traced(context: Dict[str, Any]
                ) -> Optional[Tuple[Dict[str, Any], Tuple[float, float, float]]]:
    """(``{"planes": read_scoped_planes, "host": read_host_spans}`` of the
    run's trace, ``traced_stretch``) for a reader of ``run.py``, which hands
    its readers the traced stretch's host clock and keeps the file until
    they have run; None where either is missing.  The file is read once for
    all the readers of a run: what was read stays in their ``context``."""
    import pathlib

    from benchmark.lib import trace_reduce

    if context.get("traced") is None:
        return None
    if "xplane_spans" not in context:
        root = pathlib.Path(trace_reduce.__file__).resolve().parents[2]
        path = trace_reduce.find_xplane(
            str(root / ".bench_out" / "trace" / context["cell"].name))
        prefix = "/device:TPU" if context["peak"] is not None else "/host:CPU"
        context["xplane_spans"] = None if path is None else {
            "planes": read_scoped_planes(path, prefix),
            "host": read_host_spans(path)}
    data = context["xplane_spans"]
    if data is None:
        return None
    stretch = traced_stretch(data["host"], context["traced"])
    return None if stretch is None else (data, stretch)


def scoped_seconds_of(context: Dict[str, Any], programs: Sequence[str]
                      ) -> Optional[Dict[Tuple[str, str, str, str], float]]:
    """``scoped_seconds`` of the run's trace for the programs whose name
    holds one of ``programs``, under the program's own tuples of names; None
    where the program has none or the trace holds no operation."""
    try:
        from consensus_tpu.models import MODEL_PHASES, MODEL_SCOPES
    except ImportError:
        return None
    traced = load_traced(context)
    if traced is None:
        return None
    data, stretch = traced
    if "scoped_seconds" not in data:
        data["scoped_seconds"] = scoped_seconds(
            data["planes"], stretch[:2], MODEL_SCOPES, MODEL_PHASES)
    return {key: s for key, s in data["scoped_seconds"].items()
            if any(part in key[0] for part in programs)} or None
