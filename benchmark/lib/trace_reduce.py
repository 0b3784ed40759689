"""From a profiler trace (``.xplane.pb``) to the numbers the benchmark prints.

Read with ``jax.profiler.ProfileData`` and nothing else.  A device plane
(``/device:TPU:n``) carries a line of whole programs ("XLA Modules") and a
line of their operations ("XLA Ops"); busy time is the union of the
operations' intervals, program time the sum of each module's durations by
its name with the run id stripped.  ``reduce_planes`` takes plain tuples so
that a small recorded trace can be kept as JSON beside the tests.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

#: (line name, [(event name, start ns, duration ns), ...])
Line = Tuple[str, List[Tuple[str, float, float]]]
#: (plane name, lines)
Plane = Tuple[str, List[Line]]

MODULE_LINES = ("XLA Modules",)
OP_LINES = ("XLA Ops",)
_RUN_ID = re.compile(r"\(\d+\)$")


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def read_planes(path: str, device_prefix: str = "/device:TPU") -> List[Plane]:
    """The device planes of an ``.xplane.pb`` as plain tuples; only the
    module and operation lines are kept."""
    from jax.profiler import ProfileData

    planes: List[Plane] = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith(device_prefix):
            continue
        lines: List[Line] = []
        for line in plane.lines:
            if line.name in MODULE_LINES + OP_LINES:
                lines.append((line.name, [
                    (event.name, float(event.start_ns), float(event.duration_ns))
                    for event in line.events]))
        planes.append((plane.name, lines))
    return planes


def describe(path: str) -> List[str]:
    """Every plane of the file with its lines and their event counts: what
    to look at when no device plane is found."""
    from jax.profiler import ProfileData

    return [f"{plane.name}: " + ", ".join(
        f"{line.name} ({sum(1 for _ in line.events)})" for line in plane.lines)
        for plane in ProfileData.from_file(path).planes]


def union_seconds(intervals: Iterable[Tuple[float, float]]) -> float:
    """Length of the union of (start ns, end ns) intervals, in seconds."""
    total, end = 0.0, None
    for start, stop in sorted(intervals):
        if end is None or start > end:
            total += stop - start
            end = stop
        elif stop > end:
            total += stop - end
            end = stop
    return total / 1e9


def program_name(event_name: str) -> str:
    return _RUN_ID.sub("", event_name)


def gaps(intervals: Sequence[Tuple[float, float]], first: float, last: float,
         keep: int) -> List[Tuple[float, float]]:
    """The ``keep`` longest (start ns, end ns) stretches of [first, last]
    that no interval covers."""
    out, end = [], first
    for start, stop in sorted(intervals):
        if start > end:
            out.append((end, min(start, last)))
        end = max(end, stop)
    if last > end:
        out.append((end, last))
    return sorted(out, key=lambda g: g[0] - g[1])[:keep]


def _cut(events, clip):
    """The events' parts that lie inside ``clip`` (start ns, end ns)."""
    if clip is None:
        return list(events)
    out = []
    for name, start, duration in events:
        lo, hi = max(start, clip[0]), min(start + duration, clip[1])
        if hi > lo:
            out.append((name, lo, hi - lo))
    return out


def reduce_planes(planes: Sequence[Plane], window_s: float,
                  clip: Optional[Tuple[float, float]] = None,
                  top: int = 10) -> Optional[Dict[str, Any]]:
    """``busy_s`` (union of operation intervals, averaged over the device
    planes), seconds by program name, the operations that took most time,
    and the longest gaps of the first device; all of it inside ``clip``
    (start ns, end ns) where one is given.  None where no device plane holds
    an event there: there is then nothing to read."""
    busy: List[float] = []
    by_program: Dict[str, float] = {}
    by_op: Dict[str, float] = {}
    launches: Dict[str, int] = {}
    first_gaps: List[Tuple[float, float]] = []
    for index, (_, lines) in enumerate(planes):
        ops = _cut((e for name, events in lines if name in OP_LINES
                    for e in events), clip)
        modules = _cut((e for name, events in lines if name in MODULE_LINES
                        for e in events), clip)
        # A trace with no operation line still has whole programs.
        spans = ops or modules
        if not spans:
            continue
        intervals = [(start, start + duration) for _, start, duration in spans]
        busy.append(union_seconds(intervals))
        for name, _, duration in modules:
            key = program_name(name)
            by_program[key] = by_program.get(key, 0.0) + duration / 1e9
            launches[key] = launches.get(key, 0) + 1
        for name, _, duration in ops:
            by_op[name] = by_op.get(name, 0.0) + duration / 1e9
        if index == 0 or not first_gaps:
            lo, hi = clip or (min(start for start, _ in intervals),
                              max(stop for _, stop in intervals))
            first_gaps = gaps(intervals, lo, hi, top)
    if not busy:
        return None
    n = len(busy)
    return {
        "busy_s": sum(busy) / n,
        "window_s": window_s,
        "devices": n,
        "by_program_s": {k: v / n for k, v in by_program.items()},
        "launches": launches,
        "top_ops": sorted(((k, v / n) for k, v in by_op.items()),
                          key=lambda kv: -kv[1])[:top],
        "gaps_ns": first_gaps,
    }
