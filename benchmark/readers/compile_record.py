"""Seconds in JAX's compile path and the executables made, from the program's
own compile record: the ``compiles`` block of ``/healthz`` as set-up left it
(``health_before``) and as the window left it (``health_after``).  The
metric file's ``"part"`` names what is read: ``setup_seconds``,
``setup_programs`` or ``window_seconds``.

Nothing where ``health_before`` holds no such block: a program without the
record.  ``deltas`` is not read, because a family that did not grow in the
window is absent from it on a program with the record and on one without.
"""

STAGES = ("trace_s", "lower_s", "compile_s")


def _block(context, key):
    block = (context.get(key) or {}).get("compiles")
    return block if isinstance(block, dict) else None


def _seconds(block):
    return sum(float(block.get(stage, 0.0)) for stage in STAGES)


def _largest(block, by, n=10):
    """The ``n`` largest programs of ``by_program`` as [name, seconds,
    meetings], by ``seconds`` or by ``meetings``."""
    rows = [[name, _seconds(row), int(row.get("meetings", 0))]
            for name, row in (block.get("by_program") or {}).items()]
    column = 1 if by == "seconds" else 2
    return sorted(rows, key=lambda row: (-row[column], row[0]))[:n]


def _setup_seconds(before, after):
    out = {"value": _seconds(before)}
    for key in STAGES + ("cache_read_s", "cache_reads"):
        out[key] = before.get(key, 0)
    out["by_seconds"] = _largest(before, "seconds")
    return out


def _setup_programs(before, after):
    programs = int(before.get("programs", 0))
    cache_reads = int(before.get("cache_reads", 0))
    return {
        "value": programs,
        "compiled": programs - cache_reads,
        "cache_reads": cache_reads,
        "functions": sum(1 for row in (before.get("by_program") or {}).values()
                         if row.get("meetings", 0)),
        "by_meetings": _largest(before, "meetings"),
    }


def _window_seconds(before, after):
    if after is None:
        return None
    return {"value": _seconds(after) - _seconds(before),
            "programs": int(after.get("programs", 0))
            - int(before.get("programs", 0))}


PARTS = {
    "setup_seconds": _setup_seconds,
    "setup_programs": _setup_programs,
    "window_seconds": _window_seconds,
}


def read(context, metric):
    before = _block(context, "health_before")
    if before is None:
        return None
    return PARTS[metric["part"]](before, _block(context, "health_after"))
