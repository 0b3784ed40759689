"""Of the model programs' device seconds (each operation's self time), the
share spent in operations that carry a name of ``MODEL_SCOPES`` other than
a bare phase; beside it the seconds by phase and scope, and the ten
operations that took most of the time no scope covers."""

from benchmark.lib import xplane_spans


def read(context, metric):
    seconds = xplane_spans.scoped_seconds_of(context, metric["programs"])
    if not seconds:
        return None
    total = sum(seconds.values())
    by_scope, unscoped = {}, {}
    for (program, phase, scope, op), s in seconds.items():
        key = f"{phase or '-'}/{scope or '-'}"
        by_scope[key] = by_scope.get(key, 0.0) + s
        if not scope:
            unscoped[program, phase, op] = unscoped.get((program, phase, op), 0.0) + s
    if not total or set(by_scope) == {"-/-"}:
        return None  # a program without scopes
    named = sum(s for key, s in seconds.items() if key[2])
    largest = sorted(unscoped.items(), key=lambda kv: -kv[1])[:10]
    return {"value": 100.0 * named / total, "device_s": total,
            "by_scope_s": dict(sorted(by_scope.items(), key=lambda kv: -kv[1])),
            "unscoped": [[*key, s] for key, s in largest]}
