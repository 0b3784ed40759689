"""Of the model programs' device seconds (each operation's self time), the
share spent under the recurrent mixer's scopes and under ``state_fork`` (the
metric's ``scopes``): what the recurrent branch, and the copies of its state
that stand for shared prefixes, cost.  Beside it the seconds by scope and by
phase.  Nothing where no operation carries one of those scopes: a program
without a mixer, or one that does not name them."""

from benchmark.lib import xplane_spans


def read(context, metric):
    seconds = xplane_spans.scoped_seconds_of(context, metric["programs"])
    if not seconds:
        return None
    total = sum(seconds.values())
    by_scope, by_phase = {}, {}
    for (_, phase, scope, _), s in seconds.items():
        if scope in metric["scopes"]:
            by_scope[scope] = by_scope.get(scope, 0.0) + s
            by_phase[phase or "-"] = by_phase.get(phase or "-", 0.0) + s
    if not total or not by_scope:
        return None
    own = sum(by_scope.values())
    return {"value": 100.0 * own / total, "device_s": total, "ssm_s": own,
            "by_scope_s": dict(sorted(by_scope.items(), key=lambda kv: -kv[1])),
            "by_phase_s": dict(sorted(by_phase.items(), key=lambda kv: -kv[1]))}
