"""Client-side mean seconds to a response minus the scheduler's own mean
(submit to outcome), in milliseconds."""

from benchmark.lib.meter import family_total


def read(context, metric):
    deltas = context["deltas"]
    count = family_total(deltas, "serve_request_latency_seconds", "count")
    total = family_total(deltas, "serve_request_latency_seconds", "sum")
    times = [s.seconds for s in context["sent"] if s.seconds is not None]
    if not count or not times:
        return None
    return 1000.0 * (sum(times) / len(times) - total / count)
