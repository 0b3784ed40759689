"""Where the window's requests waited, from the span trees the program
keeps (``consensus_tpu.obs.trace``): the metric's ``measure`` names one of
the reductions below, and the reading is its mean over the requests whose
tree was found, in milliseconds, with how many were read beside it; a
metric file that sets ``example`` also gets one request's phases
(``TraceContext.critical_path``) beside what its client timed."""

#: Spans a request thread runs the method's own host code in.
_METHOD_SPANS = ("handler", "serve.method", "serve.evaluate")


def _union(intervals):
    total, end = 0.0, None
    for start, stop in sorted(intervals):
        if end is None or start > end:
            total, end = total + stop - start, stop
        elif stop > end:
            total, end = total + stop - end, stop
    return total


def _interval(span):
    return span["start_s"], span["start_s"] + span["duration_s"]


def queue_wait(spans):
    waits = [s["duration_s"] for s in spans if s["name"] == "queue_wait"]
    return sum(waits) if waits else None


def engine_wait(spans):
    """Seconds with a call in the engine and none of the request's calls
    inside a dispatch."""
    runs = [_interval(s) for s in spans if s["name"] == "engine.dispatch"]
    calls = [_interval(s) for s in spans
             if s["name"].startswith("engine_") and s["name"] != "engine_row"]
    if not runs or not calls:
        return None
    return _union(calls) - _union(runs)


def method_host(spans):
    """Self seconds of the handler's, the service's and the method's own
    spans: their duration less their children's."""
    own = [s for s in spans
           if s["name"] in _METHOD_SPANS or s["name"].startswith("method.")]
    if not any(s["name"] == "serve.method" for s in own):
        return None
    children = {}
    for s in spans:
        children[s["parent"]] = children.get(s["parent"], 0.0) + s["duration_s"]
    return sum(max(s["duration_s"] - children.get(s["id"], 0.0), 0.0)
               for s in own)


MEASURES = {"queue_wait": queue_wait, "engine_wait": engine_wait,
            "method_host": method_host}


def read(context, metric):
    try:
        from consensus_tpu.obs.trace import get_trace_store
    except ImportError:
        return None
    measure = MEASURES[metric["measure"]]
    readings = []
    for sent in context["sent"]:
        trace = get_trace_store().get(str(sent.payload.get("request_id")))
        if trace is None:
            continue
        value = measure(trace.to_dict()["spans"])
        if value is not None:
            readings.append((value, sent, trace))
    if not readings:
        return None
    out = {"value": 1000.0 * sum(r[0] for r in readings) / len(readings),
           "requests_read": len(readings), "requests": len(context["sent"])}
    if metric.get("example"):
        # One request whole: the one whose time the client read as the
        # median, with the phases its span tree's root divides into.
        timed = sorted((r for r in readings if r[1].seconds is not None),
                       key=lambda r: r[1].seconds)
        if timed:
            _, sent, trace = timed[len(timed) // 2]
            path = trace.critical_path()
            out["example"] = {"client_s": sent.seconds,
                              "http_request_s": path["total_s"],
                              "phases": path["phases"]}
    return out
