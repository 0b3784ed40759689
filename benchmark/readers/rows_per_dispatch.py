"""Rows the engine hands the backend in one call, from the span trees the
program keeps (``consensus_tpu.obs.trace``): the mean of the attribute
``rows`` over the ``engine.dispatch`` spans of the metric's ``kind`` in the
trees of the window's requests, with the number of such spans beside it.
A dispatch merged from several requests' calls stands in each of their
trees, with all its rows."""


def rows(spans, kind):
    """``rows`` of every ``engine.dispatch`` span of this kind."""
    attrs = (s.get("attrs") or {} for s in spans
             if s["name"] == "engine.dispatch")
    return [a["rows"] for a in attrs if a.get("kind") == kind and "rows" in a]


def read(context, metric):
    try:
        from consensus_tpu.obs.trace import get_trace_store
    except ImportError:
        return None
    found = []
    for sent in context["sent"]:
        trace = get_trace_store().get(str(sent.payload.get("request_id")))
        if trace is not None:
            found.extend(rows(trace.to_dict()["spans"], metric["kind"]))
    if not found:
        return None
    return {"value": sum(found) / len(found), "dispatches": len(found)}
