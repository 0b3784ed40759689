"""Cached latent positions that the expanded form of latent attention turned
into every head's keys and values, for each query position it served:
increases over the window of ``backend_mla_keys_expanded_total`` over
``backend_mla_queries_total{form="expanded"}``, with both forms' query counts
beside it.  Nothing where the program has no such counters (it has no latent
attention) or the expanded form served no query."""

from benchmark.lib.meter import family_total


def read(context, metric):
    deltas = context["deltas"]
    keys = family_total(deltas, "backend_mla_keys_expanded_total")
    expanded = family_total(deltas, "backend_mla_queries_total", form="expanded")
    absorbed = family_total(deltas, "backend_mla_queries_total", form="absorbed")
    if not expanded:
        return None
    return {"value": keys / expanded, "keys_expanded": keys,
            "expanded_queries": expanded, "absorbed_queries": absorbed}
