"""The median seconds to a statement over the window's requests that carry
the mix's own parameters (``harness.end_to_end``: a failed one counts as the
slowest), with how many there were and the rate times the median beside it:
in a closed loop that product is the clients, less the ramp and the drain."""

from benchmark.lib import harness


def read(context, metric):
    e2e = harness.end_to_end(context["cell"], context["sent"])
    own = sum(1 for s in context["sent"] if harness.traffic_lib.paper_shaped(
        context["cell"].traffic, s.payload))
    if not own:
        return None
    median = e2e["time_to_statement_p50_s"]
    return {"value": median, "requests": own,
            "rate_x_median": e2e["statements_per_s"] * median}
