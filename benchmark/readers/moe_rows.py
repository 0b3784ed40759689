"""Rows a held expert's product sees: assignments to experts held here over
products of a held expert, increases over the window.  Nothing where the
program has no such counters (it has no routed experts) or none moved."""

from benchmark.lib.meter import family_total


def read(context, metric):
    deltas = context["deltas"]
    held = family_total(deltas, "backend_moe_assignments_total", held="held")
    absent = family_total(deltas, "backend_moe_assignments_total", held="absent")
    calls = family_total(deltas, "backend_moe_expert_calls_total")
    if not calls:
        return None
    return {"value": held / calls, "held": held, "absent": absent,
            "expert_calls": calls}
