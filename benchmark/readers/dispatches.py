"""Calls into the backend layer per statement answered."""


def read(context, metric):
    if not context["answered"]:
        return None
    lo, hi = context["first_send"], context["last_done"]
    return sum(1 for call in context["calls"]
               if lo <= call["start"] <= hi) / context["answered"]
