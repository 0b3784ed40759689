"""The engine's mean slot occupancy over the window's iterations only."""


def read(context, metric):
    before = (context["health_before"] or {}).get("engine") or {}
    after = (context["health_after"] or {}).get("engine") or {}
    n0, n1 = before.get("iterations", 0), after.get("iterations", 0)
    if n1 <= n0 or "slot_occupancy_mean" not in after:
        return None
    total = after["slot_occupancy_mean"] * n1 - before.get("slot_occupancy_mean", 0.0) * n0
    return 100.0 * total / (n1 - n0)
