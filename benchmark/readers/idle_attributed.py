"""Of the device's idle seconds in the traced stretch, the share that falls
inside a span of the program other than a bare ``engine.idle``: the host
plane's annotations and the device's gaps are on one clock."""

from benchmark.lib import xplane_spans


def read(context, metric):
    traced = xplane_spans.load_traced(context)
    if traced is None:
        return None
    data, stretch = traced
    if not any(xplane_spans.is_span_name(span[0]) for span in data["host"]):
        return None  # a program that writes no span
    gaps = xplane_spans.device_gaps(data["planes"], stretch[:2])
    if gaps is None:
        return None
    found = xplane_spans.attribute_gaps(gaps, data["host"])
    if not found["idle_s"]:
        return None
    return {"value": 100.0 * found.pop("attributed_s") / found["idle_s"],
            **found}
