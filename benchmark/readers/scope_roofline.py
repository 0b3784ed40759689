"""A group of scopes' share of their roofline in the traced stretch: the
least seconds the chip could take for one term of the calls' useful work
(the metric's ``term``, a name of the ``TERMS`` of the cell's work file;
FLOPs over the peak against bytes over the bandwidth, the larger, call kind
by call kind) over the self seconds of the operations that were traced under
the metric's ``scopes``, in every program the metric names.  Beside the
share stand the bound that set most of the least seconds, and both.  A cell
whose work file has no such term has nothing to read."""

from benchmark.lib import xplane_spans
from benchmark.lib.peaks import least_seconds

#: The share of the programs' device seconds that has to carry a scope for a
#: scope's seconds to be a denominator.
LEAST_SCOPED = 0.9


def read(context, metric):
    peak, traced = context["peak"], context["traced"]
    if metric["term"] not in context["cell"].work.TERMS:
        return None
    seconds = xplane_spans.scoped_seconds_of(context, metric["programs"])
    if peak is None or seconds is None:
        return None
    # A share needs the whole of its denominator: where the programs'
    # operations carry few names (an executable compiled before the scopes
    # came, read from a cache that is keyed by the program alone), there is
    # nothing to read.
    named = sum(s for key, s in seconds.items() if key[2])
    if named < LEAST_SCOPED * sum(seconds.values()):
        return None
    device_s = sum(s for key, s in seconds.items()
                   if key[2] in metric["scopes"])
    kinds = context["tally"](context["calls"], traced[0], traced[1],
                             term=metric["term"])
    least, by_bound = 0.0, {}
    for entry in kinds.values():
        part, bound = least_seconds(entry["flops"], entry["bytes"], peak)
        least += part
        by_bound[bound] = by_bound.get(bound, 0.0) + part
    if not device_s or not least:
        return None
    return {"value": 100.0 * least / device_s,
            "bound": max(by_bound, key=by_bound.get),
            "least_s": least, "device_s": device_s}
