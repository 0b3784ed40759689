"""A group of programs' share of their roofline in the traced window: the
least seconds the chip could take for the useful work of the calls the
metric names (FLOPs over the peak against bytes over the bandwidth,
whichever is larger), over the device seconds of the programs it names.
Beside the share stand the bound that set the least seconds, and both."""

from benchmark.lib.peaks import least_seconds


def read(context, metric):
    peak, trace, traced = context["peak"], context["trace"], context["traced"]
    if peak is None or trace is None or traced is None:
        return None
    device_s = sum(seconds for name, seconds in trace["by_program_s"].items()
                   if any(part in name for part in metric["programs"]))
    kinds = context["tally"](context["calls"], traced[0], traced[1])
    flops = sum(kinds[k]["flops"] for k in metric["calls"] if k in kinds)
    bytes_ = sum(kinds[k]["bytes"] for k in metric["calls"] if k in kinds)
    if not device_s or not flops:
        return None
    least, bound = least_seconds(flops, bytes_, peak)
    return {"value": 100.0 * least / device_s, "bound": bound,
            "least_s": least, "device_s": device_s}
