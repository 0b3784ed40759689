"""The whole window's useful FLOPs over its seconds times the chip's peak."""


def read(context, metric):
    peak = context["peak"]
    if peak is None or not context["span_s"]:
        return None
    kinds = context["tally"](context["calls"], context["first_send"],
                             context["last_done"])
    flops = sum(entry["flops"] for entry in kinds.values())
    if not flops:
        return None
    chips = int(context["cell"].workload["chips"])
    return 100.0 * flops / (context["span_s"] * peak["bf16_flops_per_s"] * chips)
