"""Useful over allocated token positions of the padded device batches."""

from benchmark.lib.meter import family_total


def read(context, metric):
    allocated = family_total(context["deltas"], "backend_padding_allocated_tokens_total")
    useful = family_total(context["deltas"], "backend_padding_useful_tokens_total")
    if not allocated:
        return None
    return 100.0 * useful / allocated
