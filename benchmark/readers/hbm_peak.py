"""Peak bytes in use on the fullest chip, in GB."""


def read(context, metric):
    peak = context["memory_peak_bytes"]
    return None if peak is None else peak / 1e9
