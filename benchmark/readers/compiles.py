"""Programs compiled, or read from the persistent cache, inside the window."""


def read(context, metric):
    return context["compiled"]["programs"]
