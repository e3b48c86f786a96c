"""A number the program states in ``/stats`` (dotted ``path``) at the
window's close, times ``scale``: a size that does not move with the
traffic (a cache's bytes).  None where the program has no such key."""
from .stats_delta import dig


def reduce(ctx, path, scale=1.0):
    value = dig(ctx["stats"].get("close"), path)
    return None if value is None else scale * float(value)
