"""Percentile ``q`` of what a ``/debug/perf`` totals histogram grew by
between the window's two ends, times ``scale``.  ``hist`` is a dotted
path under ``totals`` to {upper edge: count} with the edges as numbers
(``inf`` for the overflow); the percentile is interpolated linearly
inside its bucket (from 0 in the first; an overflow reads its lower
edge).  None when a snapshot or the key is missing (an older program) or
nothing grew."""
from .stats_delta import dig


def reduce(ctx, hist, q, scale=1.0):
    a = dig(ctx["perf"].get("open"), "totals." + hist)
    b = dig(ctx["perf"].get("close"), "totals." + hist)
    if not isinstance(a, dict) or not isinstance(b, dict):
        return None
    grown = sorted((float(edge), n - a.get(edge, 0)) for edge, n in b.items())
    total = sum(n for _, n in grown)
    if total <= 0:
        return None
    rank, below, seen = total * q / 100.0, 0.0, 0
    for edge, n in grown:
        if n > 0 and seen + n >= rank:
            if edge == float("inf"):
                return scale * below
            return scale * (below + (edge - below) * (rank - seen) / n)
        seen += n
        below = edge
    return None
