"""Growth of one sum of ``/debug/perf`` totals over the growth of
another between the window's two ends, times ``scale``: a mean measured
inside the program (seconds per request, seconds per step, tokens per
step).  ``num`` and ``den`` are lists of dotted paths under ``totals``
(the gateway's counters under ``gateway.``).  None when a snapshot or a
counter is missing (an older program) or the denominator did not grow."""
from .stats_delta import dig


def growth(ctx, paths):
    a, b = ctx["perf"].get("open"), ctx["perf"].get("close")
    total = 0.0
    for path in paths:
        v0, v1 = dig(a, "totals." + path), dig(b, "totals." + path)
        if v0 is None or v1 is None:
            return None
        total += v1 - v0
    return total


def reduce(ctx, num, den, scale=1.0):
    top, bottom = growth(ctx, num), growth(ctx, den)
    if top is None or not bottom or bottom <= 0:
        return None
    return scale * top / bottom
