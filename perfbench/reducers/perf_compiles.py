"""Program variants compiled (or loaded from the cache) inside the
window: growth of ``/debug/perf`` totals.compiles, all programs."""
from .stats_delta import dig


def count(perf):
    compiles = dig(perf, "totals.compiles")
    return None if compiles is None else sum(compiles.values())


def reduce(ctx):
    a, b = count(ctx["perf"].get("open")), count(ctx["perf"].get("close"))
    if a is None or b is None:
        return None
    return float(b - a)
