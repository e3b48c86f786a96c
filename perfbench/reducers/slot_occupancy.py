"""Mean share (%) of decode slots holding a sequence, from ``/stats``
polled once a second inside the window (the two ends included)."""
from .stats_delta import dig


def reduce(ctx):
    slots = ctx["max_slots"]
    running = [dig(s, "engine.scheduler.running") for s in ctx["stats_polls"]]
    running = [r for r in running if r is not None]
    if not running or not slots:
        return None
    return 100.0 * sum(running) / len(running) / slots
