"""Mean share (%) of the KV pool's pages that hold live tokens, from
``/stats`` polled once a second inside the window (the two ends
included): ``used_pages`` over ``kv_pages_total``.  The allocator's
``bytes_in_use`` is the reserved pool and never moves; this is what the
traffic keeps in it."""
from .stats_delta import dig


def reduce(ctx):
    shares = []
    for s in ctx["stats_polls"]:
        used = dig(s, "engine.scheduler.used_pages")
        total = dig(s, "engine.kv_pages_total")
        if used is not None and total:
            shares.append(used / total)
    return 100.0 * sum(shares) / len(shares) if shares else None
