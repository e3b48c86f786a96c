"""Mean length (steps) of the decode chunks read back inside the window:
growth of ``/debug/perf`` totals.chunks_by_steps {steps: count}.
The engine cuts chunks short while prompts wait for a free slot
(``_pick_chunk``), and every chunk costs one host round trip."""
from .stats_delta import dig


def reduce(ctx):
    a = dig(ctx["perf"].get("open"), "totals.chunks_by_steps")
    b = dig(ctx["perf"].get("close"), "totals.chunks_by_steps")
    if a is None or b is None:
        return None
    grown = {int(k): v - a.get(k, 0) for k, v in b.items()}
    chunks = sum(grown.values())
    if chunks <= 0:
        return None
    return sum(k * n for k, n in grown.items()) / chunks
