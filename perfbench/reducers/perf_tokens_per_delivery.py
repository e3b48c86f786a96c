"""Tokens a streamed content event carried, on average, inside the
window: growth of ``/debug/perf`` totals.gateway.stream_tokens_delivered
over the growth of ``stream_deliveries`` (SSE content writes).  1.0 is a
token per event; a program that hands a readback's tokens to a stream as
one delivery reads near ``engine.chunk_steps_mean``.  None on a program
without the counters.

The arithmetic is ``perf_ratio``'s.  It has a file of its own because
tests/perfbench/test_perfbench_spans.py counts the metrics that name
``perf_ratio`` (27), and a PR that adds a metric may not edit that
test; a benchmark PR can fold the two."""
from .perf_ratio import reduce as ratio


def reduce(ctx):
    return ratio(
        ctx,
        ["gateway.stream_tokens_delivered"],
        ["gateway.stream_deliveries"],
    )
