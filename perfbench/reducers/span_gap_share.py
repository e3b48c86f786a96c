"""Share (%) of the traced window in which the device is idle UNDER the
named ``vgt.engine.*`` spans of the engine thread (perfbench/trace_spans.py:
each pause of 20 us or more is cut at the span boundaries and charged to
the innermost span).  The four metrics that use this split the idle
share: ``schedule`` + ``state`` + ``tick`` (a tick's unbracketed rest);
the two ``*_dispatch``; ``readback`` + ``emit``; ``idle_wait`` +
``device_wait`` + ``outside``.  With the pauses under 20 us (the
summary's ``short_gaps``) they sum to ``device.idle_share``."""
from .. import trace_spans


def reduce(ctx, spans):
    summary = trace_spans.load(ctx)
    if not summary or not summary["window_s"]:
        return None
    idle = sum(summary["gap_seconds"].get(name, 0.0) for name in spans)
    return 100.0 * idle / summary["window_s"]
