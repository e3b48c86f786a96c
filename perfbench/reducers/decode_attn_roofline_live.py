"""``decode_attn_roofline`` with the live context taken from INSIDE the
program: the ``vgt.engine.decode_dispatch`` spans of the traced interval
carry each chunk's ``steps``, ``rows``, ``ctx_tokens`` (tokens resident
in all rows at dispatch) and ``lead`` (steps in flight that the host had
not folded in yet).  Over a chunk every row grows by one token a step,
so its mean context is ctx_tokens + rows x (lead + (steps - 1) / 2);
the live context is the step-weighted mean of that.  The client's view
(``decode_attn_roofline``) counts prompt + tokens RECEIVED, which lags
by what is in flight and misses what the chat template adds."""
import re

from .. import trace_spans


def live_context(chunks):
    steps = sum(c["steps"] for c in chunks)
    if not steps:
        return None
    return sum(
        c["steps"] * (c["ctx_tokens"] + (c.get("rows") or 0)
                      * ((c.get("lead") or 0) + (c["steps"] - 1) / 2.0))
        for c in chunks
    ) / steps


def reduce(ctx, pattern):
    trace, summary = ctx.get("trace"), trace_spans.load(ctx)
    if not trace or not summary or not ctx.get("peaks"):
        return None
    reg = re.compile(pattern)
    names = [n for n in trace["op_seconds"] if reg.search(n)]
    kernel_s = sum(trace["op_seconds"][n] for n in names)
    launches = sum(trace["op_counts"][n] for n in names)
    live = live_context(summary["decode"])
    if kernel_s <= 0 or launches == 0 or live is None:
        return None
    steps = launches / ctx["num_layers"]
    least_s = (steps * live * ctx["kv_bytes_per_token"]
               / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / kernel_s
