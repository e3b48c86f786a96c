"""The paged decode attention kernel's share (%) of its memory roofline.

The kernel is memory-bound: one decode step reads every resident token's
K and V once, in every layer.  Bytes the traced steps HAD to read =
steps x mean live context tokens x kv_bytes_per_token (perfbench/shapes.py;
all layers), where steps = kernel launches / layers and the live context
is what the client knows to be resident during the traced interval
(prompt + tokens received so far of every open stream).  Least time =
bytes / peak HBM bandwidth (perfbench/peaks.json); the share is that
over the kernel's summed device time in the trace."""
import re

from ..stats import live_context_tokens


def reduce(ctx, pattern):
    trace, prof = ctx.get("trace"), ctx.get("profile")
    if not trace or not prof or not ctx.get("peaks"):
        return None
    reg = re.compile(pattern)
    names = [n for n in trace["op_seconds"] if reg.search(n)]
    kernel_s = sum(trace["op_seconds"][n] for n in names)
    launches = sum(trace["op_counts"][n] for n in names)
    if kernel_s <= 0 or launches == 0:
        return None
    steps = launches / ctx["num_layers"]
    live = live_context_tokens(ctx["samples"], prof["t0"], prof["t1"])
    least_s = (steps * live * ctx["kv_bytes_per_token"]
               / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / kernel_s
