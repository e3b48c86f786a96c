"""The EVA decode launch's share (%) of its roofline, from what the
program counted of the REAL work (``/debug/perf`` ``totals.eva``, booked
once per readback from real lengths: no padding, no dead row of a
window buffer) and the launch's summed device time in the trace.

``window_rows_read + chunk_rows_read + window_rows_written`` over
``decode_steps`` in the window is the (row, layer) pairs a step reads
and the one it writes; the trace holds launches / ``attn_layers`` steps;
each row is ``eva_row_bytes`` moved and (a read one) ``eva_decode_flops_per_row``
operations (the configuration's shapes module).  The counts come from
the shapes module and the program's counters, so they read the same
work whatever implements it, and a launch that read more than it had to
(a partly filled page) reads under 100 %, never over.  Least time = the
LARGER of bytes / peak HBM bandwidth and operations / peak bf16 rate
(``peaks.json``).  None where the program has no such counters (a
parent without the layer) or the trace no such launch."""
import importlib
import re

from .perf_ratio import growth


def reduce(ctx, pattern):
    trace = ctx.get("trace")
    if not trace or not ctx.get("peaks"):
        return None
    shapes = importlib.import_module(
        ctx["config"].get("shapes", "perfbench.shapes"))
    if not hasattr(shapes, "eva_row_bytes"):
        return None
    read = growth(ctx, ["eva.window_rows_read", "eva.chunk_rows_read"])
    written = growth(ctx, ["eva.window_rows_written"])
    steps = growth(ctx, ["eva.decode_steps"])
    if read is None or written is None or not steps:
        return None
    reg = re.compile(pattern)
    names = [n for n in trace["op_seconds"] if reg.search(n)]
    kernel_s = sum(trace["op_seconds"][n] for n in names)
    launches = sum(trace["op_counts"][n] for n in names)
    if kernel_s <= 0 or launches == 0:
        return None
    cfg, peaks = ctx["config"], ctx["peaks"]
    traced = launches / shapes.attn_layers(cfg) / steps
    least_s = max(
        traced * (read + written) * shapes.eva_row_bytes(cfg)
        / peaks["hbm_bytes_per_s"],
        traced * read * shapes.eva_decode_flops_per_row(cfg)
        / peaks["bf16_flops"])
    return 100.0 * least_s / kernel_s
