"""The window layers' decode launches' share (%) of their roofline, from
what the program counted of the REAL work (``/debug/perf``
``totals.swa``, booked once per readback) and the launches' summed
device time in the trace.

``decode_row_reads`` / ``decode_steps`` over the window is the (live
ring row, window layer) pairs a step reads: min(context, window) rows a
sequence a layer, no padding, no dead page.  The trace holds launches /
``swa_layers`` steps.  Each row read is ``ring_row_bytes`` moved (K and
V) and ``swa_decode_flops_per_row_read`` operations (the configuration's
shapes module).  Least time = the LARGER of bytes / peak HBM bandwidth
and operations / peak bf16 rate (``peaks.json``).  Returns None where the
program has no such counters (a parent without ring layers), the shapes
module no such functions or the trace no such kernel."""
import importlib
import re

from .perf_ratio import growth


def reduce(ctx, pattern):
    trace = ctx.get("trace")
    if not trace or not ctx.get("peaks"):
        return None
    shapes = importlib.import_module(
        ctx["config"].get("shapes", "perfbench.shapes"))
    if not hasattr(shapes, "swa_decode_flops_per_row_read"):
        return None
    reads = growth(ctx, ["swa.decode_row_reads"])
    steps = growth(ctx, ["swa.decode_steps"])
    if reads is None or not steps:
        return None
    reg = re.compile(pattern)
    names = [n for n in trace["op_seconds"] if reg.search(n)]
    kernel_s = sum(trace["op_seconds"][n] for n in names)
    launches = sum(trace["op_counts"][n] for n in names)
    if kernel_s <= 0 or launches == 0:
        return None
    cfg, peaks = ctx["config"], ctx["peaks"]
    traced = launches / shapes.swa_layers(cfg) * reads / steps
    least_s = max(
        traced * shapes.ring_row_bytes(cfg) / peaks["hbm_bytes_per_s"],
        traced * shapes.swa_decode_flops_per_row_read(cfg)
        / peaks["bf16_flops"])
    return 100.0 * least_s / kernel_s
