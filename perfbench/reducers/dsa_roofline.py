"""A learned-sparse-attention kernel's share (%) of its roofline in a
DECODE step, from what the program counted of the REAL work
(``/debug/perf`` ``totals.dsa``, booked once per readback: no padding)
and the kernel's summed device time in the trace.

``kind`` ``index``: the scoring pass.  ``rows_scored`` /
``decode_steps`` over the window is the (cached token, picking layer)
rows a step scores; the trace holds launches / ``index_layers`` steps;
each row is ``index_row_bytes`` moved and ``index_flops_per_row``
operations (the configuration's shapes module).  ``kind`` ``attend``:
the attention over the selected rows.  ``rows_attended`` /
``decode_steps`` is the (selected token, layer) rows a step attends to;
the trace holds launches / ``attn_layers`` steps; each row is
``latent_row_bytes`` moved and ``attend_flops_per_row`` operations.  The
counts come from the shapes module and the program's counters, so they
read the same work whatever implements it.  Least time = the LARGER of
bytes / peak HBM bandwidth and operations / peak bf16 rate
(``peaks.json``).  Returns None where the program has no such counters
(a parent without the selection) or the trace no such kernel."""
import importlib
import re

from .perf_ratio import growth


def reduce(ctx, pattern, kind):
    trace = ctx.get("trace")
    if not trace or not ctx.get("peaks"):
        return None
    shapes = importlib.import_module(
        ctx["config"].get("shapes", "perfbench.shapes"))
    if not hasattr(shapes, "index_row_bytes"):
        return None
    work = "dsa.rows_scored" if kind == "index" else "dsa.rows_attended"
    units, steps = growth(ctx, [work]), growth(ctx, ["dsa.decode_steps"])
    if units is None or not steps:
        return None
    reg = re.compile(pattern)
    names = [n for n in trace["op_seconds"] if reg.search(n)]
    kernel_s = sum(trace["op_seconds"][n] for n in names)
    launches = sum(trace["op_counts"][n] for n in names)
    if kernel_s <= 0 or launches == 0:
        return None
    cfg, peaks = ctx["config"], ctx["peaks"]
    if kind == "index":
        traced = launches / shapes.index_layers(cfg) * units / steps
        moved = traced * shapes.index_row_bytes(cfg)
        flops = traced * shapes.index_flops_per_row(cfg)
    else:
        traced = launches / shapes.attn_layers(cfg) * units / steps
        moved = traced * shapes.latent_row_bytes(cfg)
        flops = traced * shapes.attend_flops_per_row(cfg)
    least_s = max(moved / peaks["hbm_bytes_per_s"],
                  flops / peaks["bf16_flops"])
    return 100.0 * least_s / kernel_s
