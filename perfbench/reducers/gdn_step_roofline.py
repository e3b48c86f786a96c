"""The recurrent-step kernel's share (%) of its memory roofline.

One decode step reads and writes, in every linear layer, the float32
state of every row the program ran (``state_bytes_per_slot_layer`` of
the configuration's shapes module, twice).  The kernel launches once a
linear layer a step, so steps = launches / ``linear_layers``; the rows
are those of the ``vgt.engine.decode_dispatch`` spans of the traced
interval (the sequences each chunk carried), weighted by the chunk's
steps.  Least time = bytes / peak HBM bandwidth (``peaks.json``); the
share is that over the kernel's summed device time in the trace.  The
kernel also moves the idle slots' rows (its grid is every slot), which
is why an idle slot lowers the share."""
import importlib
import re

from .. import trace_spans


def reduce(ctx, pattern):
    trace, summary = ctx.get("trace"), trace_spans.load(ctx)
    if not trace or not summary or not ctx.get("peaks"):
        return None
    shapes = importlib.import_module(
        ctx["config"].get("shapes", "perfbench.shapes"))
    if not hasattr(shapes, "state_bytes_per_slot_layer"):
        return None
    reg = re.compile(pattern)
    names = [n for n in trace["op_seconds"] if reg.search(n)]
    kernel_s = sum(trace["op_seconds"][n] for n in names)
    launches = sum(trace["op_counts"][n] for n in names)
    chunks = [c for c in summary["decode"] if c.get("rows")]
    chunk_steps = sum(c["steps"] for c in chunks)
    if kernel_s <= 0 or launches == 0 or not chunk_steps:
        return None
    cfg = ctx["config"]
    layers = shapes.linear_layers(cfg)
    rows = sum(c["steps"] * c["rows"] for c in chunks) / chunk_steps
    steps = launches / layers
    moved = (steps * rows * layers
             * 2 * shapes.state_bytes_per_slot_layer(cfg))
    return 100.0 * moved / ctx["peaks"]["hbm_bytes_per_s"] / kernel_s
