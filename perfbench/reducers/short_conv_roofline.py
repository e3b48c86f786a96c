"""The gated short convolution's decode step's share (%) of its memory
roofline.

In every conv layer a decode step has to move, for every row it runs,
the slot's tail in and out, the step's three rows ``B``, ``C``, ``X``
in and the gated result out (``conv_step_bytes_per_row_layer`` of the
configuration's shapes module): the same count whether XLA fuses the
step or a kernel runs it.  The step is no launch of its own, so its
time is the device time traced under the named scopes ``scopes``
(``perfbench.trace_scopes``, as ``scope_share`` reads it) and the steps
of the traced interval are the launches of the paged decode kernel
(``steps_pattern``) over ``attn_layers``; the rows are those of the
``vgt.engine.decode_dispatch`` spans (the sequences each chunk carried),
weighted by the chunk's steps.  Least time = the LARGER of bytes / peak
HBM bandwidth and operations / peak bf16 rate (``peaks.json``).  The
program also moves the idle slots' tails (its arrays are every slot's),
which is why an idle slot lowers the share.  None where the program has
no such scope (a parent without the layer)."""
import importlib
import re

from .. import trace_spans
from .scope_share import summary as scope_summary


def reduce(ctx, scopes, steps_pattern):
    trace, spans = ctx.get("trace"), trace_spans.load(ctx)
    if not trace or not spans or not ctx.get("peaks"):
        return None
    shapes = importlib.import_module(
        ctx["config"].get("shapes", "perfbench.shapes"))
    if not hasattr(shapes, "conv_step_bytes_per_row_layer"):
        return None
    data = scope_summary(ctx)
    if not data:
        return None
    spent = sum(s for name, s in data["scope_seconds"].items()
                if any(scope in name.split("/") for scope in scopes))
    reg = re.compile(steps_pattern)
    launches = sum(n for name, n in trace["op_counts"].items()
                   if reg.search(name))
    chunks = [c for c in spans["decode"] if c.get("rows")]
    chunk_steps = sum(c["steps"] for c in chunks)
    if spent <= 0 or launches == 0 or not chunk_steps:
        return None
    cfg, peaks = ctx["config"], ctx["peaks"]
    steps = launches / shapes.attn_layers(cfg)
    rows = sum(c["steps"] * c["rows"] for c in chunks) / chunk_steps
    row_layers = steps * rows * shapes.conv_layers(cfg)
    least_s = max(
        row_layers * shapes.conv_step_bytes_per_row_layer(cfg)
        / peaks["hbm_bytes_per_s"],
        row_layers * shapes.conv_step_flops_per_row_layer(cfg)
        / peaks["bf16_flops"])
    return 100.0 * least_s / spent
