"""The held experts' grouped product's share (%) of its roofline, for
an expert layer whose launches a layer, bytes an expert and operations a
pair the configuration's shapes module states
(``expert_launches_per_layer``, ``held_expert_bytes``,
``expert_flops_per_assignment``): ``moe_experts_roofline``'s arithmetic
without its constant of three launches.

A layer's products have to read the matrices of every held expert that
was HIT once, and to compute for every (token, choice) pair that fell
on a held expert.  Both are counted on the device and booked into
``/debug/perf`` ``totals.moe`` once per readback (``experts_hit``,
``held_assignments``, over ``layer_steps`` expert layers run); their
means over the window, times the layer-steps the trace holds (launches
/ launches a layer), give the bytes and the operations of the traced
interval.  Least time = the larger of bytes / peak HBM bandwidth and
operations / peak bf16 rate (``peaks.json``); the share is that over
the products' summed device time in the trace.  ``experts_hit`` cannot
pass the experts held, so with every held expert hit the bytes are the
held matrices' once a layer-step and the share cannot pass 100 %."""
import importlib
import re

from .perf_ratio import growth


def reduce(ctx, pattern):
    trace = ctx.get("trace")
    if not trace or not ctx.get("peaks"):
        return None
    shapes = importlib.import_module(
        ctx["config"].get("shapes", "perfbench.shapes"))
    if not hasattr(shapes, "expert_launches_per_layer"):
        return None
    layer_steps = growth(ctx, ["moe.layer_steps"])
    hit = growth(ctx, ["moe.experts_hit"])
    held = growth(ctx, ["moe.held_assignments"])
    if not layer_steps or hit is None or held is None:
        return None
    reg = re.compile(pattern)
    names = [n for n in trace["op_seconds"] if reg.search(n)]
    kernel_s = sum(trace["op_seconds"][n] for n in names)
    launches = sum(trace["op_counts"][n] for n in names)
    if kernel_s <= 0 or launches == 0:
        return None
    cfg, peaks = ctx["config"], ctx["peaks"]
    traced = launches / shapes.expert_launches_per_layer(cfg)
    moved = traced * hit / layer_steps * shapes.held_expert_bytes(cfg)
    flops = (traced * held / layer_steps
             * shapes.expert_flops_per_assignment(cfg))
    least_s = max(moved / peaks["hbm_bytes_per_s"],
                  flops / peaks["bf16_flops"])
    return 100.0 * least_s / kernel_s
