"""A latent-attention kernel's share (%) of its roofline, from what the
program counted of the REAL work (``/debug/perf`` ``totals.mla``, booked
once per readback: no padding, nothing above the diagonal) and the
kernel's summed device time in the trace.

``kind`` ``decode``: the absorbed decode kernel.  ``decode_token_reads``
/ ``decode_steps`` over the window is the (token, layer) rows a step
reads; the trace holds launches / ``attn_layers`` steps.  Each row read
is ``latent_row_bytes`` moved and ``mla_decode_flops_per_token_read``
operations (the configuration's shapes module).  ``kind`` ``prefill``:
the prompt pass's attention.  ``prefill_pairs`` / ``prefill_prompts``
over the window is the (query, key, layer) triples a prompt costs; the
trace holds launches / ``attn_layers`` prompts (one prompt a wave in the
cell); each is ``mla_prefill_flops_per_pair`` operations, and the bytes
are negligible beside them.  Least time = the LARGER of bytes / peak HBM
bandwidth and operations / peak bf16 rate (``peaks.json``).  Returns
None where the program has no such counters (a parent without latent
attention) or the trace no such kernel."""
import importlib
import re

from .perf_ratio import growth


def reduce(ctx, pattern, kind):
    trace = ctx.get("trace")
    if not trace or not ctx.get("peaks"):
        return None
    shapes = importlib.import_module(
        ctx["config"].get("shapes", "perfbench.shapes"))
    if not hasattr(shapes, "latent_row_bytes"):
        return None
    work, per = (("mla.decode_token_reads", "mla.decode_steps")
                 if kind == "decode"
                 else ("mla.prefill_pairs", "mla.prefill_prompts"))
    units, count = growth(ctx, [work]), growth(ctx, [per])
    if units is None or not count:
        return None
    reg = re.compile(pattern)
    names = [n for n in trace["op_seconds"] if reg.search(n)]
    kernel_s = sum(trace["op_seconds"][n] for n in names)
    launches = sum(trace["op_counts"][n] for n in names)
    if kernel_s <= 0 or launches == 0:
        return None
    cfg, peaks = ctx["config"], ctx["peaks"]
    traced = launches / ctx["attn_layers"] * units / count
    if kind == "decode":
        moved = traced * shapes.latent_row_bytes(cfg)
        flops = traced * shapes.mla_decode_flops_per_token_read(cfg)
    else:
        moved = 0.0
        flops = traced * shapes.mla_prefill_flops_per_pair(cfg)
    least_s = max(moved / peaks["hbm_bytes_per_s"],
                  flops / peaks["bf16_flops"])
    return 100.0 * least_s / kernel_s
