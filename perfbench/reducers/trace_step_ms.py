"""The device's time for ONE decode step (ms), from the trace: self time
of every operation under ``module`` (``jit__decode_chunk``) over the
steps the trace holds, steps = launches of the kernel that runs once a
layer a step (``pattern``) / layers.  The program's own counter
(``engine.device_wait_ms_per_step``) is the time the HOST is blocked per
step, which falls below this as soon as a second chunk is in flight."""
import re

from .trace_share import seconds


def reduce(ctx, module, pattern):
    trace = ctx.get("trace")
    if not trace or not trace["devices"]:
        return None
    reg = re.compile(pattern)
    launches = sum(n for name, n in trace["op_counts"].items()
                   if reg.search(name))
    if launches == 0:
        return None
    steps = launches / ctx["num_layers"]
    return 1000.0 * seconds(trace, ["^" + re.escape(module) + "/"]) / steps
