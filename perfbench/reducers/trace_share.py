"""Share (%) of device busy time spent in operations whose
``<module>/<op>`` name matches one of ``patterns`` (self time, so a loop
does not count its body twice)."""
import re


def seconds(trace, patterns):
    regs = [re.compile(p) for p in patterns]
    return sum(s for name, s in trace["op_seconds"].items()
               if any(r.search(name) for r in regs))


def reduce(ctx, patterns):
    trace = ctx.get("trace")
    if not trace or not trace["devices"]:
        return None
    busy = sum(d["busy_s"] for d in trace["devices"])
    return 100.0 * seconds(trace, patterns) / busy if busy > 0 else None
