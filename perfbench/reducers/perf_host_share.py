"""Share (%) of the engine tick's wall time NOT spent blocked on the
device: 1 - device_s / wall_s from ``/debug/perf`` totals, window's two
ends (host clock inside the program; scheduling, dispatch, readback
transfer and detokenisation all count as host)."""
from .stats_delta import dig


def reduce(ctx):
    a, b = ctx["perf"].get("open"), ctx["perf"].get("close")
    wall0, wall1 = dig(a, "totals.wall_s"), dig(b, "totals.wall_s")
    dev0 = dig(a, "totals.phase_seconds.device")
    dev1 = dig(b, "totals.phase_seconds.device")
    if None in (wall0, wall1, dev0, dev1) or wall1 <= wall0:
        return None
    return 100.0 * (1.0 - (dev1 - dev0) / (wall1 - wall0))
