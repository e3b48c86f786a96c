"""Share (%) of the engine tick's wall time in which the engine thread
neither ran nor waited for the device: wall - device - readback - (cpu
- cpu in those two waits), from ``/debug/perf`` totals at the window's
two ends (``engine_cpu_s`` is ``time.thread_time`` of the engine thread
per worked tick).  What is left is the thread waiting for the GIL
behind the gateway's event loop, or for the operating system."""
from .perf_ratio import growth


def reduce(ctx):
    wall = growth(ctx, ["wall_s"])
    waits = growth(ctx, ["phase_seconds.device", "phase_seconds.readback"])
    cpu = growth(ctx, ["engine_cpu_s"])
    cpu_in_wait = growth(ctx, ["engine_cpu_in_wait_s"])
    if None in (wall, waits, cpu, cpu_in_wait) or wall <= 0:
        return None
    return 100.0 * (wall - waits - (cpu - cpu_in_wait)) / wall
