"""Device memory in use on the fullest chip (GB), as the chip reports it
through ``/stats`` at the window's close."""
from .stats_delta import dig


def reduce(ctx):
    mem = dig(ctx["stats"].get("close"), "engine.device_memory") or []
    used = [m["bytes_in_use"] for m in mem if "bytes_in_use" in m]
    return max(used) / 1e9 if used else None
