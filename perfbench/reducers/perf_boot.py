"""The program's own part of ``setup_s`` (s), from ``/debug/perf``
totals.boot_seconds: ``weights`` = drawing or loading the weights and
digesting them on the host; ``rest`` = process start to the engine being
ready, less that (imports, device set-up, pool, integrity baseline)."""
from .stats_delta import dig


def reduce(ctx, what):
    boot = (dig(ctx["perf"].get("close"), "totals.boot_seconds")
            or dig(ctx["perf"].get("open"), "totals.boot_seconds"))
    if not boot or "weights" not in boot:
        return None
    weights = boot["weights"] + boot.get("digest", 0.0)
    if what == "weights":
        return weights
    if what == "rest":
        return boot["ready"] - weights if "ready" in boot else None
    raise ValueError(f"unknown boot part {what!r}")
