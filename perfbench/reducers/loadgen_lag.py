"""How late the generator ran (ms): open loop, send time minus due time;
closed loop, lateness of a 10 ms heartbeat on the generator's loop."""
from ..stats import percentile


def reduce(ctx, q):
    p = percentile(ctx["lags"], q)
    return None if p is None else p * 1000.0
