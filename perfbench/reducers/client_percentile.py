"""A percentile of a per-request client observation (``ttft_s``,
``tpot_s``, ``max_gap_s``) over the scored requests that succeeded."""
from ..stats import percentile


def reduce(ctx, field, q, scale=1.0):
    values = [getattr(s, field) for s in ctx["scored"] if s.ok]
    p = percentile([v for v in values if v is not None], q)
    return None if p is None else p * scale
