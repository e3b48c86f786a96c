"""Shares (%) of the scored requests: ``refused`` = answered with
another status than 200; ``slo`` = met the traffic file's TTFT and TPOT
limits (a failed request misses)."""
from ..stats import slo_share


def reduce(ctx, what):
    scored = ctx["scored"]
    if not scored:
        return None
    if what == "refused":
        bad = sum(1 for s in scored if s.status not in (200, None))
        return 100.0 * bad / len(scored)
    if what == "slo":
        slo = ctx["traffic"].get("slo")
        if not slo:
            return None
        return slo_share(scored, slo["ttft_s"], slo["tpot_s"])
    raise ValueError(f"unknown share {what!r}")
