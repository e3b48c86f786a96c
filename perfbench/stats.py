"""Metric arithmetic on what the client saw.

Origin: ``vgate_tpu/loadlab/slo.py`` (percentiles, goodput) with linear
interpolation in place of nearest rank, and TPOT from the server's own
token count instead of the number of SSE chunks.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Iterable, List, Optional, Sequence

# loadlab's own bound (driver.py SEND_LAG_BOUND_S): past it the measuring
# host stalled or was saturated.  The run says so on stderr and in
# ``send_lag_p99_s``; it does not decide ``correct``, which is about the
# program's outputs (a shared host freezes for seconds in about one run
# of twenty, and the check's statistics set such a run aside)
SEND_LAG_BOUND_S = 0.25


def percentile(values: Iterable[float], q: float) -> Optional[float]:
    """Linearly interpolated percentile (``q`` in 0..100) between the
    two nearest ranks; None for no values.  Nearest rank moves in steps
    of one sample, which at a few hundred samples is itself noise."""
    xs = sorted(values)
    if not xs:
        return None
    if len(xs) == 1:
        return float(xs[0])
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


@dataclass
class Sample:
    """What the client observed of one request.  Times are on the load
    generator's clock (seconds); ``due_t`` is when the request was DUE,
    so a late send counts as latency (open loop)."""

    segment: str
    due_t: float
    prompt_tokens: int  # planned
    max_tokens: int  # planned
    resumed: bool = False
    sent_t: Optional[float] = None
    status: Optional[int] = None
    first_t: Optional[float] = None  # first content chunk
    last_t: Optional[float] = None  # last content chunk
    end_t: Optional[float] = None
    chunks: int = 0
    chunk_tokens: int = 0  # tokens counted from content chunks
    max_gap_s: float = 0.0  # longest wait between two content chunks
    done: bool = False  # saw [DONE]
    usage_prompt: Optional[int] = None
    usage_completion: Optional[int] = None
    error: Optional[str] = None

    @property
    def ok(self) -> bool:
        """HTTP 200, [DONE], and exactly the planned token counts."""
        return (
            self.status == 200 and self.done and self.error is None
            and self.usage_completion == self.max_tokens
            and self.usage_prompt == self.prompt_tokens
        )

    @property
    def ttft_s(self) -> Optional[float]:
        if self.first_t is None:
            return None
        return self.first_t - self.due_t

    @property
    def tpot_s(self) -> Optional[float]:
        """(t_last - t_first) / (tokens - 1), tokens from ``usage``: a
        decode chunk's tokens arrive together, so counting SSE chunks
        would make a short answer's TPOT swing by a chunk time."""
        n = self.usage_completion or 0
        if self.first_t is None or self.last_t is None or n < 2:
            return None
        return (self.last_t - self.first_t) / (n - 1)


def scored(samples: Sequence[Sample], loop: str, t_open: float,
           t_close: float) -> List[Sample]:
    """The requests a run is judged on.  Open loop: those DUE inside the
    window (the plan's ``window`` segment), whenever they finished.
    Closed loop: those that ENDED inside it (what is still running at
    the close is cut off by design)."""
    if loop == "open":
        return [s for s in samples if s.segment == "window"]
    return [
        s for s in samples
        if s.end_t is not None and t_open <= s.end_t < t_close
    ]


def slo_share(samples: Sequence[Sample], ttft_s: float,
              tpot_s: float) -> Optional[float]:
    """Share (%) of requests that met both limits; a failed request
    misses."""
    if not samples:
        return None
    met = sum(
        1 for s in samples
        if s.ok and s.ttft_s is not None and s.ttft_s <= ttft_s
        and (s.tpot_s is None or s.tpot_s <= tpot_s)
    )
    return 100.0 * met / len(samples)


def live_context_tokens(samples: Sequence[Sample], t0: float,
                        t1: float, steps: int = 32) -> float:
    """Mean number of context tokens resident between ``t0`` and ``t1``:
    a request holds prompt + generated-so-far tokens from its first
    token to its last, growing linearly in between.  This is what one
    decode step has to read from the KV cache."""
    if t1 <= t0:
        return 0.0
    total = 0.0
    for k in range(steps):
        t = t0 + (k + 0.5) * (t1 - t0) / steps
        for s in samples:
            if s.first_t is None or s.first_t > t:
                continue
            last = s.last_t if s.last_t is not None else s.first_t
            if s.end_t is not None and last < t:
                continue
            span = max(last - s.first_t, 1e-9)
            frac = min(1.0, (t - s.first_t) / span)
            total += s.prompt_tokens + frac * max(s.chunk_tokens, 1)
    return total / steps
