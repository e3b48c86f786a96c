"""SLO-graded workload lab: open-loop load generation, scenario traffic
suite, and goodput regression gating (ISSUE 11).

The lab drives the REAL HTTP server (never the engine directly — the
gateway, admission, batcher and SSE path are part of what is measured)
with pre-computed open-loop arrival schedules, grades what the client
observed against per-tier SLOs, and writes a stamped JSONL artifact
that ``python -m vgate_tpu.loadlab.compare`` gates perf PRs on.

Entry points:

    python -m vgate_tpu.loadlab run --scenario smoke_mixed \
        --base-url http://127.0.0.1:8000 --out new.jsonl
    python -m vgate_tpu.loadlab run --scenario smoke_mixed --launch
    python -m vgate_tpu.loadlab.compare old.jsonl new.jsonl

This package is deliberately jax-free: it runs from any client host,
and a process that imports jax next to the server would take the chip
from it.
"""

from .scenario import (  # noqa: F401
    ArrivalSpec,
    ChaosSpec,
    Scenario,
    SLOSpec,
    TrafficMix,
    bundled_scenarios,
    load_scenario,
)

__all__ = [
    "ArrivalSpec",
    "ChaosSpec",
    "Scenario",
    "SLOSpec",
    "TrafficMix",
    "bundled_scenarios",
    "load_scenario",
]
