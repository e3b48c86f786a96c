"""Scenario definitions: YAML + dataclasses composing traffic mixes,
arrival shapes, QPS sweeps, per-tier SLOs, and an optional chaos arm.

A scenario is the unit of comparison: two artifact files produced from
the same scenario (same name + same content hash) are comparable
cell-for-cell by ``python -m vgate_tpu.loadlab.compare``.  Bundled
scenarios live in ``vgate_tpu/loadlab/scenarios/*.yaml`` and are
addressable by bare name (``smoke_mixed``); anything else is a path.

Shapes map onto levers the engine already has:

* ``chat`` / ``multi_turn_chat`` — shared system prefixes + growing
  per-user transcripts exercise the PR-6 radix prefix cache,
* ``rag`` — common corpus preambles ahead of unique questions, same
  radix lever at a coarser grain,
* ``long_context`` — chunked-prefill pressure,
* ``embeddings`` — the non-generative path (admission + batcher only).

Tier mixes (interactive/standard/batch) exercise PR-4 admission and
priority scheduling; the chaos arm replays the PR 1-9 fault drills
under measured load via the ``/debug/faults`` surface.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import yaml

from vgate_tpu.admission import TIERS

from . import arrivals

SHAPES = ("chat", "multi_turn_chat", "rag", "long_context", "embeddings")

_SCENARIO_DIR = os.path.join(os.path.dirname(__file__), "scenarios")


@dataclass
class SLOSpec:
    """Per-request bounds a sample must meet to count toward goodput.

    All bounds are milliseconds; ``None`` means "not graded on this
    axis".  A request must ALSO have completed without error — a typed
    503/429/504 or an SSE error event can never be "good" no matter how
    fast it failed.
    """

    ttft_ms: Optional[float] = None
    tpot_ms: Optional[float] = None
    e2e_ms: Optional[float] = None

    def to_dict(self) -> Dict[str, Any]:
        return {
            k: v for k, v in dataclasses.asdict(self).items()
            if v is not None
        }

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "SLOSpec":
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = set(d) - known
        if unknown:
            raise ValueError(f"unknown SLO fields: {sorted(unknown)}")
        return cls(**d)


@dataclass
class TrafficMix:
    """One weighted slice of the offered traffic."""

    shape: str = "chat"
    weight: float = 1.0
    tier: str = "standard"
    # prompt/output sizing in tokenizer-agnostic "units" (~words).  On
    # the byte-tokenizer tiny-dense smoke model a unit is several
    # tokens; on real models roughly 1.3 tokens.  Sizing is relative —
    # scenarios compare against themselves, not across tokenizers.
    prompt_units: int = 48
    max_tokens: int = 16
    stream: bool = True
    # shared-prefix levers (chat/multi_turn_chat/rag): how many units
    # of prefix are shared, and across how large a cohort
    shared_prefix_units: int = 0
    group_size: int = 4
    # multi_turn_chat: transcript turns per simulated user
    turns: int = 3
    # rag: size of the shared corpus-passage pool
    num_docs: int = 8

    def __post_init__(self) -> None:
        if self.shape not in SHAPES:
            raise ValueError(
                f"unknown shape {self.shape!r}; valid: {SHAPES}"
            )
        if self.tier not in TIERS:
            raise ValueError(
                f"unknown tier {self.tier!r}; valid: {tuple(TIERS)}"
            )
        if self.weight <= 0:
            raise ValueError("mix weight must be > 0")

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "TrafficMix":
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = set(d) - known
        if unknown:
            raise ValueError(f"unknown mix fields: {sorted(unknown)}")
        return cls(**d)


@dataclass
class ChaosSpec:
    """Arm ``VGT_FAULTS``-style fault points mid-cell through the
    server's ``/debug/faults`` surface (requires the server to run with
    ``VGT_FAULTS_HTTP=1``).  ``cell_index`` limits arming to one sweep
    cell (None = every cell); ``at_s`` is the offset into that cell."""

    faults: str = ""
    at_s: float = 2.0
    cell_index: Optional[int] = None
    disarm_at_end: bool = True

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "ChaosSpec":
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = set(d) - known
        if unknown:
            raise ValueError(f"unknown chaos fields: {sorted(unknown)}")
        return cls(**d)


@dataclass
class ArrivalSpec:
    process: str = "poisson"
    # bursty-only knobs (ignored by poisson/constant)
    on_s: float = 2.0
    off_s: float = 4.0
    burst_mult: float = 3.0

    def __post_init__(self) -> None:
        if self.process not in arrivals.PROCESSES:
            raise ValueError(
                f"unknown arrival process {self.process!r}; "
                f"valid: {arrivals.PROCESSES}"
            )

    def generate(
        self, rate_qps: float, duration_s: float, seed: int
    ) -> List[float]:
        kwargs: Dict[str, float] = {}
        if self.process == "bursty":
            kwargs = {
                "on_s": self.on_s,
                "off_s": self.off_s,
                "burst_mult": self.burst_mult,
            }
        return arrivals.generate(
            self.process, rate_qps, duration_s, seed, **kwargs
        )

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "ArrivalSpec":
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = set(d) - known
        if unknown:
            raise ValueError(f"unknown arrival fields: {sorted(unknown)}")
        return cls(**d)


@dataclass
class Scenario:
    name: str = "unnamed"
    seed: int = 20260803
    # per-cell wall clock; the sweep runs every cell in qps_cells
    duration_s: float = 15.0
    qps_cells: List[float] = field(default_factory=lambda: [2.0])
    arrival: ArrivalSpec = field(default_factory=ArrivalSpec)
    mixes: List[TrafficMix] = field(default_factory=lambda: [TrafficMix()])
    slos: Dict[str, SLOSpec] = field(default_factory=dict)
    # per-request client timeout; a request past it is a typed
    # ``client_timeout`` sample, never an unhandled error
    request_timeout_s: float = 60.0
    # serial, un-measured requests fired before cell 0 (route warmup +
    # first-dispatch compiles must not skew the first cell's tail)
    warmup_requests: int = 3
    # env overrides for --launch mode (scripts boot the server with
    # these on top of the caller's environment)
    server_env: Dict[str, str] = field(default_factory=dict)
    chaos: Optional[ChaosSpec] = None

    def __post_init__(self) -> None:
        if not self.qps_cells:
            raise ValueError("scenario needs at least one qps cell")
        if not self.mixes:
            raise ValueError("scenario needs at least one traffic mix")
        for tier in self.slos:
            if tier not in TIERS:
                raise ValueError(
                    f"SLO for unknown tier {tier!r}; valid: {tuple(TIERS)}"
                )

    # -- (de)serialization ------------------------------------------------

    def to_dict(self) -> Dict[str, Any]:
        d: Dict[str, Any] = {
            "name": self.name,
            "seed": self.seed,
            "duration_s": self.duration_s,
            "qps_cells": list(self.qps_cells),
            "arrival": self.arrival.to_dict(),
            "mixes": [m.to_dict() for m in self.mixes],
            "slos": {t: s.to_dict() for t, s in self.slos.items()},
            "request_timeout_s": self.request_timeout_s,
            "warmup_requests": self.warmup_requests,
            "server_env": dict(self.server_env),
        }
        if self.chaos is not None:
            d["chaos"] = self.chaos.to_dict()
        return d

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "Scenario":
        d = dict(d)
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = set(d) - known
        if unknown:
            raise ValueError(f"unknown scenario fields: {sorted(unknown)}")
        if "arrival" in d:
            d["arrival"] = ArrivalSpec.from_dict(d["arrival"])
        if "mixes" in d:
            d["mixes"] = [TrafficMix.from_dict(m) for m in d["mixes"]]
        if "slos" in d:
            d["slos"] = {
                t: SLOSpec.from_dict(s) for t, s in d["slos"].items()
            }
        if d.get("chaos") is not None:
            d["chaos"] = ChaosSpec.from_dict(d["chaos"])
        return cls(**d)

    def to_yaml(self) -> str:
        return yaml.safe_dump(self.to_dict(), sort_keys=False)

    def content_hash(self) -> str:
        """Stable hash of everything that affects the offered load —
        compare refuses cross-scenario diffs on it.  server_env is
        included, but only the YAML's view of it: env-EXPORTED server
        overrides bypass this hash by design (a sweep re-points one
        scenario at other models), which is why compare.py additionally
        gates on the artifact's config_fingerprint (hashed from the
        live server's /stats config block)."""
        canon = json.dumps(self.to_dict(), sort_keys=True)
        return hashlib.sha256(canon.encode()).hexdigest()[:16]


def bundled_scenarios() -> List[str]:
    """Names of the scenarios shipped in the package."""
    if not os.path.isdir(_SCENARIO_DIR):
        return []
    return sorted(
        os.path.splitext(f)[0]
        for f in os.listdir(_SCENARIO_DIR)
        if f.endswith(".yaml")
    )


def load_scenario(name_or_path: str) -> Scenario:
    """Load a scenario by bundled name or filesystem path."""
    path = name_or_path
    if not os.path.exists(path):
        bundled = os.path.join(_SCENARIO_DIR, f"{name_or_path}.yaml")
        if os.path.exists(bundled):
            path = bundled
        else:
            raise FileNotFoundError(
                f"no scenario file {name_or_path!r} and no bundled "
                f"scenario of that name (bundled: {bundled_scenarios()})"
            )
    with open(path) as f:
        data = yaml.safe_load(f)
    if not isinstance(data, dict):
        raise ValueError(f"scenario file {path} is not a YAML mapping")
    return Scenario.from_dict(data)
