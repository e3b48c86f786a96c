"""Deterministic request synthesis: scenario mixes -> concrete HTTP
request plans.

Everything derives from the scenario seed + cell index + arrival index,
so the same scenario offers byte-identical traffic on every run (the
compare tool depends on it) while still exercising prefix sharing:
requests in the same cohort share system/corpus preambles verbatim, and
multi-turn users re-send their own growing transcript — the shapes the
PR-6 radix cache keys on.

Open-loop note: multi-turn transcripts are PRE-generated (the
"assistant" turns are synthesized filler, not the server's live
answers).  A closed-loop chat replay would condition turn N+1's send
time on turn N's completion — exactly the feedback loop this lab
refuses.  Prompt-side prefix reuse (the dominant term) is preserved;
generated-token reuse needs a closed loop (the benchmark's
``sharing: sessions`` traffic, perfbench/README.md).
"""

from __future__ import annotations

import random
from dataclasses import dataclass, field
from typing import Any, Dict, List

from .scenario import Scenario, TrafficMix

# tokenizer-agnostic filler vocabulary: wide enough that prefixes only
# collide when the generator MEANS them to collide
_WORDS = [
    "latency", "tensor", "batch", "page", "prefill", "decode", "cache",
    "shard", "router", "replica", "kernel", "systolic", "bandwidth",
    "queue", "token", "stream", "admission", "tier", "goodput", "knee",
    "roofline", "mesh", "pallas", "vector", "scalar", "matrix", "fused",
    "paged", "radix", "prefix", "chunk", "bucket", "slot", "grant",
]


def _words(rng: random.Random, n: int) -> str:
    return " ".join(rng.choice(_WORDS) for _ in range(max(1, n)))


@dataclass
class PlannedRequest:
    """One concrete request the driver will fire at ``offset_s``."""

    offset_s: float
    endpoint: str  # /v1/chat/completions | /v1/embeddings
    body: Dict[str, Any]
    tier: str
    shape: str
    stream: bool
    index: int = 0
    meta: Dict[str, Any] = field(default_factory=dict)


def _chat_body(
    mix: TrafficMix, messages: List[Dict[str, str]]
) -> Dict[str, Any]:
    body: Dict[str, Any] = {
        "messages": messages,
        "max_tokens": mix.max_tokens,
        "temperature": 0.0,
        "priority": mix.tier,
    }
    if mix.stream:
        body["stream"] = True
        body["stream_options"] = {"include_usage": True}
    return body


def _build_one(
    mix: TrafficMix, rng: random.Random, state: Dict[str, Any]
) -> Dict[str, Any]:
    """Endpoint + body for one request of ``mix``.  ``state`` carries
    per-mix cohort structures (shared prefixes, user transcripts)."""
    if mix.shape == "embeddings":
        return {
            "endpoint": "/v1/embeddings",
            "body": {
                "input": _words(rng, mix.prompt_units),
                "priority": mix.tier,
            },
            "stream": False,
        }

    if mix.shape == "rag":
        # shared corpus passages: every request opens with one of
        # num_docs verbatim preambles (the radix tree indexes each the
        # first time it is seen), then asks a unique question
        docs = state.setdefault("docs", {})
        doc_id = rng.randrange(max(1, mix.num_docs))
        if doc_id not in docs:
            doc_rng = random.Random(0x5A6 + doc_id)
            docs[doc_id] = _words(
                doc_rng, max(8, mix.shared_prefix_units or 32)
            )
        question = _words(rng, max(4, mix.prompt_units // 4))
        messages = [
            {
                "role": "system",
                "content": f"Answer from the passage. Passage {doc_id}: "
                           f"{docs[doc_id]}",
            },
            {"role": "user", "content": f"Question: {question}"},
        ]
        return {
            "endpoint": "/v1/chat/completions",
            "body": _chat_body(mix, messages),
            "stream": mix.stream,
        }

    if mix.shape == "multi_turn_chat":
        # cohort of group_size users sharing one system prompt; each
        # request advances one user's transcript by a turn and re-sends
        # the whole history (the growing-prefix shape)
        users = state.setdefault("users", {})
        system = state.setdefault(
            "system",
            "You are a concise serving-systems assistant. "
            + _words(random.Random(7), max(0, mix.shared_prefix_units)),
        )
        uid = rng.randrange(max(1, mix.group_size))
        history = users.setdefault(uid, [])
        if len(history) >= 2 * mix.turns:
            history.clear()  # user starts a fresh conversation
        history.append(
            {"role": "user",
             "content": _words(rng, max(4, mix.prompt_units // 2))}
        )
        messages = (
            [{"role": "system", "content": system}] + list(history)
        )
        # synthesize the assistant's reply into the transcript so the
        # NEXT turn re-sends it (prefix growth without closing the loop)
        history.append(
            {"role": "assistant",
             "content": _words(rng, max(4, mix.max_tokens // 2))}
        )
        return {
            "endpoint": "/v1/chat/completions",
            "body": _chat_body(mix, messages),
            "stream": mix.stream,
        }

    # chat / long_context: single turn, optional shared system prefix
    messages = []
    if mix.shared_prefix_units > 0:
        system = state.setdefault(
            "system",
            "You are a helpful assistant. "
            + _words(random.Random(11), mix.shared_prefix_units),
        )
        messages.append({"role": "system", "content": system})
    messages.append(
        {"role": "user", "content": _words(rng, mix.prompt_units)}
    )
    return {
        "endpoint": "/v1/chat/completions",
        "body": _chat_body(mix, messages),
        "stream": mix.stream,
    }


def build_plan(
    scenario: Scenario, cell_index: int, qps: float
) -> List[PlannedRequest]:
    """Arrivals + mix assignment + request synthesis for one sweep cell.

    The arrival process and the mix/content RNGs are seeded
    independently (seed, cell, purpose) so changing the traffic mix
    never perturbs the arrival timestamps and vice versa.
    """
    offsets = scenario.arrival.generate(
        qps, scenario.duration_s, seed=scenario.seed * 1009 + cell_index
    )
    mix_rng = random.Random(scenario.seed * 9176 + cell_index)
    weights = [m.weight for m in scenario.mixes]
    states: List[Dict[str, Any]] = [{} for _ in scenario.mixes]
    plan: List[PlannedRequest] = []
    for i, offset in enumerate(offsets):
        (mix_i,) = mix_rng.choices(range(len(scenario.mixes)), weights)
        mix = scenario.mixes[mix_i]
        built = _build_one(mix, mix_rng, states[mix_i])
        plan.append(
            PlannedRequest(
                offset_s=offset,
                endpoint=built["endpoint"],
                body=built["body"],
                tier=mix.tier,
                shape=mix.shape,
                stream=built["stream"],
                index=i,
            )
        )
    return plan


def warmup_requests(scenario: Scenario, n: int) -> List[PlannedRequest]:
    """Small serial pre-cell requests (not measured, not graded)."""
    rng = random.Random(scenario.seed + 77)
    out = []
    for i in range(n):
        out.append(
            PlannedRequest(
                offset_s=0.0,
                endpoint="/v1/chat/completions",
                body={
                    "messages": [
                        {"role": "user",
                         "content": f"warmup {i} " + _words(rng, 6)}
                    ],
                    "max_tokens": 4,
                    "temperature": 0.0,
                },
                tier="standard",
                shape="chat",
                stream=False,
                index=-1 - i,
            )
        )
    return out
