"""One general traffic generator, driven by a traffic file.

A traffic file (``perfbench/traffic/<name>.json``) fixes a mix: the loop
kind, the arrival process, the length distributions, what prompts share,
lead-in and drain.  ``params`` of the workload (rate, clients) fill in
what depends on the pairing with a configuration.

Steadiness rules, each removing a source of run-to-run spread:

* **same multiset, different order** -- lengths are drawn by stratified
  sampling (fixed quantiles of the distribution, as many as the segment
  needs); ``seed`` only permutes them, jitters arrivals and draws prompt
  text.  Every run of a cell offers the same tokens;
* **fixed count** -- open-loop arrivals are a Poisson process conditioned
  on its count: N = round(rate x seconds) sorted uniform draws;
* **resumed first wave** -- a closed loop's first request per client is a
  request caught mid-life (prompt = p + floor(u*o), budget = o -
  floor(u*o)), drawn length-biased (a slot holds a long answer for
  longer), so slots hold the stationary mix of ages after one prefill
  wave.

Origin: extended copy of ``vgate_tpu/loadlab/arrivals.py`` and
``workload.py`` (open loop, ``bursty``, ``rag`` and ``multi_turn_chat``
shapes); the originals draw the count from the seed and use one fixed
length per mix.
"""

from __future__ import annotations

import math
import random
from dataclasses import dataclass, field
from statistics import NormalDist
from typing import Any, Dict, List, Optional, Sequence, Tuple

# the gateway flattens chat messages for tokenizers without a template
# (vgate_tpu/server/openai_models.py messages_to_prompt); with the byte
# tokenizer one byte is one token, so prompt length is computable.  The
# harness checks this against ``usage.prompt_tokens`` of a probe request.
ROLE_OVERHEAD = {"system": len("System: "), "user": len("User: "),
                 "assistant": len("Assistant: ")}
TAIL = len("\nAssistant:")
ALPHABET = "abcdefghijklmnopqrstuvwxyz"

ARRIVAL_PROCESSES = (
    "poisson-fixed-count", "bursty-fixed-count", "constant",
)
LOOPS = ("open", "closed")
SHARING_KINDS = ("none", "system_prefix", "documents", "sessions")


# ------------------------------------------------------------ lengths

def quantile(dist: Dict[str, Any], q: float) -> float:
    """Inverse CDF of a length distribution at ``q`` in (0, 1)."""
    kind = dist["kind"]
    if kind == "fixed":
        return float(dist["value"])
    lo, hi = float(dist["lo"]), float(dist["hi"])
    if kind == "uniform":
        return lo + q * (hi - lo)
    if kind == "loguniform":
        return math.exp(math.log(lo) + q * (math.log(hi) - math.log(lo)))
    if kind == "lognormal":
        # truncated to [lo, hi]: map q onto the kept part of the CDF
        mu, sigma = math.log(float(dist["median"])), float(dist["sigma"])
        nd = NormalDist(mu, sigma)
        c_lo, c_hi = nd.cdf(math.log(lo)), nd.cdf(math.log(hi))
        return math.exp(nd.inv_cdf(c_lo + q * (c_hi - c_lo)))
    raise ValueError(f"unknown length distribution {kind!r}")


def stratified(dist: Dict[str, Any], n: int) -> List[int]:
    """``n`` lengths at the mid-quantiles (i + 0.5) / n: the same
    multiset whatever the seed."""
    return [max(1, round(quantile(dist, (i + 0.5) / n))) for i in range(n)]


def paired_lengths(
    prompt: Dict[str, Any], output: Dict[str, Any], n: int, pairing_seed: int
) -> List[Tuple[int, int]]:
    """``n`` (prompt, output) pairs; the pairing is a fixed permutation
    (of the traffic file, not of the run), so the multiset of PAIRS is
    fixed too."""
    ps, os_ = stratified(prompt, n), stratified(output, n)
    random.Random(pairing_seed).shuffle(os_)
    return list(zip(ps, os_))


# ----------------------------------------------------------- arrivals

def fixed_count_arrivals(
    rate: float, seconds: float, rng: random.Random, start: float = 0.0
) -> List[float]:
    """Poisson process conditioned on its count: given N arrivals in an
    interval they are N independent uniform draws, sorted."""
    n = round(rate * seconds)
    return sorted(start + rng.random() * seconds for _ in range(n))


def bursty_arrivals(
    rate: float, seconds: float, rng: random.Random, start: float = 0.0,
    on_s: float = 2.0, off_s: float = 4.0, burst_mult: float = 3.0,
) -> List[float]:
    """On/off-modulated arrivals with a fixed count per phase: ``on_s``
    seconds at ``rate * burst_mult``, ``off_s`` at the rate that keeps
    the long-run mean at ``rate`` (loadlab's ``bursty``, count fixed)."""
    cycle = on_s + off_s
    burst_mult = min(burst_mult, cycle / on_s)
    rate_on = rate * burst_mult
    rate_off = (rate * cycle - rate_on * on_s) / off_s if off_s > 0 else 0.0
    out: List[float] = []
    t = 0.0
    while t < seconds:
        for width, r in ((on_s, rate_on), (off_s, rate_off)):
            width = min(width, seconds - t)
            if width <= 0:
                break
            out += fixed_count_arrivals(r, width, rng, start + t)
            t += width
    return sorted(out)


def arrivals(
    spec: Dict[str, Any], rate: float, seconds: float,
    rng: random.Random, start: float = 0.0,
) -> List[float]:
    process = spec["process"]
    if process == "poisson-fixed-count":
        return fixed_count_arrivals(rate, seconds, rng, start)
    if process == "bursty-fixed-count":
        return bursty_arrivals(
            rate, seconds, rng, start,
            on_s=float(spec.get("on_s", 2.0)),
            off_s=float(spec.get("off_s", 4.0)),
            burst_mult=float(spec.get("burst_mult", 3.0)),
        )
    if process == "constant":
        n = round(rate * seconds)
        return [start + (i + 0.5) * seconds / n for i in range(n)]
    raise ValueError(
        f"unknown arrival process {process!r}; valid: {ARRIVAL_PROCESSES}"
    )


# ----------------------------------------------------------- requests

@dataclass
class Request:
    """One planned request.  ``prompt_tokens`` and ``max_tokens`` are
    what the server's ``usage`` must report for it."""

    messages: List[Dict[str, str]]
    prompt_tokens: int
    max_tokens: int
    due_s: Optional[float] = None  # open loop: offset from traffic start
    segment: str = "window"  # lead_in | window | drain | warmup
    resumed: bool = False


def flattened_len(messages: Sequence[Dict[str, str]]) -> int:
    """Byte-tokenizer prompt tokens of the gateway's flattening."""
    n = sum(ROLE_OVERHEAD[m["role"]] + len(m["content"]) for m in messages)
    return n + (len(messages) - 1) + TAIL


def text(rng: random.Random, n: int) -> str:
    """``n`` letters that share no prefix with other draws beyond chance."""
    return "".join(rng.choices(ALPHABET, k=max(0, n)))


def min_prompt_tokens(prefix_messages: Sequence[Dict[str, str]] = ()) -> int:
    """Shortest prompt a request with these leading messages can have."""
    return flattened_len(
        list(prefix_messages) + [{"role": "user", "content": "x"}]
    )


class _Sharing:
    """What prompts share with earlier ones (traffic file ``sharing``).

    * ``none``: every prompt is fresh text;
    * ``system_prefix``: one system prompt of ``prefix_tokens`` for all;
    * ``documents``: each request opens with one of ``num_docs`` passages
      of ``prefix_tokens`` (loadlab's ``rag``);
    * ``sessions``: ``users`` users share a system prompt and re-send a
      growing transcript for ``turns`` turns (loadlab's
      ``multi_turn_chat``; assistant turns are synthesized, so the loop
      stays open).
    """

    def __init__(self, spec: Dict[str, Any], content_seed: int) -> None:
        self.spec = spec
        self.kind = spec.get("kind", "none")
        if self.kind not in SHARING_KINDS:
            raise ValueError(f"unknown sharing kind {self.kind!r}")
        fixed = random.Random(content_seed)  # shared text: not per run
        n = int(spec.get("prefix_tokens", 0))
        self.system = text(fixed, n)
        self.docs = [
            text(fixed, n) for _ in range(int(spec.get("num_docs", 0)))
        ]
        self.histories: Dict[int, List[Dict[str, str]]] = {}

    def build(
        self, rng: random.Random, prompt_tokens: int, max_tokens: int
    ) -> Tuple[List[Dict[str, str]], int]:
        """Messages for one request whose fresh part brings the prompt
        to ``prompt_tokens`` where that is possible; returns the
        messages and their true flattened length."""
        head: List[Dict[str, str]] = []
        if self.kind == "system_prefix":
            head = [{"role": "system", "content": self.system}]
        elif self.kind == "documents":
            head = [{"role": "system", "content": rng.choice(self.docs)}]
        elif self.kind == "sessions":
            uid = rng.randrange(int(self.spec["users"]))
            hist = self.histories.setdefault(uid, [])
            if len(hist) >= 2 * int(self.spec["turns"]):
                hist.clear()  # the user starts a new conversation
            head = [{"role": "system", "content": self.system}] + hist
        fresh = max(1, prompt_tokens - min_prompt_tokens(head) + 1)
        if self.kind == "sessions":
            # a turn adds its own text to the shared history, not a
            # whole prompt: prompt_tokens is the length of one turn
            fresh = max(1, prompt_tokens)
        user = {"role": "user", "content": text(rng, fresh)}
        messages = head + [user]
        if self.kind == "sessions":
            hist.append(user)
            hist.append({"role": "assistant",
                         "content": text(rng, max_tokens)})
        return messages, flattened_len(messages)


@dataclass
class Plan:
    """Everything one run sends, drawn from (traffic, params, seed)."""

    loop: str
    seconds: float
    lead_in_s: float
    drain_s: float
    # open loop: every request with its due time, all segments
    requests: List[Request] = field(default_factory=list)
    # closed loop: per-client queues (first entries are the first wave)
    clients: List[List[Request]] = field(default_factory=list)

    def all_requests(self) -> List[Request]:
        return self.requests + [r for c in self.clients for r in c]


def _seed_rng(seed: int, purpose: str) -> random.Random:
    # str seeds hash deterministically (sha512) in random.Random
    return random.Random(f"{seed}:{purpose}")


def build_plan(
    traffic: Dict[str, Any], params: Dict[str, Any], seed: int,
    seconds: float,
) -> Plan:
    loop = traffic["loop"]
    if loop not in LOOPS:
        raise ValueError(f"loop must be one of {LOOPS}, got {loop!r}")
    lead_in = float(traffic.get("lead_in_s", 0.0))
    drain = float(traffic.get("drain_s", 0.0)) if loop == "open" else 0.0
    pairing_seed = int(traffic.get("pairing_seed", 0))
    sharing = _Sharing(traffic.get("sharing", {}), pairing_seed)
    content = _seed_rng(seed, "content")
    order = _seed_rng(seed, "order")
    plan = Plan(loop=loop, seconds=seconds, lead_in_s=lead_in, drain_s=drain)

    def make(p: int, o: int, **kw: Any) -> Request:
        messages, true_p = sharing.build(content, p, o)
        return Request(messages=messages, prompt_tokens=true_p,
                       max_tokens=o, **kw)

    if loop == "open":
        rate = float(params["rate"])
        arr = _seed_rng(seed, "arrivals")
        start = 0.0
        for segment, width in (
            ("lead_in", lead_in), ("window", seconds), ("drain", drain),
        ):
            dues = arrivals(traffic["arrival"], rate, width, arr, start)
            pairs = paired_lengths(
                traffic["prompt_tokens"], traffic["output_tokens"],
                len(dues), pairing_seed,
            )
            order.shuffle(pairs)
            plan.requests += [
                make(p, o, due_s=due, segment=segment)
                for due, (p, o) in zip(dues, pairs)
            ]
            start += width
        return plan

    clients = int(params["clients"])
    per_client = int(traffic.get("requests_per_client", 8))
    pool = paired_lengths(
        traffic["prompt_tokens"], traffic["output_tokens"],
        clients * per_client, pairing_seed,
    )
    first_wave = _resumed_first_wave(
        pool, min(clients, int(params.get("resumed", clients))),
        random.Random(pairing_seed), order,
    )
    order.shuffle(pool)
    for c in range(clients):
        queue: List[Request] = []
        if c < len(first_wave):
            p, o, done = first_wave[c]
            queue.append(make(p + done, o - done, segment="lead_in",
                              resumed=True))
        queue += [
            make(p, o) for p, o in pool[c * per_client:(c + 1) * per_client]
        ]
        plan.clients.append(queue)
    return plan


def _resumed_first_wave(
    pool: Sequence[Tuple[int, int]], n: int, pairing: random.Random,
    order: random.Random,
) -> List[Tuple[int, int, int]]:
    """``n`` requests caught mid-life: (prompt, output, tokens already
    generated).  Length-biased -- at a random instant a slot is holding
    a request with probability proportional to its lifetime, which is its
    output length -- and stratified over both the choice and the age, so
    every seed starts from the same mix of ages in another order."""
    if n <= 0:
        return []
    ranked = sorted(pool, key=lambda po: (po[1], po[0]))
    total = sum(o for _, o in ranked)
    picks: List[Tuple[int, int]] = []
    acc, j = 0.0, 0
    for i in range(n):
        target = (i + 0.5) / n * total
        while acc + ranked[j][1] < target:
            acc += ranked[j][1]
            j += 1
        picks.append(ranked[j])
    ages = [(i + 0.5) / n for i in range(n)]
    pairing.shuffle(ages)  # which request has which age: fixed
    wave = [
        (p, o, min(o - 1, int(u * o))) for (p, o), u in zip(picks, ages)
    ]
    order.shuffle(wave)
    return wave


# ------------------------------------------------------------ warm-up

def bucket_for(n: int, buckets: Sequence[int]) -> int:
    """The prefill bucket a prompt of ``n`` tokens compiles under (the
    engine's ladder: smallest bucket that holds it)."""
    for b in sorted(buckets):
        if n <= b:
            return b
    return max(buckets)


def warmup_plan(
    plan: Plan, buckets: Sequence[int], wave_sizes: Sequence[int],
    decode_chunk: int, max_model_len: int, seed: int, attempts: int = 3,
) -> Dict[str, Any]:
    """Exactly the program variants this plan's traffic can reach: each
    prefill bucket its prompts fall in, at each wave size (the engine
    pads a same-bucket admission group to a power of two), and each
    power-of-two decode chunk up to the configured one.  Returned as a
    resident request (keeps the engine decoding, so that a burst queues
    and is admitted as one group; the harness cancels it when the bursts
    are through), a list of bursts -- each with fresh text for up to
    ``attempts`` tries, should a burst straddle an engine tick and split
    -- and a ladder request."""
    rng = _seed_rng(seed, "warmup")
    used = sorted({
        bucket_for(r.prompt_tokens, buckets) for r in plan.all_requests()
    })
    floor = min_prompt_tokens()

    def req(prompt_tokens: int, max_tokens: int) -> Request:
        n = max(floor, prompt_tokens)
        msgs = [{"role": "user", "content": text(rng, n - floor + 1)}]
        return Request(messages=msgs, prompt_tokens=flattened_len(msgs),
                       max_tokens=max_tokens, segment="warmup")

    bursts: List[Dict[str, Any]] = []
    prev = 0
    for b in used:
        # a length inside (prev, b], near the top: the bucket's worst case
        n = max(prev + 1, b - 1) if b - 1 >= floor else b
        n = min(n, max_model_len - 1)  # room for the one token it asks for
        for size in sorted(set(wave_sizes), reverse=True):
            bursts.append({
                "bucket": b, "size": size,
                "tries": [[req(n, 1) for _ in range(size)]
                          for _ in range(attempts)],
            })
        prev = b
    # one request alone walks the chunk ladder downwards as its budget
    # runs out: 2*chunk tokens -> chunk, chunk/2, ..., 1
    ladder = req(floor, 2 * decode_chunk)
    resident = req(floor, max(1, max_model_len - floor - 8))
    return {"buckets": used, "resident": resident, "bursts": bursts,
            "ladder": ladder}
