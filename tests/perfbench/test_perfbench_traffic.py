"""The traffic generator: same multiset whatever the seed, fixed counts,
resumed first wave, warm-up plan."""

import collections
import json
import os
import random

import pytest

from perfbench import manifest, traffic

HERE = os.path.join(manifest.ROOT, "perfbench", "traffic")
SEEDS = [1, 2147483659, 3000000019]


def load(name):
    with open(os.path.join(HERE, name + ".json")) as fh:
        return json.load(fh)


def sizes(plan):
    return collections.Counter(
        (r.segment, r.prompt_tokens, r.max_tokens, r.resumed)
        for r in plan.all_requests()
    )


@pytest.mark.parametrize("name,params", [
    ("chat", {"rate": 18.0}),
    ("decode-heavy", {"clients": 320, "resumed": 256}),
    ("prefill-heavy", {"clients": 32, "resumed": 32}),
])
def test_same_multiset_different_order(name, params):
    tr = load(name)
    plans = [traffic.build_plan(tr, params, s, 20.0) for s in SEEDS]
    assert sizes(plans[0]) == sizes(plans[1]) == sizes(plans[2])
    order = [[(r.prompt_tokens, r.max_tokens) for r in p.all_requests()]
             for p in plans]
    assert order[0] != order[1]
    again = traffic.build_plan(tr, params, SEEDS[0], 20.0)
    assert [r.messages for r in again.all_requests()] == \
        [r.messages for r in plans[0].all_requests()]


@pytest.mark.parametrize("rate,seconds", [(18.0, 45.0), (7.5, 10.0),
                                          (0.4, 3.0)])
def test_fixed_count_per_segment(rate, seconds):
    tr = load("chat")
    for seed in SEEDS:
        plan = traffic.build_plan(tr, {"rate": rate}, seed, seconds)
        by = collections.Counter(r.segment for r in plan.requests)
        assert by["window"] == round(rate * seconds)
        assert by["lead_in"] == round(rate * tr["lead_in_s"])
        assert by["drain"] == round(rate * tr["drain_s"])
        lead = tr["lead_in_s"]
        for r in plan.requests:
            lo, hi = {"lead_in": (0, lead), "window": (lead, lead + seconds),
                      "drain": (lead + seconds,
                                lead + seconds + tr["drain_s"])}[r.segment]
            assert lo <= r.due_s <= hi
        dues = [r.due_s for r in plan.requests]
        assert dues == sorted(dues)


def test_bursty_keeps_the_mean_and_fixes_the_count():
    rng = random.Random(5)
    spec = {"process": "bursty-fixed-count", "on_s": 2.0, "off_s": 4.0,
            "burst_mult": 3.0}
    arr = traffic.arrivals(spec, 10.0, 12.0, rng)
    assert len(arr) == 120  # two cycles of 2 s at 30/s and 4 s at 0/s
    on = [t for t in arr if t % 6.0 < 2.0]
    assert len(on) == 120  # burst_mult 3 = cycle / on_s: off rate is zero
    spec["burst_mult"] = 2.0
    arr = traffic.arrivals(spec, 10.0, 12.0, random.Random(5))
    assert len(arr) == 120 and len([t for t in arr if t % 6 < 2]) == 80


@pytest.mark.parametrize("kind,dist,lo,hi", [
    ("uniform", {"kind": "uniform", "lo": 16, "hi": 64}, 16, 64),
    ("loguniform", {"kind": "loguniform", "lo": 32, "hi": 128}, 32, 128),
    ("lognormal", {"kind": "lognormal", "median": 256, "sigma": 0.7,
                   "lo": 64, "hi": 1024}, 64, 1024),
    ("fixed", {"kind": "fixed", "value": 77}, 77, 77),
])
def test_stratified_lengths(kind, dist, lo, hi):
    xs = traffic.stratified(dist, 200)
    assert len(xs) == 200 and xs == sorted(xs)
    assert lo <= xs[0] and xs[-1] <= hi
    if kind == "lognormal":
        assert 230 <= xs[100] <= 285  # the median survives truncation
    if kind == "loguniform":
        assert 60 <= xs[100] <= 68  # geometric mean of 32 and 128


def test_resumed_first_wave():
    tr = load("decode-heavy")
    plan = traffic.build_plan(tr, {"clients": 320, "resumed": 256}, 9, 20.0)
    firsts = [q[0] for q in plan.clients]
    resumed = [r for r in firsts if r.resumed]
    assert len(resumed) == 256 and all(not r.resumed for r in firsts[256:])
    assert all(not r.resumed for q in plan.clients for r in q[1:])
    # a request caught mid-life: longer prompt, shorter budget, same total
    assert all(r.max_tokens >= 1 for r in resumed)
    assert max(r.prompt_tokens + r.max_tokens for r in resumed) <= 128 + 1024
    assert max(r.prompt_tokens for r in resumed) > 128
    # length-biased: the wave's mean answer is longer than the pool's
    pool = [r for q in plan.clients for r in q if not r.resumed]
    mean_pool = sum(r.max_tokens for r in pool) / len(pool)
    mean_total = sum(r.prompt_tokens + r.max_tokens for r in resumed) / 256
    assert mean_total > mean_pool + 64
    # ages are spread over the whole life, not bunched
    ages = sorted(r.prompt_tokens for r in resumed)
    assert ages[25] < 200 and ages[230] > 500


def test_prompt_length_is_what_the_gateway_will_count():
    msgs = [{"role": "system", "content": "abc"},
            {"role": "user", "content": "hello"}]
    flat = "System: abc\nUser: hello\nAssistant:"
    assert traffic.flattened_len(msgs) == len(flat)
    tr = load("chat")
    plan = traffic.build_plan(tr, {"rate": 5.0}, 3, 5.0)
    for r in plan.requests:
        assert traffic.flattened_len(r.messages) == r.prompt_tokens
        assert 64 <= r.prompt_tokens <= 1024 and 64 <= r.max_tokens <= 384


@pytest.mark.parametrize("kind,extra", [
    ("system_prefix", {"prefix_tokens": 512}),
    ("documents", {"prefix_tokens": 256, "num_docs": 4}),
    ("sessions", {"prefix_tokens": 512, "users": 8, "turns": 6}),
])
def test_sharing_kinds_share_what_they_say(kind, extra):
    tr = dict(load("chat"), sharing=dict(kind=kind, **extra))
    plan = traffic.build_plan(tr, {"rate": 6.0}, 11, 10.0)
    heads = collections.Counter(r.messages[0]["content"]
                                for r in plan.requests)
    assert all(r.messages[0]["role"] == "system" for r in plan.requests)
    assert len(heads) == (4 if kind == "documents" else 1)
    for r in plan.requests:
        assert traffic.flattened_len(r.messages) == r.prompt_tokens
    if kind == "sessions":
        assert max(len(r.messages) for r in plan.requests) > 3
    other = traffic.build_plan(tr, {"rate": 6.0}, 12, 10.0)
    assert set(heads) == {r.messages[0]["content"] for r in other.requests}


def test_warmup_covers_exactly_the_cells_buckets():
    buckets = [128, 256, 512, 1024, 2048]
    tr = load("prefill-heavy")
    plan = traffic.build_plan(tr, {"clients": 32, "resumed": 32}, 1, 10.0)
    warm = traffic.warmup_plan(plan, buckets, [8, 4, 2, 1], 8, 2048, 1)
    assert warm["buckets"] == [2048]
    assert sorted(b["size"] for b in warm["bursts"]) == [1, 2, 4, 8]
    tries = [t for b in warm["bursts"] for t in b["tries"]]
    assert all(traffic.bucket_for(r.prompt_tokens, buckets) == 2048
               for t in tries for r in t)
    assert warm["ladder"].max_tokens == 16
    assert all(r.prompt_tokens + r.max_tokens <= 2048
               for t in tries for r in t)
    # a second try has fresh text: the same would hit the prefix cache
    texts = [r.messages[0]["content"] for t in tries for r in t]
    assert len(set(texts)) == len(texts)
    chat = traffic.build_plan(load("chat"), {"rate": 18.0}, 1, 45.0)
    warm = traffic.warmup_plan(chat, buckets, [8, 4, 2, 1], 8, 2048, 1)
    assert warm["buckets"] == [128, 256, 512, 1024]
    hit = {(traffic.bucket_for(b["tries"][0][0].prompt_tokens, buckets),
            len(b["tries"][0])) for b in warm["bursts"]}
    assert hit == {(b["bucket"], b["size"]) for b in warm["bursts"]}
    assert hit == {(k, n) for k in warm["buckets"] for n in (8, 4, 2, 1)}


def test_a_closed_loops_window_holds_the_same_tokens_whatever_the_seed():
    """Which answers of the pool fall inside the window is sampling noise
    the shuffle leaves in ``out_tok_s``.  A client modelled on the chip's
    readings (2.8 s to the first token, then a token every 185 ms) puts
    it at about 1 % of the window's tokens in prefill-heavy, whose
    answers vary fourfold: no ordering rule beside the shuffle is
    needed."""
    cell = manifest.cell("qwen2.5-7b-l14.prefill-heavy")
    lead, seconds, wait, step = cell["traffic"]["lead_in_s"], 51.0, 2.8, 0.185

    def tokens_in_window(plan):
        total = 0.0
        for queue in plan.clients:
            t = 0.0
            for r in queue:
                first, last = t + wait, t + wait + step * r.max_tokens
                total += max(0.0, min(last, lead + seconds)
                             - max(first, lead)) / step
                t = last
            assert t > lead + seconds  # the queue outlasts the window
        return total

    counts = [tokens_in_window(traffic.build_plan(
        cell["traffic"], cell["params"], seed, seconds))
        for seed in range(1000, 1024)]
    mid = sorted(counts)[len(counts) // 2]
    assert 12_000 < mid < 13_500  # the chip delivered 250 tokens/s
    assert (max(counts) - min(counts)) / mid < 0.03
