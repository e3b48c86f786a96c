"""Metric arithmetic: interpolated percentiles, window scoring, TPOT."""

import pytest

from perfbench import stats
from perfbench.stats import Sample, percentile


@pytest.mark.parametrize("values,q,want", [
    ([1, 2, 3, 4, 5], 50, 3.0),
    ([1, 2, 3, 4], 50, 2.5),
    ([10, 20], 90, 19.0),
    ([0, 10, 20, 30, 40, 50, 60, 70, 80, 90, 100], 95, 95.0),
    ([7], 99, 7.0),
    ([], 50, None),
])
def test_percentile_interpolates(values, q, want):
    got = percentile(values, q)
    assert got == want if want is None else got == pytest.approx(want)


def sample(segment="window", due=0.0, first=1.0, last=3.0, end=3.1,
           n=21, status=200, done=True, planned=21, prompt=100):
    return Sample(segment=segment, due_t=due, prompt_tokens=prompt,
                  max_tokens=planned, status=status, first_t=first,
                  last_t=last, end_t=end, done=done, usage_prompt=prompt,
                  usage_completion=n, chunk_tokens=n, chunks=n)


def test_tpot_uses_the_servers_token_count():
    s = sample(first=1.0, last=3.0, n=21)
    s.chunks = 3  # tokens arrived in three bursts: irrelevant
    assert s.tpot_s == pytest.approx(0.1)
    assert s.ttft_s == pytest.approx(1.0)
    assert sample(n=1, planned=1).tpot_s is None


@pytest.mark.parametrize("change,ok", [
    ({}, True),
    ({"status": 503}, False),
    ({"done": False}, False),
    ({"n": 20}, False),  # a token short of the plan
])
def test_ok_needs_200_done_and_exact_counts(change, ok):
    assert sample(**change).ok is ok
    s = sample()
    s.usage_prompt = 99
    assert not s.ok


def test_open_loop_scores_what_was_due_in_the_window():
    t_open, t_close = 10.0, 20.0
    timeline = [
        sample("lead_in", due=9.9, first=10.5, end=12.0),
        sample("window", due=10.0, end=11.0),
        sample("window", due=19.9, first=21.0, last=29.0, end=30.0),
        sample("drain", due=20.1, end=22.0),
    ]
    got = stats.scored(timeline, "open", t_open, t_close)
    assert [s.due_t for s in got] == [10.0, 19.9]


def test_closed_loop_scores_what_ended_in_the_window():
    timeline = [
        sample("lead_in", due=1.0, end=9.9),
        sample("lead_in", due=1.0, end=10.1),   # resumed, ends inside
        sample("window", due=12.0, end=19.9),
        sample("window", due=15.0, end=20.0),   # cut off at the close
        sample("window", due=15.0, end=None),
    ]
    got = stats.scored(timeline, "closed", 10.0, 20.0)
    assert [s.end_t for s in got] == [10.1, 19.9]


def test_slo_share_counts_failures_as_misses():
    fast = sample(first=0.5, last=1.5)            # 50 ms a token
    slow_first = sample(first=2.5, last=3.5)
    slow_tokens = sample(first=0.5, last=4.5)     # 200 ms a token
    refused = sample(status=503)
    share = stats.slo_share([fast, slow_first, slow_tokens, refused],
                            ttft_s=2.0, tpot_s=0.1)
    assert share == pytest.approx(25.0)
    assert stats.slo_share([], 2.0, 0.1) is None


def test_live_context_is_prompt_plus_progress():
    s = sample(first=0.0, last=10.0, end=10.1, n=100, prompt=50)
    assert stats.live_context_tokens([s], 4.9, 5.1) == pytest.approx(
        100.0, abs=2.0)
    assert stats.live_context_tokens([s], 11.0, 12.0) == 0.0
    waiting = sample(first=None, last=None, end=None)
    assert stats.live_context_tokens([waiting], 0.0, 1.0) == 0.0
