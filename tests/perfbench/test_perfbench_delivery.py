"""``gateway.tokens_per_delivery.{tok,tpot}``: read from the gateway's
counters, and nothing (not an error) from a program that lacks them."""

import pytest

from perfbench import manifest

NAMES = ["gateway.tokens_per_delivery.tok",
         "gateway.tokens_per_delivery.tpot"]


def perf(deliveries=None, tokens=None):
    gateway = {"stream_tokens": 100}
    if deliveries is not None:
        gateway.update(stream_deliveries=deliveries,
                       stream_tokens_delivered=tokens)
    return {"totals": {"gateway": gateway}}


@pytest.mark.parametrize("name", NAMES)
def test_tokens_per_delivery_is_the_growth_of_its_two_counters(name):
    spec = manifest.metric(name)
    reduce = manifest.reducer(spec["reducer"])
    ctx = {"perf": {"open": perf(100, 480), "close": perf(1100, 5680)}}
    assert reduce(ctx, **spec["args"]) == pytest.approx(5.2)


@pytest.mark.parametrize(
    "ctx",
    [
        {"perf": {"open": perf(), "close": perf()}},  # the parent commit
        {"perf": {}},  # no snapshot at all
        {"perf": {"open": perf(7, 7), "close": perf(7, 7)}},  # no stream
    ],
    ids=["no_counter", "no_snapshot", "no_growth"],
)
def test_tokens_per_delivery_reads_nothing_where_there_is_nothing(ctx):
    spec = manifest.metric(NAMES[0])
    assert manifest.reducer(spec["reducer"])(ctx, **spec["args"]) is None


def test_the_cells_that_report_it():
    bench = manifest.benchmark()
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"][-2:]] == NAMES
    assert by_name[NAMES[0]]["workloads"] == [
        "qwen2.5-1.5b.decode-heavy", "qwen2.5-7b-l14.prefill-heavy"]
    assert by_name[NAMES[1]]["workloads"] == ["qwen2.5-1.5b.chat"]
    for name in NAMES:
        assert by_name[name]["layer"] == "gateway"
        assert by_name[name]["better"] == "higher"
