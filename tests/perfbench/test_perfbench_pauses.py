"""The benchmark's readings of delivery gaps, pauses, the collector and
the hand-off (ISSUE 35): one new reducer, ``perf_hist_percentile``, on
hand-made snapshots, and sixteen metrics that are data files."""

import pytest

from perfbench import manifest
from perfbench.reducers import perf_hist_percentile

CAUSES = ("compile", "prefill", "gc", "off_cpu", "device", "host")
CLOSED_LOOP = [
    "qwen2.5-1.5b.decode-heavy", "qwen2.5-7b-l14.prefill-heavy",
    "qwen3-next-80b-a3b-l8e128.decode-heavy",
    "nemotron-3-super-120b-a12b-l11e128.decode-heavy",
    "mistral-small-4-119b-l4e32.long-prompt",
]
NEW = {
    "engine.delivery_gap_p99_ms": ("engine tick", "ms"),
    "engine.pause_share": ("engine tick", "%"),
    "engine.pause_prefill_share": ("engine tick", "%"),
    "engine.pause_device_share": ("engine tick", "%"),
    "engine.pause_host_share": ("engine tick", "%"),
    "engine.gc_share": ("engine tick", "%"),
    "gateway.handoff_wait_p99_ms": ("gateway", "ms"),
    "gateway.handoffs_per_readback": ("gateway", "x"),
}


def ends(first, last):
    return {"perf": {"open": {"totals": first}, "close": {"totals": last}}}


def test_percentile_reads_growth_only_and_interpolates():
    hist = {"8": 1000, "16": 0, "32": 0, "inf": 0}
    grown = {"8": 1000, "16": 50, "32": 50, "inf": 0}
    ctx = ends({"delivery_gaps": hist}, {"delivery_gaps": grown})
    reduce = perf_hist_percentile.reduce
    # the 1000 gaps before the window are not the window's
    assert reduce(ctx, "delivery_gaps", 50) == pytest.approx(16.0)
    assert reduce(ctx, "delivery_gaps", 25) == pytest.approx(12.0)
    assert reduce(ctx, "delivery_gaps", 99) == pytest.approx(31.68)
    assert reduce(ctx, "delivery_gaps", 99, scale=2.0) == pytest.approx(
        63.36)
    # the first bucket starts at 0
    ctx = ends({"h": {"8": 0, "16": 0}}, {"h": {"8": 4, "16": 0}})
    assert reduce(ctx, "h", 50) == pytest.approx(4.0)
    # an overflow reads its lower edge: it has no upper one
    ctx = ends({"h": {"8": 0, "inf": 0}}, {"h": {"8": 1, "inf": 9}})
    assert reduce(ctx, "h", 99) == pytest.approx(8.0)
    # a dotted path, and the edges sorted as numbers (not as text)
    ctx = ends({"gateway": {"w": {"8": 0, "11.31": 0, "128": 0}}},
               {"gateway": {"w": {"128": 1, "8": 0, "11.31": 1}}})
    assert reduce(ctx, "gateway.w", 100) == pytest.approx(128.0)
    assert reduce(ctx, "gateway.w", 50) == pytest.approx(11.31)


@pytest.mark.parametrize("first, last", [
    ({}, {}),  # an older program: no such key
    ({"delivery_gaps": {"8": 3}}, {"delivery_gaps": {"8": 3}}),
    ({"delivery_gaps": 3}, {"delivery_gaps": 4}),  # not a histogram
])
def test_percentile_of_nothing_is_none_not_an_error(first, last):
    ctx = ends(first, last)
    assert perf_hist_percentile.reduce(ctx, "delivery_gaps", 99) is None
    ctx = {"perf": {}}  # no snapshot at all
    assert perf_hist_percentile.reduce(ctx, "delivery_gaps", 99) is None


def totals(wall_s, handoffs=0, deliveries=0, gc_s=0.0, **pauses):
    return {
        "wall_s": wall_s, "deliveries": deliveries,
        "pauses": {f"{c}_s": pauses.get(c, 0.0) for c in CAUSES},
        "gc": {"gc_s": gc_s},
        "gateway": {"stream_handoffs": handoffs},
    }


@pytest.mark.parametrize("suffix", [".tok", ".tpot"])
def test_the_shares_read_the_recorders_counters(suffix):
    ctx = ends(
        totals(10.0, handoffs=5, deliveries=5, gc_s=0.5, host=1.0),
        totals(60.0, handoffs=405, deliveries=505, gc_s=0.75,
               host=2.0, prefill=2.5, device=0.5, gc=0.25, off_cpu=0.5,
               compile=0.25),
    )

    def read(base):
        spec = manifest.metric(base + suffix)
        return manifest.reducer(spec["reducer"])(ctx, **spec["args"])

    assert read("engine.pause_prefill_share") == pytest.approx(5.0)
    assert read("engine.pause_device_share") == pytest.approx(1.0)
    assert read("engine.pause_host_share") == pytest.approx(4.0)
    # the three partition the whole
    assert read("engine.pause_share") == pytest.approx(10.0)
    assert read("engine.gc_share") == pytest.approx(0.5)
    assert read("gateway.handoffs_per_readback") == pytest.approx(0.8)
    # the parent has none of the counters: nothing, and no error
    old = {"ticks": 3, "wall_s": 1.0, "gateway": {"stream_handoffs": 2}}
    ctx = ends(old, {**old, "wall_s": 2.0})
    for base in NEW:
        assert read(base) is None, base


def test_the_manifest_holds_the_sixteen_and_the_files_agree():
    bench = manifest.benchmark()
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert len(bench["per_layer"]) == 96  # 80 at PR 34, these 16
    assert [m["name"] for m in bench["per_layer"][-16:]] == [
        base + suffix for base in NEW for suffix in (".tok", ".tpot")]
    for base, (layer, unit) in NEW.items():
        for suffix, moves, cells in (
            (".tok", "out_tok_s", CLOSED_LOOP),
            (".tpot", "tpot_p50_ms", ["qwen2.5-1.5b.chat"]),
        ):
            entry, spec = by_name[base + suffix], manifest.metric(
                base + suffix)
            assert entry["workloads"] == cells
            assert entry["moves"] == moves == spec["moves"]
            assert entry["layer"] == layer == spec["layer"]
            assert entry["unit"] == unit == spec["unit"]
            assert entry["better"] == "lower"
            assert entry["source"] == "program_counter"
            assert spec["reducer"] in ("perf_ratio", "perf_hist_percentile")
    assert manifest.problems() == []
