"""The gap-to-span arithmetic on plain tuples, and the reducers that read
the program's own spans and window counters on hand-made contexts."""

import json
import os

import pytest

from perfbench import manifest, trace_spans
from perfbench.reducers import (decode_attn_roofline_live, perf_boot,
                                perf_chunk_steps, perf_off_cpu, perf_ratio,
                                span_gap_share, trace_step_ms)

MS = 1e-3
SPANS = [("tick", 0, 10), ("schedule", 1, 3), ("decode_dispatch", 3, 5),
         ("emit", 7, 9), ("idle_wait", 10, 12), ("tick", 12, 14)]


def test_innermost_span_wins_and_the_tick_keeps_its_rest():
    assert trace_spans.innermost(SPANS) == [
        (0, 1, "tick"), (1, 3, "schedule"), (3, 5, "decode_dispatch"),
        (5, 7, "tick"), (7, 9, "emit"), (9, 10, "tick"),
        (10, 12, "idle_wait"), (12, 14, "tick")]
    assert trace_spans.innermost([]) == []
    # three deep: the leaf's time is taken out of both ancestors
    deep = [("tick", 0, 10), ("emit", 2, 8), ("inner", 4, 5)]
    assert trace_spans.innermost(deep) == [
        (0, 2, "tick"), (2, 4, "emit"), (4, 5, "inner"), (5, 8, "emit"),
        (8, 10, "tick")]


@pytest.mark.parametrize("gap, want", [
    ((3.5, 4.5), {"decode_dispatch": 1.0}),            # nested span
    ((2.5, 3.5), {"schedule": 0.5, "decode_dispatch": 0.5}),  # straddles
    ((15.0, 16.0), {"outside": 1.0}),                  # under no span
    ((13.5, 14.5), {"tick": 0.5, "outside": 0.5}),     # runs off the end
    ((4.0, 4.00001), {"short_gaps": 0.00001}),         # launch latency
])
def test_a_gap_is_cut_at_the_span_boundaries(gap, want):
    got = trace_spans.charge([gap], trace_spans.innermost(SPANS))
    assert got == pytest.approx(want)


def test_charged_gaps_sum_to_the_idle_time():
    gaps = [(0.5, 1.5), (2.5, 3.5), (9.5, 10.5), (14.5, 15.0), (6, 6.5)]
    got = trace_spans.charge(gaps, trace_spans.innermost(SPANS))
    assert sum(got.values()) == pytest.approx(sum(b - a for a, b in gaps))


def engine_spans():
    def span(name, a, b, **args):
        return {"name": name, "start": a * MS, "end": b * MS, "args": args}
    return [
        span("tick", 0, 30, tick=1), span("schedule", 0, 2),
        span("decode_dispatch", 2, 4, program="decode", steps=8, rows=10,
             ctx_tokens=1000, lead=8),
        span("device_wait", 4, 20), span("readback", 20, 21),
        span("emit", 21, 29, tokens=80),
        span("idle_wait", 30, 35),
        span("tick", 35, 60, tick=2),
        span("decode_dispatch", 36, 38, program="decode", steps=2, rows=10,
             ctx_tokens=1160, lead=0),
        # began after the device's last operation: not of this window
        span("decode_dispatch", 58, 59, program="decode", steps=8, rows=10,
             ctx_tokens=5000, lead=0),
    ]


def summary():
    # busy 5-20 and 38-50: idle 20-38 inside a 45 ms window
    ops = {"/device:TPU:0": [(5 * MS, 12 * MS), (12 * MS, 20 * MS),
                             (38 * MS, 50 * MS)]}
    return trace_spans.summarize(ops, engine_spans())


def test_summary_charges_the_idle_window_to_the_engines_spans():
    s = summary()
    assert s["engine_thread"] is True
    assert s["window_s"] == pytest.approx(45 * MS)
    assert s["busy_s"] == pytest.approx(27 * MS)
    assert s["gap_seconds"] == pytest.approx({
        "readback": 1 * MS, "emit": 8 * MS, "tick": 2 * MS,
        "idle_wait": 5 * MS, "decode_dispatch": 2 * MS})
    assert sum(s["gap_seconds"].values()) == pytest.approx(
        s["window_s"] - s["busy_s"])
    assert s["engine_cover"] == pytest.approx(1.0)
    assert [c["steps"] for c in s["decode"]] == [8, 2]
    assert s["span_seconds"]["device_wait"] == pytest.approx(16 * MS)
    # a program without the spans
    assert trace_spans.summarize({"/device:TPU:0": [(0, 1)]}, [])[
        "engine_thread"] is False


@pytest.fixture
def traced(tmp_path):
    """A ctx whose profile points at a directory with a ready summary
    (what ``trace_spans.load`` leaves beside the trace)."""
    trace_dir = tmp_path / "trace"
    trace_dir.mkdir()
    (tmp_path / "trace_spans.json").write_text(json.dumps(summary()))
    return {"profile": {"trace_dir": str(trace_dir)}}


def test_gap_share_reducers_split_the_idle_share(traced):
    share = lambda spans: span_gap_share.reduce(traced, spans=spans)
    assert share(["readback", "emit"]) == pytest.approx(100 * 9 / 45)
    assert share(["schedule", "state", "tick"]) == pytest.approx(100 * 2 / 45)
    assert share(["idle_wait", "device_wait", "outside"]) \
        == pytest.approx(100 * 5 / 45)
    four = [manifest.metric(f"engine.gap_{k}_share.tok")["args"]["spans"]
            for k in ("schedule", "dispatch", "emit", "wait")]
    assert sum(share(spans) for spans in four) == pytest.approx(100 * 18 / 45)
    assert len({name for spans in four for name in spans}) \
        == sum(len(spans) for spans in four)  # no span counted twice


def test_roofline_live_takes_the_context_from_the_dispatch_spans(traced):
    # chunk of 8: 1000 + 10 x (8 + 3.5) = 1115; chunk of 2: 1160 + 10 x
    # 0.5 = 1165; step-weighted mean over 10 steps = 1125
    assert decode_attn_roofline_live.live_context(summary()["decode"]) \
        == pytest.approx(1125.0)
    ctx = dict(traced, num_layers=2, kv_bytes_per_token=1000,
               peaks={"hbm_bytes_per_s": 1e9},
               trace={"op_seconds": {"m/paged_decode_attention.8": 12 * MS},
                      "op_counts": {"m/paged_decode_attention.8": 2}})
    # one step x 1125 tokens x 1000 B at 1 GB/s = 1.125 ms of 12 ms
    assert decode_attn_roofline_live.reduce(
        ctx, pattern="paged_decode_attention") == pytest.approx(
            100 * 1.125 / 12)
    assert decode_attn_roofline_live.reduce(
        dict(ctx, trace=None), pattern="x") is None


@pytest.mark.parametrize("profile", [None, {}, {"trace_dir": "/nonexistent"}])
def test_without_a_trace_the_span_reducers_return_none(profile):
    ctx = {"profile": profile, "trace": {"op_seconds": {}, "op_counts": {}},
           "peaks": {"hbm_bytes_per_s": 1e9}}
    assert trace_spans.load(ctx) is None
    assert span_gap_share.reduce(ctx, spans=["emit"]) is None
    assert decode_attn_roofline_live.reduce(ctx, pattern="x") is None


def test_a_trace_without_the_programs_spans_reads_as_nothing(tmp_path):
    (tmp_path / "trace").mkdir()
    (tmp_path / "trace_spans.json").write_text(json.dumps(
        trace_spans.summarize({"/device:TPU:0": [(0.0, 1.0)]}, [])))
    ctx = {"profile": {"trace_dir": str(tmp_path / "trace")}}
    assert trace_spans.load(ctx) is None
    assert span_gap_share.reduce(ctx, spans=["emit"]) is None


def perf_ctx():
    a = {"totals": {
        "wall_s": 10.0, "decode_steps": 100, "decode_device_s": 1.0,
        "decode_ctx_token_steps": 50_000, "engine_cpu_s": 3.0,
        "engine_cpu_in_wait_s": 0.5,
        "phase_seconds": {"device": 4.0, "readback": 1.0},
        "chunks_by_steps": {"1": 10, "2": 0, "4": 0, "8": 5},
        "admitted": 10, "queue_wait_s": 1.0,
        "first_tokens": 10, "prefill_s": 0.5,
        "boot_seconds": {"weights": 40.0, "digest": 6.0, "ready": 60.0},
        "gateway": {"ingress_n": 10, "ingress_s": 0.1, "first_chunk_n": 10,
                    "first_chunk_s": 0.2, "stream_tokens": 1000,
                    "stream_detok_s": 0.03, "stream_write_s": 0.02}}}
    b = {"totals": {
        "wall_s": 30.0, "decode_steps": 300, "decode_device_s": 9.0,
        "decode_ctx_token_steps": 250_000, "engine_cpu_s": 11.0,
        "engine_cpu_in_wait_s": 1.5,
        "phase_seconds": {"device": 10.0, "readback": 3.0},
        "chunks_by_steps": {"1": 30, "2": 10, "4": 0, "8": 15},
        "admitted": 30, "queue_wait_s": 5.0,
        "first_tokens": 30, "prefill_s": 2.5,
        "boot_seconds": {"weights": 40.0, "digest": 6.0, "ready": 60.0},
        "gateway": {"ingress_n": 30, "ingress_s": 0.5, "first_chunk_n": 30,
                    "first_chunk_s": 0.8, "stream_tokens": 5000,
                    "stream_detok_s": 0.23, "stream_write_s": 0.22}}}
    return {"perf": {"open": a, "close": b}}


def args_of(metric):
    return manifest.metric(metric)["args"]


@pytest.mark.parametrize("metric, want", [
    ("engine.device_wait_ms_per_step.tok", 40.0),             # 8 s / 200 steps
    ("scheduler.live_tokens_mean.tpot", 1000.0),    # 200 k / 200 steps
    ("gateway.stream_us_per_token.tok", 100.0),     # 0.4 s / 4000 tokens
    ("gateway.ingress_mean_ms.ttft", 20.0),
    ("scheduler.queue_wait_mean_ms.ttft", 200.0),
    ("engine.prefill_mean_ms.ttft", 100.0),
    ("gateway.first_chunk_mean_ms.ttft", 30.0),
])
def test_window_means_from_the_programs_counters(metric, want):
    assert perf_ratio.reduce(perf_ctx(), **args_of(metric)) \
        == pytest.approx(want)


def test_chunk_length_off_cpu_share_and_boot_parts():
    ctx = perf_ctx()
    # 20 chunks of 1, 10 of 2, 10 of 8 = 120 steps in 40 chunks
    assert perf_chunk_steps.reduce(ctx) == pytest.approx(3.0)
    # wall 20 - waits 8 - (cpu 8 - in-wait 1) = 5 of 20
    assert perf_off_cpu.reduce(ctx) == pytest.approx(25.0)
    assert perf_boot.reduce(ctx, what="weights") == pytest.approx(46.0)
    assert perf_boot.reduce(ctx, what="rest") == pytest.approx(14.0)


@pytest.mark.parametrize("perf", [
    {},                                             # no snapshot at all
    {"open": {"enabled": False}, "close": {"enabled": False}},
    # an older program: the totals it has, none of the new ones
    {"open": {"totals": {"wall_s": 1.0, "decode_steps": 1,
                         "phase_seconds": {"device": 0.5}}},
     "close": {"totals": {"wall_s": 2.0, "decode_steps": 9,
                          "phase_seconds": {"device": 1.0}}}},
])
def test_a_missing_snapshot_or_counter_reads_as_nothing(perf):
    ctx = {"perf": perf}
    for metric in ("engine.device_wait_ms_per_step.tok",
                   "gateway.stream_us_per_token.tpot",
                   "scheduler.queue_wait_mean_ms.ttft"):
        assert perf_ratio.reduce(ctx, **args_of(metric)) is None
    assert perf_chunk_steps.reduce(ctx) is None
    assert perf_off_cpu.reduce(ctx) is None
    assert perf_boot.reduce(ctx, what="weights") is None
    assert perf_boot.reduce(ctx, what="rest") is None


def test_a_chunk_length_off_the_ladder_counts():
    """tpu.decode_chunk is configurable: the reducer takes whatever
    lengths the program reports, and a length new at the window's end."""
    ctx = perf_ctx()
    ctx["perf"]["open"]["totals"]["chunks_by_steps"] = {"16": 5}
    ctx["perf"]["close"]["totals"]["chunks_by_steps"] = {"16": 10, "4": 5}
    assert perf_chunk_steps.reduce(ctx) == pytest.approx(10.0)
    ctx["perf"]["close"]["totals"]["chunks_by_steps"] = {"16": 5}
    assert perf_chunk_steps.reduce(ctx) is None  # no chunk in the window


def test_the_devices_step_time_comes_from_the_trace():
    args = args_of("model.decode_step_ms.tok")
    assert manifest.metric("model.decode_step_ms.tpot")["args"] == args
    trace = {
        "devices": [{"busy_s": 3.0}],
        "op_seconds": {
            "jit__decode_chunk/paged_decode_attention_pallas.8": 0.5,
            "jit__decode_chunk/fusion.174": 1.5,
            "jit__prefill_step/fusion.232": 1.0,
        },
        "op_counts": {
            "jit__decode_chunk/paged_decode_attention_pallas.8": 100,
            "jit__decode_chunk/fusion.174": 50,
            "jit__prefill_step/fusion.232": 7,
        },
    }
    # 100 launches / 2 layers = 50 steps; 2.0 s of decode ops
    ctx = {"trace": trace, "num_layers": 2}
    assert trace_step_ms.reduce(ctx, **args) == pytest.approx(40.0)
    assert trace_step_ms.reduce({"trace": None}, **args) is None
    trace["op_counts"] = {"jit__prefill_step/fusion.232": 7}
    assert trace_step_ms.reduce(ctx, **args) is None  # no decode traced


def test_a_window_without_decode_steps_has_no_mean():
    ctx = perf_ctx()
    ctx["perf"]["close"]["totals"]["decode_steps"] = 100
    assert perf_ratio.reduce(ctx, **args_of("engine.device_wait_ms_per_step.tok")) \
        is None


def test_the_manifest_holds_with_the_new_files():
    assert manifest.problems() == []
    bench = manifest.benchmark()
    cells = {w["name"] for w in bench["workloads"]}
    new = [m for m in bench["per_layer"]
           if os.path.exists(os.path.join(
               manifest.HERE, "metrics", m["name"] + ".json"))
           and manifest.metric(m["name"])["reducer"] in (
               "span_gap_share", "decode_attn_roofline_live", "perf_ratio",
               "perf_chunk_steps", "perf_off_cpu", "perf_boot",
               "trace_step_ms")]
    assert len(new) == 27
    for m in new:
        assert set(m["workloads"]) <= cells
    setup = [m for m in new if m["moves"] == "setup_s"]
    assert {m["name"] for m in setup} == {"engine.boot_weights_s.setup",
                                          "engine.boot_rest_s.setup"}
    assert all(set(m["workloads"]) == cells for m in setup)
