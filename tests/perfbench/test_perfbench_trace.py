"""Trace arithmetic on plain tuples, and the reducers on a summary."""

import os

import pytest

from perfbench import trace
from perfbench.reducers import (decode_attn_roofline, perf_compiles,
                                perf_host_share, pool_fill, slot_occupancy,
                                trace_idle, trace_share)
from perfbench.stats import Sample

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MS = 1e-3


def test_union_and_merge():
    spans = [(0, 2), (1, 3), (5, 6), (6, 7), (10, 11)]
    assert trace.merged(spans) == [(0, 3), (5, 7), (10, 11)]
    assert trace.merged([]) == []


def test_self_time_takes_out_nested_events():
    events = [("while.1", 0.0, 10.0), ("fusion.1", 1.0, 4.0),
              ("kernel.2", 4.0, 9.0), ("inner", 5.0, 6.0),
              ("copy.3", 12.0, 13.0)]
    got = {n: s for n, _, s in trace.self_times(events)}
    assert got == pytest.approx({"while.1": 2.0, "fusion.1": 3.0,
                                 "kernel.2": 4.0, "inner": 1.0,
                                 "copy.3": 1.0})
    assert sum(got.values()) == pytest.approx(sum(
        b - a for a, b in trace.merged((a, b) for _, a, b in events)))


def summary():
    ops = [("fusion.1", 0 * MS, 2 * MS), ("paged_decode_attention.8", 2 * MS,
           8 * MS), ("fusion.1", 20 * MS, 22 * MS),
           ("paged_decode_attention.8", 22 * MS, 28 * MS),
           ("flash_prefill.6", 40 * MS, 50 * MS)]
    modules = [("jit__decode_chunk(77)", 0, 8 * MS),
               ("jit__decode_chunk(77)", 20 * MS, 28 * MS),
               ("jit__prefill_step(5)", 40 * MS, 50 * MS)]
    host = {"python3": [("loop", 0, 60 * MS),
                        ("np.asarray", 7 * MS, 21 * MS),
                        ("tiny", 14 * MS, 14.5 * MS),
                        ("dispatch", 27 * MS, 41 * MS)],
            "pjrt": [("D2H", 13 * MS, 15 * MS)]}
    devices = {"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": modules}}
    return trace.summarize(devices, host)


def test_summary_names_ops_by_module_and_charges_gaps():
    s = summary()
    assert s["busy_s"] == pytest.approx(26 * MS)
    assert s["window_s"] == pytest.approx(50 * MS)
    assert s["op_seconds"]["jit__decode_chunk/paged_decode_attention.8"] \
        == pytest.approx(12 * MS)
    assert s["op_counts"]["jit__decode_chunk/paged_decode_attention.8"] == 2
    assert s["op_seconds"]["jit__prefill_step/flash_prefill.6"] \
        == pytest.approx(10 * MS)
    # a gap goes to the innermost host events under it, never to the
    # thread's whole loop; 16 sample points of 0.75 ms each
    assert s["gap_seconds"] == pytest.approx(
        {"python3:np.asarray": 10.5 * MS, "pjrt:D2H": 0.75 * MS,
         "python3:tiny": 0.75 * MS, "python3:dispatch": 12 * MS})
    assert sum(s["gap_seconds"].values()) == pytest.approx(
        s["window_s"] - s["busy_s"])
    b = trace.breakdown(s)
    assert b["device_ops"][0][0].endswith("paged_decode_attention.8")
    assert b["idle_gaps"][0] == ["python3:dispatch", pytest.approx(12 * MS)]


def test_reducers_on_a_summary():
    ctx = {"trace": summary()}
    assert trace_idle.reduce(ctx) == pytest.approx(48.0)
    assert trace_share.reduce(ctx, patterns=["^jit__decode_chunk"]) \
        == pytest.approx(100 * 16 / 26)
    assert trace_share.reduce(ctx, patterns=["paged_decode_attention"]) \
        == pytest.approx(100 * 12 / 26)
    assert trace_idle.reduce({"trace": None}) is None
    assert trace_share.reduce({"trace": None}, patterns=["x"]) is None


def test_decode_attn_roofline_arithmetic():
    # two layers, two launches = one step; 1,000 live tokens of 1,000 B
    # = 1 MB at 1 GB/s = 1 ms least, against 12 ms measured
    live = Sample(segment="window", due_t=0, prompt_tokens=1000,
                  max_tokens=10, first_t=-1.0, last_t=100.0, chunk_tokens=0)
    ctx = {"trace": summary(), "profile": {"t0": 0.0, "t1": 1.0},
           "samples": [live], "num_layers": 2, "kv_bytes_per_token": 1000,
           "peaks": {"hbm_bytes_per_s": 1e9}}
    got = decode_attn_roofline.reduce(ctx, pattern="paged_decode_attention")
    assert got == pytest.approx(100 * 1.0 / 12, rel=0.01)
    assert decode_attn_roofline.reduce(
        dict(ctx, trace=None), pattern="x") is None


def test_perf_reducers_read_the_windows_two_ends():
    a = {"totals": {"wall_s": 10.0, "phase_seconds": {"device": 6.0},
                    "compiles": {"decode": 4, "prefill": 9}}}
    b = {"totals": {"wall_s": 30.0, "phase_seconds": {"device": 21.0},
                    "compiles": {"decode": 4, "prefill": 11}}}
    ctx = {"perf": {"open": a, "close": b}}
    assert perf_host_share.reduce(ctx) == pytest.approx(25.0)
    assert perf_compiles.reduce(ctx) == 2.0
    assert perf_compiles.reduce({"perf": {}}) is None


def test_scheduler_reducers_average_the_polls():
    def poll(running, used):
        return {"engine": {"kv_pages_total": 200,
                           "scheduler": {"running": running,
                                         "used_pages": used}}}
    ctx = {"max_slots": 8,
           "stats_polls": [poll(8, 50), poll(4, 100), {}, poll(6, 150)]}
    assert slot_occupancy.reduce(ctx) == pytest.approx(75.0)
    # the live share of the pool, not the allocator's reserved bytes
    assert pool_fill.reduce(ctx) == pytest.approx(50.0)
    assert pool_fill.reduce({"stats_polls": [{}]}) is None


@pytest.mark.skipif(
    not os.path.exists(os.path.join(DATA, "tiny_tpu.xplane.pb")),
    reason="no recorded trace")
def test_recorded_tpu_trace_reduces():
    """A trace recorded on a v5e: three rounds of a jitted
    ``_decode_chunk`` (a fori_loop of matmuls) and ``_prefill_step``."""
    devices, host = trace.read_planes(
        os.path.join(DATA, "tiny_tpu.xplane.pb"))
    assert list(devices) == ["/device:TPU:0"]
    s = trace.summarize(devices, host)
    assert 0 < s["busy_s"] < s["window_s"]
    names = list(s["op_seconds"])
    assert any(n.startswith("jit__decode_chunk/") for n in names)
    assert any(n.startswith("jit__prefill_step/") for n in names)
    assert sum(s["op_seconds"].values()) == pytest.approx(s["busy_s"],
                                                          rel=0.02)
    ctx = {"trace": s}
    share = (trace_share.reduce(ctx, patterns=["^jit__decode_chunk"])
             + trace_share.reduce(ctx, patterns=["^jit__prefill_step"]))
    assert share == pytest.approx(100.0, abs=2.0)
    assert 0 < trace_idle.reduce(ctx) < 100
    assert s["gap_seconds"]
    # the TPU's op names are whole HLO lines: only the name is kept
    assert all(" " not in n and "=" not in n for n in names)
    assert "jit__decode_chunk/while" in names
