"""The device clock's metrics (PR 49): eight data files that reduce the
window's growth of ``/debug/perf -> totals.device_clock`` with the
``perf_ratio`` reducer as it stands, and the clock's trace spans
(``vgt.device.<program>``, on a thread of their own), which the
reduction of the engine's spans must not see."""

import json
import os

import pytest

from perfbench import manifest, trace_spans

BENCH = manifest.benchmark()
BY_NAME = {m["name"]: m for m in BENCH["per_layer"]}
PROMPT_S = [f"device_clock.programs.{p}.s"
            for p in ("prefill", "suffix_prefill", "chunked_prefill")]
# new metric -> (the accepted metric whose list of cells it takes, moves,
# unit, num, den, scale)
NEW = {
    "device.window_idle_share.tok": (
        "device.idle_share.tok", "out_tok_s", "%",
        ["device_clock.idle_s"],
        ["device_clock.idle_s", "device_clock.busy_s"], 100.0),
    "device.window_idle_share.tpot": (
        "device.idle_share.tpot", "tpot_p50_ms", "%",
        ["device_clock.idle_s"],
        ["device_clock.idle_s", "device_clock.busy_s"], 100.0),
    "model.window_prefill_share.tok": (
        "model.prefill_share.tok", "out_tok_s", "%",
        PROMPT_S, ["device_clock.busy_s"], 100.0),
    "model.window_prefill_share.ttft": (
        "model.prefill_share.ttft", "ttft_p50_ms", "%",
        PROMPT_S, ["device_clock.busy_s"], 100.0),
    "model.window_decode_step_ms.tok": (
        "device.idle_share.tok", "out_tok_s", "ms",
        ["device_clock.programs.decode.s"],
        ["device_clock.decode_steps"], 1000.0),
    "model.window_decode_step_ms.tpot": (
        "model.decode_step_ms.tpot", "tpot_p50_ms", "ms",
        ["device_clock.programs.decode.s"],
        ["device_clock.decode_steps"], 1000.0),
    "model.window_prompt_ms_per_ktok.tok": (
        "model.prefill_share.tok", "out_tok_s", "ms/ktok",
        PROMPT_S, ["device_clock.prompt_tokens"], 1e6),
    "engine.prefill_device_queue_ms.ttft": (
        "engine.prefill_mean_ms.ttft", "ttft_p50_ms", "ms",
        ["device_clock.programs.prefill.queued_s"],
        ["device_clock.programs.prefill.n"], 1000.0),
}


def test_the_manifest_ends_with_the_eight_and_is_full():
    """Additions only, at the END of the list; with them the list holds
    the 128 metrics the contract allows (ISSUE 49's ninth,
    ``model.window_prompt_ms_per_ktok.ttft``, would have been the
    129th)."""
    assert [m["name"] for m in BENCH["per_layer"][-8:]] == list(NEW)
    assert len(BENCH["per_layer"]) == 128
    assert manifest.problems() == []


@pytest.mark.parametrize("name", list(NEW))
def test_a_file_names_perf_ratio_and_takes_its_neighbours_cells(name):
    beside, moves, unit, num, den, scale = NEW[name]
    spec = manifest.metric(name)
    entry = BY_NAME[name]
    assert spec["reducer"] == "perf_ratio" and spec["kind"] == "per_layer"
    assert spec["args"] == {"num": num, "den": den, "scale": scale}
    for key, want in (("source", "program_counter"), ("moves", moves),
                      ("unit", unit), ("better", "lower")):
        assert spec[key] == entry[key] == want, key
    layer = {"device": "device", "model": "model step",
             "engine": "engine tick"}[name.split(".")[0]]
    assert spec["layer"] == entry["layer"] == layer
    assert entry["workloads"] == BY_NAME[beside]["workloads"]
    loop = {"out_tok_s": "closed"}.get(moves, "open")
    for cell in entry["workloads"]:
        assert manifest.cell(cell)["traffic"]["loop"] == loop, cell
    assert os.path.basename(spec["_path"]) == name + ".json"


def snapshot(busy, idle, steps, tokens, dropped=0, **programs):
    rows = {p: {"n": 0, "s": 0.0, "queued_s": 0.0}
            for p in ("prefill", "suffix_prefill", "chunked_prefill",
                      "decode", "spec_verify")}
    for program, (n, s, queued) in programs.items():
        rows[program] = {"n": n, "s": s, "queued_s": queued}
    return {"totals": {"device_clock": {
        "busy_s": busy, "idle_s": idle, "decode_steps": steps,
        "prompt_tokens": tokens, "dropped": dropped, "programs": rows}}}


def read(name, ctx):
    spec = manifest.metric(name)
    return manifest.reducer(spec["reducer"])(ctx, **spec["args"])


def test_the_eight_reduce_a_pair_of_snapshots_to_the_hand_worked_values():
    """A 51 s window: the device busy 50 s, of them 36 s in 2,400 decode
    steps and 14 s in 61 prompt programs over 70,000 prompt tokens; the
    40 ``prefill`` launches waited 0.5 s in all behind the chunk ahead."""
    ctx = {"perf": {
        "open": snapshot(100.0, 10.0, 5000, 90000, prefill=(10, 4.0, 0.25),
                         suffix_prefill=(5, 1.0, 0.0), decode=(600, 95.0, 0)),
        "close": snapshot(150.0, 11.0, 7400, 160000,
                          prefill=(50, 12.0, 0.75),
                          suffix_prefill=(25, 5.0, 1.0),
                          chunked_prefill=(1, 2.0, 0.0),
                          decode=(900, 131.0, 0)),
    }}
    want = {
        "device.window_idle_share": 100 * 1.0 / 51.0,
        "model.window_prefill_share": 100 * (8.0 + 4.0 + 2.0) / 50.0,
        "model.window_decode_step_ms": 1000 * 36.0 / 2400,
        "model.window_prompt_ms_per_ktok": 1e6 * 14.0 / 70000,
        "engine.prefill_device_queue_ms": 1000 * 0.5 / 40,
    }
    for name in NEW:
        assert read(name, ctx) == pytest.approx(
            want[name.rsplit(".", 1)[0]]), name
    # the idle share and the programs' shares of the same window sum to
    # 100 % by construction
    busy = read("model.window_prefill_share.tok", ctx) + 100 * 36.0 / 50.0
    assert busy == pytest.approx(100.0)


def test_a_program_without_the_clock_reads_as_nothing():
    """The parent commit serves no ``totals.device_clock``: every new
    metric is left out of the line, none raises."""
    old = {"totals": {"decode_steps": 5, "wall_s": 1.0}}
    for ctx in ({"perf": {"open": old, "close": old}},
                {"perf": {}},
                {"perf": {"open": snapshot(1.0, 0, 0, 0), "close": None}}):
        assert [read(name, ctx) for name in NEW] == [None] * 8
    # a window without the work in it (no decode step, no prompt token)
    still = snapshot(2.0, 1.0, 7, 9)
    ctx = {"perf": {"open": still, "close": still}}
    assert [read(name, ctx) for name in NEW] == [None] * 8


# ---------------------------------------------------- the trace's side

MS = 10 ** 9  # picoseconds


def xspace(lines):
    """A serialized XSpace of one device plane (operations 5-20 ms and
    38-50 ms) and host lines ``{line name: [(event, start ms, end ms,
    {stat: int})]}``."""
    from jax.profiler import ProfileData

    names = sorted({ev[0] for events in lines.values() for ev in events})
    stats = sorted({k for events in lines.values() for ev in events
                    for k in ev[3]})
    text = ['planes { name: "/device:TPU:0"',
            ' lines { name: "XLA Ops" timestamp_ns: 0']
    for a, b in ((5, 12), (12, 20), (38, 50)):
        text.append(f"  events {{ metadata_id: 1 offset_ps: {a * MS} "
                    f"duration_ps: {(b - a) * MS} }}")
    # a decode chunk's module over the first two, a prompt program's
    # over the third
    text.append(' } lines { name: "XLA Modules" timestamp_ns: 0')
    for key, a, b in ((2, 5, 20), (3, 38, 50)):
        text.append(f"  events {{ metadata_id: {key} offset_ps: {a * MS} "
                    f"duration_ps: {(b - a) * MS} }}")
    text.append(' } event_metadata { key: 1 value { id: 1 name: '
                '"%fusion.1 = bf16[8]{0} fusion(%p)" } }')
    for key, name in ((2, "jit__decode_chunk(7)"),
                      (3, "jit__prefill_step(9)")):
        text.append(f' event_metadata {{ key: {key} value {{ id: {key} '
                    f'name: "{name}" }} }}')
    text.append("}")
    text.append('planes { name: "/host:CPU"')
    for tid, (line, events) in enumerate(lines.items(), start=1):
        text.append(f' lines {{ id: {tid} name: "{line}" timestamp_ns: 0')
        for name, a, b, args in events:
            held = " ".join(
                f"stats {{ metadata_id: {stats.index(k) + 1} "
                f"int64_value: {v} }}" for k, v in args.items())
            text.append(
                f"  events {{ metadata_id: {names.index(name) + 1} "
                f"offset_ps: {int(a * MS)} "
                f"duration_ps: {int((b - a) * MS)} {held} }}")
        text.append(" }")
    for i, name in enumerate(names, start=1):
        text.append(f' event_metadata {{ key: {i} value {{ id: {i} '
                    f'name: "{name}" }} }}')
    for i, name in enumerate(stats, start=1):
        text.append(f' stat_metadata {{ key: {i} value {{ id: {i} '
                    f'name: "{name}" }} }}')
    text.append("}")
    return ProfileData.text_proto_to_serialized_xspace("\n".join(text))


ENGINE = [
    ("vgt.engine.tick", 0, 30, {"tick": 1}),
    ("vgt.engine.schedule", 0, 2, {}),
    ("vgt.engine.decode_dispatch", 2, 4, {"steps": 8, "rows": 10}),
    ("vgt.engine.device_wait", 4, 20, {}),
    ("vgt.engine.readback", 20, 21, {}),
    ("vgt.engine.emit", 21, 29, {"tokens": 80}),
    ("vgt.engine.idle_wait", 30, 35, {}),
    ("vgt.engine.tick", 35, 60, {"tick": 2}),
    ("vgt.engine.decode_dispatch", 36, 38, {"steps": 2, "rows": 10}),
]
# the clock's thread: one wait a launch, each on top of the device's
# operations and over the pauses between them
CLOCK = [
    ("vgt.device.decode", 4, 20.5, {"steps": 8, "rows": 10}),
    ("vgt.device.prefill", 36.5, 50.5, {"prompt_tokens": 182, "rows": 1}),
    ("vgt.device.decode", 50.5, 58, {"steps": 2, "rows": 10}),
]


def test_the_clocks_spans_leave_the_engines_gap_shares_as_they_were(
    tmp_path
):
    """A capture of this PR's program holds ``vgt.device.*`` spans on a
    second thread.  ``trace_spans`` charges device pauses to the
    innermost ``vgt.engine.*`` span of the thread that ticks: with and
    without the clock's line it reads the same summary, so the four
    ``engine.gap_*_share`` read what they read before."""
    summaries = {}
    for key, lines in (
        ("before", {"python3/11": ENGINE}),
        ("after", {"python3/12": CLOCK, "python3/11": ENGINE,
                   "python3/13": [("vgt.gateway.sse_write", 1, 2, {})]}),
    ):
        path = tmp_path / f"{key}.xplane.pb"
        path.write_bytes(xspace(lines))
        summaries[key] = trace_spans.summarize(*trace_spans.read(str(path)))
    before, after = summaries["before"], summaries["after"]
    assert after == before
    assert after["engine_thread"] is True
    assert after["engine_cover"] == pytest.approx(1.0)
    assert after["gap_seconds"] == pytest.approx({
        "readback": 1e-3, "emit": 8e-3, "tick": 2e-3, "idle_wait": 5e-3,
        "decode_dispatch": 2e-3})
    assert not [k for k in after["span_seconds"] if "device." in k]

    (tmp_path / "trace").mkdir()
    (tmp_path / "trace_spans.json").write_text(json.dumps(after))
    ctx = {"profile": {"trace_dir": str(tmp_path / "trace")}}
    shares = {
        kind: read(f"engine.gap_{kind}_share.tok", ctx)
        for kind in ("schedule", "dispatch", "emit", "wait")}
    assert shares == pytest.approx({
        "schedule": 100 * 2 / 45, "dispatch": 100 * 2 / 45,
        "emit": 100 * 9 / 45, "wait": 100 * 5 / 45})


def test_a_capture_of_the_clocks_spans_alone_has_no_engine_thread(tmp_path):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(xspace({"python3/12": CLOCK}))
    ops, engine = trace_spans.read(str(path))
    assert engine == [] and len(ops["/device:TPU:0"]) == 3
    assert trace_spans.summarize(ops, engine)["engine_thread"] is False


def test_the_builders_check_sets_the_spans_beside_the_modules(tmp_path):
    """``benchmarks/device_clock_check.py``, the tool that makes the
    clock believable: inside the device's window (5-50 ms) the decode
    module ran 15 ms under a span of 15.5, the prompt program 12 ms
    under one of 13.5 (its wait began before the launch did), the third
    span lies past the window, and no busy time lies under no span."""
    from benchmarks import device_clock_check

    path = tmp_path / "t.xplane.pb"
    path.write_bytes(xspace({"python3/11": ENGINE, "python3/12": CLOCK}))
    got = device_clock_check.reduce_capture(str(tmp_path))
    assert got["trace_window_s"] == pytest.approx(45e-3)
    assert got["trace_busy_s"] == pytest.approx(27e-3)
    assert got["trace_idle_share_pct"] == pytest.approx(40.0)
    assert got["trace_prefill_share_pct"] == pytest.approx(100 * 12 / 27)
    assert got["clock_prefill_share_pct"] == pytest.approx(
        100 * 13.5 / 29, abs=1e-3)
    assert got["span_lines"] == 1
    assert got["busy_outside_spans_share_pct"] == pytest.approx(0.0)
    assert got["modules"]["jit__decode_chunk"] == {
        "program": "decode", "trace_self_s": pytest.approx(15e-3),
        "clock_span_s": pytest.approx(15.5e-3), "spans": 1,
        "diff_pct": pytest.approx(3.33, abs=0.01)}
    assert got["modules"]["jit__prefill_step"]["diff_pct"] == pytest.approx(
        12.5)
    # without the clock's line all the busy time is outside its spans
    (tmp_path / "t.xplane.pb").write_bytes(xspace({"python3/11": ENGINE}))
    bare = device_clock_check.reduce_capture(str(tmp_path))
    assert bare["busy_outside_spans_share_pct"] == pytest.approx(100.0)
    assert bare["span_lines"] == 0
    assert device_clock_check.reduce_capture(str(tmp_path / "none")) == {
        "error": f"no .xplane.pb under {tmp_path / 'none'}"}
