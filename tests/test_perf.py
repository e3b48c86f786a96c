"""Decode-loop perf observatory (ISSUE 13): per-tick phase attribution,
the compile ledger, and the live roofline/MFU gauges.

Fast tier: the attribution math on a fake clock (phases sum to the tick
wall, idle ticks excluded, window MFU/roofline match roofline.py
hand-computed on a pinned geometry), compile-ledger bookkeeping, the
shared roofline definition site + its benchmarks shim, dp merge
aggregation, the /debug/perf gateway surface (disabled / auth-gated /
drain-uncounted), and the loadlab perf-delta schema.  Slow tier: a real
tiny-dense engine whose measured phases sum to tick wall within
tolerance and whose ledger counts each variant's first compile exactly
once.
"""

import os
import threading
import time

import pytest
from aiohttp.test_utils import TestClient, TestServer

from vgate_tpu.config import ObservabilityConfig, load_config
from vgate_tpu.observability import perf as perf_mod
from vgate_tpu.observability.perf import PHASES, PerfRecorder
from vgate_tpu.observability.roofline import (
    DEVICE_PEAKS,
    EngineRoofline,
    decode_step_bytes,
    kv_bytes_per_token,
    peaks_for,
    roofline_row,
)


class FakeClock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


class FakeDevice:
    """The device of a test of the device clock: a launch's output is
    its name, and the wait for it returns when the test says the launch
    finished, and at what time on the fake clock."""

    def __init__(self, clock):
        self.clock = clock
        self.gates = {}
        self.poisoned = set()
        self.waited = []

    def gate(self, output):
        return self.gates.setdefault(output, threading.Event())

    def wait(self, output):
        assert self.gate(output).wait(10), output
        self.waited.append(output)
        if output in self.poisoned:
            raise RuntimeError(f"{output}: poisoned launch")

    @staticmethod
    def stamped(dev):
        totals = dev.totals()
        return totals["dropped"] + sum(
            row["n"] for row in totals["programs"].values())

    def finish(self, dev, output, at):
        """``output`` is done at ``at``; returns once it is stamped."""
        before = self.stamped(dev)
        self.clock.t = at
        self.gate(output).set()
        deadline = time.monotonic() + 10
        while self.stamped(dev) == before:
            assert time.monotonic() < deadline, output
            time.sleep(0.001)


def recorder(clock=None, roofline=None, **cfg):
    return PerfRecorder(
        ObservabilityConfig(**cfg),
        roofline=roofline,
        clock=clock or FakeClock(),
    )


PINNED = EngineRoofline(
    device_kind="TPU v4",
    num_chips=1,
    num_params=1_000_000_000,
    weight_stream_bytes=2_000_000_000,
    kv_token_bytes=kv_bytes_per_token(24, 8, 128, dtype_bytes=2),
)


# ------------------------------------------------- roofline definition


def test_peak_table_covers_tpu_v4_v5_v6():
    """ISSUE 13 satellite: the promoted peak table keeps the known
    per-chip numbers for every supported generation."""
    assert peaks_for("TPU v4") == (275e12, 1228.0)
    assert peaks_for("TPU v5e") == (197e12, 819.0)
    assert peaks_for("TPU v5 lite") == peaks_for("TPU v5e")
    assert peaks_for("TPU v5p") == (459e12, 2765.0)
    assert peaks_for("TPU v6e") == (918e12, 1640.0)
    assert peaks_for("TPU v6 lite") == peaks_for("TPU v6e")
    assert peaks_for("TPU v5") == (459e12, 2765.0)
    assert peaks_for("GPU H100") is None
    assert peaks_for("cpu") is None


def test_roofline_row_and_step_bytes_unchanged_semantics():
    kb = kv_bytes_per_token(2, 4, 8, dtype_bytes=2, scale_bytes=0)
    assert kb == 2 * 2 * 4 * 8 * 2
    assert decode_step_bytes(100, 2, 10, kb) == 100 + 2 * 10 * kb
    # 1.228e9 bytes in 1 ms = 1228 GB/s = exactly the v4 HBM peak
    row = roofline_row(1.0, 1_228_000_000, "TPU v4")
    assert row["achieved_hbm_gbps"] == pytest.approx(1228.0, abs=0.1)
    assert row["pct_of_hbm_roofline"] == pytest.approx(100.0, abs=0.1)
    assert roofline_row(0.0, 1, "TPU v4") == {}
    assert "pct_of_hbm_roofline" not in roofline_row(1.0, 1, "who?")


def test_engine_roofline_mfu_and_hbm_pct_hand_computed():
    flops, gbps = DEVICE_PEAKS["TPU v4"]
    # 1000 tok/s at 1B params = 2e12 FLOP/s over a 275e12 peak
    assert PINNED.mfu(1000.0) == pytest.approx(
        2.0 * 1e9 * 1000.0 / flops
    )
    # 61.4 GB moved over 0.1 s = 614 GB/s = 50% of the 1228 GB/s peak
    assert PINNED.hbm_roofline_pct(61.4e9, 0.1) == pytest.approx(50.0)
    assert PINNED.mfu(0.0) is None
    assert PINNED.hbm_roofline_pct(1.0, 0.0) is None
    unknown = EngineRoofline("cpu", 1, 1, 1, 1)
    assert unknown.mfu(100.0) is None
    assert unknown.hbm_roofline_pct(1e9, 1.0) is None


# ------------------------------------------------ per-tick attribution


def test_phases_sum_to_tick_wall_with_host_as_remainder():
    clock = FakeClock()
    rec = recorder(clock=clock)
    rec.tick_begin()
    rec.phase("dispatch", 0.010)
    rec.phase("device", 0.030)
    rec.phase("readback", 0.004)
    rec.phase("detok", 0.006)
    clock.advance(0.100)
    rec.tick_end(worked=True)
    totals = rec.totals()
    assert totals["ticks"] == 1
    phases = totals["phase_seconds"]
    assert phases["host"] == pytest.approx(0.050)
    assert sum(phases.values()) == pytest.approx(totals["wall_s"])
    assert set(phases) == set(PHASES)


def test_host_phase_clamps_at_zero_on_clock_noise():
    clock = FakeClock()
    rec = recorder(clock=clock)
    rec.tick_begin()
    rec.phase("device", 0.2)  # measured > wall (clock noise)
    clock.advance(0.1)
    rec.tick_end(worked=True)
    assert rec.totals()["phase_seconds"]["host"] == 0.0


def test_idle_ticks_are_counted_but_not_attributed():
    clock = FakeClock()
    rec = recorder(clock=clock)
    for _ in range(3):
        rec.tick_begin()
        clock.advance(0.005)
        rec.tick_end(worked=False)
    totals = rec.totals()
    assert totals["ticks"] == 0
    assert totals["idle_ticks"] == 3
    assert totals["wall_s"] == 0.0


def test_disabled_recorder_is_inert():
    rec = recorder(enabled=False)
    rec.tick_begin()
    rec.phase("device", 1.0)
    rec.note_tokens(5)
    rec.tick_end(worked=True)
    rec.record_compile("decode", ("k",), 1.0, trigger="x")
    assert rec.snapshot() == {"enabled": False}
    assert rec.get_stats() == {"enabled": False}
    assert rec.totals()["ticks"] == 0
    rec2 = recorder(perf_enabled=False)
    assert rec2.enabled is False


def test_window_gauges_match_roofline_hand_computed():
    """ISSUE 13 acceptance: the rolling-window MFU / roofline values
    equal roofline.py hand-computed on the pinned geometry."""
    clock = FakeClock()
    rec = recorder(clock=clock, roofline=PINNED, perf_window_s=60.0)
    device = FakeDevice(clock)
    rec.device = perf_mod.DeviceClock(clock=clock, wait=device.wait)
    # one decode tick: 8 fused steps over 500 resident ctx tokens,
    # 0.040 s of the DEVICE's time by its clock (the host waited 1 ms
    # of it: hidden behind the device), 8 tokens delivered
    rec.tick_begin()
    rec.phase("dispatch", 0.002)
    rec.device.post("decode", clock.t, "chunk", steps=8, rows=4)
    device.finish(rec.device, "chunk", at=clock.t + 0.040)
    rec.phase("device", 0.001)
    rec.note_decode(steps=8, ctx_tokens=500, device_s=0.001)
    rec.note_tokens(8)
    clock.advance(0.010)
    rec.tick_end(worked=True)
    rec.device.shutdown()
    clock.advance(1.950)  # window spans exactly 2 s since the tick began
    win = rec.window()
    assert win["ticks"] == 1
    tok_s = 8 / 2.0
    assert win["tokens_per_s"] == pytest.approx(tok_s, abs=0.01)
    assert win["mfu"] == pytest.approx(PINNED.mfu(tok_s), abs=1e-4)
    modeled = 8 * decode_step_bytes(
        PINNED.weight_stream_bytes, 1, 500, PINNED.kv_token_bytes
    )
    assert win["hbm_roofline_pct"] == pytest.approx(
        PINNED.hbm_roofline_pct(modeled, 0.040), abs=0.01
    )
    assert win["device_decode_s"] == pytest.approx(0.040)
    assert win["host_overhead_ratio"] == pytest.approx(
        (0.050 - 0.003) / 0.050, abs=1e-3
    )


def test_window_expires_old_ticks():
    clock = FakeClock()
    rec = recorder(clock=clock, perf_window_s=10.0)
    rec.tick_begin()
    rec.note_tokens(4)
    clock.advance(0.01)
    rec.tick_end(worked=True)
    clock.advance(60.0)  # tick now far outside the window
    win = rec.window()
    assert win["ticks"] == 0
    assert win["tokens"] == 0
    assert win["tokens_per_s"] == 0.0
    assert win["host_overhead_ratio"] is None
    # lifetime totals keep it
    assert rec.totals()["tokens"] == 4


# ------------------------------------------------------ compile ledger


def test_compile_ledger_one_entry_per_variant():
    rec = recorder()
    rec.record_compile("decode", (8, False), 1.5, trigger="chunk_variant")
    rec.record_compile("decode", (4, False), 0.5, trigger="chunk_variant")
    rec.record_compile("prefill", (16, 1), 2.0, trigger="bucket")
    ledger = rec.compile_ledger()
    assert len(ledger) == 3
    assert all(e["count"] == 1 for e in ledger)
    assert rec.totals()["compiles"] == {"decode": 2, "prefill": 1}
    assert rec.totals()["compile_seconds"] == pytest.approx(4.0)
    # the SAME signature again is a re-compile of a known variant:
    # count bumps on the one entry, no new entry appears
    rec.record_compile("decode", (8, False), 1.0, trigger="chunk_variant")
    ledger = rec.compile_ledger()
    assert len(ledger) == 3
    entry = next(
        e for e in ledger if e["signature"] == str((8, False))
    )
    assert entry["count"] == 2
    assert entry["seconds"] == pytest.approx(2.5)
    assert entry["trigger"] == "chunk_variant"


def test_compile_ledger_is_bounded():
    rec = recorder(perf_compile_ledger_max=16)
    for i in range(40):
        rec.record_compile("decode", ("sig", i), 0.01, trigger="t")
    assert len(rec.compile_ledger()) == 16
    # oldest evicted, newest kept
    sigs = {e["signature"] for e in rec.compile_ledger()}
    assert str(("sig", 39)) in sigs
    assert str(("sig", 0)) not in sigs


def test_profile_capture_links_into_snapshot():
    rec = recorder()
    rec.note_profile(
        {"trace_dir": "/tmp/vgt_profile_1", "duration_s": 0.5, "files": 3}
    )
    snap = rec.snapshot()
    assert snap["last_profile"]["trace_dir"] == "/tmp/vgt_profile_1"
    assert "ts" in snap["last_profile"]


# ------------------------------------------------------ dp aggregation


def _fake_snapshot(tokens=100, host=0.5, wall=1.0, mfu=0.1):
    phases = {name: 0.0 for name in PHASES}
    phases["host"] = host
    phases["device"] = wall - host
    return {
        "enabled": True,
        "window": {
            "window_s": 30.0,
            "span_s": 10.0,
            "ticks": 5,
            "tokens": tokens,
            "tokens_per_s": tokens / 10.0,
            "decode_steps": 50,
            "device_decode_s": wall - host,
            "phase_seconds": dict(phases),
            "wall_s": wall,
            "host_overhead_ratio": host / wall,
            "mfu": mfu,
            "hbm_roofline_pct": 10.0 * mfu,
        },
        "totals": {
            "ticks": 5,
            "idle_ticks": 2,
            "tokens": tokens,
            "decode_steps": 50,
            "wall_s": wall,
            "phase_seconds": dict(phases),
            "compiles": {"decode": 3, "prefill": 1},
            "compile_seconds": 2.0,
        },
        "pauses": [],
        "compile_ledger": [],
        "roofline": None,
        "last_profile": None,
    }


def test_merge_snapshots_sums_and_weights():
    a = _fake_snapshot(tokens=100, host=0.5, wall=1.0, mfu=0.1)
    b = _fake_snapshot(tokens=300, host=0.1, wall=1.0, mfu=0.3)
    merged = perf_mod.merge_snapshots([a, b])
    assert merged["enabled"] is True
    assert [r["replica"] for r in merged["replicas"]] == [0, 1]
    win = merged["window"]
    assert win["tokens"] == 400
    assert win["tokens_per_s"] == pytest.approx(40.0)
    assert win["phase_seconds"]["host"] == pytest.approx(0.6)
    # token-weighted MFU: (0.1*100 + 0.3*300) / 400 = 0.25
    assert win["mfu"] == pytest.approx(0.25)
    # wall-weighted host ratio: equal walls -> plain mean
    assert win["host_overhead_ratio"] == pytest.approx(0.3)
    totals = merged["totals"]
    assert totals["compiles"] == {"decode": 6, "prefill": 2}
    assert totals["tokens"] == 400


def test_merge_snapshots_all_disabled():
    merged = perf_mod.merge_snapshots([{"enabled": False}])
    assert merged["enabled"] is False
    assert "window" not in merged


def test_merge_stats_aggregates_stats_blocks():
    blocks = [
        {
            "enabled": True, "tokens_per_s": 10.0, "mfu": 0.1,
            "hbm_roofline_pct": 5.0, "host_overhead_ratio": 0.5,
            "phase_seconds": {n: 1.0 for n in PHASES},
            "ticks": 4, "compiles": {"decode": 2},
            "compile_seconds": 1.0,
        },
        {"enabled": False},
    ]
    agg = perf_mod.merge_stats(blocks)
    assert agg["enabled"] is True
    assert agg["tokens_per_s"] == pytest.approx(10.0)
    assert agg["compiles"] == {"decode": 2}
    assert perf_mod.merge_stats([{"enabled": False}]) == {
        "enabled": False
    }


# ------------------------------------------------- gateway surface


def _dry_config(**overrides):
    return load_config(
        model={"engine_type": "dry_run"},
        logging={"level": "WARNING"},
        **overrides,
    )


async def _client(config=None):
    from vgate_tpu.server.app import create_app

    client = TestClient(TestServer(create_app(config or _dry_config())))
    await client.start_server()
    return client


async def test_debug_perf_reports_disabled_without_engine_core():
    client = await _client()
    try:
        resp = await client.get("/debug/perf")
        assert resp.status == 200
        body = await resp.json()
        assert body["enabled"] is False
    finally:
        await client.close()


async def test_debug_perf_is_auth_gated():
    client = await _client(
        _dry_config(security={"enabled": True, "api_keys": ["sk-test"]})
    )
    try:
        assert (await client.get("/debug/perf")).status == 401
        resp = await client.get(
            "/debug/perf",
            headers={"Authorization": "Bearer sk-test"},
        )
        assert resp.status == 200
    finally:
        await client.close()


def test_debug_perf_never_holds_a_drain_open():
    from vgate_tpu.server.app import _drain_counted

    assert not _drain_counted("/debug/perf")


# ------------------------------------------------- loadlab perf fields


def test_cell_schema_pins_the_perf_field():
    from vgate_tpu.loadlab import slo

    assert "perf" in slo.CELL_REQUIRED
    cell = slo.grade_cell([], {}, qps=1.0, duration_s=1.0)
    assert cell["perf"] is None  # placeholder the runner overwrites


def test_runner_perf_delta_math():
    from vgate_tpu.loadlab.runner import perf_delta

    def snap(ticks, tokens, host, device, compiles, window=None):
        phases = {n: 0.0 for n in PHASES}
        phases["host"] = host
        phases["device"] = device
        return {
            "enabled": True,
            "window": window or {
                "tokens_per_s": 12.0, "mfu": 0.2,
                "hbm_roofline_pct": 30.0, "host_overhead_ratio": 0.4,
            },
            "totals": {
                "ticks": ticks, "tokens": tokens,
                "wall_s": host + device,
                "phase_seconds": phases,
                "compiles": compiles,
                "compile_seconds": 0.5 * sum(compiles.values()),
            },
        }

    before = snap(10, 100, 1.0, 3.0, {"decode": 2})
    after = snap(30, 500, 2.0, 8.0, {"decode": 2, "prefill": 1})
    delta = perf_delta(before, after)
    assert delta["ticks"] == 20
    assert delta["tokens"] == 400
    assert delta["phase_seconds"]["host"] == pytest.approx(1.0)
    assert delta["phase_seconds"]["device"] == pytest.approx(5.0)
    assert delta["wall_s"] == pytest.approx(6.0)
    assert delta["host_overhead_ratio"] == pytest.approx(
        1.0 / 6.0, abs=1e-4
    )
    # only the variants that MOVED land in the cell (recompile storm
    # visibility, not a full inventory)
    assert delta["recompiles"] == {"prefill": 1}
    assert delta["window"]["mfu"] == 0.2
    assert perf_delta(None, after) is None
    assert perf_delta(before, None) is None


# --------------------------------------------- real engine (slow tier)


def _engine_config():
    return load_config(
        model={
            "model_id": "tiny-dense",
            "engine_type": "jax_tpu",
            "dtype": "float32",
            "max_model_len": 64,
        },
        tpu={
            "dp": 1, "tp": 1, "ep": 1, "sp": 1, "num_devices": 1,
            "kv_num_pages": 64, "kv_page_size": 4,
            "max_batch_slots": 4, "prefill_buckets": [8, 16, 32],
            "use_pallas": False,
        },
        logging={"level": "WARNING"},
    )


@pytest.mark.slow
def test_engine_phase_attribution_sums_and_ledger_counts_once():
    """ISSUE 13 acceptance (engine half): on a real engine the per-phase
    decomposition sums to measured tick wall within 5%, the compile
    ledger counts each variant's first compile exactly once (repeating
    the same shape moves nothing), and /stats carries the perf block."""
    from vgate_tpu.backends.base import SamplingParams
    from vgate_tpu.runtime.engine_core import EngineCore

    core = EngineCore(_engine_config())
    core.start()
    try:
        params = [SamplingParams(max_tokens=8, temperature=0.0)] * 2
        core.generate(["perf probe one", "perf probe two"], params)
        # generate() returns from inside the last tick's emit bracket;
        # that tick's tokens reach the totals at its tick_end
        deadline = time.monotonic() + 5.0
        while (
            core.perf_snapshot()["totals"]["tokens"] < 16
            and time.monotonic() < deadline
        ):
            time.sleep(0.01)
        snap = core.perf_snapshot()
        assert snap["enabled"] is True
        totals = snap["totals"]
        assert totals["ticks"] > 0
        assert totals["tokens"] >= 16
        phase_sum = sum(totals["phase_seconds"].values())
        assert phase_sum == pytest.approx(
            totals["wall_s"], rel=0.05
        )
        ledger = snap["compile_ledger"]
        assert ledger, "no compiles recorded"
        assert all(e["count"] == 1 for e in ledger)
        before = {
            (e["program"], e["signature"]): e["count"] for e in ledger
        }
        assert any(p == "decode" for p, _ in before)
        assert any(p == "prefill" for p, _ in before)

        # the same shapes again: no variant compiles TWICE (admission
        # timing may group the wave differently and touch a new batch/
        # chunk variant — that is a new entry with count 1, not a
        # recompile; the drill pins the exact bucket-change contract
        # with serial requests, scripts/perf_check.sh)
        core.generate(["perf probe three", "perf probe four"], params)
        after = {
            (e["program"], e["signature"]): e["count"]
            for e in core.perf_snapshot()["compile_ledger"]
        }
        assert all(count == 1 for count in after.values())
        assert set(before) <= set(after)

        stats = core.get_stats()["perf"]
        assert stats["enabled"] is True
        assert stats["compiles"] == core.perf.totals()["compiles"]
        # CPU test meshes are off the peak table: the gauges exist and
        # are honestly None rather than mislabeled
        assert "mfu" in stats and "hbm_roofline_pct" in stats

        # the /v1/profile link: a capture lands in the flight ring AND
        # /debug/perf's last_profile
        result = core.capture_profile(duration_s=0.05)
        snap = core.perf_snapshot()
        assert snap["last_profile"]["trace_dir"] == result["trace_dir"]
        assert any(
            t["kind"] == "profile" for t in core.flight.ticks()
        )
    finally:
        core.stop()


@pytest.mark.slow
def test_engine_decode_window_reports_live_throughput():
    """The rolling window reports a live tok/s while decoding (the
    gauge the megatick refactor will be judged against)."""
    from vgate_tpu.backends.base import SamplingParams
    from vgate_tpu.runtime.engine_core import EngineCore

    core = EngineCore(_engine_config())
    core.start()
    try:
        core.generate(
            ["throughput probe"],
            [SamplingParams(
                max_tokens=24, min_tokens=24, temperature=0.0
            )],
        )
        win = core.perf_snapshot()["window"]
        assert win["tokens"] >= 24
        assert win["tokens_per_s"] > 0
        assert win["decode_steps"] > 0
    finally:
        core.stop()


# ------------------------------ ISSUE 24: one bracket, two sinks


@pytest.mark.parametrize("span, phase", sorted(perf_mod.SPAN_PHASE.items()))
def test_bracket_accrues_exactly_what_phase_did(span, phase):
    """The bracket on the recorder's clock books the same seconds into
    the same phase as the old ``phase(name, seconds)`` call."""
    clock = FakeClock()
    old, new = recorder(clock=clock), recorder(clock=clock)
    old.tick_begin()
    new.tick_begin()
    with new.span(span) as bracket:
        clock.advance(0.0125)
    old.phase(phase, 0.0125)
    clock.advance(0.004)
    old.tick_end(worked=True)
    new.tick_end(worked=True)
    assert bracket.seconds == pytest.approx(0.0125)
    assert new.totals()["phase_seconds"] == pytest.approx(
        old.totals()["phase_seconds"]
    )
    assert new.totals()["phase_seconds"][phase] == pytest.approx(0.0125)
    assert new.totals()["wall_s"] == pytest.approx(0.0165)


def test_old_phases_plus_schedule_and_state_sum_to_the_tick_wall():
    clock = FakeClock()
    rec = recorder(clock=clock)
    rec.tick_begin()
    for span, seconds in (
        ("schedule", 0.002), ("state", 0.003), ("decode_dispatch", 0.010),
        ("prefill_dispatch", 0.005), ("device_wait", 0.030),
        ("readback", 0.004), ("emit", 0.006),
    ):
        with rec.span(span):
            clock.advance(seconds)
    clock.advance(0.040)  # what no bracket covers
    rec.tick_end(worked=True)
    phases = rec.totals()["phase_seconds"]
    assert set(phases) == set(PHASES)
    assert phases["dispatch"] == pytest.approx(0.015)
    assert phases["schedule"] == pytest.approx(0.002)
    assert phases["state"] == pytest.approx(0.003)
    assert phases["host"] == pytest.approx(0.040)
    assert sum(phases.values()) == pytest.approx(rec.totals()["wall_s"])
    # idle_wait annotates only: outside a tick it books nothing
    with rec.span("idle_wait"):
        clock.advance(1.0)
    assert rec.totals()["wall_s"] == pytest.approx(0.100)


def test_an_idle_poll_with_a_schedule_bracket_is_still_idle():
    clock = FakeClock()
    rec = recorder(clock=clock)
    rec.tick_begin()
    with rec.span("schedule"):
        clock.advance(0.0001)
    rec.tick_end(worked=False)
    assert rec.totals()["ticks"] == 0 and rec.totals()["idle_ticks"] == 1


def _numbers(totals, prefix=""):
    out = {}
    for key, value in totals.items():
        if isinstance(value, dict):
            out.update(_numbers(value, f"{prefix}{key}."))
        elif isinstance(value, (int, float)):
            out[prefix + key] = value
    return out


NEW_TOTALS = (
    "chunks_by_steps.1", "chunks_by_steps.8", "decode_device_s",
    "decode_ctx_token_steps", "engine_cpu_s",
    "engine_cpu_in_wait_s", "admitted", "queue_wait_s", "first_tokens",
    "prefill_s",
)


def test_new_totals_are_monotone_and_survive_the_dp_merge(monkeypatch):
    from vgate_tpu.observability.flight import FlightRecorder

    monkeypatch.setattr(perf_mod, "BOOT_SECONDS", {"weights": 3.0})
    clock = FakeClock()
    rec = recorder(clock=clock)
    flight = FlightRecorder(ObservabilityConfig())
    rec.request_totals = flight.phase_totals

    class Seq:
        seq_id, request_id, trace, preempt_count = 1, "r", None, 0
        num_prompt_tokens = 5
        arrival_t = time.perf_counter() - 0.25

        class params:
            timeout_s = None

    snaps = [rec.snapshot()]
    for steps in (8, 1, 8):
        rec.tick_begin()
        with rec.span("device_wait") as wait:
            clock.advance(0.010)
        with rec.span("readback"):
            clock.advance(0.001)
        rec.note_decode(
            steps=steps, ctx_tokens=1000, device_s=wait.seconds
        )
        clock.advance(0.002)
        rec.tick_end(worked=True)
        if steps == 1:
            flight.on_admit(Seq, bucket=8)
            flight.on_first_token(Seq)
        snaps.append(rec.snapshot())
    series = [_numbers(s["totals"]) for s in snaps]
    for key in NEW_TOTALS:
        # a chunk length appears with its first chunk
        values = [s.get(key, 0) for s in series]
        assert values == sorted(values), key
    last = series[-1]
    assert last["chunks_by_steps.8"] == 2 and last["chunks_by_steps.1"] == 1
    assert last["decode_device_s"] == pytest.approx(0.030)
    assert last["decode_ctx_token_steps"] == 17 * 1000
    assert last["admitted"] == 1 and last["first_tokens"] == 1
    assert last["queue_wait_s"] == pytest.approx(0.25, abs=0.05)
    assert 0 <= last["engine_cpu_in_wait_s"] <= last["engine_cpu_s"]
    assert snaps[-1]["totals"]["boot_seconds"] == {"weights": 3.0}

    merged = perf_mod.merge_snapshots([snaps[-1], snaps[-1]])["totals"]
    both = _numbers(merged)
    for key in NEW_TOTALS:
        assert both[key] == pytest.approx(2 * last[key]), key
    assert merged["boot_seconds"] == {"weights": 3.0}  # one process


def test_the_expert_layers_counters_are_booked_with_their_overflow():
    """Five device counters a decode step (ops/moe.py STAT_NAMES), one
    readback a chunk: the first three and the overflow add up over the
    steps, the largest load is summed as ``load_max_sum``."""
    import numpy as np

    from vgate_tpu.ops.moe import STAT_NAMES, combine_stats

    assert STAT_NAMES[-1] == "overflow" and len(STAT_NAMES) == 5
    layers = np.array([[16, 4, 2, 3, 0], [16, 9, 3, 5, 2]], np.int32)
    assert combine_stats(layers).tolist() == [32, 13, 5, 5, 2]
    rec = recorder()
    rec.note_moe(np.array([[32, 13, 5, 5, 2], [32, 6, 4, 2, 0]]), rows=4,
                 moe_layers=2, linear_layers=0)
    rec.note_moe(np.array([[32, 8, 4, 3, 1]]), rows=4, moe_layers=2,
                 linear_layers=0)
    assert rec.totals()["moe"] == {
        "assignments": 96, "held_assignments": 27, "experts_hit": 13,
        "load_max_sum": 10, "overflow": 3, "layer_steps": 6, "steps": 3}


@pytest.mark.parametrize("model_id, bucket, lens, whole, worked", [
    # a long whole prompt of a stack of several kinds: the blocks of
    # 1,024 rows up to the longest prompt, in every row of the program
    ("tiny-swa-moe", 8192, [5500], True, 6 * 1024),
    ("tiny-mla-moe", 8192, [4097, 1], True, 2 * 5 * 1024),
    ("tiny-dsa-moe", 16384, [8193], True, 9 * 1024),
    ("tiny-swa-moe", 2048, [1024], True, 1024),
    # a whole group of a stack of one kind (models/decoder.py's own
    # pass): the blocks its real rows fill, end to end
    ("tiny-dense", 2048, [1820, 1025], True, 3 * 1024),
    ("tiny-dense", 2048, [1280, 1290, 1500, 1025, 1100, 1, 1, 1], True,
     7 * 1024),
    ("tiny-dense", 2048, [1024, 1024], True, 2 * 1024),
    ("tiny-dense", 2048, [1024, 1025], True, 3 * 1024),
    ("tiny-dense", 256, [250, 130, 256, 1, 200, 90, 1, 1], True, 1024),
    # the loop does not engage: under two blocks, a suffix or a chunk of
    # a prompt, rows sharded or relayed (``whole`` False)
    ("tiny-swa-moe", 1024, [300, 900, 7, 1], True, 4 * 1024),
    ("tiny-mla-moe", 8192, [5500], False, 8192),
    ("tiny-dense", 1024, [1000], True, 1024),
    ("tiny-dense", 128, [100] * 8, True, 8 * 128),
    ("tiny-dense", 2048, [1820, 1025], False, 2 * 2048),
])
def test_prefill_rows_are_counted_by_the_models_own_rule(
        model_id, bucket, lens, whole, worked):
    """``totals.prefill``: what ``engine.prefill_pad_share.tok`` reads.
    The engine books a prompt program's rows through ``prompt_rows``,
    the function the program's own loop takes its trips from: on the
    host's ints, on traced lengths, and by the rows the loop itself
    (``_by_row_blocks``) touches."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from vgate_tpu.models import hybrid
    from vgate_tpu.models.specs import spec_for_model_id

    spec = spec_for_model_id(model_id)
    arrays, rows = hybrid.prompt_rows(
        spec, bucket, np.asarray(lens, np.int32), whole)
    got = arrays * int(rows)
    assert got == worked

    # the same rule on traced lengths gives the loop its trips: the
    # rows of each array that the loop's blocks reach
    @jax.jit
    def touched(lens):
        arrays, n_rows = hybrid.prompt_rows(spec, bucket, lens, whole)
        ones = jnp.zeros((arrays, len(lens) * bucket // arrays, 1))
        return jnp.sum(hybrid._by_row_blocks(
            lambda r: r + 1, (ones,),
            None if isinstance(n_rows, int) else n_rows))

    assert int(touched(jnp.asarray(lens, jnp.int32))) == worked
    rec = recorder()
    assert rec.totals()["prefill"] == {
        "rows_worked": 0, "rows_real": 0, "rows_padding": 0}
    real = sum(n for n in lens if n > 1)  # a padding row holds one
    rec.note_prefill_rows(got, real)
    rec.note_prefill_rows(got, real)
    assert rec.totals()["prefill"] == {
        "rows_worked": 2 * worked, "rows_real": 2 * real,
        "rows_padding": 2 * (worked - real)}
    merged = perf_mod.merge_snapshots([rec.snapshot(), rec.snapshot()])
    assert merged["totals"]["prefill"]["rows_padding"] == 4 * (worked - real)


def test_the_prompt_kernels_tiles_add_up_and_merge():
    """``totals.prefill_attn``: a program's tiles and those of them the
    kernel runs without position tests, there from the start (a
    ``decode-heavy`` cell reads 0 interior tiles, not a missing key),
    summed over the replicas."""
    rec = recorder()
    assert rec.totals()["prefill_attn"] == {
        "programs": 0, "tiles": 0, "interior_tiles": 0}
    rec.note_prefill_attn(66 * 5, 55 * 5)
    rec.note_prefill_attn(8, 0)
    assert rec.totals()["prefill_attn"] == {
        "programs": 2, "tiles": 338, "interior_tiles": 275}
    merged = perf_mod.merge_snapshots([rec.snapshot(), rec.snapshot()])
    assert merged["totals"]["prefill_attn"] == {
        "programs": 4, "tiles": 676, "interior_tiles": 550}


def test_chunk_lengths_are_whatever_the_engine_ran():
    """No fixed ladder: tpu.decode_chunk is configurable, so a 16-step
    chunk counts; a spec-verify pass is steps, not a chunk; the dp merge
    runs over the union of the replicas' lengths."""
    rec = recorder()
    rec.tick_begin()
    rec.note_decode(steps=16, ctx_tokens=10, device_s=0.01)
    rec.note_decode(steps=16, ctx_tokens=10, device_s=0.01)
    rec.note_decode(steps=1, ctx_tokens=10, device_s=0.01, chunk=False)
    rec.tick_end(worked=True)
    totals = rec.snapshot()["totals"]
    assert totals["chunks_by_steps"] == {"16": 2}
    assert totals["decode_steps"] == 33
    assert totals["decode_ctx_token_steps"] == 330
    other = recorder()
    other.tick_begin()
    other.note_decode(steps=4, ctx_tokens=10, device_s=0.01)
    other.note_decode(steps=16, ctx_tokens=10, device_s=0.01)
    other.tick_end(worked=True)
    merged = perf_mod.merge_snapshots([rec.snapshot(), other.snapshot()])
    assert merged["totals"]["chunks_by_steps"] == {"4": 1, "16": 3}


def test_fused_head_steps_count_beside_the_decode_steps_and_merge():
    """``totals.decode_steps_fused_head``: the steps of the chunks whose
    program kept its logits on the chip, booked with ``decode_steps`` at
    the tick's end (a scrape never sees one without the other); a
    verify round or a chunk that needs the logits adds none."""
    rec = recorder()
    assert rec.snapshot()["totals"]["decode_steps_fused_head"] == 0
    rec.tick_begin()
    rec.note_decode(steps=8, ctx_tokens=10, device_s=0.01, fused_head=True)
    rec.note_decode(steps=8, ctx_tokens=10, device_s=0.01)
    rec.note_decode(steps=4, ctx_tokens=10, device_s=0.01, chunk=False)
    mid = rec.totals()
    assert mid["decode_steps"] == mid["decode_steps_fused_head"] == 0
    rec.tick_end(worked=True)
    totals = rec.snapshot()["totals"]
    assert totals["decode_steps"] == 20
    assert totals["decode_steps_fused_head"] == 8
    merged = perf_mod.merge_snapshots([rec.snapshot(), rec.snapshot()])
    assert merged["totals"]["decode_steps_fused_head"] == 16
    # a replica of an older build carries no such key: it counts 0
    old = rec.snapshot()
    del old["totals"]["decode_steps_fused_head"]
    merged = perf_mod.merge_snapshots([rec.snapshot(), old])
    assert merged["totals"]["decode_steps_fused_head"] == 8


def test_membership_changes_and_drains_are_counted_and_merge():
    """``totals.membership_changes`` counts the ticks that found the
    decode batch's membership changed and ``totals.pipeline_drains`` those
    of them that drained the pipeline; both are monotone counters that
    add across dp replicas."""
    rec = recorder()
    for drained in (True, False, False, False, True):
        rec.note_membership_change(drained=drained)
    totals = rec.snapshot()["totals"]
    assert totals["membership_changes"] == 5
    assert totals["pipeline_drains"] == 2
    other = recorder()
    other.note_membership_change(drained=False)
    merged = perf_mod.merge_snapshots([rec.snapshot(), other.snapshot()])
    assert merged["totals"]["membership_changes"] == 6
    assert merged["totals"]["pipeline_drains"] == 2


@pytest.mark.parametrize(
    "name", ["engine.drain_share.tok", "engine.drain_share.tpot"]
)
def test_drain_share_is_read_from_the_two_counters(name):
    """The benchmark's ``engine.drain_share.*`` (data files only): drains
    over membership changes between the window's two ends, in percent,
    and nothing (not an error) from a program without the counters."""
    from perfbench import manifest

    spec = manifest.metric(name)
    assert spec["reducer"] == "perf_ratio"
    assert spec["layer"] == "engine tick" and spec["better"] == "lower"
    reduce = manifest.reducer(spec["reducer"])

    def scrape(changes, drains):
        rec = recorder()
        for i in range(changes):
            rec.note_membership_change(drained=i < drains)
        return {"totals": rec.totals()}

    ctx = {"perf": {"open": scrape(10, 1), "close": scrape(210, 4)}}
    assert reduce(ctx, **spec["args"]) == pytest.approx(1.5)
    parent = {"totals": {"ticks": 3}}
    ctx = {"perf": {"open": parent, "close": parent}}
    assert reduce(ctx, **spec["args"]) is None
    by_name = {m["name"]: m for m in manifest.benchmark()["per_layer"]}
    cells = by_name[name]["workloads"]
    if name.endswith(".tpot"):
        assert cells == ["qwen2.5-1.5b.chat"]
    else:
        assert len(cells) == 9 and "qwen2.5-1.5b.chat" not in cells


def test_host_overhead_ratio_counts_schedule_and_state_as_host():
    """The gauge behind VgtHostOverheadHigh keeps the meaning it had
    before schedule/state got brackets of their own: the engine's own
    Python outside the jitted call and the device, over the wall."""
    clock = FakeClock()
    bracketed = recorder(clock=clock)
    plain = recorder(clock=clock)  # the parent: the same work unbracketed
    for rec, spans in ((bracketed, True), (plain, False)):
        rec.tick_begin()
        for name, seconds in (("schedule", 0.020), ("state", 0.010)):
            if spans:
                with rec.span(name):
                    clock.advance(seconds)
            else:
                clock.advance(seconds)
        with rec.span("decode_dispatch"):
            clock.advance(0.010)
        with rec.span("device_wait"):
            clock.advance(0.050)
        clock.advance(0.010)
        rec.tick_end(worked=True)
    assert bracketed.totals()["phase_seconds"]["host"] == pytest.approx(0.010)
    assert plain.totals()["phase_seconds"]["host"] == pytest.approx(0.040)
    ratio = bracketed.window()["host_overhead_ratio"]
    assert ratio == pytest.approx(0.4)
    assert ratio == plain.window()["host_overhead_ratio"]
    assert bracketed.get_stats()["host_overhead_ratio"] == ratio


def test_no_annotation_is_built_without_a_capture(monkeypatch):
    """Off = one flag test: nothing is constructed, the span arguments
    are never evaluated; on, every bracket lands in the trace."""
    opened = []

    class Ann:
        def __exit__(self, *exc):
            opened.append("closed")

        def set_metadata(self, **args):
            opened.append(args)

    def fake_open(name, args):
        opened.append((name, args() if args is not None else {}))
        return Ann()

    monkeypatch.setattr(perf_mod, "_open_annotation", fake_open)
    boom = lambda: (_ for _ in ()).throw(AssertionError("args evaluated"))
    rec = recorder()
    gateway = perf_mod.GatewayPerf(clock=FakeClock())
    assert perf_mod.capturing() is False
    rec.tick_begin()
    with rec.span("decode_dispatch", boom) as b:
        b.note(tokens=3)
    rec.tick_end(worked=True)
    gateway.ingress_begin()
    gateway.ingress_end()
    stream = gateway.stream_clock()
    gateway.detok_end(stream, gateway.detok_begin(stream), 1)
    gateway.write_end(gateway.write_begin())
    assert opened == []

    perf_mod.set_capturing(True)
    try:
        rec.tick_begin()
        with rec.span("emit", lambda: {"rows": 2}) as b:
            b.note(tokens=3)
        rec.tick_end(worked=True)
        gateway.ingress_begin()
        gateway.ingress_end()
        stream = gateway.stream_clock()
        gateway.detok_end(stream, gateway.detok_begin(stream), 2)
        gateway.write_end(gateway.write_begin())
    finally:
        perf_mod.set_capturing(False)
    names = [o[0] for o in opened if isinstance(o, tuple)]
    assert names == ["vgt.engine.tick", "vgt.engine.emit",
                     "vgt.gateway.ingress", "vgt.gateway.stream_detok",
                     "vgt.gateway.sse_write"]
    assert ("vgt.engine.emit", {"rows": 2}) in opened
    assert {"tokens": 3} in opened
    assert opened.count("closed") == 5


def test_thread_time_is_read_per_tick_and_per_wait_never_per_token():
    """Acceptance: no per-token thread_time call exists."""
    import inspect

    from vgate_tpu.backends import jax_backend
    from vgate_tpu.runtime import engine_core, sequence

    for module in (engine_core, jax_backend, sequence):
        assert "thread_time" not in inspect.getsource(module)
    source = inspect.getsource(perf_mod)
    # tick x2, wait x2, and one a decode readback (note_delivery)
    assert source.count("time.thread_time()") == 5


def test_gateway_counters_follow_one_stream():
    clock = FakeClock()
    gateway = perf_mod.GatewayPerf(clock=clock)
    gateway.ingress_begin()
    clock.advance(0.020)
    stream = gateway.stream_clock()
    gateway.ingress_end()
    gateway.ingress_end()  # closing twice counts once
    def delivery(n_tokens, detok_s=0.0001, write_s=0.001, write=True):
        """One delivery of ``n_tokens`` through detokenisation and,
        unless its text is held back, its SSE write."""
        t0 = gateway.detok_begin(stream)
        clock.advance(detok_s)
        gateway.detok_end(stream, t0, n_tokens)
        if write:
            t1 = gateway.write_begin()
            clock.advance(write_s)
            gateway.write_end(t1)

    stream.t_first_token = clock()  # the engine thread's stamp
    clock.advance(0.004)
    # EVERY delivery is timed, whatever it carries; a held-back one's
    # tokens ride in the next write
    delivery(1)
    delivery(8)
    delivery(3, write=False)
    delivery(5)
    armed = gateway.handoff_armed()
    clock.advance(0.012)  # the loop was busy: the wake-up waited
    gateway.note_handoff(armed)
    totals = gateway.totals()
    assert totals["ingress_n"] == 1
    assert totals["ingress_s"] == pytest.approx(0.020)
    assert totals["stream_tokens"] == 17  # tokens, not deliveries
    assert totals["stream_detok_s"] == pytest.approx(0.0004)
    assert totals["stream_write_s"] == pytest.approx(0.003)
    assert totals["stream_deliveries"] == 3  # content writes
    assert totals["stream_tokens_delivered"] == 17
    assert totals["stream_handoffs"] == 1
    assert totals["handoff_wait_s"] == pytest.approx(0.012)
    assert totals["handoff_waits"]["16"] == 1
    assert sum(totals["handoff_waits"].values()) == 1
    assert totals["first_chunk_n"] == 1  # the first chunk on the wire
    assert totals["first_chunk_s"] == pytest.approx(0.0051)
    gateway.ingress_close()
    assert gateway.stream_clock() is None
    off = perf_mod.GatewayPerf(clock=clock)
    off.enabled = False
    off.ingress_begin()  # observability off: the stream gets no clock
    off.ingress_end()
    assert off.stream_clock() is None
    assert off.detok_begin(off.stream_clock()) is None
    assert off.write_begin() is None
    assert off.handoff_armed() is None  # no stamp, nothing booked
    off.note_handoff(off.handoff_armed())
    waits = off.totals().pop("handoff_waits")
    assert set(waits.values()) == {0}
    assert {
        v for k, v in off.totals().items() if k != "handoff_waits"
    } == {0, 0.0}


def test_first_chunk_is_timed_when_the_first_token_writes_nothing():
    """A stop-string hold-back (or half a UTF-8 piece) yields no text
    for the stream's first deliveries: first_chunk_s ends at the first
    write, and that write's delivery counts the tokens held until it."""
    clock = FakeClock()
    gateway = perf_mod.GatewayPerf(clock=clock)
    gateway.ingress_begin()
    stream = gateway.stream_clock()
    gateway.ingress_end()
    stream.t_first_token = clock()
    for _ in range(2):  # decoded, held back: no write
        t0 = gateway.detok_begin(stream)
        clock.advance(0.001)
        gateway.detok_end(stream, t0, 1)
        assert gateway.totals()["first_chunk_n"] == 0
        assert gateway.totals()["stream_deliveries"] == 0
    t0 = gateway.detok_begin(stream)
    clock.advance(0.001)
    gateway.detok_end(stream, t0, 4)
    t1 = gateway.write_begin()
    clock.advance(0.002)
    gateway.write_end(t1)
    totals = gateway.totals()
    assert totals["first_chunk_n"] == 1
    assert totals["first_chunk_s"] == pytest.approx(0.005)
    assert totals["stream_deliveries"] == 1
    assert totals["stream_tokens_delivered"] == 6
    assert totals["stream_tokens"] == 6


def test_a_request_that_is_not_streamed_leaves_no_annotation_open(
    monkeypatch,
):
    """ingress_begin runs for every chat request, ingress_end only in
    stream_async: the handler's finally closes what is still open."""
    events = []

    class Ann:
        def __init__(self, name):
            self.name = name

        def __exit__(self, *exc):
            events.append(("close", self.name))

    def fake_open(name, args):
        events.append(("open", name))
        return Ann(name)

    monkeypatch.setattr(perf_mod, "_open_annotation", fake_open)
    gateway = perf_mod.GatewayPerf(clock=FakeClock())
    perf_mod.set_capturing(True)
    try:
        gateway.ingress_begin()  # a non-stream or rejected (422) request
        gateway.ingress_close()
        assert events == [("open", "vgt.gateway.ingress"),
                          ("close", "vgt.gateway.ingress")]
        assert gateway.stream_clock() is None
        assert gateway.totals()["ingress_n"] == 0
        gateway.ingress_close()  # nothing open: nothing to do
        # a write cut by a cancel is closed on the way out too
        del events[:]
        gateway.ingress_begin()
        gateway.ingress_end()
        stream = gateway.stream_clock()
        gateway.detok_end(stream, gateway.detok_begin(stream), 1)
        gateway.write_begin()
        gateway.ingress_close()
    finally:
        perf_mod.set_capturing(False)
    assert [e for e in events if e[0] == "open"] == [
        ("open", "vgt.gateway.ingress"),
        ("open", "vgt.gateway.stream_detok"),
        ("open", "vgt.gateway.sse_write"),
    ]
    assert len([e for e in events if e[0] == "close"]) == 3


async def test_the_chat_handler_closes_its_ingress_annotation(monkeypatch):
    """Through the real handler, while a capture runs: a non-streamed
    chat and a rejected one (422) each open vgt.gateway.ingress at
    entry and leave it closed."""
    from vgate_tpu.server import app as app_mod

    events = []

    class Ann:
        def __init__(self, log):
            self.log = log

        def __exit__(self, *exc):
            self.log.append("close")

    def fake_open(name, args):
        # the app's collector clock annotates too (vgt.host.gc),
        # whenever a collection falls inside the capture
        log = events if name.startswith("vgt.gateway.") else []
        log.append(name)
        return Ann(log)

    monkeypatch.setattr(perf_mod, "_open_annotation", fake_open)
    monkeypatch.setattr(app_mod.GATEWAY, "enabled", True)
    client = await _client()
    perf_mod.set_capturing(True)
    try:
        ok = await client.post("/v1/chat/completions", json={
            "model": "m", "max_tokens": 4,
            "messages": [{"role": "user", "content": "hi"}],
        })
        assert ok.status == 200
        bad = await client.post(
            "/v1/chat/completions", json={"messages": []}
        )
        assert bad.status == 422
    finally:
        perf_mod.set_capturing(False)
        await client.close()
    assert events == ["vgt.gateway.ingress", "close"] * 2


def _trace_names(trace_dir):
    import glob

    from jax.profiler import ProfileData

    path = max(
        glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                  recursive=True),
        key=os.path.getmtime,
    )
    lines = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            lines.append([ev.name for ev in line.events])
    return lines


def test_capture_profile_is_quiet_by_default_and_frames_on_request(tmp_path):
    """A default capture holds the engine's tick and its children and
    not one Python frame; ``python_tracer`` brings the frames back."""
    import re
    import threading

    from vgate_tpu.backends.base import SamplingParams
    from vgate_tpu.runtime.engine_core import EngineCore

    frame = re.compile(r"\.py:\d+")
    core = EngineCore(_engine_config())
    core.start()
    try:
        params = [SamplingParams(max_tokens=24, min_tokens=24,
                                 temperature=0.0)]
        # same length, different first page: every probe runs the
        # programs this one compiles, and none hits the prefix cache
        core.generate(["0 probe"], params)
        results = {}

        def load(prompt):
            time.sleep(0.15)  # let the capture begin first
            core.generate([prompt], params)

        for i, (key, tracer) in enumerate(
            (("quiet", False), ("frames", True)), start=1
        ):
            thread = threading.Thread(target=load, args=(f"{i} probe",))
            thread.start()
            results[key] = core.capture_profile(
                0.6, str(tmp_path / key), python_tracer=tracer
            )
            thread.join()
        assert perf_mod.capturing() is False
        quiet = results["quiet"]
        assert quiet["python_tracer"] is False and quiet["files"] >= 1
        assert quiet["file_bytes"] > 0 and quiet["stop_s"] >= 0
        lines = _trace_names(quiet["trace_dir"])
        engine = max(lines, key=lambda n: n.count("vgt.engine.tick"))
        assert engine.count("vgt.engine.tick") >= 2
        for child in ("schedule", "decode_dispatch", "device_wait",
                      "readback", "emit"):
            assert f"vgt.engine.{child}" in engine, child
        assert not [n for names in lines for n in names if frame.search(n)]
        frames = _trace_names(results["frames"]["trace_dir"])
        assert results["frames"]["python_tracer"] is True
        assert [n for names in frames for n in names if frame.search(n)]
        assert any("vgt.engine.tick" in names for names in frames)
        assert (results["frames"]["file_bytes"]
                > results["quiet"]["file_bytes"])
    finally:
        core.stop()


# ------------------------------- delivery gaps and pauses (ISSUE 35)


class FakeCpu:
    """``time.thread_time`` under the test's hand: the engine thread is
    on the CPU exactly when the test says so."""

    def __init__(self):
        self.t = 5.0

    def __call__(self):
        return self.t


@pytest.fixture
def cpu(monkeypatch):
    fake = FakeCpu()
    monkeypatch.setattr(perf_mod.time, "thread_time", fake)
    return fake


@pytest.fixture
def gc_clock(monkeypatch):
    """A collector's clock of the test's own in the module's place (the
    process's real one must not tick into a constructed pause)."""
    fake = perf_mod.GcClock(clock=FakeClock())
    monkeypatch.setattr(perf_mod, "GC", fake)
    return fake


def deliver(rec, clock, cpu=None, gap=0.1, on_cpu=0.0, device=0.0,
            readback=0.0, schedule=0.0, dispatch=0.0, new_tick=False,
            before=None, **kw):
    """One decode readback ``gap`` seconds after the last: so much of it
    in each bracket, ``on_cpu`` of the rest with the thread running,
    what is left in no bracket at all.  ``new_tick`` closes the open
    tick first, so that the gap straddles a tick boundary."""
    if new_tick:
        rec.tick_end(worked=True)
        rec.tick_begin()
    if before is not None:
        before()
    rest = gap - device - readback - schedule - dispatch
    assert rest >= 0
    for span, seconds in (("schedule", schedule),
                          ("decode_dispatch", dispatch),
                          ("device_wait", device), ("readback", readback)):
        if seconds:
            with rec.span(span):
                clock.advance(seconds)
                if cpu is not None and span in ("schedule",
                                                "decode_dispatch"):
                    cpu.t += seconds  # the engine's Python: on the CPU
    clock.advance(rest)
    if cpu is not None:
        cpu.t += on_cpu
    return rec.note_delivery(kw.pop("steps", 8), kw.pop("rows", 4), **kw)


def test_gaps_land_in_their_buckets_and_an_idle_engine_makes_none(
    cpu, gc_clock
):
    clock = FakeClock()
    rec = recorder(clock=clock)
    assert perf_mod.GAP_KEYS[0] == "8" and perf_mod.GAP_KEYS[-2:] == (
        "8192", "inf")
    assert len(perf_mod.GAP_KEYS) == 22
    for a, b in zip(perf_mod.GAP_EDGES_S, perf_mod.GAP_EDGES_S[1:]):
        assert b / a == pytest.approx(2 ** 0.5)
    rec.tick_begin()
    assert deliver(rec, clock, cpu) is None  # the first: no gap yet
    assert rec.totals()["deliveries"] == 0
    for gap in (0.004, 0.0079, 0.0081, 0.1, 0.3, 0.3, 9.0):
        deliver(rec, clock, cpu, gap=gap, device=gap)
    totals = rec.totals()
    gaps = {k: n for k, n in totals["delivery_gaps"].items() if n}
    assert gaps == {"8": 2, "11.31": 1, "128": 1, "362.04": 2, "inf": 1}
    assert totals["deliveries"] == 7 == sum(gaps.values())
    assert totals["delivery_gap_s"] == pytest.approx(9.72)
    # an edge is its bucket's upper end; past the last, the overflow
    hist = perf_mod.GapHistogram()
    for seconds in (0.0, 0.008, 8.192, 8.1921):
        hist.add(seconds)
    assert {k: n for k, n in hist.to_dict().items() if n} == {
        "8": 2, "8192": 1, "inf": 1}
    # a tick that finds no stream running clears the clock: the hour
    # until the next delivery is nobody's silence
    rec.clear_delivery_clock()
    assert deliver(rec, clock, cpu, gap=3600.0) is None
    assert rec.totals()["deliveries"] == 7
    deliver(rec, clock, cpu, gap=0.05, device=0.05)
    assert rec.totals()["deliveries"] == 8
    # a membership change does not clear it
    rec.note_membership_change(drained=True, reason="preempt")
    deliver(rec, clock, cpu, gap=0.05, device=0.05)
    assert rec.totals()["deliveries"] == 9


def test_a_pause_differences_its_clocks_across_a_tick_boundary(
    cpu, gc_clock
):
    clock = FakeClock()
    rec = recorder(clock=clock)
    rec.tick_begin()
    with rec.span("schedule"):
        clock.advance(0.25)  # before the first delivery: not the gap's
    deliver(rec, clock, cpu)
    with rec.span("emit"):
        clock.advance(0.05)  # the closing tick's part of the gap
    cpu.t += 0.05

    def dispatched():
        rec.count(decode_steps=8)
        rec.count(prompt_programs=2, prompt_tokens=300, swap_ins=1)
        rec.note_decode(steps=8, ctx_tokens=10, device_s=0.1)
        rec.note_membership_change(drained=False)
        rec.note_membership_change(drained=True, reason="preempt")

    pause = deliver(
        rec, clock, cpu, gap=0.95, schedule=0.6, device=0.2,
        readback=0.01, on_cpu=0.09, new_tick=True, before=dispatched,
        queue_depth=7, preemptions=3, steps=4, rows=2,
    )
    assert pause["gap_s"] == pytest.approx(1.0)
    assert pause["phases"] == pytest.approx({
        "host": 0.14, "schedule": 0.6, "state": 0.0, "dispatch": 0.0,
        "device": 0.2, "readback": 0.01, "detok": 0.05,
    })
    assert sum(pause["phases"].values()) == pytest.approx(1.0)
    assert pause["decode_device_s"] == pytest.approx(0.1)
    assert pause["first_token_wait_s"] == pytest.approx(0.1)
    assert pause["cpu_s"] == pytest.approx(0.74)
    assert pause["off_cpu_s"] == pytest.approx(0.05)
    assert (pause["prompt_programs"], pause["prompt_tokens"]) == (2, 300)
    assert (pause["decode_steps"], pause["swap_ins"]) == (8, 1)
    assert pause["membership_changes"] == 2
    assert pause["drains"] == ["preempt"]
    assert pause["preemptions"] == 3 and pause["queue_depth"] == 7
    assert (pause["steps"], pause["rows"]) == (4, 2)
    assert pause["cause"] == "host"  # 0.6 s of its own scheduling
    assert rec.snapshot()["pauses"] == [pause]
    # the next gap starts from this delivery's clocks, not from zero
    quiet = deliver(rec, clock, cpu, gap=0.6, device=0.6)
    assert quiet["prompt_programs"] == 0 and quiet["drains"] == []
    assert quiet["phases"]["schedule"] == 0.0


def _pause_of(cause, rec, clock, cpu, gc_clock):
    """A one-second pause built to have ``cause``."""
    if cause == "compile":
        with rec.span("decode_dispatch") as disp:
            clock.advance(0.6)
        cpu.t += 0.6
        rec.record_compile("decode", (8,), disp.seconds, "chunk_variant")
        return deliver(rec, clock, cpu, gap=0.4, device=0.4)
    if cause == "prefill":
        # a wave's programs, the wait for their first tokens: even with
        # a collection and lost CPU beside them, the wave comes first
        return deliver(
            rec, clock, cpu, gap=1.0, device=0.4, dispatch=0.15,
            before=lambda: rec.count(prompt_programs=3, prompt_tokens=900),
        )
    if cause == "gc":
        # a collection on ANOTHER thread holds the GIL: the engine
        # thread is off the CPU for as long
        def collect():
            gc_clock._on_gc("start", {"generation": 2})
            gc_clock._clock.advance(0.7)
            gc_clock._on_gc("stop", {"generation": 2})

        def elsewhere():
            import threading

            thread = threading.Thread(target=collect)
            thread.start()
            thread.join()
        pause = deliver(rec, clock, cpu, gap=1.0, on_cpu=0.3,
                        before=elsewhere)
        assert pause["gc_s"] == 0.0
        assert pause["gc_other_threads_s"] == pytest.approx(0.7)
        return pause
    if cause == "off_cpu":
        return deliver(rec, clock, cpu, gap=1.0, on_cpu=0.2, device=0.1)
    if cause == "device":
        return deliver(rec, clock, cpu, gap=1.0, device=0.9, on_cpu=0.1)
    return deliver(rec, clock, cpu, gap=1.0, device=0.3, on_cpu=0.7)


@pytest.mark.parametrize("cause", perf_mod.PAUSE_CAUSES)
def test_each_cause_is_reached_and_a_pause_has_exactly_one(
    cause, cpu, gc_clock
):
    clock = FakeClock()
    rec = recorder(clock=clock)
    rec.tick_begin()
    deliver(rec, clock, cpu)
    pause = _pause_of(cause, rec, clock, cpu, gc_clock)
    assert pause["cause"] == cause
    pauses = rec.totals()["pauses"]
    assert pauses[cause + "_n"] == 1
    assert pauses[cause + "_s"] == pytest.approx(1.0)
    assert sum(v for k, v in pauses.items() if k.endswith("_n")) == 1


def test_a_wave_too_small_for_the_wait_is_the_devices_pause(cpu, gc_clock):
    """What the chip showed (PR 35): 2.9 s of waiting for the first
    token of ONE 182-token prompt program.  Once the recorder knows
    what a prompt token costs, such a wave does not explain its gap."""
    clock = FakeClock()
    rec = recorder(clock=clock)
    rec.tick_begin()
    deliver(rec, clock, cpu)
    wave = lambda n: (lambda: rec.count(prompt_programs=1, prompt_tokens=n))
    for _ in range(10):  # 0.5 ms of waiting a prompt token, so far
        deliver(rec, clock, cpu, gap=0.14, device=0.1, before=wave(200))
    stood_still = deliver(
        rec, clock, cpu, gap=2.9, device=2.85, before=wave(182))
    assert stood_still["wave_at_pace_s"] == pytest.approx(0.091)
    assert stood_still["first_token_wait_s"] == pytest.approx(2.85)
    assert stood_still["cause"] == "device"
    # the same wait behind a wave that large is the wave's
    big = deliver(rec, clock, cpu, gap=2.9, device=2.85, before=wave(4000))
    assert big["cause"] == "prefill"
    # within the slack it is still the wave's: the pace is a mean
    slow = deliver(rec, clock, cpu, gap=0.7, device=0.6, before=wave(182))
    assert slow["cause"] == "prefill"
    # decode chunks' own waits are not the waves' pace
    pace = slow["wave_at_pace_s"]
    deliver(rec, clock, cpu, gap=50.0, device=50.0, before=lambda:
            rec.note_decode(steps=8, ctx_tokens=10, device_s=50.0))
    again = deliver(rec, clock, cpu, gap=2.9, device=2.85, before=wave(182))
    assert again["cause"] == "device"
    assert again["wave_at_pace_s"] == pytest.approx(pace, rel=0.1)


def test_a_collection_on_the_engine_thread_is_gc_not_lost_cpu(
    cpu, gc_clock
):
    clock = FakeClock()
    rec = recorder(clock=clock)
    rec.tick_begin()  # the engine thread asks for a sum of its own
    assert gc_clock.thread_s == {rec._gc_thread: 0.0}
    deliver(rec, clock, cpu)

    def collect():
        gc_clock._on_gc("start", {"generation": 2})
        gc_clock._clock.advance(0.8)
        gc_clock._on_gc("stop", {"generation": 2})

    # the thread ran the whole second: 0.8 s of it the collector
    pause = deliver(rec, clock, cpu, gap=1.0, on_cpu=1.0, before=collect)
    assert pause["cause"] == "gc" and pause["gc_s"] == pytest.approx(0.8)
    assert pause["gc_other_threads_s"] == 0.0
    assert pause["off_cpu_s"] == 0.0


def test_a_sleep_taken_on_purpose_is_not_lost_cpu(cpu, gc_clock):
    clock = FakeClock()
    rec = recorder(clock=clock)
    rec.tick_begin()
    deliver(rec, clock, cpu)
    with rec.span("schedule"):
        clock.advance(0.7)  # an armed fault's delay: the thread slept
    rec.note_sleep(0.7)
    pause = deliver(rec, clock, cpu, gap=0.1, device=0.1)
    assert pause["cause"] == "host" and pause["slept_s"] == 0.7
    assert pause["off_cpu_s"] == pytest.approx(0.0)
    assert pause["phases"]["schedule"] == pytest.approx(0.7)


def test_causes_partition_the_seconds_and_merge_adds_them(cpu, gc_clock):
    clocks = [FakeClock(), FakeClock()]
    recs = [recorder(clock=clock) for clock in clocks]
    for rec, clock in zip(recs, clocks):
        rec.tick_begin()
        deliver(rec, clock, cpu)
    for cause in perf_mod.PAUSE_CAUSES:
        _pause_of(cause, recs[0], clocks[0], cpu, gc_clock)
    _pause_of("device", recs[1], clocks[1], cpu, gc_clock)
    # a gap, no pause
    deliver(recs[1], clocks[1], cpu, gap=0.4, device=0.4)
    for rec in recs:
        rec.tick_end(worked=True)
    snaps = [rec.snapshot() for rec in recs]
    totals = snaps[0]["totals"]
    seconds = sum(
        v for k, v in totals["pauses"].items() if k.endswith("_s"))
    assert seconds == pytest.approx(6.0)
    assert seconds == pytest.approx(
        sum(p["gap_s"] for p in snaps[0]["pauses"]))
    assert {p["cause"] for p in snaps[0]["pauses"]} == set(
        perf_mod.PAUSE_CAUSES)
    merged = perf_mod.merge_snapshots(snaps)
    both = merged["totals"]
    assert both["pauses"]["device_n"] == 2
    assert both["pauses"]["device_s"] == pytest.approx(2.0)
    assert both["pauses"]["host_n"] == 1
    assert both["deliveries"] == 6 + 2
    assert both["delivery_gap_s"] == pytest.approx(6.0 + 1.4)
    assert sum(both["delivery_gaps"].values()) == both["deliveries"]
    assert both["delivery_gaps"]["1024"] == 7
    assert both["gc"] == totals["gc"]  # one process, one collector
    assert [p["replica"] for p in merged["pauses"]].count(1) == 1
    assert len(merged["pauses"]) == 7
    times = [p["t"] for p in merged["pauses"]]
    assert times == sorted(times)


def test_only_the_last_pause_records_are_kept(cpu, gc_clock):
    clock = FakeClock()
    rec = recorder(clock=clock)
    rec.tick_begin()
    deliver(rec, clock, cpu)
    for _ in range(perf_mod.PAUSES_KEPT + 3):
        deliver(rec, clock, cpu, gap=0.5, device=0.5)  # 0.5 s IS a pause
    assert len(rec.pauses()) == perf_mod.PAUSES_KEPT
    assert rec.totals()["pauses"]["device_n"] == perf_mod.PAUSES_KEPT + 3


def test_the_delivery_recorder_is_inert_when_observability_is_off(
    monkeypatch
):
    """Off: note_delivery, the hand-off stamp and the collector's
    callback test one flag; on with no capture, no annotation."""
    opened = []
    monkeypatch.setattr(
        perf_mod, "_open_annotation",
        lambda name, args: opened.append(name),
    )
    clock = FakeClock()
    rec = recorder(clock=clock, enabled=False)
    rec.tick_begin()
    rec.count(decode_steps=8)
    assert rec.note_delivery(8, 4) is None
    clock.advance(2.0)
    assert rec.note_delivery(8, 4) is None
    assert rec._mark is None and rec._counts["decode_steps"] == 0
    assert rec.snapshot() == {"enabled": False}
    collector = perf_mod.GcClock(clock=clock)
    collector.enabled = False
    collector._on_gc("start", {"generation": 0})
    clock.advance(1.0)
    collector._on_gc("stop", {"generation": 0})
    assert collector.totals()["gc_s"] == 0.0 and collector._t0 is None
    collector.enabled = True
    collector._on_gc("start", {"generation": 1})
    clock.advance(0.25)
    collector._on_gc("stop", {"generation": 1})
    assert collector.totals() == {
        "gc_s": 0.25, "gc_max_s": 0.25,
        "gc_collections": {"0": 0, "1": 1, "2": 0},
        "gc_seconds": {"0": 0.0, "1": 0.25, "2": 0.0}}
    assert opened == []  # no capture: no annotation
    perf_mod.set_capturing(True)
    try:
        collector._on_gc("start", {"generation": 2})
    finally:
        perf_mod.set_capturing(False)
    assert opened == ["vgt.host.gc"]


def test_the_collectors_clock_times_a_real_collection():
    import gc

    collector = perf_mod.GcClock()
    collector.install()
    collector.install()  # once, however often asked
    try:
        assert gc.callbacks.count(collector._on_gc) == 1
        key = collector.watch()
        before = collector.totals()
        cycle = [[] for _ in range(200_000)]
        for item in cycle:
            item.append(cycle)
        del cycle, item
        gc.collect()
        after = collector.totals()
    finally:
        collector.remove()
    assert collector._on_gc not in gc.callbacks
    assert after["gc_s"] > before["gc_s"]
    assert after["gc_collections"]["2"] > before["gc_collections"]["2"]
    # this thread asked for a sum of its own, and ran the collection
    assert collector.thread_s[key] == pytest.approx(collector.gc_s)
    collector.remove()  # twice is harmless


async def test_the_app_installs_the_collectors_clock_once_and_removes_it():
    import gc

    assert perf_mod.GC._on_gc not in gc.callbacks
    client = await _client()
    try:
        assert gc.callbacks.count(perf_mod.GC._on_gc) == 1
    finally:
        await client.close()
    assert perf_mod.GC._on_gc not in gc.callbacks


@pytest.mark.parametrize("suffix", [".tok", ".tpot"])
def test_the_benchmark_reads_the_recorders_own_snapshots(
    suffix, cpu, gc_clock
):
    """``engine.delivery_gap_p99_ms`` / ``engine.pause_*`` /
    ``gateway.handoff*`` through their data files, from what the
    recorder and the gateway really serve."""
    from perfbench import manifest

    clock = FakeClock()
    rec = recorder(clock=clock)
    gateway = perf_mod.GatewayPerf(clock=clock)

    def scrape():
        totals = rec.totals()
        totals["gateway"] = gateway.totals()
        return {"totals": totals}

    def tick(gap, **kw):
        rec.tick_begin()
        pause = deliver(rec, clock, cpu, gap=gap, **kw)
        armed = gateway.handoff_armed()
        clock.advance(0.001)
        gateway.note_handoff(armed)
        rec.tick_end(worked=True)
        return pause

    tick(0.1)
    tick(0.1, device=0.1)
    first = scrape()
    for _ in range(98):
        tick(0.099, device=0.099)
    tick(0.899, device=0.6, before=lambda: rec.count(prompt_programs=1))
    tick(0.999, device=0.999)
    ctx = {"perf": {"open": first, "close": scrape()}}

    def read(base):
        spec = manifest.metric(base + suffix)
        return manifest.reducer(spec["reducer"])(ctx, **spec["args"])

    # 100 gaps: the 99th is the 0.9 s one, in (724.08, 1024] ms
    assert 724.08 < read("engine.delivery_gap_p99_ms") <= 1024
    wall = 98 * 0.1 + 0.9 + 1.0
    assert read("engine.pause_share") == pytest.approx(100 * 1.9 / wall)
    assert read("engine.pause_prefill_share") == pytest.approx(
        100 * 0.9 / wall)
    assert read("engine.pause_device_share") == pytest.approx(
        100 * 1.0 / wall)
    assert read("engine.pause_host_share") == 0.0
    assert read("engine.gc_share") == 0.0
    assert read("gateway.handoffs_per_readback") == pytest.approx(1.0)
    assert 0 < read("gateway.handoff_wait_p99_ms") <= 8


# ----------------------------------------- the device clock (ISSUE 49)


def device_clock(t=10.0, **kw):
    clock = FakeClock(t)
    device = FakeDevice(clock)
    return clock, device, perf_mod.DeviceClock(
        clock=clock, wait=device.wait, **kw)


def test_every_counter_of_the_device_clock_is_there_at_zero_from_boot():
    """The benchmark's ratios read a missing path as no reading, so the
    keys exist before the first launch; and no thread before it."""
    rec = recorder()
    clock = rec.totals()["device_clock"]
    assert clock == {
        "busy_s": 0.0, "idle_s": 0.0, "decode_steps": 0,
        "prompt_tokens": 0, "dropped": 0,
        "programs": {
            name: {"n": 0, "s": 0.0, "queued_s": 0.0}
            for name in ("prefill", "suffix_prefill", "chunked_prefill",
                         "decode", "spec_verify")
        },
    }
    assert tuple(clock["programs"]) == perf_mod.DEVICE_PROGRAMS
    assert rec.device._thread is None and rec.device.launches_since(0) == []


@pytest.mark.parametrize("late", [0.0, 0.125], ids=["on-time", "late-stamp"])
def test_completion_times_tile_the_devices_time_by_program(late):
    """Launches finish in order; busy + idle seconds are the time from
    the first call to the last stamp, to the float (the times are
    dyadic).  A stamp that comes late moves seconds from the launch
    behind it to the one it closes and loses none."""
    clock, device, dev = device_clock()
    try:
        dev.post("prefill", 10.0, "a", prompt_tokens=100, rows=2, bucket=64)
        # called while ``a`` is on the device: it queues behind it
        dev.post("decode", 10.25, "b", steps=8, rows=4)
        device.finish(dev, "a", at=11.0 + late)
        device.finish(dev, "b", at=11.5)
        # called half a second after the device went idle
        dev.post("decode", 12.0, "c", steps=4, rows=3)
        device.finish(dev, "c", at=12.25)
        totals = dev.totals()
    finally:
        dev.shutdown()
    assert totals["busy_s"] + totals["idle_s"] == 12.25 - 10.0
    assert totals["idle_s"] == 0.5  # only before ``c``
    programs = totals["programs"]
    assert programs["prefill"] == {"n": 1, "s": 1.0 + late, "queued_s": 0.0}
    assert programs["decode"] == {
        "n": 2, "s": 0.5 - late + 0.25, "queued_s": 0.75 + late}
    assert totals["decode_steps"] == 12 and totals["prompt_tokens"] == 100
    assert totals["dropped"] == 0
    assert dev.decode_s == programs["decode"]["s"]


def test_the_clock_drops_past_its_cap_and_skips_a_wait_that_raises():
    clock, device, dev = device_clock()
    try:
        for i in range(perf_mod.DEVICE_QUEUE_MAX + 3):
            dev.post("decode", 10.0, f"chunk{i}", steps=1, rows=1)
        assert dev.totals()["dropped"] == 3
        assert len(dev._open) == perf_mod.DEVICE_QUEUE_MAX
        # the first finishes; the second is a poisoned launch: counted,
        # skipped, and its second goes to the launch behind it
        device.poisoned.add("chunk1")
        device.finish(dev, "chunk0", at=11.0)
        device.finish(dev, "chunk1", at=12.0)
        assert dev._thread.is_alive()
        device.finish(dev, "chunk2", at=12.5)
        totals = dev.totals()
        assert totals["dropped"] == 4
        assert totals["programs"]["decode"] == {
            "n": 2, "s": 2.5, "queued_s": 1.0}
        assert totals["busy_s"] == 2.5 and totals["idle_s"] == 0.0
        assert totals["decode_steps"] == 2
    finally:
        thread = dev._thread
        dev.shutdown()
    # mortal: no output is held past the shutdown, and the thread ends
    # once the wait it stood in returns, stamping nothing
    assert not dev._open and dev._thread is None
    device.gate("chunk3").set()
    thread.join(5)
    assert not thread.is_alive() and device.waited[-1] == "chunk3"
    assert dev.totals() == totals
    # a core started again (``warmup`` stops the core it started) posts
    # again: a fresh thread, and the time in between was the device's idle
    dev.post("decode", 13.0, "again", steps=1, rows=1)
    device.finish(dev, "again", at=13.25)
    again = dev._thread
    assert again is not thread and again.is_alive()
    dev.shutdown()
    assert not again.is_alive()
    assert dev.totals()["idle_s"] == 0.5 and dev.totals()["busy_s"] == 2.75


def test_the_device_clock_is_off_with_the_recorder(monkeypatch):
    """``observability.perf_enabled: false``: a post is one flag test,
    no thread exists, nothing is held."""
    for off in ({"enabled": False}, {"perf_enabled": False}):
        rec = recorder(**off)
        assert rec.device.enabled is False
        rec.device.post("decode", 1.0, object(), steps=8, rows=4)
        assert rec.device._thread is None and not rec.device._open
        rec.device.shutdown()
    started = []
    monkeypatch.setattr(
        perf_mod.threading, "Thread",
        lambda **kw: started.append(kw) or pytest.fail("a thread"))
    recorder(enabled=False).device.post("decode", 1.0, "x")
    assert started == []


def test_the_waits_are_trace_spans_only_while_a_capture_runs(monkeypatch):
    """``vgt.device.<program>`` with what the launch carried, NOT under
    ``vgt.engine.`` (the benchmark charges device pauses to that
    prefix); with no capture nothing builds an annotation."""
    opened = []

    class Ann:
        def __exit__(self, *exc):
            opened.append("closed")

    def fake(name, args):
        opened.append((name, args()))
        return Ann()

    monkeypatch.setattr(perf_mod, "_open_annotation", fake)
    clock, device, dev = device_clock()
    try:
        dev.post("decode", 10.0, "quiet", steps=8, rows=4)
        device.finish(dev, "quiet", at=10.5)
        assert opened == []
        perf_mod.set_capturing(True)
        dev.post("suffix_prefill", 10.5, "traced", prompt_tokens=182,
                 rows=1, bucket=256)
        device.finish(dev, "traced", at=11.0)
    finally:
        perf_mod.set_capturing(False)
        dev.shutdown()
    assert opened == [
        ("vgt.device.suffix_prefill",
         {"prompt_tokens": 182, "rows": 1, "bucket": 256}),
        "closed",
    ]


def test_a_pause_names_its_launches_and_the_devices_brings_its_memory(
    cpu, gc_clock
):
    """S13's accident as the record now reads it: the wait for the
    device took the gap, no wave explains it (cause ``device``), and
    ``programs`` says which launch the device sat in, ``memory`` what
    the chip had free."""
    clock = FakeClock()
    rec = recorder(clock=clock)
    device = FakeDevice(clock)
    rec.device = perf_mod.DeviceClock(clock=clock, wait=device.wait)
    rec.device_memory = lambda: [
        {"id": 0, "bytes_in_use": 15_000, "bytes_limit": 16_900}]
    try:
        rec.tick_begin()
        t0 = clock.t
        rec.device.post("decode", t0 - 1.0, "old", steps=8, rows=4)
        device.finish(rec.device, "old", at=t0 - 0.5)  # before the gap
        clock.t = t0
        deliver(rec, clock, cpu)
        t1 = clock.t

        def launches():
            rec.device.post("decode", t1, "ahead", steps=8, rows=4)
            rec.device.post("prefill", t1 + 0.0625, "small",
                            prompt_tokens=182, rows=1, bucket=256)
            device.finish(rec.device, "ahead", at=t1 + 0.125)
            clock.t = t1

        pause = deliver(rec, clock, cpu, gap=3.0, device=2.9,
                        before=launches)
        assert pause["cause"] == "device"
        assert pause["programs"] == [
            {"program": "decode", "steps": 8, "rows": 4, "queued_s": 0.0,
             "device_s": 0.125},
            {"program": "prefill", "prompt_tokens": 182, "rows": 1,
             "bucket": 256, "queued_s": 0.0625, "device_s": 2.875,
             "open": True},
        ]
        assert pause["memory"] == rec.device_memory()
        assert rec.pauses()[-1] is pause
        # any other cause: the launches, no memory
        device.finish(rec.device, "small", at=clock.t)
        host = deliver(rec, clock, cpu, gap=1.0, schedule=0.9)
        assert host["cause"] == "host" and "memory" not in host
        assert [p["program"] for p in host["programs"]] == ["prefill"]
        assert "open" not in host["programs"][0]
        # at most the last eight of a gap
        t2 = clock.t

        def many():
            for i in range(10):
                rec.device.post("decode", t2, f"m{i}", steps=i, rows=1)
                device.finish(rec.device, f"m{i}", at=t2)

        last = deliver(rec, clock, cpu, gap=1.0, schedule=0.9, before=many)
        assert [p["steps"] for p in last["programs"]] == list(range(2, 10))
    finally:
        rec.device.shutdown()


def test_the_device_clock_adds_across_replicas():
    clock, device, dev = device_clock()
    rec = recorder(clock=clock)
    rec.device = dev
    try:
        dev.post("chunked_prefill", 10.0, "a", prompt_tokens=512, rows=1,
                 bucket=512)
        dev.post("spec_verify", 10.5, "b", steps=1, rows=2)
        device.finish(dev, "a", at=10.75)
        device.finish(dev, "b", at=11.0)
        rec.tick_begin()
        clock.advance(0.5)
        rec.tick_end(worked=True)
        snap = rec.snapshot()
    finally:
        rec.device.shutdown()
    one = snap["totals"]["device_clock"]
    assert one["programs"]["chunked_prefill"] == {
        "n": 1, "s": 0.75, "queued_s": 0.0}
    assert one["programs"]["spec_verify"] == {
        "n": 1, "s": 0.25, "queued_s": 0.25}
    # a verify round's steps are not a decode chunk's
    assert one["decode_steps"] == 0 and one["prompt_tokens"] == 512
    both = perf_mod.merge_snapshots([snap, snap])["totals"]["device_clock"]
    assert _numbers(both) == {
        key: 2 * value for key, value in _numbers(one).items()}
    assert tuple(both["programs"]) == perf_mod.DEVICE_PROGRAMS


def _settled_clock(core, launches, timeout_s=30.0):
    """``totals.device_clock`` once the clock's thread has stamped
    ``launches`` launches (it runs behind the engine thread)."""
    deadline = time.monotonic() + timeout_s
    while True:
        clock = core.perf.totals()["device_clock"]
        stamped = sum(row["n"] for row in clock["programs"].values())
        if stamped >= launches or time.monotonic() > deadline:
            return clock
        time.sleep(0.01)


def test_the_engine_posts_every_launch_and_a_pause_names_them():
    """A real engine on the CPU: every decode chunk read back and every
    prompt program dispatched is one launch of the clock, whose steps
    and prompt tokens are the recorder's own ``count()`` sums; a
    provoked pause carries the launches of its gap; the thread ends
    with the core."""
    from vgate_tpu import faults
    from vgate_tpu.backends.base import SamplingParams
    from vgate_tpu.runtime.engine_core import EngineCore

    core = EngineCore(_engine_config())
    core.start()
    params = [SamplingParams(max_tokens=40, min_tokens=40,
                             temperature=0.0)] * 2
    prompts = ["device clock probe one", "device clock probe two, longer"]
    try:
        core.generate(prompts, params)
        core.generate(prompts[::-1], params)
        counts = dict(core.perf._counts)
        totals = core.perf.totals()
        chunks = sum(totals["chunks_by_steps"].values())
        clock = _settled_clock(core, chunks + counts["prompt_programs"])
        programs = clock["programs"]
        assert programs["decode"]["n"] == chunks > 0
        assert (programs["prefill"]["n"] + programs["suffix_prefill"]["n"]
                == counts["prompt_programs"] > 0)
        assert clock["decode_steps"] == counts["decode_steps"]
        assert clock["prompt_tokens"] == counts["prompt_tokens"] > 0
        assert clock["dropped"] == 0
        assert clock["busy_s"] == pytest.approx(
            sum(row["s"] for row in programs.values()), abs=1e-4)
        assert clock["busy_s"] > 0 and clock["idle_s"] > 0
        thread = core.perf.device._thread
        assert thread.is_alive() and thread.daemon
        assert thread is not core._thread

        seen = totals["deliveries"]
        records = len(core.perf.pauses())
        spec = faults.arm(
            "stall", mode="delay", delay_s=0.7, times=1,
            match=lambda _: core.perf.totals()["deliveries"] >= seen + 2,
        )
        core.generate(prompts, params)
        assert spec.fired == 1
        (pause,) = core.perf.pauses()[records:]
        assert pause["cause"] == "host" and "memory" not in pause
        assert 1 <= len(pause["programs"]) <= perf_mod.PAUSE_PROGRAMS_KEPT
        for launch in pause["programs"]:
            assert launch["program"] in perf_mod.DEVICE_PROGRAMS
            assert launch["device_s"] >= 0 and launch["queued_s"] >= 0
            assert "rows" in launch
        assert any(p["program"] == "decode" and p["steps"] >= 1
                   for p in pause["programs"])
        tick = [t for t in core.flight.ticks() if t["kind"] == "pause"][-1]
        assert tick["programs"] == pause["programs"]
    finally:
        faults.reset()
        core.stop()
    assert core.perf.device._thread is None and not thread.is_alive()
    assert not core.perf.device._open


@pytest.mark.parametrize("tpu, program", [
    ({"prefill_chunk": 16, "prefill_buckets": [8, 16]}, "chunked_prefill"),
    ({"speculative_k": 2}, "spec_verify"),
], ids=["chunked-prefill", "spec-verify"])
def test_a_posted_output_is_never_one_a_later_launch_donates(tpu, program):
    """The clock's thread waits on an array while the engine launches
    on: a pool, a ring or a state among the posted outputs would be
    deleted under it.  A wait that raised is counted under ``dropped``,
    so it is the deleted buffer this fails on, not a number."""
    from vgate_tpu.backends.base import SamplingParams
    from vgate_tpu.runtime.engine_core import EngineCore

    base = _engine_config()
    core = EngineCore(load_config(
        model=base.model.model_dump(),
        tpu={**base.tpu.model_dump(), **tpu},
        logging={"level": "WARNING"},
    ))
    raised = []
    wait = __import__("jax").block_until_ready

    def checked(output):
        try:
            return wait(output)
        except Exception as exc:  # a deleted buffer: RuntimeError
            raised.append(repr(exc))
            raise

    core.perf.device._wait = checked
    core.start()
    try:
        greedy = SamplingParams(max_tokens=12, temperature=0.0)
        long_prompt = [3 + (i % 31) for i in range(40)]
        for _ in range(2):
            seqs = [core.submit_tokens(long_prompt, greedy),
                    core.submit_tokens(long_prompt[::-1], greedy)]
            assert all(seq.done_event.wait(300) for seq in seqs)
        launches = core.perf._counts["prompt_programs"] + 1
        clock = _settled_clock(core, launches)
    finally:
        core.stop()
    assert raised == [] and clock["dropped"] == 0
    assert clock["programs"][program]["n"] >= 2
    assert clock["programs"][program]["s"] > 0


def test_a_capture_holds_the_clocks_spans_on_a_thread_of_their_own(tmp_path):
    """``vgt.device.<program>`` spans lie on ONE line of the trace, and
    it is not the line that ticks: the benchmark's reduction of the
    engine's spans (``vgt.engine.*`` of the thread that ticks) reads
    what it read before."""
    from vgate_tpu.backends.base import SamplingParams
    from vgate_tpu.runtime.engine_core import EngineCore

    core = EngineCore(_engine_config())
    core.start()
    try:
        params = [SamplingParams(max_tokens=24, min_tokens=24,
                                 temperature=0.0)]
        core.generate(["0 probe"], params)

        def load():
            time.sleep(0.15)  # let the capture begin first
            core.generate(["1 probe"], params)

        thread = threading.Thread(target=load)
        thread.start()
        result = core.capture_profile(0.6, str(tmp_path / "trace"))
        thread.join()
    finally:
        core.stop()
    lines = _trace_names(result["trace_dir"])
    clock = [names for names in lines
             if any(n.startswith("vgt.device.") for n in names)]
    assert len(clock) == 1
    (names,) = clock
    assert "vgt.device.decode" in names and "vgt.device.prefill" in names
    assert not [n for n in names if n.startswith("vgt.engine.")]
    engine = max(lines, key=lambda n: n.count("vgt.engine.tick"))
    assert engine.count("vgt.engine.tick") >= 2
    assert not [n for n in engine if n.startswith("vgt.device.")]
