"""Engine flight recorder (ISSUE 3 tentpole 2) + the /debug surface
(tentpole 3) + /stats and /v1/profile error paths (satellite).

Fast tier: recorder unit behavior, the dry-run gateway's /debug
responses, auth gating, drain accounting, and the profile/stats error
paths.  Slow tier: a decode_step fault through the real supervised
engine leaves a crash snapshot whose final tick is the faulting one.
"""

import asyncio
import time

import pytest
from aiohttp.test_utils import TestClient, TestServer

from vgate_tpu import faults
from vgate_tpu.backends.base import SamplingParams
from vgate_tpu.config import ObservabilityConfig, load_config
from vgate_tpu.observability.flight import FlightRecorder
from vgate_tpu.server.app import _drain_counted, create_app


class _FakeSeq:
    _ids = iter(range(10_000))

    def __init__(self, request_id=None, timeout_s=None):
        self.seq_id = next(self._ids)
        self.request_id = request_id
        self.trace = None
        self.arrival_t = time.perf_counter()
        self.first_token_t = None
        self.finish_t = None
        self.preempt_count = 0
        self.prompt_ids = [1, 2, 3]
        self.generated_ids = []
        self.error = None
        self.finish_reason = "stop"
        self.params = SamplingParams(timeout_s=timeout_s)

    @property
    def num_prompt_tokens(self):
        return len(self.prompt_ids)

    @property
    def num_generated(self):
        return len(self.generated_ids)


# ------------------------------------------------------------ unit tier


def test_tick_ring_is_bounded_and_ordered():
    rec = FlightRecorder(ObservabilityConfig(flight_ticks=4))
    for i in range(10):
        rec.record_tick("decode", chunk=i)
    ticks = rec.ticks()
    assert len(ticks) == 4
    assert [t["chunk"] for t in ticks] == [6, 7, 8, 9]
    assert [t["n"] for t in ticks] == sorted(t["n"] for t in ticks)
    assert rec.ticks(2)[0]["chunk"] == 8


def test_disabled_recorder_records_nothing():
    rec = FlightRecorder(ObservabilityConfig(enabled=False))
    rec.record_tick("decode")
    rec.on_admit(_FakeSeq(), bucket=8)
    assert rec.ticks() == []
    assert rec.live_requests() == []
    assert rec.get_stats()["enabled"] is False


def test_request_record_lifecycle_and_phases():
    rec = FlightRecorder(ObservabilityConfig())
    seq = _FakeSeq(request_id="abc123", timeout_s=9.0)
    rec.on_admit(seq, bucket=16, cached_len=8)
    assert rec.live_requests()[0]["status"] == "running"
    # phases while live: queue known, prefill running
    phases = rec.phases_of(seq)
    assert "queue_s" in phases and "prefill_s" in phases
    seq.first_token_t = time.perf_counter()
    rec.on_first_token(seq)
    seq.generated_ids = [4, 5]
    phases = rec.phases_of(seq)
    assert "decode_s" in phases
    seq.finish_t = time.perf_counter()
    rec.on_close(seq)
    assert rec.live_requests() == []
    (record,) = rec.requests()
    assert record["request_id"] == "abc123"
    assert record["bucket"] == 16
    assert record["cached_tokens"] == 8
    assert record["deadline_s"] == 9.0
    assert record["status"] == "finished"
    assert record["generated_tokens"] == 2
    for key in ("queue_s", "prefill_s", "decode_s", "total_s"):
        assert record[key] >= 0.0
    assert rec.find_request("abc123") == record
    assert rec.find_request(str(seq.seq_id)) == record
    assert rec.find_request("nope") is None


def test_preempted_request_keeps_nonnegative_cumulative_phases():
    """A preemption moves the sequence back to the queue while
    first_token_t survives — phase accounting must stay cumulative and
    non-negative across re-admission (code-review regression)."""
    rec = FlightRecorder(ObservabilityConfig())
    seq = _FakeSeq(request_id="pre1")
    rec.on_admit(seq, bucket=16)
    time.sleep(0.01)
    seq.first_token_t = time.perf_counter()
    rec.on_first_token(seq)
    time.sleep(0.01)
    # preempted mid-decode: back to the queue, then re-admitted
    seq.preempt_count = 1
    rec.on_preempt(seq)
    time.sleep(0.01)
    rec.on_admit(seq, bucket=32)
    time.sleep(0.01)
    rec.on_first_token(seq)  # re-prefill's token (first_token_t stale)
    seq.generated_ids = [1, 2, 3]
    seq.finish_t = time.perf_counter()
    rec.on_close(seq)
    (record,) = rec.requests()
    assert record["preemptions"] == 1
    assert record["bucket"] == 32  # the re-admission's bucket
    for key in ("queue_s", "prefill_s", "decode_s"):
        assert record[key] >= 0.0, (key, record)
    # queue includes the post-preempt wait; prefill both prompt passes
    assert record["queue_s"] >= 0.01
    assert record["prefill_s"] >= 0.02
    assert record["total_s"] >= (
        record["queue_s"] + record["prefill_s"] + record["decode_s"]
    ) - 1e-3


def test_failed_sequence_records_error():
    rec = FlightRecorder(ObservabilityConfig())
    seq = _FakeSeq()
    rec.on_admit(seq, bucket=8)
    seq.error = RuntimeError("boom")
    rec.on_close(seq)
    (record,) = rec.requests()
    assert record["status"] == "failed"
    assert "RuntimeError: boom" in record["error"]


def test_never_admitted_sequence_still_gets_a_queue_only_record():
    """A request shed from the waiting queue (deadline, drain, crash)
    settles without ever being admitted — it must still leave a record;
    queued-forever is the case operators most need to see."""
    rec = FlightRecorder(ObservabilityConfig())
    seq = _FakeSeq(request_id="queued-only", timeout_s=0.05)
    time.sleep(0.01)
    seq.error = RuntimeError("deadline passed in queue")
    seq.finish_t = time.perf_counter()
    rec.on_close(seq)
    (record,) = rec.requests()
    assert record["request_id"] == "queued-only"
    assert record["status"] == "failed"
    assert record["bucket"] is None  # never admitted
    assert record["queue_s"] >= 0.01
    assert record["prefill_s"] == 0.0 and record["decode_s"] == 0.0
    assert rec.find_request("queued-only") == record


def test_prompt_text_redacted_by_default():
    rec = FlightRecorder(ObservabilityConfig())
    seq = _FakeSeq()
    rec.on_admit(seq, bucket=8, preview="secret prompt text")
    assert "prompt_preview" not in rec.live_requests()[0]
    # explicit opt-out keeps a clamped preview
    rec2 = FlightRecorder(
        ObservabilityConfig(redact_prompts=False, prompt_preview_chars=6)
    )
    seq2 = _FakeSeq()
    rec2.on_admit(seq2, bucket=8, preview="secret prompt text")
    assert rec2.live_requests()[0]["prompt_preview"] == "secret"


def test_crash_snapshot_ends_with_latest_tick():
    rec = FlightRecorder(ObservabilityConfig(crash_dump_ticks=8))
    for i in range(20):
        rec.record_tick("decode", chunk=i)
    rec.record_tick("crash", error="InjectedFault: boom")
    seq = _FakeSeq(request_id="inflight")
    rec.on_admit(seq, bucket=8)
    snap = rec.crash_snapshot(RuntimeError("boom"))
    assert snap["error"] == "RuntimeError: boom"
    assert len(snap["ticks"]) == 8
    assert snap["ticks"][-1]["kind"] == "crash"
    assert snap["in_flight"][0]["request_id"] == "inflight"


def test_debug_paths_never_hold_a_drain_open():
    assert not _drain_counted("/debug/flight")
    assert not _drain_counted("/debug/requests")
    assert not _drain_counted("/debug/requests/abc")
    assert not _drain_counted("/stats")
    assert _drain_counted("/v1/chat/completions")


# ------------------------------------------------ gateway tier (dry run)


async def _client(**overrides):
    overrides.setdefault("model", {"engine_type": "dry_run"})
    overrides.setdefault(
        "batch", {"max_batch_size": 4, "max_wait_time_ms": 5.0}
    )
    overrides.setdefault("logging", {"level": "ERROR"})
    config = load_config(**overrides)
    client = TestClient(TestServer(create_app(config)))
    await client.start_server()
    return client


async def test_debug_endpoints_report_disabled_without_engine_core():
    client = await _client()
    try:
        body = await (await client.get("/debug/flight")).json()
        assert body == {
            "enabled": False, "ticks": [],
            "reason": "engine has no flight recorder",
        }
        body = await (await client.get("/debug/requests")).json()
        assert body["enabled"] is False
        resp = await client.get("/debug/requests/whatever")
        assert resp.status == 404
    finally:
        await client.close()


async def test_debug_endpoints_are_auth_gated():
    client = await _client(
        security={"enabled": True, "api_keys": ["k1"]}
    )
    try:
        assert (await client.get("/debug/flight")).status == 401
        assert (
            await client.get(
                "/debug/flight",
                headers={"Authorization": "Bearer k1"},
            )
        ).status == 200
        # probes stay exempt
        assert (await client.get("/health")).status == 200
    finally:
        await client.close()


async def test_profile_requires_jax_engine_as_400():
    client = await _client()
    try:
        resp = await client.post("/v1/profile", json={"duration_ms": 10})
        assert resp.status == 400
        body = await resp.json()
        assert body["error"]["type"] == "invalid_request_error"
        assert "jax_tpu" in body["error"]["message"]
    finally:
        await client.close()


async def test_profile_concurrent_capture_409():
    client = await _client()
    try:

        class _FakeCore:
            def capture_profile(self, duration_s, out_dir=None):
                time.sleep(0.3)
                return {"trace_dir": "/tmp/x", "duration_s": duration_s,
                        "files": 0}

        client.app["engine"].backend.core = _FakeCore()
        first, second = await asyncio.gather(
            client.post("/v1/profile", json={"duration_ms": 300}),
            client.post("/v1/profile", json={"duration_ms": 300}),
        )
        statuses = sorted((first.status, second.status))
        assert statuses == [200, 409]
    finally:
        await client.close()


async def test_profile_rejects_bad_bodies():
    client = await _client()
    try:

        class _FakeCore:
            def capture_profile(self, duration_s, out_dir=None):
                return {}

        client.app["engine"].backend.core = _FakeCore()
        resp = await client.post("/v1/profile", json=[1, 2, 3])
        assert resp.status == 422
        resp = await client.post(
            "/v1/profile", json={"duration_ms": "soon"}
        )
        assert resp.status == 422
        resp = await client.post(
            "/v1/profile", json={"out_dir": "/etc/definitely-not-tmp"}
        )
        assert resp.status == 422
    finally:
        await client.close()


async def test_stats_survives_backend_stats_failure():
    client = await _client()
    try:

        def explode():
            raise RuntimeError("mid-rebuild")

        client.app["engine"].backend.get_stats = explode
        resp = await client.get("/stats")
        assert resp.status == 200
        body = await resp.json()
        assert "RuntimeError" in body["engine"]["error"]
        assert body["batcher"]["running"] is True
    finally:
        await client.close()


# --------------------------------------------- real engine (slow tier)


@pytest.mark.slow
def test_decode_fault_crash_log_includes_flight_snapshot():
    """ISSUE 3 acceptance: with a fault armed at decode_step, the
    supervisor's crash handling captures a flight-recorder snapshot
    whose final tick is the faulting one, and /stats surfaces it under
    engine.last_crash."""
    from vgate_tpu.runtime.supervisor import EngineSupervisor

    config = load_config(
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
        recovery={
            "enabled": True, "max_restarts": 5,
            "restart_window_s": 120.0, "backoff_base_s": 0.02,
            "backoff_cap_s": 0.2, "degraded_probation_s": 0.25,
        },
        logging={"level": "ERROR"},
    )
    sup = EngineSupervisor(config)
    sup.start()
    try:
        faults.arm("decode_step", mode="raise", kind="transient", times=1)
        with pytest.raises(Exception):
            sup.generate(
                ["crash me"],
                [SamplingParams(max_tokens=4, temperature=0.0)],
            )
        deadline = time.monotonic() + 60
        while sup.last_crash is None and time.monotonic() < deadline:
            time.sleep(0.02)
        snap = sup.last_crash
        assert snap is not None, "supervisor never captured a snapshot"
        assert snap["classification"] == "transient"
        assert "decode_step" in snap["error"]
        # the ring's final tick is the faulting dispatch
        assert snap["ticks"], "snapshot carries no ticks"
        assert snap["ticks"][-1]["kind"] == "crash"
        assert "decode_step" in snap["ticks"][-1]["error"]
        # the prefill that preceded the faulting decode is in the ring
        assert any(t["kind"] == "prefill" for t in snap["ticks"])
        # the crashed request was resident at the time of death
        assert snap["in_flight"], "no in-flight records captured"
        # /stats surfaces the same snapshot
        assert sup.get_stats()["last_crash"] is snap
    finally:
        faults.reset()
        sup.stop()


# ------------------------------------- a pause, end to end (ISSUE 35)


def test_a_provoked_pause_leaves_one_tick_one_record_and_one_log_line(
    caplog,
):
    """The real engine, two streams running, a ``delay`` armed at the
    ``stall`` site between two decode readbacks: the delivery gap it
    makes is a PAUSE with exactly one flight-recorder tick, one
    ``/debug/perf -> pauses`` record and one ``engine_pause`` log line,
    cause ``host`` (the thread slept in its own ``schedule`` bracket:
    not CPU time it lost)."""
    import logging

    from vgate_tpu.runtime.engine_core import EngineCore

    core = EngineCore(load_config(
        model={
            "model_id": "tiny-dense", "engine_type": "jax_tpu",
            "dtype": "float32", "max_model_len": 64,
        },
        tpu={
            "dp": 1, "tp": 1, "ep": 1, "sp": 1, "num_devices": 1,
            "kv_num_pages": 64, "kv_page_size": 4,
            "max_batch_slots": 4, "prefill_buckets": [8, 16],
            "use_pallas": False, "decode_chunk": 4,
        },
        logging={"level": "WARNING"},
    ))
    core.start()
    params = [SamplingParams(max_tokens=40, min_tokens=40,
                             temperature=0.0)] * 2
    prompts = ["pause probe one", "pause probe two"]

    def pause_ticks():
        return [t for t in core.flight.ticks() if t["kind"] == "pause"]

    try:
        # every program variant compiles here, not inside a gap
        core.generate(prompts, params)
        core.generate(prompts, params)
        seen = core.perf.totals()["deliveries"]
        records, ticks = len(core.perf.pauses()), len(pause_ticks())
        spec = faults.arm(
            "stall", mode="delay", delay_s=0.7, times=1,
            # once streams are running and have had two deliveries
            match=lambda _: core.perf.totals()["deliveries"] >= seen + 2,
        )
        caplog.clear()  # the warm-up's compile pauses are logged too
        with caplog.at_level(logging.WARNING):
            core.generate(prompts, params)
        assert spec.fired == 1
        snap = core.perf_snapshot()
        new = snap["pauses"][records:]
        assert len(new) == 1, new
        pause = new[0]
        assert pause["cause"] == "host"
        assert 0.7 <= pause["slept_s"] <= pause["gap_s"] < 3.0
        assert pause["phases"]["schedule"] >= 0.7
        assert pause["prompt_programs"] == 0 and pause["rows"] == 2
        new_ticks = pause_ticks()[ticks:]
        assert len(new_ticks) == 1
        assert new_ticks[0]["gap_s"] == pause["gap_s"]
        assert new_ticks[0]["cause"] == "host"
        totals = snap["totals"]
        assert totals["pauses"]["host_n"] >= 1
        assert sum(totals["delivery_gaps"].values()) == totals["deliveries"]
        assert core.flight.crash_snapshot()["ticks"][-1]["n"] >= (
            new_ticks[0]["n"])
        lines = [r for r in caplog.records if r.msg == "engine_pause"]
        assert len(lines) == 1
        assert lines[0].extra_data["gap_s"] == pause["gap_s"]
    finally:
        faults.reset()
        core.stop()
