"""End-to-end request deadlines, cancellation propagation and graceful
drain (ISSUE 2).

Fast tier: gateway/batcher/scheduler behavior on the dry-run backend —
timeout parsing, CancelToken mechanics, queued-request cancellation,
drain admission/readiness semantics, and the partial-result cache
regression.  Slow tier (real jax engine on the tiny model): the three
acceptance scenarios — (a) a client disconnect mid-generation frees the
sequence's KV pages and scheduler slot within a tick, (b) a 50 ms
deadline against a slow fault-injected backend 504s without failing its
batchmates, (c) SIGTERM under load completes every in-flight request
while /health/ready reports draining throughout.
"""

import asyncio
import json
import os
import signal
import time

import pytest
from aiohttp.test_utils import TestClient, TestServer

from vgate_tpu import faults
from vgate_tpu.backends.base import GenerationResult
from vgate_tpu.batcher import RequestBatcher
from vgate_tpu.config import load_config
from vgate_tpu.engine import VGTEngine
from vgate_tpu.errors import (
    ClientDisconnectError,
    DeadlineExceededError,
    ServerDrainingError,
)
from vgate_tpu.lifecycle import CancelToken, DrainController, all_of
from vgate_tpu.server.app import create_app

JAX_TINY = dict(
    model={
        "model_id": "tiny-dense",
        "engine_type": "jax_tpu",
        "dtype": "float32",
        "max_model_len": 64,
    },
    tpu={
        "dp": 1, "tp": 1, "ep": 1, "sp": 1, "num_devices": 1,
        "kv_num_pages": 128, "kv_page_size": 4,
        "max_batch_slots": 4, "prefill_buckets": [16, 32],
        "use_pallas": False,
    },
    scheduler={"max_queue_size": 32},
)


async def _client(**overrides):
    overrides.setdefault("model", {"engine_type": "dry_run"})
    overrides.setdefault(
        "batch", {"max_batch_size": 8, "max_wait_time_ms": 10.0}
    )
    overrides.setdefault("logging", {"level": "WARNING"})
    config = load_config(**overrides)
    client = TestClient(TestServer(create_app(config)))
    await client.start_server()
    return client


def _chat_body(i=0, **extra):
    return {
        "messages": [{"role": "user", "content": f"lifecycle probe {i}"}],
        "max_tokens": 8,
        "temperature": 0.0,
        **extra,
    }


async def _warm(client, n=1):
    """Fire n concurrent tiny requests so the engine compiles the
    [B=n, bucket] batched-prefill and decode programs the timed tests
    use — a first-contact XLA compile (seconds on CPU) mid-test would
    stall the engine tick past the deadlines being asserted."""
    responses = await asyncio.gather(
        *(
            client.post(
                "/v1/chat/completions",
                json=_chat_body(i, max_tokens=2, min_tokens=2),
            )
            for i in range(n)
        )
    )
    assert [r.status for r in responses] == [200] * n


# --------------------------------------------------------------- fast tier


def test_cancel_token_runs_callbacks_once_and_late():
    token = CancelToken()
    fired = []
    token.add_callback(lambda: fired.append("early"))
    assert token.cancel("client_disconnect") is True
    assert token.cancel("client_disconnect") is False  # one-shot
    token.add_callback(lambda: fired.append("late"))  # runs inline
    assert fired == ["early", "late"]
    assert token.cancelled and token.reason == "client_disconnect"


def test_all_of_fires_only_when_every_member_cancelled():
    """Dedup-group cancellation semantics: the shared generation aborts
    only when EVERY duplicate requester is gone."""
    t1, t2 = CancelToken(), CancelToken()
    combined = all_of([t1, t2])
    t1.cancel("client_disconnect")
    assert not combined.cancelled  # t2's client is still waiting
    t2.cancel("client_disconnect")
    assert combined.cancelled
    # a member that can never cancel makes the group uncancellable
    assert all_of([CancelToken(), None]) is None
    assert all_of([]) is None
    # single-member group degenerates to the member itself
    t3 = CancelToken()
    assert all_of([t3]) is t3


async def test_dedup_group_sends_composite_cancel_token(dry_config):
    """The batcher hands the backend a GROUP-level token: one duplicate
    requester disconnecting must not cancel it while its twin waits."""
    engine = VGTEngine(dry_config)
    batcher = RequestBatcher(engine, dry_config)
    await batcher.start()
    seen = {}

    class RecordingBackend:
        async def generate_settled_async(
            self, prompts, params, cancel_tokens=None
        ):
            seen["tokens"] = cancel_tokens
            return [
                GenerationResult(text="done", num_tokens=4)
                for _ in prompts
            ]

    engine.backend = RecordingBackend()
    try:
        t1, t2 = CancelToken(), CancelToken()
        first, second = await asyncio.gather(
            batcher.submit("twin prompt", max_tokens=4, temperature=0.0,
                           cancel_token=t1),
            batcher.submit("twin prompt", max_tokens=4, temperature=0.0,
                           cancel_token=t2),
        )
        assert first["text"] == second["text"] == "done"
        assert len(seen["tokens"]) == 1  # deduped into one group
        combined = seen["tokens"][0]
        t1.cancel("client_disconnect")
        assert not combined.cancelled
        t2.cancel("client_disconnect")
        assert combined.cancelled
    finally:
        await batcher.stop()


def test_scheduler_sheds_waiting_request_past_deadline():
    from vgate_tpu.backends.base import SamplingParams
    from vgate_tpu.runtime.kv_cache import PageAllocator
    from vgate_tpu.runtime.scheduler import Scheduler
    from vgate_tpu.runtime.sequence import Sequence

    sched = Scheduler(
        allocator=PageAllocator(16),
        max_slots=0,  # nothing can admit: the seq must shed in queue
        page_size=4,
        prefill_buckets=[16],
        max_model_len=64,
    )
    seq = Sequence(
        prompt_ids=[1, 2, 3],
        params=SamplingParams(max_tokens=4, timeout_s=0.01),
    )
    sched.add(seq)
    time.sleep(0.03)
    assert sched.try_admit() is None
    assert seq.status.value == "failed"
    assert isinstance(seq.error, DeadlineExceededError)
    assert sched.total_deadline_shed == 1


async def test_timeout_header_invalid_is_422():
    client = await _client()
    try:
        for bad in ("nan-seconds", "-1", "0"):
            resp = await client.post(
                "/v1/chat/completions",
                json=_chat_body(),
                headers={"X-Request-Timeout": bad},
            )
            assert resp.status == 422, bad
    finally:
        await client.close()


async def test_timeout_header_and_body_accepted():
    client = await _client()
    try:
        resp = await client.post(
            "/v1/chat/completions",
            json=_chat_body(timeout=5.0),
            headers={"X-Request-Timeout": "10"},
        )
        assert resp.status == 200
    finally:
        await client.close()


async def test_cancel_token_dequeues_queued_request():
    """A queued request whose client disconnects leaves the batch queue
    immediately and fails with the typed ClientDisconnectError."""
    config = load_config(
        model={"engine_type": "dry_run"},
        # park the queue: nothing fires for 60s at batch size 64
        batch={"max_batch_size": 64, "max_wait_time_ms": 60000.0},
        logging={"level": "WARNING"},
    )
    engine = VGTEngine(config)
    batcher = RequestBatcher(engine, config)
    await batcher.start()
    try:
        token = CancelToken()
        task = asyncio.ensure_future(
            batcher.submit("park me", cancel_token=token)
        )
        await asyncio.sleep(0.05)
        assert len(batcher._queue) == 1
        token.cancel("client_disconnect")
        with pytest.raises(ClientDisconnectError):
            await asyncio.wait_for(task, 2.0)
        assert len(batcher._queue) == 0
    finally:
        await batcher.stop()


async def test_result_cache_never_stores_partial_results(dry_config):
    """Regression (ISSUE 2 satellite): a cancelled/deadline-shed batch
    result (finish_reason "abort"/"deadline") must never enter the
    ResultCache — the next identical request gets a FULL generation."""
    engine = VGTEngine(dry_config)
    batcher = RequestBatcher(engine, dry_config)
    await batcher.start()

    class FlakyBackend:
        mode = "abort"

        async def generate_settled_async(
            self, prompts, params, cancel_tokens=None
        ):
            if self.mode == "abort":
                return [
                    GenerationResult(
                        text="par", num_tokens=2, finish_reason="abort"
                    )
                    for _ in prompts
                ]
            return [
                GenerationResult(
                    text="the full completion",
                    num_tokens=8,
                    finish_reason="stop",
                )
                for _ in prompts
            ]

    engine.backend = FlakyBackend()
    try:
        first = await batcher.submit("same prompt", max_tokens=8,
                                     temperature=0.0)
        assert first["finish_reason"] == "abort"
        engine.backend.mode = "stop"
        second = await batcher.submit("same prompt", max_tokens=8,
                                      temperature=0.0)
        # a cached partial would come back cached=True with text "par"
        assert second["cached"] is False
        assert second["finish_reason"] == "stop"
        assert second["text"] == "the full completion"
        # completed results still cache as before
        third = await batcher.submit("same prompt", max_tokens=8,
                                     temperature=0.0)
        assert third["cached"] is True
    finally:
        await batcher.stop()


async def test_drain_rejects_admission_and_flips_ready():
    """begin_drain: ready → 503 "draining" (+Retry-After), live stays
    200, new chat/embeddings admissions shed 503, batcher rejects with
    the retryable typed error."""
    client = await _client()
    app = client.server.app
    try:
        done = []
        app["drain"].on_complete = lambda: done.append(True)
        app["drain"].begin()
        resp = await client.get("/health/ready")
        assert resp.status == 503
        body = await resp.json()
        assert body["engine"]["state"] == "draining"
        assert "Retry-After" in resp.headers
        resp = await client.get("/health")
        assert resp.status == 503
        assert (await resp.json())["status"] == "draining"
        resp = await client.get("/health/live")
        assert resp.status == 200
        resp = await client.post("/v1/chat/completions", json=_chat_body())
        assert resp.status == 503
        assert "Retry-After" in resp.headers
        resp = await client.post("/v1/embeddings", json={"input": "x"})
        assert resp.status == 503
        with pytest.raises(ServerDrainingError):
            await app["batcher"].submit("direct")
        assert await app["drain"].wait_drained(5.0)
        assert done == [True]
    finally:
        await client.close()


async def test_drain_completes_inflight_dry_run():
    """In-flight requests complete through the drain (zero drops) while
    admission is already shedding — the drain_check.sh scenario
    in-process."""
    faults.arm(
        "backend_generate", mode="delay", delay_s=0.3, times=-1
    )
    client = await _client(
        batch={"max_batch_size": 64, "max_wait_time_ms": 30.0},
    )
    app = client.server.app
    try:
        done = []
        app["drain"].on_complete = lambda: done.append(True)
        inflight = [
            asyncio.ensure_future(
                client.post("/v1/chat/completions", json=_chat_body(i))
            )
            for i in range(4)
        ]
        # wait until all 4 are actually in flight (a fixed sleep races
        # the event loop under full-suite load: drain would flip
        # readiness before the POSTs reach the handler and shed them)
        deadline = time.monotonic() + 5.0
        while (
            app["drain"].inflight() < 4
            and time.monotonic() < deadline
        ):
            await asyncio.sleep(0.02)
        assert app["drain"].inflight() == 4
        app["drain"].begin()
        resp = await client.get("/health/ready")
        assert resp.status == 503
        responses = await asyncio.gather(*inflight)
        assert [r.status for r in responses] == [200] * 4
        assert await app["drain"].wait_drained(5.0)
        # `on_complete` is a callback of its own (`call_soon`, ahead of
        # the event).  A waiter that was waiting finds it run; this one
        # may come when the drain has ALREADY finished, in the very turn
        # of the loop in which the last response woke it, and then stands
        # ahead of the callback in the loop's queue (seen beside five
        # busy workers, PR 55): wait for the callback, not for a time
        for _ in range(100):
            if done:
                break
            await asyncio.sleep(0)
        assert done == [True]
        assert app["drain"].aborted_stragglers == 0
    finally:
        await client.close()


async def test_wait_drained_returns_after_on_complete_ran():
    """A waiter that was already waiting when the drain finished finds
    ``on_complete`` run: the callback is scheduled ahead of the event
    that wakes the waiter.  Fifty drains, each with one request in
    flight while the waiter waits."""
    for _ in range(50):
        inflight = [1]
        done = []
        drain = DrainController(
            poll_s=0.005, inflight=lambda: inflight[0],
            on_complete=lambda: done.append(True))
        drain.begin()
        waiter = asyncio.ensure_future(drain.wait_drained(5.0))
        await asyncio.sleep(0.01)  # the waiter waits, the drain polls
        assert not waiter.done()
        inflight[0] = 0
        assert await waiter
        assert done == [True]


# --------------------------------------------------------------- slow tier


async def _raw_disconnecting_post(host, port, body: dict, after_s: float):
    """Open a raw TCP connection, POST, then close the socket after
    ``after_s`` — a REAL mid-request client disconnect (TestClient
    cancellation may return the connection to its pool instead)."""
    payload = json.dumps(body).encode()
    reader, writer = await asyncio.open_connection(host, port)
    writer.write(
        (
            "POST /v1/chat/completions HTTP/1.1\r\n"
            f"Host: {host}\r\n"
            "Content-Type: application/json\r\n"
            f"Content-Length: {len(payload)}\r\n"
            "Connection: close\r\n\r\n"
        ).encode()
        + payload
    )
    await writer.drain()
    await asyncio.sleep(after_s)
    writer.close()
    try:
        await writer.wait_closed()
    except (ConnectionError, OSError):
        pass


async def _assert_disconnect_frees_resources(get_stats, chat, host, port):
    """Shared body for the two disconnect transports: warm up, slow the
    decode, disconnect mid-generation, assert the abort released the
    slot and KV pages promptly."""
    resp_status = await chat(_chat_body(max_tokens=2, min_tokens=2))
    assert resp_status == 200
    # warm the chunk-8 decode ladder the 48-token request below uses
    # (different prompt so it can't cache-hit); without this a
    # first-contact XLA compile can block the engine tick for seconds
    # right when the abort should land
    resp_status = await chat(_chat_body(7, max_tokens=48, min_tokens=48))
    assert resp_status == 200
    # ~0.2s per decode-chunk dispatch → a 48-token request runs for
    # seconds, far past the 0.4s disconnect below
    faults.arm("decode_step", mode="delay", delay_s=0.2, times=-1)
    await _raw_disconnecting_post(
        host, port, _chat_body(max_tokens=48, min_tokens=48), after_s=0.4
    )
    # the abort must land within ~a decode tick (0.2s chunks here, plus
    # watcher/cancellation latency) — 8s is generous; completing
    # naturally instead would leave aborted == 0 and fail below
    deadline = time.perf_counter() + 8.0
    sched = None
    while time.perf_counter() < deadline:
        sched = (await get_stats())["engine"]["scheduler"]
        if (
            sched["running"] == 0
            and sched["used_pages"] == 0
            and sched["aborted"] >= 1
        ):
            break
        await asyncio.sleep(0.05)
    assert sched is not None
    assert sched["running"] == 0, sched
    assert sched["used_pages"] == 0, sched
    assert sched["aborted"] >= 1, sched


@pytest.mark.slow
async def test_client_disconnect_frees_kv_and_slot_production_mode():
    """(a) Production server semantics (handler_cancellation=False, the
    aiohttp default under run_app): the DISCONNECT WATCHER notices the
    closed transport and fires the CancelToken — slot and KV pages free
    within a tick."""
    import aiohttp
    from aiohttp import web as aioweb

    config = load_config(
        **JAX_TINY,
        batch={"max_batch_size": 8, "max_wait_time_ms": 10.0},
        logging={"level": "WARNING"},
    )
    runner = aioweb.AppRunner(create_app(config))
    await runner.setup()
    site = aioweb.TCPSite(runner, "127.0.0.1", 0)
    await site.start()
    port = site._server.sockets[0].getsockname()[1]
    base = f"http://127.0.0.1:{port}"
    try:
        async with aiohttp.ClientSession() as session:

            async def chat(body):
                async with session.post(
                    f"{base}/v1/chat/completions", json=body
                ) as resp:
                    await resp.read()
                    return resp.status

            async def get_stats():
                async with session.get(f"{base}/stats") as resp:
                    return await resp.json()

            await _assert_disconnect_frees_resources(
                get_stats, chat, "127.0.0.1", port
            )
    finally:
        faults.reset()
        await runner.cleanup()


@pytest.mark.slow
async def test_client_disconnect_frees_kv_and_slot_cancellation_mode():
    """(a') The same disconnect under handler_cancellation=True (what
    TestServer enables): aiohttp cancels the handler task, and
    batcher.submit's CancelledError path fires the token instead of the
    watcher.  Same observable outcome: resources free within a tick."""
    client = await _client(**JAX_TINY)
    try:

        async def chat(body):
            resp = await client.post("/v1/chat/completions", json=body)
            await resp.read()
            return resp.status

        async def get_stats():
            return await (await client.get("/stats")).json()

        await _assert_disconnect_frees_resources(
            get_stats, chat, str(client.server.host), client.server.port
        )
    finally:
        faults.reset()
        await client.close()


@pytest.mark.slow
async def test_deadline_504_without_failing_batchmates():
    """(b) A 50 ms deadline against a slow fault-injected backend gets a
    504 with partial-tokens metadata while its batchmate completes."""
    client = await _client(**JAX_TINY)
    try:
        await _warm(client, 1)
        # warm the EXACT program variants the timed pair compiles —
        # B=2 prefill plus the chunk-8/4/2/1 decode ladder with the
        # min_tokens masking arrays — so no first-contact XLA compile
        # (seconds on CPU) can stall the tick past the 50ms deadline.
        # Different prompts (i=3,4) than the timed pair: identical
        # bodies would let the timed requests cache-hit these results.
        warm_pair = await asyncio.gather(
            client.post(
                "/v1/chat/completions",
                json=_chat_body(3, max_tokens=40, min_tokens=40),
            ),
            client.post(
                "/v1/chat/completions",
                json=_chat_body(4, max_tokens=3, min_tokens=3),
            ),
        )
        assert [r.status for r in warm_pair] == [200, 200]
        faults.arm("decode_step", mode="delay", delay_s=0.1, times=-1)
        doomed, patient = await asyncio.gather(
            client.post(
                "/v1/chat/completions",
                json=_chat_body(1, max_tokens=40, min_tokens=40),
                headers={"X-Request-Timeout": "0.05"},
            ),
            client.post(
                "/v1/chat/completions",
                json=_chat_body(2, max_tokens=3, min_tokens=3),
            ),
        )
        assert doomed.status == 504
        err = (await doomed.json())["error"]
        assert err["type"] == "timeout_error"
        assert "partial_tokens" in err
        assert patient.status == 200
        body = await patient.json()
        assert body["usage"]["completion_tokens"] == 3
        # the shed freed the doomed request's residency
        stats = await (await client.get("/stats")).json()
        sched = stats["engine"]["scheduler"]
        assert sched["running"] == 0 and sched["used_pages"] == 0
        assert sched["deadline_shed"] >= 1
    finally:
        faults.reset()
        await client.close()


@pytest.mark.slow
async def test_sigterm_drain_completes_every_inflight_request():
    """(c) SIGTERM under load: every in-flight request completes, and
    /health/ready returns 503 ("draining") throughout the drain."""
    client = await _client(
        **JAX_TINY,
        batch={"max_batch_size": 8, "max_wait_time_ms": 10.0},
    )
    app = client.server.app
    try:
        await _warm(client, 1)
        await _warm(client, 4)  # the load's B=4 prefill shape
        faults.arm("decode_step", mode="delay", delay_s=0.05, times=-1)
        done = []
        app["drain"].on_complete = lambda: done.append(True)
        inflight = [
            asyncio.ensure_future(
                client.post(
                    "/v1/chat/completions",
                    json=_chat_body(i, max_tokens=6 + i, min_tokens=6 + i),
                )
            )
            for i in range(4)
        ]
        await asyncio.sleep(0.2)  # sequences decoding
        # the REAL signal path: _on_startup registered drain.begin
        assert app.get("drain_signal_installed")
        os.kill(os.getpid(), signal.SIGTERM)
        # ready must report draining for the WHOLE drain window
        ready_seen = []
        for _ in range(3):
            resp = await client.get("/health/ready")
            ready_seen.append(
                (resp.status, (await resp.json())["engine"]["state"])
            )
            await asyncio.sleep(0.05)
        assert all(s == (503, "draining") for s in ready_seen), ready_seen
        responses = await asyncio.gather(*inflight)
        assert [r.status for r in responses] == [200] * 4
        for i, r in enumerate(responses):
            body = await r.json()
            assert body["usage"]["completion_tokens"] == 6 + i
        assert await app["drain"].wait_drained(10.0)
        assert done == [True]
        assert app["drain"].aborted_stragglers == 0
    finally:
        faults.reset()
        await client.close()


@pytest.mark.slow
async def test_abort_by_seq_id_sheds_within_a_tick():
    """EngineCore.abort(seq_id) — the request-scoped abort surface:
    marks exactly the target sequence, which sheds (slot + KV pages
    freed, finish_reason "abort") within a tick of the engine thread
    picking up the command."""
    from vgate_tpu.backends.base import SamplingParams

    config = load_config(**JAX_TINY, logging={"level": "WARNING"})
    engine = VGTEngine(config)
    try:
        core = engine.backend.core  # EngineSupervisor delegates to core
        warm = core.submit_prompt(
            "warm it up first", SamplingParams(max_tokens=2, temperature=0.0)
        )
        warm.done_event.wait(120)
        faults.arm("decode_step", mode="delay", delay_s=0.2, times=-1)
        seq = core.submit_prompt(
            "abort me by id please",
            SamplingParams(max_tokens=40, min_tokens=40, temperature=0.0),
        )
        bystander = core.submit_prompt(
            "leave me decoding",
            SamplingParams(max_tokens=6, min_tokens=6, temperature=0.0),
        )
        await asyncio.sleep(0.3)
        core.abort(seq.seq_id)
        deadline = time.perf_counter() + 8.0
        while time.perf_counter() < deadline and not seq.done_event.is_set():
            await asyncio.sleep(0.05)
        assert seq.done_event.is_set()
        assert seq.finish_reason == "abort"
        while (
            time.perf_counter() < deadline
            and not bystander.done_event.is_set()
        ):
            await asyncio.sleep(0.05)
        assert bystander.finish_reason in ("stop", "length")
        assert bystander.num_output_tokens == 6
        sched = engine.backend.get_stats()["scheduler"]
        assert sched["running"] == 0 and sched["used_pages"] == 0
    finally:
        faults.reset()
        engine.shutdown()


@pytest.mark.slow
async def test_drain_timeout_aborts_stragglers_cleanly():
    """Past lifecycle.drain_timeout_s the drain aborts stragglers: their
    responses settle (finish_reason "abort", no hang) and the drain
    still completes."""
    client = await _client(
        **JAX_TINY,
        lifecycle={"drain_timeout_s": 0.3, "drain_poll_ms": 20.0},
    )
    app = client.server.app
    try:
        await _warm(client, 1)
        await _warm(client, 2)  # the straggler pair's B=2 prefill shape
        faults.arm("decode_step", mode="delay", delay_s=0.2, times=-1)
        done = []
        app["drain"].on_complete = lambda: done.append(True)
        inflight = [
            asyncio.ensure_future(
                client.post(
                    "/v1/chat/completions",
                    json=_chat_body(i, max_tokens=40, min_tokens=40),
                )
            )
            for i in range(2)
        ]
        await asyncio.sleep(0.3)  # decoding, will outlive the 0.3s window
        app["drain"].begin()
        responses = await asyncio.gather(*inflight)
        # aborted mid-generation but SETTLED: 200 with partial text and
        # finish_reason "abort", never a dropped connection
        for r in responses:
            assert r.status == 200
            body = await r.json()
            assert body["choices"][0]["finish_reason"] == "abort"
        assert await app["drain"].wait_drained(10.0)
        assert done == [True]
        assert app["drain"].aborted_stragglers >= 1
        stats = await (await client.get("/stats")).json()
        sched = stats["engine"]["scheduler"]
        assert sched["running"] == 0 and sched["used_pages"] == 0
    finally:
        faults.reset()
        await client.close()
