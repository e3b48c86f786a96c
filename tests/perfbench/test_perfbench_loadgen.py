"""The load generator against a small in-process SSE server: what it
records, what it scores, and that it notices a wrong token count."""

import asyncio
import json
import socket

import pytest
from aiohttp import web

from perfbench import stats, traffic
from perfbench.loadgen import Driver, _content_len, chat_body


def chunk(content):
    body = {"id": "x", "object": "chat.completion.chunk", "created": 1,
            "model": "m", "choices": [{"index": 0, "delta": {
                "content": content}, "finish_reason": None}]}
    return f"data: {json.dumps(body)}\n\n".encode()


class FakeGateway:
    """Streams max_tokens letters, 2 ms apart, two to a burst."""

    def __init__(self, short_by=0, refuse_every=0):
        self.short_by, self.refuse_every = short_by, refuse_every
        self.seen = []

    async def chat(self, request):
        body = await request.json()
        self.seen.append(body)
        if self.refuse_every and len(self.seen) % self.refuse_every == 0:
            return web.json_response({"error": {"reason": "overloaded"}},
                                     status=503)
        resp = web.StreamResponse(
            headers={"Content-Type": "text/event-stream"})
        await resp.prepare(request)
        await resp.write(b'data: {"choices": [{"delta": {"role": '
                         b'"assistant"}}]}\n\n')
        n = body["max_tokens"] - self.short_by
        for i in range(0, n, 2):
            await asyncio.sleep(0.002)
            await resp.write(chunk("a") + (chunk("b") if i + 1 < n else b""))
        prompt = traffic.flattened_len(body["messages"])
        usage = {"choices": [], "usage": {
            "prompt_tokens": prompt, "completion_tokens": n}}
        await resp.write(f"data: {json.dumps(usage)}\n\n".encode())
        await resp.write(b"data: [DONE]\n\n")
        return resp

    async def start(self):
        app = web.Application()
        app.router.add_post("/v1/chat/completions", self.chat)
        self.runner = web.AppRunner(app)
        await self.runner.setup()
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        await web.TCPSite(self.runner, "127.0.0.1", port).start()
        return f"http://127.0.0.1:{port}"


TRAFFIC = {
    "loop": "open", "lead_in_s": 0.3, "drain_s": 2.0,
    "arrival": {"process": "poisson-fixed-count"},
    "prompt_tokens": {"kind": "uniform", "lo": 24, "hi": 48},
    "output_tokens": {"kind": "uniform", "lo": 8, "hi": 20},
    "pairing_seed": 1, "sharing": {"kind": "none"},
}


def test_quick_content_parse_agrees_with_json():
    assert _content_len(chunk("a")[6:].rstrip()) == 1
    assert _content_len(chunk("abc")[6:].rstrip()) == 3
    assert _content_len(chunk("")[6:].rstrip()) == 0
    assert _content_len(chunk('q"uo\\te')[6:].rstrip()) is None
    assert _content_len(b'{"choices": [], "usage": {}}') is None


def test_body_pins_the_length():
    req = traffic.Request(messages=[{"role": "user", "content": "hi"}],
                          prompt_tokens=19, max_tokens=33)
    body = chat_body(req, "m", {"100": 100.0})
    assert body["max_tokens"] == body["min_tokens"] == 33
    assert body["stream"] and body["stream_options"]["include_usage"]
    assert body["temperature"] == 0 and body["logit_bias"] == {"100": 100.0}


async def test_open_loop_records_and_scores():
    gw = FakeGateway()
    base = await gw.start()
    try:
        plan = traffic.build_plan(TRAFFIC, {"rate": 40.0}, 5, 1.0)
        driver = Driver(base, "m", {})
        marks = {}
        async def mark(session):
            marks["n"] = len(driver.samples)
        await driver.run(plan, at=[(0.5, mark)], extra_s=0.3)
    finally:
        await gw.runner.cleanup()
    scored = stats.scored(driver.samples, "open", driver.t_open,
                          driver.t_close)
    assert len(scored) == 40 and all(s.ok for s in scored)
    assert 0 < marks["n"] < len(plan.requests)
    assert all(s.chunk_tokens == s.max_tokens for s in scored)
    assert all(s.chunks <= s.max_tokens for s in scored)
    assert all(s.ttft_s >= 0 and s.tpot_s > 0 for s in scored)
    assert len(driver.send_lags) >= len(driver.samples) - 1
    assert stats.percentile(driver.send_lags, 99) < 0.25
    # arrivals went on after the window (here for the 0.3 s asked for),
    # and nothing after the close was counted
    late = [s for s in driver.samples if s.segment == "drain"]
    assert late and all(s.due_t >= driver.t_close for s in late)
    assert driver.t_stop == pytest.approx(driver.t_close + 0.3)
    assert 0 < driver.window_tokens <= sum(s.max_tokens for s in
                                           driver.samples)


async def test_closed_loop_counts_tokens_by_arrival():
    gw = FakeGateway()
    base = await gw.start()
    try:
        tr = dict(TRAFFIC, loop="closed", requests_per_client=50)
        plan = traffic.build_plan(tr, {"clients": 4, "resumed": 2}, 2, 1.0)
        driver = Driver(base, "m", {})
        await driver.run(plan)
    finally:
        await gw.runner.cleanup()
    done = [s for s in driver.samples if s.done]
    assert len(done) > 8  # every client went round several times
    assert driver.heartbeat_lags and not driver.send_lags
    scored = stats.scored(driver.samples, "closed", driver.t_open,
                          driver.t_close)
    assert scored and all(s.ok for s in scored)
    # tokens are counted when they arrive, whoever they belong to
    assert driver.window_tokens >= sum(
        s.max_tokens for s in scored if s.first_t >= driver.t_open)
    assert any(s.resumed for s in driver.samples)


@pytest.mark.parametrize("kwargs,what", [
    ({"short_by": 1}, "count"), ({"refuse_every": 3}, "refused")])
async def test_a_wrong_answer_is_a_failed_request(kwargs, what):
    gw = FakeGateway(**kwargs)
    base = await gw.start()
    try:
        plan = traffic.build_plan(TRAFFIC, {"rate": 30.0}, 5, 0.5)
        driver = Driver(base, "m", {})
        await driver.run(plan)
    finally:
        await gw.runner.cleanup()
    scored = stats.scored(driver.samples, "open", 0, 0)
    failed = [s for s in scored if not s.ok]
    if what == "count":
        assert len(failed) == len(scored) == 15
    else:
        assert 0 < len(failed) < len(scored)
        assert all(s.status == 503 for s in failed)
