"""The load generator: asyncio + aiohttp, one process, never imports JAX.

Open loop: every arrival is its own task sleeping until its ABSOLUTE due
time; nothing waits for an earlier reply, and latency runs from the due
time.  Closed loop: a fixed number of clients, each sending its next
request when the previous reply has ended.  Both record, per request,
what a client sees: status, first and last content chunk, ``usage``,
``[DONE]``.

The generator watches itself: in an open loop the lateness of each send,
in a closed loop (nothing is scheduled there) the lateness of a 10 ms
heartbeat timer on the same event loop.  Past ``stats.SEND_LAG_BOUND_S``
at the 99th percentile the numbers measure this host, not the server:
the run reports the lag (a per-layer metric, and ``send_lag_p99_s`` in
every line) and warns on stderr; ``correct`` is about outputs only.

Origin: ``vgate_tpu/loadlab/driver.py`` (open loop, due-time latency,
``send_lag_s``), plus the closed loop, lead-in / window / drain, and
token counting by arrival time.
"""

from __future__ import annotations

import asyncio
import json
from typing import Any, Awaitable, Callable, Dict, List, Optional

import aiohttp

from .stats import Sample
from .traffic import Plan, Request

HEARTBEAT_S = 0.010
CHAT = "/v1/chat/completions"
_CONTENT = b'"content": "'


def chat_body(req: Request, model: str, logit_bias: Dict[str, float],
              stream: bool = True, **extra: Any) -> Dict[str, Any]:
    """min_tokens == max_tokens pins the length (random weights may emit
    EOS anywhere).  ``logit_bias`` keeps every generated token inside the
    byte tokenizer's printable range, so that each token reaches the
    client as a visible SSE chunk: without it nearly every id of a
    150k-entry vocabulary decodes to no text and the stream stays empty
    until its end."""
    body: Dict[str, Any] = {
        "model": model, "messages": req.messages,
        "max_tokens": req.max_tokens, "min_tokens": req.max_tokens,
        "temperature": 0, **extra,
    }
    if logit_bias:
        body["logit_bias"] = logit_bias
    if stream:
        body["stream"] = True
        body["stream_options"] = {"include_usage": True}
    return body


def visible_bias(traffic: Dict[str, Any]) -> Dict[str, float]:
    """The ``logit_bias`` of a traffic file's ``output_visible``: byte
    tokenizer ids (runtime/tokenizer.py ByteTokenizer.OFFSET = 3) of its
    letters."""
    visible = traffic["output_visible"]
    return {str(3 + ord(c)): float(visible["bias"])
            for c in visible["letters"]}


def _content_len(line: bytes) -> Optional[int]:
    """Characters of delta content in one SSE data line, by a byte search
    (json.loads per token is most of a generator's CPU at thousands of
    tokens a second); None when the quick look is not conclusive."""
    i = line.find(_CONTENT)
    if i < 0:
        return None
    start = i + len(_CONTENT)
    j = line.find(b'"', start)
    if j < 0 or b"\\" in line[start:j]:
        return None
    return j - start


class Driver:
    """Runs one plan against ``base_url`` and keeps every sample."""

    def __init__(self, base_url: str, model: str,
                 logit_bias: Dict[str, float],
                 request_timeout_s: float = 300.0) -> None:
        self.base_url = base_url
        self.model = model
        self.logit_bias = logit_bias
        self.timeout = aiohttp.ClientTimeout(total=request_timeout_s)
        self.samples: List[Sample] = []
        self.send_lags: List[float] = []
        self.heartbeat_lags: List[float] = []
        self.window_tokens = 0
        self.window_prompt_tokens = 0
        self.t_open = float("inf")
        self.t_close = float("inf")
        self.t_stop = float("inf")  # the load goes on until here
        self.in_flight = 0
        self.in_flight_marks: Dict[str, int] = {}

    # ------------------------------------------------------ one request

    async def fire(self, session: aiohttp.ClientSession, req: Request,
                   due_t: float) -> Sample:
        loop = asyncio.get_running_loop()
        sample = Sample(
            segment=req.segment, due_t=due_t,
            prompt_tokens=req.prompt_tokens, max_tokens=req.max_tokens,
            resumed=req.resumed,
        )
        self.samples.append(sample)
        sample.sent_t = loop.time()
        self.in_flight += 1
        try:
            async with session.post(
                self.base_url + CHAT, timeout=self.timeout,
                json=chat_body(req, self.model, self.logit_bias),
            ) as resp:
                sample.status = resp.status
                if resp.status != 200:
                    sample.error = (await resp.text())[:200]
                else:
                    await self._consume(resp, sample, loop)
        except (TimeoutError, asyncio.TimeoutError):
            sample.error = "client_timeout"
        except aiohttp.ClientError as exc:
            sample.error = f"transport: {exc!r}"[:200]
        finally:
            self.in_flight -= 1
            sample.end_t = loop.time()
        return sample

    async def _consume(self, resp: aiohttp.ClientResponse, sample: Sample,
                       loop: asyncio.AbstractEventLoop) -> None:
        async for raw in resp.content:
            if not raw.startswith(b"data: "):
                continue
            line = raw[6:].rstrip()
            if line == b"[DONE]":
                sample.done = True
                break
            n = _content_len(line)
            if n is None:
                event = json.loads(line)
                if "error" in event:
                    sample.error = str(event["error"].get("type"))
                    continue
                usage = event.get("usage")
                if usage:
                    sample.usage_prompt = usage.get("prompt_tokens")
                    sample.usage_completion = usage.get("completion_tokens")
                choices = event.get("choices") or []
                delta = choices[0].get("delta", {}) if choices else {}
                n = len(delta.get("content") or "")
            if n <= 0:
                continue
            now = loop.time()
            if sample.first_t is None:
                sample.first_t = now
                if self.t_open <= now < self.t_close:
                    self.window_prompt_tokens += sample.prompt_tokens
            else:
                sample.max_gap_s = max(sample.max_gap_s, now - sample.last_t)
            sample.last_t = now
            sample.chunks += 1
            sample.chunk_tokens += n  # one letter is one byte token
            if self.t_open <= now < self.t_close:
                self.window_tokens += n

    # ------------------------------------------------------------ loops

    async def _open_one(self, session: aiohttp.ClientSession, req: Request,
                        t0: float) -> None:
        loop = asyncio.get_running_loop()
        due_t = t0 + req.due_s
        delay = due_t - loop.time()
        if delay > 0:
            await asyncio.sleep(delay)
        self.send_lags.append(max(0.0, loop.time() - due_t))
        await self.fire(session, req, due_t)

    async def _client(self, session: aiohttp.ClientSession,
                      queue: List[Request]) -> None:
        loop = asyncio.get_running_loop()
        for req in queue:
            if loop.time() >= self.t_stop:
                return
            await self.fire(session, req, loop.time())

    async def _heartbeat(self) -> None:
        loop = asyncio.get_running_loop()
        while True:
            due = loop.time() + HEARTBEAT_S
            await asyncio.sleep(HEARTBEAT_S)
            self.heartbeat_lags.append(max(0.0, loop.time() - due))

    async def run(
        self, plan: Plan,
        at: Optional[List[tuple]] = None,
        on_start: Optional[Callable[[float], None]] = None,
        extra_s: float = 0.0,
    ) -> None:
        """Lead-in, window, drain.  ``at`` is a list of ``(offset_s,
        coroutine function(session))`` run at offsets from the window's
        opening (snapshots, the profile request); they share the loop and
        the session, and their failures surface after the run.  The same
        load goes on for ``extra_s`` after the window (a traced run takes
        its profile there, so that the tracer's cost stays out of the
        window); nothing after the window's close is counted."""
        loop = asyncio.get_running_loop()
        connector = aiohttp.TCPConnector(limit=0)  # no connection cap
        async with aiohttp.ClientSession(connector=connector) as session:
            t0 = loop.time()
            self.t_open = t0 + plan.lead_in_s
            self.t_close = self.t_open + plan.seconds
            self.t_stop = self.t_close + extra_s
            if on_start is not None:
                on_start(self.t_open)
            beat = asyncio.ensure_future(self._heartbeat())
            side = [
                asyncio.ensure_future(self._at(session, off, fn))
                for off, fn in (at or [])
            ]
            if plan.loop == "open":
                work = [
                    asyncio.ensure_future(self._open_one(session, r, t0))
                    for r in plan.requests
                ]
                await self._until_scored_done(plan)
            else:
                work = [
                    asyncio.ensure_future(self._client(session, q))
                    for q in plan.clients
                ]
                await asyncio.sleep(max(0.0, self.t_stop - loop.time()))
            for task in work:
                task.cancel()
            await asyncio.gather(*work, return_exceptions=True)
            beat.cancel()
            await asyncio.gather(beat, return_exceptions=True)
            results = await asyncio.gather(*side, return_exceptions=True)
        for r in results:
            if isinstance(r, BaseException):
                raise r

    async def _at(self, session: aiohttp.ClientSession, offset_s: float,
                  fn: Callable[[aiohttp.ClientSession], Awaitable[None]]
                  ) -> None:
        loop = asyncio.get_running_loop()
        await asyncio.sleep(max(0.0, self.t_open + offset_s - loop.time()))
        await fn(session)

    async def _until_scored_done(self, plan: Plan) -> None:
        """Arrivals go on at the same rate after the window until every
        scored request has finished, bounded by ``drain_s``: the last
        scored request then sees the same load as the first."""
        loop = asyncio.get_running_loop()
        end = self.t_close + plan.drain_s
        while loop.time() < end:
            await asyncio.sleep(0.05)
            now = loop.time()
            if now < self.t_stop:
                continue
            scored = [s for s in self.samples if s.segment == "window"]
            pending = [s for s in scored if s.end_t is None]
            unsent = sum(
                1 for r in plan.requests if r.segment == "window"
            ) - len(scored)
            if not pending and unsent <= 0:
                return

    def mark_in_flight(self, name: str) -> None:
        self.in_flight_marks[name] = self.in_flight

    def lag_samples(self, loop_kind: str) -> List[float]:
        return self.send_lags if loop_kind == "open" else self.heartbeat_lags
