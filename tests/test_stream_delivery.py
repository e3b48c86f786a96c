"""The seam between the engine thread and the gateway's event loop: a
readback's tokens cross it as ONE list per stream and ONE wake-up per
loop, and the streamed text does not depend on how the tokens were cut
into deliveries."""

import asyncio
import dataclasses
import threading
from types import SimpleNamespace

import pytest

from vgate_tpu.backends.base import SamplingParams
from vgate_tpu.backends.jax_backend import JaxTPUBackend, _LoopHandoff
from vgate_tpu.models.specs import spec_for_model_id
from vgate_tpu.runtime.engine_core import EngineCore
from vgate_tpu.runtime.sequence import SeqStatus, Sequence
from vgate_tpu.runtime.tokenizer import (
    ByteTokenizer,
    IncrementalDetokenizer,
)

BYTES = ByteTokenizer(spec_for_model_id("tiny-dense"))


class PieceTokenizer:
    """Word pieces, sentencepiece fashion: a piece that begins a word
    carries its space, and ``decode`` drops the space that would begin
    the text, so ``decode(ids[k:])`` is NOT the tail of ``decode(ids)``
    wherever ``ids[k]`` begins a word."""

    PIECES = ["<eos>", "<bos>", " Hello", " wor", "ld", "!", " STOP", " and",
              " more", " x", "é"]
    eos_id, bos_id = 0, 1

    def decode(self, ids):
        text = "".join(self.PIECES[i] for i in ids if i > 1)
        return text[1:] if text.startswith(" ") else text

    def ids(self, *pieces):
        return [self.PIECES.index(p) for p in pieces]


PIECES = PieceTokenizer()


class ScriptedCore:
    """The engine's half of the seam with the sampled tokens scripted:
    EngineCore's own append / stop / finish logic on a real Sequence,
    one ``readback`` per delivery, from a thread that is not the event
    loop's (as the engine's is not)."""

    _maybe_finish = EngineCore._maybe_finish
    _hit_stop_string = EngineCore._hit_stop_string
    final_text = EngineCore.final_text
    lp_entry = EngineCore.lp_entry

    def __init__(self, tokenizer, readbacks):
        self.tokenizer = tokenizer
        self.readbacks = readbacks
        self._stop_ids = frozenset()
        self.config = SimpleNamespace(
            model=SimpleNamespace(max_model_len=4096)
        )
        self.scheduler = SimpleNamespace(remove=lambda seq: None)
        self.seq = None
        self.thread = None

    def submit_prompt(self, prompt, params, stream_cb=None, meta=None):
        self.seq = Sequence(
            prompt_ids=[5], params=params, stream_cb=stream_cb
        )
        self.seq.status = SeqStatus.RUNNING
        self.thread = threading.Thread(target=self.run, daemon=True)
        self.thread.start()
        return self.seq

    def run(self):
        for tokens in self.readbacks:
            self.readback(tokens)

    def readback(self, tokens):
        """What _process_chunks does with one sequence's column."""
        seq, wakes = self.seq, {}
        for token in tokens:
            if seq.status is not SeqStatus.RUNNING:
                break  # overshoot past a stop: never appended
            if seq.params.logprobs:
                seq.logprob_data.append(
                    (-0.5 - len(seq.logprob_data), [(token, -0.25)])
                )
            seq.append_token(token)
            self._maybe_finish(seq, token, wakes)
        seq.deliver(wakes)
        EngineCore._wake_streams(wakes)


def cut(tokens, sizes):
    """``tokens`` as consecutive deliveries of ``sizes`` (cycled)."""
    out, i, k = [], 0, 0
    while i < len(tokens):
        n = sizes[k % len(sizes)]
        out.append(tokens[i:i + n])
        i, k = i + n, k + 1
    return out


async def stream(tokenizer, readbacks, params):
    """Drive stream_async; returns (pieces, finish reason, usage, seq)."""
    backend = JaxTPUBackend()
    backend.core = ScriptedCore(tokenizer, readbacks)
    box = {}
    pieces = []
    async for piece in backend.stream_async(
        "p", params,
        on_finish=lambda r: box.__setitem__("finish", r),
        on_usage=lambda u: box.__setitem__("usage", u),
    ):
        pieces.append(piece)
    return pieces, box["finish"], box["usage"], backend.core.seq


def parent_stream(tokenizer, ids, stops):
    """The text the per-token stream of the parent commit produced for
    ``ids`` (whole-list decode a token, its own stop search): what the
    deliveries must still add up to wherever that was right."""
    emitted, seen = "", []
    longest = max((len(s) for s in stops), default=0)
    for token in ids:
        seen.append(token)
        text = tokenizer.decode(seen)
        cuts = [i for i in (text.find(s) for s in stops) if i != -1]
        if cuts:
            return emitted + text[len(emitted):min(cuts)]
        if stops:
            text = text[: max(len(emitted), len(text) - longest)]
        if len(text) > len(emitted):
            emitted = text
    return tokenizer.decode(seen)


def byte_ids(text):
    return BYTES.encode(text)


def to_length(tokenizer, ids, text, as_parent=True):
    """A case that runs out its budget of exactly these tokens."""
    return (tokenizer, ids, SamplingParams(max_tokens=len(ids)), text,
            "length", len(ids), as_parent)


# name -> (tokenizer, sampled ids, params, text, finish reason, tokens
# appended, does the parent's per-token stream give the same text)
CASES = {
    "ascii": to_length(
        BYTES, byte_ids("the quick brown fox jumps"),
        "the quick brown fox jumps",
    ),
    # an accented letter is two bytes, the euro sign three: deliveries
    # of 1 split them, and so do the uneven ones.  The parent emitted
    # U+FFFD for the first part and never took it back; held back,
    # every cut gives the real text
    "utf8_split": to_length(
        BYTES, byte_ids("café €5 naïve"), "café €5 naïve",
        as_parent=False,
    ),
    "suffix_decode_differs": to_length(
        PIECES,
        PIECES.ids(" Hello", " wor", "ld", "!", " and", " more", " x"),
        "Hello world! and more x",
    ),
    "stop_inside_one_delivery": (
        BYTES, byte_ids("abcdeSTOPtail"),
        SamplingParams(max_tokens=32, stop=["STOP"]), "abcde",
        "stop", 9, True,
    ),
    # with deliveries of 8 the stop begins in the first and ends in the
    # second; the tokens after it are overshoot and never appended
    "stop_across_deliveries_and_overshoot": (
        BYTES, byte_ids("abcdefSTOP and some more"),
        SamplingParams(max_tokens=64, stop=["STOP", "never"]), "abcdef",
        "stop", 10, True,
    ),
    "stop_of_pieces": (
        PIECES,
        PIECES.ids(" Hello", " wor", "ld", " STOP", " and", " more"),
        SamplingParams(max_tokens=9, stop=[" STOP"]), "Hello world",
        "stop", 4, True,
    ),
    # the first STOP ends inside the floor of 12 tokens, so the engine
    # reads over it and the text keeps it; the parent's stream cut there
    # and closed with the tokens of that moment
    "min_tokens_above_the_stop": (
        BYTES, byte_ids("abSTOPcdefghijkSTOPzz"),
        SamplingParams(max_tokens=40, min_tokens=12, stop=["STOP"]),
        "abSTOPcdefghijk", "stop", 19, False,
    ),
    "one_token": to_length(BYTES, byte_ids("Z"), "Z"),
}
SIZES = {"1": [1], "2": [2], "8": [8], "uneven": [3, 1, 5, 2, 7]}


@pytest.mark.parametrize("logprobs", [False, True], ids=["text", "lp"])
@pytest.mark.parametrize("sizes", SIZES.values(), ids=SIZES.keys())
@pytest.mark.parametrize("case", CASES.values(), ids=CASES.keys())
async def test_streamed_text_does_not_depend_on_the_deliveries(
    case, sizes, logprobs
):
    tokenizer, ids, params, text, reason, n_tokens, as_parent = case
    if logprobs:
        params = dataclasses.replace(
            params, logprobs=True, top_logprobs=1
        )
    pieces, finish, usage, seq = await stream(
        tokenizer, cut(ids, sizes), params
    )
    deltas = [p["text"] if logprobs else p for p in pieces]
    assert "".join(deltas) == text
    if as_parent:
        assert text == parent_stream(tokenizer, ids, params.stop or [])
    assert finish == reason
    assert seq.generated_ids == ids[:n_tokens]
    assert usage == {
        "prompt_tokens": 1,
        "completion_tokens": n_tokens,
        "total_tokens": 1 + n_tokens,
    }
    # at most one delta per delivery
    assert len(pieces) <= len(cut(ids[:n_tokens], sizes))
    if logprobs:
        entries = [e for p in pieces for e in p["logprobs"]]
        assert [e["token_id"] for e in entries] == ids[:n_tokens]
        assert [e["logprob"] for e in entries] == [
            -0.5 - i for i in range(n_tokens)
        ]


@pytest.mark.parametrize(
    "tokenizer,ids",
    [
        (BYTES, byte_ids("naïve café — \U0001f600 ok")),
        (PIECES, PIECES.ids(" Hello", " wor", "ld", "!", " x", " x",
                            "é", " and", " more", " Hello")),
        # ids no byte stands behind (specials, a large vocabulary) add
        # nothing and must not stall the text that follows them
        (BYTES, [300, 301] + byte_ids("ab") + [400] * 40 + byte_ids("cd")),
    ],
    ids=["bytes", "pieces", "silent_ids"],
)
@pytest.mark.parametrize("sizes", SIZES.values(), ids=SIZES.keys())
def test_incremental_detokenizer_adds_up_to_the_whole_decode(
    tokenizer, ids, sizes
):
    detok = IncrementalDetokenizer(tokenizer)
    longest, text = 0, ""
    for tokens in cut(ids, sizes):
        text += detok.feed(tokens)
        assert tokenizer.decode(ids).startswith(text)
        assert "�" not in text
        longest = max(longest, len(detok.ids) - detok._prefix)
    assert text == tokenizer.decode(ids)
    # the decoded slice is bounded: context plus a delivery or two
    assert longest <= detok.CONTEXT + 2 * max(sizes)


def test_incremental_detokenizer_lets_an_invalid_tail_out():
    """Bytes that never become a character are not held for ever."""
    detok = IncrementalDetokenizer(BYTES)
    bad = [BYTES.OFFSET + 0xFF]
    text = "".join(detok.feed(bad) for _ in range(40))
    assert 0 < len(text) <= 40 and set(text) == {"�"}
    assert len(detok.ids) - detok._prefix <= (
        detok.CONTEXT + detok.MAX_HOLD + 1
    )


# ------------------------------------------------------------ the hand-off


class CountingLoop:
    """Stands in for the event loop: counts the cross-thread wake-ups
    and runs each at once, as a loop that is never busy would."""

    def __init__(self):
        self.wakeups = 0

    def call_soon_threadsafe(self, fn, *args):
        self.wakeups += 1
        fn(*args)


class Inbox:
    """A stream's queue, seen from the hand-off."""

    def __init__(self):
        self.items = []

    def put_nowait(self, item):
        self.items.append(item)


def wire(handoff):
    inbox = Inbox()

    def on_tokens(tokens, done):
        handoff.post(inbox, (list(tokens), done))
        return handoff.wake

    return inbox, on_tokens


@pytest.fixture(scope="module")
def tiny_engine():
    import jax

    from vgate_tpu.config import load_config

    core = EngineCore(
        load_config(
            model={
                "model_id": "tiny-dense",
                "engine_type": "jax_tpu",
                "dtype": "float32",
                "max_model_len": 64,
            },
            tpu={
                "dp": 1, "tp": 1, "ep": 1, "sp": 1,
                "kv_num_pages": 64, "kv_page_size": 4,
                "max_batch_slots": 4, "prefill_buckets": [8, 16],
                "use_pallas": False, "decode_chunk": 4,
            },
            scheduler={"max_queue_size": 16},
            logging={"level": "WARNING"},
        ),
        devices=jax.devices()[:1],
    )
    core.start()
    yield core
    core.stop()


@pytest.mark.parametrize("streams", [1, 3])
def test_one_wakeup_per_readback_and_the_end_comes_last(
    tiny_engine, streams, monkeypatch
):
    """The real engine, its streams wired to one (stub) loop: as many
    wake-ups as readbacks that had something to deliver, however many
    streams and steps a readback holds; each stream's deliveries are in
    order, add up to its tokens, and only the last says done."""
    loop = CountingLoop()
    handoff = _LoopHandoff(loop)
    readbacks = []
    wake_streams = EngineCore._wake_streams

    def counting(wakes):
        if wakes:
            readbacks.append(len(wakes))
        wake_streams(wakes)

    monkeypatch.setattr(tiny_engine, "_wake_streams", counting)
    params = SamplingParams(max_tokens=13, min_tokens=13, temperature=0.0)
    wired = [wire(handoff) for _ in range(streams)]
    seqs = [
        tiny_engine.submit_prompt(f"prompt {i}", params, stream_cb=cb)
        for i, (_, cb) in enumerate(wired)
    ]
    for seq in seqs:
        assert seq.done_event.wait(timeout=300)
    assert loop.wakeups == len(readbacks)
    assert set(readbacks) == {1}  # one consumer, one wake each
    tokens = sum(len(s.generated_ids) for s in seqs)
    assert tokens == 13 * streams
    # 13 tokens a stream: 1 from its prefill, 12 in chunks of up to 4
    assert loop.wakeups <= tokens // 2
    for seq, (inbox, _) in zip(seqs, wired):
        got = [t for toks, _ in inbox.items for t in toks]
        assert got == seq.generated_ids
        assert [done for _, done in inbox.items] == (
            [False] * (len(inbox.items) - 1) + [True]
        )
        assert max(len(toks) for toks, _ in inbox.items) > 1


def test_a_wakeup_on_its_way_serves_what_is_posted_before_it_runs():
    calls = []
    loop = SimpleNamespace(
        call_soon_threadsafe=lambda fn: calls.append(fn)
    )
    handoff = _LoopHandoff(loop)
    a, b = Inbox(), Inbox()
    handoff.wake()
    assert calls == []  # nothing posted: nothing to wake for
    handoff.post(a, ([1, 2], False))
    handoff.wake()
    handoff.post(b, ([3], False))
    handoff.post(a, ([4], True))
    handoff.wake()  # the loop has not run yet: still one wake-up
    assert len(calls) == 1
    calls.pop()()
    assert a.items == [([1, 2], False), ([4], True)]
    assert b.items == [([3], False)]
    handoff.post(b, ([5], False))
    handoff.wake()
    assert len(calls) == 1  # drained: the next readback wakes again


def test_a_wakeup_books_how_long_it_waited_for_the_loop(monkeypatch):
    """The hand-off timed on its real path: the stamp is taken when a
    wake-up is armed, the wait booked when the loop runs it; a wake-up
    that serves two readbacks' posts is ONE hand-off, timed from the
    first; with observability off nothing is stamped or booked."""
    from vgate_tpu.backends import jax_backend
    from vgate_tpu.observability.perf import GatewayPerf

    now = [50.0]
    gateway = GatewayPerf(clock=lambda: now[0])
    monkeypatch.setattr(jax_backend, "GATEWAY", gateway)
    calls = []
    handoff = _LoopHandoff(SimpleNamespace(
        call_soon_threadsafe=lambda fn: calls.append(fn)
    ))
    a = Inbox()
    handoff.post(a, ([1], False))
    handoff.wake()
    now[0] += 0.020  # the loop is writing to other streams
    handoff.post(a, ([2], False))  # the next readback's post
    handoff.wake()  # rides the wake-up already on its way
    now[0] += 0.010
    calls.pop()()
    assert a.items == [([1], False), ([2], False)]
    totals = gateway.totals()
    assert totals["stream_handoffs"] == 1
    assert totals["handoff_wait_s"] == pytest.approx(0.030)
    assert totals["handoff_waits"]["32"] == 1
    handoff.post(a, ([3], True))
    handoff.wake()
    calls.pop()()  # at once
    totals = gateway.totals()
    assert totals["stream_handoffs"] == 2
    assert totals["handoff_wait_s"] == pytest.approx(0.030)
    assert totals["handoff_waits"]["8"] == 1
    assert sum(totals["handoff_waits"].values()) == 2
    gateway.enabled = False
    handoff.post(a, ([4], True))
    handoff.wake()
    assert handoff._armed_t is None
    now[0] += 1.0
    calls.pop()()
    assert a.items[-1] == ([4], True)  # delivered all the same
    assert gateway.totals() == totals


def test_a_closed_loop_drops_the_deliveries():
    def closed(fn):
        raise RuntimeError("Event loop is closed")

    handoff = _LoopHandoff(SimpleNamespace(call_soon_threadsafe=closed))
    handoff.post(Inbox(), ([1], False))
    handoff.wake()  # no raise into the engine thread
    assert handoff._pending == [] and handoff._armed is False


@pytest.mark.parametrize("how", ["fail", "abort"])
def test_a_settle_from_another_thread_ends_the_stream(how):
    """Watchdog, containment and drain settle a sequence from their own
    threads, outside any readback: the end notice wakes the loop itself
    and comes behind the tokens already appended."""
    loop = CountingLoop()
    inbox, on_tokens = wire(_LoopHandoff(loop))
    seq = Sequence(
        prompt_ids=[5], params=SamplingParams(), stream_cb=on_tokens
    )
    seq.status = SeqStatus.RUNNING
    wakes = {}
    seq.append_token(7)
    seq.append_token(8)
    seq.deliver(wakes)  # a readback that has not woken the loop yet
    seq.append_token(9)  # appended, not yet delivered

    def settle():
        if how == "fail":
            seq.fail(RuntimeError("engine restarting"))
        else:
            seq.finish("abort")

    other = threading.Thread(target=settle)
    other.start()
    other.join()
    assert loop.wakeups == 1
    assert inbox.items == [([7, 8], False), ([9], True)]
    assert seq.done_event.is_set()


async def test_a_failed_sequence_raises_out_of_the_stream():
    backend = JaxTPUBackend()
    core = backend.core = ScriptedCore(BYTES, [byte_ids("abc")])
    core.run = lambda: (
        core.readback(byte_ids("abc")),
        core.seq.fail(RuntimeError("engine restarting")),
    )
    pieces = []
    with pytest.raises(RuntimeError, match="engine restarting"):
        async for piece in backend.stream_async(
            "p", SamplingParams(max_tokens=9)
        ):
            pieces.append(piece)
    assert "".join(pieces) == "abc"


async def test_a_client_that_leaves_mid_delivery_aborts_the_sequence():
    backend = JaxTPUBackend()
    more = threading.Event()
    core = backend.core = ScriptedCore(BYTES, [])
    core.run = lambda: (
        core.readback(byte_ids("abcd")),
        more.wait(timeout=30),
        core.readback(byte_ids("efgh")),
    )
    agen = backend.stream_async("p", SamplingParams(max_tokens=64))
    assert await agen.__anext__() == "abcd"
    assert core.seq.abort_requested is False
    await agen.aclose()  # the SSE handler was cancelled
    assert core.seq.abort_requested is True
    more.set()
    core.thread.join(timeout=30)
    # the engine's late delivery finds no reader and harms nothing
    await asyncio.sleep(0)
    assert core.seq.generated_ids == byte_ids("abcdefgh")


async def test_many_streams_share_one_wakeup_on_a_real_loop(monkeypatch):
    """Two streams on the running loop, one engine thread that appends
    to both in each readback: the loop is woken once a readback."""
    loop = asyncio.get_running_loop()
    wakeups = []
    real = loop.call_soon_threadsafe

    def counted(fn, *args):
        if isinstance(getattr(fn, "__self__", None), _LoopHandoff):
            wakeups.append(fn)
        return real(fn, *args)

    monkeypatch.setattr(loop, "call_soon_threadsafe", counted)

    class TwoStreams(ScriptedCore):
        seqs = []

        def submit_prompt(self, prompt, params, stream_cb=None, meta=None):
            seq = Sequence(
                prompt_ids=[5], params=params, stream_cb=stream_cb
            )
            seq.status = SeqStatus.RUNNING
            self.seqs.append(seq)
            return seq

    backend = JaxTPUBackend()
    core = backend.core = TwoStreams(BYTES, [])
    texts = ["hello world, ", "HELLO WORLD, "]

    async def consume():
        return "".join([
            p async for p in backend.stream_async(
                "p", SamplingParams(max_tokens=13)
            )
        ])

    def engine():
        for step in range(0, 13, 5):  # three readbacks of 5, 5, 3 steps
            wakes = {}
            for seq, text in zip(core.seqs, texts):
                for token in byte_ids(text)[step:step + 5]:
                    seq.append_token(token)
                    core._maybe_finish(seq, token, wakes)
                seq.deliver(wakes)
            EngineCore._wake_streams(wakes)

    tasks = [asyncio.ensure_future(consume()) for _ in texts]
    while len(core.seqs) < 2:
        await asyncio.sleep(0.01)
    thread = threading.Thread(target=engine, daemon=True)
    thread.start()
    assert await asyncio.gather(*tasks) == texts
    thread.join(timeout=30)
    assert 1 <= len(wakeups) <= 3  # 26 tokens, 6 deliveries, 2 streams
