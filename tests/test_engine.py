"""EngineCore integration tests: the full continuous-batching loop on a CPU
device with the tiny dense model (SURVEY.md section 4: CPU-backed jax tests
for scheduler/engine logic)."""

import asyncio
import threading

import numpy as np
import pytest

import jax

from vgate_tpu.backends.base import SamplingParams
from vgate_tpu.config import load_config
from vgate_tpu.runtime.engine_core import EngineCore


def tiny_config(**tpu_overrides):
    tpu = {
        "dp": 1,
        "tp": 1,
        "ep": 1,
        "sp": 1,
        "kv_num_pages": 64,
        "kv_page_size": 4,
        "max_batch_slots": 4,
        "prefill_buckets": [8, 16, 32],
        "use_pallas": False,
    }
    tpu.update(tpu_overrides)
    return load_config(
        model={
            "model_id": "tiny-dense",
            "engine_type": "jax_tpu",
            "dtype": "float32",
            "max_model_len": 64,
        },
        tpu=tpu,
        scheduler={"max_queue_size": 16},
        logging={"level": "WARNING"},
    )


@pytest.fixture(scope="module")
def engine():
    core = EngineCore(tiny_config(), devices=jax.devices()[:1])
    core.start()
    yield core
    core.stop()


def greedy(max_tokens=8):
    return SamplingParams(max_tokens=max_tokens, temperature=0.0)


def test_generate_single(engine):
    [result] = engine.generate(["hello world"], [greedy(6)])
    assert result["num_tokens"] >= 1
    assert result["num_tokens"] <= 6
    assert result["finish_reason"] in ("stop", "length")
    assert result["metrics"]["ttft"] > 0
    assert isinstance(result["text"], str)


def test_generate_is_deterministic_greedy(engine):
    [a] = engine.generate(["determinism probe"], [greedy(8)])
    [b] = engine.generate(["determinism probe"], [greedy(8)])
    assert a["token_ids"] == b["token_ids"]


def test_generate_batch_matches_single(engine):
    """Continuous batching must not change greedy results: running three
    prompts together equals running each alone."""
    prompts = ["alpha beta", "gamma", "delta epsilon zeta"]
    together = engine.generate(prompts, [greedy(6)] * 3)
    alone = [engine.generate([p], [greedy(6)])[0] for p in prompts]
    for t, a in zip(together, alone):
        assert t["token_ids"] == a["token_ids"]


def test_max_tokens_respected(engine):
    [result] = engine.generate(["count tokens"], [greedy(3)])
    assert result["num_tokens"] <= 3


def test_concurrent_submission_from_threads(engine):
    results = {}

    def worker(i):
        results[i] = engine.generate([f"prompt {i}"], [greedy(5)])[0]

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert len(results) == 6
    assert all(r["num_tokens"] >= 1 for r in results.values())


def test_stats_surface(engine):
    engine.generate(["stats probe"], [greedy(2)])
    stats = engine.get_stats()
    assert stats["prefills"] >= 1
    assert stats["steps"] >= 1
    assert stats["scheduler"]["finished"] >= 1
    assert stats["kv_token_capacity"] > 0
    assert stats["mesh"]["tp"] == 1


@pytest.mark.fast  # seconds: runs in tier-1, unlike this file
def test_decode_spans_and_stats_name_the_kv_write_path(engine, monkeypatch):
    """Who writes a decode step's K/V is fixed when the program is
    traced, so it is a word, not a rate: the same one in /stats → engine
    and on every ``vgt.engine.decode_dispatch`` span of a capture (here
    the jnp twin's: XLA's scatter)."""
    from vgate_tpu.observability import perf as perf_mod

    spans = []

    class Ann:
        def __exit__(self, *exc):
            pass

        def set_metadata(self, **args):
            pass

    def fake_open(name, args):
        spans.append((name, args() if args is not None else {}))
        return Ann()

    monkeypatch.setattr(perf_mod, "_open_annotation", fake_open)
    perf_mod.set_capturing(True)
    try:
        engine.generate(["which path writes"], [greedy(4)])
    finally:
        perf_mod.set_capturing(False)
    assert engine.get_stats()["kv_write"] == "scatter"
    dispatched = [
        args for name, args in spans
        if name == "vgt.engine.decode_dispatch"
    ]
    assert dispatched
    assert {args["kv_write"] for args in dispatched} == {"scatter"}
    # and what ends a step: without the kernel, the logits' array
    assert {args["head"] for args in dispatched} == {"logits"}
    totals = engine.perf.totals()
    assert totals["decode_steps"] > 0 == totals["decode_steps_fused_head"]


@pytest.mark.fast
def test_fused_head_steps_are_booked_as_the_dispatch_judged(
        engine, monkeypatch):
    """The engine reads ``decode_head_impl`` once a dispatch, from the
    variant's static facts, and books the chunk's steps at its readback
    under that word (the rule itself: tests/test_greedy_head.py).  Here
    its answer is forced for the engine's report alone, the program
    being the CPU's either way."""
    from vgate_tpu.runtime import engine_core

    asked = []

    def rule(params, spec, use_pallas, mesh=None, **facts):
        asked.append(facts)
        return "fused"

    monkeypatch.setattr(engine_core, "decode_head_impl", rule)
    before = engine.perf.totals()
    engine.generate(["which head ends a step"], [greedy(6)])
    after = engine.perf.totals()
    steps = after["decode_steps"] - before["decode_steps"]
    assert steps > 0
    assert (after["decode_steps_fused_head"]
            - before["decode_steps_fused_head"]) == steps
    assert asked and all(
        facts["all_greedy"] and facts["num_logprobs"] == 0
        and not facts["penalised"] and facts["rows"] > 0 for facts in asked)


def test_device_health(engine):
    health = engine.device_health()
    assert health["alive"] is True
    assert health["num_devices"] == 1


def test_long_generation_crosses_pages(engine):
    """page_size=4: a 20-token generation crosses several page boundaries."""
    [result] = engine.generate(["page crossing probe"], [greedy(20)])
    if result["finish_reason"] == "length":
        assert result["num_tokens"] == 20 or result["num_tokens"] >= 1


def test_preemption_preserves_greedy_output():
    """Recompute correctness: a preempted-and-resumed sequence must produce
    exactly what a fresh request for its folded prompt would produce.

    The assertion deliberately replays the victim inside the SAME engine
    (same compiled programs).  Comparing against a *differently shaped*
    engine (e.g. a bigger KV pool, or per-step instead of chunked decode)
    is not bitwise-stable: XLA emits different programs and random-init
    logits sit close enough to ties that greedy argmax can legitimately
    flip on ulp-level differences.  What preemption must guarantee is that
    recompute == fresh-restart-with-the-folded-prompt, and that is exact.
    """
    # 14 usable pages; 3 seqs × (prompt ~2 pages + 10 tokens) ≈ 15+ pages.
    # decode_chunk=1 keeps every decode step in the SAME compiled program
    # regardless of batch composition — with larger chunks the victim's
    # resumed steps can run in a different chunk-length program than the
    # solo replay walks, reintroducing the ulp hazard described above.
    tight_core = EngineCore(
        tiny_config(kv_num_pages=15, decode_chunk=1),
        devices=jax.devices()[:1],
    )
    tight_core.start()
    prompts = ["preempt probe one", "preempt probe two", "preempt pr three"]
    try:
        seqs = [tight_core.submit_prompt(p, greedy(10)) for p in prompts]
        for seq in seqs:
            assert seq.done_event.wait(timeout=300)
        assert tight_core.scheduler.total_preemptions >= 1
        for seq in seqs:
            assert seq.num_output_tokens == 10
            assert seq.finish_reason == "length"

        victims = [s for s in seqs if s.preempt_count >= 1]
        assert victims, "preemption happened but no victim recorded"
        seq = victims[0]
        folded = seq.num_prompt_tokens - seq.orig_prompt_len
        assert 0 < folded < 10  # preempted mid-generation
        # the folded prefix is exactly the tokens generated pre-preemption
        assert (
            seq.prompt_ids[seq.orig_prompt_len:]
            == seq.generated_ids[:folded]
        )

        # replay: fresh request = folded prompt, budget = remaining tokens.
        # The pool is empty now, so the replay prefills+decodes through the
        # same programs the recompute path used -> must match exactly.
        replay = tight_core.submit_tokens(
            list(seq.prompt_ids), greedy(10 - folded)
        )
        assert replay.done_event.wait(timeout=300)
        assert replay.generated_ids == seq.generated_ids[folded:]
    finally:
        tight_core.stop()


def test_decode_signature_includes_preempt_epoch(engine):
    """A victim re-admitted into the same freed slot with the same page
    count must NOT match the pre-preemption signature cache — its device
    tokens/positions are stale (advisor finding r1: dispatching against
    them corrupts the sequence silently)."""
    from vgate_tpu.runtime.sequence import Sequence

    seq = Sequence(prompt_ids=[1, 2, 3], params=greedy(4))
    seq.slot = 0
    seq.pages = [1, 2]
    sig_before = engine._decode_signature([seq])

    seq.output_ids = [7]
    seq.reset_for_recompute()
    # re-admission lands it back in the same slot with an identical
    # page-count footprint (horizon-inflated count == pre-preemption count)
    seq.slot = 0
    seq.pages = [1, 2]
    assert engine._decode_signature([seq]) != sig_before


def test_preemption_under_chunked_pipeline_is_clean():
    """Preemption while chunks are in flight (decode_chunk>1, pipeline 2):
    every sequence still finishes with its exact budget and no pages leak.
    Exercises the signature-cache invalidation paths in _tick.

    min_tokens pins the full 10-token budget: on random-init weights
    greedy argmax occasionally lands on EOS mid-generation, which used
    to flip finish_reason to "stop" under full-suite ordering (flaky
    since PR 12) — the invariant under test is preemption cleanliness
    (exact budget, zero leaks), not where a random model stops."""
    core = EngineCore(
        tiny_config(kv_num_pages=15, decode_chunk=4, decode_pipeline=2),
        devices=jax.devices()[:1],
    )
    core.start()
    try:
        prompts = ["pipeline one", "pipeline two", "pipeline number three"]
        params = [
            SamplingParams(max_tokens=10, min_tokens=10, temperature=0.0)
            for _ in prompts
        ]
        seqs = [
            core.submit_prompt(p, sp) for p, sp in zip(prompts, params)
        ]
        for seq in seqs:
            assert seq.done_event.wait(timeout=300)
        assert core.scheduler.total_preemptions >= 1
        for seq in seqs:
            assert seq.num_output_tokens == 10
            assert seq.finish_reason == "length"
        stats = core.get_stats()["scheduler"]
        assert stats["running"] == 0
        assert stats["used_pages"] == 0
    finally:
        core.stop()


def test_decode_flows_during_prefill_burst():
    """With prefill_admit_limit set, a burst of new prompts must not stall
    a resident decoding sequence: its tokens keep arriving interleaved with
    the burst's first tokens (VERDICT r1 item 2 'done' criterion)."""
    import time as _time

    core = EngineCore(
        tiny_config(
            max_batch_slots=16,
            kv_num_pages=256,
            decode_chunk=4,
            prefill_admit_limit=1,
        ),
        devices=jax.devices()[:1],
    )
    core.start()
    events = []  # (kind, t) appended from engine thread callbacks
    try:
        long_seq = core.submit_prompt(
            "resident decoder", greedy(48),
            stream_cb=lambda toks, done: events.append(
                ("decode", _time.perf_counter())
            ),
        )
        # wait until the resident sequence is producing
        deadline = _time.perf_counter() + 120
        while not events and _time.perf_counter() < deadline:
            _time.sleep(0.01)
        assert events, "resident sequence never started"

        burst = []
        for i in range(8):
            first_done = []

            def cb(toks, done, first_done=first_done):
                if not first_done:
                    first_done.append(True)
                    events.append(("first", _time.perf_counter()))

            burst.append(
                core.submit_prompt(f"burst prompt {i}", greedy(2), cb)
            )
        for seq in burst:
            assert seq.done_event.wait(timeout=300)
        assert long_seq.done_event.wait(timeout=300)

        firsts = [t for kind, t in events if kind == "first"]
        assert len(firsts) == 8
        window = [
            kind for kind, t in events
            if min(firsts) < t < max(firsts)
        ]
        assert "decode" in window, (
            "resident sequence made no progress during the prefill burst: "
            f"{events}"
        )
    finally:
        core.stop()


def test_engine_queue_full_fails_cleanly():
    core = EngineCore(tiny_config(), devices=jax.devices()[:1])
    # engine NOT started: fill the queue beyond max_queue_size
    try:
        seqs = [
            core.submit_tokens([3, 4, 5], greedy(2)) for _ in range(20)
        ]
        core.start()
        for seq in seqs:
            seq.done_event.wait(timeout=120)
        failed = [s for s in seqs if s.error is not None]
        ok = [s for s in seqs if s.error is None]
        assert len(ok) == 16  # max_queue_size
        assert all("queue full" in str(s.error) for s in failed)
    finally:
        core.stop()


def test_streaming_callback_order(engine):
    """stream_cb takes what each readback appended, as a list, in
    order; the call that settles the sequence says so, and is the
    last."""
    calls = []
    seq = engine.submit_prompt(
        "stream probe", greedy(5),
        stream_cb=lambda toks, done: calls.append((list(toks), done)),
    )
    seq.done_event.wait(timeout=120)
    assert [t for toks, _ in calls for t in toks] == seq.generated_ids
    assert calls[0][0] == seq.generated_ids[:1]  # the prefill's token
    assert len(calls) < 5  # decode chunks deliver several at a time
    assert [done for _, done in calls] == [False] * (len(calls) - 1) + [
        True
    ]


def test_chunk_overshoot_discarded(engine):
    """decode_chunk=8 with max_tokens that's not a chunk multiple: the
    overshoot steps the chunk ran past the budget must be discarded."""
    for budget in (3, 5, 9):
        [r] = engine.generate(["overshoot probe"], [greedy(budget)])
        assert r["num_tokens"] <= budget
        assert len(r["token_ids"]) == r["num_tokens"]


def test_eos_mid_chunk_truncates():
    """A sequence whose EOS lands mid-chunk stops there; trailing steps of
    the chunk are discarded and the slot is freed."""
    core = EngineCore(tiny_config(decode_chunk=8), devices=jax.devices()[:1])
    core.start()
    try:
        # probe an unconstrained greedy run to learn the token stream
        [probe] = core.generate(["eos mid chunk probe"], [greedy(12)])
        assert probe["num_tokens"] >= 4
        # declare the 3rd generated token to be EOS and rerun
        fake_eos = probe["token_ids"][2]
        real_eos = core.tokenizer.eos_id
        core.tokenizer.eos_id = fake_eos
        try:
            [r] = core.generate(["eos mid chunk probe"], [greedy(12)])
        finally:
            core.tokenizer.eos_id = real_eos
        first_eos = probe["token_ids"].index(fake_eos)
        assert r["finish_reason"] == "stop"
        assert r["token_ids"] == probe["token_ids"][: first_eos + 1]
        assert not core.scheduler.running
    finally:
        core.stop()


def test_decode_chunk_ladder_compiles_powers_of_two():
    core = EngineCore(
        tiny_config(decode_chunk=8), devices=jax.devices()[:1]
    )
    core.start()
    try:
        core.generate(["ladder probe"], [greedy(16)])
        # keys are (chunk_len, penalties_active, min_tokens_width, ...)
        chunks = {k for family, k in core._compiled if family == "decode"}
        lens = {k[0] for k in chunks}
        assert lens <= {1, 2, 4, 8}
        assert max(lens) == 8
        assert all(k[1] is False and k[2] is None for k in chunks)
    finally:
        core.stop()


def test_page_growth_does_not_rebuild_state():
    """Pages growing mid-generation (same membership) must refresh only the
    page-table upload, not drain the pipeline and rebuild device state —
    otherwise the depth-2 pipeline collapses at every page boundary."""
    core = EngineCore(
        tiny_config(decode_chunk=4, decode_pipeline=2),
        devices=jax.devices()[:1],
    )
    core.start()
    try:
        # 40 tokens across page_size=4 -> ~10 page-boundary crossings
        [r] = core.generate(["rebuild probe"], [greedy(40)])
        assert r["num_tokens"] >= 30
        # one rebuild at admission; page growth must not add more
        assert core.total_state_rebuilds == 1
    finally:
        core.stop()


# ---- a plain membership change edits the decode state's rows (PR 32):
# streams that end at a readback and prompts that join through a prompt
# program no longer drain the pipeline and rebuild the device state


def _membership(core):
    totals = core.perf.totals()
    return totals["membership_changes"], totals["pipeline_drains"]


def _drain_reasons(core):
    """Record the reason of every rebuild of ``core``'s decode state."""
    reasons = []
    real = core._build_decode_state

    def recording(seqs, reason="initial"):
        reasons.append(reason)
        return real(seqs, reason)

    core._build_decode_state = recording
    return reasons


def _alone_then_together(core, prompts, params):
    """Every prompt served alone, then all of them at once through the
    core's few slots (a closed loop: each stream's end admits the next
    prompt).  Returns both runs' token lists, and the membership changes
    and pipeline drains of the second."""
    alone = [
        core.generate([p], [sp])[0]["token_ids"]
        for p, sp in zip(prompts, params)
    ]
    before = _membership(core)
    together = [r["token_ids"] for r in core.generate(prompts, params)]
    counted = tuple(b - a for a, b in zip(before, _membership(core)))
    return alone, together, counted


# as many as the queue of tiny_config() holds
_LOOP_PROMPTS = [f"closed loop prompt {i} of many" for i in range(16)]
_LOOP_LENGTHS = [3 + (5 * i) % 17 for i in range(16)]


@pytest.mark.fast  # seconds a case: runs in tier-1, unlike this file
@pytest.mark.parametrize("radix", [True, False], ids=["radix", "no_cache"])
def test_closed_loop_edits_rows_and_keeps_greedy_tokens(radix):
    """More prompts than slots, answers of staggered lengths, greedy:
    every stream's tokens are those of the same prompt served alone, and
    after the first build no membership change drains the pipeline,
    with the radix cache on (joiners come through suffix programs) and
    off."""
    core = EngineCore(
        tiny_config(decode_chunk=4, decode_pipeline=2, prefix_cache=radix),
        devices=jax.devices()[:1],
    )
    core.start()
    try:
        alone, together, (changes, drains) = _alone_then_together(
            core, _LOOP_PROMPTS, [greedy(n) for n in _LOOP_LENGTHS]
        )
        assert together == alone
        # its ends and joins (several may fall into one tick) left
        # every chunk in flight in flight
        assert changes >= 8 and drains == 0
        # dozens by now, and the one build is the first, for the first
        # prompt served alone
        total, drains = _membership(core)
        assert total >= 24 and drains == 1 == core.total_state_rebuilds
        stats = core.get_stats()["scheduler"]
        assert stats["running"] == 0
        if not radix:
            assert stats["used_pages"] == 0
    finally:
        core.stop()


@pytest.mark.fast  # seconds a case: runs in tier-1, unlike this file
@pytest.mark.parametrize(
    "sampling",
    [
        {"temperature": 0.8},
        {"temperature": 1.0, "top_p": 0.9},
        {"temperature": 1.2, "top_k": 5},
    ],
    ids=["temperature", "top_p", "top_k"],
)
def test_closed_loop_edits_rows_and_keeps_seeded_tokens(sampling):
    """The same closed loop with seeded sampling rows: a row that joins
    the decode batch on the device keeps its ``(seed, token index)``
    keys, so every stream's tokens are those of the same prompt and
    seed served alone."""
    core = EngineCore(
        tiny_config(decode_chunk=4, decode_pipeline=2),
        devices=jax.devices()[:1],
    )
    core.start()
    try:
        prompts = _LOOP_PROMPTS[:14]
        params = [
            SamplingParams(max_tokens=n, seed=1000 + i, **sampling)
            for i, n in enumerate(_LOOP_LENGTHS[:14])
        ]
        alone, together, (changes, drains) = _alone_then_together(
            core, prompts, params
        )
        assert together == alone
        assert len({tuple(t) for t in together}) == len(together)
        assert changes >= 7 and drains == 0
    finally:
        core.stop()


@pytest.mark.fast  # seconds a case: runs in tier-1, unlike this file
@pytest.mark.parametrize("case", ["penalties", "variant", "preempt"])
def test_other_membership_changes_still_drain(case):
    """What is not a plain change keeps the drain and the rebuild, names
    its reason, and gives the tokens it gave: a first row with a penalty
    (its histogram is built from host state), a ``logprobs`` row joining
    (another program variant), a preemption."""
    tight = case == "preempt"
    core = EngineCore(
        tiny_config(
            decode_chunk=1 if tight else 4, decode_pipeline=2,
            **({"kv_num_pages": 15} if tight else {}),
        ),
        devices=jax.devices()[:1],
    )
    core.start()
    reasons = _drain_reasons(core)
    try:
        if tight:
            # test_preemption_preserves_greedy_output's pool and check:
            # the victim's re-prefill joins like any prompt, and decodes
            # what a fresh request for its folded prompt decodes
            seqs = [
                core.submit_prompt(p, greedy(10))
                for p in ("preempt probe one", "preempt probe two",
                          "preempt pr three")
            ]
            for seq in seqs:
                assert seq.done_event.wait(timeout=300)
                assert seq.num_output_tokens == 10
            assert core.scheduler.total_preemptions >= 1
            victim = next(s for s in seqs if s.preempt_count >= 1)
            folded = victim.num_prompt_tokens - victim.orig_prompt_len
            replay = core.submit_tokens(
                list(victim.prompt_ids), greedy(10 - folded)
            )
            assert replay.done_event.wait(timeout=300)
            assert replay.generated_ids == victim.generated_ids[folded:]
        else:
            odd = (
                {"frequency_penalty": 0.7} if case == "penalties"
                else {"logprobs": True, "top_logprobs": 2}
            )
            params = [
                SamplingParams(
                    max_tokens=n, temperature=0.0,
                    **(odd if i == 6 else {}),
                )
                for i, n in enumerate(_LOOP_LENGTHS[:10])
            ]
            alone, together, _ = _alone_then_together(
                core, _LOOP_PROMPTS[:10], params
            )
            assert together == alone
        assert case in reasons, reasons
        changes, drains = _membership(core)
        # (a drain that leaves no row to step builds nothing)
        assert drains >= len(reasons) >= 2 and changes > drains
    finally:
        core.stop()


@pytest.mark.fast  # seconds a case: runs in tier-1, unlike this file
@pytest.mark.parametrize("ending", ["max_tokens", "stop_id"])
def test_a_joiner_that_ends_at_its_first_token_leaves_no_live_row(ending):
    """A prompt whose first token ends it (``max_tokens`` 1, a stop id)
    joined the decode batch on the device before the host read that
    token: the chunk dispatched behind it steps its row once for
    nothing, and the next edit switches the row off."""
    core = EngineCore(
        tiny_config(decode_chunk=2, decode_pipeline=2, max_batch_slots=2,
                    prefix_cache=False),
        devices=jax.devices()[:1],
    )
    core.start()
    try:
        prompts = ["a long answer", "ends at once", "another long one",
                   "ends at once too", "the last long answer"]
        long = [greedy(14), greedy(11), greedy(16)]
        firsts = [
            core.generate([p], [greedy(1)])[0]["token_ids"][0]
            for p in prompts
        ]
        short = [
            greedy(1) if ending == "max_tokens" else SamplingParams(
                max_tokens=8, temperature=0.0, stop_token_ids=[first]
            )
            for first in firsts
        ]
        params = [long[0], short[1], long[1], short[3], long[2]]
        alone, together, (changes, drains) = _alone_then_together(
            core, prompts, params
        )
        assert together == alone
        assert [len(t) for t in together[1::2]] == [1, 1]
        assert changes >= 4 and drains == 0
        # the last stream standing decoded alone for several chunks:
        # the state it left has its row live and no other
        state = core._dec_state
        assert len(state["members"]) == 1
        assert int(np.asarray(state["active"]).sum()) == 1
        stats = core.get_stats()["scheduler"]
        assert stats["running"] == 0 and stats["used_pages"] == 0
    finally:
        core.stop()


@pytest.mark.fast  # seconds a case: runs in tier-1, unlike this file
def test_prefix_of_a_stream_whose_overshoot_is_in_flight():
    """Radix cache on: a prompt that repeats a finished stream's prompt
    and the head of its answer is admitted while the chunk that still
    steps that stream (overshoot, discarded at readback) is in flight,
    matches the pages the stream left in the tree, and decodes what it
    decodes on an engine without a cache.  The overshoot writes at and
    past the stream's last position, the tree holds full pages below it
    (scheduler._radix_insert_final)."""
    config = dict(decode_chunk=4, decode_pipeline=2, max_batch_slots=2)
    cached = EngineCore(tiny_config(**config), devices=jax.devices()[:1])
    plain = EngineCore(
        tiny_config(prefix_cache=False, **config), devices=jax.devices()[:1]
    )
    cached.start()
    plain.start()
    try:
        head = [41, 42, 43, 44, 45, 46, 47, 48, 49]
        side = [71, 72, 73, 74, 75]

        def serve(core, ids, n):
            seq = core.submit_tokens(list(ids), greedy(n))
            assert seq.done_event.wait(timeout=300)
            return list(seq.generated_ids)

        answer = serve(plain, head, 6)
        turn = head + answer[:3] + [81, 82, 83, 84]
        want = serve(plain, turn, 9)
        # what the joiner's dispatch finds in flight
        seen = []
        real = cached._dispatch_prompt

        def watching(plans, *args, **kwargs):
            for plan in plans:
                if plan.seq.prompt_ids == turn:
                    seen.append((
                        plan.cached_len,
                        [
                            s.status.value
                            for chunk in cached._pending_chunks
                            for s, _ in chunk[0]
                        ],
                    ))
            return real(plans, *args, **kwargs)

        cached._dispatch_prompt = watching
        other = cached.submit_tokens(list(side), greedy(40))
        first = cached.submit_tokens(list(head), greedy(6))
        second = cached.submit_tokens(list(turn), greedy(9))
        for seq in (other, first, second):
            assert seq.done_event.wait(timeout=300)
        assert list(first.generated_ids) == answer
        assert list(second.generated_ids) == want
        assert list(other.generated_ids) == serve(plain, side, 40)
        [(cached_len, in_flight)] = seen
        assert cached_len == 12, "the finished stream's pages did not match"
        assert "finished" in in_flight, "no overshoot was in flight"
        assert _membership(cached)[1] == 1
    finally:
        cached.stop()
        plain.stop()


@pytest.mark.fast  # seconds a case: runs in tier-1, unlike this file
@pytest.mark.parametrize("model_id", ["tiny-hybrid", "tiny-nemotron-h"])
def test_closed_loop_on_a_recurrent_state(model_id):
    """The two hybrid families: a slot's recurrent state row is written
    by the previous tenant's overshoot first and by the joiner's prompt
    program after (device order), so a stream that joins on the device
    decodes what it decodes alone."""
    core = EngineCore(
        load_config(
            model={"model_id": model_id, "engine_type": "jax_tpu",
                   "dtype": "float32", "max_model_len": 128},
            tpu={"dp": 1, "tp": 1, "ep": 1, "sp": 1, "kv_num_pages": 64,
                 "kv_page_size": 4, "max_batch_slots": 2,
                 "prefill_buckets": [16, 32], "use_pallas": False,
                 "decode_chunk": 2, "decode_pipeline": 2},
            scheduler={"max_queue_size": 16},
            logging={"level": "WARNING"},
        ),
        devices=jax.devices()[:1],
    )
    core.start()
    try:
        alone, together, (changes, drains) = _alone_then_together(
            core, _LOOP_PROMPTS[:7],
            [greedy(n) for n in (5, 9, 3, 12, 7, 4, 10)],
        )
        assert together == alone
        assert changes >= 4 and drains == 0
    finally:
        core.stop()


@pytest.mark.fast  # seconds: runs in tier-1, unlike this file
def test_decode_state_page_tables_are_a_copy_of_the_host_table():
    """The decode state's page tables are a device array of their own.
    On a CPU ``jnp.asarray`` may alias the host table (by the buffer's
    alignment, so by chance), and the next prompt dispatch rewrites a
    slot's row there for its new tenant while the chunk that still steps
    the old one (its overshoot) is in flight: that chunk then wrote the
    old stream's K/V into the new one's pages, about once in thirty
    runs of a chunked-prefill or preemption test under load."""
    core = EngineCore(
        tiny_config(decode_chunk=2, decode_pipeline=2),
        devices=jax.devices()[:1],
    )
    core.start()
    try:
        core.generate(["page table probe"], [greedy(6)])
        core._page_tables_np[:] = 7  # the engine is idle: nothing races
        core._refresh_page_tables([])
        np.testing.assert_array_equal(
            np.asarray(core._dec_state["page_tables"]), 7
        )
        core._page_tables_np[:] = 0
        np.testing.assert_array_equal(
            np.asarray(core._dec_state["page_tables"]), 7
        )
    finally:
        core.stop()


def test_moe_engine_end_to_end_expert_parallel():
    """The MoE decoder serves through the full continuous-batching engine
    with experts sharded over the ep axis (SURVEY.md section 2.2: EP is a
    first-class strategy the reference lacks entirely)."""
    n = min(2, jax.device_count())
    config = load_config(
        model={
            "model_id": "tiny-moe",
            "engine_type": "jax_tpu",
            "dtype": "float32",
            "max_model_len": 64,
        },
        tpu={
            "dp": 1, "tp": 1, "ep": n, "sp": 1,
            "num_devices": n,
            "kv_num_pages": 64, "kv_page_size": 4,
            "max_batch_slots": 2, "prefill_buckets": [16],
            "use_pallas": False,
        },
        scheduler={"max_queue_size": 8},
        logging={"level": "WARNING"},
    )
    core = EngineCore(config, devices=jax.devices()[:n])
    core.start()
    try:
        results = core.generate(
            ["moe serving probe", "second expert route"],
            [greedy(6)] * 2,
        )
        for r in results:
            assert r["num_tokens"] >= 1
            assert r["finish_reason"] in ("stop", "length")
        assert core.get_stats()["mesh"]["ep"] == n
    finally:
        core.stop()


def test_sp_x_tp_end_to_end():
    """sp x tp (the natural multi-chip long-context mesh, e.g. v5e-8 as
    sp4 x tp2): the sp shard bodies run per (sp, tp) shard on local
    heads (r4: tp-aware specs in parallel/sp_decode.py _tp_axis).
    Greedy output must be token-identical to the single-device engine."""
    if jax.device_count() < 4:
        pytest.skip("needs >=4 devices")

    def cfg(sp, tp, n_dev):
        return load_config(
            model={"model_id": "tiny-dense", "engine_type": "jax_tpu",
                   "dtype": "float32", "max_model_len": 64},
            tpu={"dp": 1, "tp": tp, "ep": 1, "sp": sp,
                 "num_devices": n_dev,
                 "kv_num_pages": 64, "kv_page_size": 4,
                 "max_batch_slots": 2, "prefill_buckets": [16, 32],
                 "use_pallas": False},
            scheduler={"max_queue_size": 8},
            logging={"level": "WARNING"},
        )

    prompt_ids = [5 + (i % 21) for i in range(26)]
    outs = []
    for sp, tp, n_dev in ((1, 1, 1), (2, 2, 4)):
        core = EngineCore(cfg(sp, tp, n_dev), devices=jax.devices()[:n_dev])
        core.start()
        try:
            seq = core.submit_tokens(prompt_ids, greedy(8))
            assert seq.done_event.wait(300)
            outs.append(list(seq.generated_ids))
        finally:
            core.stop()
    assert outs[0] == outs[1]


def test_moe_ep_x_sp_end_to_end():
    """ep x sp composes: the sp shard_map covers only attention + the
    KV write, so the MoE FFN's ep dispatch stays under jit auto
    sharding.  Greedy output must be token-identical to the ep=1/sp=1
    engine."""
    if jax.device_count() < 4:
        pytest.skip("needs >=4 devices")

    def cfg(ep, sp, n_dev):
        return load_config(
            model={
                "model_id": "tiny-moe",
                "engine_type": "jax_tpu",
                "dtype": "float32",
                "max_model_len": 64,
            },
            tpu={
                "dp": 1, "tp": 1, "ep": ep, "sp": sp,
                "num_devices": n_dev,
                "kv_num_pages": 64, "kv_page_size": 4,
                "max_batch_slots": 2, "prefill_buckets": [16, 32],
                "use_pallas": False,
            },
            scheduler={"max_queue_size": 8},
            logging={"level": "WARNING"},
        )

    prompt_ids = [3 + (i % 19) for i in range(24)]
    outs = []
    for ep, sp, n_dev in ((1, 1, 1), (2, 2, 4)):
        core = EngineCore(cfg(ep, sp, n_dev), devices=jax.devices()[:n_dev])
        core.start()
        try:
            seq = core.submit_tokens(prompt_ids, greedy(8))
            assert seq.done_event.wait(300)
            outs.append(list(seq.generated_ids))
            if sp > 1:
                assert "sp" in str(core.k_pages.sharding.spec)
        finally:
            core.stop()
    assert outs[0] == outs[1]


def test_sp_engine_long_prefill_end_to_end():
    """Sequence-parallel serving: with sp=2 the engine's prefill runs ring
    attention over the sp axis (SURVEY.md section 5.7 long-context path) and
    decode continues normally."""
    if jax.device_count() < 2:
        pytest.skip("needs >=2 devices")
    config = load_config(
        model={
            "model_id": "tiny-dense",
            "engine_type": "jax_tpu",
            "dtype": "float32",
            "max_model_len": 64,
        },
        tpu={
            "dp": 1, "tp": 1, "ep": 1, "sp": 2,
            "num_devices": 2,
            "kv_num_pages": 64, "kv_page_size": 4,
            "max_batch_slots": 2, "prefill_buckets": [16, 32],
            "use_pallas": False,
        },
        scheduler={"max_queue_size": 8},
        logging={"level": "WARNING"},
    )
    core = EngineCore(config, devices=jax.devices()[:2])
    core.start()
    try:
        # a prompt long enough to span several sp shards of the 32 bucket
        long_prompt = " ".join(["ring"] * 24)
        [r] = core.generate([long_prompt], [greedy(8)])
        assert r["num_tokens"] >= 1
        assert core.get_stats()["mesh"]["sp"] == 2
    finally:
        core.stop()


def test_sp_engine_gemma2_sliding_window():
    """Gemma-2 (sliding-window + softcap + sandwich norms) under sp=2:
    ring prefill composes the per-layer window mask with the block-
    position masks, so greedy output must be token-identical to the
    sp=1 engine (VERDICT r2 next-10: the guard is gone)."""
    if jax.device_count() < 2:
        pytest.skip("needs >=2 devices")

    def gemma_cfg(sp, n_dev):
        return load_config(
            model={
                "model_id": "tiny-gemma2",
                "engine_type": "jax_tpu",
                "dtype": "float32",
                "max_model_len": 64,
            },
            tpu={
                "dp": 1, "tp": 1, "ep": 1, "sp": sp,
                "num_devices": n_dev,
                "kv_num_pages": 64, "kv_page_size": 4,
                "max_batch_slots": 2, "prefill_buckets": [16, 32],
                "use_pallas": False,
            },
            scheduler={"max_queue_size": 8},
            logging={"level": "WARNING"},
        )

    # prompt long enough to cross the 8-token sliding window AND span
    # both sp shards of the 32 bucket
    prompt_ids = [2 + (i % 37) for i in range(30)]
    outs = []
    for sp, n_dev in ((1, 1), (2, 2)):
        core = EngineCore(gemma_cfg(sp, n_dev), devices=jax.devices()[:n_dev])
        core.start()
        try:
            seq = core.submit_tokens(prompt_ids, greedy(10))
            assert seq.done_event.wait(300)
            outs.append(list(seq.generated_ids))
        finally:
            core.stop()
    assert outs[0] == outs[1]


def test_sp_decode_token_identical_and_capacity_sharded():
    """Decode now runs sp-SHARDED (VERDICT r2 partial-22): greedy output
    across multiple decode page boundaries must be token-identical to
    the sp=1 engine, and the KV pool must actually shard over sp (the
    long-context capacity relief) with per-shard trash pages reserved."""
    if jax.device_count() < 4:
        pytest.skip("needs >=4 devices")

    def cfg(sp, n_dev):
        return load_config(
            model={
                "model_id": "tiny-dense",
                "engine_type": "jax_tpu",
                "dtype": "float32",
                "max_model_len": 64,
            },
            tpu={
                "dp": 1, "tp": 1, "ep": 1, "sp": sp,
                "num_devices": n_dev,
                "kv_num_pages": 64, "kv_page_size": 4,
                "max_batch_slots": 2, "prefill_buckets": [16],
                "use_pallas": False,
            },
            scheduler={"max_queue_size": 8},
            logging={"level": "WARNING"},
        )

    prompt_ids = [3 + (i % 29) for i in range(14)]
    outs = []
    for sp, n_dev in ((1, 1), (4, 4)):
        core = EngineCore(cfg(sp, n_dev), devices=jax.devices()[:n_dev])
        if sp > 1:
            # pool sharded over sp + one reserved trash page per shard
            assert core.allocator.reserved == frozenset({0, 16, 32, 48})
            from jax.sharding import PartitionSpec as P

            assert core.k_pages.sharding.spec == P(
                None, None, "sp", None, None
            )
        core.start()
        try:
            # 20 generated tokens: crosses several 4-token page
            # boundaries, so decode allocates pages on multiple shards
            seq = core.submit_tokens(prompt_ids, greedy(20))
            assert seq.done_event.wait(300)
            outs.append(list(seq.generated_ids))
        finally:
            core.stop()
    assert outs[0] == outs[1]


def test_sp_bucket_divisibility_enforced():
    config = load_config(
        model={"model_id": "tiny-dense", "engine_type": "jax_tpu",
               "dtype": "float32", "max_model_len": 60},
        tpu={"dp": 1, "tp": 1, "ep": 1, "sp": 4, "num_devices": 4,
             "kv_num_pages": 64, "kv_page_size": 2,
             "max_batch_slots": 2, "prefill_buckets": [6],
             "use_pallas": False},
        logging={"level": "WARNING"},
    )
    if jax.device_count() < 4:
        pytest.skip("needs >=4 devices")
    with pytest.raises(ValueError, match="not divisible by sp"):
        EngineCore(config, devices=jax.devices()[:4])


def test_stop_string_truncates(engine):
    """A stop string terminates the sequence with finish_reason "stop" and
    the final text is truncated before the match (VERDICT r1 missing-4; the
    reference passes stop to vLLM, vgate/backends/vllm_backend.py:39-46)."""
    # probe the greedy stream to learn its text, then pick a mid-text
    # substring as the stop string
    [probe] = engine.generate(["stop string probe"], [greedy(10)])
    text = probe["text"]
    assert len(text) >= 4
    mid = len(text) // 2
    stop = text[mid : mid + 2]
    prefix = text[:mid]
    assert stop and stop not in prefix  # make the probe site unambiguous
    [r] = engine.generate(
        ["stop string probe"],
        [SamplingParams(max_tokens=10, temperature=0.0, stop=[stop])],
    )
    assert r["finish_reason"] == "stop"
    assert stop not in r["text"]
    assert r["text"] == text[: text.index(stop)]


def test_stop_string_mid_chunk_frees_slot():
    """Stop detection happens at chunk readback; the slot must be freed."""
    core = EngineCore(tiny_config(decode_chunk=8), devices=jax.devices()[:1])
    core.start()
    try:
        [probe] = core.generate(["stop chunk probe"], [greedy(12)])
        stop = probe["text"][1:3]
        [r] = core.generate(
            ["stop chunk probe"],
            [SamplingParams(max_tokens=12, temperature=0.0, stop=[stop])],
        )
        assert r["finish_reason"] == "stop"
        assert stop not in r["text"]
        assert not core.scheduler.running
    finally:
        core.stop()


def test_seed_reproducible_across_runs(engine):
    """Same seed at temperature>0 => identical tokens, independent of the
    engine's global step counter (the key is a function of (seed, token
    index) only)."""
    sp = lambda: SamplingParams(max_tokens=8, temperature=1.0, seed=1234)
    [a] = engine.generate(["seeded sampling probe"], [sp()])
    # perturb the global step counter with an unrelated request
    engine.generate(["interleaved other work"], [greedy(4)])
    [b] = engine.generate(["seeded sampling probe"], [sp()])
    assert a["token_ids"] == b["token_ids"]


def test_seed_independent_of_batch_composition(engine):
    """A seeded request gives the same tokens alone or batched with
    unseeded neighbours (per-slot keys, not one key per step)."""
    sp = SamplingParams(max_tokens=6, temperature=1.0, seed=77)
    [alone] = engine.generate(["batch seeded probe"], [sp])
    batched = engine.generate(
        ["noise one", "batch seeded probe", "noise two"],
        [
            SamplingParams(max_tokens=6, temperature=1.0),
            SamplingParams(max_tokens=6, temperature=1.0, seed=77),
            SamplingParams(max_tokens=6, temperature=1.0),
        ],
    )
    assert batched[1]["token_ids"] == alone["token_ids"]


def test_different_seeds_diverge(engine):
    """Different seeds at temperature>0 should (overwhelmingly) differ."""
    outs = []
    for seed in (1, 2, 3):
        [r] = engine.generate(
            ["divergence probe"],
            [SamplingParams(max_tokens=8, temperature=1.0, seed=seed)],
        )
        outs.append(tuple(r["token_ids"]))
    assert len(set(outs)) > 1


def test_gemma2_engine_end_to_end_across_window():
    """The Gemma-2 family (sliding-window + softcap attention, sandwich
    norms, tied embeddings) serves through the full continuous-batching
    engine, generating past the sliding window (8) so decode steps beyond
    the window exercise the local-attention mask over paged KV."""
    config = load_config(
        model={
            "model_id": "tiny-gemma2",
            "engine_type": "jax_tpu",
            "dtype": "float32",
            "max_model_len": 64,
        },
        tpu={
            "dp": 1, "tp": 1, "ep": 1, "sp": 1,
            "num_devices": 1,
            "kv_num_pages": 64, "kv_page_size": 4,
            "max_batch_slots": 2, "prefill_buckets": [8],
            # use_pallas left ON: the kernels take Gemma's
            # window/softcap/scale natively, so the engine keeps them on
            # wherever the platform supports Pallas (TPU)
            "use_pallas": True,
        },
        scheduler={"max_queue_size": 8},
        logging={"level": "WARNING"},
    )
    core = EngineCore(config, devices=jax.devices()[:1])
    # kernels on real TPU, jnp twins elsewhere — platform is the only gate
    assert core.use_pallas == (jax.devices()[0].platform == "tpu")
    core.start()
    try:
        results = core.generate(
            ["sliding window probe", "second gemma request"],
            [greedy(16)] * 2,  # prompt+output crosses the 8-token window
        )
        for r in results:
            assert r["num_tokens"] >= 1
            assert r["finish_reason"] in ("stop", "length")
            assert np.all(np.isfinite(r.get("ttft", 0.0)))
    finally:
        core.stop()


def test_pp_engine_gemma2_sliding_window():
    """Gemma-2 (sliding-window + softcap + embed scale) through the
    pipeline relay: per-layer windows thread the stage scan and
    softcap/scale ride the attention partials (parallel/pipeline.py,
    r4 — the r3 rejection is gone).  Greedy output must be
    token-identical to the pp=1 engine."""
    if jax.device_count() < 2:
        pytest.skip("needs 2 devices")

    def cfg(pp, n_dev):
        return load_config(
            model={
                "model_id": "tiny-gemma2",
                "engine_type": "jax_tpu",
                "dtype": "float32",
                "max_model_len": 64,
            },
            tpu={
                "dp": 1, "tp": 1, "ep": 1, "sp": 1, "pp": pp,
                "num_devices": n_dev,
                "kv_num_pages": 64, "kv_page_size": 4,
                "max_batch_slots": 2, "prefill_buckets": [8, 32],
                "use_pallas": False,
            },
            scheduler={"max_queue_size": 8},
            logging={"level": "WARNING"},
        )

    # prompt crosses the tiny-gemma2 sliding window so the local-layer
    # masks matter, and decode runs well past it
    prompt_ids = [2 + (i % 37) for i in range(30)]
    outs = []
    for pp, n_dev in ((1, 1), (2, 2)):
        core = EngineCore(cfg(pp, n_dev), devices=jax.devices()[:n_dev])
        core.start()
        try:
            seq = core.submit_tokens(prompt_ids, greedy(10))
            assert seq.done_event.wait(300)
            outs.append(list(seq.generated_ids))
        finally:
            core.stop()
    assert outs[0] == outs[1]


def test_stop_token_ids_finish(engine):
    """A token in stop_token_ids ends the sequence with finish_reason
    "stop" (the id-level sibling of stop strings)."""
    # discover what the model greedily emits, then stop on its 3rd token
    [base] = engine.generate(["stop id probe"], [greedy(8)])
    assert len(base["token_ids"]) >= 4
    target = base["token_ids"][2]
    [stopped] = engine.generate(
        ["stop id probe"],
        [SamplingParams(max_tokens=8, temperature=0.0,
                        stop_token_ids=[target])],
    )
    assert stopped["finish_reason"] == "stop"
    assert stopped["token_ids"][: 3] == base["token_ids"][: 3]
    assert len(stopped["token_ids"]) == 3


# ----------------------------------------------------- chunked prefill

def chunked_cfg(prefill_chunk, **model_overrides):
    model = {
        "model_id": "tiny-dense",
        "engine_type": "jax_tpu",
        "dtype": "float32",
        "max_model_len": 64,
    }
    model.update(model_overrides)
    return load_config(
        model=model,
        tpu={
            "dp": 1, "tp": 1, "ep": 1, "sp": 1, "num_devices": 1,
            "kv_num_pages": 128, "kv_page_size": 4,
            "max_batch_slots": 2, "prefill_buckets": [8, 16],
            "use_pallas": False,
            "prefill_chunk": prefill_chunk,
        },
        scheduler={"max_queue_size": 8},
        logging={"level": "WARNING"},
    )


def test_chunked_prefill_token_identical_to_whole_prompt():
    """A 40-token prompt with a 16-token chunk cap runs three serial
    suffix passes (16+16+8); greedy output must be token-identical to
    the unchunked engine, seeded sampled output too (the final chunk
    carries the real sampling params)."""
    prompt_ids = [3 + (i % 31) for i in range(40)]
    outs = []
    for chunk in (0, 16):
        core = EngineCore(chunked_cfg(chunk), devices=jax.devices()[:1])
        if chunk:
            # ladder capped at the chunk size
            assert core.scheduler.prefill_buckets[-1] == chunk
        core.start()
        try:
            g = core.submit_tokens(prompt_ids, greedy(10))
            s = core.submit_tokens(
                prompt_ids[::-1],
                SamplingParams(max_tokens=8, temperature=0.8, seed=13),
            )
            assert g.done_event.wait(300) and s.done_event.wait(300)
            outs.append(
                (list(g.generated_ids), list(s.generated_ids))
            )
        finally:
            core.stop()
    assert outs[0] == outs[1]


def test_chunked_prefill_with_prefix_cache_hit():
    """Chunked prefill composes with automatic prefix caching: the
    second identical prompt starts its chunks after the cached pages
    and produces identical greedy output."""
    cfg = chunked_cfg(16)
    assert cfg.tpu.prefix_cache
    core = EngineCore(cfg, devices=jax.devices()[:1])
    core.start()
    try:
        prompt_ids = [5 + (i % 17) for i in range(40)]
        a = core.submit_tokens(prompt_ids, greedy(8))
        assert a.done_event.wait(300)
        hits_before = core.scheduler.total_prefix_hit_tokens
        b = core.submit_tokens(prompt_ids, greedy(8))
        assert b.done_event.wait(300)
        assert list(a.generated_ids) == list(b.generated_ids)
        assert core.scheduler.total_prefix_hit_tokens > hits_before
        stats = core.scheduler.get_stats()
        assert stats["running"] == 0
    finally:
        core.stop()


def test_chunked_prefill_rejects_pp():
    """pp still reshapes the prompt pass incompatibly (sp no longer
    does: chunks ride the sp-capable suffix program, RESULTS_r4)."""
    if jax.device_count() < 2:
        pytest.skip("needs 2 devices")
    cfg = load_config(
        model={"model_id": "tiny-dense", "engine_type": "jax_tpu",
               "dtype": "float32", "max_model_len": 64},
        tpu={"dp": 1, "tp": 1, "ep": 1, "sp": 1, "pp": 2,
             "num_devices": 2,
             "kv_num_pages": 64, "kv_page_size": 4,
             "max_batch_slots": 2, "prefill_buckets": [16],
             "use_pallas": False, "prefill_chunk": 16},
        logging={"level": "WARNING"},
    )
    with pytest.raises(ValueError, match="prefill_chunk"):
        EngineCore(cfg, devices=jax.devices()[:2])


def _sp_prefix_cfg(sp, n_dev, prefill_chunk=0):
    return load_config(
        model={"model_id": "tiny-dense", "engine_type": "jax_tpu",
               "dtype": "float32", "max_model_len": 64},
        tpu={"dp": 1, "tp": 1, "ep": 1, "sp": sp, "num_devices": n_dev,
             "kv_num_pages": 64, "kv_page_size": 4,
             "max_batch_slots": 2, "prefill_buckets": [16, 32],
             "use_pallas": False, "prefill_chunk": prefill_chunk},
        scheduler={"max_queue_size": 8},
        logging={"level": "WARNING"},
    )


def test_sp_prefix_cache_hit_end_to_end():
    """Prefix caching now composes with sp (VERDICT r3 next-7): on an
    sp=2 pool the second identical prompt rides the sp-sharded suffix
    program (sp_suffix_attention_and_write), records a prefix hit, and
    produces output token-identical to the sp=1 engine."""
    if jax.device_count() < 2:
        pytest.skip("needs >=2 devices")
    prompt_ids = [7 + (i % 23) for i in range(28)]
    outs = []
    for sp, n_dev in ((1, 1), (2, 2)):
        cfg = _sp_prefix_cfg(sp, n_dev)
        core = EngineCore(cfg, devices=jax.devices()[:n_dev])
        assert core.prefix_cache_enabled
        core.start()
        try:
            a = core.submit_tokens(prompt_ids, greedy(8))
            assert a.done_event.wait(300)
            hits_before = core.scheduler.total_prefix_hit_tokens
            b = core.submit_tokens(prompt_ids, greedy(8))
            assert b.done_event.wait(300)
            assert list(a.generated_ids) == list(b.generated_ids)
            assert core.scheduler.total_prefix_hit_tokens > hits_before
            outs.append(list(b.generated_ids))
        finally:
            core.stop()
    assert outs[0] == outs[1]


def test_sp_chunked_prefill_end_to_end():
    """Chunked prefill under sp=2: long prompts run page-aligned suffix
    chunks through the sp-sharded suffix program; greedy output is
    token-identical to the sp=1 chunked engine."""
    if jax.device_count() < 2:
        pytest.skip("needs >=2 devices")
    prompt_ids = [3 + (i % 29) for i in range(44)]
    outs = []
    for sp, n_dev in ((1, 1), (2, 2)):
        core = EngineCore(
            _sp_prefix_cfg(sp, n_dev, prefill_chunk=16),
            devices=jax.devices()[:n_dev],
        )
        core.start()
        try:
            seq = core.submit_tokens(prompt_ids, greedy(8))
            assert seq.done_event.wait(300)
            outs.append(list(seq.generated_ids))
        finally:
            core.stop()
    assert outs[0] == outs[1]


# ------------------------------------------------------ client aborts

def test_abort_running_sequence_frees_resources():
    """request_abort on a RUNNING sequence: the engine finishes it with
    reason "abort" at its next tick, frees slot+pages, and co-resident
    sequences complete untouched."""
    core = EngineCore(
        tiny_config(decode_chunk=1), devices=jax.devices()[:1]
    )
    core.start()
    try:
        victim = core.submit_tokens([3] * 12, greedy(40))
        mate = core.submit_tokens([9] * 12, greedy(10))
        # cancel as soon as the first token lands (decode_chunk=1 on the
        # CPU-pinned test mesh steps in milliseconds, so the remaining
        # 39-token budget cannot complete inside this tight poll)
        import time as _t

        for _ in range(2000):
            if victim.num_output_tokens >= 1:
                break
            _t.sleep(0.005)
        assert victim.num_output_tokens >= 1
        victim.request_abort()
        assert victim.done_event.wait(120)
        assert victim.finish_reason == "abort"
        assert victim.num_output_tokens < 40  # stopped early
        assert mate.done_event.wait(300)
        assert mate.num_output_tokens == 10
        stats = core.scheduler.get_stats()
        assert stats["aborted"] == 1
        assert stats["running"] == 0
        assert stats["used_pages"] == 0
    finally:
        core.stop()


def test_abort_waiting_sequence_drops_at_queue_head():
    """A queued (not yet admitted) sequence whose client cancelled is
    dropped when it reaches the queue head, never prefilled."""
    core = EngineCore(
        tiny_config(max_batch_slots=1), devices=jax.devices()[:1]
    )
    core.start()
    try:
        runner = core.submit_tokens([3] * 8, greedy(8))
        queued = core.submit_tokens([5] * 8, greedy(8))
        queued.request_abort()
        assert queued.done_event.wait(300)
        assert queued.finish_reason == "abort"
        assert queued.num_output_tokens == 0
        assert runner.done_event.wait(300)
        assert runner.num_output_tokens == 8
        assert core.scheduler.get_stats()["aborted"] == 1
    finally:
        core.stop()


def test_stream_disconnect_aborts_sequence():
    """Closing the SSE token stream mid-generation (client disconnect)
    aborts the underlying sequence instead of decoding to completion."""
    import asyncio

    from vgate_tpu.backends.jax_backend import JaxTPUBackend

    backend = JaxTPUBackend()
    backend.load_model(tiny_config(decode_chunk=1, num_devices=1))
    try:
        async def run():
            agen = backend.stream_async(
                "stream abort probe",
                SamplingParams(max_tokens=40, temperature=0.0),
            )
            await agen.__anext__()  # first delta arrived
            await agen.aclose()  # client went away

        asyncio.run(run())
        core = backend.core
        deadline = 120
        import time as _t

        t0 = _t.perf_counter()
        while (
            core.scheduler.get_stats()["running"] > 0
            and _t.perf_counter() - t0 < deadline
        ):
            _t.sleep(0.05)
        stats = core.scheduler.get_stats()
        assert stats["running"] == 0
        assert stats["used_pages"] == 0
        assert stats["aborted"] == 1
    finally:
        backend.shutdown()


def test_pick_chunk_caps_under_admission_pressure():
    """With prompts waiting AND a free slot, the next decode chunk caps
    at decode_chunk/8 so the loop returns to admission quickly; with no
    free slot (or an empty queue) full-size chunks are kept."""
    from vgate_tpu.runtime.sequence import Sequence

    core = EngineCore(
        tiny_config(decode_chunk=32, max_batch_slots=2),
        devices=jax.devices()[:1],
    )
    try:
        seq = Sequence(prompt_ids=[1, 2, 3], params=greedy(40))
        seq.output_ids = [5]
        seq.generated_ids = [5]
        # idle queue: full chunk
        assert core._pick_chunk([seq]) == 32
        # waiting prompt + free slot: capped to decode_chunk/8 = 4
        core.scheduler.waiting.append(
            Sequence(prompt_ids=[7], params=greedy(4))
        )
        assert core._pick_chunk([seq]) == 4
        # waiting prompt but slots saturated: full chunk again
        core.scheduler.slots[0] = seq
        core.scheduler.slots[1] = Sequence(
            prompt_ids=[8], params=greedy(4)
        )
        assert core._pick_chunk([seq]) == 32
    finally:
        core.stop()


def test_stream_async_reports_usage():
    """The real engine's token stream delivers usage through on_usage
    (the OpenAI stream_options.include_usage plumbing)."""
    import asyncio

    from vgate_tpu.backends.jax_backend import JaxTPUBackend

    backend = JaxTPUBackend()
    backend.load_model(tiny_config(num_devices=1))
    try:
        seen = {}

        async def run():
            agen = backend.stream_async(
                "usage stream probe",
                SamplingParams(max_tokens=5, temperature=0.0),
                on_usage=lambda u: seen.update(u),
            )
            async for _ in agen:
                pass

        asyncio.run(run())
        assert seen["completion_tokens"] >= 1
        assert (
            seen["total_tokens"]
            == seen["prompt_tokens"] + seen["completion_tokens"]
        )
    finally:
        backend.shutdown()


def test_chunked_prefill_carries_logprobs():
    """The final chunk of a chunked prefill delegates to the suffix
    group, so a long prompt's request-level logprobs must come back
    aligned with every generated token."""
    core = EngineCore(chunked_cfg(16), devices=jax.devices()[:1])
    core.start()
    try:
        seq = core.submit_tokens(
            [3 + (i % 13) for i in range(40)],
            SamplingParams(
                max_tokens=6, temperature=0.0, logprobs=True,
                top_logprobs=3,
            ),
        )
        assert seq.done_event.wait(300)
        assert seq.num_output_tokens == len(seq.logprob_data) == 6
        entries = core.logprob_entries(seq)
        assert len(entries) == 6
        for e in entries:
            assert e["logprob"] <= 0.0
            assert len(e["top_logprobs"]) == 3
    finally:
        core.stop()


def test_engine_fatal_fails_inflight_and_rejects_new():
    """A fatal engine-loop error (SURVEY 5.3) must fail EVERY owed
    future — in-flight, waiting, and still-queued submissions — free
    the slots, and reject new submissions with "engine is dead": the
    containment contract the dp router builds on.  The fault is
    injected BEFORE submission so no finish race exists; the queued
    sequence exercises the submit-queue drain (a client blocked on it
    would otherwise hang forever)."""
    from vgate_tpu.runtime.sequence import SeqStatus

    core = EngineCore(tiny_config(), devices=jax.devices()[:1])
    core.start()
    try:
        boom = RuntimeError("injected loop fault")

        def bad_tick():
            raise boom

        core._tick = bad_tick
        core._wakeup.set()
        try:
            seq = core.submit_tokens([5, 9, 13, 17], greedy(40))
        except RuntimeError:
            seq = None  # loop died before the submit: rejected, correct
        if seq is not None:
            # queued (or admitted) before the loop died: the fatal path
            # must fail it — a hang here is the submit-queue-drain bug
            assert seq.done_event.wait(60)
            assert seq.status is SeqStatus.FAILED
            assert seq.error is boom
        assert all(s is None for s in core.scheduler.slots)
        with pytest.raises(RuntimeError, match="engine is dead"):
            core.submit_tokens([1, 2, 3], greedy(2))
    finally:
        core._fatal = None
        core.stop()


def test_submit_fatal_toctou_drain():
    """If the engine dies between submit_tokens' fatal check and its
    queue put, the fatal handler's drain has already run and will never
    see the new sequence — the post-put re-check must drain/fail it and
    raise instead of leaving the client hung on done_event (ADVICE r4,
    engine_core.py submit_tokens)."""
    from vgate_tpu.runtime.sequence import SeqStatus

    core = EngineCore(tiny_config(), devices=jax.devices()[:1])
    boom = RuntimeError("died mid-submit")
    real_put = core._submit_q.put

    def racing_put(seq):
        real_put(seq)
        core._fatal = boom  # the loop died right as the put landed

    core._submit_q.put = racing_put
    with pytest.raises(RuntimeError, match="engine is dead"):
        core.submit_tokens([1, 2, 3], greedy(2))
    assert core._submit_q.empty()


# ------------------------------------------- the programs a prompt compiles

def _variant_cfg(**tpu_overrides):
    """decode_chunk 1 and groups of one row: the variants a case compiles
    then depend on its prompt layout alone, not on when a token stops a
    stream or on which tick a request arrives."""
    return tiny_config(
        decode_chunk=1, prefill_batch_max=1,
        prefix_cache={"enabled": True, "cow_min_tokens": 2},
        **tpu_overrides,
    )


def _run(core, ids, params=None):
    seq = core.submit_tokens(list(ids), params or greedy(3))
    assert seq.done_event.wait(300)
    assert seq.error is None
    return seq


def _fresh(core, salt):
    _run(core, [salt + i for i in range(10)])


def _aligned_hit(core, salt):
    base = [salt + i for i in range(12)]
    _run(core, base)
    hits = core.scheduler.total_prefix_hit_tokens
    _run(core, base[:8] + [salt + 40 + i for i in range(4)])
    assert core.scheduler.total_prefix_hit_tokens == hits + 8


def _cow_hit(core, salt):
    base = [salt + i for i in range(14)]
    _run(core, base)
    cows = core.radix_cache.total_cow_copies
    _run(core, base[:10] + [salt + 40 + i for i in range(4)])
    assert core.radix_cache.total_cow_copies == cows + 1


def _chunked(core, salt):
    _run(core, [salt + (i % 29) for i in range(40)])


def _re_prefill_with_penalties(core, salt):
    """What a preemption leaves: the generated tokens folded into the
    prompt, still counted by the penalties of the re-sampled token."""
    from vgate_tpu.runtime.sequence import Sequence

    done = [salt + 50, salt + 51, salt + 50]
    seq = Sequence(
        prompt_ids=[salt + i for i in range(10)],
        params=SamplingParams(
            max_tokens=6, temperature=0.0, frequency_penalty=0.5
        ),
        output_ids=list(done),
        generated_ids=list(done),
    )
    seq.reset_for_recompute()
    core.submit_existing(seq)
    assert seq.done_event.wait(300)
    assert seq.error is None
    assert seq.preempt_count == 1 and seq.num_prompt_tokens == 13


def _sampling_extras(core, salt):
    seq = _run(core, [salt + i for i in range(10)], SamplingParams(
        max_tokens=3, min_tokens=3, temperature=0.0, logprobs=True,
        top_logprobs=2, logit_bias={salt + 3: 4.0, salt + 4: -4.0,
                                    salt + 5: 1.0},
    ))
    assert len(seq.logprob_data) == 3


def _suffix(bucket, ctx_pages, unaligned=False):
    return str(
        ("suffix", bucket, 1, ctx_pages, False, None, 0, None, unaligned)
    )


# (record_compile kind, variant key, trigger), read from the tree before
# the dispatchers were merged (PR 28's)
_DECODE_PLAIN = ("decode", "(1, False, None, 0, True, None)", "chunk_variant")
_PREFILL_PLAIN = ("prefill", "(16, 1, False, None, 0, None)", "bucket")
_VARIANT_CASES = {
    "fresh-prompt": (_variant_cfg, _fresh, {_PREFILL_PLAIN, _DECODE_PLAIN}),
    "aligned-prefix-hit": (_variant_cfg, _aligned_hit, {
        _PREFILL_PLAIN, _DECODE_PLAIN,
        ("suffix_prefill", _suffix(8, 4), "bucket"),
    }),
    "copy-on-write-hit": (_variant_cfg, _cow_hit, {
        _PREFILL_PLAIN, _DECODE_PLAIN,
        ("suffix_prefill", _suffix(8, 4, unaligned=True), "bucket"),
    }),
    "chunked-prompt": (
        lambda: _variant_cfg(prefill_chunk=16, prefill_buckets=[8, 16]),
        _chunked,
        {
            ("chunked_prefill", _suffix(16, 4), "ctx_width"),
            ("chunked_prefill", _suffix(16, 8), "ctx_width"),
            ("suffix_prefill", _suffix(8, 16), "bucket"),
            _DECODE_PLAIN,
        },
    ),
    "re-prefill-with-penalties": (_variant_cfg, _re_prefill_with_penalties, {
        ("prefill", "(16, 1, True, None, 0, None)", "bucket"),
        ("decode", "(1, True, None, 0, True, None)", "chunk_variant"),
    }),
    "logprobs-min-tokens-logit-bias": (_variant_cfg, _sampling_extras, {
        ("prefill", "(16, 1, False, 1, 8, 4)", "bucket"),
        ("decode", "(1, False, 1, 8, False, 4)", "chunk_variant"),
    }),
}


@pytest.mark.fast  # seconds a case: runs in tier-1, unlike this file
@pytest.mark.parametrize("case", sorted(_VARIANT_CASES))
def test_prompt_layout_compiles_exactly_these_programs(case):
    """Pins, per prompt layout, the (kind, variant key) pairs the compile
    ledger records, and that the same layout a second time (other
    tokens, so nothing is cached) compiles nothing more."""
    make_cfg, drive, expected = _VARIANT_CASES[case]
    core = EngineCore(make_cfg(), devices=jax.devices()[:1])
    core.start()
    try:
        def ledger():
            return {
                (e["program"], e["signature"], e["trigger"]): e["count"]
                for e in core.perf.compile_ledger()
            }

        drive(core, 3)
        assert ledger() == dict.fromkeys(expected, 1)
        drive(core, 60)
        assert ledger() == dict.fromkeys(expected, 1)
    finally:
        core.stop()
