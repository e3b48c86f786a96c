"""``tiny-eva`` through the ENGINE against the plain reference
(``perfbench/references/evabyte.py``): unequal rows in one wave and what
``/stats`` and ``/debug/perf`` report of the windows and the summary
rows, pages counted by the tokens a pool row stands for, chunked
prefill, a slot reused, preemption by recompute, journal replay; and
what knows pages only, refused by name at engine construction."""

import dataclasses

import numpy as np
import pytest

from perfbench.references import evabyte as ref
from tests import family_contract as contract
from vgate_tpu.models import specs

PS, SLOTS = 4, 4  # page rows, decode slots
W, C = 32, 4  # tiny-eva's window and chunk
FAMILY = contract.Family(
    "evabyte-6.5b-l8.json", ref=ref,
    tol={"float32": 1e-4},  # float32 on both sides: tests/test_evabyte.py
    tpu={"kv_num_pages": 64, "kv_page_size": PS, "max_batch_slots": SLOTS,
         "prefill_buckets": [32, 96], "decode_chunk": 4},
    keeps="window of exact rows")
# the rehearsal serves the preset with windows of 512 (the configuration
# file says why); the engine tests take the preset as it is: the
# record's cached reading of the file, with the preset's own window
TINY = dict(FAMILY.cfg, window_size=W, chunk_size=C)
FAMILY.__dict__["_rehearsal"] = dict(FAMILY._rehearsal, model=TINY)


def pages_of(n_tokens: int) -> int:
    return -(-(-(-n_tokens // C)) // PS)


@pytest.fixture(scope="module")
def engine():
    with contract.booted(FAMILY) as core:
        yield core


def test_unequal_rows_through_the_engine_and_what_it_reports(engine):
    """Three prompts in one wave (inside a window, two windows, three
    with a partial chunk), each a whole-prompt pass and 40 decode steps
    in chunks of 4, across the close of a window or two; /stats and
    /debug/perf say what the cache is and what moved."""
    lens, steps = (7, 45, 70), 40
    contract.unequal_rows(FAMILY, engine, lens, max_tokens=steps)
    assert not engine.prefix_cache_enabled
    assert engine.allocator.num_used == 0  # every page came back
    stats = engine.get_stats()
    # a page: 4 layers x (K, V) x 4 heads x 4 rows x 16 x 4 B
    assert stats["kv_page_bytes"] == 4 * 2 * 4 * PS * 16 * 4
    assert stats["kv_pool"] == {
        "row_tokens": C, "rows": 63 * PS, "rows_used": 0,
        "tokens": 63 * PS * C, "tokens_used": 0}
    assert stats["kv_token_capacity"] == 63 * PS * C
    cache = stats["state_cache"]
    assert cache["kind"] == "eva_window" and cache["layers"] == 4
    assert cache["tokens_per_slot"] == W and cache["chunk"] == C
    assert cache["bytes_per_slot"] == 4 * 2 * 4 * W * 16 * 4
    assert cache["bytes"] == SLOTS * cache["bytes_per_slot"]
    # the slots' windows lie behind the allocator's 64 pages
    assert engine.k_pages.shape == (4, 4, 64 + SLOTS * W // PS, PS, 16)
    assert np.asarray(engine.state["eva_pages"]).tolist()[0] == list(
        range(64, 64 + W // PS))
    eva = engine.perf.totals()["eva"]
    assert eva["prompts"] == 3 and eva["prompt_rows"] == 4 * sum(lens)
    assert eva["prompt_chunk_rows_written"] == 4 * sum(
        -(-n // C) for n in lens)
    assert eva["prompt_windows_closed"] == sum(n // W for n in lens)
    # by hand: a step at position t reads t % W + 1 window rows and
    # (W / C) * (t // W) summary rows a layer; 39 steps a sequence (the
    # first token is the prompt pass's), booked in chunks of 4, so the
    # last chunk's overshoot is counted as computed
    booked = eva["decode_steps"]
    assert booked >= steps - 1
    ts = [range(n, n + booked) for n in lens]
    assert eva["window_rows_read"] <= 4 * sum(
        t % W + 1 for r in ts for t in r)
    assert eva["chunk_rows_read"] <= 4 * sum(
        W // C * (t // W) for r in ts for t in r)
    assert eva["chunk_rows_read"] >= 4 * sum(
        W // C * (t // W) for n in lens for t in range(n, n + steps - 1))
    assert eva["windows_closed"] >= sum(
        (n + steps - 1) // W - n // W for n in lens)
    # the summary rows a decode step writes: W / C a layer of a window
    # it closes, and none at any other step
    assert eva["chunk_rows_written"] == W // C * 4 * eva["windows_closed"]
    ticks = [t for t in engine.flight.ticks() if "window_bytes" in t]
    assert ticks and max(t["window_bytes"] for t in ticks) <= cache["bytes"]


def test_windows_close_inside_a_chunk_beside_a_parked_slot(engine):
    """Three prompts in one wave.  The one of 23 has its five tokens
    after one chunk of 4 steps and is switched off behind the chunk
    that was in flight by then: its slot idles at position 31, = W - 1,
    beside the others for the rest of the run, its pages given back,
    and closes nothing (if it did, it would write the rows of pages
    that are no longer its own; that it writes NOTHING is held page by
    page in tests/test_evabyte.py).  The prompts of 58 and 90 (one
    prompt program, so one decode step for both) each fill their
    window's last row at their sixth step, the second of a chunk of 4:
    two closers on ONE step, mid-chunk, and the steps after read what
    it wrote.  The summary rows a decode step writes are the closes'
    alone."""
    before = dict(engine.perf.totals().get("eva", {}))
    rng = np.random.default_rng(8)
    prompts = [contract.tokens(rng, n) for n in (23, 58, 90)]
    seqs = [engine.submit_tokens(p, contract.lp_params(m))
            for p, m in zip(prompts, (5, 14, 14))]
    for s, p in zip(seqs, prompts):
        assert s.done_event.wait(timeout=600) and s.error is None, s.error
        contract.agree(FAMILY, engine, s, p)
    assert engine.allocator.num_used == 0
    grew = {k: v - before.get(k, 0)
            for k, v in engine.perf.totals()["eva"].items()}
    assert grew["windows_closed"] >= 2
    assert grew["chunk_rows_written"] == (
        W // C * 4 * grew["windows_closed"])
    state = engine._dec_state
    idle = np.asarray(state["positions"])[~np.asarray(state["active"])]
    assert W - 1 in idle.tolist()


def test_a_sequence_holds_a_page_for_every_page_of_summary_rows(engine):
    """Pages a sequence holds = ceil(ceil(len / chunk) / page rows), at
    admission and as it grows; all returned at the end."""
    rng = np.random.default_rng(3)
    prompt = contract.tokens(rng, 70)
    seq = engine.submit_tokens(prompt, contract.lp_params(30))
    seen = set()
    while not seq.done_event.wait(timeout=0.002):
        held, n = len(seq.pages), seq.total_len
        if held and seq.status.name == "RUNNING":
            seen.add((n, held))
    assert seq.error is None
    assert engine.allocator.num_used == 0
    # never fewer than the rows written need, never more than the
    # decode horizon (a chunk of 4 steps ahead) can ask for
    assert seen and all(
        pages_of(n - 1) <= held <= pages_of(n + 2 * 4)
        for n, held in seen), sorted(seen)[:5]
    assert max(h for _, h in seen) == pages_of(70 + 30 - 1)


def test_chunked_prefill_and_a_slot_reused_after_a_longer_tenant():
    """75 tokens go in as chunks of 32 + 32 + 11 (the third starts at
    the start of the third window; the window's pages carry the rows
    from one chunk to the next); the 9-token prompt then takes a window
    that still holds the first tenant's rows, and decodes past its
    close."""
    contract.chunked_prefill_and_slot_reuse(
        FAMILY, 32, (75, 9), (8, W), cfg=TINY)


def test_chunks_that_start_mid_window():
    """Chunks of 16: every second one starts in the middle of a
    window."""
    contract.chunked_prefill_and_slot_reuse(
        FAMILY, 16, (75, 9), (8, 6), cfg=TINY)


def test_preemption_by_recompute_rebuilds_the_window():
    """Three allocatable pages of 16 tokens: the prompts of 17 and 18
    hold two each, so they run one after the other; the prompt of 16
    holds one, asks for its second at its first decode step beside a
    tenant of two, and is preempted and recomputed."""
    contract.preemption_by_recompute(
        FAMILY, {"kv_num_pages": 4, "prefill_buckets": [32]}, TINY)


def test_journal_replay_gives_the_same_logits(engine):
    contract.journal_replay(FAMILY, engine)


@pytest.mark.parametrize("sections, devices, named", contract.REFUSALS)
def test_engine_construction_refuses_by_name(sections, devices, named):
    contract.construction_refuses(
        dataclasses.replace(FAMILY), sections, devices, named)


@pytest.mark.parametrize("page", [3, 16])
def test_a_page_that_cuts_no_window_into_whole_pages_is_refused(page):
    import jax

    from vgate_tpu.runtime.engine_core import EngineCore

    cfg = FAMILY.config({"kv_page_size": page})
    with pytest.raises(ValueError, match="kv_page_size"):
        EngineCore(cfg, devices=jax.devices()[:1])


def test_a_checkpoint_of_the_family_is_refused_by_name():
    """No checkpoint or index file is here to hold tensor names against:
    the family serves on the random draw alone."""
    from vgate_tpu.runtime.weights import params_from_getter

    with pytest.raises(NotImplementedError, match="EVA"):
        params_from_getter(specs.TINY_EVA, lambda name: None)


def test_the_presets_are_registered():
    assert specs.spec_for_model_id("EvaByte/EvaByte").eva_window == 2048
    assert specs.spec_for_model_id("tiny-eva").cache_row_tokens == C
