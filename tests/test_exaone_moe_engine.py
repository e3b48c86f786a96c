"""``tiny-swa-moe`` through the ENGINE against the plain reference
(``perfbench/references/exaone_moe.py``): unequal rows in one wave and
what ``/stats`` and ``/debug/perf`` report of the rings, chunked
prefill, a slot reused, preemption by recompute, journal replay; and
what knows pages only, refused by name at engine construction."""

import dataclasses

import numpy as np
import pytest

from perfbench.references import exaone_moe as ref
from tests import family_contract as contract
from vgate_tpu.models import specs

PS, SLOTS, RING = 4, 4, 12  # page, decode slots, a ring's tokens (3 pages)
FAMILY = contract.Family(
    "k-exaone-236b-a23b-l5e16.json", ref=ref,
    tol={"float32": 1e-4},  # float32 on both sides: tests/test_exaone_moe.py
    tpu={"kv_num_pages": 96, "kv_page_size": PS, "max_batch_slots": SLOTS,
         "prefill_buckets": [16, 64], "decode_chunk": 1},
    keeps="per-slot ring")

# one period behind the leading layer (L L L G L, the cell's own stack):
# what the engine tests that boot an engine of their own compile
SHORT = specs._register(dataclasses.replace(
    specs.spec_for_model_id(FAMILY.model_id), name="tiny-swa-moe-l5",
    num_layers=5))
TINY_SHORT = dict(FAMILY.cfg, num_hidden_layers=5)


@pytest.fixture(scope="module")
def engine():
    with contract.booted(FAMILY) as core:
        yield core


def test_unequal_rows_through_the_engine_and_what_it_reports(engine):
    """Three prompts in one wave (under a ring, past one, several), each
    a whole-prompt pass and decode steps past a ring's length; /stats and
    /debug/perf say what the cache is and what the rings moved."""
    contract.unequal_rows(FAMILY, engine, (7, 19, 45), max_tokens=RING + 2)
    assert not engine.prefix_cache_enabled
    stats = engine.get_stats()
    # pages over the TWO full layers only: 2 x (K, V) x 4 x 2 x 16 x 4 B
    assert stats["kv_page_bytes"] == 2 * 2 * PS * 2 * 16 * 4
    cache = stats["state_cache"]
    assert cache["kind"] == "ring" and cache["layers"] == 7
    assert cache["tokens_per_slot"] == RING and cache["window"] == 8
    assert cache["bytes_per_slot"] == 7 * 2 * 2 * RING * 16 * 4
    assert cache["bytes"] == SLOTS * cache["bytes_per_slot"]
    assert engine.state["ring_k"].shape == (7, 2, 1 + SLOTS * 3, PS, 16)
    swa = engine.perf.totals()["swa"]
    assert swa["prefill_prompts"] == 3 and swa["prefill_launches"] == 21
    assert swa["prefill_rows"] == 7 * (7 + 19 + 45)
    # rows kept: whole prompts of 7; the last 3 pages' real rows else
    kept = 7 + (19 - 8) + (45 - 36)
    assert swa["ring_rows_written_prefill"] == 7 * kept
    assert swa["prefill_rows_to_trash"] == 7 * (7 + 19 + 45 - kept)
    assert swa["decode_launches"] == 7 * swa["decode_steps"]
    assert 0 < swa["decode_row_reads"] <= (
        7 * 8 * 3 * swa["decode_steps"])
    ticks = [t for t in engine.flight.ticks() if "ring_bytes" in t]
    assert ticks and max(t["ring_bytes"] for t in ticks) <= cache["bytes"]


def test_chunked_prefill_and_a_slot_reused_after_a_longer_tenant():
    """75 tokens go in as chunks of 32 + 32 + 11; the 9-token prompt then
    takes a ring whose other pages still hold the first tenant's rows,
    and decodes past the ring's length."""
    contract.chunked_prefill_and_slot_reuse(
        FAMILY, 32, (75, 9), (8, RING + 2), model_id=SHORT.name,
        cfg=TINY_SHORT)


def test_held_pairs_past_the_capacity_are_served_and_counted(monkeypatch):
    """The dispatch takes three pairs at a time where a decode step of
    two rows has four and a 16-row prompt 32: every trip beyond the
    first is in ``/debug/perf -> totals.moe.overflow``, and the tokens'
    logprobs are the reference's all the same."""
    from vgate_tpu.ops import moe

    monkeypatch.setattr(moe, "capacity", lambda spec, pairs: 3)
    tpu = {"prefill_buckets": [16], "max_batch_slots": 2}
    with contract.booted(FAMILY, tpu, model_id=SHORT.name) as core:
        rng = np.random.default_rng(6)
        prompts = [contract.tokens(rng, 9), contract.tokens(rng, 14)]
        for p, s in zip(prompts, contract.run(core, prompts, max_tokens=5)):
            contract.agree(FAMILY, core, s, p, TINY_SHORT)
        booked = core.perf.totals()["moe"]
        # four expert layers a step, a trip more in each at the least
        assert booked["overflow"] >= booked["layer_steps"] > 0
        assert booked["held_assignments"] == booked["assignments"]


def test_preemption_by_recompute_rebuilds_the_rings():
    contract.preemption_by_recompute(
        FAMILY, {"kv_num_pages": 15, "prefill_buckets": [32]}, TINY_SHORT,
        model_id=SHORT.name)


def test_journal_replay_gives_the_same_logits(engine):
    contract.journal_replay(FAMILY, engine)


@pytest.mark.parametrize("sections, devices, named", contract.REFUSALS)
def test_engine_construction_refuses_by_name(sections, devices, named):
    contract.construction_refuses(FAMILY, sections, devices, named)
