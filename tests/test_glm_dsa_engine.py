"""``tiny-dsa-moe`` through the ENGINE against the plain reference
(``perfbench/references/glm_moe_dsa.py``): unequal rows in one wave and
what ``/stats`` and ``/debug/perf`` report of the two rows a page holds
and of the selection; chunked prefill; a prefix hit on whole pages that
brings the index keys with it; pages freed free both rows; preemption by
recompute; journal replay; and what knows K and V pages only, refused by
name at engine construction."""

import dataclasses

import pytest

from perfbench.references import glm_moe_dsa as ref
from tests import family_contract as contract
from vgate_tpu.models import specs

PS, SLOTS, TOPK = 8, 4, 16
FAMILY = contract.Family(
    "glm-5.2-l5e16.json", ref=ref,
    tol={"float32": 1e-4},  # float32 on both sides: tests/test_glm_dsa.py
    tpu={"kv_num_pages": 96, "kv_page_size": PS, "max_batch_slots": SLOTS,
         "prefill_buckets": [16, 64], "decode_chunk": 1},
    keeps="index keys under one page table")

# one period behind the leading layer (F S S S F, the cell's own stack):
# what the engine tests that boot an engine of their own compile
SHORT = specs._register(dataclasses.replace(
    specs.spec_for_model_id(FAMILY.model_id), name="tiny-dsa-moe-l5",
    num_layers=5, indexer_pattern="FSSSF"))
TINY_SHORT = dict(
    FAMILY.cfg, num_hidden_layers=5,
    indexer_types=["full", "shared", "shared", "shared", "full"],
    mlp_layer_types=["dense"] + ["sparse"] * 4)


@pytest.fixture(scope="module")
def engine():
    with contract.booted(FAMILY) as core:
        yield core


def test_unequal_rows_through_the_engine_and_what_it_reports(engine):
    """Three prompts in one wave (under the pick, just past it, three
    times it), each a whole-prompt pass and decode steps; /stats and
    /debug/perf say what a page holds and what the selection read."""
    lens, steps = (7, 19, 45), 12
    contract.unequal_rows(FAMILY, engine, lens, max_tokens=steps)
    stats = engine.get_stats()
    # nine latent rows of 128 lanes and three index keys of 16, float32
    assert stats["kv_page_bytes"] == PS * (9 * 128 + 3 * 16) * 4
    assert stats["kv_layout"] == {
        "pools": 1, "heads": 1, "row_lanes": 128, "latent": 40,
        "row_pairs": True, "index": {"layers": 3, "row_bytes": 64}}
    assert stats["kv_write"] == "scatter"
    # the latent rows by pairs of tokens, the index keys by rows
    assert engine.k_pages.shape == (9, 1, 96, PS // 2, 2, 128)
    assert engine.v_pages.shape == (3, 1, 96, PS, 16)
    dsa = engine.perf.totals()["dsa"]
    assert dsa["prefill_prompts"] == 3
    assert dsa["prefill_pairs_scored"] == 3 * sum(
        n * (n + 1) // 2 for n in lens)
    # a decode step k of a row of length n + k: the first of the 12
    # tokens came with the prompt pass, 11 steps follow
    ctx = sum(n + 1 + k for n in lens for k in range(steps - 1))
    attended = sum(min(n + 1 + k, TOPK)
                   for n in lens for k in range(steps - 1))
    assert dsa["decode_steps"] == steps - 1
    assert dsa["rows_scored"] == 3 * ctx and dsa["rows_in_context"] == 9 * ctx
    assert dsa["rows_attended"] == 9 * attended < dsa["rows_in_context"]
    # the jnp twin gathers the picked rows alone; the kernel moves pairs
    assert dsa["rows_fetched"] == dsa["rows_attended"]
    assert dsa["index_layer_steps"] == 3 * (steps - 1)
    assert dsa["selections_reused"] == 6 * (steps - 1)
    assert dsa["index_rows_written"] == 3 * (sum(lens) + 3 * (steps - 1))
    ticks = [t for t in engine.flight.ticks() if "index_bytes" in t]
    assert ticks and max(t["index_bytes"] for t in ticks) > 0
    # everything is given back: both rows of a page go with its id
    assert engine.allocator.num_used == 0 or engine.prefix_cache_enabled


def test_chunked_prefill_and_a_slot_reused_after_a_longer_tenant():
    """75 tokens go in as chunks of 32 + 32 + 11: each later chunk is
    scored against the index keys the earlier ones left in the pool.
    The 9-token prompt then takes pages whose other rows still hold the
    first tenant's."""
    contract.chunked_prefill_and_slot_reuse(  # freed pages free both rows
        FAMILY, 32, (75, 9), (8, TOPK), {"prefix_cache": {"enabled": False}},
        TINY_SHORT, model_id=SHORT.name)


def test_a_prefix_hit_on_whole_pages_brings_the_index_keys_with_it():
    """The suffix alone writes index keys, and is scored against the
    FIRST prompt's index keys on the shared pages."""
    written = contract.prefix_hit_on_whole_pages(
        FAMILY, lambda core: core.perf.totals()["dsa"]["index_rows_written"],
        TINY_SHORT, model_id=SHORT.name)
    # only the suffix's keys were written: 21 and five decode steps' in
    # each of the two picking layers
    assert written == 2 * (21 + 5)


def test_preemption_by_recompute_rebuilds_both_rows():
    contract.preemption_by_recompute(
        FAMILY, {"kv_num_pages": 9, "prefill_buckets": [32],
                 "prefix_cache": {"enabled": False}},
        TINY_SHORT, model_id=SHORT.name)


def test_journal_replay_gives_the_same_logits(engine):
    contract.journal_replay(FAMILY, engine, prompt_len=21)


@pytest.mark.parametrize("sections, devices, named", contract.REFUSALS)
def test_engine_construction_refuses_by_name(sections, devices, named):
    contract.construction_refuses(FAMILY, sections, devices, named)
