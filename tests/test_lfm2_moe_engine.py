"""``tiny-lfm2-moe`` through the ENGINE against the plain reference
(``perfbench/references/lfm2_moe.py``): unequal rows in one wave and
what ``/stats`` and ``/debug/perf`` report of the tails, chunked
prefill, a slot reused, preemption by recompute, journal replay, heads
of 64 two to a pool row; and what knows pages only, refused by name at
engine construction."""

import dataclasses

import numpy as np
import pytest

from perfbench.references import lfm2_moe as ref
from tests import family_contract as contract
from vgate_tpu.models import specs

PS, SLOTS = 4, 4
FAMILY = contract.Family(
    "lfm2-24b-a2b-e8.json", ref=ref,
    tol={"float32": 1e-5},  # float32 on both sides: tests/test_lfm2_moe.py
    tpu={"kv_num_pages": 96, "kv_page_size": PS, "max_batch_slots": SLOTS,
         "prefill_buckets": [16, 64], "decode_chunk": 1},
    keeps="a convolution tail a slot")

# the leading layers and ONE period (conv conv attn conv conv attn conv
# conv, the first two dense): what the tests that boot an engine of
# their own compile
SHORT = specs._register(dataclasses.replace(
    specs.spec_for_model_id(FAMILY.model_id), name="tiny-lfm2-moe-short",
    num_layers=8, conv_pattern=specs.TINY_LFM2_MOE.conv_pattern[:8]))
TINY_SHORT = dict(FAMILY.cfg, num_hidden_layers=8,
                  layer_types=FAMILY.cfg["layer_types"][:8])


@pytest.fixture(scope="module")
def engine():
    with contract.booted(FAMILY) as core:
        yield core


def test_unequal_rows_through_the_engine_and_what_it_reports(engine):
    """Three prompts in one wave (shorter than the tail, a page and a
    bit, several pages), each a whole-prompt pass and decode steps;
    /stats and /debug/perf say what a slot keeps: a tail, no tile."""
    contract.unequal_rows(FAMILY, engine, (2, 19, 45), max_tokens=8)
    assert not engine.prefix_cache_enabled
    stats = engine.get_stats()
    # pages over the THREE attention layers only: (K, V) x 3 x 2 x 16 x 4 B
    assert stats["kv_page_bytes"] == 2 * 3 * PS * 2 * 16 * 4
    assert "heads_per_row" not in stats["kv_layout"]  # heads of 16
    cache = stats["state_cache"]
    assert cache["kind"] == "conv" and cache["conv_layers"] == 9
    assert cache["rows_per_slot"] == 2
    assert cache["bytes_per_slot"] == 9 * 2 * 64 * 4
    assert cache["bytes"] == SLOTS * cache["bytes_per_slot"]
    assert set(engine.state) == {"conv"}
    assert engine.state["conv"].shape == (9, SLOTS, 2, 64)
    totals = engine.perf.totals()
    conv = totals["conv"]
    assert conv["layers"] == 9 and conv["taps"] == 3
    assert conv["tail_bytes_per_slot"] == cache["bytes_per_slot"]
    # every decode step moved the tail of each running row in each layer
    assert conv["layer_steps"] == 9 * totals["moe"]["steps"] > 0
    assert 0 < conv["tails_moved"] <= 3 * conv["layer_steps"]
    assert totals["moe"]["held_assignments"] == totals["moe"]["assignments"]


def test_chunked_prefill_and_a_slot_reused_after_a_longer_tenant():
    """75 tokens go in as chunks of 32 + 32 + 11, the tail carried from
    chunk to chunk; the 2-token prompt (shorter than the tail) then
    takes the slot whose tail still holds the first tenant's rows."""
    contract.chunked_prefill_and_slot_reuse(
        FAMILY, 32, (75, 2), (8, 6), model_id=SHORT.name, cfg=TINY_SHORT)


def test_preemption_by_recompute_rebuilds_the_tails():
    contract.preemption_by_recompute(
        FAMILY, {"kv_num_pages": 15, "prefill_buckets": [32]}, TINY_SHORT,
        model_id=SHORT.name)


def test_journal_replay_gives_the_same_logits(engine):
    contract.journal_replay(FAMILY, engine)


def test_heads_of_64_are_served_two_to_a_pool_row():
    """The same stack at 4 heads on 2 KV heads of 64: the engine packs
    the pool's rows (ONE row of 128 lanes a token a layer), /stats says
    so, and the served log-probabilities are the reference's through
    the whole-prompt pass, a chunked prefill and decode steps."""
    wide = specs._register(dataclasses.replace(
        SHORT, name="tiny-lfm2-moe-hd64", head_dim=64))
    cfg = dict(TINY_SHORT, head_dim=64)
    tpu = {"prefill_chunk": 32, "prefill_buckets": [16, 32]}
    with contract.booted(FAMILY, tpu, model_id=wide.name) as core:
        assert core.spec.kv_head_pack == 2
        assert core.k_pages.shape == (2, 1, 96, PS, 128)
        stats = core.get_stats()
        assert stats["kv_layout"]["heads_per_row"] == 2
        assert stats["kv_layout"]["row_lanes"] == 128
        # no padding lane: (K, V) x 2 layers x 2 heads x 64 x 4 B a token
        assert stats["kv_page_bytes"] == 2 * 2 * PS * 2 * 64 * 4
        rng = np.random.default_rng(2)
        prompts = [contract.tokens(rng, n) for n in (7, 41)]
        for p, s in zip(prompts, contract.run(core, prompts, max_tokens=6)):
            contract.agree(FAMILY, core, s, p, cfg)


@pytest.mark.parametrize("sections, devices, named", contract.REFUSALS)
def test_engine_construction_refuses_by_name(sections, devices, named):
    contract.construction_refuses(FAMILY, sections, devices, named)
