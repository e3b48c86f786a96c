"""``tiny-hybrid`` (three Gated DeltaNet layers to one gated attention
layer, a dropless expert layer with a shared expert) through the ENGINE
-- prefill into pages and the per-slot recurrent state, then decode --
against the plain reference's full forward
(``perfbench/references/qwen3_next.py``) on the same seeded weights:
log-probabilities, not tokens, in float32."""

import dataclasses

import numpy as np
import pytest

from perfbench.references import qwen3_next as ref
from tests import family_contract as contract
from tests import prompt_row_blocks as row_blocks
from vgate_tpu.models.specs import spec_for_model_id

FAMILY = contract.Family(
    "qwen3-next-80b-a3b-l8e128.json", ref=ref, draws_weights=True,
    # float32 on both sides; only the order of sums differs
    tol={"float32": 2e-5},
    tpu={"kv_num_pages": 64, "kv_page_size": 4, "max_batch_slots": 4,
         "prefill_buckets": [16, 32, 64],
         # two chunk lengths to compile, not four
         "decode_chunk": 2},
    keeps="recurrent")
TINY = FAMILY.cfg


@pytest.fixture(scope="module")
def engine():
    with contract.booted(FAMILY) as core:
        yield core


def test_unequal_rows_in_one_wave_and_a_prompt_shorter_than_its_bucket(
        engine):
    contract.unequal_rows(FAMILY, engine, (19, 3, 9))  # buckets 32, 16, 16
    stats = engine.get_stats()
    assert stats["state_cache"]["slots"] == 4
    assert stats["kv_page_bytes"] == 4 * ref.sizes(TINY)["KV"] * 16 * 2 * 4
    moe = engine.perf.totals()["moe"]
    assert moe["held_assignments"] == moe["assignments"] > 0
    assert moe["overflow"] == 0  # every expert held: one trip


def test_common_prefix_matches_reference_without_prefix_hits(engine):
    rng = np.random.default_rng(2)
    shared = contract.tokens(rng, 64)  # sixteen whole pages in common
    prompts = [shared + contract.tokens(rng, 5)]
    (first,) = contract.run(engine, prompts)
    prompts.append(shared + contract.tokens(rng, 7))
    (second,) = contract.run(engine, prompts[1:])
    contract.agree(FAMILY, engine, first, prompts[0])
    contract.agree(FAMILY, engine, second, prompts[1])
    assert engine.prefix_cache_enabled is False
    assert engine.allocator.prefix_hits == 0


def test_chunked_prefill_and_a_slot_reused_after_a_longer_tenant():
    """Two periods (8 layers).  41 tokens go in as chunks of 16 + 16 + 9
    (a border that is no multiple of 64): the state is carried from
    chunk to chunk through the slot's row."""
    spec = dataclasses.replace(
        spec_for_model_id("tiny-hybrid"), name="tiny-hybrid-2p",
        num_layers=8)
    contract.chunked_prefill_and_slot_reuse(
        FAMILY, 16, (41, 6), spec=spec, cfg=dict(TINY, num_hidden_layers=8))


def test_preemption_by_recompute_rebuilds_the_state():
    contract.preemption_by_recompute(
        FAMILY, {"kv_num_pages": 15, "prefill_buckets": [8, 16, 32]})


def test_journal_replay_gives_the_same_logits(engine):
    contract.journal_replay(FAMILY, engine)


@pytest.mark.parametrize("sections, devices, named", [
    r for r in contract.REFUSALS if "model" not in r[0]])
def test_engine_construction_refuses_by_name(sections, devices, named):
    contract.construction_refuses(FAMILY, sections, devices, named)


@pytest.mark.parametrize("model_id", [
    "tiny-hybrid", "tiny-nemotron-h", "tiny-mla-moe", "tiny-swa-moe",
    "tiny-dsa-moe"])
def test_a_decode_step_and_a_short_wave_hold_no_loop_over_row_blocks(
        model_id):
    """The loop over a long prompt's row blocks engages by the traced
    shape alone: a decode step and a wave of 1,024-row prompts are, to
    the letter, the jaxprs they are with the loop off; a 2,048-row
    prompt program holds more ``while``."""
    row_blocks.check_small_programs_hold_no_loop(model_id)
