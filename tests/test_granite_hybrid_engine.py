"""``tiny-granite-hybrid`` through the ENGINE against the plain reference
(``perfbench/references/granite_hybrid.py``): unequal rows in one wave
and what ``/stats`` reports of the state, the step kernel's block and
the multipliers; chunked prefill and a slot reused; preemption by
recompute; journal replay; greedy rows under ``logit_bias`` on the
fused head against the ``logits`` path; and what knows pages only,
refused by name at engine construction."""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.references import granite_hybrid as ref
from tests import family_contract as contract
from tests.test_greedy_head import (
    _chunk_inputs, _chunk_jaxpr, _fresh_chunk, kernel_on_cpu,  # noqa: F401
)
from vgate_tpu.models import decoder, specs
from vgate_tpu.models.specs import spec_for_model_id
from vgate_tpu.runtime import step_programs

PS, SLOTS = 4, 4
FAMILY = contract.Family(
    "granite-4.0-h-micro.json", ref=ref,
    tol={"float32": 1e-5},  # float32 on both sides: tests/test_granite_hybrid.py
    tpu={"kv_num_pages": 96, "kv_page_size": PS, "max_batch_slots": SLOTS,
         "prefill_buckets": [16, 64], "decode_chunk": 1},
    keeps="recurrent")


# a tied head's tile is [columns, hidden]: the pass takes whole 128-lane
# groups of it (ops/pallas/greedy_head.py worth_fusing), so the chunk
# test serves the preset at a hidden size of 128
WIDE = specs._register(dataclasses.replace(
    spec_for_model_id(FAMILY.model_id), name="tiny-granite-hybrid-d128",
    hidden_size=128))


@pytest.fixture(scope="module")
def engine():
    with contract.booted(FAMILY) as core:
        yield core


def test_unequal_rows_through_the_engine_and_what_it_reports(engine):
    """Three prompts in one wave (shorter than the convolution's tail, a
    chunk and a bit, several pages), each a whole-prompt pass and decode
    steps; /stats says what a slot keeps, at what block the step kernel
    would run, and the spec's four multipliers."""
    contract.unequal_rows(FAMILY, engine, (2, 19, 45), max_tokens=8)
    assert not engine.prefix_cache_enabled
    stats = engine.get_stats()
    # pages over the ONE attention layer only: (K, V) x 2 heads x 16 x 4 B
    assert stats["kv_page_bytes"] == 2 * 1 * PS * 2 * 16 * 4
    cache = stats["state_cache"]
    assert cache["kind"] == "mamba" and cache["linear_layers"] == 4
    # a float32 tile [4, 16, 16] and a float32 tail of 3 x (64 + 2 x 16)
    assert cache["bytes_per_slot"] == 4 * (4 * 16 * 16 * 4 + 3 * 96 * 4)
    assert cache["bytes"] == SLOTS * cache["bytes_per_slot"]
    assert cache["step_block_heads"] == 0  # the jax.numpy twin here
    assert engine.state["S"].shape == (4, SLOTS, 4, 16, 16)
    assert engine.state["conv"].shape == (4, SLOTS, 3, 96)
    assert stats["multipliers"] == {
        "embedding_multiplier": 6.0, "attention_multiplier": 0.5,
        "residual_multiplier": 0.5, "logits_scaling": 4.0}


def test_stats_name_the_block_under_the_kernel_and_no_multiplier_elsewhere():
    """What ``/stats`` would say on the chip, of an engine's facts
    alone: the published spec's step kernel takes the one group's 64
    heads a program; a spec without multipliers reports none."""
    from vgate_tpu.ops.pallas.ssd import block_heads
    from vgate_tpu.runtime.engine_core import EngineCore

    class OnTheChip:
        spec = spec_for_model_id("ibm-granite/granite-4.0-h-micro")
        use_pallas = True
        _state_dtype = jnp.bfloat16

    out = EngineCore._state_cache_kind(OnTheChip())
    assert out["step_block_heads"] == block_heads(64, 1) == 64
    assert out["linear_layers"] == 36 and out["kind"] == "mamba"
    assert OnTheChip.spec.multipliers == {
        "embedding_multiplier": 12.0, "attention_multiplier": 0.015625,
        "residual_multiplier": 0.22, "logits_scaling": 8.0}
    assert spec_for_model_id("tiny-dense").multipliers == {}


def test_chunked_prefill_and_a_slot_reused_after_a_longer_tenant():
    """75 tokens go in as chunks of 32 + 32 + 11, state and tail carried
    from chunk to chunk; the 2-token prompt (shorter than the tail) then
    takes the slot whose row still holds the first tenant's state."""
    contract.chunked_prefill_and_slot_reuse(FAMILY, 32, (75, 2), (8, 6))


def test_preemption_by_recompute_rebuilds_the_state():
    contract.preemption_by_recompute(
        FAMILY, {"kv_num_pages": 15, "prefill_buckets": [32]})


def test_journal_replay_gives_the_same_logits(engine):
    contract.journal_replay(FAMILY, engine)


def test_greedy_rows_take_the_fused_head_and_give_the_logits_paths_tokens(
        kernel_on_cpu):  # noqa: F811
    """A greedy chunk of this spec (the cell's traffic: every row
    greedy, sixteen ``logit_bias`` ids a row, live stop ids, the guard
    on) is served by the fused pass, ``logits_scaling`` inside it: the
    division comes before the +/-100 edits and before the guard's
    threshold is read, so tokens, flags, pools and state are those of
    ``_logits`` and the three passes, bit for bit."""
    args, kw, caches = _chunk_inputs(WIDE.name)
    assert args[1].logits_scaling == 4.0 and args[1].tie_embeddings
    assert "pallas_call" in _chunk_jaxpr(args, kw, caches)
    lowered = _fresh_chunk().lower(*args, **kw, **caches()).as_text(
        debug_info=True)
    assert re.search(r'["/]head/', lowered)  # the trace's name for it
    assert not re.search(r'["/]logits/', lowered)
    fused = _fresh_chunk()(*args, **kw, **caches())
    with pytest.MonkeyPatch.context() as present:
        present.setattr(
            step_programs, "decode_head_impl", lambda *a, **k: "logits")
        assert "pallas_call" not in _chunk_jaxpr(args, kw, caches)
        plain = _fresh_chunk()(*args, **kw, **caches())
    assert jax.tree.structure(fused) == jax.tree.structure(plain)
    for got, want in zip(jax.tree.leaves(fused), jax.tree.leaves(plain)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # under +/-100 any divisor names the same tokens; under edits of the
    # logits' own size the order of division and edit shows (a tied
    # head at random weights scores the step's own token 2.56 before
    # the division, 0.64 after it, the others within 0.23 / 4 of zero;
    # an edit of +1 wins after the division and loses before it): still
    # the ``logits`` path's tokens, no longer a spec's that lost the
    # divisor
    small = {**kw, "bias_vals": kw["bias_vals"] * 1e-2}
    fused = _fresh_chunk()(*args, **small, **caches())
    with pytest.MonkeyPatch.context() as present:
        present.setattr(
            step_programs, "decode_head_impl", lambda *a, **k: "logits")
        plain = _fresh_chunk()(*args, **small, **caches())
    np.testing.assert_array_equal(np.asarray(fused[0]), np.asarray(plain[0]))
    lost = dataclasses.replace(args[1], name="lost", logits_scaling=1.0)
    other = _fresh_chunk()(args[0], lost, *args[2:], **small, **caches())
    assert not np.array_equal(np.asarray(other[0]), np.asarray(fused[0]))


def test_the_rule_keeps_the_scaled_head_on_the_fused_pass():
    """``decode_head_impl`` at the published widths and the cell's 80
    rows: fused (32 MB of float32 logits a step stay on the chip); a
    soft cap would still fall back, a divisor does not."""
    spec = spec_for_model_id("ibm-granite/granite-4.0-h-micro")
    params = jax.eval_shape(functools.partial(
        decoder.init_params, spec, jax.random.PRNGKey(0), jnp.bfloat16))
    greedy = dict(rows=80, all_greedy=True, bias_width=16, stop_width=2)
    assert decoder.decode_head_impl(params, spec, True, **greedy) == "fused"
    capped = dataclasses.replace(spec, final_softcap=30.0)
    assert decoder.decode_head_impl(params, capped, True, **greedy) == "logits"


@pytest.mark.parametrize("sections, devices, named", contract.REFUSALS)
def test_engine_construction_refuses_by_name(sections, devices, named):
    contract.construction_refuses(FAMILY, sections, devices, named)
