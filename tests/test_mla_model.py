"""``tiny-mla-moe`` (every layer latent attention over a latent paged
cache and a softmax-routed expert layer with an ungated shared expert;
YaRN over an original maximum of 32) through the ENGINE -- the
whole-prompt pass (non-absorbed) into the latent pool, then absorbed
decode steps; chunked prefill; a prefix hit that prefills only the
suffix; a slot reused -- against the plain reference's full forward in
the NON-absorbed form (``perfbench/references/mistral4.py``) on the same
seeded weights: log-probabilities, not tokens."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import manifest
from perfbench.references import mistral4 as ref
from tests import family_contract as contract
from tests import prompt_row_blocks as row_blocks
from tests.family_contract import tokens
from vgate_tpu.models.specs import spec_for_model_id

FAMILY = contract.Family(
    "mistral-small-4-119b-l4e32.json", ref=ref, max_model_len=256,
    tol={
        # float32 on both sides; only the order of sums and the form
        # differ (the absorbed product against the expanded one,
        # blockwise softmax against one masked softmax, the grouped
        # product against one expert at a time): measured 9.5e-7 at most
        "float32": 5e-5,
        # bf16 weights AND activations in the engine against float32
        # arithmetic on the same bf16 weights: three layers of bf16
        # rounding (2^-9 a product) and a bf16 latent row in the pool;
        # measured 2.1e-3 at most and 7.8e-4 in the mean.  Five times
        # that: a top-2 choice among 8 experts that flips on another
        # platform's rounding moves one token's values together
        "bfloat16": 0.01},
    tpu={"kv_num_pages": 160, "kv_page_size": 4, "max_batch_slots": 4,
         "prefill_buckets": [16, 32, 64, 128], "decode_chunk": 2})
TINY, TOL_F32 = FAMILY.cfg, FAMILY.tol["float32"]


@pytest.mark.parametrize(
    "dtype, tol", [(d, FAMILY.tol[d]) for d in ("float32", "bfloat16")])
def test_prompt_pass_then_absorbed_decode_match_the_reference(dtype, tol):
    """Three prompts of unequal length, two of them past the original
    maximum of 32 (the queries' position scaling leaves 1, YaRN's
    interpolated frequencies turn), each a whole-prompt pass
    (non-absorbed) and six absorbed decode steps through the latent
    pool."""
    with contract.booted(FAMILY, dtype=dtype) as core:
        contract.unequal_rows(FAMILY, core, (19, 70, 101), dtype=dtype)
        stats = core.get_stats()
        assert "state_cache" not in stats
        width = jnp.dtype(dtype).itemsize
        # ONE pool: three layers x 4 tokens x the latent row (24 values
        # in 128 lanes), no V
        assert stats["kv_page_bytes"] == 3 * 4 * 128 * width
        assert stats["kv_layout"] == {
            "pools": 1, "heads": 1, "row_lanes": 128, "latent": 24}
        assert core.v_pages is None and core.state is None
        assert core.k_pages.shape == (3, 1, 160, 4, 128)
        totals = core.perf.totals()
        moe = totals["moe"]
        assert moe["held_assignments"] == moe["assignments"] > 0
        assert moe["layer_steps"] == 3 * moe["steps"]
        assert moe["overflow"] == 0  # every expert held: one trip
        mla = totals["mla"]
        assert mla["prefill_prompts"] == 3
        assert mla["prefill_pairs"] == 3 * sum(
            n * (n + 1) // 2 for n in (19, 70, 101))
        assert mla["decode_steps"] == moe["steps"]
        # every decode step reads at least the prompts and writes a row
        assert mla["decode_token_reads"] >= 3 * mla["decode_steps"] * 19
        assert mla["latent_rows_written"] >= 3 * (19 + 70 + 101)


def test_chunked_prefill_and_a_slot_reused_after_a_longer_tenant():
    """75 tokens go in as chunks of 32 + 32 + 11: each later chunk
    attends to the latent rows the earlier ones left in the pool
    (expanded again, never cached), across the original maximum."""
    contract.chunked_prefill_and_slot_reuse(
        FAMILY, 32, (75, 9), tpu={"prefix_cache": {"enabled": False}})


def test_a_prefix_hit_prefills_only_the_suffix_against_latent_pages():
    written = contract.prefix_hit_on_whole_pages(
        FAMILY, lambda core: core.perf.totals()["mla"]["latent_rows_written"])
    # only the suffix's rows were written: 85 - 64 a layer, and its six
    # decode steps' rows
    assert written < 3 * (21 + 2 * 6 + 4), written


def test_attention_is_not_flat_at_the_draws():
    """The draw of ``q_b`` and ``kv_b`` at 0.05: a 101-token context's
    attention probabilities are far from uniform, so that a wrong rotary
    or a stale latent row cannot hide under the tolerance.  Zeroing the
    rotary part of the latent moves the logits by orders more than
    ``TOL_F32``."""
    cfg, rng = TINY, np.random.default_rng(3)
    seq = tokens(rng, 101)
    base = ref.logprobs(cfg, 0, jnp.float32, [seq], [100])[0]
    layers = [ref.draw_layer(cfg, 0, i, jnp.float32)
              for i in range(cfg["num_hidden_layers"])]
    kl = cfg["kv_lora_rank"]
    for lw in layers:  # no rotary key: k_r = 0
        lw["kv_a"] = lw["kv_a"].at[:, kl:].set(0.0)
    ends = ref.draw_ends(cfg, 0, jnp.float32)
    other = ref.logprobs(cfg, 0, jnp.float32, [seq], [100],
                         weights={**ends, "layers": layers})[0]
    assert np.abs(base - other).max() > 100 * TOL_F32


def test_published_preset_counts_its_name():
    spec = spec_for_model_id("mistralai/Mistral-Small-4-119B-2603")
    assert (spec.linear_layers, spec.moe_layers, spec.attn_layers) == (
        0, 36, 36)
    assert spec.is_hybrid and spec.is_mla and spec.recurrent_kind == ""
    assert round(spec.num_params / 1e9, 2) == 118.97
    assert (spec.latent_dim, spec.cache_head_dim, spec.kv_pools) == (
        320, 384, 1)
    assert abs(spec.mla_softmax_scale - 128 ** -0.5 * 1.48520 ** 2) < 1e-5
    cut = dataclasses.replace(
        spec, num_layers=4, num_experts=32, vocab_size=32768)
    assert round(cut.num_params / 1e9, 3) == 3.705
    # serve.py holds the program to the published group as a whole
    published = manifest.load_json(
        manifest.HERE, "configs", "mistral-small-4-119b-l4e32.json")
    assert spec.rope_parameters == published["rope_parameters"]


@pytest.mark.parametrize("fill", list(row_blocks.FILLS))
def test_a_long_prompt_pass_works_on_its_own_row_blocks(fill):
    """A bucket of four blocks of rows (the block patched to 8): the
    queries, the latent rows, their expansion and the output projection
    in a counted loop over the blocks the longer prompt reaches, against
    the pass over the whole bucket."""
    row_blocks.check_prompt_pass("tiny-mla-moe", row_blocks.FILLS[fill])


def test_greedy_tokens_are_the_same_with_the_row_loop(monkeypatch):
    row_blocks.check_greedy_identity(monkeypatch, "tiny-mla-moe")
