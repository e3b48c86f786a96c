"""``tiny-nemotron-h`` (layers of ONE sub-block each: Mamba-2, attention
without rotary, a sigmoid-routed expert layer in a latent) through the
ENGINE -- prefill into pages and the per-slot recurrent state, chunked
prefill, then decode -- against the plain reference's full forward
(``perfbench/references/nemotron_h.py``) on the same seeded weights:
log-probabilities, not tokens."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.references import nemotron_h as ref
from tests import family_contract as contract
from vgate_tpu.models.specs import spec_for_model_id

FAMILY = contract.Family(
    "nemotron-3-super-120b-a12b-l11e128.json", ref=ref, draws_weights=True,
    tol={
        # float32 on both sides; only the order of sums differs (the
        # chunk-wise form against the token-by-token recurrence, the
        # grouped product against one expert at a time): measured 4.8e-7
        # at most
        "float32": 5e-5,
        # bf16 weights AND activations in the engine against float32
        # arithmetic on the same bf16 weights: five layers of bf16
        # rounding (2^-9 a product); measured 4.3e-3 at most and 8.5e-4
        # in the mean.  Five times that: a top-3 choice among 8 experts
        # that flips on another platform's rounding moves one token's
        # values together
        "bfloat16": 0.02},
    tpu={"kv_num_pages": 64, "kv_page_size": 4, "max_batch_slots": 4,
         "prefill_buckets": [16, 32, 64], "decode_chunk": 2},
    keeps="recurrent")
TINY = FAMILY.cfg


@pytest.mark.parametrize(
    "dtype, tol", [(d, FAMILY.tol[d]) for d in ("float32", "bfloat16")])
def test_unequal_rows_in_one_wave_match_the_reference(dtype, tol):
    with contract.booted(FAMILY, dtype=dtype) as core:
        # buckets 32, 16, 16
        contract.unequal_rows(FAMILY, core, (19, 3, 9), dtype=dtype)
        stats = core.get_stats()
        assert stats["state_cache"]["slots"] == 4
        assert stats["state_cache"]["kind"] == "mamba"
        assert stats["state_cache"]["linear_layers"] == 2
        z = ref.sizes(TINY)
        width = jnp.dtype(dtype).itemsize
        # one attention layer of five holds pages
        assert stats["kv_page_bytes"] == 4 * z["KV"] * z["hd"] * 2 * width
        assert stats["state_cache"]["bytes_per_slot"] == 2 * (
            z["Hm"] * z["P"] * z["N"] * 4 + 3 * z["C"] * width)
        moe = core.perf.totals()["moe"]
        assert moe["held_assignments"] == moe["assignments"] > 0
        assert moe["layer_steps"] == 2 * moe["steps"]
        assert moe["overflow"] == 0  # every expert held: one trip
        state = core.perf.totals()["state"]
        assert state["layer_steps"] == 2 * moe["steps"]


def test_chunked_prefill_and_a_slot_reused_after_a_longer_tenant():
    """Two periods (10 layers, ``EMEM*EMEM*``).  41 tokens go in as
    chunks of 16 + 16 + 9 (the Mamba-2 chunk is 16 too, so the last
    chunk is no multiple of it): the state and the convolution tail are
    carried from chunk to chunk through the slot's row."""
    spec = dataclasses.replace(
        spec_for_model_id("tiny-nemotron-h"), name="tiny-nemotron-h-2p",
        num_layers=10, layer_pattern="EMEM*EMEM*")
    assert spec.num_periods == 2 and spec.linear_layers == 4
    cfg = dict(TINY, num_hidden_layers=10,
               hybrid_override_pattern="EMEM*EMEM*")
    contract.chunked_prefill_and_slot_reuse(
        FAMILY, 16, (41, 6), spec=spec, cfg=cfg)


def test_a_pattern_that_does_not_repeat_is_one_period_of_scanned_pairs():
    """The published pattern's shape at toy size: ``MEM*EMEME`` has no
    period, so it is one, and its repeated pairs are inner scans."""
    from vgate_tpu.models.hybrid import _segments

    pattern = "MEM*EMEME"
    spec = dataclasses.replace(
        spec_for_model_id("tiny-nemotron-h"), name="tiny-nemotron-h-odd",
        num_layers=len(pattern), layer_pattern=pattern)
    assert spec.num_periods == 1 and spec.layers_per_period == 9
    assert (spec.linear_layers, spec.attn_layers, spec.moe_layers) == (4, 1, 4)
    runs = [(len(unit), reps) for unit, reps in
            _segments(spec.period_blocks)]
    assert runs == [(1, 1), (1, 1), (1, 1), (1, 1), (2, 2), (1, 1)]
    cfg = dict(TINY, num_hidden_layers=len(pattern),
               hybrid_override_pattern=pattern)
    with contract.booted(FAMILY, spec=spec) as core:
        prompt = contract.tokens(np.random.default_rng(7), 21)
        (seq,) = contract.run(core, [prompt])
        contract.agree(FAMILY, core, seq, prompt, cfg)


def test_published_preset_counts_its_name():
    spec = spec_for_model_id("nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16")
    assert (spec.linear_layers, spec.moe_layers, spec.attn_layers) == (
        40, 40, 8)
    assert round(spec.num_params / 1e9, 2) == 120.67
    cut = dataclasses.replace(
        spec, num_layers=11, layer_pattern=spec.layer_pattern[26:37],
        num_experts=128, vocab_size=32768)
    assert cut.layer_pattern == "EMEMEMEMEM*"
    assert round(cut.num_params / 1e9, 3) == 4.648
    from vgate_tpu.models.hybrid import state_bytes_per_slot
    assert state_bytes_per_slot(cut, 2) == 5 * (
        128 * 64 * 128 * 4 + 3 * 10240 * 2)
