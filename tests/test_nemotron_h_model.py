"""``tiny-nemotron-h`` (layers of ONE sub-block each: Mamba-2, attention
without rotary, a sigmoid-routed expert layer in a latent) through the
ENGINE -- prefill into pages and the per-slot recurrent state, chunked
prefill, then decode -- against the plain reference's full forward
(``perfbench/references/nemotron_h.py``) on the same seeded weights:
log-probabilities, not tokens."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import manifest
from perfbench.references import nemotron_h as ref
from vgate_tpu.backends.base import SamplingParams
from vgate_tpu.config import load_config
from vgate_tpu.models.specs import spec_for_model_id
from vgate_tpu.runtime.engine_core import EngineCore

# the tiny-nemotron-h preset under the published config's keys: what the
# configuration's rehearsal serves
TINY = manifest.load_json(
    manifest.HERE, "configs", "nemotron-3-super-120b-a12b-l11e128.json"
)["rehearse"]["model"]
# float32 on both sides; only the order of sums differs (the chunk-wise
# form against the token-by-token recurrence, the grouped product
# against one expert at a time): measured 4.8e-7 at most
TOL_F32 = 5e-5
# bf16 weights AND activations in the engine against float32 arithmetic
# on the same bf16 weights: five layers of bf16 rounding (2^-9 a
# product); measured 4.3e-3 at most and 8.5e-4 in the mean.  Five times
# that: a top-3 choice among 8 experts that flips on another platform's
# rounding moves one token's values together
TOL_BF16 = 0.02


def engine_config(dtype="float32", tpu=None):
    base = {
        "dp": 1, "tp": 1, "ep": 1, "sp": 1, "kv_num_pages": 64,
        "kv_page_size": 4, "max_batch_slots": 4,
        "prefill_buckets": [16, 32, 64], "use_pallas": False,
        "decode_chunk": 2,
    }
    base.update(tpu or {})
    return load_config(
        model={"model_id": "tiny-nemotron-h", "engine_type": "jax_tpu",
               "dtype": dtype, "max_model_len": 128},
        tpu=base, scheduler={"max_queue_size": 16},
        logging={"level": "WARNING"},
    )


def lp_params(max_tokens):
    return SamplingParams(max_tokens=max_tokens, temperature=0.0,
                          logprobs=True, top_logprobs=5)


def tokens(rng, n):
    return [int(t) for t in rng.integers(3, 259, size=n)]


def differences(core, cfg, weights, seq, prompt):
    """|served - reference| over the top log-probabilities of every
    generated token, the reference's full forward on prompt +
    generated."""
    full = list(prompt) + list(seq.generated_ids)
    want = ref.logprobs(cfg, weights, [full], [len(prompt)])[0]
    entries = core.logprob_entries(seq)
    assert len(entries) == len(seq.generated_ids)
    return [
        abs(t["logprob"] - want[pos, t["token_id"]])
        for pos, e in enumerate(entries) for t in e["top_logprobs"]
    ]


def run(core, prompts, max_tokens=6):
    seqs = [core.submit_tokens(p, lp_params(max_tokens)) for p in prompts]
    for s in seqs:
        assert s.done_event.wait(timeout=600)
        assert s.error is None, s.error
    return seqs


@pytest.mark.parametrize(
    "dtype, tol", [("float32", TOL_F32), ("bfloat16", TOL_BF16)])
def test_unequal_rows_in_one_wave_match_the_reference(dtype, tol):
    core = EngineCore(engine_config(dtype), devices=jax.devices()[:1])
    core.start()
    try:
        weights = ref.draw_weights(TINY, 0, jnp.dtype(dtype))
        rng = np.random.default_rng(1)
        prompts = [tokens(rng, n) for n in (19, 3, 9)]  # buckets 32, 16, 16
        diffs = []
        for p, s in zip(prompts, run(core, prompts)):
            diffs += differences(core, TINY, weights, s, p)
        assert diffs and max(diffs) < tol, (max(diffs), np.mean(diffs))
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
    finally:
        core.stop()


def test_chunked_prefill_and_a_slot_reused_after_a_longer_tenant():
    """Two periods (10 layers, ``EMEM*EMEM*``), ONE slot.  A 41-token
    prompt goes in as chunks of 16 + 16 + 9 (the Mamba-2 chunk is 16
    too, so the last chunk is no multiple of it): the state and the
    convolution tail are carried from chunk to chunk through the slot's
    row.  Then a 6-token prompt takes the same slot: the longer tenant
    must have left nothing behind."""
    spec = dataclasses.replace(
        spec_for_model_id("tiny-nemotron-h"), name="tiny-nemotron-h-2p",
        num_layers=10, layer_pattern="EMEM*EMEM*")
    assert spec.num_periods == 2 and spec.linear_layers == 4
    cfg = dict(TINY, num_hidden_layers=10,
               hybrid_override_pattern="EMEM*EMEM*")
    weights = ref.draw_weights(cfg, 0, jnp.float32)
    core = EngineCore(
        engine_config(tpu={"prefill_chunk": 16, "prefill_buckets": [8, 16],
                           "max_batch_slots": 1}),
        spec=spec, devices=jax.devices()[:1])
    core.start()
    try:
        rng = np.random.default_rng(4)
        long_prompt, short_prompt = tokens(rng, 41), tokens(rng, 6)
        (a,) = run(core, [long_prompt], max_tokens=8)
        (b,) = run(core, [short_prompt])
        for seq, prompt in ((a, long_prompt), (b, short_prompt)):
            diffs = differences(core, cfg, weights, seq, prompt)
            assert max(diffs) < TOL_F32, max(diffs)
    finally:
        core.stop()


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
    weights = ref.draw_weights(cfg, 0, jnp.float32)
    core = EngineCore(engine_config(), spec=spec, devices=jax.devices()[:1])
    core.start()
    try:
        prompt = tokens(np.random.default_rng(7), 21)
        (seq,) = run(core, [prompt])
        diffs = differences(core, cfg, weights, seq, prompt)
        assert max(diffs) < TOL_F32, max(diffs)
    finally:
        core.stop()


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
