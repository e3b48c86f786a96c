"""``tiny-hybrid`` (three Gated DeltaNet layers to one gated attention
layer, a dropless expert layer with a shared expert) through the ENGINE
-- prefill into pages and the per-slot recurrent state, then decode --
against the plain reference's full forward
(``perfbench/references/qwen3_next.py``) on the same seeded weights:
log-probabilities, not tokens, in float32."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import manifest
from perfbench.references import qwen3_next as ref
from tests import prompt_row_blocks as row_blocks
from vgate_tpu.backends.base import SamplingParams
from vgate_tpu.config import load_config
from vgate_tpu.models.specs import spec_for_model_id
from vgate_tpu.runtime.engine_core import EngineCore, replay_into
from vgate_tpu.runtime.sequence import Sequence

# the tiny-hybrid preset under the published config's keys: what the
# configuration's rehearsal serves
TINY = manifest.load_json(
    manifest.HERE, "configs", "qwen3-next-80b-a3b-l8e128.json"
)["rehearse"]["model"]
TOL = 2e-5  # float32 on both sides; only the order of sums differs


def hybrid_config(tpu=None, **sections):
    base = {
        "dp": 1, "tp": 1, "ep": 1, "sp": 1, "kv_num_pages": 64,
        "kv_page_size": 4, "max_batch_slots": 4,
        "prefill_buckets": [16, 32, 64], "use_pallas": False,
        # two chunk lengths to compile, not four
        "decode_chunk": 2,
    }
    base.update(tpu or {})
    return load_config(
        model={"model_id": "tiny-hybrid", "engine_type": "jax_tpu",
               "dtype": "float32", "max_model_len": 128},
        tpu=base, scheduler={"max_queue_size": 16},
        logging={"level": "WARNING"}, **sections,
    )


def lp_params(max_tokens):
    return SamplingParams(max_tokens=max_tokens, temperature=0.0,
                          logprobs=True, top_logprobs=5)


def tokens(rng, n):
    return [int(t) for t in rng.integers(3, 259, size=n)]


@pytest.fixture(scope="module")
def weights():
    return ref.draw_weights(TINY, 0, jnp.float32)


@pytest.fixture(scope="module")
def engine():
    core = EngineCore(hybrid_config(), devices=jax.devices()[:1])
    core.start()
    yield core
    core.stop()


def agree(core, cfg, weights, seq, prompt):
    """The served top log-probabilities of every generated token against
    the reference's full forward on prompt + generated."""
    full = list(prompt) + list(seq.generated_ids)
    want = ref.logprobs(cfg, weights, [full], [len(prompt)])[0]
    entries = core.logprob_entries(seq)
    assert len(entries) == len(seq.generated_ids)
    diffs = [
        abs(t["logprob"] - want[pos, t["token_id"]])
        for pos, e in enumerate(entries) for t in e["top_logprobs"]
    ]
    assert diffs and max(diffs) < TOL, max(diffs)


def run(core, prompts, max_tokens=6):
    seqs = [core.submit_tokens(p, lp_params(max_tokens)) for p in prompts]
    for s in seqs:
        assert s.done_event.wait(timeout=600)
        assert s.error is None, s.error
    return seqs


def test_unequal_rows_in_one_wave_and_a_prompt_shorter_than_its_bucket(
        engine, weights):
    rng = np.random.default_rng(1)
    prompts = [tokens(rng, n) for n in (19, 3, 9)]  # buckets 32, 16, 16
    for p, s in zip(prompts, run(engine, prompts)):
        agree(engine, TINY, weights, s, p)
    stats = engine.get_stats()
    assert stats["state_cache"]["slots"] == 4
    assert stats["kv_page_bytes"] == 4 * ref.sizes(TINY)["KV"] * 16 * 2 * 4
    moe = engine.perf.totals()["moe"]
    assert moe["held_assignments"] == moe["assignments"] > 0
    assert moe["overflow"] == 0  # every expert held: one trip


def test_common_prefix_matches_reference_without_prefix_hits(
        engine, weights):
    rng = np.random.default_rng(2)
    shared = tokens(rng, 64)  # sixteen whole pages in common
    prompts = [shared + tokens(rng, 5)]
    (first,) = run(engine, prompts)
    prompts.append(shared + tokens(rng, 7))
    (second,) = run(engine, prompts[1:])
    agree(engine, TINY, weights, first, prompts[0])
    agree(engine, TINY, weights, second, prompts[1])
    assert engine.prefix_cache_enabled is False
    assert engine.allocator.prefix_hits == 0


def test_chunked_prefill_and_a_slot_reused_after_a_longer_tenant():
    """Two periods (8 layers), ONE slot.  A 41-token prompt goes in as
    chunks of 16 + 16 + 9 (a border that is no multiple of 64): the
    state is carried from chunk to chunk through the slot's row.  Then a
    6-token prompt takes the same slot: the longer tenant must have left
    nothing behind."""
    spec = dataclasses.replace(
        spec_for_model_id("tiny-hybrid"), name="tiny-hybrid-2p",
        num_layers=8)
    cfg = dict(TINY, num_hidden_layers=8)
    weights = ref.draw_weights(cfg, 0, jnp.float32)
    core = EngineCore(
        hybrid_config({"prefill_chunk": 16, "prefill_buckets": [8, 16],
                       "max_batch_slots": 1}),
        spec=spec, devices=jax.devices()[:1])
    core.start()
    try:
        rng = np.random.default_rng(4)
        long_prompt, short_prompt = tokens(rng, 41), tokens(rng, 6)
        (a,) = run(core, [long_prompt], max_tokens=8)
        (b,) = run(core, [short_prompt])
        agree(core, cfg, weights, a, long_prompt)
        agree(core, cfg, weights, b, short_prompt)
    finally:
        core.stop()


def test_preemption_by_recompute_rebuilds_the_state(weights):
    core = EngineCore(
        hybrid_config({"kv_num_pages": 15, "decode_chunk": 1,
                       "prefill_buckets": [8, 16, 32]}),
        devices=jax.devices()[:1])
    core.start()
    try:
        rng = np.random.default_rng(5)
        prompts = [tokens(rng, n) for n in (17, 18, 16)]
        seqs = run(core, prompts, max_tokens=10)
        assert core.scheduler.total_preemptions >= 1
        assert any(s.preempt_count for s in seqs)
        for p, s in zip(prompts, seqs):
            assert s.num_output_tokens == 10
            agree(core, TINY, weights, s, p)
    finally:
        core.stop()


def test_journal_replay_gives_the_same_logits(engine, weights):
    rng = np.random.default_rng(6)
    prompt = tokens(rng, 11)
    (whole,) = run(engine, [prompt], max_tokens=8)
    # the same request caught after three tokens, replayed from its
    # checkpoint: the state is rebuilt by prefilling prompt + partial
    partial = Sequence(prompt_ids=list(prompt), params=lp_params(8))
    for t in whole.generated_ids[:3]:
        partial.append_token(t)
    restored = Sequence.from_checkpoint(partial.checkpoint())
    assert replay_into(engine, restored, set()) == "replayed"
    assert restored.done_event.wait(timeout=600)
    assert restored.generated_ids == whole.generated_ids
    want = ref.logprobs(TINY, weights, [prompt + whole.generated_ids],
                        [len(prompt)])[0]
    tail = engine.logprob_entries(restored)[-5:]
    diffs = [abs(t["logprob"] - want[3 + pos, t["token_id"]])
             for pos, e in enumerate(tail) for t in e["top_logprobs"]]
    assert max(diffs) < TOL


@pytest.mark.parametrize("sections, devices, named", [
    ({"tpu": {"speculative_k": 2}}, 1, "speculative decoding"),
    ({"kv_cache": {"host_swap_bytes": 1 << 20}}, 1, "host swap"),
    ({"kv_cache": {"dtype": "int8"}}, 1, "int8"),
    ({"pod": {"workers": 2, "roles": ["prefill", "decode"]}}, 1,
     "handoff of a live sequence"),
    ({"tpu": {"tp": 2}}, 2, "'tp': 2"),
    ({"tpu": {"pp": 2}}, 2, "'pp': 2"),
    ({"tpu": {"sp": 2}}, 2, "'sp': 2"),
])
def test_engine_construction_refuses_by_name(sections, devices, named):
    sections = dict(sections)
    cfg = hybrid_config(sections.pop("tpu", None), **sections)
    with pytest.raises(ValueError, match="recurrent") as exc:
        EngineCore(cfg, devices=jax.devices()[:devices])
    assert named in str(exc.value)



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
