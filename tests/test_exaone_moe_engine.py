"""``tiny-swa-moe`` through the ENGINE against the plain reference
(``perfbench/references/exaone_moe.py``): unequal rows in one wave and
what ``/stats`` and ``/debug/perf`` report of the rings, chunked
prefill, a slot reused, preemption by recompute, journal replay; and
what knows pages only, refused by name at engine construction."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import manifest
from perfbench.references import exaone_moe as ref
from vgate_tpu.backends.base import SamplingParams
from vgate_tpu.config import load_config
from vgate_tpu.models import specs
from vgate_tpu.runtime.engine_core import EngineCore, replay_into
from vgate_tpu.runtime.sequence import Sequence

SPEC = specs.spec_for_model_id("tiny-swa-moe")
TINY = manifest.load_json(
    manifest.HERE, "configs", "k-exaone-236b-a23b-l5e16.json"
)["rehearse"]["model"]
TOL = 1e-4  # float32 on both sides: tests/test_exaone_moe.py
PS, SLOTS, RING = 4, 4, 12  # page, decode slots, a ring's tokens (3 pages)

# one period behind the leading layer (L L L G L, the cell's own stack):
# what the engine tests that boot an engine of their own compile
SHORT = specs._register(dataclasses.replace(
    SPEC, name="tiny-swa-moe-l5", num_layers=5))
TINY_SHORT = dict(TINY, num_hidden_layers=5)


def engine_config(tpu=None, model_id="tiny-swa-moe", **sections):
    base = {
        "dp": 1, "tp": 1, "ep": 1, "sp": 1, "kv_num_pages": 96,
        "kv_page_size": PS, "max_batch_slots": SLOTS,
        "prefill_buckets": [16, 64], "use_pallas": False,
        "decode_chunk": 1,
    }
    base.update(tpu or {})
    return load_config(
        model={"model_id": model_id, "engine_type": "jax_tpu",
               "dtype": "float32", "max_model_len": 128},
        tpu=base, scheduler={"max_queue_size": 16},
        logging={"level": "WARNING"}, **sections,
    )


def lp_params(max_tokens):
    return SamplingParams(max_tokens=max_tokens, temperature=0.0,
                          logprobs=True, top_logprobs=5)


def tokens(rng, n):
    return [int(t) for t in rng.integers(3, 259, size=n)]


def run(core, prompts, max_tokens=6):
    seqs = [core.submit_tokens(p, lp_params(max_tokens)) for p in prompts]
    for s in seqs:
        assert s.done_event.wait(timeout=600)
        assert s.error is None, s.error
    return seqs


def differences(core, seq, prompt, cfg=TINY):
    full = list(prompt) + list(seq.generated_ids)
    want = ref.logprobs(cfg, 0, jnp.float32, [full], [len(prompt)])[0]
    entries = core.logprob_entries(seq)
    assert len(entries) == len(seq.generated_ids)
    return [abs(t["logprob"] - want[pos, t["token_id"]])
            for pos, e in enumerate(entries) for t in e["top_logprobs"]]


@pytest.fixture(scope="module")
def engine():
    core = EngineCore(engine_config(), devices=jax.devices()[:1])
    core.start()
    yield core
    core.stop()


def test_unequal_rows_through_the_engine_and_what_it_reports(engine):
    """Three prompts in one wave (under a ring, past one, several), each
    a whole-prompt pass and decode steps past a ring's length; /stats and
    /debug/perf say what the cache is and what the rings moved."""
    rng = np.random.default_rng(1)
    prompts = [tokens(rng, n) for n in (7, 19, 45)]
    diffs = []
    for p, s in zip(prompts, run(engine, prompts, max_tokens=RING + 2)):
        diffs += differences(engine, s, p)
    assert diffs and max(diffs) < TOL, max(diffs)
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
    """ONE slot.  A 75-token prompt goes in as chunks of 32 + 32 + 11;
    then a 9-token prompt takes the same slot and ring, whose other
    pages still hold the first tenant's rows."""
    core = EngineCore(
        engine_config({"prefill_chunk": 32, "prefill_buckets": [16, 32],
                       "max_batch_slots": 1}, model_id=SHORT.name),
        devices=jax.devices()[:1])
    core.start()
    try:
        rng = np.random.default_rng(4)
        long_prompt, short_prompt = tokens(rng, 75), tokens(rng, 9)
        (a,) = run(core, [long_prompt], max_tokens=8)
        (b,) = run(core, [short_prompt], max_tokens=RING + 2)
        for seq, prompt in ((a, long_prompt), (b, short_prompt)):
            diffs = differences(core, seq, prompt, TINY_SHORT)
            assert max(diffs) < TOL, max(diffs)
    finally:
        core.stop()


def test_held_pairs_past_the_capacity_are_served_and_counted(monkeypatch):
    """The dispatch takes three pairs at a time where a decode step of
    two rows has four and a 16-row prompt 32: every trip beyond the
    first is in ``/debug/perf -> totals.moe.overflow``, and the tokens'
    logprobs are the reference's all the same."""
    from vgate_tpu.ops import moe

    monkeypatch.setattr(moe, "capacity", lambda spec, pairs: 3)
    core = EngineCore(
        engine_config({"prefill_buckets": [16], "max_batch_slots": 2},
                      model_id=SHORT.name),
        devices=jax.devices()[:1])
    core.start()
    try:
        rng = np.random.default_rng(6)
        prompts = [tokens(rng, 9), tokens(rng, 14)]
        for p, s in zip(prompts, run(core, prompts, max_tokens=5)):
            assert max(differences(core, s, p, TINY_SHORT)) < TOL
        booked = core.perf.totals()["moe"]
        # four expert layers a step, a trip more in each at the least
        assert booked["overflow"] >= booked["layer_steps"] > 0
        assert booked["held_assignments"] == booked["assignments"]
    finally:
        core.stop()


def test_preemption_by_recompute_rebuilds_the_rings():
    core = EngineCore(
        engine_config({"kv_num_pages": 15, "decode_chunk": 1,
                       "prefill_buckets": [32]}, model_id=SHORT.name),
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
            assert max(differences(core, s, p, TINY_SHORT)) < TOL
    finally:
        core.stop()


def test_journal_replay_gives_the_same_logits(engine):
    rng = np.random.default_rng(6)
    prompt = tokens(rng, 11)
    (whole,) = run(engine, [prompt], max_tokens=8)
    partial = Sequence(prompt_ids=list(prompt), params=lp_params(8))
    for t in whole.generated_ids[:3]:
        partial.append_token(t)
    restored = Sequence.from_checkpoint(partial.checkpoint())
    assert replay_into(engine, restored, set()) == "replayed"
    assert restored.done_event.wait(timeout=600)
    assert restored.generated_ids == whole.generated_ids
    want = ref.logprobs(TINY, 0, jnp.float32,
                        [prompt + whole.generated_ids], [len(prompt)])[0]
    tail = engine.logprob_entries(restored)[-5:]
    diffs = [abs(t["logprob"] - want[3 + pos, t["token_id"]])
             for pos, e in enumerate(tail) for t in e["top_logprobs"]]
    assert max(diffs) < TOL


@pytest.mark.parametrize("sections, devices, named", [
    ({"tpu": {"speculative_k": 2}}, 1, "speculative decoding"),
    ({"kv_cache": {"host_swap_bytes": 1 << 20}}, 1, "host swap"),
    ({"kv_cache": {"dtype": "int8"}}, 1, "int8"),
    ({"model": {"quantization": "int8"}}, 1, "model.quantization"),
    ({"pod": {"workers": 2, "roles": ["prefill", "decode"]}}, 1,
     "handoff of a live sequence"),
    ({"tpu": {"tp": 2}}, 2, "'tp': 2"),
    ({"tpu": {"pp": 2}}, 2, "'pp': 2"),
    ({"tpu": {"sp": 2}}, 2, "'sp': 2"),
])
def test_engine_construction_refuses_by_name(sections, devices, named):
    sections = dict(sections)
    model = sections.pop("model", {})
    cfg = engine_config(sections.pop("tpu", None), **sections)
    if model:
        cfg.model.quantization = model["quantization"]
    with pytest.raises(ValueError, match="per-slot ring") as exc:
        EngineCore(cfg, devices=jax.devices()[:devices])
    assert named in str(exc.value)
