"""``tiny-dsa-moe`` through the ENGINE against the plain reference
(``perfbench/references/glm_moe_dsa.py``): unequal rows in one wave and
what ``/stats`` and ``/debug/perf`` report of the two rows a page holds
and of the selection; chunked prefill; a prefix hit on whole pages that
brings the index keys with it; pages freed free both rows; preemption by
recompute; journal replay; and what knows K and V pages only, refused by
name at engine construction."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import manifest
from perfbench.references import glm_moe_dsa as ref
from vgate_tpu.backends.base import SamplingParams
from vgate_tpu.config import load_config
from vgate_tpu.models import specs
from vgate_tpu.runtime.engine_core import EngineCore, replay_into
from vgate_tpu.runtime.sequence import Sequence

SPEC = specs.spec_for_model_id("tiny-dsa-moe")
TINY = manifest.load_json(
    manifest.HERE, "configs", "glm-5.2-l5e16.json")["rehearse"]["model"]
TOL = 1e-4  # float32 on both sides: tests/test_glm_dsa.py
PS, SLOTS, TOPK = 8, 4, 16

# one period behind the leading layer (F S S S F, the cell's own stack):
# what the engine tests that boot an engine of their own compile
SHORT = specs._register(dataclasses.replace(
    SPEC, name="tiny-dsa-moe-l5", num_layers=5, indexer_pattern="FSSSF"))
TINY_SHORT = dict(
    TINY, num_hidden_layers=5,
    indexer_types=["full", "shared", "shared", "shared", "full"],
    mlp_layer_types=["dense"] + ["sparse"] * 4)


def engine_config(tpu=None, model_id="tiny-dsa-moe", **sections):
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
    """Three prompts in one wave (under the pick, just past it, three
    times it), each a whole-prompt pass and decode steps; /stats and
    /debug/perf say what a page holds and what the selection read."""
    rng = np.random.default_rng(1)
    lens = (7, 19, 45)
    prompts = [tokens(rng, n) for n in lens]
    steps = 12
    diffs = []
    for p, s in zip(prompts, run(engine, prompts, max_tokens=steps)):
        diffs += differences(engine, s, p)
    assert diffs and max(diffs) < TOL, max(diffs)
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
    """ONE slot.  A 75-token prompt goes in as chunks of 32 + 32 + 11:
    each later chunk is scored against the index keys the earlier ones
    left in the pool.  Then a 9-token prompt takes the same slot and
    pages, whose other rows still hold the first tenant's."""
    core = EngineCore(
        engine_config({"prefill_chunk": 32, "prefill_buckets": [16, 32],
                       "max_batch_slots": 1,
                       "prefix_cache": {"enabled": False}},
                      model_id=SHORT.name),
        devices=jax.devices()[:1])
    core.start()
    try:
        rng = np.random.default_rng(4)
        long_prompt, short_prompt = tokens(rng, 75), tokens(rng, 9)
        (a,) = run(core, [long_prompt], max_tokens=8)
        (b,) = run(core, [short_prompt], max_tokens=TOPK)
        for seq, prompt in ((a, long_prompt), (b, short_prompt)):
            diffs = differences(core, seq, prompt, TINY_SHORT)
            assert max(diffs) < TOL, max(diffs)
        assert core.allocator.num_used == 0  # freed pages free both rows
    finally:
        core.stop()


def test_a_prefix_hit_on_whole_pages_brings_the_index_keys_with_it():
    """Two prompts share their first 64 tokens (8 whole pages).  The
    second is a prefix hit: its suffix alone goes through the prompt
    pass and writes index keys, and is scored against the FIRST one's
    index keys on the shared pages; its answer is the reference's."""
    core = EngineCore(engine_config(model_id=SHORT.name),
                      devices=jax.devices()[:1])
    core.start()
    try:
        assert core.prefix_cache_enabled
        rng = np.random.default_rng(9)
        shared = tokens(rng, 64)
        first, second = shared + tokens(rng, 7), shared + tokens(rng, 21)
        (a,) = run(core, [first])
        before = core.perf.totals()["dsa"]["index_rows_written"]
        (b,) = run(core, [second])
        after = core.perf.totals()["dsa"]["index_rows_written"]
        for seq, prompt in ((a, first), (b, second)):
            diffs = differences(core, seq, prompt, TINY_SHORT)
            assert max(diffs) < TOL, max(diffs)
        assert core.allocator.prefix_hits > 0 or (
            core.radix_cache is not None
            and core.radix_cache.get_stats()["hits"] > 0)
        # only the suffix's keys were written: 21 and five decode
        # steps' in each of the two picking layers
        assert after - before == 2 * (21 + 5)
    finally:
        core.stop()


def test_preemption_by_recompute_rebuilds_both_rows():
    core = EngineCore(
        engine_config({"kv_num_pages": 9, "decode_chunk": 1,
                       "prefill_buckets": [32],
                       "prefix_cache": {"enabled": False}},
                      model_id=SHORT.name),
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
    prompt = tokens(rng, 21)
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
    with pytest.raises(ValueError, match="index keys under one page "
                                         "table") as exc:
        EngineCore(cfg, devices=jax.devices()[:devices])
    assert named in str(exc.value)
