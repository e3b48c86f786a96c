"""``tiny-keye-dsa`` through the ENGINE against the plain reference
(``perfbench/references/keye_vl2.py``): unequal rows in one wave and
what ``/stats`` and ``/debug/perf`` report of the two arrays a page
holds and of the selection; a prefix hit on whole pages that brings K, V
and index keys with it; preemption by recompute; and what knows
head-major K and V pages only, refused by name at engine construction."""

import pytest

from perfbench.references import keye_vl2 as ref
from tests import family_contract as contract

PS, SLOTS, TOPK = 8, 4, 16
FAMILY = contract.Family(
    "keye-vl-2.0-30b-a3b-l12e32.json", ref=ref,
    tol={"float32": 1e-4},  # float32 on both sides: tests/test_keye_dsa.py
    tpu={"kv_num_pages": 96, "kv_page_size": PS, "max_batch_slots": SLOTS,
         "prefill_buckets": [16, 64], "decode_chunk": 1},
    keeps="index keys under one page table")


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
    # four layers' K over V (2 x 32 lanes) and index keys (8 values in a
    # row of 128 lanes), float32
    assert stats["kv_page_bytes"] == PS * 4 * (2 * 32 + 128) * 4
    assert stats["kv_layout"] == {
        "pools": 2, "heads": 1, "row_lanes": 32, "kv_rows": True,
        "index": {"layers": 4, "row_bytes": 512}}
    assert stats["kv_write"] == "scatter"
    assert engine.k_pages.shape == (4, 1, 96, PS, 2, 32)
    assert engine.v_pages.shape == (4, 1, 96, PS, 128)
    dsa = engine.perf.totals()["dsa"]
    assert dsa["prefill_prompts"] == 3
    assert dsa["prefill_pairs_scored"] == 4 * sum(
        n * (n + 1) // 2 for n in lens)
    # a decode step k of a row of length n + k: the first of the 12
    # tokens came with the prompt pass, 11 steps follow; EVERY layer
    # scores the context and attends to its own pick
    ctx = sum(n + 1 + k for n in lens for k in range(steps - 1))
    attended = sum(min(n + 1 + k, TOPK)
                   for n in lens for k in range(steps - 1))
    assert dsa["decode_steps"] == steps - 1
    assert dsa["rows_scored"] == dsa["rows_in_context"] == 4 * ctx
    assert dsa["rows_attended"] == 4 * attended < dsa["rows_in_context"]
    assert dsa["rows_fetched"] == dsa["rows_attended"]  # the jnp twin
    assert dsa["index_layer_steps"] == 4 * (steps - 1)
    assert dsa["selections_reused"] == 0
    assert dsa["index_rows_written"] == 4 * (sum(lens) + 3 * (steps - 1))
    assert engine.perf.totals()["moe"]["layer_steps"] > 0
    ticks = [t for t in engine.flight.ticks() if "index_bytes" in t]
    assert ticks and max(t["index_bytes"] for t in ticks) > 0
    # everything is given back: both arrays' rows go with the page's id
    assert engine.allocator.num_used == 0 or engine.prefix_cache_enabled


def test_the_prompt_kernels_tiles_are_booked_where_the_kernel_runs(
        engine, monkeypatch):
    """``/debug/perf -> totals.prefill_attn``: a whole-prompt program
    whose attention is the Pallas prompt kernel books the tiles a head
    computes and how many of them run without position tests, once a
    program, by the model layer's own count (models/decoder.py
    ``prefill_attn_tiles``); the jnp twin's programs book nothing."""
    import numpy as np

    from vgate_tpu.runtime import engine_core

    rng = np.random.default_rng(54)
    before = dict(engine.perf.totals()["prefill_attn"])
    contract.run(engine, [contract.tokens(rng, 45)], 2)
    assert engine.perf.totals()["prefill_attn"] == before  # the twin's
    booked, count = [], engine_core.prefill_attn_tiles

    def counted(spec, bucket, lens):
        booked.append((bucket, [int(n) for n in lens],
                       count(spec, bucket, lens)))
        return booked[-1][2]

    monkeypatch.setattr(engine_core, "prefill_attn_tiles", counted)
    # (what the engine REPORTS of the program alone: it still runs the twin)
    monkeypatch.setattr(engine_core, "prefill_attention_impl",
                        lambda *a: "pallas")
    contract.run(engine, [contract.tokens(rng, 45),
                          contract.tokens(rng, 19)], 2)
    after = engine.perf.totals()["prefill_attn"]
    assert booked and all(bucket == 64 for bucket, _, _ in booked)
    assert sorted(n for _, lens, _ in booked for n in lens if n > 1) == [
        19, 45]
    assert after["programs"] - before["programs"] == len(booked)
    assert after["tiles"] - before["tiles"] == sum(
        t for _, _, (t, _) in booked) > 0
    assert after["interior_tiles"] - before["interior_tiles"] == sum(
        i for _, _, (_, i) in booked)


def test_a_kernel_fetch_counts_one_token_a_pick():
    """Under the kernel a pick's pair of rows is ONE token (the latent
    form's is two): whole chunks of picks, no partner rows."""
    from vgate_tpu.observability.perf import PerfRecorder

    for pair, want in ((1, 512), (2, 1024)):
        perf = PerfRecorder()
        perf.note_dsa_decode(steps=1, lens=[300], layers=1, index_layers=1,
                             topk=2048, fetch_chunk=512, pair_tokens=pair)
        assert perf.totals()["dsa"]["rows_fetched"] == want


def test_a_prefix_hit_on_whole_pages_brings_k_v_and_index_keys_with_it():
    """The suffix alone writes rows, and is scored against, and attends
    to, what the FIRST prompt left on the shared pages."""
    written = contract.prefix_hit_on_whole_pages(
        FAMILY, lambda core: core.perf.totals()["dsa"]["index_rows_written"])
    # only the suffix's keys were written: 21 and five decode steps' in
    # each of the four layers
    assert written == 4 * (21 + 5)


def test_preemption_by_recompute_rebuilds_both_arrays():
    contract.preemption_by_recompute(
        FAMILY, {"kv_num_pages": 9, "prefill_buckets": [32],
                 "prefix_cache": {"enabled": False}})


@pytest.mark.parametrize("sections, devices, named", contract.REFUSALS)
def test_engine_construction_refuses_by_name(sections, devices, named):
    contract.construction_refuses(FAMILY, sections, devices, named)
