"""Automatic prefix caching: content-hashed KV page sharing + suffix-only
prefill (runtime/kv_cache.py PageAllocator, runtime/scheduler.py matching,
models/decoder.py prefill_suffix_forward).  The capability vLLM provides
opaquely to the reference; here it is first-party and tested."""

import jax
import numpy as np
import pytest

from vgate_tpu.backends.base import SamplingParams
from vgate_tpu.config import load_config
from vgate_tpu.runtime.kv_cache import PageAllocator
from vgate_tpu.runtime.scheduler import Scheduler
from vgate_tpu.runtime.sequence import Sequence

PS = 4  # page size used throughout


# --------------------------------------------------------------- allocator


def test_allocator_register_lookup_refcount():
    alloc = PageAllocator(8)
    [p] = alloc.allocate(1)
    alloc.register(p, 123)
    # lookup takes a new reference
    assert alloc.lookup(123) == p
    assert alloc.lookup(999) is None
    # two holders: one release keeps the page live
    alloc.release([p])
    assert alloc.num_cached == 0  # still referenced by the lookup
    alloc.release([p])
    # now parked as evictable cached content, still reusable
    assert alloc.num_cached == 1
    assert alloc.lookup(123) == p
    alloc.release([p])


def test_allocator_evicts_lru_cached_pages():
    alloc = PageAllocator(4)  # pages 1..3
    pages = alloc.allocate(3)
    for i, p in enumerate(pages):
        alloc.register(p, 100 + i)
    alloc.release(pages)  # all parked, LRU order 1,2,3
    assert alloc.num_cached == 3
    assert alloc.num_free == 3  # evictable counts as allocatable
    got = alloc.allocate(2)  # evicts the two oldest
    assert got is not None
    assert alloc.prefix_evictions == 2
    # the evicted hashes are gone; the survivor still resolves
    surviving = [h for h in (100, 101, 102) if alloc.lookup(h) is not None]
    assert len(surviving) == 1


def test_allocator_oversubscription_still_fails():
    alloc = PageAllocator(4)
    assert alloc.allocate(4) is None  # only 3 usable pages
    pages = alloc.allocate(3)
    assert alloc.allocate(1) is None
    alloc.release(pages)


# --------------------------------------------------------------- scheduler


def make_sched(num_pages=64, prefix_cache=True, slots=4):
    alloc = PageAllocator(num_pages)
    return Scheduler(
        allocator=alloc,
        max_slots=slots,
        page_size=PS,
        prefill_buckets=[8, 16, 32],
        max_model_len=64,
        max_queue_size=16,
        prefix_cache=prefix_cache,
    ), alloc


def seq_of(ids, max_tokens=8):
    return Sequence(
        prompt_ids=list(ids), params=SamplingParams(max_tokens=max_tokens)
    )


def register(alloc, plan):
    """What the engine does after dispatching the plan's program."""
    for page, h in plan.register_hashes or ():
        alloc.register(page, h)


def test_scheduler_matches_shared_prefix():
    sched, alloc = make_sched()
    prompt = list(range(2, 2 + 11))  # 11 tokens -> 2 full pages + partial
    a = seq_of(prompt)
    sched.add(a)
    plan_a = sched.try_admit()
    assert plan_a.cached_len == 0
    # the two full pages are handed back for post-dispatch registration
    # (registering at admission would let a same-tick reader's program
    # dispatch ahead of this writer's)
    assert len(plan_a.register_hashes) == 2
    register(alloc, plan_a)

    b = seq_of(prompt)  # identical prompt
    sched.add(b)
    plan_b = sched.try_admit()
    assert plan_b.cached_len == 2 * PS
    assert b.pages[:2] == a.pages[:2]  # shared ids
    assert b.pages[2] != a.pages[2]  # own partial page
    assert plan_b.bucket == 8  # buckets the 3-token suffix, not the prompt
    assert sched.total_prefix_hit_tokens == 2 * PS

    # releasing one sequence must not free the shared pages for the other
    sched.remove(a)
    assert alloc.lookup is not None
    c = seq_of(prompt + [99])
    sched.add(c)
    plan_c = sched.try_admit()
    assert plan_c.cached_len == 2 * PS  # still matches via b / cache


def test_scheduler_never_matches_entire_prompt():
    """A fully page-aligned identical prompt keeps its last page un-matched
    so the suffix prefill has at least one real token to sample from."""
    sched, _ = make_sched()
    prompt = list(range(2, 2 + 8))  # exactly 2 pages
    a = seq_of(prompt)
    sched.add(a)
    register(sched.allocator, sched.try_admit())
    b = seq_of(prompt)
    sched.add(b)
    plan_b = sched.try_admit()
    assert plan_b.cached_len == PS  # only the first page matched
    assert b.pages[0] == a.pages[0]
    assert b.pages[1] != a.pages[1]


def test_scheduler_disabled_no_sharing():
    sched, alloc = make_sched(prefix_cache=False)
    prompt = list(range(2, 2 + 11))
    a = seq_of(prompt)
    sched.add(a)
    plan_a = sched.try_admit()
    assert plan_a.cached_len == 0 and not plan_a.register_hashes
    b = seq_of(prompt)
    sched.add(b)
    plan_b = sched.try_admit()
    assert plan_b.cached_len == 0
    assert set(a.pages).isdisjoint(b.pages)


# ------------------------------------------------------------------ engine


def engine_config(prefix_cache=True):
    return load_config(
        model={
            "model_id": "tiny-dense",
            "engine_type": "jax_tpu",
            "dtype": "float32",
            "max_model_len": 64,
        },
        tpu={
            "dp": 1, "tp": 1, "ep": 1, "sp": 1, "num_devices": 1,
            "kv_num_pages": 64, "kv_page_size": PS,
            "max_batch_slots": 4, "prefill_buckets": [8, 16, 32],
            "use_pallas": False, "prefix_cache": prefix_cache,
        },
        scheduler={"max_queue_size": 16},
        logging={"level": "WARNING"},
    )


@pytest.fixture(scope="module")
def engines():
    from vgate_tpu.runtime.engine_core import EngineCore

    cached = EngineCore(engine_config(True), devices=jax.devices()[:1])
    plain = EngineCore(engine_config(False), devices=jax.devices()[:1])
    cached.start()
    plain.start()
    yield cached, plain
    cached.stop()
    plain.stop()


def greedy(n=8):
    return SamplingParams(max_tokens=n, temperature=0.0)


def test_engine_prefix_hit_matches_uncached_output(engines):
    """Greedy output through the suffix-prefill path must equal the
    cold-path output exactly (same KV, fewer FLOPs)."""
    cached, plain = engines
    base = [7, 3, 9, 4, 11, 6, 2, 13, 5, 8, 12, 10, 14]  # 13 tokens
    [cold] = cached.generate(["x"], [greedy(1)])  # warm the engine
    [a1] = cached.generate([" ".join(map(str, base))], [greedy()])
    hit0 = cached.scheduler.total_prefix_hit_tokens
    [a2] = cached.generate([" ".join(map(str, base))], [greedy()])
    assert cached.scheduler.total_prefix_hit_tokens > hit0  # hit happened
    [p] = plain.generate([" ".join(map(str, base))], [greedy()])
    assert a1["token_ids"] == p["token_ids"]
    assert a2["token_ids"] == p["token_ids"]


def test_engine_shared_prefix_divergent_suffixes(engines):
    """Two prompts sharing a long prefix but different endings: the second
    reuses prefix pages yet produces its own correct continuation."""
    cached, plain = engines
    prefix = "alpha beta gamma delta epsilon zeta eta theta"
    p1 = prefix + " one"
    p2 = prefix + " two"
    [c1] = cached.generate([p1], [greedy()])
    [c2] = cached.generate([p2], [greedy()])
    [u1] = plain.generate([p1], [greedy()])
    [u2] = plain.generate([p2], [greedy()])
    assert c1["token_ids"] == u1["token_ids"]
    assert c2["token_ids"] == u2["token_ids"]
    assert c1["token_ids"] != c2["token_ids"] or len(c1["token_ids"]) == 0


def test_engine_stats_surface_prefix_cache(engines):
    cached, _ = engines
    stats = cached.get_stats()["scheduler"]["prefix_cache"]
    assert stats["enabled"] is True
    assert stats["hit_tokens"] > 0


def test_engine_same_wave_identical_prompts_correct(engines):
    """Two identical prompts admitted in ONE wave: the second must NOT
    read pages whose writer program hasn't dispatched (registration is
    deferred until after dispatch), so both produce correct output."""
    cached, plain = engines
    prompt = "wave one two three four five six seven eight nine"
    seqs = [
        cached.submit_prompt(prompt, greedy()) for _ in range(2)
    ]
    for s in seqs:
        assert s.done_event.wait(timeout=300)
    [ref] = plain.generate([prompt], [greedy()])
    for s in seqs:
        assert list(s.generated_ids) == ref["token_ids"]


def test_preemption_of_one_sharer_spares_shared_pages():
    """Preempting a sequence that shares prefix pages must only drop its
    reference: the surviving sharer's KV stays resident and its greedy
    output is unchanged."""
    sched, alloc = make_sched(num_pages=64)
    prompt = list(range(2, 2 + 11))
    a = seq_of(prompt)
    sched.add(a)
    register(alloc, sched.try_admit())
    b = seq_of(prompt)
    sched.add(b)
    plan_b = sched.try_admit()
    assert plan_b.cached_len == 2 * PS
    shared = list(b.pages[:2])

    used_before = alloc.num_used
    sched._preempt(a)  # a's refs drop; shared pages must survive for b
    assert all(p in b.pages for p in shared)
    # b still holds them: not evictable, not free
    assert alloc.num_used < used_before
    got = alloc.allocate(alloc.num_free)  # drain everything allocatable
    assert got is not None
    assert not set(got) & set(shared)  # shared pages were never handed out
    alloc.release(got)


def test_prefix_cache_survives_engine_preemption_pressure():
    """End-to-end: a KV pool small enough to force preemptions, prefix
    cache on — greedy outputs still match the uncached engine."""
    from vgate_tpu.runtime.engine_core import EngineCore

    def run(prefix_cache):
        config = load_config(
            model={
                "model_id": "tiny-dense",
                "engine_type": "jax_tpu",
                "dtype": "float32",
                "max_model_len": 64,
            },
            tpu={
                "dp": 1, "tp": 1, "ep": 1, "sp": 1, "num_devices": 1,
                # tight pool: 13 usable pages for 3 sequences needing ~15
                "kv_num_pages": 14, "kv_page_size": PS,
                "max_batch_slots": 3, "prefill_buckets": [8, 16, 32],
                "use_pallas": False, "prefix_cache": prefix_cache,
            },
            scheduler={"max_queue_size": 16},
            logging={"level": "ERROR"},
        )
        core = EngineCore(config, devices=jax.devices()[:1])
        core.start()
        try:
            prompts = [
                "shared long prefix words " + tail
                for tail in ("alpha", "beta", "gamma")
            ]
            out = core.generate(
                prompts, [SamplingParams(max_tokens=10, temperature=0.0)] * 3
            )
            return [r["token_ids"] for r in out], core.get_stats()
        finally:
            core.stop()

    cached_out, cached_stats = run(True)
    plain_out, _ = run(False)
    assert cached_out == plain_out
    # the pool really was tight (otherwise the test proves nothing)
    assert (
        cached_stats["scheduler"]["preemptions"] > 0
        or cached_stats["scheduler"]["prefix_cache"]["evictions"] > 0
    )


# ------------------------------------------------- radix: COW + multi-turn


def radix_config(prefix_cache=True, **pc_overrides):
    pc = {"enabled": prefix_cache, "cow_min_tokens": 2, **pc_overrides}
    return load_config(
        model={
            "model_id": "tiny-dense",
            "engine_type": "jax_tpu",
            "dtype": "float32",
            "max_model_len": 96,
        },
        tpu={
            "dp": 1, "tp": 1, "ep": 1, "sp": 1, "num_devices": 1,
            "kv_num_pages": 96, "kv_page_size": PS,
            "max_batch_slots": 4, "prefill_buckets": [8, 16, 32],
            "use_pallas": False, "prefix_cache": pc,
        },
        scheduler={"max_queue_size": 16},
        logging={"level": "ERROR"},
    )


@pytest.fixture(scope="module")
def radix_engines():
    from vgate_tpu.runtime.engine_core import EngineCore

    cached = EngineCore(radix_config(True), devices=jax.devices()[:1])
    plain = EngineCore(radix_config(False), devices=jax.devices()[:1])
    cached.start()
    plain.start()
    yield cached, plain
    cached.stop()
    plain.stop()


def test_engine_cow_partial_page_identity(radix_engines):
    """A prompt diverging INSIDE a shared page takes the copy-on-write
    path (device page copy + unaligned suffix prefill) and must still
    produce exactly the cold-path greedy output."""
    cached, plain = radix_engines
    base = [7, 3, 9, 4, 11, 6, 2, 13, 5, 8, 12, 10, 14, 9]
    ids_a = base
    ids_b = base[:10] + [21, 22, 23, 24]  # 2 full pages + 2 in-page
    sa = cached.submit_tokens(list(ids_a), greedy())
    assert sa.done_event.wait(timeout=300)
    cow0 = cached.radix_cache.total_cow_copies
    sb = cached.submit_tokens(list(ids_b), greedy())
    assert sb.done_event.wait(timeout=300)
    assert cached.radix_cache.total_cow_copies > cow0, "COW never fired"
    pa = plain.submit_tokens(list(ids_a), greedy())
    pb = plain.submit_tokens(list(ids_b), greedy())
    assert pa.done_event.wait(timeout=300)
    assert pb.done_event.wait(timeout=300)
    assert list(sa.generated_ids) == list(pa.generated_ids)
    assert list(sb.generated_ids) == list(pb.generated_ids)


def test_engine_multi_turn_generated_reuse(radix_engines):
    """Turn N+1 re-sends turn N's prompt AND answer: the radix tree
    indexes generated pages at finish, so the next turn's hit covers
    (nearly) the whole previous transcript — the flat chain could only
    ever match the previous PROMPT pages."""
    cached, plain = radix_engines
    t1 = [31, 32, 33, 34, 35, 36, 37, 38, 39, 40, 41]
    s1 = cached.submit_tokens(list(t1), greedy())
    assert s1.done_event.wait(timeout=300)
    answer = list(s1.generated_ids)
    # next turn: transcript (minus the final token, whose KV was never
    # written) + new user text
    t2 = t1 + answer + [51, 52, 53, 54, 55]
    hit0 = cached.scheduler.total_prefix_hit_tokens
    s2 = cached.submit_tokens(list(t2), greedy())
    assert s2.done_event.wait(timeout=300)
    hit = cached.scheduler.total_prefix_hit_tokens - hit0
    # the hit must reach INTO the generated region: more than the
    # prompt-only pages the flat chain would serve
    flat_max = (len(t1) // PS) * PS
    assert hit > flat_max, (hit, flat_max)
    p2 = plain.submit_tokens(list(t2), greedy())
    assert p2.done_event.wait(timeout=300)
    assert list(s2.generated_ids) == list(p2.generated_ids)


def test_engine_radix_stats_surface(radix_engines):
    cached, _ = radix_engines
    stats = cached.get_stats()["scheduler"]["prefix_cache"]
    assert stats["mode"] == "radix"
    assert stats["inserted_pages"] > 0
    assert "evictions_pressure" in stats and "cow_copies" in stats


@pytest.mark.fast  # tier-1: the decode kernel's write leans on it
def test_the_page_a_decode_step_writes_has_one_owner(radix_engines):
    """What the decode kernel's write leans on
    (ops/pallas/paged_attention.py): it copies the new token's WHOLE
    page back to the pool, so no other sequence, and not the tree, may
    hold that page while a chunk that writes it is in flight.  Shared
    prompts, a divergence inside a page (copy-on-write), a turn that
    reuses generated pages and streams side by side: at every decode
    dispatch every page the chunk can write has exactly one holder."""
    cached, _ = radix_engines
    real = cached._dispatch_chunk
    checked, shared = [], []

    def checking(active, chunk):
        lead = sum(c[1] for c in cached._pending_chunks)
        in_tree = set(cached.radix_cache.pages_in_tree())
        for seq in active:
            first = seq.total_len - 1
            for pos in range(first, first + lead + chunk):
                if pos // PS >= len(seq.pages):
                    break  # past its pages: the trash page
                page = seq.pages[pos // PS]
                checked.append(page)
                if cached.allocator.refcount(page) != 1 or page in in_tree:
                    shared.append((seq.seq_id, pos, page))
        return real(active, chunk)

    cached._dispatch_chunk = checking
    try:
        base = [61, 62, 63, 64, 65, 66, 67, 68, 69, 70, 71, 72, 73, 74]
        first = cached.submit_tokens(list(base), greedy(12))
        assert first.done_event.wait(timeout=300)
        turn = base + list(first.generated_ids) + [81, 82, 83]
        seqs = [
            cached.submit_tokens(ids, greedy(12))
            for ids in (
                list(base),  # the whole prompt again
                base[:10] + [91, 92, 93],  # diverges inside page 3
                base[:8] + [94, 95, 96, 97, 98],  # at a page's edge
                turn,  # reuses generated pages
            )
        ]
        for seq in seqs:
            assert seq.done_event.wait(timeout=300)
    finally:
        cached._dispatch_chunk = real
    assert len(checked) > 40, "no decode chunk was dispatched"
    assert not shared, shared
