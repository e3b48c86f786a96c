"""Continuous-batching scheduler unit tests (host-only, no JAX)."""

import pytest

from vgate_tpu.backends.base import SamplingParams
from vgate_tpu.runtime.kv_cache import KVGeometry, PageAllocator
from vgate_tpu.runtime.scheduler import (
    DecodePlan,
    EngineBusyError,
    PrefillPlan,
    Scheduler,
)
from vgate_tpu.runtime.sequence import Sequence, SeqStatus


def make_sched(num_pages=32, slots=4, page_size=4, buckets=(8, 16), max_len=64,
               queue=8):
    alloc = PageAllocator(num_pages)
    return Scheduler(
        allocator=alloc,
        max_slots=slots,
        page_size=page_size,
        prefill_buckets=list(buckets),
        max_model_len=max_len,
        max_queue_size=queue,
    ), alloc


def seq_of(n_prompt, max_tokens=8):
    return Sequence(
        prompt_ids=list(range(2, 2 + n_prompt)),
        params=SamplingParams(max_tokens=max_tokens),
    )


def test_allocator_all_or_nothing():
    alloc = PageAllocator(4)  # pages 1..3 usable
    assert alloc.num_free == 3
    assert alloc.allocate(4) is None
    pages = alloc.allocate(3)
    assert sorted(pages) == [1, 2, 3]
    alloc.release(pages)
    assert alloc.num_free == 3


def test_allocator_rejects_bad_release():
    alloc = PageAllocator(4)
    with pytest.raises(ValueError):
        alloc.release([0])  # trash page must never be released


def test_kv_geometry():
    geom = KVGeometry(
        num_layers=2, num_pages=9, page_size=4, kv_heads=2, head_dim=8,
        max_model_len=32,
    )
    assert geom.pages_per_seq == 8
    assert geom.total_tokens == 32  # trash page excluded


def test_prefill_admission_and_bucketing():
    sched, alloc = make_sched()
    seq = seq_of(n_prompt=5)
    sched.add(seq)
    plan = sched.schedule()
    assert isinstance(plan, PrefillPlan)
    assert plan.bucket == 8  # 5 -> bucket 8
    assert len(seq.pages) == 2  # ceil(5/4)
    assert seq.status is SeqStatus.RUNNING
    assert alloc.num_used == 2


def test_decode_after_prefill():
    sched, _ = make_sched()
    seq = seq_of(4)
    sched.add(seq)
    sched.schedule()
    seq.append_token(9)  # engine appends prefill token
    plan = sched.schedule()
    assert isinstance(plan, DecodePlan)
    assert plan.seqs == [seq]


def test_prefill_priority_over_decode():
    sched, _ = make_sched()
    a = seq_of(4)
    sched.add(a)
    sched.schedule()
    a.append_token(1)
    b = seq_of(4)
    sched.add(b)
    plan = sched.schedule()
    assert isinstance(plan, PrefillPlan)
    assert plan.seq is b


def test_page_allocated_on_boundary_crossing():
    sched, alloc = make_sched(page_size=4)
    seq = seq_of(4)  # exactly one page
    sched.add(seq)
    sched.schedule()
    assert len(seq.pages) == 1
    seq.append_token(1)  # position 4 -> needs page 2
    plan = sched.schedule()
    assert isinstance(plan, DecodePlan)
    assert len(seq.pages) == 2


def test_queue_full_sheds_load():
    sched, _ = make_sched(queue=2)
    sched.add(seq_of(4))
    sched.add(seq_of(4))
    with pytest.raises(EngineBusyError):
        sched.add(seq_of(4))


def test_oversized_prompt_rejected():
    sched, _ = make_sched(max_len=16)
    with pytest.raises(ValueError):
        sched.add(seq_of(20))


def test_preemption_frees_youngest():
    # 5 usable pages, two seqs of 2 pages each -> 1 free page
    sched, alloc = make_sched(num_pages=6, page_size=4)
    old = seq_of(8)
    sched.add(old)
    sched.schedule()
    old.append_token(1)
    young = seq_of(8)
    sched.add(young)
    sched.schedule()
    young.append_token(1)
    assert alloc.num_free == 1
    # old crosses a page boundary (uses the last page), then young crosses:
    # allocator is empty -> young (the newest) must be preempted
    for _ in range(4):
        old.append_token(1)
        young.append_token(1)
        plan = sched.schedule()
        assert isinstance(plan, (DecodePlan, PrefillPlan))
        if young.status is SeqStatus.WAITING:
            break
    assert young.status is SeqStatus.WAITING
    assert young.preempt_count == 1
    assert young.slot is None
    assert sched.total_preemptions == 1
    # preempted seq keeps its generated tokens for recompute
    assert young.num_prompt_tokens > 8


def test_remove_releases_everything():
    sched, alloc = make_sched()
    seq = seq_of(6)
    sched.add(seq)
    sched.schedule()
    used = alloc.num_used
    assert used > 0
    sched.remove(seq)
    assert alloc.num_used == 0
    assert sched.slots[0] is None


def test_impossible_prompt_fails_instead_of_deadlocking():
    sched, _ = make_sched(num_pages=2, page_size=4, max_len=64)
    seq = seq_of(30)  # needs 8 pages, only 1 usable
    sched.add(seq)
    plan = sched.schedule()
    assert plan is None
    assert seq.status is SeqStatus.FAILED


def test_idle_returns_none():
    sched, _ = make_sched()
    assert sched.schedule() is None
    assert not sched.has_work()


def test_prepare_decode_horizon_allocates_ahead():
    """horizon=k must reserve pages covering positions pos..pos+k-1 (the
    engine's chunked-decode contract, engine_core.py:_tick)."""
    sched, alloc = make_sched(page_size=4)
    seq = seq_of(4, max_tokens=16)  # fills exactly one page
    sched.add(seq)
    sched.schedule()  # admit: 1 page for the 4 prompt tokens
    assert len(seq.pages) == 1
    seq.append_token(9)  # first (prefill) token -> pos 4, page 2 territory
    assert sched.prepare_decode([seq], horizon=6)
    # positions 4..9 span pages 1 and 2 -> 3 pages total... pos 4..9 -> 2 more
    assert len(seq.pages) == 3  # ceil((4+6)/4)


def test_prepare_decode_horizon_capped_by_budget():
    """A sequence with 1 token of budget left must not allocate horizon
    pages for steps that will be discarded as overshoot."""
    sched, alloc = make_sched(page_size=4)
    seq = seq_of(4, max_tokens=2)
    sched.add(seq)
    sched.schedule()
    seq.append_token(9)  # 1 generated, budget leaves 1 more
    used_before = alloc.num_used
    assert sched.prepare_decode([seq], horizon=8)
    # only the page holding pos 4 (already needed for the kept step) counts
    assert alloc.num_used == used_before + 1
    assert len(seq.pages) == 2


def test_admission_deadline_sheds_stale_requests():
    """scheduler.admission_deadline_ms: queued requests older than the
    deadline are failed with AdmissionDeadlineExceeded instead of admitted
    (SURVEY.md section 5.3 load shedding); fresh requests still admit."""
    import time

    from vgate_tpu.runtime.scheduler import AdmissionDeadlineExceeded

    alloc = PageAllocator(32)
    sched = Scheduler(
        allocator=alloc,
        max_slots=4,
        page_size=4,
        prefill_buckets=[8],
        max_model_len=64,
        max_queue_size=8,
        admission_deadline_ms=50.0,
    )
    stale = seq_of(4)
    stale.arrival_t = time.perf_counter() - 1.0  # 1s in queue
    fresh = seq_of(4)
    sched.add(stale)
    sched.add(fresh)
    plan = sched.try_admit()
    assert stale.status is SeqStatus.FAILED
    assert isinstance(stale.error, AdmissionDeadlineExceeded)
    assert isinstance(stale.error, EngineBusyError)  # maps to HTTP 503
    assert plan is not None and plan.seq is fresh
    assert sched.total_deadline_shed == 1
    assert sched.get_stats()["deadline_shed"] == 1


def test_admission_deadline_spares_preempted():
    """A preempted sequence re-queued past the deadline must NOT be shed:
    it was already admitted once and holds generated tokens."""
    import time

    alloc = PageAllocator(32)
    sched = Scheduler(
        allocator=alloc,
        max_slots=4,
        page_size=4,
        prefill_buckets=[8],
        max_model_len=64,
        max_queue_size=8,
        admission_deadline_ms=50.0,
    )
    seq = seq_of(4)
    sched.add(seq)
    sched.try_admit()
    seq.append_token(9)
    sched._preempt(seq)
    seq.arrival_t = time.perf_counter() - 1.0
    plan = sched.try_admit()
    assert plan is not None and plan.seq is seq
    assert sched.total_deadline_shed == 0


def test_auto_num_pages_dtype_and_hbm_aware():
    """fp32 KV halves the page budget of bf16; hbm_bytes scales it; a
    mesh that splits each page `shards` ways fits that many more; and an
    accelerator that reports no memory_stats is an error unless
    tpu.hbm_bytes names its size — never an assumed 16 GiB."""
    import pytest

    from vgate_tpu.models.specs import TINY_DENSE
    from vgate_tpu.runtime.kv_cache import auto_num_pages

    class FakeTPU:
        platform = "tpu"
        device_kind = "fake"

        @staticmethod
        def memory_stats():
            return None

    common = dict(
        spec=TINY_DENSE, page_size=16, hbm_utilization=0.5,
        device=FakeTPU(), params_bytes=0, hard_cap=1 << 40,
    )
    gib16 = 16 * 1024**3
    bf16 = auto_num_pages(dtype_bytes=2, hbm_bytes=gib16, **common)
    fp32 = auto_num_pages(dtype_bytes=4, hbm_bytes=gib16, **common)
    assert fp32 == bf16 // 2
    double = auto_num_pages(dtype_bytes=2, hbm_bytes=2 * gib16, **common)
    assert double == bf16 * 2
    assert (
        auto_num_pages(dtype_bytes=2, hbm_bytes=gib16, shards=2, **common)
        == bf16 * 2
    )
    with pytest.raises(RuntimeError, match="bytes_limit"):
        auto_num_pages(dtype_bytes=2, **common)

    class ReportingTPU(FakeTPU):
        @staticmethod
        def memory_stats():
            return {"bytes_limit": gib16, "bytes_in_use": gib16 // 4}

    # the device's own limit wins over tpu.hbm_bytes
    reported = auto_num_pages(
        dtype_bytes=2, hbm_bytes=2 * gib16,
        **{**common, "device": ReportingTPU()},
    )
    assert reported == bf16 // 2


def _pressure_sched(num_pages=32, max_slots=2, page_size=4):
    from vgate_tpu.runtime.kv_cache import PageAllocator
    from vgate_tpu.runtime.scheduler import Scheduler

    return Scheduler(
        allocator=PageAllocator(num_pages),
        max_slots=max_slots,
        page_size=page_size,
        prefill_buckets=[8, 16],
        max_model_len=32,
    )


def test_has_admissible_waiting_distinguishes_blockers():
    """The admission-pressure predicate is true only when the head of
    the queue could ACTUALLY be admitted: free slot AND allocatable
    pages.  Page exhaustion must read as not-admissible (the engine
    keys chunk shrinking off this — shrinking buys nothing when
    admission is blocked on pages)."""
    from vgate_tpu.backends.base import SamplingParams
    from vgate_tpu.runtime.sequence import Sequence

    sched = _pressure_sched(num_pages=9, max_slots=2, page_size=4)
    assert not sched.has_admissible_waiting()  # empty queue

    sp = SamplingParams(max_tokens=4, temperature=0.0)
    sched.add(Sequence(prompt_ids=[1] * 8, params=sp))  # needs 2 pages
    assert sched.has_admissible_waiting()

    # drain the pool: 8 allocatable pages (1 reserved) -> take 7
    held = sched.allocator.allocate(7)
    assert held is not None
    assert not sched.has_admissible_waiting()  # pages exhausted
    sched.allocator.release(held)
    assert sched.has_admissible_waiting()

    # saturate slots
    sched.slots[0] = object()
    sched.slots[1] = object()
    assert not sched.has_admissible_waiting()
    sched.slots[0] = sched.slots[1] = None

    # an aborted head is skipped; the next live prompt decides
    sched.waiting[0].abort_requested = True
    assert not sched.has_admissible_waiting()  # only entry is aborted
    sched.add(Sequence(prompt_ids=[2] * 4, params=sp))
    assert sched.has_admissible_waiting()


def test_has_admissible_waiting_counts_evictable_matched_pages():
    """A matched prefix page parked in the evictable LRU counts toward
    num_free, but admission would REVIVE it out of that pool — the
    predicate must not double-count it as both free and matched."""
    from vgate_tpu.backends.base import SamplingParams
    from vgate_tpu.runtime.kv_cache import PageAllocator
    from vgate_tpu.runtime.scheduler import Scheduler

    alloc = PageAllocator(8)  # pages 1..7 allocatable
    sched = Scheduler(
        allocator=alloc, max_slots=2, page_size=4,
        prefill_buckets=[8, 16], max_model_len=32, prefix_cache=True,
    )
    sp = SamplingParams(max_tokens=4, temperature=0.0)
    from vgate_tpu.runtime.sequence import Sequence

    seq = Sequence(prompt_ids=[3] * 12, params=sp)  # needs 3 pages
    # make its first full page resident-and-evictable: register a page
    # under the prompt's first chain hash, then release it to refcount 0
    chain = sched._prefix_chain(seq)
    [page] = alloc.allocate(1)
    alloc.register(page, chain[0])
    alloc.release([page])
    assert alloc.is_evictable(page)

    sched.add(seq)
    # pool state: 7 allocatable, 6 truly free + 1 evictable-matched.
    # needs 3 pages total, 1 matched -> allocate(2) vs 6 free: fine
    assert sched.has_admissible_waiting()

    # drain free pages so only the evictable matched page + 1 remain:
    # allocate(5) leaves num_free = 2 (1 free + 1 evictable-matched);
    # naive math says needed 2 <= 2, but admission revives the matched
    # page first, leaving just 1 allocatable for the 2-page remainder
    held = alloc.allocate(5)
    assert held is not None
    assert alloc.num_free == 2
    assert not sched.has_admissible_waiting()
