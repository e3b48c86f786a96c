"""Paged KV cache: geometry, page allocator and device buffers.

First-party replacement for the paged-KV capability the reference gets
opaquely from vLLM (SURVEY.md section 2.1 "Paged KV cache + attention
kernels").  Layout: ``[num_layers, kv_heads, num_pages, page_size, head_dim]``
per K and V (head-major so one page of one head is a contiguous
``(page_size, head_dim)`` tile — the unit the Pallas decode kernel DMAs),
resident in TPU HBM; **page 0 is a reserved trash page** that
absorbs writes from padded positions and idle decode slots so device code
never branches on validity.
"""

from __future__ import annotations

from collections import OrderedDict, deque
from dataclasses import dataclass
from typing import Deque, Dict, List, Optional

import jax
import jax.numpy as jnp

from vgate_tpu import faults, metrics
from vgate_tpu.logging_config import get_logger
from vgate_tpu.models.specs import ModelSpec
from vgate_tpu.utils.math import cdiv

logger = get_logger(__name__)


def _page_bytes(
    num_layers: int, page_size: int, kv_heads: int, head_dim: int,
    dtype_bytes: int, scale_bytes: int = 0, pools: int = 2,
    index_layers: int = 0, index_dim: int = 0,
) -> int:
    """Bytes one page occupies across all layers, K and V together — the
    single source of truth for page sizing (used by both KVGeometry and
    auto_num_pages).  ``scale_bytes`` is the per-token-per-head
    quantization-scale overhead (0 for plain bf16/f32 pools; int8 KV
    stores one bf16 scale per (page, head, slot) — ops/kv_quant.py).
    ``pools``: 2 for K and V; 1 for latent attention's ONE pool, whose
    "head" is the token's latent row (``ModelSpec.cache_head_dim``).
    ``index_layers`` x ``index_dim``: the index keys a page holds beside
    them under the same page id, one row a token a picking layer
    (learned sparse attention: ``ModelSpec.index_layers``, the row's
    lanes AS HELD, ``ModelSpec.index_key_lanes``).  GQA attention under
    a selection counts ``pools`` 2 and ``kv_heads`` 1: a token's K over
    its V, each all KV heads wide, in ONE array."""
    return (
        pools * num_layers * page_size * kv_heads
        * (head_dim * dtype_bytes + scale_bytes)
        + index_layers * page_size * index_dim * dtype_bytes
    )


@dataclass(frozen=True)
class KVGeometry:
    num_layers: int
    num_pages: int  # includes the reserved trash page(s)
    page_size: int
    kv_heads: int
    head_dim: int
    max_model_len: int
    dtype_bytes: int = 2  # bf16 default
    # reserved trash pages: 1 normally, sp under sequence-parallel decode
    # (one local trash per pool shard, parallel/sp_decode.py)
    num_reserved: int = 1
    # per-token-per-head scale bytes: 0 for plain pools, 2 (bf16) for
    # int8 KV (kv_cache.dtype: int8 — ops/kv_quant.py)
    scale_bytes: int = 0
    # reporting name for /stats, drills and bench artifacts
    kv_dtype: str = "bf16"
    # arrays of the cache: K and V, or latent attention's one pool
    # (kv_heads 1, head_dim the latent row's lanes: ModelSpec.cache_*)
    pools: int = 2
    # the second array of a spec that picks (learned sparse attention):
    # ONE index key of ``index_dim`` a token in each of ``index_layers``
    # layers, addressed by the same page ids
    index_layers: int = 0
    index_dim: int = 0
    # tokens ONE row of a page stands for (``ModelSpec.cache_row_tokens``:
    # an EVA spec's row is a chunk's summary), so a page holds
    # ``page_tokens`` tokens and a sequence ``ceil(ceil(len / row_tokens)
    # / page_size)`` pages
    row_tokens: int = 1
    # pages of the arrays BEHIND the allocator's ``num_pages``, held a
    # decode slot and never allocated (an EVA spec's open windows:
    # ops/eva.py window_pages)
    slot_pages: int = 0

    @property
    def page_tokens(self) -> int:
        return self.page_size * self.row_tokens

    @property
    def pages_per_seq(self) -> int:
        return cdiv(self.max_model_len, self.page_tokens)

    @property
    def page_bytes(self) -> int:
        return _page_bytes(
            self.num_layers, self.page_size, self.kv_heads, self.head_dim,
            self.dtype_bytes, self.scale_bytes, self.pools,
            self.index_layers, self.index_dim,
        )

    @property
    def total_tokens(self) -> int:
        return (self.num_pages - self.num_reserved) * self.page_tokens


def auto_num_pages(
    spec: ModelSpec,
    page_size: int,
    hbm_utilization: float,
    device=None,
    params_bytes: int = 0,
    fallback: int = 512,
    hard_cap: int = 65536,
    dtype_bytes: int = 2,
    hbm_bytes: int = 0,
    scale_bytes: int = 0,
    shards: int = 1,
    reserved_bytes: int = 0,
) -> int:
    """Size the page pool from free device HBM after weights are resident
    (the serving analogue of vLLM's gpu_memory_utilization knob,
    reference config: vgate/config.py:47).

    The device's own ``memory_stats()["bytes_limit"]`` is the budget.  An
    accelerator that reports none is budgeted against ``hbm_bytes``
    (config ``tpu.hbm_bytes``) minus the parameter bytes, and with that
    unset it is an error — never an assumed chip size.  CPU test
    platforms return ``fallback``.  The step programs hold weights plus
    ONE pool (PERF.md "Bring-up", memory_analysis table), so what
    ``hbm_utilization`` leaves over is head-room for their temporaries
    only.  ``dtype_bytes`` is the KV cache element width (fp32 KV needs
    twice the page budget of bf16); ``scale_bytes`` the per-token-per-
    head quantization-scale overhead (int8 KV: dtype_bytes=1,
    scale_bytes=2 — the same budget then yields ~2x the bf16 page
    count).  ``shards`` is how many ways the mesh splits each page
    (layers over pp, kv heads over tp): a chip stores 1/shards of it.
    """
    device = device or jax.devices()[0]
    stats = getattr(device, "memory_stats", lambda: None)()
    page_bytes = _page_bytes(
        spec.attn_layers, page_size, spec.cache_heads, spec.cache_head_dim,
        dtype_bytes, scale_bytes, spec.kv_pools,
        spec.index_layers, spec.index_key_lanes,
    ) // max(1, shards)
    if stats and "bytes_limit" in stats:
        limit = stats["bytes_limit"] * hbm_utilization
        free = max(0, limit - stats.get("bytes_in_use", 0) - reserved_bytes)
    elif device.platform == "cpu":
        return fallback
    elif hbm_bytes:
        free = max(
            0, hbm_bytes * hbm_utilization - params_bytes - reserved_bytes
        )
    else:
        raise RuntimeError(
            f"{device.device_kind} reports no memory_stats()['bytes_limit'] "
            "to size the KV pool from; set tpu.kv_num_pages, or "
            "tpu.hbm_bytes to this part's per-chip HBM"
        )
    pages = int(free // page_bytes)
    return max(16, min(pages, hard_cap))


class PageAllocator:
    """Refcounting free-list allocator with a content-hash index for
    **automatic prefix caching**.

    Page 0 is the reserved trash page; with ``num_shards`` (sp) > 1 the
    first page of each contiguous pool shard ``{i * num_pages/sp}`` is
    reserved instead, so every sp shard has a LOCAL trash page
    (parallel/sp_decode.py) — the degenerate num_shards=1 case reserves
    exactly {0}.

    A page whose content corresponds to a full page of prompt tokens can be
    ``register``ed under a chain hash; a later prompt with the same prefix
    ``lookup``s the hash and shares the page (refcount++) instead of
    recomputing its KV.  Pages released to refcount 0 keep their content and
    park in an LRU of *evictable* cached pages — reusable until ``allocate``
    needs the space (vLLM's automatic-prefix-caching capability, which the
    reference can't reach because vLLM hides it; here it is first-party).
    """

    def __init__(self, num_pages: int, num_shards: int = 1) -> None:
        from vgate_tpu.parallel.sp_decode import reserved_page_ids

        self.num_pages = num_pages
        self.reserved = frozenset(reserved_page_ids(num_pages, num_shards))
        self._free: Deque[int] = deque(
            p for p in range(num_pages) if p not in self.reserved
        )
        self._refs: Dict[int, int] = {}
        self._hash_to_page: Dict[int, int] = {}
        self._page_hash: Dict[int, int] = {}
        # refcount-0 pages with live cached content, in LRU order
        self._evictable: "OrderedDict[int, None]" = OrderedDict()
        # radix-tree prefix cache (runtime/radix_cache.py): holds its own
        # references on cached pages and reclaims them on demand when
        # the free list runs short — the tree-mode replacement for the
        # flat _evictable LRU above
        self._reclaimer = None
        self.prefix_hits = 0
        self.prefix_evictions = 0
        # set by the engine when the pool stores int8 KV (kv_cache.dtype:
        # int8): every in-use page then holds quantized content, and the
        # vgt_kv_quantized_pages gauge tracks it alongside KV_PAGES_IN_USE
        self.quantized = False
        self._allocatable = num_pages - len(self.reserved)
        metrics.KV_PAGES_TOTAL.set(self._allocatable)
        self._set_in_use(0)

    def _set_in_use(self, used: int) -> None:
        metrics.KV_PAGES_IN_USE.set(used)
        metrics.KV_QUANTIZED_PAGES.set(used if self.quantized else 0)

    def set_reclaimer(self, reclaimer) -> None:
        """Attach a cache that can free refcounted pages on demand
        (``evictable_pages() -> int`` and ``reclaim(n) -> int freed``).
        Reclaimable pages count as obtainable in ``num_free``."""
        self._reclaimer = reclaimer

    @property
    def num_allocatable(self) -> int:
        """Total non-reserved pages (the pool size stats should report)."""
        return self._allocatable

    @property
    def num_free(self) -> int:
        """Pages obtainable by allocate(): truly free + evictable cached
        (flat LRU or reclaimable radix-tree pages)."""
        return len(self._free) + self.num_cached

    @property
    def num_truly_free(self) -> int:
        """Pages obtainable without evicting cache — the proactive-trim
        watermark (radix_cache.trim_to_watermark) keys off this so
        eviction cost is paid ahead of the allocation hot path."""
        return len(self._free)

    @property
    def num_used(self) -> int:
        return self._allocatable - self.num_free

    @property
    def num_cached(self) -> int:
        if self._reclaimer is not None:
            return self._reclaimer.evictable_pages()
        return len(self._evictable)

    def allocate(self, n: int) -> Optional[List[int]]:
        """All-or-nothing allocation of n pages; None when insufficient.
        Evicts least-recently-used cached pages when the free list runs
        short."""
        faults.check("kv_alloc", payload=n)
        if n > self.num_free:
            return None
        if self._reclaimer is not None:
            short = n - len(self._free)
            if short > 0 and self._reclaimer.reclaim(short) < short:
                # a reclaimable page was still referenced (lock races
                # are excluded by design; defensive all-or-nothing)
                return None  # pragma: no cover - lock invariant holds
        pages = []
        for _ in range(n):
            if self._free:
                page = self._free.popleft()
            else:  # evict the LRU cached page (flat-chain mode)
                page, _ = self._evictable.popitem(last=False)
                self._drop_hash(page)
                self.prefix_evictions += 1
                metrics.PREFIX_EVICTIONS.labels(reason="lru").inc()
            self._refs[page] = 1
            pages.append(page)
        self._set_in_use(self.num_used)
        return pages

    def refcount(self, page: int) -> int:
        """Live reference count of a page (0 = free/parked)."""
        return self._refs.get(page, 0)

    def retain(self, pages: List[int]) -> None:
        """Take an extra reference on already-allocated pages (prefix
        sharing: the radix tree and each matching sequence hold their
        own reference; release() drops them symmetrically)."""
        for page in pages:
            refs = self._refs.get(page, 0)
            if refs <= 0:
                # a retained page must already be live — retaining a
                # free page would let allocate() hand it out again
                raise ValueError(f"retain of unreferenced page {page}")
            self._refs[page] = refs + 1
        self._set_in_use(self.num_used)

    def release(self, pages: List[int]) -> None:
        for page in pages:
            if not 0 <= page < self.num_pages or page in self.reserved:
                raise ValueError(f"bad page id {page}")
            refs = self._refs.get(page, 1) - 1
            if refs > 0:
                self._refs[page] = refs
                continue
            self._refs.pop(page, None)
            if page in self._page_hash:
                # content stays reusable until evicted
                self._evictable[page] = None
                self._evictable.move_to_end(page)
            else:
                self._free.append(page)
        self._set_in_use(self.num_used)
        if self._reclaimer is None and self._page_hash:
            metrics.PREFIX_CACHED_PAGES.set(len(self._evictable))

    # ----------------------------------------------------- prefix caching

    def register(self, page: int, content_hash: int) -> None:
        """Index a page's content under its prefix-chain hash.  On a hash
        collision with a live mapping, the existing page wins (both hold
        identical content by construction)."""
        if content_hash in self._hash_to_page:
            return
        self._hash_to_page[content_hash] = page
        self._page_hash[page] = content_hash

    def peek(self, content_hash: int) -> Optional[int]:
        """Check whether a page is cached for this hash WITHOUT taking a
        reference (scheduler admissibility probes must not mutate
        refcounts)."""
        return self._hash_to_page.get(content_hash)

    def is_evictable(self, page: int) -> bool:
        """True when the page is parked in the refcount-0 LRU: a prefix
        lookup() would revive it OUT of the allocatable pool, so
        admissibility math must not count it as free AND matched."""
        return page in self._evictable

    def lookup(self, content_hash: int) -> Optional[int]:
        """Find a cached page for this hash and take a reference to it."""
        page = self._hash_to_page.get(content_hash)
        if page is None:
            return None
        if page in self._evictable:  # revive a parked page
            del self._evictable[page]
            self._refs[page] = 1
            metrics.PREFIX_CACHED_PAGES.set(len(self._evictable))
        else:
            self._refs[page] = self._refs.get(page, 0) + 1
        self._set_in_use(self.num_used)
        return page

    def _drop_hash(self, page: int) -> None:
        h = self._page_hash.pop(page, None)
        if h is not None and self._hash_to_page.get(h) == page:
            del self._hash_to_page[h]


def make_kv_buffers(geometry: KVGeometry, dtype=jnp.bfloat16, sharding=None):
    """Allocate the page pools (zeros; ``(k, v)``, or ``(latent, None)``
    for a geometry of ONE pool, or ``(latent by pairs of rows, index
    keys)`` for one with ``index_layers``) directly on device, each chip
    of a mesh creating only its own shard (``device=sharding``): a global
    pool drawn on the default device and spread afterwards does not fit
    the one chip it is drawn on.

    With ``geometry.kv_dtype == "int8"`` each pool is a
    :class:`~vgate_tpu.ops.kv_quant.QuantPages` pair — int8 data plus
    the per-(page, head, slot) bf16 scale pool (initialized to 1, the
    scale :func:`~vgate_tpu.ops.kv_quant.quantize` assigns all-zero
    rows, so the zeroed pool dequantizes to exactly 0).  int8 KV
    requires a plain mesh (the engine enforces it), so ``sharding``
    is effectively single-device/replicated there.
    """
    from vgate_tpu.ops.kv_quant import SCALE_DTYPE, QuantPages

    shape = (
        geometry.num_layers,
        geometry.kv_heads,
        geometry.num_pages + geometry.slot_pages,
        geometry.page_size,
        geometry.head_dim,
    )

    if geometry.kv_dtype == "int8":
        scale_sharding = None
        if sharding is not None and hasattr(sharding, "spec"):
            # the scale pool drops the trailing head_dim: same spec
            # minus its last axis (all-None on the plain meshes int8
            # is restricted to, but keep the shapes honest)
            from jax.sharding import NamedSharding, PartitionSpec

            scale_sharding = NamedSharding(
                sharding.mesh, PartitionSpec(*tuple(sharding.spec)[:-1])
            )

        def pool():
            return QuantPages(
                data=jnp.zeros(shape, jnp.int8, device=sharding),
                scale=jnp.ones(
                    shape[:-1], SCALE_DTYPE, device=scale_sharding
                ),
            )

        k, v = pool(), pool()
    else:
        # a spec that picks holds its latent rows by PAIRS of tokens:
        # its decode kernel fetches single picked rows, and a pair is the
        # least Mosaic lets a descriptor address (ops/kv_quant.py
        # by_pairs).  The same bytes in the same order
        latent = shape
        if geometry.index_layers and geometry.pools == 2:
            # GQA attention under a selection: a token's K over its V,
            # ``[L, 1, P, ps, 2, KV x hd]``, ONE array (a pick is one
            # fetch of the pair; ops/pallas/dsa.py); ``pools`` counts
            # the two rows
            latent = shape[:4] + (2,) + shape[4:]
        elif geometry.index_layers:
            if geometry.page_size % 2:
                raise ValueError(
                    f"kv_cache.page_size={geometry.page_size}: a spec under "
                    "a learned selection holds its rows by pairs, an even "
                    "page")
            latent = shape[:3] + (geometry.page_size // 2, 2) + shape[4:]
        k = jnp.zeros(latent, dtype, device=sharding)
        v = (jnp.zeros(shape, dtype, device=sharding)
             if geometry.pools == 2 and not geometry.index_layers else None)
        if geometry.index_layers:
            v = jnp.zeros(
                (geometry.index_layers, 1) + shape[2:4]
                + (geometry.index_dim,), dtype, device=sharding)
    pool_bytes = sum(
        x.size * x.dtype.itemsize for x in jax.tree.leaves((k, v))
    )
    logger.info(
        "kv cache allocated",
        extra={
            "extra_data": {
                "pages": geometry.num_pages,
                "tokens_capacity": geometry.total_tokens,
                "kv_dtype": geometry.kv_dtype,
                "mb": round(pool_bytes / 1e6),
            }
        },
    )
    return k, v
