"""Pallas paged-attention decode kernels.

The TPU-native replacement for the CUDA paged-attention the reference gets
opaquely through vLLM (SURVEY.md section 2.1; technique family: "Ragged
Paged Attention", PAPERS.md).  Semantics are pinned by the jnp twin
``vgate_tpu.ops.attention.paged_decode_attention`` (kernel tests compare the
two); the kernels' advantage is the memory path:

* the jnp twin gathers every slot's full ``pages_per_seq`` window into a
  contiguous HBM buffer (write + re-read), touching ``ctx_max`` tokens even
  for short sequences;
* the kernels DMA **only the live pages** of each sequence directly from
  the HBM page pool into VMEM, buffered in chunks of pages, and run an
  online-softmax accumulation entirely in VMEM — no gathered copy, no
  dead-token traffic.

``paged_decode_attention_pallas`` (one query token a slot) runs one program
per BLOCK of slots, which walks the block's live chunks and serves all KV
heads an iteration, and, handed the slots' new tokens, writes each one's
page into the pool itself (a decode step has no scatter before it);
``paged_multitok_attention_pallas`` (speculative
verify: S candidate tokens a slot) still runs one program per (slot,
kv_head) over ``_chunk_dma``.
"""

from __future__ import annotations

import functools
import operator

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from vgate_tpu.utils.math import cdiv

# pages DMA'd per double-buffer slot of the multi-token kernel
CHUNK_PAGES = 8


def _chunk_dma(
    page_tables_ref, k_pages_ref, v_pages_ref, k_buf, v_buf, sems,
    b, g, n_pages, page_size, layer=None,
    k_scale_ref=None, v_scale_ref=None, sk_buf=None, sv_buf=None,
):
    """Double-buffered page-DMA machinery of the multi-token kernel.

    Returns ``(start_chunk, wait_chunk)`` closures: ``start_chunk(c, slot)``
    kicks off the async copies of chunk ``c``'s live pages into buffer
    ``slot`` (zero-filling pages beyond the sequence — stale VMEM could
    hold NaNs, and softmax-weight 0 x NaN would poison the accumulator);
    ``wait_chunk`` blocks on those copies.

    With ``layer`` (a traced scalar) the page pools carry a leading
    layer dim ``[L, KV, P, ps, hd]`` and the DMA indexes it — the
    carry-threaded decode path (models/decoder.py) passes the FULL
    stacked buffer instead of a per-layer slice, so no 2x67MB slice
    materialization per layer feeds the kernel.

    int8 KV (``k_scale_ref`` et al. given — ops/kv_quant.py): each
    page's per-(head, slot) bf16 scale row ``[ps]`` rides its own tiny
    DMA into ``sk_buf``/``sv_buf`` ``[2, 1, chunk_tokens]`` alongside
    the int8 page tile; the scale sems live at indices 2/3 (the sem
    array widens to ``[2, 4, CHUNK]``).  Dead-page scale slots zero-fill
    like the data tiles — stale-VMEM NaN times an exactly-0 softmax
    weight would still poison the accumulator."""
    quant = k_scale_ref is not None

    def src(ref, page_id):
        if layer is None:
            return ref.at[g, page_id]
        return ref.at[layer, g, page_id]

    def start_chunk(c, slot):
        for j in range(CHUNK_PAGES):  # static unroll
            page_pos = c * CHUNK_PAGES + j

            @pl.when(page_pos < n_pages)
            def _():
                page_id = page_tables_ref[b, page_pos]
                pltpu.make_async_copy(
                    src(k_pages_ref, page_id),
                    k_buf.at[slot, pl.ds(j * page_size, page_size), :],
                    sems.at[slot, 0, j],
                ).start()
                pltpu.make_async_copy(
                    src(v_pages_ref, page_id),
                    v_buf.at[slot, pl.ds(j * page_size, page_size), :],
                    sems.at[slot, 1, j],
                ).start()
                if quant:
                    pltpu.make_async_copy(
                        src(k_scale_ref, page_id),
                        sk_buf.at[
                            slot, 0, pl.ds(j * page_size, page_size)
                        ],
                        sems.at[slot, 2, j],
                    ).start()
                    pltpu.make_async_copy(
                        src(v_scale_ref, page_id),
                        sv_buf.at[
                            slot, 0, pl.ds(j * page_size, page_size)
                        ],
                        sems.at[slot, 3, j],
                    ).start()

            @pl.when(page_pos >= n_pages)
            def _():
                k_buf[slot, pl.ds(j * page_size, page_size), :] = jnp.zeros(
                    (page_size, k_buf.shape[-1]), k_buf.dtype
                )
                v_buf[slot, pl.ds(j * page_size, page_size), :] = jnp.zeros(
                    (page_size, v_buf.shape[-1]), v_buf.dtype
                )
                if quant:
                    sk_buf[
                        slot, 0, pl.ds(j * page_size, page_size)
                    ] = jnp.zeros((page_size,), sk_buf.dtype)
                    sv_buf[
                        slot, 0, pl.ds(j * page_size, page_size)
                    ] = jnp.zeros((page_size,), sv_buf.dtype)

    def wait_chunk(c, slot):
        for j in range(CHUNK_PAGES):
            page_pos = c * CHUNK_PAGES + j

            @pl.when(page_pos < n_pages)
            def _():
                pltpu.make_async_copy(
                    src(k_pages_ref, 0),
                    k_buf.at[slot, pl.ds(j * page_size, page_size), :],
                    sems.at[slot, 0, j],
                ).wait()
                pltpu.make_async_copy(
                    src(v_pages_ref, 0),
                    v_buf.at[slot, pl.ds(j * page_size, page_size), :],
                    sems.at[slot, 1, j],
                ).wait()
                if quant:
                    pltpu.make_async_copy(
                        src(k_scale_ref, 0),
                        sk_buf.at[
                            slot, 0, pl.ds(j * page_size, page_size)
                        ],
                        sems.at[slot, 2, j],
                    ).wait()
                    pltpu.make_async_copy(
                        src(v_scale_ref, 0),
                        sv_buf.at[
                            slot, 0, pl.ds(j * page_size, page_size)
                        ],
                        sems.at[slot, 3, j],
                    ).wait()

    return start_chunk, wait_chunk


def _scale_row(buf, slot):
    """The active double-buffer's scale row as f32 ``[1, chunk_tokens]``
    (broadcasts over the score rows)."""
    return jax.lax.cond(
        slot == 0, lambda: buf[0], lambda: buf[1]
    ).astype(jnp.float32)


# VMEM the single-token decode kernel spends, half on its K and V chunk
# buffers (which bounds the chunk's tokens, down to DECODE_TILE_TOKENS) and
# half on the pipelined q and out blocks (which sets the slots a program
# serves): _decode_sizes.  The write's own VMEM rides beside it, at most
# 2.1 MiB at the cells' shapes (32 KV heads): the new tokens' [BS, KV, hd]
# blocks and DECODE_WRITE_BUFFERS staged pages.
DECODE_VMEM_BUDGET = 4 << 20
# Loop trips whose K/V chunk buffers a launch holds: the trip computed and
# two in flight behind it.  Two in flight keep the HBM busy only while a
# trip's arithmetic outlasts its pages' transfer.  A trip of ITEMS items
# computes ITEMS buffers, so the launch holds DECODE_PHASES x ITEMS of
# them: decode_buffers, which also says where a two-item trip keeps ONE
# trip in flight and not two.
DECODE_PHASES = 3
# A chunk (one item of the work list) holds at most this many tokens: two
# 128-token MXU weight tiles a head.  Wider chunks mostly add tail work at
# the lengths served (PERF.md section 6, PR 28: 512 tokens cost the 1.5B
# 5 % and the 7B 20 %); a trip of two items pays the trip's fixed cost once
# per 512 tokens without those tails.
DECODE_CHUNK_TOKENS = 256
# ... and at least this many, whatever the budget's share comes to: one
# 128-token MXU weight tile a head.  Under it a head's products meet a part
# of a tile and a softmax update a part-filled vreg: 32 KV heads of 128 in
# the budget came to ONE page of 32 tokens a chunk and 4.88 ms a launch
# where four pages take 1.02 (two pages 1.44, eight 1.01: PERF.md section
# 6, PR 45).  The chunk buffers then take what that needs (6.3 MB there),
# beside the budget and inside the launch's `vmem_limit_bytes`.
DECODE_TILE_TOKENS = 128
# Staged pages of new tokens on their way back to the pool: a slot's
# write is waited for only when its staging page comes round again, which
# a trip of two items that both end their slots brings two pages nearer.
DECODE_WRITE_BUFFERS = 4
# The most KV heads an item may hold for a trip to serve two (the rule of
# _decode_sizes; PERF.md section 6, PR 37 has the probe behind it).
DECODE_PAIR_KV_HEADS = 2


def decode_buffers(items: int, pools: int = 2) -> int:
    """Chunk buffers of a kernel whose trip serves `items` items, a whole
    number of trips' worth: the trip computed and the trips in flight
    behind it.  A trip of one item has two behind it.  A trip of two has
    two where K and V are pools of their own: an item is little there,
    and with ONE trip in flight (PR 37's four buffers) the scalar core's
    issue of the next trip's 32 descriptors stood between a wait and the
    bytes it waited for; two took 5.8 % off a launch at the 1.5B's
    `decode-heavy` shape, 11.0 % at `long-context`'s, 5.2 % at
    nemotron's, 2.1 % at qwen3-next's and 1.8 % at `chat`'s.  Over the
    ONE latent pool it has one: an item there is 32 heads' products over
    384 lanes, a trip's arithmetic outlasts its pages' transfer, and a
    second trip in flight cost 1.2 % (in PR 37's probe and again in PR
    56's; three cost 3.5 %: PERF.md section 6, PR 56)."""
    behind = 1 if items > 1 and pools == 1 else DECODE_PHASES - 1
    return (1 + behind) * items


def _decode_sizes(B, KV, G, hd, page_size, pages_per_seq, kv_dtype, q_dtype,
                  pools: int = 2, items: int = 0, chunk_pages: int = 0,
                  buffers: int = 0):
    """(pages a chunk, slots a program, items a loop trip, chunk buffers)
    for one geometry.  `items` = 0 asks the rule; the probe and the tests
    force 1 or 2.  `chunk_pages` = 0 asks the rule; the probe forces a
    power of two.  `buffers` = 0 asks decode_buffers; the probe and the
    tests force a multiple of `items`, at least twice it."""
    kv_bytes = jnp.dtype(kv_dtype).itemsize
    q_bytes = jnp.dtype(q_dtype).itemsize
    # An item's work is a chain of products and one softmax update a KV
    # head.  Where that is little, the trip's fixed cost (its branches,
    # the wait before the first product, the work list's step) is a
    # large part of it, and a trip serves TWO items for it.
    if not items:
        items = 2 if KV <= DECODE_PAIR_KV_HEADS else 1
    buffers = buffers or decode_buffers(items, pools)
    if buffers % items or buffers < 2 * items:
        raise ValueError(
            f"{buffers} chunk buffers are no two or more trips of {items}"
        )
    # K and V (or the one latent pool's rows) of every KV head in every
    # buffer of a one-item trip (a second item's buffers ride beside the
    # budget: a chunk is the same whatever a trip holds), but no fewer
    # than a weight tile's, whatever the heads; a power of two, so the
    # kernel's page arithmetic is shifts
    if not chunk_pages:
        token_bytes = DECODE_PHASES * pools * KV * hd * kv_bytes
        chunk_tokens = min(
            max(DECODE_VMEM_BUDGET // 2 // token_bytes, DECODE_TILE_TOKENS),
            DECODE_CHUNK_TOKENS,
        )
        chunk_pages = max(1, chunk_tokens // page_size)
        chunk_pages = 1 << (chunk_pages.bit_length() - 1)
    # q and out rows of every head (G pads to the dtype's sublane tile),
    # two buffers each
    g_rows = cdiv(G, 32 // q_bytes) * (32 // q_bytes)
    slot_bytes = 2 * 2 * KV * g_rows * hd * q_bytes
    block_slots = DECODE_VMEM_BUDGET // 2 // slot_bytes
    return (
        min(chunk_pages, pages_per_seq), max(1, min(block_slots, B)), items,
        buffers,
    )


def _div(x, d: int):
    """x // d for a traced x >= 0 and a static d."""
    if d & (d - 1) == 0:
        return jax.lax.shift_right_logical(x, jnp.int32(d.bit_length() - 1))
    return jax.lax.div(x, jnp.int32(d))


# rows of the decode kernel's per-slot SMEM table
_LEN, _LO, _PAGES, _FIRST, _END, _NEXT, _TICK = range(7)


def _decode_kernel(
    # scalar prefetch
    page_tables_ref,  # [B, pages_per_seq] int32 (SMEM)
    seq_lens_ref,  # [B] int32 (SMEM); 0 => the slot holds nothing
    window_ref,  # [1] int32 (SMEM); >0 => attend only to the last `window`
    layer_ref,  # [1] int32 (SMEM); pool layer index (-1 => no layer dim)
    # inputs: q_ref [BS, KV, G, hd] VMEM block of this program's slots;
    # k/v_pages_ref [KV, P, ps, hd] in ANY/HBM (head-major), or
    # [L, KV, P, ps, hd] when has_layer.  `quant` (int8 KV) adds
    # k/v_scale_ref [KV, P, ps] bf16 pools after them; `write` adds
    # k/v_new_ref [BS, KV, hd] VMEM blocks, the slots' new token.
    # outputs: out_ref [BS, KV, G, hd]; `write` adds k/v_out_ref, the
    # pools again (aliased onto the inputs).
    # scratch: k_buf/v_buf [buffers, KV, CP*ps, hd] VMEM, `buffers` as
    # _decode_sizes gives them (+ sk/sv_buf [buffers, KV, CP*ps] when
    # quant; + wk/wv_buf
    # [DECODE_WRITE_BUFFERS, KV, ps, hd] when write), acc [KV, G, hd] f32,
    # m/l [KV, G, 128] f32 running max/denom (col-broadcast), slots_ref
    # [6, BS + 1] int32 SMEM (the block's slots, see below; a seventh row
    # when hollow), DMA sems, one a trip's buffers (+ one a staged page
    # when write)
    *refs,
    page_size: int,
    chunk_pages: int,
    items: int,
    batch: int,
    softcap: float,
    scale: float,
    has_layer: bool,
    quant: bool,
    write: bool,
    latent: int = 0,
    hollow: bool = False,
):
    """One program serves a BLOCK of slots: its work list is the live
    chunks of those slots in order, and the next chunks' pages are in
    flight while a chunk is computed whether or not they belong to one
    slot, so the pipeline is primed once a program and never drains
    inside it.  A slot of length 0 is not on the list: no DMA, no
    item, zeros out.  An ITEM of the list is one chunk (at most
    DECODE_CHUNK_TOKENS tokens) of one slot for all KV heads.

    A loop TRIP serves `items` consecutive items of the list (1 or 2:
    _decode_sizes reads it off the shape), whichever slots they belong
    to.  A trip's fixed cost is paid once whatever it holds: the loop's
    step, ONE wait for all its items' pages (they share a semaphore,
    which counts bytes), one issue site for the chunks that go in flight
    behind it, the branch structure and the bubble before the first
    product; the items' products and softmax updates lie side by side in
    one basic block for the compiler to interleave, while each keeps its
    own 256 tokens, so no dead tail grows (512-token chunks bought the
    same saving with one: PERF.md section 6, PR 28 and PR 37).  Within a
    slot the chunks' softmax updates keep their order, the second item
    starting from the first one's state: every output and the written
    pool are the one-item kernel's to the bit.  Two items may end two
    slots in one trip (two staged pages, two stores); an odd last item
    gets a trip of its own after the loop, and nothing stands in for a
    second.  `items` = 1 is the program as it was before trips held two.

    What an item costs is scalar work and MXU weight loads, not
    bytes (PERF.md section 6, PR 28).  A page of 32 tokens is one
    descriptor for K and one for V, and the compiler does not overlap a
    descriptor's scalar work with the vector work: hence one descriptor a
    page for ALL heads, the issue code once per buffer (static
    destinations), no DMA bounds checks (the page id is clamped instead),
    per-slot scalars computed once a program into SMEM, and merged
    waits.  The products take 8 query rows against 128-token weight
    tiles, so the MXU's time is its weight loads: operands stay bf16.

    With `write` the slot's new token (position length - 1) reaches the
    pool from here and not through a scatter before the call: the pool
    does not hold it yet, so the trip that serves the slot's LAST
    chunk puts the row into the fetched page where it lies in the
    buffer, the products run over the buffer as ever, and that one page
    goes back to the pool through a staging page, one descriptor for K
    and one for V, all KV heads in each (a one-row descriptor is a
    slice Mosaic refuses).  The page's other rows go back as they came.
    That leans on the cache's invariant: THE PAGE A DECODE STEP WRITES
    BELONGS TO THAT ONE SEQUENCE (the radix cache shares whole pages
    only and copies a partial one before anyone appends to it), so no
    other slot reads or writes it during the call.  A slot of length 0
    writes nothing.  However many trips' chunks stand in flight
    (_decode_sizes gives the buffers: two trips' behind the one
    computed, or one), the chunks further ahead belong to LATER items of
    the list, and the page a slot writes is its LAST chunk's: nothing
    after it on the list is that slot's, and no other slot's chunk holds
    the page, so no copy in flight reads a page that a write behind it
    changes.  A staged page is waited for when its staging page comes
    round again (and all of them before the program ends), whatever the
    depth.

    With `latent` > 0 (multi-head latent attention, absorbed form) there
    is ONE pool and no V: a row of it is the key of every query head,
    and its first `latent` lanes are the value.  The refs are then
    q_ref [BS, 1, G, W], the pool, (`write`) new_ref [BS, 1, W]; out_ref
    [BS, 1, G, latent], (`write`) the pool again; k_buf, (`write`)
    wk_buf, acc [1, G, latent], m, l, slots_ref, sems, (`write`) wsems.

    `hollow` is the probe's (benchmarks/bench_kernels.py): the trips'
    bookkeeping alone.  Every descriptor's start and wait, the write and
    the store are one SMEM counter's step each (so their branches stay),
    and nothing is multiplied; the output is meaningless."""
    k_scale_ref = v_scale_ref = sk_buf = sv_buf = None
    k_new_ref = v_new_ref = k_out_ref = v_out_ref = None
    wk_buf = wv_buf = wsems = v_pages_ref = v_buf = None
    if latent and write:
        (
            q_ref, k_pages_ref, k_new_ref, out_ref, k_out_ref, k_buf,
            wk_buf, acc_ref, m_ref, l_ref, slots_ref, sems, wsems,
        ) = refs
    elif latent:
        (
            q_ref, k_pages_ref, out_ref, k_buf, acc_ref, m_ref, l_ref,
            slots_ref, sems,
        ) = refs
    elif quant:
        (
            q_ref, k_pages_ref, v_pages_ref, k_scale_ref, v_scale_ref,
            out_ref, k_buf, v_buf, sk_buf, sv_buf, acc_ref, m_ref, l_ref,
            slots_ref, sems,
        ) = refs
    elif write:
        (
            q_ref, k_pages_ref, v_pages_ref, k_new_ref, v_new_ref,
            out_ref, k_out_ref, v_out_ref, k_buf, v_buf, wk_buf, wv_buf,
            acc_ref, m_ref, l_ref, slots_ref, sems, wsems,
        ) = refs
    else:
        (
            q_ref, k_pages_ref, v_pages_ref,
            out_ref, k_buf, v_buf, acc_ref, m_ref, l_ref, slots_ref, sems,
        ) = refs
    # (pool, its chunk buffer), and what a new token's row goes through
    pools = ((k_pages_ref, k_buf),) if latent else (
        (k_pages_ref, k_buf), (v_pages_ref, v_buf))
    writes = ((k_new_ref, k_buf, wk_buf, k_out_ref),) if latent else (
        (k_new_ref, k_buf, wk_buf, k_out_ref),
        (v_new_ref, v_buf, wv_buf, v_out_ref))
    value_buf = k_buf if latent else v_buf
    BS, KV, G, _ = q_ref.shape
    num_pages = k_pages_ref.shape[2 if has_layer else 1]
    CP = chunk_pages
    T = CP * page_size
    base = pl.program_id(0) * BS
    window = window_ref[0]
    layer = layer_ref[0] if has_layer else None

    # the block's slots, once a program: length, window start, live pages,
    # first chunk and one past the last, and the next live slot after this
    # one.  Entry BS is the end of the work list.  `total` = live chunks.
    next_live = jnp.int32(BS)
    total = jnp.int32(0)
    for j in reversed(range(BS + 1)):
        if j == BS:
            sl = jnp.int32(0)
        else:
            sl = jnp.where(
                base + j < batch,
                seq_lens_ref[jnp.minimum(base + j, batch - 1)], 0,
            )
        # tokens below `lo` contribute nothing: chunks wholly below the
        # window start are never fetched
        lo = jnp.where(window > 0, jnp.maximum(sl - window, 0), 0)
        n_pages = _div(sl + page_size - 1, page_size)
        first, end = _div(lo, T), _div(n_pages + CP - 1, CP)
        for row, value in (
            (_LEN, sl), (_LO, lo), (_PAGES, n_pages), (_FIRST, first),
            (_END, end), (_NEXT, next_live),
        ):
            slots_ref[row, j] = value
        next_live = jnp.where(sl > 0, j, next_live)
        total = total + end - first

    out_ref[...] = jnp.zeros_like(out_ref)
    # a tail chunk leaves the rows past its live pages as they were:
    # masked scores drop K's, but softmax weight 0 x stale NaN would
    # poison the accumulator through V (and V's scales), so the buffers
    # start finite and only ever hold pool rows after that
    value_buf[...] = jnp.zeros_like(value_buf)
    if quant:
        sv_buf[...] = jnp.zeros_like(sv_buf)

    nbuf = k_buf.shape[0]
    nwrite = wk_buf.shape[0] if write else 0
    if hollow:
        slots_ref[_TICK, 0] = 0
    I = items
    D = nbuf - I  # chunks in flight ahead of the trip's own
    # trip t computes buffers I * phase + (0 .. I - 1), phase = t mod PHASES
    PHASES = nbuf // I
    assert I in (1, 2) and nbuf == PHASES * I and D % I == 0

    def tick():
        """The hollow kernel's stand-in for a descriptor: one SMEM
        counter's step, so the branch that held it stays."""
        slots_ref[_TICK, 0] = slots_ref[_TICK, 0] + 1

    def pages_of(item):
        """Pool row, first page position and live pages of a chunk."""
        j, c = item
        b = jnp.minimum(base + j, batch - 1)
        return b, c * CP, jnp.minimum(slots_ref[_PAGES, j] - c * CP, CP)

    def start_chunk(item, buf: int):
        """Issue the copies of the chunk's live pages into buffer `buf`:
        per page one descriptor for K and one for V, all KV heads in
        each (and one each for an int8 pool's scale rows), on the
        semaphore of the trip the buffer belongs to.  An item past the
        work list's end has no live page and issues nothing.  Every page
        stands under its own test, a full chunk's too: all of a full
        chunk's descriptors in ONE straight-line block under one test,
        the page-by-page form as the other arm, read 2.3 to 3.1 % SLOWER
        a launch at three shapes in four forms (PERF.md section 6, PR
        56)."""
        b, page0, live = pages_of(item)
        for i in range(CP):  # static unroll
            rows = pl.ds(i * page_size, page_size)

            def page(i=i, rows=rows):
                if hollow:
                    return tick()
                # in range by construction: the kernel is compiled
                # without the DMA bounds checks, which cost more scalar
                # work than the descriptor itself
                page_id = jnp.clip(
                    page_tables_ref[b, page0 + i], 0, num_pages - 1
                )
                sem = sems.at[buf // I]

                def src(ref):
                    return (
                        ref.at[layer, :, page_id] if has_layer
                        else ref.at[:, page_id]
                    )

                for pool, buffer in pools:
                    pltpu.make_async_copy(
                        src(pool), buffer.at[buf, :, rows, :], sem
                    ).start()
                if quant:
                    for pool, buffer in (
                        (k_scale_ref, sk_buf), (v_scale_ref, sv_buf),
                    ):
                        pltpu.make_async_copy(
                            src(pool), buffer.at[buf, :, rows], sem
                        ).start()

            pl.when(i < live)(page)

    def wait_chunks(trip_items, phase, buf0):
        """Wait for what start_chunk issued for a trip's items (buffers
        `buf0` on, semaphore `phase`), all at once: the semaphore counts
        bytes, so the items' live pages together are waited for in
        power-of-two runs.  (One test for a trip of full chunks before
        the runs' tests moved no launch by more than 0.5 %, either way:
        PERF.md section 6, PR 56.)"""
        live = functools.reduce(
            operator.add, (pages_of(item)[2] for item in trip_items)
        )

        def wait_pages(n):
            if hollow:
                return tick()
            # a run longer than a chunk is whole buffers' bytes, and the
            # pages it holds past them (none where a chunk's pages are a
            # power of two; a ring of five pages is a chunk of five)
            whole, rest = divmod(n, CP) if n > CP else (0, n)
            for buffer in [buffer for _, buffer in pools] + (
                [sk_buf, sv_buf] if quant else []
            ):
                dsts = [buffer.at[pl.ds(buf0, whole)]] if whole else []
                if rest:
                    dsts.append(buffer.at[
                        buf0 + whole if whole else buf0, :,
                        pl.ds(0, rest * page_size),
                    ])
                for dst in dsts:
                    pltpu.make_async_copy(dst, dst, sems.at[phase]).wait()

        for bit in range((len(trip_items) * CP).bit_length()):
            pl.when((live & (1 << bit)) != 0)(
                functools.partial(wait_pages, 1 << bit)
            )

    def wait_write(stage):
        """Wait for the copies that left staging page `stage`."""
        for _, _, buffer, _ in writes:
            src = buffer.at[stage]
            pltpu.make_async_copy(src, src, wsems.at[stage]).wait()

    def write_token(item, buf, written):
        """The slot's new K and V row into its page, in the chunk buffer
        (the products read it there) and back to the pool."""
        if hollow:
            return tick()
        j, c = item
        b = jnp.minimum(base + j, batch - 1)
        last = slots_ref[_PAGES, j] - 1  # the page that holds the row
        off = slots_ref[_LEN, j] - 1 - last * page_size
        rows = pl.ds(
            pl.multiple_of((last - c * CP) * page_size, page_size), page_size
        )
        page_id = jnp.clip(page_tables_ref[b, last], 0, num_pages - 1)
        is_new = jax.lax.broadcasted_iota(
            jnp.int32, (page_size, 1), 0
        ) == off
        stage = jax.lax.rem(written, nwrite)
        pl.when(written >= nwrite)(functools.partial(wait_write, stage))
        for new_ref, buffer, staged, pool in writes:
            new = new_ref[j]  # [KV, hd]
            for kv in range(KV):
                page = jnp.where(
                    is_new, new[kv:kv + 1], buffer[buf, kv, rows, :]
                )
                buffer[buf, kv, rows, :] = page
                staged[stage, kv] = page
            pltpu.make_async_copy(
                staged.at[stage],
                pool.at[layer, :, page_id] if has_layer
                else pool.at[:, page_id],
                wsems.at[stage],
            ).start()

    def following(item):
        """The next item of the work list: the slot's next chunk, or the
        first chunk of the next live slot."""
        j, c = item
        same = c + 1 < slots_ref[_END, j]
        j2 = jnp.where(same, j, slots_ref[_NEXT, j])
        return j2, jnp.where(same, c + 1, slots_ref[_FIRST, j2])

    # the items the first trip computes, then those in flight behind them
    work = [(next_live, slots_ref[_FIRST, next_live])]
    for d in range(D):
        pl.when(d < total)(functools.partial(start_chunk, work[d], d))
        work.append(following(work[d]))
    while len(work) < nbuf:
        work.append(following(work[-1]))

    # operands go to the MXU in the pages' own type (int8 pages as
    # float32, as their scales are)
    mxu = jnp.float32 if quant else k_buf.dtype

    def attend(item, buf, state, keep):
        """One item's products and softmax update for all KV heads, from
        `state` (a KV head's (m, l, acc) after the trip's item before
        this one; None: what the refs hold) to the state after it, which
        goes to the refs when `keep`."""
        j, c = item
        sl, lo = slots_ref[_LEN, j], slots_ref[_LO, j]
        # the softmax state restarts at a slot boundary
        first = c == slots_ref[_FIRST, j]
        token_pos = c * T + jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)
        valid = (token_pos >= lo) & (token_pos < sl)
        after = []
        for kv in range(KV):  # static unroll: all KV heads an item
            q = q_ref[j, kv].astype(mxu)  # [G, hd]
            k = k_buf[buf, kv].astype(mxu)  # [T, hd]
            # operands in the pages' own type, float32 accumulation, the
            # query scale on the float32 scores
            scores = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # [G, T]
            if quant:
                # linearity-exact in-VMEM dequant (ops/kv_quant.py): the
                # per-token scale is constant over hd, so q . (k_q * s)
                # == (q . k_q) * s — fold it into the score row.  BEFORE
                # softcap/masking: those act on real scores.
                scores = scores * sk_buf[buf, pl.ds(kv, 1), :].astype(
                    jnp.float32
                )
            if softcap:
                scores = jnp.tanh(scores / softcap) * softcap
            scores = jnp.where(valid, scores, -1e30)

            held = state is None
            m_prev = jnp.where(  # [G, 1]
                first, -1e30, m_ref[kv, :, :1] if held else state[kv][0]
            )
            l_prev = jnp.where(
                first, 0.0, l_ref[kv, :, :1] if held else state[kv][1]
            )
            acc_prev = jnp.where(
                first, 0.0, acc_ref[kv] if held else state[kv][2]
            )
            m_new = jnp.maximum(
                m_prev, jnp.max(scores, axis=-1, keepdims=True)
            )
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(scores - m_new)  # [G, T]
            l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
            if quant:
                # V-side twin: weight the softmax row by the per-token
                # scale, dot int8 V.  l uses the UNWEIGHTED p (it
                # normalizes probabilities, not values).
                p = p * sv_buf[buf, pl.ds(kv, 1), :].astype(jnp.float32)
            # [T, hd]; the latent row's first lanes are its own value
            v = (k_buf[buf, kv, :, pl.ds(0, latent)] if latent
                 else v_buf[buf, kv])
            pv_dims = (((1,), (0,)), ((), ()))
            if mxu == jnp.bfloat16:
                # the float32 weights as two bf16 terms (<= 2^-16
                # relative): V goes to the MXU as it lies in the pages
                p_hi = p.astype(jnp.bfloat16)
                p_lo = (p - p_hi.astype(jnp.float32)).astype(jnp.bfloat16)
                pv_acc = jax.lax.dot_general(
                    p_hi, v, pv_dims, preferred_element_type=jnp.float32
                ) + jax.lax.dot_general(
                    p_lo, v, pv_dims, preferred_element_type=jnp.float32
                )
            else:
                pv_acc = jax.lax.dot_general(
                    p, v.astype(jnp.float32), pv_dims,
                    preferred_element_type=jnp.float32,
                )
            acc_new = acc_prev * alpha + pv_acc
            if keep:
                acc_ref[kv] = acc_new
                m_ref[kv] = jnp.broadcast_to(m_new, m_ref.shape[1:])
                l_ref[kv] = jnp.broadcast_to(l_new, l_ref.shape[1:])
            after.append((m_new, l_new, acc_new))
        return after

    def put_out(j, state):
        """A slot's attention, from its last chunk's state (None: what
        the refs hold)."""
        held = state is None
        for kv in range(KV):
            denom = jnp.maximum(
                l_ref[kv, :, :1] if held else state[kv][1], 1e-30
            )
            acc = acc_ref[kv] if held else state[kv][2]
            out_ref[j, kv] = (acc / denom).astype(out_ref.dtype)

    def trip(t, carry, n: int = I):
        """One loop trip: the next `n` items of the work list, whichever
        slots they belong to.  The wait, the issue of the chunks D ahead,
        the tests for a slot's end and the loop's own step are paid once;
        the items' products and softmax updates lie side by side in one
        basic block, a slot's chunks in their order."""
        work, written = carry
        own = work[:n]
        phase = jax.lax.rem(t, PHASES)
        # the trip's first item on the list, and its first buffer (no
        # `* 1`, and no `+ 0` below: one item a trip traces to the
        # program as it was, equation for equation)
        at, buf0 = (t, phase) if I == 1 else (t * I, phase * I)
        if n == I:  # the loop's trip (an odd last item issues nothing)
            ahead = jax.lax.rem(t + D // I, PHASES)
            # the issue code once per buffer: static destination addresses
            for s in range(PHASES):
                def issue(s=s):
                    for e in range(I):
                        start_chunk(work[D + e], s * I + e)

                pl.when((ahead == s) & (at + D < total))(issue)
        wait_chunks(own, phase, buf0)
        ends = [c == slots_ref[_END, j] - 1 for j, c in own]
        bufs = [buf0] + [buf0 + e for e in range(1, n)]
        if write:
            for item, buf, last_chunk in zip(own, bufs, ends):
                pl.when(last_chunk)(
                    functools.partial(write_token, item, buf, written)
                )
                written = written + last_chunk.astype(jnp.int32)
        if not hollow:
            states, state = [], None
            for e, (item, buf) in enumerate(zip(own, bufs)):
                state = attend(item, buf, state, keep=e == n - 1)
                states.append(state)
            # the trip's last item left its state in the refs
            states[-1] = None
        for e, ((j, _), last_chunk) in enumerate(zip(own, ends)):
            pl.when(last_chunk)(
                tick if hollow else functools.partial(put_out, j, states[e])
            )
        nxt = list(work[n:])
        for _ in range(n):
            nxt.append(following(nxt[-1]))
        return tuple(nxt), written

    full = total if I == 1 else _div(total, I)
    carry = jax.lax.fori_loop(0, full, trip, (tuple(work), jnp.int32(0)))
    written = carry[1]
    if I > 1:
        # an odd last item is a trip of its own, and nothing stands in
        # for a second: no copies, no products, no store
        written = jax.lax.cond(
            full * I < total, lambda: trip(full, carry, 1)[1],
            lambda: written,
        )
    # the pool is whole again before the program ends (the hollow
    # kernel staged nothing)
    for stage in range(0 if hollow else nwrite):
        pl.when(stage < written)(functools.partial(wait_write, stage))


@functools.partial(
    jax.jit,
    static_argnames=("interpret", "softcap", "scale", "items", "chunk_pages",
                     "buffers", "hollow", "name"),
)
def paged_decode_attention_pallas(
    q: jnp.ndarray,  # [B, H, hd]
    k_pages: jnp.ndarray,  # [KV, P, ps, hd] (head-major, kv_cache.py)
    v_pages: jnp.ndarray,  # or [L, KV, P, ps, hd] with `layer` given
    page_tables: jnp.ndarray,  # [B, pages_per_seq]
    seq_lens: jnp.ndarray,  # [B]; 0 => the row holds nothing: zeros out
    window=None,  # int32 scalar; >0 => attend only to the last `window`
    layer=None,  # int32 scalar: pool layer index (carry-threaded decode)
    k_new=None,  # [B, KV, hd]: the K and V of each slot's token at
    v_new=None,  # seq_lens - 1, not in the pool yet (plain pools only)
    interpret: bool = False,
    softcap: float = 0.0,
    scale=None,  # static query scale; default hd**-0.5
    items: int = 0,  # work-list items a loop trip serves; 0: _decode_sizes
    chunk_pages: int = 0,  # pages an item holds; 0: _decode_sizes
    buffers: int = 0,  # chunk buffers, trips of `items`; 0: _decode_sizes
    hollow: bool = False,  # the probe's: _decode_kernel
    name=None,  # the launch's name in a device trace
):
    """Single-token decode attention over the paged pool, [B, H, hd].

    The jnp twin ``ops.attention.paged_decode_attention`` pins the
    semantics, with two differences: a row of length 0 costs nothing and
    comes out ZERO (the twin has no such row), and q meets K in the
    pages' own float type (a float32 q over bf16 pages is rounded to
    bf16, as the model's q already is).

    With ``k_new`` / ``v_new`` the kernel also WRITES each live slot's
    new token into the pool (cast to the pool's type, at position
    ``seq_lens - 1``: what ``kv_write_tokens`` would have put there
    before the call) and attends to it, and returns ``(attention,
    k_pages, v_pages)`` with the pools updated in place (donate them).
    The page written must belong to that slot alone: ``_decode_kernel``."""
    from vgate_tpu.ops.kv_quant import is_quantized

    B, H, hd = q.shape
    has_layer = layer is not None
    quant = is_quantized(k_pages)
    write = k_new is not None
    if write and quant:
        raise ValueError(
            "the decode kernel writes plain pools only: an int8 pool's "
            "token goes through kv_write_tokens before the call"
        )
    k_data, k_scale = (
        (k_pages.data, k_pages.scale) if quant else (k_pages, None)
    )
    v_data, v_scale = (
        (v_pages.data, v_pages.scale) if quant else (v_pages, None)
    )
    KV, P, ps, _ = k_data.shape[1:] if has_layer else k_data.shape
    G = H // KV
    # an int8 pool's path is as it was: one item a trip
    CP, BS, items, nbuf = _decode_sizes(
        B, KV, G, hd, ps, page_tables.shape[1], k_data.dtype, q.dtype,
        items=1 if quant else items, chunk_pages=chunk_pages,
        buffers=buffers,
    )
    chunk_tokens = CP * ps

    if window is None:
        window_arr = jnp.zeros((1,), jnp.int32)
    else:
        window_arr = jnp.asarray(window, jnp.int32).reshape(1)
    layer_arr = (
        jnp.asarray(layer, jnp.int32).reshape(1)
        if has_layer
        else jnp.full((1,), -1, jnp.int32)
    )
    kernel = functools.partial(
        _decode_kernel,
        page_size=ps,
        chunk_pages=CP,
        items=items,
        batch=B,
        softcap=float(softcap),
        scale=float(scale) if scale is not None else hd ** -0.5,
        has_layer=has_layer,
        quant=quant,
        write=write,
        hollow=hollow,
    )
    # q is laid out [B, KV, G, hd] so a program's block covers the FULL
    # trailing (G, hd) dims — Mosaic requires trailing block dims either
    # tile-aligned (8, 128) or equal to the array dims, and G (q heads per
    # kv group, e.g. 6 or 7) is rarely tile-aligned.  A last block past B
    # reads padding and its rows are never written back.
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    block = pl.BlockSpec(
        (BS, KV, G, hd), lambda bb, *prefetch: (bb, 0, 0, 0),
        memory_space=pltpu.VMEM,
    )
    scratch = [
        pltpu.VMEM((nbuf, KV, chunk_tokens, hd), k_data.dtype),
        pltpu.VMEM((nbuf, KV, chunk_tokens, hd), v_data.dtype),
    ]
    if quant:
        # per-token bf16 scale rows ride their own chunk buffers
        scratch += [
            pltpu.VMEM((nbuf, KV, chunk_tokens), k_scale.dtype),
            pltpu.VMEM((nbuf, KV, chunk_tokens), v_scale.dtype),
        ]
    if write:
        # the new tokens' staging pages (beside the budget's buffers, as
        # the [BS, KV, hd] blocks of the tokens themselves are)
        scratch += [
            pltpu.VMEM((DECODE_WRITE_BUFFERS, KV, ps, hd), k_data.dtype),
            pltpu.VMEM((DECODE_WRITE_BUFFERS, KV, ps, hd), v_data.dtype),
        ]
    scratch += [
        pltpu.VMEM((KV, G, hd), jnp.float32),
        pltpu.VMEM((KV, G, 128), jnp.float32),
        pltpu.VMEM((KV, G, 128), jnp.float32),
        pltpu.SMEM((6 + hollow, BS + 1), jnp.int32),
        pltpu.SemaphoreType.DMA((nbuf // items,)),
    ]
    inputs = [q.reshape(B, KV, G, hd), k_data, v_data]
    in_specs = [block, any_spec, any_spec]
    out_specs = block
    out_shape = jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype)
    aliases = {}
    if quant:
        inputs += [k_scale, v_scale]
        in_specs += [any_spec, any_spec]
    if write:
        scratch.append(pltpu.SemaphoreType.DMA((DECODE_WRITE_BUFFERS,)))
        new_block = pl.BlockSpec(
            (BS, KV, hd), lambda bb, *prefetch: (bb, 0, 0),
            memory_space=pltpu.VMEM,
        )
        inputs += [k_new.astype(k_data.dtype), v_new.astype(v_data.dtype)]
        in_specs += [new_block, new_block]
        out_specs = [block, any_spec, any_spec]
        out_shape = [
            out_shape,
            jax.ShapeDtypeStruct(k_data.shape, k_data.dtype),
            jax.ShapeDtypeStruct(v_data.shape, v_data.dtype),
        ]
        # operands count from the scalar-prefetch arguments: the pools
        # are outputs 1 and 2, in place
        aliases = {5: 1, 6: 2}
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(cdiv(B, BS),),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        input_output_aliases=aliases,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024,
            # a descriptor's bounds checks cost the scalar core more than
            # the descriptor; page ids are clamped in the kernel instead
            disable_bounds_checks=True,
        ),
        name=name,
    )(page_tables, seq_lens, window_arr, layer_arr, *inputs)
    if write:
        return out[0].reshape(B, H, hd), out[1], out[2]
    return out.reshape(B, H, hd)


def swa_decode_attention_pallas(q, ring_k, ring_v, ring_tables, seq_lens,
                                window, **kw):
    """A window layer's decode attention over the slots' RINGS
    (models/hybrid.py: ``ring_tables`` sends a sequence's page ``p`` to
    its slot's ring page ``p mod R``): ``_decode_kernel`` as it stands,
    whose ``window`` fetches the live pages alone, launched under a name
    of its own so that a device trace tells a ring's launch from the
    full layer's."""
    return paged_decode_attention_pallas(
        q, ring_k, ring_v, ring_tables, seq_lens, window=window,
        name="swa_decode_attention_pallas", **kw)


@functools.partial(
    jax.jit,
    static_argnames=("interpret", "scale", "v_width", "items", "buffers",
                     "hollow", "name"),
)
def mla_decode_attention_pallas(
    q: jnp.ndarray,  # [B, H, W] absorbed queries, W the pool's row width
    pages: jnp.ndarray,  # [L, 1, P, ps, W]: the latent pool
    page_tables: jnp.ndarray,  # [B, pages_per_seq]
    seq_lens: jnp.ndarray,  # [B]; 0 => the row holds nothing: zeros out
    layer,  # int32 scalar: pool layer index
    new=None,  # [B, W]: each slot's latent row at seq_lens - 1
    *,
    v_width: int,  # leading lanes of a row that are its value
    scale: float,
    interpret: bool = False,
    items: int = 0,  # work-list items a loop trip serves; 0: _decode_sizes
    buffers: int = 0,  # chunk buffers, trips of `items`; 0: _decode_sizes
    hollow: bool = False,  # the probe's: _decode_kernel
    name: str = "mla_decode_attention_pallas",  # in a device trace
):
    """Decode attention in the ABSORBED form of multi-head latent
    attention over the latent pool, [B, H, v_width]: every query head
    meets the ONE cached row of a token as its key (all W lanes) and
    takes the row's first ``v_width`` lanes as its value.  It is
    ``_decode_kernel`` with one pool (``latent``): a program per block
    of slots, live pages only, all heads an iteration.  The jnp twin is
    ``ops.attention.mla_decode_attention``.

    With ``new`` the kernel also writes each live slot's new row into
    the pool and attends to it, and returns ``(attention, pages)`` with
    the pool updated in place (donate it): ``_decode_kernel``."""
    B, H, W = q.shape
    _, KV, P, ps, _ = pages.shape
    write = new is not None
    CP, BS, items, nbuf = _decode_sizes(
        B, KV, H, W, ps, page_tables.shape[1], pages.dtype, q.dtype, pools=1,
        items=items, buffers=buffers,
    )
    chunk_tokens = CP * ps
    kernel = functools.partial(
        _decode_kernel, page_size=ps, chunk_pages=CP, items=items, batch=B,
        softcap=0.0, scale=float(scale), has_layer=True, quant=False,
        write=write, latent=v_width, hollow=hollow,
    )
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    q_block = pl.BlockSpec(
        (BS, KV, H, W), lambda bb, *prefetch: (bb, 0, 0, 0),
        memory_space=pltpu.VMEM,
    )
    out_block = pl.BlockSpec(
        (BS, KV, H, v_width), lambda bb, *prefetch: (bb, 0, 0, 0),
        memory_space=pltpu.VMEM,
    )
    scratch = [pltpu.VMEM((nbuf, KV, chunk_tokens, W), pages.dtype)]
    if write:
        scratch.append(
            pltpu.VMEM((DECODE_WRITE_BUFFERS, KV, ps, W), pages.dtype))
    scratch += [
        pltpu.VMEM((KV, H, v_width), jnp.float32),
        pltpu.VMEM((KV, H, 128), jnp.float32),
        pltpu.VMEM((KV, H, 128), jnp.float32),
        pltpu.SMEM((6 + hollow, BS + 1), jnp.int32),
        pltpu.SemaphoreType.DMA((nbuf // items,)),
    ]
    inputs = [q.reshape(B, KV, H, W), pages]
    in_specs = [q_block, any_spec]
    out_specs = out_block
    out_shape = jax.ShapeDtypeStruct((B, KV, H, v_width), q.dtype)
    aliases = {}
    if write:
        scratch.append(pltpu.SemaphoreType.DMA((DECODE_WRITE_BUFFERS,)))
        inputs.append(new.astype(pages.dtype).reshape(B, KV, W))
        in_specs.append(pl.BlockSpec(
            (BS, KV, W), lambda bb, *prefetch: (bb, 0, 0),
            memory_space=pltpu.VMEM,
        ))
        out_specs = [out_block, any_spec]
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct(pages.shape, pages.dtype)]
        aliases = {5: 1}  # the pool, counted from the scalar prefetch
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(cdiv(B, BS),),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=scratch,
        ),
        out_shape=out_shape,
        input_output_aliases=aliases,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024,
            disable_bounds_checks=True,
        ),
        name=name,
    )(page_tables, seq_lens, jnp.zeros((1,), jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), *inputs)
    if write:
        return out[0].reshape(B, H, v_width), out[1]
    return out.reshape(B, H, v_width)


def _mt_kernel(
    # scalar prefetch
    page_tables_ref,  # [B, pages_per_seq] int32 (SMEM)
    positions0_ref,  # [B] int32 — global position of query row 0
    input_lens_ref,  # [B] int32 — real query rows this slot (<= S)
    window_ref,  # [1] int32; >0 => attend only to the last `window`
    layer_ref,  # [1] int32; pool layer index (-1 => no layer dim)
    # inputs: q_ref [1, 1, S, G, hd] VMEM block for (b, g); k/v_pages_ref
    # [KV, P, ps, hd] ANY/HBM ([L, KV, ...] when has_layer); `quant`
    # adds k/v_scale_ref [KV, P, ps] bf16 after them (int8 KV).
    # outputs: out_ref [1, 1, S, G, hd]
    # scratch: k_buf/v_buf [2, CHUNK*ps, hd] (+ sk/sv_buf
    # [2, 1, CHUNK*ps] when quant), acc [S*G, hd] f32, m/l [S*G, 128]
    # f32, DMA sems
    *refs,
    page_size: int,
    softcap: float,
    scale: float,
    has_layer: bool = False,
    quant: bool = False,
):
    """Multi-token decode attention: S candidate tokens per slot attend
    the slot's paged context in one program (the speculative-decoding
    verify step; runtime/speculative.py).  Same double-buffered per-page
    DMA as the single-token kernel — query row s sees keys up to
    ``positions0 + s`` (causal within the candidates) intersected with
    the sliding window when one applies."""
    if quant:
        (
            q_ref, k_pages_ref, v_pages_ref, k_scale_ref, v_scale_ref,
            out_ref, k_buf, v_buf, sk_buf, sv_buf, acc_ref, m_ref, l_ref,
            sems,
        ) = refs
    else:
        (
            q_ref, k_pages_ref, v_pages_ref,
            out_ref, k_buf, v_buf, acc_ref, m_ref, l_ref, sems,
        ) = refs
        k_scale_ref = v_scale_ref = sk_buf = sv_buf = None
    b = pl.program_id(0)
    g = pl.program_id(1)
    pos0 = positions0_ref[b]
    input_len = input_lens_ref[b]
    seq_len = pos0 + input_len  # keys written incl. all candidates
    n_pages = jax.lax.div(seq_len + page_size - 1, page_size)
    n_chunks = jax.lax.div(n_pages + CHUNK_PAGES - 1, CHUNK_PAGES)
    chunk_tokens = CHUNK_PAGES * page_size
    window = window_ref[0]
    # the FIRST query row (position pos0) has the lowest window start, so
    # chunks entirely below ITS window are dead for every row
    lo = jnp.where(window > 0, jnp.maximum(pos0 - window + 1, 0), 0)
    lo_chunk = jax.lax.div(lo, chunk_tokens)

    start_chunk, wait_chunk = _chunk_dma(
        page_tables_ref, k_pages_ref, v_pages_ref, k_buf, v_buf, sems,
        b, g, n_pages, page_size,
        layer=layer_ref[0] if has_layer else None,
        k_scale_ref=k_scale_ref, v_scale_ref=v_scale_ref,
        sk_buf=sk_buf, sv_buf=sv_buf,
    )

    S, G, hd = q_ref.shape[-3], q_ref.shape[-2], q_ref.shape[-1]
    q = q_ref[0, 0].astype(jnp.float32).reshape(S * G, hd) * scale
    # per-row global query position: row r = (s, g') -> pos0 + s
    row_pos = pos0 + jax.lax.broadcasted_iota(
        jnp.int32, (S * G, 1), 0
    ) // G  # [S*G, 1]

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, -1e30)
    l_ref[...] = jnp.zeros_like(l_ref)

    start_chunk(lo_chunk, jax.lax.rem(lo_chunk, 2))

    def body(c, _):
        slot = jax.lax.rem(c, 2)
        next_slot = jax.lax.rem(c + 1, 2)

        @pl.when(c + 1 < n_chunks)
        def _():
            start_chunk(c + 1, next_slot)

        wait_chunk(c, slot)

        k = jax.lax.cond(
            slot == 0, lambda: k_buf[0], lambda: k_buf[1]
        ).astype(jnp.float32)
        v = jax.lax.cond(
            slot == 0, lambda: v_buf[0], lambda: v_buf[1]
        ).astype(jnp.float32)

        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [S*G, chunk_tokens]
        if quant:
            # fold the per-token K scale into the score row (exact:
            # the scale is constant over hd) — see _kernel
            scores = scores * _scale_row(sk_buf, slot)
        if softcap:
            scores = jnp.tanh(scores / softcap) * softcap
        token_pos = c * chunk_tokens + jax.lax.broadcasted_iota(
            jnp.int32, scores.shape, 1
        )
        valid = (token_pos <= row_pos) & (token_pos < seq_len)
        valid = valid & (
            (window <= 0) | (row_pos - token_pos < window)
        )
        scores = jnp.where(valid, scores, -1e30)

        m_prev = m_ref[:, :1]
        m_cur = jnp.max(scores, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new)
        # fully-masked chunks (possible for early rows) must not pollute
        # the accumulator with exp(-1e30 - (-1e30)) = 1 weights
        p = jnp.where(valid, p, 0.0)
        l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        if quant:
            # weight the softmax row by the per-token V scale; l stays
            # unweighted (it normalizes probabilities, not values)
            p = p * _scale_row(sv_buf, slot)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)
        return 0

    jax.lax.fori_loop(lo_chunk, n_chunks, body, 0)
    denom = jnp.maximum(l_ref[:, :1], 1e-30)
    out = (acc_ref[...] / denom).astype(out_ref.dtype)
    out_ref[0, 0] = out.reshape(S, G, hd)


@functools.partial(
    jax.jit, static_argnames=("interpret", "softcap", "scale")
)
def paged_multitok_attention_pallas(
    q: jnp.ndarray,  # [B, S, H, hd] candidate-token queries
    k_pages: jnp.ndarray,  # [KV, P, ps, hd] ([L, KV, ...] with `layer`)
    v_pages: jnp.ndarray,
    page_tables: jnp.ndarray,  # [B, pages_per_seq]
    positions0: jnp.ndarray,  # [B] global position of q[:, 0]
    input_lens: jnp.ndarray,  # [B] real candidate rows (<= S)
    window=None,
    layer=None,  # int32 scalar: pool layer index (carry-threaded verify)
    interpret: bool = False,
    softcap: float = 0.0,
    scale=None,
) -> jnp.ndarray:
    """Speculative-verify attention over paged KV. Returns [B, S, H, hd].

    The candidates' KV must already be written into the pages (the
    verify layer scatters before attending).  Rows past ``input_lens``
    return unspecified values (their garbage queries attend the real
    context) — callers must mask by ``input_lens``, as the engine and
    the tests do."""
    from vgate_tpu.ops.kv_quant import is_quantized

    B, S, H, hd = q.shape
    has_layer = layer is not None
    quant = is_quantized(k_pages)
    k_data, k_scale = (
        (k_pages.data, k_pages.scale) if quant else (k_pages, None)
    )
    v_data, v_scale = (
        (v_pages.data, v_pages.scale) if quant else (v_pages, None)
    )
    KV, P, ps, _ = k_data.shape[1:] if has_layer else k_data.shape
    G = H // KV
    chunk_tokens = CHUNK_PAGES * ps

    if window is None:
        window_arr = jnp.zeros((1,), jnp.int32)
    else:
        window_arr = jnp.asarray(window, jnp.int32).reshape(1)
    layer_arr = (
        jnp.asarray(layer, jnp.int32).reshape(1)
        if has_layer
        else jnp.full((1,), -1, jnp.int32)
    )
    kernel = functools.partial(
        _mt_kernel,
        page_size=ps,
        softcap=float(softcap),
        scale=float(scale) if scale is not None else hd ** -0.5,
        has_layer=has_layer,
        quant=quant,
    )
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    scratch = [
        pltpu.VMEM((2, chunk_tokens, hd), k_data.dtype),
        pltpu.VMEM((2, chunk_tokens, hd), v_data.dtype),
    ]
    if quant:
        scratch += [
            pltpu.VMEM((2, 1, chunk_tokens), k_scale.dtype),
            pltpu.VMEM((2, 1, chunk_tokens), v_scale.dtype),
        ]
    scratch += [
        pltpu.VMEM((S * G, hd), jnp.float32),
        pltpu.VMEM((S * G, 128), jnp.float32),
        pltpu.VMEM((S * G, 128), jnp.float32),
        pltpu.SemaphoreType.DMA((2, 4 if quant else 2, CHUNK_PAGES)),
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(B, KV),
        in_specs=[
            pl.BlockSpec(
                (1, 1, S, G, hd),
                lambda b, g, *pf: (b, g, 0, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            any_spec,
            any_spec,
        ]
        + ([any_spec, any_spec] if quant else []),
        out_specs=pl.BlockSpec(
            (1, 1, S, G, hd),
            lambda b, g, *pf: (b, g, 0, 0, 0),
            memory_space=pltpu.VMEM,
        ),
        scratch_shapes=scratch,
    )
    # [B, S, H, hd] -> [B, KV, S, G, hd]: KV-major so one program's block
    # covers its group's rows contiguously
    qt = jnp.transpose(
        q.reshape(B, S, KV, G, hd), (0, 2, 1, 3, 4)
    )
    inputs = [qt, k_data, v_data]
    if quant:
        inputs += [k_scale, v_scale]
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, S, G, hd), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024,
        ),
    )(
        page_tables, positions0, input_lens, window_arr, layer_arr,
        *inputs,
    )
    return jnp.transpose(out, (0, 2, 1, 3, 4)).reshape(B, S, H, hd)
