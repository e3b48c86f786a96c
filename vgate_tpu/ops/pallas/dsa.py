"""Pallas kernels of learned sparse attention (ops/dsa.py has the
mathematics and the ``jax.numpy`` twins):

* ``dsa_index_scores_pallas``: a DECODE step's scoring pass.  A program
  a slot walks the slot's live pages of the index keys (one
  ``index_head_dim`` row a token, in the pool's second array) in chunks
  of ``INDEX_CHUNK_PAGES`` pages, two chunks in flight, and writes
  ``I(t, s)`` for every cached ``s``: ``[B, context]`` float32, ``-inf``
  past a slot's length.  A slot of length 0 costs nothing.
* ``dsa_prompt_scores_pallas`` (the same launch name): a block of a
  prompt's query rows against all of the prompt's keys, tile by tile;
  tiles above the diagonal are ``-inf`` and cost nothing.
* ``dsa_decode_attention_pallas``: a decode step's attention over the
  picked rows, which the kernel fetches itself from the pool BY PAIRS
  of token rows (``[L, 1, P, ps / 2, 2, W]``: one row of a page is no
  descriptor Mosaic takes, a pair is), a descriptor a pick, the next
  chunk's in flight; the picked row of each pair kept by the pick's
  place in the slot's list (ops/dsa.py ``order_picks``).
* ``dsa_kv_decode_attention_pallas`` (the same kernel and launch name):
  GQA attention over picked TOKENS of a pool whose pairs are a token's K
  over its V (``[L, 1, P, ps, 2, KV x hd]``, ``ModelSpec.kv_rows``): a
  descriptor a pick, both rows of the pair read.
* ``dsa_write_pages_pallas``: a prompt's rows into either pool of pairs,
  a page a descriptor.
* ``dsa_prefill_attention_pallas``: the flash prompt kernel with the
  selection as a mask tile beside each key block, made a bias once for
  the block of heads a program takes, under a name of its own.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from vgate_tpu.ops.dsa import NEG_INF
from vgate_tpu.utils.math import cdiv

# pages of index keys a loop trip of the decode scoring pass takes: 512
# tokens at a page of 32, one [heads, 512] product a trip
INDEX_CHUNK_PAGES = 16


def _index_decode_kernel(
    # scalar prefetch (SMEM)
    tables_ref,  # [B, n_chunks * CP] page ids
    lens_ref,  # [B]
    layer_ref,  # [1]
    # inputs
    q_ref,  # [1, Hi, d] VMEM: the slot's index queries
    w_ref,  # [1, Hi, 1] float32 VMEM: the heads' weights
    keys_hbm,  # [Li, 1, P, ps, d]: the index keys' pool
    # output
    out_ref,  # [1, n_chunks, CT] float32
    # scratch
    buf,  # [2, CT, d]
    sem,  # DMA [2]
    *, page_size: int, chunk_pages: int,
):
    b = pl.program_id(0)
    length = lens_ref[b]
    layer = layer_ref[0]
    ps, CP = page_size, chunk_pages
    CT = CP * ps
    n = (length + CT - 1) // CT

    def copies(c, slot):
        return [
            pltpu.make_async_copy(
                keys_hbm.at[layer, 0, tables_ref[b, c * CP + j]],
                buf.at[slot, pl.ds(j * ps, ps)], sem.at[slot])
            for j in range(CP)
        ]

    out_ref[...] = jnp.full(out_ref.shape, NEG_INF, out_ref.dtype)

    @pl.when(n > 0)
    def _():
        for cp in copies(0, 0):
            cp.start()

    def trip(c, carry):
        slot = c % 2

        @pl.when(c + 1 < n)
        def _():
            for cp in copies(c + 1, 1 - slot):
                cp.start()

        for cp in copies(c, slot):
            cp.wait()
        s = jax.lax.dot_general(
            q_ref[0], buf[slot], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # [Hi, CT]
        row = jnp.sum(jnp.maximum(s, 0.0) * w_ref[0], axis=0,
                      keepdims=True)  # [1, CT]
        pos = c * CT + jax.lax.broadcasted_iota(jnp.int32, row.shape, 1)
        out_ref[0, pl.ds(c, 1), :] = jnp.where(pos < length, row, NEG_INF)
        return carry

    jax.lax.fori_loop(0, n, trip, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def dsa_index_scores_pallas(
    qi: jnp.ndarray,  # [B, Hi, d] the step's index queries
    w: jnp.ndarray,  # [B, Hi] float32: the heads' weights, scales in
    keys: jnp.ndarray,  # [Li, 1, P, ps, d]: the index keys' pool
    page_tables: jnp.ndarray,  # [B, pages_per_seq]
    seq_lens: jnp.ndarray,  # [B]; 0 => nothing read, a row of -inf
    layer,  # int32 scalar: the picking layer's index in ``keys``
    interpret: bool = False,
):
    """A decode step's index scores, [B, pages_per_seq x ps] float32
    (``-inf`` at and past ``seq_lens``): ops/dsa.py ``index_scores`` over
    each slot's live pages."""
    B, Hi, d = qi.shape
    ps = keys.shape[-2]
    n_pages = page_tables.shape[1]
    CP = min(INDEX_CHUNK_PAGES, n_pages)
    n_chunks = cdiv(n_pages, CP)
    tables = jnp.pad(page_tables.astype(jnp.int32),
                     ((0, 0), (0, n_chunks * CP - n_pages)))
    CT = CP * ps
    kernel = functools.partial(
        _index_decode_kernel, page_size=ps, chunk_pages=CP)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, Hi, d), lambda b, *pf: (b, 0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, Hi, 1), lambda b, *pf: (b, 0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec(
                (1, n_chunks, CT), lambda b, *pf: (b, 0, 0),
                memory_space=pltpu.VMEM),
            scratch_shapes=[
                pltpu.VMEM((2, CT, d), keys.dtype),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, n_chunks, CT), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024,
            disable_bounds_checks=True,
        ),
        name="dsa_index_scores_pallas",
    )(tables, seq_lens.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1),
      qi.astype(keys.dtype), w.astype(jnp.float32)[..., None], keys)
    return out.reshape(B, n_chunks * CT)[:, :n_pages * ps]


def _index_prompt_kernel(
    start_ref,  # [1] SMEM: the global position of the block's row 0
    q_ref,  # [bq, Hi * d]
    w_ref,  # [bq, Hi] float32
    k_ref,  # [bk, d]
    out_ref,  # [bq, bk] float32
    *, heads: int, dim: int, block_q: int, block_k: int,
):
    qi, ki = pl.program_id(0), pl.program_id(1)
    q_start = start_ref[0] + qi * block_q
    k_start = ki * block_k
    live = k_start <= q_start + block_q - 1

    @pl.when(live)
    def _():
        k = k_ref[...]
        acc = jnp.zeros((block_q, block_k), jnp.float32)
        for j in range(heads):
            s = jax.lax.dot_general(
                q_ref[:, j * dim:(j + 1) * dim], k,
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            acc = acc + jnp.maximum(s, 0.0) * w_ref[:, j:j + 1]
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, acc.shape, 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, acc.shape, 1)
        out_ref[...] = jnp.where(k_pos <= q_pos, acc, NEG_INF)

    @pl.when(jnp.logical_not(live))
    def _():
        out_ref[...] = jnp.full(out_ref.shape, NEG_INF, out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_q", "block_k", "interpret"))
def dsa_prompt_scores_pallas(
    qi: jnp.ndarray,  # [R, Hi, d]: a block of a prompt's index queries
    w: jnp.ndarray,  # [R, Hi] float32
    keys: jnp.ndarray,  # [T, d]: the prompt's index keys, from position 0
    start,  # int32 scalar: the position of the block's first row
    block_q: int = 256,
    block_k: int = 512,
    interpret: bool = False,
):
    """A prompt's index scores for ``R`` query rows that start at
    ``start``, [R, T] float32, ``-inf`` above the diagonal."""
    R, Hi, d = qi.shape
    T = keys.shape[0]
    block_q, block_k = min(block_q, R), min(block_k, T)
    if R % block_q or T % block_k:
        raise ValueError(f"rows {R} / keys {T} must divide {block_q} / "
                         f"{block_k}")
    kernel = functools.partial(
        _index_prompt_kernel, heads=Hi, dim=d, block_q=block_q,
        block_k=block_k)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(R // block_q, T // block_k),
            in_specs=[
                pl.BlockSpec((block_q, Hi * d), lambda i, j, *pf: (i, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((block_q, Hi), lambda i, j, *pf: (i, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((block_k, d), lambda i, j, *pf: (j, 0),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec(
                (block_q, block_k), lambda i, j, *pf: (i, j),
                memory_space=pltpu.VMEM),
        ),
        out_shape=jax.ShapeDtypeStruct((R, T), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024,
        ),
        name="dsa_index_scores_pallas",
    )(jnp.asarray(start, jnp.int32).reshape(1),
      qi.reshape(R, Hi * d).astype(keys.dtype), w.astype(jnp.float32),
      keys)


# picks a loop trip of the decode attention fetches and attends to: one
# descriptor a pick, the next trip's in flight while this one's multiply
# (us a layer of 48 x 2,048 picks on the v5e, PERF.md section 6, PR 41:
# 1,299 at 256, 1,231 at 512, 1,193 at 1,024, the loop of 8 below), and
# descriptors a trip of the loop that issues them (1,242 at 8, 1,160 at
# 16, 1,128 at 32, 1,113 at 64; the issue alone is 783 of them)
FETCH_CHUNK = 512
FETCH_UNROLL = 32


def fetch_chunk(k: int, chunk: int = FETCH_CHUNK) -> int:
    """Picks a trip of the decode attention over ``k`` picks a slot."""
    return min(chunk, -(-k // 8) * 8)


def _fetch_decode_kernel(
    # scalar prefetch (SMEM)
    pairs_ref,  # [B, n_chunks * K]: each pick's pair of rows in the pool
    nsel_ref,  # [B] picks that are real (the first of a slot's)
    neven_ref,  # [B] of those, how many sit in their pair's FIRST row
    base_ref,  # [B] chunks of the slots before this one
    next_ref,  # [B] the next slot with a pick (B: none)
    # inputs
    q_ref,  # [1, H, W] VMEM
    pool,  # [N, 2, W] HBM: the latent rows, a pair of tokens a block
    # output
    out_ref,  # [1, H, v_width]
    # scratch
    buf,  # [2, K, 2, W]
    sem,  # DMA [2]
    *, chunk: int, batch: int, v_width: int, scale: float, form: str,
):
    """One slot's attention over its picked rows: the pair that holds
    each pick comes HBM -> VMEM by a descriptor of its own, ``chunk``
    picks a trip, the next trip's descriptors issued before this trip's
    products (a slot's last trip issues the next slot's first), and the
    picked row of each pair is kept by the pick's place in the slot's
    list: the first ``n_even`` picks sit in their pair's first row, the
    others in the second.  The partner row meets no score."""
    b = pl.program_id(0)
    K = chunk
    n_sel = nsel_ref[b]
    n_even = neven_ref[b]
    base = base_ref[b]
    nxt = next_ref[b]
    n = (n_sel + K - 1) // K
    unroll = math.gcd(K, FETCH_UNROLL)

    def issue(slot_b, c, at):
        def some(g, carry):
            for j in range(unroll):
                i = g * unroll + j
                pltpu.make_async_copy(
                    pool.at[pairs_ref[slot_b, c * K + i]], buf.at[at, i],
                    sem.at[at]).start()
            return carry

        jax.lax.fori_loop(0, K // unroll, some, 0)

    def wait(at):
        # every descriptor of the chunk signals the one semaphore: one
        # wait for the chunk's bytes
        pltpu.make_async_copy(buf.at[at], buf.at[at], sem.at[at]).wait()

    # the first slot with a pick starts its own first chunk; every other
    # finds it started by the slot before
    @pl.when((n > 0) & (base == 0))
    def _():
        issue(b, 0, 0)

    q = q_ref[0]  # [H, W]
    H, W = q.shape

    def attend(carry, at, first):
        """The softmax state after the chunk in ``buf[at]``, whose first
        pick is the slot's ``first``-th."""
        m_prev, l_prev, acc_prev = carry
        pairs = buf[at]  # [K, 2, W]
        # (the latent forms': which row of its pair a pick is)
        place = first + jax.lax.broadcasted_iota(jnp.int32, (K, 1), 0)
        values = None  # where they are not the rows' own first lanes
        if form == "kv" and buf.dtype == jnp.bfloat16:
            # a pair is ONE token's K over its V: both halves of a word
            w = pltpu.bitcast(pairs.reshape(2 * K, W), jnp.uint32)
            half = lambda u: pltpu.bitcast(u, jnp.float32).astype(buf.dtype)
            rows, values = half(w << 16), half(w & jnp.uint32(0xFFFF0000))
        elif form == "kv":
            rows, values = pairs[:, 0, :], pairs[:, 1, :]
        elif form == "words":
            # a pair's two bf16 rows lie in one 32-bit word a lane, the
            # first row in the low half: either half, moved to the top,
            # is that row's value as a float32
            w = pltpu.bitcast(pairs.reshape(2 * K, W), jnp.uint32)
            w = jnp.where(place < n_even, w << 16,
                          w & jnp.uint32(0xFFFF0000))
            rows = pltpu.bitcast(w, jnp.float32).astype(buf.dtype)
        else:
            rows = jnp.where(place < n_even, pairs[:, 0, :], pairs[:, 1, :])
        live = first + jax.lax.broadcasted_iota(
            jnp.int32, (1, K), 1) < n_sel
        s = jax.lax.dot_general(
            q, rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [H, K]
        s = jnp.where(live, s, -1e30)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        v = rows[:, :v_width] if values is None else values
        dims = (((1,), (0,)), ((), ()))
        if rows.dtype == jnp.bfloat16:
            # the float32 weights as two bf16 terms, as _decode_kernel
            p_hi = p.astype(jnp.bfloat16)
            p_lo = (p - p_hi.astype(jnp.float32)).astype(jnp.bfloat16)
            pv = jax.lax.dot_general(
                p_hi, v, dims, preferred_element_type=jnp.float32
            ) + jax.lax.dot_general(
                p_lo, v, dims, preferred_element_type=jnp.float32)
        else:
            pv = jax.lax.dot_general(
                p, v.astype(jnp.float32), dims,
                preferred_element_type=jnp.float32)
        return m_new, l_new, acc_prev * alpha + pv

    def trip(c, carry):
        at = (base + c) % 2
        more = c + 1 < n

        @pl.when(more | (nxt < batch))
        def _():
            issue(jnp.where(more, b, nxt), jnp.where(more, c + 1, 0),
                  1 - at)

        wait(at)
        if form == "hollow":  # the probe's: the fetch alone
            return carry
        return attend(carry, at, c * K)

    m, l, acc = jax.lax.fori_loop(0, n, trip, (
        jnp.full((H, 1), -1e30, jnp.float32),
        jnp.zeros((H, 1), jnp.float32),
        jnp.zeros((H, v_width), jnp.float32)))
    out_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("v_width", "scale", "interpret", "chunk",
                              "form"))
def dsa_decode_attention_pallas(
    q: jnp.ndarray,  # [B, H, W] absorbed queries
    pool: jnp.ndarray,  # [L, 1, P, ps / 2, 2, W]: the latent pool, by pairs
    rows: jnp.ndarray,  # [B, k] the picks' places in a layer (order_picks)
    n_sel: jnp.ndarray,  # [B] of them real; 0 => nothing fetched: zeros out
    layer,  # int32 scalar: the pool's layer
    *, v_width: int, scale: float, interpret: bool = False,
    chunk: int = FETCH_CHUNK, form: str = "",
):
    """Absorbed latent decode attention over the SELECTED rows, fetched
    by the kernel itself: [B, H, v_width].  ``rows`` as
    ops/dsa.py ``order_picks`` leaves them: a slot's real picks first,
    those at even places (the first rows of their pairs) before the
    others, so the kernel tells a pick's row of its pair by the pick's
    place in the list.  The jnp twin is
    ``ops.dsa.dsa_decode_attention(use_pallas=False)``."""
    L, _, P, half, _, W = pool.shape
    if P * half >= 1 << 28:  # order_picks' sort key holds 29 bits of it
        raise ValueError(f"{P} pages of {2 * half} tokens in a layer")
    k = rows.shape[1]
    form = form or ("words" if pool.dtype == jnp.bfloat16 else "select")
    n_sel = n_sel.astype(jnp.int32)
    real = jnp.arange(k, dtype=jnp.int32)[None, :] < n_sel[:, None]
    n_even = jnp.sum(real & (rows % 2 == 0), axis=1, dtype=jnp.int32)
    pairs = jnp.asarray(layer, jnp.int32) * (P * half) + rows // 2
    return _fetch_attend(
        q, pool.reshape(L * P * half, 2, W), pairs, n_sel, n_even,
        v_width=v_width, scale=scale, chunk=chunk, form=form,
        interpret=interpret)


def _fetch_attend(q, pool, pairs, n_sel, n_even, *, v_width: int,
                  scale: float, chunk: int, form: str, interpret: bool):
    """The launch both decode attentions under a selection share: q [B,
    H, W] against the pairs of rows ``pairs`` [B, k] of pool [N, 2, W],
    a slot's first ``n_sel`` real."""
    B, H, W = q.shape
    k = pairs.shape[1]
    K = fetch_chunk(k, chunk)
    n_chunks = cdiv(k, K)
    pairs = jnp.pad(pairs, ((0, 0), (0, n_chunks * K - k)))
    chunks = (n_sel + K - 1) // K
    base = jnp.cumsum(chunks) - chunks
    # the next slot with a pick: a running minimum from the right
    slot = jnp.where(chunks > 0, jnp.arange(B, dtype=jnp.int32), B)
    after = jnp.concatenate([slot[1:], jnp.full((1,), B, jnp.int32)])
    nxt = jax.lax.cummin(after, reverse=True)
    kernel = functools.partial(
        _fetch_decode_kernel, chunk=K, batch=B, v_width=v_width,
        scale=float(scale), form=form)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, H, W), lambda b, *pf: (b, 0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec(
                (1, H, v_width), lambda b, *pf: (b, 0, 0),
                memory_space=pltpu.VMEM),
            scratch_shapes=[
                pltpu.VMEM((2, K, 2, W), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, v_width), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024,
            disable_bounds_checks=True,
        ),
        name="dsa_decode_attention_pallas",
    )(pairs, n_sel, n_even, base.astype(jnp.int32), nxt,
      q.astype(pool.dtype), pool)


@functools.partial(
    jax.jit, static_argnames=("scale", "interpret", "chunk"))
def dsa_kv_decode_attention_pallas(
    q: jnp.ndarray,  # [B, H, hd]
    pool: jnp.ndarray,  # [L, 1, P, ps, 2, KV x hd]: a token's K over its V
    rows: jnp.ndarray,  # [B, k] the picks' places in a layer (order_picks)
    n_sel: jnp.ndarray,  # [B] of them real; 0 => nothing fetched: zeros out
    layer,  # int32 scalar: the pool's layer
    *, scale: float, interpret: bool = False, chunk: int = FETCH_CHUNK,
):
    """GQA decode attention over the SELECTED tokens, [B, H, hd]: the
    same launch as the latent form's, a descriptor a pick, and a pick's
    pair of rows is the token's K and its V (2,048 B at 4 heads of 128
    in bf16, every byte of it read).  The KV heads lie side by side in a
    row, so a query head rides in its group's lanes of a row-wide query
    (zeros elsewhere: ONE product for all heads, its weights the chunk's
    K either way) and takes its group's lanes of the row-wide result.
    The jnp twin is ``ops.dsa.kv_rows_decode_attention(use_pallas=
    False)``."""
    B, H, hd = q.shape
    L, _, P, ps, _, W = pool.shape
    KV = W // hd
    own = (jnp.arange(H)[:, None] // (H // KV) == jnp.arange(KV)[None, :])
    wide = (q[:, :, None, :] * own[None, :, :, None].astype(q.dtype)
            ).reshape(B, H, W)
    pairs = jnp.asarray(layer, jnp.int32) * (P * ps) + rows
    n_sel = n_sel.astype(jnp.int32)
    out = _fetch_attend(
        wide, pool.reshape(L * P * ps, 2, W), pairs, n_sel,
        jnp.zeros_like(n_sel), v_width=W, scale=scale, chunk=chunk,
        form="kv", interpret=interpret)
    out = out.reshape(B, H, KV, hd)
    return jnp.sum(out * own[None, :, :, None].astype(out.dtype), axis=2)


# page copies a group of the prompt's page writer has in flight
WRITE_GROUP = 16


def _write_pages_kernel(tables_ref, layer_ref, value, pool_in, pool_out,
                        sem, *, pages: int):
    """Each of ``pages`` blocks of ``value`` to its page of the pool's
    layer, HBM -> HBM, a descriptor a page."""
    del pool_in  # the same array as pool_out
    layer = layer_ref[0]

    def copy(i):
        return pltpu.make_async_copy(
            value.at[i], pool_out.at[layer, 0, tables_ref[i]], sem.at[0])

    def group(first, count):
        for j in range(count):
            copy(first + j).start()
        for j in range(count):
            copy(first + j).wait()

    whole = pages // WRITE_GROUP

    def some(g, carry):
        group(g * WRITE_GROUP, WRITE_GROUP)
        return carry

    jax.lax.fori_loop(0, whole, some, 0)
    group(whole * WRITE_GROUP, pages % WRITE_GROUP)


@functools.partial(jax.jit, static_argnames=("interpret",),
                   donate_argnames=("pool",))
def dsa_write_pages_pallas(pool, page_tables, value, layer,
                           interpret: bool = False):
    """A prompt's latent rows into a pool BY PAIRS, whole pages: pool
    [L, 1, P, ps / 2, 2, W], page_tables [..., n], value [..., n, 1, ps,
    W] -> the pool, updated in place (donate it); or a prompt's K over V
    into a pool of such pairs, [L, 1, P, ps, 2, W] with value [..., n,
    ps, 2, W]: a page is the pool's trailing three dimensions either way.  XLA's scatter into
    such a pool re-lays or flattens the WHOLE pool first, whichever
    window it is given (tests/test_tpu_aot.py); a page is one leading
    index and one copy.  Pages named twice (the trash page of a prompt's
    padding) hold either writer's rows."""
    tables = page_tables.reshape(-1).astype(jnp.int32)
    n = tables.shape[0]
    kernel = functools.partial(_write_pages_kernel, pages=n)
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[any_spec, any_spec],
            out_specs=any_spec,
            scratch_shapes=[pltpu.SemaphoreType.DMA((1,))],
        ),
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        input_output_aliases={3: 0},  # the pool, after the prefetch
        interpret=interpret,
        name="dsa_write_pages_pallas",
    )(tables, jnp.asarray(layer, jnp.int32).reshape(1),
      value.astype(pool.dtype).reshape((n,) + pool.shape[3:]), pool)


def dsa_prefill_attention_pallas(q, k, v, seq_lens, mask, *, scale: float,
                                 block_q: int = 256, block_k: int = 256,
                                 interpret: bool = False):
    """A prompt's attention under the selection: q [B, S, H, hd], k / v
    [B, S, H, .] expanded from the prompt's own latent rows, mask [B, S,
    S] int8 (nonzero = the query attends to the key; nothing above the
    diagonal).  The flash kernel with a mask tile beside each key block
    (a float32 bias once for the heads that share it:
    ops/pallas/flash_prefill.py ``head_block``), under a name of its
    own; blocks of padding rows (past ``seq_lens``) are left out and
    come out zero: a prompt pass reads no such row."""
    from vgate_tpu.ops.pallas.flash_prefill import (
        flash_prefill_attention_pallas,
    )

    return flash_prefill_attention_pallas(
        q, k, v, seq_lens, block_q=block_q, block_k=block_k, scale=scale,
        mask=mask, skip_padding=True, interpret=interpret,
        name="dsa_prefill_attention_pallas")
