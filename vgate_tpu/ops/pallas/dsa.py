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
* ``dsa_decode_attention_pallas``: the dense latent decode kernel
  (``_decode_kernel``) over the rows a gather has put in order, under a
  name of its own.
* ``dsa_prefill_attention_pallas``: the flash prompt kernel with the
  selection as a mask tile beside each key block, under a name of its
  own.
"""

from __future__ import annotations

import functools

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


def dsa_decode_attention_pallas(q, rows, tables, n_sel, *, v_width: int,
                                scale: float, interpret: bool = False):
    """Absorbed latent decode attention over gathered rows (``rows`` [1,
    1, B x n, ps, W], a slot's pick in its ``n`` pages in order, the
    first ``n_sel`` rows real): ``_decode_kernel`` as the dense latent
    layer launches it, under a name of its own so that a device trace
    tells a layer under a selection from one without."""
    from vgate_tpu.ops.pallas.paged_attention import (
        mla_decode_attention_pallas,
    )

    return mla_decode_attention_pallas(
        q, rows, tables, n_sel, 0, v_width=v_width, scale=scale,
        interpret=interpret, name="dsa_decode_attention_pallas")


def dsa_prefill_attention_pallas(q, k, v, seq_lens, mask, *, scale: float,
                                 block_q: int = 256, block_k: int = 256,
                                 interpret: bool = False):
    """A prompt's attention under the selection: q [B, S, H, hd], k / v
    [B, S, H, .] expanded from the prompt's own latent rows, mask [B, S,
    S] int8 (nonzero = the query attends to the key; nothing above the
    diagonal).  The flash kernel with a mask tile beside each key block,
    under a name of its own; blocks of padding rows (past ``seq_lens``)
    are left out and come out zero: a prompt pass reads no such row."""
    from vgate_tpu.ops.pallas.flash_prefill import (
        flash_prefill_attention_pallas,
    )

    return flash_prefill_attention_pallas(
        q, k, v, seq_lens, block_q=block_q, block_k=block_k, scale=scale,
        mask=mask, skip_padding=True, interpret=interpret,
        name="dsa_prefill_attention_pallas")
