"""Pallas paged-attention decode kernel.

The TPU-native replacement for the CUDA paged-attention the reference gets
opaquely through vLLM (SURVEY.md section 2.1; technique family: "Ragged
Paged Attention", PAPERS.md).  Semantics are pinned by the jnp twin
``vgate_tpu.ops.attention.paged_decode_attention`` (kernel tests compare the
two); the kernel's advantage is the memory path:

* the jnp twin gathers every slot's full ``pages_per_seq`` window into a
  contiguous HBM buffer (write + re-read), touching ``ctx_max`` tokens even
  for short sequences;
* this kernel DMAs **only the live pages** of each sequence directly from the
  HBM page pool into VMEM, double-buffered in chunks of
  ``CHUNK_PAGES`` pages, and runs an online-softmax
  accumulation entirely in VMEM — no gathered copy, no dead-token traffic.

Grid: one program per (slot, kv_head); each program serves the G = H/KV
query heads of that group (GQA).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from vgate_tpu.utils.math import cdiv

# pages DMA'd per double-buffer slot (VGT_CHUNK_PAGES sweeps on-device:
# wider chunks amortize per-page DMA issue overhead for long contexts)
CHUNK_PAGES = int(os.environ.get("VGT_CHUNK_PAGES", 8))
if CHUNK_PAGES <= 0:
    raise ValueError(
        f"VGT_CHUNK_PAGES must be a positive integer, got {CHUNK_PAGES}"
    )



def _chunk_dma(
    page_tables_ref, k_pages_ref, v_pages_ref, k_buf, v_buf, sems,
    b, g, n_pages, page_size, layer=None,
    k_scale_ref=None, v_scale_ref=None, sk_buf=None, sv_buf=None,
):
    """Shared double-buffered page-DMA machinery for the paged kernels.

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


def _kernel(
    # scalar prefetch
    page_tables_ref,  # [B, pages_per_seq] int32 (SMEM)
    seq_lens_ref,  # [B] int32 (SMEM)
    window_ref,  # [1] int32 (SMEM); >0 => attend only to the last `window`
    layer_ref,  # [1] int32 (SMEM); pool layer index (-1 => no layer dim)
    # inputs: q_ref [1, 1, G, hd] VMEM block for (b, g); k/v_pages_ref
    # [KV, P, ps, hd] in ANY/HBM (head-major: one page of one head is a
    # contiguous (ps, hd) DMA tile), or [L, KV, P, ps, hd] when
    # has_layer (carry decode).  `quant` (int8 KV) adds k/v_scale_ref
    # [KV, P, ps] bf16 pools after them.
    # outputs: out_ref [1, 1, G, hd]
    # scratch: k_buf/v_buf [2, CHUNK*ps, hd] VMEM (+ sk/sv_buf
    # [2, 1, CHUNK*ps] when quant), acc [G, hd] f32, m/l [G, 128] f32
    # running max/denom (col-broadcast), DMA sems [2, 2 or 4, CHUNK]
    *refs,
    page_size: int,
    softcap: float,
    scale: float,
    has_layer: bool = False,
    quant: bool = False,
):
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
    seq_len = seq_lens_ref[b]
    n_pages = jax.lax.div(seq_len + page_size - 1, page_size)
    n_chunks = jax.lax.div(n_pages + CHUNK_PAGES - 1, CHUNK_PAGES)
    chunk_tokens = CHUNK_PAGES * page_size
    # Sliding window: tokens below `lo` contribute nothing, so whole chunks
    # below the window start are never DMA'd at all — the kernel's traffic
    # is O(window), not O(context), for local-attention layers.
    window = window_ref[0]
    lo = jnp.where(
        window > 0, jnp.maximum(seq_len - window, 0), 0
    )
    lo_chunk = jax.lax.div(lo, chunk_tokens)

    start_chunk, wait_chunk = _chunk_dma(
        page_tables_ref, k_pages_ref, v_pages_ref, k_buf, v_buf, sems,
        b, g, n_pages, page_size,
        layer=layer_ref[0] if has_layer else None,
        k_scale_ref=k_scale_ref, v_scale_ref=v_scale_ref,
        sk_buf=sk_buf, sv_buf=sv_buf,
    )

    q = q_ref[0, 0].astype(jnp.float32) * scale  # [G, hd]

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
        ).astype(jnp.float32)  # [chunk_tokens, hd]
        v = jax.lax.cond(
            slot == 0, lambda: v_buf[0], lambda: v_buf[1]
        ).astype(jnp.float32)

        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [G, chunk_tokens]
        if quant:
            # linearity-exact in-VMEM dequant (ops/kv_quant.py): the
            # per-token scale is constant over hd, so q . (k_q * s) ==
            # (q . k_q) * s — fold it into the score row instead of
            # materializing a dequantized K tile.  Applied BEFORE
            # softcap/masking: those act on real scores.
            scores = scores * _scale_row(sk_buf, slot)
        if softcap:
            scores = jnp.tanh(scores / softcap) * softcap
        token_pos = c * chunk_tokens + jax.lax.broadcasted_iota(
            jnp.int32, scores.shape, 1
        )
        valid = (token_pos >= lo) & (token_pos < seq_len)
        scores = jnp.where(valid, scores, -1e30)

        m_prev = m_ref[:, :1]  # [G, 1]
        m_cur = jnp.max(scores, axis=-1, keepdims=True)  # [G, 1]
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)  # [G, 1]
        p = jnp.exp(scores - m_new)  # [G, chunk_tokens]
        l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        if quant:
            # V-side twin: sum_t p_t * (v_q_t * s_t) == sum_t
            # (p_t * s_t) . v_q_t — weight the softmax row, dot int8 V.
            # The denominator l uses the UNWEIGHTED p (it normalizes
            # probabilities, not values).
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
    out_ref[0, 0] = (acc_ref[...] / denom).astype(out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("interpret", "softcap", "scale")
)
def paged_decode_attention_pallas(
    q: jnp.ndarray,  # [B, H, hd]
    k_pages: jnp.ndarray,  # [KV, P, ps, hd] (head-major, kv_cache.py)
    v_pages: jnp.ndarray,  # or [L, KV, P, ps, hd] with `layer` given
    page_tables: jnp.ndarray,  # [B, pages_per_seq]
    seq_lens: jnp.ndarray,  # [B]
    window=None,  # int32 scalar; >0 => attend only to the last `window`
    layer=None,  # int32 scalar: pool layer index (carry-threaded decode)
    interpret: bool = False,
    softcap: float = 0.0,
    scale=None,  # static query scale; default hd**-0.5
) -> jnp.ndarray:
    from vgate_tpu.ops.kv_quant import is_quantized

    B, H, hd = q.shape
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
        _kernel,
        page_size=ps,
        softcap=float(softcap),
        scale=float(scale) if scale is not None else hd ** -0.5,
        has_layer=has_layer,
        quant=quant,
    )
    # q is laid out [B, KV, G, hd] so each program's block covers the FULL
    # trailing (G, hd) dims — Mosaic requires trailing block dims either
    # tile-aligned (8, 128) or equal to the array dims, and G (q heads per
    # kv group, e.g. 6 or 7) is rarely tile-aligned.
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    scratch = [
        pltpu.VMEM((2, chunk_tokens, hd), k_data.dtype),
        pltpu.VMEM((2, chunk_tokens, hd), v_data.dtype),
    ]
    if quant:
        # per-token bf16 scale rows ride their own chunk buffers; the
        # extra sem pair (indices 2/3) covers their DMAs
        scratch += [
            pltpu.VMEM((2, 1, chunk_tokens), k_scale.dtype),
            pltpu.VMEM((2, 1, chunk_tokens), v_scale.dtype),
        ]
    scratch += [
        pltpu.VMEM((G, hd), jnp.float32),
        pltpu.VMEM((G, 128), jnp.float32),
        pltpu.VMEM((G, 128), jnp.float32),
        pltpu.SemaphoreType.DMA((2, 4 if quant else 2, CHUNK_PAGES)),
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, KV),
        in_specs=[
            pl.BlockSpec(
                (1, 1, G, hd), lambda b, g, *prefetch: (b, g, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            any_spec,
            any_spec,
        ]
        + ([any_spec, any_spec] if quant else []),
        out_specs=pl.BlockSpec(
            (1, 1, G, hd), lambda b, g, *prefetch: (b, g, 0, 0),
            memory_space=pltpu.VMEM,
        ),
        scratch_shapes=scratch,
    )
    inputs = [q.reshape(B, KV, G, hd), k_data, v_data]
    if quant:
        inputs += [k_scale, v_scale]
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024,
        ),
    )(page_tables, seq_lens, window_arr, layer_arr, *inputs)
    return out.reshape(B, H, hd)


def _blocked_kernel(
    # scalar prefetch
    page_tables_ref,  # [B, pages_per_seq] int32 (SMEM)
    seq_lens_ref,  # [B] int32 (SMEM)
    window_ref,  # [1] int32 (SMEM)
    layer_ref,  # [1] int32 (SMEM); -1 => no layer dim
    # inputs
    q_ref,  # [1, 1, BS, G, hd] VMEM block for (bb, g)
    k_pages_ref,  # [KV, P, ps, hd] ANY/HBM ([L, KV, ...] when has_layer)
    v_pages_ref,
    # output
    out_ref,  # [1, 1, BS, G, hd]
    # scratch
    k_buf,  # [2, BS, CHUNK*ps, hd] VMEM
    v_buf,
    acc_ref,  # [BS*G, hd] f32
    m_ref,  # [BS*G, 128] f32
    l_ref,  # [BS*G, 128] f32
    sems,  # DMA semaphores [2, 2, BS, CHUNK]
    *,
    page_size: int,
    softcap: float,
    scale: float,
    block_slots: int,
    has_layer: bool = False,
):
    """Multi-slot decode attention: ``block_slots`` sequences per program.

    The per-(slot, kv_head) kernel above runs B*KV tiny programs per
    layer (7,168 grid steps per decode step at B=128, KV=2, 28 layers);
    per-program iteration overhead is a prime suspect for the measured
    gap to the HBM roofline (RESULTS_r3.md decision tree item 4).  This
    variant serves ``BS`` slots per program — grid B/BS x KV — with the
    same double-buffered live-page DMA per slot and a static unroll of
    the per-slot 2D dots (Mosaic-safe; no batched dot_general).  The
    fori_loop runs to the block's MAX chunk count; shorter slots mask.
    """
    BS = block_slots
    bb = pl.program_id(0)
    g = pl.program_id(1)
    window = window_ref[0]
    chunk_tokens = CHUNK_PAGES * page_size
    G = q_ref.shape[3]

    # per-slot page counts; loop bound is the block max
    n_pages_j = [
        jax.lax.div(
            seq_lens_ref[bb * BS + j] + page_size - 1, page_size
        )
        for j in range(BS)
    ]
    n_chunks = jax.lax.div(
        n_pages_j[0] + CHUNK_PAGES - 1, CHUNK_PAGES
    )
    for j in range(1, BS):
        n_chunks = jnp.maximum(
            n_chunks,
            jax.lax.div(n_pages_j[j] + CHUNK_PAGES - 1, CHUNK_PAGES),
        )
    # sliding window: chunks wholly below the BLOCK's earliest window
    # start are skipped (per-slot masks handle the rest)
    lo_block = jnp.where(
        window > 0,
        jnp.maximum(seq_lens_ref[bb * BS] - window, 0),
        0,
    )
    for j in range(1, BS):
        lo_block = jnp.minimum(
            lo_block,
            jnp.where(
                window > 0,
                jnp.maximum(seq_lens_ref[bb * BS + j] - window, 0),
                0,
            ),
        )
    lo_chunk = jax.lax.div(lo_block, chunk_tokens)

    def src(ref, page_id):
        if has_layer:
            return ref.at[layer_ref[0], g, page_id]
        return ref.at[g, page_id]

    def start_chunk(c, slot):
        for j in range(BS):
            b = bb * BS + j
            for i in range(CHUNK_PAGES):  # static unroll
                page_pos = c * CHUNK_PAGES + i

                @pl.when(page_pos < n_pages_j[j])
                def _():
                    page_id = page_tables_ref[b, page_pos]
                    pltpu.make_async_copy(
                        src(k_pages_ref, page_id),
                        k_buf.at[
                            slot, j, pl.ds(i * page_size, page_size), :
                        ],
                        sems.at[slot, 0, j, i],
                    ).start()
                    pltpu.make_async_copy(
                        src(v_pages_ref, page_id),
                        v_buf.at[
                            slot, j, pl.ds(i * page_size, page_size), :
                        ],
                        sems.at[slot, 1, j, i],
                    ).start()

                @pl.when(page_pos >= n_pages_j[j])
                def _():
                    k_buf[
                        slot, j, pl.ds(i * page_size, page_size), :
                    ] = jnp.zeros(
                        (page_size, k_buf.shape[-1]), k_buf.dtype
                    )
                    v_buf[
                        slot, j, pl.ds(i * page_size, page_size), :
                    ] = jnp.zeros(
                        (page_size, v_buf.shape[-1]), v_buf.dtype
                    )

    def wait_chunk(c, slot):
        for j in range(BS):
            for i in range(CHUNK_PAGES):
                page_pos = c * CHUNK_PAGES + i

                @pl.when(page_pos < n_pages_j[j])
                def _():
                    pltpu.make_async_copy(
                        src(k_pages_ref, 0),
                        k_buf.at[
                            slot, j, pl.ds(i * page_size, page_size), :
                        ],
                        sems.at[slot, 0, j, i],
                    ).wait()
                    pltpu.make_async_copy(
                        src(v_pages_ref, 0),
                        v_buf.at[
                            slot, j, pl.ds(i * page_size, page_size), :
                        ],
                        sems.at[slot, 1, j, i],
                    ).wait()

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

        k_all = jax.lax.cond(
            slot == 0, lambda: k_buf[0], lambda: k_buf[1]
        )  # [BS, chunk_tokens, hd]
        v_all = jax.lax.cond(
            slot == 0, lambda: v_buf[0], lambda: v_buf[1]
        )
        token_base = c * chunk_tokens
        for j in range(BS):  # static unroll: 2D dots only
            b = bb * BS + j
            q = q_ref[0, 0, j].astype(jnp.float32) * scale  # [G, hd]
            k = k_all[j].astype(jnp.float32)  # [chunk_tokens, hd]
            v = v_all[j].astype(jnp.float32)
            scores = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [G, chunk_tokens]
            if softcap:
                scores = jnp.tanh(scores / softcap) * softcap
            token_pos = token_base + jax.lax.broadcasted_iota(
                jnp.int32, scores.shape, 1
            )
            sl = seq_lens_ref[b]
            lo = jnp.where(
                window > 0, jnp.maximum(sl - window, 0), 0
            )
            valid = (token_pos >= lo) & (token_pos < sl)
            scores = jnp.where(valid, scores, -1e30)
            r = slice(j * G, (j + 1) * G)
            m_prev = m_ref[r, :1]
            m_cur = jnp.max(scores, axis=-1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(scores - m_new)
            l_new = alpha * l_ref[r, :1] + jnp.sum(
                p, axis=-1, keepdims=True
            )
            acc_ref[r, :] = acc_ref[r, :] * alpha + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_ref[r, :] = jnp.broadcast_to(m_new, (G, 128))
            l_ref[r, :] = jnp.broadcast_to(l_new, (G, 128))
        return 0

    jax.lax.fori_loop(lo_chunk, n_chunks, body, 0)
    for j in range(BS):
        r = slice(j * G, (j + 1) * G)
        denom = jnp.maximum(l_ref[r, :1], 1e-30)
        out_ref[0, 0, j] = (acc_ref[r, :] / denom).astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("interpret", "softcap", "scale", "block_slots"),
)
def paged_decode_attention_pallas_blocked(
    q: jnp.ndarray,  # [B, H, hd]
    k_pages: jnp.ndarray,  # [KV, P, ps, hd] ([L, KV, ...] with `layer`)
    v_pages: jnp.ndarray,
    page_tables: jnp.ndarray,  # [B, pages_per_seq]
    seq_lens: jnp.ndarray,  # [B]
    window=None,
    layer=None,
    interpret: bool = False,
    softcap: float = 0.0,
    scale=None,
    block_slots: int = 8,
) -> jnp.ndarray:
    """Multi-slot-blocked variant of ``paged_decode_attention_pallas``:
    grid (B/block_slots, KV) instead of (B, KV).  Opt-in via
    ``tpu.decode_block_slots`` until its win is measured on hardware
    (the r3 lesson: no unmeasured default flips).  Falls back to the
    per-slot kernel when ``B % block_slots != 0`` — and for int8 KV
    pools: the blocked grid is itself unmeasured, so it doesn't carry
    the scale-DMA plumbing yet (the per-slot kernel dequantizes
    in-VMEM; revisit if the hardware A/B picks the blocked grid)."""
    from vgate_tpu.ops.kv_quant import is_quantized

    B, H, hd = q.shape
    has_layer = layer is not None
    BS = block_slots
    if BS <= 1 or B % BS or is_quantized(k_pages):
        return paged_decode_attention_pallas(
            q, k_pages, v_pages, page_tables, seq_lens, window=window,
            layer=layer, interpret=interpret, softcap=softcap,
            scale=scale,
        )
    KV, P, ps, _ = k_pages.shape[1:] if has_layer else k_pages.shape
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
        _blocked_kernel,
        page_size=ps,
        softcap=float(softcap),
        scale=float(scale) if scale is not None else hd ** -0.5,
        block_slots=BS,
        has_layer=has_layer,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B // BS, KV),
        in_specs=[
            pl.BlockSpec(
                (1, 1, BS, G, hd),
                lambda bb, g, *prefetch: (bb, g, 0, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, BS, G, hd),
            lambda bb, g, *prefetch: (bb, g, 0, 0, 0),
            memory_space=pltpu.VMEM,
        ),
        scratch_shapes=[
            pltpu.VMEM((2, BS, chunk_tokens, hd), k_pages.dtype),
            pltpu.VMEM((2, BS, chunk_tokens, hd), v_pages.dtype),
            pltpu.VMEM((BS * G, hd), jnp.float32),
            pltpu.VMEM((BS * G, 128), jnp.float32),
            pltpu.VMEM((BS * G, 128), jnp.float32),
            pltpu.SemaphoreType.DMA((2, 2, BS, CHUNK_PAGES)),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B // BS, KV, BS, G, hd), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=96 * 1024 * 1024,
        ),
    )(
        page_tables, seq_lens, window_arr, layer_arr,
        # q [B, H, hd] = [NB*BS, KV*G, hd] -> [NB, KV, BS, G, hd]
        jnp.swapaxes(q.reshape(B // BS, BS, KV, G, hd), 1, 2),
        k_pages, v_pages,
    )
    # out [NB, KV, BS, G, hd] -> [B, H, hd]
    return jnp.swapaxes(out, 1, 2).reshape(B, H, hd)


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
