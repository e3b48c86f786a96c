"""Attention reference implementations (jnp).

These are the semantic ground truth the Pallas kernels are tested against
(SURVEY.md section 4: kernel unit tests compare Pallas outputs vs jnp).  The
engine uses them directly on CPU test meshes and as the `use_pallas=False`
fallback on TPU.

Replaces the capability the reference delegates to vLLM's CUDA
paged-attention (SURVEY.md section 2.1, vllm_backend.py:51 — opaque there,
first-party here).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from vgate_tpu.ops.kv_quant import gather_pages


def repeat_kv(x: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    """Broadcast KV heads across query-head groups (GQA). x: [..., KV, hd]."""
    if n_rep == 1:
        return x
    return jnp.repeat(x, n_rep, axis=-2)


def _softcap(scores: jnp.ndarray, cap: float) -> jnp.ndarray:
    """Gemma-2 style tanh soft-capping of attention/logit scores (fp32)."""
    if not cap:
        return scores
    return jnp.tanh(scores / cap) * cap


def causal_prefill_attention(
    q: jnp.ndarray,  # [B, S, H, hd]
    k: jnp.ndarray,  # [B, S, KV, hd]
    v: jnp.ndarray,  # [B, S, KV, hd]
    seq_lens: jnp.ndarray,  # [B] real lengths (tokens beyond are padding)
    softcap: float = 0.0,
    window=None,  # int32 scalar; >0 => attend only to the last `window` keys
    scale=None,  # query scale; default hd**-0.5
) -> jnp.ndarray:
    """Causal self-attention over a padded prompt batch. Returns [B, S, H, hd].

    fp32 softmax accumulation; padded key positions are masked out so garbage
    in the padding region cannot leak into real tokens.
    """
    B, S, H, hd = q.shape
    n_rep = H // k.shape[2]
    k = repeat_kv(k, n_rep)
    v = repeat_kv(v, n_rep)
    if scale is None:
        scale = 1.0 / (hd ** 0.5)
    # [B, H, S, S]
    scores = jnp.einsum(
        "bshd,bthd->bhst", q, k, preferred_element_type=jnp.float32
    ) * scale
    scores = _softcap(scores, softcap)
    pos = jnp.arange(S)
    causal = pos[None, :] <= pos[:, None]  # [S(q), S(k)] keys <= query pos
    key_valid = pos[None, :] < seq_lens[:, None]  # [B, S]
    mask = causal[None, None, :, :] & key_valid[:, None, None, :]
    if window is not None:
        dist = pos[:, None] - pos[None, :]  # q_pos - k_pos, [S, S]
        win_ok = (window <= 0) | (dist < window)
        mask = mask & win_ok[None, None, :, :]
    scores = jnp.where(mask, scores, -1e30)
    probs = jnp.exp(
        scores - jnp.max(scores, axis=-1, keepdims=True)
    )
    probs = probs / jnp.sum(probs, axis=-1, keepdims=True)
    out = jnp.einsum(
        "bhst,bthd->bshd", probs.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return out.astype(q.dtype)


def flash_prefill_attention(
    q: jnp.ndarray,  # [B, S, H, hd]
    k: jnp.ndarray,  # [B, S, KV, hd]
    v: jnp.ndarray,  # [B, S, KV, hd]
    seq_lens: jnp.ndarray,  # [B] real lengths (tokens beyond are padding)
    block_k: int = 256,
    q_offset=None,  # [B] int32: global position of q[:, 0] (chunked prefill)
    softcap: float = 0.0,
    window=None,  # int32 scalar; >0 => attend only to the last `window` keys
    scale=None,  # query scale; default hd**-0.5
    k_start=None,  # [B] int32: keys before this index are nobody's
) -> jnp.ndarray:
    """Blockwise causal attention with online softmax. Returns [B, S, H, hd].

    Same semantics as ``causal_prefill_attention`` (the test oracle) but
    scans over key blocks, so peak memory is O(B·H·S·block_k) instead of the
    O(B·H·S²) score materialization — at the 2048 bucket that is ~25 MB per
    block vs ~200 MB (fp32, H=12).  This is the default prefill path; the
    Pallas kernel (ops/pallas/flash_prefill.py) goes further by streaming KV
    through VMEM.

    With ``q_offset`` the queries are a chunk starting at a nonzero global
    position attending to keys laid out from position ``0`` — the
    chunked-prefill path where ``k``/``v`` cover history + current chunk.
    """
    B, S, H, hd = q.shape
    Sk = k.shape[1]
    n_rep = H // k.shape[2]
    if scale is None:
        scale = 1.0 / (hd ** 0.5)
    q32 = q.astype(jnp.float32) * scale

    block_k = min(block_k, Sk)  # buckets are powers of two
    if Sk % block_k:
        raise ValueError(f"key length {Sk} not divisible by {block_k}")
    n_blocks = Sk // block_k

    q_pos = jnp.arange(S)[None, :]  # [1, S]
    if q_offset is not None:
        q_pos = q_pos + q_offset[:, None]  # [B, S]

    def body(carry, blk):
        acc, m, l = carry
        k_blk = jax.lax.dynamic_slice_in_dim(k, blk * block_k, block_k, 1)
        v_blk = jax.lax.dynamic_slice_in_dim(v, blk * block_k, block_k, 1)
        k_blk = repeat_kv(k_blk, n_rep).astype(jnp.float32)
        v_blk = repeat_kv(v_blk, n_rep).astype(jnp.float32)
        k_pos = blk * block_k + jnp.arange(block_k)  # [block_k]
        # [B, S(q), block_k]
        mask = (k_pos[None, None, :] <= q_pos[:, :, None]) & (
            k_pos[None, None, :] < seq_lens[:, None, None]
        )
        if window is not None:
            dist = q_pos[:, :, None] - k_pos[None, None, :]
            mask = mask & ((window <= 0) | (dist < window))
        if k_start is not None:
            mask = mask & (k_pos[None, None, :] >= k_start[:, None, None])
        scores = jnp.einsum(
            "bshd,bthd->bsth", q32, k_blk,
            preferred_element_type=jnp.float32,
        )  # [B, S, block_k, H]
        scores = _softcap(scores, softcap)
        scores = jnp.where(mask[..., None], scores, -1e30)
        m_cur = jnp.max(scores, axis=2)  # [B, S, H]
        m_new = jnp.maximum(m, m_cur)
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(scores - m_new[:, :, None, :])
        l = alpha * l + jnp.sum(p, axis=2)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bsth,bthd->bshd", p, v_blk, preferred_element_type=jnp.float32,
        )
        return (acc, m_new, l), None

    acc = jnp.zeros((B, S, H, hd), jnp.float32)
    m = jnp.full((B, S, H), -1e30, jnp.float32)
    l = jnp.zeros((B, S, H), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(
        body, (acc, m, l), jnp.arange(n_blocks)
    )
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


def paged_decode_attention(
    q: jnp.ndarray,  # [B, H, hd] one query token per slot
    k_pages: jnp.ndarray,  # [KV, P, page_size, hd] (head-major, kv_cache.py)
    v_pages: jnp.ndarray,  # [KV, P, page_size, hd]
    page_tables: jnp.ndarray,  # [B, pages_per_seq] int32
    seq_lens: jnp.ndarray,  # [B] context length per slot (incl. current token)
    softcap: float = 0.0,
    window=None,  # int32 scalar; >0 => attend only to the last `window` keys
    scale=None,  # query scale; default hd**-0.5
    layer=None,  # int32 scalar: pool layer index — k/v_pages then carry a
    #              leading [L] dim (the carry-threaded decode path)
) -> jnp.ndarray:
    """Decode-step attention over the paged KV cache. Returns [B, H, hd].

    Reference semantics for the Pallas paged kernel: gathers each slot's
    pages into a contiguous [ctx_max] view, masks positions >= seq_len, and
    runs fp32 softmax.  The Pallas version streams only the live pages
    through VMEM instead of materializing the gather.
    """
    B, H, hd = q.shape
    KV = k_pages.shape[1] if layer is not None else k_pages.shape[0]
    page_size = k_pages.shape[-2]
    n_rep = H // KV
    ctx_max = page_tables.shape[1] * page_size

    # gather_pages (ops/kv_quant.py) composes the (layer, head, page)
    # gather so only live pages are read, and DEQUANTIZES int8 pools to
    # f32 on the way (the same f32 the Pallas kernel folds scales in)
    k_sel = gather_pages(k_pages, page_tables, layer=layer)
    v_sel = gather_pages(v_pages, page_tables, layer=layer)

    # [KV, B, pages_per_seq, page_size, hd] -> [B, ctx, KV, hd]
    k = jnp.moveaxis(k_sel.reshape(KV, B, ctx_max, hd), 0, 2)
    v = jnp.moveaxis(v_sel.reshape(KV, B, ctx_max, hd), 0, 2)
    k = repeat_kv(k, n_rep)
    v = repeat_kv(v, n_rep)

    if scale is None:
        scale = 1.0 / (hd ** 0.5)
    scores = jnp.einsum(
        "bhd,bthd->bht", q, k, preferred_element_type=jnp.float32
    ) * scale
    scores = _softcap(scores, softcap)
    t = jnp.arange(ctx_max)[None, :]
    valid = t < seq_lens[:, None]  # [B, ctx]
    if window is not None:
        # the query sits at position seq_len-1: its window covers
        # (seq_len-1-window, seq_len-1]
        valid = valid & (
            (window <= 0) | (t > seq_lens[:, None] - 1 - window)
        )
    scores = jnp.where(valid[:, None, :], scores, -1e30)
    probs = jnp.exp(scores - jnp.max(scores, axis=-1, keepdims=True))
    probs = probs / jnp.sum(probs, axis=-1, keepdims=True)
    out = jnp.einsum(
        "bht,bthd->bhd", probs.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return out.astype(q.dtype)


def paged_suffix_attention(
    q: jnp.ndarray,  # [B, S, H, hd] suffix queries
    k_pages: jnp.ndarray,  # [KV, P, page_size, hd] (head-major)
    v_pages: jnp.ndarray,
    page_tables: jnp.ndarray,  # [B, ctx_pages] int32 (context window row)
    prefix_lens: jnp.ndarray,  # [B] global position of q[:, 0]
    seq_lens: jnp.ndarray,  # [B] total context (prefix + real suffix)
    softcap: float = 0.0,
    window=None,  # int32 scalar; >0 => attend only to the last `window` keys
    scale=None,  # query scale; default hd**-0.5
    layer=None,  # int32 scalar: pool layer index (carry-threaded prefill)
) -> jnp.ndarray:
    """Prompt-suffix attention over resident paged KV (prefix caching).

    The suffix tokens' KV has already been written into the page pool; this
    gathers each slot's page window — shared prefix pages plus the fresh
    suffix, bounded by the caller-bucketed ``ctx_pages`` — and runs the
    same blockwise online-softmax as flash_prefill_attention (its
    ``q_offset`` mode IS the suffix mask: ``k_pos <= prefix + s`` and
    ``k_pos < seq_len``), so no [B, H, S, ctx] score materialization.
    A Pallas kernel streaming only live pages is the natural follow-up.
    Returns [B, S, H, hd].
    """
    B = q.shape[0]
    KV = k_pages.shape[1] if layer is not None else k_pages.shape[0]
    hd = k_pages.shape[-1]
    page_size = k_pages.shape[-2]
    ctx = page_tables.shape[1] * page_size

    # dequantizing live-page gather, exactly like paged_decode_attention
    k_sel = gather_pages(k_pages, page_tables, layer=layer)
    v_sel = gather_pages(v_pages, page_tables, layer=layer)
    k = jnp.moveaxis(k_sel.reshape(KV, B, ctx, hd), 0, 2)
    v = jnp.moveaxis(v_sel.reshape(KV, B, ctx, hd), 0, 2)
    # key blocks must divide the window; fall back to page-sized blocks
    # for windows that aren't a multiple of 256 tokens
    block_k = 256 if ctx % 256 == 0 else page_size
    return flash_prefill_attention(
        q, k, v, seq_lens, block_k=block_k, q_offset=prefix_lens,
        softcap=softcap, window=window, scale=scale,
    )


def mla_gather_rows(pages, page_tables, layer):
    """The latent pool's rows of each slot's page window, [B, ctx, W]
    (pages [L, 1, P, ps, W], or by pairs [L, 1, P, ps / 2, 2, W])."""
    sel = gather_pages(pages, page_tables, layer=layer)  # [1, B, n, ps, W]
    return sel.reshape(sel.shape[1], -1, sel.shape[-1])


def mla_decode_attention(
    q: jnp.ndarray,  # [B, H, W] absorbed queries
    pages: jnp.ndarray,  # [L, 1, P, ps, W]: the latent pool
    page_tables: jnp.ndarray,  # [B, pages_per_seq]
    seq_lens: jnp.ndarray,  # [B] context length (incl. the current token)
    layer,
    v_width: int,
    scale: float,
) -> jnp.ndarray:
    """Decode attention of multi-head latent attention in its absorbed
    form, [B, H, v_width]: every head's query meets a token's ONE cached
    row as its key and takes the row's first ``v_width`` lanes as its
    value.  The twin of ``mla_decode_attention_pallas``."""
    return mla_attend_rows(
        q, mla_gather_rows(pages, page_tables, layer), seq_lens, v_width,
        scale)


def mla_attend_rows(q, rows, lens, v_width: int, scale: float):
    """The absorbed form over rows in hand: q [B, H, W] against the
    first ``lens`` of rows [B, T, W] -> [B, H, v_width]."""
    scores = jnp.einsum(
        "bhw,btw->bht", q, rows.astype(q.dtype),
        preferred_element_type=jnp.float32,
    ) * scale
    valid = jnp.arange(rows.shape[1])[None, :] < lens[:, None]
    scores = jnp.where(valid[:, None, :], scores, -1e30)
    probs = jnp.exp(scores - jnp.max(scores, axis=-1, keepdims=True))
    probs = probs / jnp.sum(probs, axis=-1, keepdims=True)
    values = rows[..., :v_width]
    out = jnp.einsum(
        "bht,btv->bhv", probs.astype(values.dtype), values,
        preferred_element_type=jnp.float32,
    )
    return out.astype(q.dtype)
