"""Learned sparse attention (DeepSeek-V3.2's DSA) over the latent cache
(GLM-5.2's ``glm_moe_dsa``) or over a GQA cache of K and V (Keye-VL-2.0's
``sa_config``; the ``kv_rows_*`` functions at the end): the indexer's
scores, the selection, and decode attention over the selected rows alone.

A layer that picks scores every cached position ``s <= t`` for the query
at ``t``::

    I(t, s) = sum_j w_j(t) * relu(q^I_j(t) . k^I_s)        (float32)

with ``index_n_heads`` query heads against ONE key a token, and keeps the
``index_topk`` positions of largest ``I(t, .)`` (all of them while ``t <
index_topk``; ties to the lower position).  The layer's latent attention,
and that of the layers that reuse the pick, runs over those positions
only.

Two forms of the selection: POSITIONS ``[B, k]`` (a decode step: they
become the rows' places in the pool once a pick, ``order_picks``, and
the attention fetches those rows alone, ``dsa_decode_attention``) and a
MASK ``[B, S, T]`` (a prompt pass: the flash kernel takes it tile
by tile).  The Pallas kernels are in ``ops/pallas/dsa.py``; what is here
is their ``jax.numpy`` twins and what XLA does as well as a kernel
would.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = float("-inf")


def index_scores(qi, w, keys):
    """The indexer's scores of query rows against keys, float32:
    qi [B, S, Hi, d], w [B, S, Hi] (float32, the heads' weights with
    their two scales in), keys [B, T, d] -> [B, S, T].  No mask."""
    s = jnp.einsum("bshd,btd->bsht", qi, keys.astype(qi.dtype),
                   preferred_element_type=jnp.float32)
    return jnp.sum(jnp.maximum(s, 0.0) * w[..., None], axis=2)


def _ordered_bits(x):
    """float32 -> uint32 whose order is the floats' (no NaN)."""
    b = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)
    b = b ^ ((b >> 31) & jnp.int32(0x7FFFFFFF))
    return jax.lax.bitcast_convert_type(b, jnp.uint32) ^ jnp.uint32(1 << 31)


def select_mask(scores, k: int):
    """The ``k`` largest of each row of scores [..., T] as a bool mask,
    ties to the lower position; a row of fewer than ``k`` entries above
    ``-inf`` keeps some of those too (the caller's causal mask takes
    them out).  No sort: the k-th largest value by a binary search over
    the floats' ordered bits (32 counts over the row), and only where a
    row has more ties at that value than places left, the ties' cut-off
    position by a second search (a count a bit of the position)."""
    T = scores.shape[-1]
    if k >= T:
        return jnp.ones(scores.shape, bool)
    u = _ordered_bits(scores)
    count = lambda m: jnp.sum(m, axis=-1, keepdims=True, dtype=jnp.int32)

    def value_bit(i, thr):
        cand = thr | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        return jnp.where(count(u >= cand) >= k, cand, thr)

    thr = jax.lax.fori_loop(
        0, 32, value_bit, jnp.zeros(scores.shape[:-1] + (1,), jnp.uint32))
    above, ties = u > thr, u == thr
    room = k - count(above)  # places left for the ties, >= 1
    pos = jnp.arange(T, dtype=jnp.int32)
    bits = max(1, (T - 1).bit_length())

    def cut_ties():
        def pos_bit(i, cut):
            cand = cut | (jnp.int32(1) << (bits - 1 - i))
            return jnp.where(count(ties & (pos < cand)) < room, cand, cut)

        cut = jax.lax.fori_loop(0, bits, pos_bit, jnp.zeros_like(room))
        return above | (ties & (pos <= cut))

    return jax.lax.cond(jnp.any(count(ties) > room), cut_ties,
                        lambda: above | ties)


def select_positions(scores, k: int):
    """The positions of the ``k`` largest of each row of scores [B, T],
    [B, min(k, T)] int32 (``jax.lax.top_k``: of equal scores the lower
    position first).  Entries at ``-inf`` come last, in position order:
    a row of ``n < k`` live entries has its pick in the first ``n``."""
    return jax.lax.top_k(scores, min(k, scores.shape[-1]))[1].astype(
        jnp.int32)


def order_picks(page_tables, sel, n_sel, page_size: int):
    """Where a slot's picks lie in a layer of the pool, ``page *
    page_size + offset``, [B, k] int32: the first ``n_sel`` of ``sel``
    (positions) through the page table, put in the order (the row's
    parity, its place) so that the rows at EVEN places come first (the
    first rows of their pairs, in a pool by pairs) and neighbours in the
    list are neighbours in the pool; the rest 0.  The same set: done
    once a pick, for every layer that attends under it.  The page of a
    position by comparison against every entry of the slot's table
    (summed over a major dimension), not by a gather: XLA's gather of
    98,304 page ids is 1.0 ms on the v5e (PERF.md section 6, PR 41),
    this whole function under 0.2."""
    k, n = sel.shape[1], page_tables.shape[1]
    hit = (sel // page_size)[:, None, :] == jnp.arange(
        n, dtype=jnp.int32)[None, :, None]
    page = jnp.sum(
        jnp.where(hit, page_tables.astype(jnp.int32)[:, :, None], 0),
        axis=1)
    row = page * page_size + sel % page_size
    real = jnp.arange(k, dtype=jnp.int32)[None, :] < n_sel[:, None]
    # one sort: parity above the place, the rest above both
    key = jnp.sort(
        jnp.where(real, ((row & 1) << 29) | row, 1 << 30), axis=1)
    return jnp.where(real, key & ((1 << 29) - 1), 0)


def gather_selected(pool, rows, layer):
    """The pool's rows at the places ``rows`` [B, k] of layer ``layer``
    (``order_picks``), [B, k, W]: pool [L, 1, P, ps, W] or by pairs [L,
    1, P, ps / 2, 2, W], seen as ``[L x P x ps, W]`` (the leading
    dimensions merge without moving a byte)."""
    W = pool.shape[-1]
    flat = pool.reshape(-1, W)
    return flat[layer * (flat.shape[0] // pool.shape[0]) + rows]


def dsa_decode_attention(q, pool, rows, n_sel, layer, *, v_width: int,
                         scale: float, use_pallas: bool):
    """Absorbed latent decode attention over the SELECTED rows only:
    q [B, H, W], rows [B, k] the picks' places in a layer of the pool (a
    slot's first ``n_sel`` real, as ``order_picks`` leaves them) -> [B,
    H, v_width].  The kernel (ops/pallas/dsa.py
    ``dsa_decode_attention_pallas``, a pool by pairs) fetches each
    picked row itself; the jnp twin gathers them.  No row outside the
    pick meets a score."""
    if use_pallas:
        from vgate_tpu.ops.pallas.dsa import dsa_decode_attention_pallas

        return dsa_decode_attention_pallas(
            q, pool, rows, n_sel, layer, v_width=v_width, scale=scale)
    from vgate_tpu.ops.attention import mla_attend_rows

    with jax.named_scope("dsa_gather"):
        picked = gather_selected(pool, rows, layer)
    return mla_attend_rows(q, picked, n_sel, v_width, scale)


def masked_attention(q, k, v, mask, scale: float):
    """Plain softmax attention under a mask, float32 inside: q [B, S, H,
    hd], k / v [B, T, H, .] (or [B, T, KV, .], each KV head the H / KV
    query heads' of its group), mask [B, S, T] (nonzero = attend; every
    real row attends to itself) -> [B, S, H, v].  The ``jax.numpy`` twin
    of the prompt kernel under a selection, whole score matrix and all:
    for a CPU's sizes and for the passes no kernel covers yet."""
    group = q.shape[2] // k.shape[2]
    if group > 1:
        k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bshd,bthd->bhst", q, k,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask[:, None] != 0, s, -1e30)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bhst,bthv->bshv", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


# ---- GQA attention under a selection (``ModelSpec.kv_rows``): the pool
# holds a token's K over its V, ``[L, 1, P, ps, 2, W]`` with ``W`` = KV
# heads x head size, so a picked token is ONE pair of rows


def kv_rows_write_tokens(pool, page_ids, page_off, k, v, layer):
    """A decode step's K and V (k, v [B, KV, hd]) into their tokens'
    pairs of rows: the pair's dimension indexed explicitly, so that the
    scatter's window is one row (ops/kv_quant.py ``kv_write_tokens``)."""
    B = k.shape[0]
    rows = jnp.stack([k.reshape(B, -1), v.reshape(B, -1)], axis=1)
    which = jnp.arange(2, dtype=jnp.int32)[None, :]
    return pool.at[layer, 0, page_ids[:, None], page_off[:, None],
                   which].set(rows.astype(pool.dtype))


def kv_rows_pages(k, v, page_size: int):
    """A prompt's k, v [B, S, KV, hd] as whole pages of pairs of rows,
    [B, S / ps, ps, 2, W]."""
    B, S = k.shape[:2]
    flat = lambda t: t.reshape(B, S // page_size, page_size, -1)
    return jnp.stack([flat(k), flat(v)], axis=3)


def kv_rows_gather(pool, page_tables, layer, kv_heads: int):
    """The K and V of each slot's page window, k, v [B, ctx, KV, hd]."""
    rows = pool[layer, 0, page_tables]  # [B, n, ps, 2, W]
    B = rows.shape[0]
    heads = lambda t: t.reshape(B, -1, kv_heads, t.shape[-1] // kv_heads)
    return heads(rows[..., 0, :]), heads(rows[..., 1, :])


def kv_rows_decode_attention(q, pool, rows, n_sel, layer, *, scale: float,
                             use_pallas: bool):
    """GQA decode attention over the SELECTED tokens only: q [B, H, hd],
    rows [B, k] the picks' places in a layer of the pool (``page x ps +
    offset``; a slot's first ``n_sel`` real) -> [B, H, hd].  The kernel
    (ops/pallas/dsa.py ``dsa_kv_decode_attention_pallas``) fetches each
    picked token's pair of rows itself; the jnp twin gathers them."""
    if use_pallas:
        from vgate_tpu.ops.pallas.dsa import dsa_kv_decode_attention_pallas

        return dsa_kv_decode_attention_pallas(
            q, pool, rows, n_sel, layer, scale=scale)
    B, H, hd = q.shape
    W = pool.shape[-1]
    KV = W // hd
    with jax.named_scope("dsa_gather"):
        flat = pool.reshape(-1, 2, W)
        picked = flat[layer * (flat.shape[0] // pool.shape[0]) + rows]
    k = picked[:, :, 0].reshape(B, -1, KV, hd)
    v = picked[:, :, 1].reshape(B, -1, KV, hd)
    qg = q.reshape(B, KV, H // KV, hd)
    s = jnp.einsum("bgjd,btgd->bgjt", qg, k.astype(q.dtype),
                   preferred_element_type=jnp.float32) * scale
    live = jnp.arange(rows.shape[1])[None, :] < n_sel[:, None]
    s = jnp.where(live[:, None, None, :], s, -1e30)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bgjt,btgd->bgjd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, H, hd).astype(q.dtype)
