"""EVA attention (EvaByte; Zheng et al., "Efficient Attention via Control
Variates", ICLR 2023, in the released model's parameterisation): what
the stack walker's ``eva`` sub-block (models/hybrid.py) computes beside
the kernels every attention layer shares.

A query at position ``t`` lies in window ``w = t // W``.  It attends
EXACTLY to the keys ``w W .. t`` of its own window and, through ONE
learned summary row for every chunk of ``c`` tokens, to every chunk of
every CLOSED window (``j < (W / c) w``), all in one softmax.  Chunk
``j``'s row under head ``h``'s two learned vectors ``phi`` and ``mu``:
``alpha = softmax_{i in chunk}(s k_i . phi)``, ``k~ = sum alpha_i k_i +
mu``, ``v~ = sum alpha_i v_i`` (``summarize``).

The cache (runtime/kv_cache.py): the paged pool's row stands for ``c``
tokens and holds ``(k~, v~)``; the open window's ``W`` exact rows are
``W / ps`` pages a decode SLOT behind the allocator's pages of the SAME
arrays (``window_pages``), so that a decode step's rows, the closed
windows' summary pages and then the window's own, are ONE sequence to
the paged decode kernel (``decode_view``): it reads the live rows alone,
under one online softmax, and writes the step's row where
``kv_write_tokens`` would.

WHEN a summary row is written.  A query in window ``w`` reads the
summary rows of CLOSED windows alone (``decode_view``'s table stops at
``per x closed`` summary pages; ``chunk_keys``: a row is seen from
``closes`` on), so the rows of the open window are first read by the
step AFTER the one that fills row ``W - 1``.  A decode step therefore
writes none; the step that fills row ``W - 1`` of a slot's window, once
its own row is in the window's pages, writes ALL ``W / c`` summary rows
of that window from the window's ``W`` exact rows, into the ``W / c /
ps`` whole pages of the sequence that own them (``decode_close``).  That
is early enough: the window's pages hold every row of the window until
the next window's first step overwrites row 0, which is the step after;
and a window closes inside a decode chunk with no host round trip.  A
prompt pass writes the summaries of the chunks its own rows touch
(``models/hybrid.py _eva_prompt``), the open window's partial ones among
them: nobody reads those, and the close overwrites them from the exact
rows.  Only a closing slot pays (one step in ``W`` a slot): 33.6 MB of
contiguous reads a layer at the published sizes, where a rewrite of the
open chunk's row at every step was a sixth of the step.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from vgate_tpu.ops.kv_quant import gather_pages, kv_write_pages

# a position no query reaches: the ``lo`` of a key nobody sees
NEVER = 1 << 30


def window_pages(first_page: int, slots: int, pages: int) -> jax.Array:
    """``[slots + 1, pages]``: the pool pages of each decode slot's open
    window (``first_page`` on: behind the allocator's), and a last row
    of trash pages for a prompt program's padding rows, whose slot is
    ``slots``.  Arithmetic, not allocation."""
    ids = first_page + jnp.arange(slots * pages, dtype=jnp.int32)
    return jnp.concatenate(
        [ids.reshape(slots, pages), jnp.zeros((1, pages), jnp.int32)])


@jax.named_scope("eva_summarize")
def summarize(k, v, phi, mu, valid, chunk: int, scale: float):
    """Whole chunks' rows k, v ``[..., n x chunk, KV, hd]`` (``valid``
    ``[..., n x chunk]``: the rows that are there) -> their summary rows
    ``(k~, v~)`` ``[..., n, KV, hd]`` in the rows' type; float32 inside.
    A chunk with no valid row comes out ``(mu, 0)``: nobody reads it."""
    lead, (rows, KV, hd) = k.shape[:-3], k.shape[-3:]
    by_chunk = lambda t: t.reshape(*lead, rows // chunk, chunk, *t.shape[
        len(lead) + 1:])
    kc, vc, seen = by_chunk(k), by_chunk(v), by_chunk(valid)[..., None]
    logit = jnp.einsum("...ckd,kd->...ck", kc, phi.astype(kc.dtype),
                       preferred_element_type=jnp.float32) * scale
    logit = jnp.where(seen, logit, -1e30)
    alpha = jnp.where(
        seen, jnp.exp(logit - jnp.max(logit, axis=-2, keepdims=True)), 0.0)
    alpha = alpha / jnp.maximum(
        jnp.sum(alpha, axis=-2, keepdims=True), 1e-30)
    pool = lambda t: jnp.einsum("...ck,...ckd->...kd", alpha,
                                t.astype(jnp.float32))
    return ((pool(kc) + mu.astype(jnp.float32)).astype(k.dtype),
            pool(vc).astype(v.dtype))


def decode_view(page_tables, win_pages, positions, window: int, chunk: int,
                ps: int):
    """A decode step's rows as ONE paged sequence: ``(tables [B, pages a
    sequence + pages a window], rows [B])``, the summary pages of the
    closed windows (whole pages: ``window / chunk`` divides by ``ps``),
    then the open window's pages; ``rows`` is the step's own row in it,
    so ``rows + 1`` rows are live and no dead row is read."""
    per, R = window // chunk // ps, window // ps
    n = page_tables.shape[1]
    closed = positions // window
    i = jnp.arange(n + R, dtype=jnp.int32)[None, :]
    j = i - per * closed[:, None]
    own = jnp.take_along_axis(page_tables, jnp.minimum(i, n - 1), axis=1)
    win = jnp.take_along_axis(win_pages, jnp.clip(j, 0, R - 1), axis=1)
    tables = jnp.where(j < 0, own, jnp.where(j < R, win, 0))
    return tables, (window // chunk) * closed + positions % window


def decode_closers(page_tables, win_pages, positions, active, window: int,
                   chunk: int, ps: int):
    """What a decode step's rows close, as ``decode_close``'s work list:
    ``(src [B x per], dst [B x per], count)``, one entry for every page
    of summary rows (``per`` a window).  The slots whose step fills row
    ``window - 1`` come first; ``src`` is the first of the ``chunk``
    CONSECUTIVE window pages (``window_pages``) that hold the page's
    ``ps x chunk`` exact rows, ``dst`` the sequence's page that owns its
    ``ps`` summary rows, ``count`` the entries that are work.  An idle
    slot closes nothing, wherever its stale position stands.  Once a
    step, for every layer."""
    per = window // chunk // ps
    closing = positions % window == window - 1
    if active is not None:
        closing &= active
    order = jnp.argsort(~closing, stable=True)
    page = jnp.arange(per, dtype=jnp.int32)[None, :]
    src = win_pages[order, :1] + chunk * page
    dst = jnp.take_along_axis(
        page_tables[order], per * (positions[order] // window)[:, None] + page,
        axis=1)
    return (src.reshape(-1), dst.reshape(-1),
            per * jnp.sum(closing, dtype=jnp.int32))


@jax.named_scope("eva_summarize")
def decode_close(kp, vp, phi, mu, layer, closers, chunk: int, scale: float):
    """After a decode step wrote its row: every window that row filled
    gets its summary rows, from its exact rows, into the whole pages of
    the sequence that own them.  A loop over ``closers``
    (``decode_closers``), a page of summary rows a trip: one contiguous
    slice of each pool in, one page out.  A step with no closer runs no
    trip of it.  (A page a trip, its ids from a list made once a step:
    a whole window a trip, or ids worked out inside the loop, left the
    compiled chunk more temporaries than the rewrite of every step had:
    tests/test_tpu_aot.py.)"""
    KV, ps, hd = kp.shape[1], kp.shape[-2], kp.shape[-1]
    src, dst, count = closers
    seen = jnp.ones((ps * chunk,), bool)

    def close(i, pools):
        rows = lambda pool: jnp.moveaxis(jax.lax.dynamic_slice(
            pool, (layer, 0, src[i], 0, 0), (1, KV, chunk, ps, hd)
        ).reshape(KV, ps * chunk, hd), 0, 1)
        ks, vs = summarize(*map(rows, pools), phi, mu, seen, chunk, scale)
        return tuple(
            kv_write_pages(pool, dst[i], jnp.moveaxis(t, 0, 1), layer=layer)
            for pool, t in zip(pools, (ks, vs)))

    return jax.lax.fori_loop(0, count, close, (kp, vp))


def gather_rows(pool, tables, layer):
    """The rows of each sequence's pages ``tables`` [B, n], in order:
    ``[B, n x ps, KV, hd]``."""
    sel = gather_pages(pool, tables, layer=layer)  # [KV, B, n, ps, hd]
    KV, B, n, ps, hd = sel.shape
    return jnp.moveaxis(sel.reshape(KV, B, n * ps, hd), 0, 2)


def chunk_keys(start, lens, S: int, n_sum: int, window: int, chunk: int):
    """Who sees which key when prompt rows ``start .. start + lens - 1``
    (a bucket of ``S``) attend to ``[the pool's first n_sum summary rows
    | the window's rows as the chunks before left them | their own
    rows]``: ``(lo, hi)`` [B, n_sum + window + S], a key is seen by the
    queries at positions ``lo .. hi``.  A summary row is seen once its
    window has closed; an exact row by its own window's later rows."""
    start, lens = start[:, None], lens[:, None]
    j = jnp.arange(n_sum)[None, :]
    closes = (j // (window // chunk) + 1) * window
    base = start // window * window
    held = base + jnp.arange(window)[None, :]
    own = start + jnp.arange(S)[None, :]
    last = lambda p: (p // window + 1) * window - 1
    lo = jnp.concatenate([
        jnp.broadcast_to(closes, (start.shape[0], n_sum)),
        jnp.where(held < start, held, NEVER),
        jnp.where(own < start + lens, own, NEVER)], axis=1)
    hi = jnp.concatenate([
        jnp.full((start.shape[0], n_sum), NEVER), last(held), last(own)],
        axis=1)
    return lo, hi


@jax.named_scope("eva_attend")
def interval_attention(q, q_pos, k, v, lo, hi, scale: float,
                       block_k: int = 256):
    """Blockwise attention with an online softmax, q ``[B, S, H, hd]`` at
    positions ``q_pos`` [B, S] over keys ``[B, T, KV, hd]``, key ``t``
    seen by the queries at ``lo[b, t] <= q_pos <= hi[b, t]``: the
    ``jax.numpy`` form of EVA's one softmax over exact rows and summary
    rows, for the rows no kernel takes (a later chunk of a chunked
    prefill, a bucket that is no whole number of windows, a CPU).  A
    query that sees nothing comes out zero."""
    B, S, H, hd = q.shape
    T, KV = k.shape[1], k.shape[2]
    block_k = min(block_k, T)
    pad = -T % block_k
    if pad:
        rows = lambda t: jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k, v = rows(k), rows(v)
        lo = jnp.pad(lo, ((0, 0), (0, pad)), constant_values=NEVER)
        hi = jnp.pad(hi, ((0, 0), (0, pad)))
    q32 = q.astype(jnp.float32) * scale
    rep = H // KV

    def body(carry, blk):
        acc, m, l = carry
        cut = lambda t: jax.lax.dynamic_slice_in_dim(
            t, blk * block_k, block_k, 1)
        k_blk = jnp.repeat(cut(k), rep, axis=2).astype(jnp.float32)
        v_blk = jnp.repeat(cut(v), rep, axis=2).astype(jnp.float32)
        seen = ((cut(lo)[:, None, :] <= q_pos[:, :, None])
                & (q_pos[:, :, None] <= cut(hi)[:, None, :]))[..., None]
        scores = jnp.einsum("bshd,bthd->bsth", q32, k_blk,
                            preferred_element_type=jnp.float32)
        scores = jnp.where(seen, scores, -1e30)
        m_new = jnp.maximum(m, jnp.max(scores, axis=2))
        shrink = jnp.exp(m - m_new)
        p = jnp.where(seen, jnp.exp(scores - m_new[:, :, None, :]), 0.0)
        l = shrink * l + jnp.sum(p, axis=2)
        acc = acc * shrink[..., None] + jnp.einsum(
            "bsth,bthd->bshd", p, v_blk, preferred_element_type=jnp.float32)
        return (acc, m_new, l), None

    init = (jnp.zeros((B, S, H, hd), jnp.float32),
            jnp.full((B, S, H), -1e30, jnp.float32),
            jnp.zeros((B, S, H), jnp.float32))
    (acc, _, l), _ = jax.lax.scan(
        body, init, jnp.arange((T + pad) // block_k))
    return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
