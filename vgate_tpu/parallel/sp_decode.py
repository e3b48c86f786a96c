"""Sequence-parallel decode: KV page pool sharded over ``sp``.

The decode-side half of the long-context story (SURVEY.md section 5.7;
VERDICT r2 partial-22/31: ring prefill existed but decode never ran
sp-sharded, so sp gave no KV-capacity relief).  Design:

* The page pool dim of ``k_pages``/``v_pages`` ``[L, KV, P, ps, hd]``
  shards **contiguously** over the mesh's sp axis: shard ``i`` owns
  global pages ``[i*P/sp, (i+1)*P/sp)`` — per-chip KV capacity scales
  linearly with sp, which is the whole point for long contexts.
* Each decode step runs attention per shard over ONLY the locally
  resident pages (ownership masks positions whose page lives elsewhere)
  producing unnormalized flash partials ``(acc, m, l)``, then merges
  across sp with a log-sum-exp reduction: ``pmax`` of the running max,
  ``psum`` of the rescaled denominators/accumulators.  Per-step ICI
  traffic is O(B·H·hd) — the partials — never the live KV itself.
* The current token's KV write lands on the owning shard; every other
  shard (and inactive slots) writes its **local trash page 0**.  Global
  page ids ``{i * P/sp}`` are reserved so each shard's local page 0 is
  a trash page (PageAllocator(num_shards=sp) skips them), the per-shard
  form of the global trash-page-0 trick.

The shard body is pure single-device jnp, so it runs on CPU test meshes
today and composes with a per-shard Pallas kernel (ownership-mask
prefetch) when multi-chip TPU hardware is available.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from vgate_tpu.parallel.mesh import AXIS_SP, AXIS_TP


def _tp_axis(mesh, H: int, KV: int):
    """``AXIS_TP`` when this mesh also carries tp and the head counts
    divide it — the shard bodies then run per (sp, tp) shard on local
    heads with NO tp collectives (attention is head-parallel).  None
    otherwise: the specs replicate over tp, which is correct but
    all-gathers tp-sharded operands at the shard_map boundary."""
    tp = int(mesh.shape.get(AXIS_TP, 1))
    if tp > 1 and H % tp == 0 and KV % tp == 0:
        return AXIS_TP
    return None


def reserved_page_ids(num_pages: int, sp: int) -> list:
    """Global ids of the per-shard trash pages (local page 0 of each
    contiguous shard block).  sp == 1 degenerates to [0]."""
    shard = num_pages // max(1, sp)
    return [i * shard for i in range(max(1, sp))]


def _partial_paged_attention(
    q,  # [B, H, hd] fp32-castable
    k_local,  # [KV, P/sp, ps, hd] this shard's page block
    v_local,
    local_pt,  # [B, pages_per_seq] LOCAL page indices (0 => not mine)
    owned,  # [B, pages_per_seq] bool: page lives on this shard
    seq_lens,  # [B]
    window,  # [] int32; >0 => only the last `window` positions
    softcap: float,
    scale: float,
):
    """Flash partials over the local page block: returns (acc [B,H,hd],
    m [B,H], l [B,H]) unnormalized, fp32."""
    B, H, hd = q.shape
    KV = k_local.shape[0]
    ps = k_local.shape[2]
    n_rep = H // KV
    ctx = local_pt.shape[1] * ps

    from vgate_tpu.ops.attention import repeat_kv

    k = repeat_kv(
        jnp.moveaxis(k_local[:, local_pt].reshape(KV, B, ctx, hd), 0, 2),
        n_rep,
    )  # [B, ctx, H, hd]
    v = repeat_kv(
        jnp.moveaxis(v_local[:, local_pt].reshape(KV, B, ctx, hd), 0, 2),
        n_rep,
    )

    scores = jnp.einsum(
        "bhd,bthd->bht", q.astype(jnp.float32) * scale,
        k.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    if softcap:
        scores = jnp.tanh(scores / softcap) * softcap
    t = jnp.arange(ctx)[None, :]
    valid = (t < seq_lens[:, None]) & jnp.repeat(owned, ps, axis=1)
    valid = valid & (
        (window <= 0) | (t > seq_lens[:, None] - 1 - window)
    )
    scores = jnp.where(valid[:, None, :], scores, -1e30)
    m = jnp.max(scores, axis=-1)  # [B, H]
    p = jnp.exp(scores - m[..., None])
    p = jnp.where(valid[:, None, :], p, 0.0)  # fully-masked rows stay 0
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum(
        "bht,bthd->bhd", p, v.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    return acc, m, l


def sp_decode_attention_and_write(
    q,  # [B, H, hd] roped queries
    k_t,  # [B, KV, hd] current token's roped keys
    v_t,  # [B, KV, hd]
    k_pages_l,  # [KV, P, ps, hd] (sp-sharded on the pool dim under jit)
    v_pages_l,
    page_ids,  # [B] GLOBAL page id of the write target (0 for inactive)
    page_off,  # [B] offset within the page
    page_tables,  # [B, pages_per_seq] GLOBAL page ids
    seq_lens,  # [B]
    mesh: Mesh,
    window=None,  # int32 scalar or None
    softcap: float = 0.0,
    scale=None,
):
    """One decode layer's KV write + attention, sequence-parallel.

    Returns ``(attn [B, H, hd] replicated, k_pages_l, v_pages_l)`` with
    the pool shards updated in place on their owners.
    """
    sp = mesh.shape[AXIS_SP]
    B, H, hd = q.shape
    P_total = k_pages_l.shape[1]
    if P_total % sp:
        raise ValueError(
            f"page pool {P_total} not divisible by sp={sp}"
        )
    shard = P_total // sp
    if scale is None:
        scale = 1.0 / (hd ** 0.5)
    window_arr = jnp.asarray(
        0 if window is None else window, jnp.int32
    )

    def body(kp, vp, q, k_t, v_t, page_ids, page_off, page_tables,
             seq_lens, window_arr):
        idx = jax.lax.axis_index(AXIS_SP)
        base = idx * shard
        # ---- write: my pages take the token, everything else lands in
        # my local trash page 0 (a globally reserved id)
        mine = (page_ids >= base) & (page_ids < base + shard)
        local_write = jnp.where(mine, page_ids - base, 0)
        kp = kp.at[:, local_write, page_off].set(
            jnp.transpose(k_t, (1, 0, 2))
        )
        vp = vp.at[:, local_write, page_off].set(
            jnp.transpose(v_t, (1, 0, 2))
        )
        # ---- partial attention over my resident pages
        owned = (page_tables >= base) & (page_tables < base + shard)
        local_pt = jnp.where(owned, page_tables - base, 0)
        acc, m, l = _partial_paged_attention(
            q, kp, vp, local_pt, owned, seq_lens, window_arr[0],
            softcap, scale,
        )
        # ---- log-sum-exp merge across the sp axis
        m_g = jax.lax.pmax(m, AXIS_SP)
        corr = jnp.exp(m - m_g)[..., None]
        acc_g = jax.lax.psum(acc * corr, AXIS_SP)
        l_g = jax.lax.psum(l * jnp.exp(m - m_g), AXIS_SP)
        out = acc_g / jnp.maximum(l_g, 1e-30)[..., None]
        return out.astype(q.dtype), kp, vp

    tp_ax = _tp_axis(mesh, H, k_t.shape[1])
    pool = P(tp_ax, AXIS_SP, None, None)
    heads = P(None, tp_ax, None)  # q [B,H,hd] / k_t,v_t [B,KV,hd]
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(pool, pool, heads, heads, heads, P(), P(), P(), P(),
                  P()),
        out_specs=(heads, pool, pool),
        check_vma=False,
    )
    return fn(
        k_pages_l, v_pages_l, q, k_t, v_t, page_ids, page_off,
        page_tables, seq_lens, window_arr.reshape(1),
    )


def _partial_suffix_attention(
    q,  # [B, S, H, hd] roped suffix queries (absolute positions)
    k_local,  # [KV, P/sp, ps, hd] this shard's page block
    v_local,
    local_ct,  # [B, ctx_pages] LOCAL ctx-window page indices (0 => not mine)
    owned,  # [B, ctx_pages] bool: ctx page lives on this shard
    prefix_lens,  # [B] global position of q[:, 0]
    total_lens,  # [B] prefix + real suffix
    window,  # [] int32; >0 => sliding window
    softcap: float,
    scale: float,
    block_pages: int = 16,
):
    """Blockwise unnormalized flash partials of suffix queries vs the
    locally resident slice of the paged context window.  Returns
    ``(acc [B,S,H,hd], m [B,S,H], l [B,S,H])`` fp32 — the multi-token
    generalization of ``_partial_paged_attention`` (no [B,S,H,ctx]
    score materialization; ctx blocks stream through a scan)."""
    B, S, H, hd = q.shape
    KV = k_local.shape[0]
    ps = k_local.shape[2]
    n_rep = H // KV
    ctx_pages = local_ct.shape[1]
    if ctx_pages == 0:
        return (
            jnp.zeros((B, S, H, hd), jnp.float32),
            jnp.full((B, S, H), -1e30, jnp.float32),
            jnp.zeros((B, S, H), jnp.float32),
        )
    block_pages = min(block_pages, ctx_pages)
    # Pad the page tables up to a block multiple instead of shrinking
    # the block (a prime ctx_pages would otherwise degrade to 1-page
    # blocks); padded entries carry owned=False so the valid mask zeroes
    # their contribution.
    pad = (-ctx_pages) % block_pages
    if pad:
        local_ct = jnp.pad(local_ct, ((0, 0), (0, pad)))
        owned = jnp.pad(owned, ((0, 0), (0, pad)))
        ctx_pages += pad
    n_blocks = ctx_pages // block_pages

    from vgate_tpu.ops.attention import repeat_kv

    q32 = q.astype(jnp.float32) * scale
    q_pos = prefix_lens[:, None] + jnp.arange(S)[None, :]  # [B, S]

    def body(carry, blk):
        acc, m, l = carry
        pt_blk = jax.lax.dynamic_slice_in_dim(
            local_ct, blk * block_pages, block_pages, 1
        )  # [B, block_pages]
        own_blk = jax.lax.dynamic_slice_in_dim(
            owned, blk * block_pages, block_pages, 1
        )
        bk = block_pages * ps
        k_blk = repeat_kv(
            jnp.moveaxis(
                k_local[:, pt_blk].reshape(KV, B, bk, hd), 0, 2
            ),
            n_rep,
        ).astype(jnp.float32)  # [B, bk, H, hd]
        v_blk = repeat_kv(
            jnp.moveaxis(
                v_local[:, pt_blk].reshape(KV, B, bk, hd), 0, 2
            ),
            n_rep,
        ).astype(jnp.float32)
        # global key positions of this block's tokens
        t = (blk * block_pages + jnp.arange(block_pages)) * ps
        t = (t[:, None] + jnp.arange(ps)[None, :]).reshape(bk)[None, None]
        valid = (
            (t <= q_pos[:, :, None])
            & (t < total_lens[:, None, None])
            & jnp.repeat(own_blk, ps, axis=1)[:, None, :]
        )
        valid = valid & (
            (window <= 0) | (q_pos[:, :, None] - t < window)
        )
        scores = jnp.einsum(
            "bshd,bthd->bsth", q32, k_blk,
            preferred_element_type=jnp.float32,
        )  # [B, S, bk, H]
        if softcap:
            scores = jnp.tanh(scores / softcap) * softcap
        scores = jnp.where(valid[..., None], scores, -1e30)
        m_cur = jnp.max(scores, axis=2)  # [B, S, H]
        m_new = jnp.maximum(m, m_cur)
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(scores - m_new[:, :, None, :])
        p = jnp.where(valid[..., None], p, 0.0)
        l_new = alpha * l + jnp.sum(p, axis=2)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bsth,bthd->bshd", p, v_blk,
            preferred_element_type=jnp.float32,
        )
        return (acc_new, m_new, l_new), None

    acc = jnp.zeros((B, S, H, hd), jnp.float32)
    m = jnp.full((B, S, H), -1e30, jnp.float32)
    l = jnp.zeros((B, S, H), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(body, (acc, m, l), jnp.arange(n_blocks))
    return acc, m, l


def sp_suffix_attention_and_write(
    q,  # [B, S, H, hd] roped suffix queries
    k_s,  # [B, S, KV, hd] fresh roped suffix keys
    v_s,  # [B, S, KV, hd]
    k_pages_l,  # [KV, P, ps, hd] (sp-sharded on the pool dim under jit)
    v_pages_l,
    suffix_page_tables,  # [B, S // ps] GLOBAL page ids the suffix fills
    ctx_page_tables,  # [B, ctx_pages] GLOBAL ids covering prefix+suffix
    prefix_lens,  # [B] global position of q[:, 0] (page-aligned)
    total_lens,  # [B] prefix + real suffix
    mesh: Mesh,
    window=None,  # int32 scalar or None
    softcap: float = 0.0,
    scale=None,
):
    """One suffix-prefill layer's KV write + attention, sequence-parallel
    — the prefix-cache path on an sp-sharded page pool (the r3 gate
    turned prefix caching off under sp; long-context serving is exactly
    where shared-prefix reuse pays, VERDICT r3 next-7).

    Each shard writes the suffix pages it owns (everything else lands in
    its local trash page 0, same trick as ``sp_decode_attention_and_
    write``), computes blockwise flash partials of ALL suffix queries vs
    its locally resident slice of the context window, and the partials
    LSE-merge across sp.  Per-layer ICI traffic is O(B·S·H·hd) partials
    — never the prefix KV itself, which stays sharded.  Returns
    ``(attn [B, S, H, hd] replicated, k_pages_l, v_pages_l)``.
    """
    sp = mesh.shape[AXIS_SP]
    B, S, H, hd = q.shape
    KV = k_s.shape[2]
    P_total = k_pages_l.shape[1]
    ps = k_pages_l.shape[2]
    if P_total % sp:
        raise ValueError(f"page pool {P_total} not divisible by sp={sp}")
    shard = P_total // sp
    n_pages = S // ps
    if scale is None:
        scale = 1.0 / (hd ** 0.5)
    window_arr = jnp.asarray(
        0 if window is None else window, jnp.int32
    )

    def body(kp, vp, q, k_s, v_s, spt, ctx_pt, prefix_lens, total_lens,
             window_arr):
        idx = jax.lax.axis_index(AXIS_SP)
        base = idx * shard
        # ---- write: my suffix pages take their tokens, every other
        # page (and padding, global id 0) lands in my local trash 0
        mine = (spt >= base) & (spt < base + shard)
        local_spt = jnp.where(mine, spt - base, 0)  # [B, n_pages]
        # [B, S, KV, hd] -> [KV, B, n_pages, ps, hd] (head-major pages)
        k_w = jnp.transpose(
            k_s.reshape(B, n_pages, ps, KV, hd), (3, 0, 1, 2, 4)
        )
        v_w = jnp.transpose(
            v_s.reshape(B, n_pages, ps, KV, hd), (3, 0, 1, 2, 4)
        )
        kp = kp.at[:, local_spt].set(k_w)
        vp = vp.at[:, local_spt].set(v_w)
        # ---- partial attention over my resident ctx pages
        owned = (ctx_pt >= base) & (ctx_pt < base + shard)
        local_ct = jnp.where(owned, ctx_pt - base, 0)
        acc, m, l = _partial_suffix_attention(
            q, kp, vp, local_ct, owned, prefix_lens, total_lens,
            window_arr[0], softcap, scale,
        )
        # ---- log-sum-exp merge across the sp axis
        m_g = jax.lax.pmax(m, AXIS_SP)
        corr = jnp.exp(m - m_g)[..., None]
        acc_g = jax.lax.psum(acc * corr, AXIS_SP)
        l_g = jax.lax.psum(l * jnp.exp(m - m_g), AXIS_SP)
        out = acc_g / jnp.maximum(l_g, 1e-30)[..., None]
        return out.astype(q.dtype), kp, vp

    tp_ax = _tp_axis(mesh, H, KV)
    pool = P(tp_ax, AXIS_SP, None, None)
    heads = P(None, None, tp_ax, None)  # [B,S,H|KV,hd]
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(pool, pool, heads, heads, heads, P(), P(), P(), P(),
                  P()),
        out_specs=(heads, pool, pool),
        check_vma=False,
    )
    return fn(
        k_pages_l, v_pages_l, q, k_s, v_s, suffix_page_tables,
        ctx_page_tables, prefix_lens, total_lens, window_arr.reshape(1),
    )


def sp_multitok_attention_and_write(
    q,  # [B, S, H, hd] roped candidate queries
    k_t,  # [B, S, KV, hd] roped candidate keys
    v_t,  # [B, S, KV, hd]
    k_pages_l,  # [KV, P, ps, hd] (sp-sharded on the pool dim under jit)
    v_pages_l,
    page_ids,  # [B, S] GLOBAL page id per candidate (0 = trash)
    page_off,  # [B, S] offset within the page
    ctx_page_tables,  # [B, ctx_pages] GLOBAL ids covering the window
    positions0,  # [B] global position of q[:, 0] (NOT page-aligned)
    total_lens,  # [B] positions0 + real candidates
    mesh: Mesh,
    window=None,
    softcap: float = 0.0,
    scale=None,
):
    """One speculative-verify layer's KV write + attention on an
    sp-sharded pool (the r3 spec x sp gate's replacement).

    Differs from ``sp_suffix_attention_and_write`` only in the write:
    candidates start at an arbitrary position, so each token scatters
    individually to its (page, offset) — owners take their tokens,
    everything else lands in the shard's local trash page 0.  The
    blockwise partial attention + LSE merge are shared.  Returns
    ``(attn [B, S, H, hd] replicated, k_pages_l, v_pages_l)``.
    """
    sp = mesh.shape[AXIS_SP]
    B, S, H, hd = q.shape
    P_total = k_pages_l.shape[1]
    if P_total % sp:
        raise ValueError(f"page pool {P_total} not divisible by sp={sp}")
    shard = P_total // sp
    if scale is None:
        scale = 1.0 / (hd ** 0.5)
    window_arr = jnp.asarray(
        0 if window is None else window, jnp.int32
    )

    def body(kp, vp, q, k_t, v_t, page_ids, page_off, ctx_pt,
             positions0, total_lens, window_arr):
        idx = jax.lax.axis_index(AXIS_SP)
        base = idx * shard
        mine = (page_ids >= base) & (page_ids < base + shard)
        local_ids = jnp.where(mine, page_ids - base, 0)  # [B, S]
        # [B, S, KV, hd] -> [KV, B, S, hd] per-token scatter
        kp = kp.at[:, local_ids, page_off].set(
            jnp.transpose(k_t, (2, 0, 1, 3))
        )
        vp = vp.at[:, local_ids, page_off].set(
            jnp.transpose(v_t, (2, 0, 1, 3))
        )
        owned = (ctx_pt >= base) & (ctx_pt < base + shard)
        local_ct = jnp.where(owned, ctx_pt - base, 0)
        acc, m, l = _partial_suffix_attention(
            q, kp, vp, local_ct, owned, positions0, total_lens,
            window_arr[0], softcap, scale,
        )
        m_g = jax.lax.pmax(m, AXIS_SP)
        corr = jnp.exp(m - m_g)[..., None]
        acc_g = jax.lax.psum(acc * corr, AXIS_SP)
        l_g = jax.lax.psum(l * jnp.exp(m - m_g), AXIS_SP)
        out = acc_g / jnp.maximum(l_g, 1e-30)[..., None]
        return out.astype(q.dtype), kp, vp

    tp_ax = _tp_axis(mesh, H, k_t.shape[2])
    pool = P(tp_ax, AXIS_SP, None, None)
    heads = P(None, None, tp_ax, None)  # [B,S,H|KV,hd]
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(pool, pool, heads, heads, heads, P(), P(), P(), P(),
                  P(), P()),
        out_specs=(heads, pool, pool),
        check_vma=False,
    )
    return fn(
        k_pages_l, v_pages_l, q, k_t, v_t, page_ids, page_off,
        ctx_page_tables, positions0, total_lens, window_arr.reshape(1),
    )


__all__ = [
    "reserved_page_ids",
    "sp_decode_attention_and_write",
    "sp_suffix_attention_and_write",
    "sp_multitok_attention_and_write",
]
