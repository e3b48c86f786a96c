"""Pallas flash-attention prefill kernel.

Completes the kernel pair the reference gets opaquely from vLLM (SURVEY.md
section 2.1): ``paged_attention.py`` covers decode, this kernel covers the
prompt pass.  Semantics are pinned by the jnp oracle
``vgate_tpu.ops.attention.causal_prefill_attention`` (and its blockwise twin
``flash_prefill_attention``); the kernel's advantage is that no score matrix
ever exists in HBM — each (batch, head, q-block) program streams key/value
blocks through VMEM with an online-softmax accumulator, so peak memory is
O(block_q · block_k) per core instead of the O(S²) per-head score
materialization of the naive path (~200 MB fp32 at the 2048 bucket).

Grid: ``(B, H, n_q_blocks, n_k_blocks)`` with the key-block axis innermost —
TPU grids execute sequentially over the trailing axis, so the accumulator
lives in VMEM scratch across the k-sweep of one q-block.  Causally dead
k-blocks (entirely above the diagonal) skip their compute via ``pl.when``
and their fetch by naming the last live block again (``_key_block``).

The mask work is done once.  A live tile is classified from scalars: an
INTERIOR tile (its last key at or before its first query and inside the
length, no window and no first key cutting it: five tiles of six where a
prompt of 11 k rows runs in 1,024-row blocks) runs a body with no iota,
no comparison and no ``where``; an EDGE tile runs the position tests.
Under a selection (``mask``) a program takes a BLOCK of heads
(``head_block``, by VMEM arithmetic), turns the int8 tile ONCE into a
float32 bias in VMEM scratch (0 where picked and seen, ``-inf``
elsewhere) and each head adds it to its scores: grid ``(B, H / heads,
n_q_blocks, n_k_blocks)``.  The result is bit for bit the edge body's on
every tile (``_all_edge``, the tests' and the probe's).

Supports chunked prefill via ``q_offsets``: the query rows may start at a
nonzero global position while keys cover the context from position 0.

A window layer (``swa_prefill_attention_pallas``) walks a BAND: its grid's
key axis holds only the ``band`` key blocks that end at a query block's own
last one, which is all a window of ``window`` keys reaches, so a prompt of
8,192 rows costs what 128 + a block's own rows of keys cost a block, not a
sweep of every block below the diagonal.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



# what a launch may hold of VMEM (``vmem_limit_bytes``), and the float32
# ``[block_q, block_k]`` arrays a tile's body has standing beside the
# blocks and the scratch: the scores and the weights of the head at
# work, and on an edge tile the two position arrays and their tests on
# the way to the bias
VMEM_LIMIT_BYTES = 64 * 1024 * 1024
TILE_TEMPORARIES = 6


def head_block(H: int, G: int, block_q: int, block_k: int, hd: int,
               itemsize: int) -> int:
    """Query heads a program of the MASKED form takes (they share the
    mask tile, which becomes a bias once for all of them): the largest
    divisor of ``H`` that is a multiple of ``G`` (the query heads of a
    KV head stay together) and whose blocks, scratch and temporaries fit
    ``VMEM_LIMIT_BYTES``.  At 1,024-row blocks: 8 of Keye's 32 heads of
    128 on 4 (52.5 MiB), 4 of GLM's 8 heads of 256 a group (57 MiB).
    The count is an upper bound: Mosaic took 16 and 8 under the same
    limit on the chip, 4 % shorter launches (PERF.md section 7, PR 54)."""

    def need(hb: int) -> int:
        blocks = 2 * (  # every block is double-buffered
            2 * hb * block_q * hd * itemsize  # q and out
            + 2 * (hb // G) * block_k * hd * itemsize  # k and v
            + block_q * block_k)  # the int8 tile
        scratch = 4 * (hb * block_q * (hd + 2 * 128)  # acc, m, l
                       + block_q * block_k)  # the bias
        body = 4 * (TILE_TEMPORARIES * block_q * block_k
                    + (block_q + 2 * block_k) * hd)  # q, k, v in float32
        return blocks + scratch + body

    fits = [hb for hb in range(G, H + 1, G)
            if H % hb == 0 and need(hb) <= VMEM_LIMIT_BYTES]
    return max(fits, default=G)


def _interior(q_start, k_start, block_q: int, block_k: int, seq_len,
              window, k_first=0):
    """Whether EVERY position test of a live tile is true for every
    element of it, from scalars: the tile's last key at or before its
    first query and inside the length, no window reaching short of its
    first key from its last query, no key before the row's first.  The
    kernel's own predicate (traced scalars) and ``tile_counts``'
    (Python integers)."""
    return ((k_start + block_k - 1 <= q_start)
            & (k_start + block_k <= seq_len)
            & ((window <= 0) | (q_start + block_q - 1 - k_start < window))
            & (k_start >= k_first))


def _live(q_start, k_start, block_q: int, block_k: int, seq_len, window,
          k_first=0, skip_padding: bool = False):
    """Whether a tile holds anything a query sees (``_interior``'s twin:
    the kernel's predicate and ``tile_counts``')."""
    # a k-block strictly above the causal diagonal — or entirely below the
    # sliding window of every query row in the block — contributes nothing
    live = ((k_start <= q_start + block_q - 1)
            & ((window <= 0) | (k_start + block_k - 1 >= q_start - window + 1))
            # a block of keys past the row's length holds nothing a query
            # sees, nor one before the row's first key
            & (k_start < seq_len) & (k_start + block_k - 1 >= k_first))
    if skip_padding:
        # a block of QUERIES past the length is padding, which a caller
        # that never reads such rows leaves out: they come out zero (a
        # prompt that fills two thirds of its bucket skips a third of
        # the lower triangle)
        live = live & (q_start < seq_len)
    return live


def _key_block(qi, ki, block_q: int, block_k: int, seq_len, q_off,
               skip_padding: bool = False):
    """The key block grid step ``ki`` of query block ``qi`` holds.  A
    step past the last block a query of ``qi`` sees (above the diagonal,
    or past the row's length: dead, ``_live``) names that last block
    AGAIN, and so does every step of a padding query block that the
    launch leaves out (``skip_padding``), so that their K, V and mask
    tiles are not fetched: a block whose index does not change is not
    copied, and a dead step is a grid step and nothing else.  (A dead
    step's fetch was 2 MiB at GLM's shape and those fetches 41 % of the
    launch: PERF.md section 6, PR 54.)"""
    last = jnp.minimum(q_off + (qi + 1) * block_q, seq_len) - 1
    last = jnp.maximum(last, 0) // block_k
    if skip_padding:
        ki = jnp.where(q_off + qi * block_q >= seq_len, last, ki)
    return jnp.minimum(ki, last)


def _kernel(
    # scalar prefetch (SMEM)
    seq_lens_ref,  # [B] int32 — real key length per batch row
    q_offsets_ref,  # [B] int32 — global position of query row 0
    window_ref,  # [1] int32; >0 => attend only to the last `window` keys
    # then: k_starts_ref [B] int32 where ``from_key`` (a fourth scalar
    # prefetch: keys before it are nobody's); the inputs (VMEM blocks)
    # q_ref [1, heads, block_q, hd], k_ref and v_ref [1, heads // group,
    # block_k, hd]; mask_ref [1, block_q, block_k] int8 where ``masked``;
    # the output out_ref [1, heads, block_q, hd]; the scratch acc_ref
    # [heads, block_q, hd] f32, m_ref [heads, block_q, 128] f32 running
    # max (column-broadcast), l_ref [heads, block_q, 128] f32 running
    # denom and, where ``masked``, bias_ref [block_q, block_k] f32
    *rest,
    block_q: int,
    block_k: int,
    n_k: int,
    softcap: float,
    scale: float,
    band: int = 0,
    masked: bool = False,
    skip_padding: bool = False,
    from_key: bool = False,
    heads: int = 1,  # query heads a program (more than 1: ``masked``)
    group: int = 1,  # of them to a KV head of the program's
    all_edge: bool = False,  # the tests' and the probe's: no interior body
):
    k_starts_ref = rest[0] if from_key else None
    q_ref, k_ref, v_ref = rest[from_key:from_key + 3]
    mask_ref = rest[from_key + 3] if masked else None
    scratch = rest[from_key + 3 + masked:]
    out_ref, acc_ref, m_ref, l_ref = scratch[:4]
    bias_ref = scratch[4] if masked else None
    b = pl.program_id(0)
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    seq_len = seq_lens_ref[b]
    q_off = q_offsets_ref[b]
    window = window_ref[0]
    k_first = k_starts_ref[b] if from_key else 0
    # the key block this step holds: the grid's own, or in a band the
    # one ``band - 1 - ki`` before the query block's last (a block before
    # the first key is the first one again, and dead)
    kb = ki if not band else (qi + 1) * (block_q // block_k) - band + ki

    @pl.when(ki == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)

    # global positions of this block's queries and keys
    q_start = q_off + qi * block_q
    k_start = kb * block_k

    live = _live(q_start, k_start, block_q, block_k, seq_len, window,
                 k_first, skip_padding)
    if band:
        live = live & (kb >= 0)
    # a tile that neither the diagonal, the length, the window nor the
    # row's first key cuts: every test ``positions`` makes is true there
    interior = live & _interior(q_start, k_start, block_q, block_k, seq_len,
                                window, k_first)
    edge = live if all_edge else live & jnp.logical_not(interior)

    def positions():
        """[block_q, block_k]: whether the query at a row may see the
        key at a column, by where both stand."""
        shape = (block_q, block_k)
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        mask = (k_pos <= q_pos) & (k_pos < seq_len)
        mask = mask & ((window <= 0) | (q_pos - k_pos < window))
        if from_key:
            mask = mask & (k_pos >= k_first)
        return mask

    def attend(h, k, v, mask=None, bias=None):
        """Head ``h`` of the program over the tile's keys: with the
        tile's tests (``mask``), with what is hidden as ``-inf`` beside
        the scores (``bias``), or with neither on an interior tile."""
        q = q_ref[0, h].astype(jnp.float32) * scale  # [block_q, hd]
        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [block_q, block_k]
        if softcap:
            scores = jnp.tanh(scores / softcap) * softcap
        if mask is not None:
            scores = jnp.where(mask, scores, -1e30)
        if bias is not None:
            scores = scores + bias

        m_prev = m_ref[h, :, :1]  # [block_q, 1]
        m_cur = jnp.max(scores, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        # (under a bias the running maximum is finite from the start and
        # a hidden key's weight is exp(-inf) = 0: a row with nothing seen
        # keeps m = -1e30, alpha = 1, p = 0)
        p = jnp.exp(scores - m_new)  # [block_q, block_k]
        if mask is not None and from_key:
            # a row with nothing seen in this block and none before it:
            # exp(-1e30 + 1e30) would count every key
            p = jnp.where(mask, p, 0.0)
        l_ref[h] = jnp.broadcast_to(
            alpha * l_ref[h, :, :1] + jnp.sum(p, axis=-1, keepdims=True),
            l_ref.shape[1:],
        )
        acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[h] = jnp.broadcast_to(m_new, m_ref.shape[1:])

    def keys(j):
        return (k_ref[0, j].astype(jnp.float32),  # [block_k, hd]
                v_ref[0, j].astype(jnp.float32))

    if not masked:  # one head a program: the tile's tests, or none
        if not all_edge:
            @pl.when(interior)
            def _():
                attend(0, *keys(0))

        @pl.when(edge)
        def _():
            attend(0, *keys(0), mask=positions())
    else:
        # a selection (ops/dsa.py): the caller's int8 tile, the same for
        # every head, becomes a bias ONCE (with the tile's tests on an
        # edge tile), and each head of the program adds it
        def bias(seen=None):
            """0 where the tile picks a key (and ``seen`` lets the query
            see it), ``-inf`` elsewhere.  (The zeros are the tile's own,
            times 0: a select of two constants is no layout Mosaic
            takes.)"""
            tile = mask_ref[0].astype(jnp.float32)
            picked = tile != 0.0
            return jnp.where(picked if seen is None else picked & seen,
                             tile * 0.0, -jnp.inf)

        if not all_edge:
            @pl.when(interior)
            def _():
                bias_ref[...] = bias()

        @pl.when(edge)
        def _():
            bias_ref[...] = bias(positions())

        @pl.when(live)
        def _():
            def kv_head(j, carry):
                k, v = keys(j)

                def head(g, carry):
                    attend(j * group + g, k, v, bias=bias_ref[...])
                    return carry

                return jax.lax.fori_loop(0, group, head, carry)

            jax.lax.fori_loop(0, heads // group, kv_head, 0)

    @pl.when(ki == n_k - 1)
    def _():
        denom = jnp.maximum(l_ref[:, :, :1], 1e-30)
        out_ref[0] = (acc_ref[...] / denom).astype(out_ref.dtype)


def tile_counts(seq_lens, S: int, Sk: int, block_q: int, block_k: int, *,
                q_offsets=None, window: int = 0, band: int = 0,
                k_starts=None, skip_padding: bool = False):
    """(tiles, interior tiles) ONE head of a launch of
    ``flash_prefill_attention_pallas`` computes for rows of these
    lengths (Python integers; the arguments as the launch's): the tiles
    whose body runs at all, and of them those that run the body without
    position tests.  Counted on the host by the kernel's own two
    predicates, for ``/debug/perf -> totals.prefill_attn``."""
    block_q, block_k = min(block_q, S), min(block_k, Sk)
    n_q, n_k = S // block_q, Sk // block_k
    ratio = block_q // block_k
    if band:
        band = min(band, n_k)
    tiles = interior = 0
    for b, seq_len in enumerate(seq_lens):
        q_off = q_offsets[b] if q_offsets is not None else 0
        k_first = k_starts[b] if k_starts is not None else 0
        for qi in range(n_q):
            q_start = q_off + qi * block_q
            blocks = (range(n_k) if not band else
                      range(max((qi + 1) * ratio - band, 0), (qi + 1) * ratio))
            for kb in blocks:
                at = (q_start, kb * block_k, block_q, block_k, int(seq_len),
                      window, k_first)
                if _live(*at, skip_padding):
                    tiles += 1
                    interior += bool(_interior(*at))
    return tiles, interior


@functools.partial(
    jax.jit,
    static_argnames=("block_q", "block_k", "interpret", "softcap", "scale",
                     "band", "name", "skip_padding", "_all_edge"),
)  # (``mask`` is an array: its presence alone is static)
def flash_prefill_attention_pallas(
    q: jnp.ndarray,  # [B, S, H, hd]
    k: jnp.ndarray,  # [B, Sk, KV, hd]
    v: jnp.ndarray,  # [B, Sk, KV, hd]
    seq_lens: jnp.ndarray,  # [B] real key lengths
    q_offsets: jnp.ndarray | None = None,  # [B] global pos of q[:, 0]
    block_q: int = 256,
    block_k: int = 256,
    interpret: bool = False,
    softcap: float = 0.0,
    window=None,  # int32 scalar; >0 => attend only to the last `window`
    scale=None,  # static query scale; default hd**-0.5
    band: int = 0,  # >0: only the last `band` key blocks of a query block
    name=None,  # the launch's name in a device trace
    mask=None,  # [B, S, Sk] int8, nonzero = attend: beside causal + length
    skip_padding: bool = False,  # query blocks past seq_lens: zeros
    k_starts: jnp.ndarray | None = None,  # [B] keys before it: nobody's
    _all_edge: bool = False,  # every tile through the edge body: the
    # tests' and the probe's, to hold the interior body to it bit for bit
) -> jnp.ndarray:
    """Causal (optionally offset) attention. Returns [B, S, H, hd].
    ``band`` (with ``window``, no ``q_offsets`` and ``block_q`` a
    multiple of ``block_k``): see the module's text."""
    B, S, H, hd = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    G = H // KV
    block_q = min(block_q, S)
    block_k = min(block_k, Sk)
    if S % block_q or Sk % block_k:
        raise ValueError(
            f"S={S}/Sk={Sk} must divide block_q={block_q}/block_k={block_k}"
        )
    n_q, n_k = S // block_q, Sk // block_k
    if band:
        if q_offsets is not None or block_q % block_k or Sk != S:
            raise ValueError("a band needs rows from position 0, keys of "
                             "their own and block_q a multiple of block_k")
        n_k = min(band, n_k)
    ratio = block_q // block_k

    def k_block(b, qi, ki, pf):
        if band:
            return jnp.maximum((qi + 1) * ratio - n_k + ki, 0)
        return _key_block(qi, ki, block_q, block_k, pf[0][b], pf[1][b],
                          skip_padding)

    if q_offsets is None:
        q_offsets = jnp.zeros((B,), jnp.int32)
    if window is None:
        window_arr = jnp.zeros((1,), jnp.int32)
    else:
        window_arr = jnp.asarray(window, jnp.int32).reshape(1)
    # heads a program: those that share a mask tile, else one (the query
    # heads of a KV head are ``hb // kvb`` to each of the block's)
    hb = 1 if mask is None else head_block(
        H, G, block_q, block_k, hd, q.dtype.itemsize)
    kvb = max(hb // G, 1)

    # head-major layout so each block's trailing dims are (seq_block, hd)
    qt = jnp.transpose(q, (0, 2, 1, 3))  # [B, H, S, hd]
    kt = jnp.transpose(k, (0, 2, 1, 3))  # [B, KV, Sk, hd]
    vt = jnp.transpose(v, (0, 2, 1, 3))

    kernel = functools.partial(
        _kernel, block_q=block_q, block_k=block_k, n_k=n_k,
        softcap=float(softcap),
        scale=float(scale) if scale is not None else hd ** -0.5,
        band=n_k if band else 0,
        masked=mask is not None,
        skip_padding=skip_padding,
        from_key=k_starts is not None,
        heads=hb, group=hb // kvb, all_edge=_all_edge,
    )
    # the block of KV heads under a block of query heads: the program's
    # ``h`` counts blocks of ``hb`` heads, ``hb // G`` KV heads each (one
    # head a program: its KV head)
    kv_block = (lambda h: h // G) if hb == 1 else (lambda h: h)
    kv_spec = pl.BlockSpec(
        (1, kvb, block_k, hd),
        lambda b, h, qi, ki, *pf: (b, kv_block(h), k_block(b, qi, ki, pf), 0),
        memory_space=pltpu.VMEM,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3 + (k_starts is not None),
        grid=(B, H // hb, n_q, n_k),
        in_specs=[
            pl.BlockSpec(
                (1, hb, block_q, hd),
                lambda b, h, qi, ki, *pf: (b, h, qi, 0),
                memory_space=pltpu.VMEM,
            ),
            kv_spec,
            kv_spec,
        ] + ([] if mask is None else [
            pl.BlockSpec(
                (1, block_q, block_k),
                lambda b, h, qi, ki, *pf: (b, qi, k_block(b, qi, ki, pf)),
                memory_space=pltpu.VMEM,
            ),
        ]),
        out_specs=pl.BlockSpec(
            (1, hb, block_q, hd),
            lambda b, h, qi, ki, *pf: (b, h, qi, 0),
            memory_space=pltpu.VMEM,
        ),
        scratch_shapes=[
            pltpu.VMEM((hb, block_q, hd), jnp.float32),
            pltpu.VMEM((hb, block_q, 128), jnp.float32),
            pltpu.VMEM((hb, block_q, 128), jnp.float32),
        ] + ([] if mask is None else [
            pltpu.VMEM((block_q, block_k), jnp.float32),
        ]),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, S, hd), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        name=name,
    )(
        seq_lens.astype(jnp.int32), q_offsets.astype(jnp.int32),
        window_arr,
        *(() if k_starts is None else (k_starts.astype(jnp.int32),)),
        qt, kt, vt, *(() if mask is None else (mask,)),
    )
    return jnp.transpose(out, (0, 2, 1, 3))


# a window layer's blocks, by its window: key blocks of the window's own
# size (a band's first block is then the only one the window cuts),
# under query blocks of that size and no fewer than 256 rows.  On the
# v5e (benchmarks/bench_kernels.py swa_prefill), at K-EXAONE's window of
# 128, 8,192 rows x 64 heads on 8: (256, 128) 6.0 ms, against 7.3 at
# (128, 128), 11.5 at (512, 128), 7.0 at (256, 256) and 6.6 for the
# window as a mask under 1,024-row blocks (PR 38); at Mellum2's window of
# 1,024, 16,384 rows x 32 heads on 4: (1,024, 1,024) 6.4 ms, a band of
# two blocks, against 8.7 at (512, 512), 10.2 at (1,024, 512), 13.8 at
# (256, 256), 15.0 at (256, 128) (ten grid steps a query block) and 7.2
# for the window as a mask (my chip run, PR 57)
SWA_BLOCK_Q_MIN, SWA_BLOCK_K_MIN, SWA_BLOCK_K_MAX = 256, 128, 1024


def swa_blocks(window: int, rows: int = 0, block_q: int = 0,
               block_k: int = 0) -> tuple:
    """(query rows, key rows) of a window layer's blocks: the largest
    power of two the window holds, between ``SWA_BLOCK_K_MIN`` and
    ``SWA_BLOCK_K_MAX`` (what the full layers' launches take), under
    query blocks of that and at least ``SWA_BLOCK_Q_MIN``: (256, 128)
    at a window of 128, a band of three blocks; (1,024, 1,024) at 1,024,
    a band of two.  ``block_q`` / ``block_k`` force one (the probe, a
    test); ``rows`` holds both to a launch of so many rows."""
    rule_k = min(max(1 << (max(window, 1).bit_length() - 1),
                     SWA_BLOCK_K_MIN), SWA_BLOCK_K_MAX)
    block_k = block_k or rule_k
    block_q = block_q or max(rule_k, SWA_BLOCK_Q_MIN)
    if rows:
        block_k = min(block_k, rows)
        block_q = max(block_k, min(block_q, rows))
    return block_q, block_k


def swa_prefill_attention_pallas(q, k, v, seq_lens, window: int,
                                 block_q: int = 0, block_k: int = 0, **kw):
    """A window layer's prompt attention ([B, S, H, hd], every row
    attending to its last ``window`` keys, a static count): the flash
    kernel over a band of ``block_q // block_k + ceil((window - 1) /
    block_k)`` key blocks a query block (``swa_blocks``), launched under
    a name of its own so that a device trace tells it from a full
    layer's."""
    block_q, block_k = swa_blocks(window, q.shape[1], block_q, block_k)
    band = block_q // block_k + -(-(window - 1) // block_k)
    return flash_prefill_attention_pallas(
        q, k, v, seq_lens, block_q=block_q, block_k=block_k,
        window=window, band=band, name="swa_prefill_attention_pallas", **kw)
