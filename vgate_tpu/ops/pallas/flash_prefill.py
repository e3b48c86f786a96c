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
k-blocks (entirely above the diagonal) skip their compute via ``pl.when``.

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



def _kernel(
    # scalar prefetch (SMEM)
    seq_lens_ref,  # [B] int32 — real key length per batch row
    q_offsets_ref,  # [B] int32 — global position of query row 0
    window_ref,  # [1] int32; >0 => attend only to the last `window` keys
    # then: k_starts_ref [B] int32 where ``from_key`` (a fourth scalar
    # prefetch: keys before it are nobody's); the inputs (VMEM blocks)
    # q_ref [1, 1, block_q, hd], k_ref and v_ref [1, 1, block_k, hd];
    # mask_ref [1, block_q, block_k] int8 where ``masked``;
    # the output out_ref [1, 1, block_q, hd]; the scratch acc_ref
    # [block_q, hd] f32, m_ref [block_q, 128] f32 running max
    # (column-broadcast) and l_ref [block_q, 128] f32 running denom
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
):
    k_starts_ref = rest[0] if from_key else None
    q_ref, k_ref, v_ref = rest[from_key:from_key + 3]
    mask_ref = rest[from_key + 3] if masked else None
    out_ref, acc_ref, m_ref, l_ref = rest[-4:]
    b = pl.program_id(0)
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    seq_len = seq_lens_ref[b]
    q_off = q_offsets_ref[b]
    window = window_ref[0]
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

    # a k-block strictly above the causal diagonal — or entirely below the
    # sliding window of every query row in the block — contributes nothing
    causal_live = k_start <= q_start + block_q - 1
    window_live = (window <= 0) | (
        k_start + block_k - 1 >= q_start - window + 1
    )

    # a block of keys past the row's length holds nothing a query sees;
    # a block of QUERIES past it is padding, which a caller that never
    # reads such rows leaves out (``skip_padding``: they come out zero;
    # a prompt that fills two thirds of its bucket skips a third of the
    # lower triangle)
    real = k_start < seq_len
    if skip_padding:
        real = real & (q_start < seq_len)
    live = causal_live & window_live & real
    if from_key:  # a block of keys before the row's first
        live = live & (k_start + block_k - 1 >= k_starts_ref[b])
    if band:
        live = live & (kb >= 0)

    @pl.when(live)
    def _():
        q = q_ref[0, 0].astype(jnp.float32) * scale  # [block_q, hd]
        k = k_ref[0, 0].astype(jnp.float32)  # [block_k, hd]
        v = v_ref[0, 0].astype(jnp.float32)

        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [block_q, block_k]
        if softcap:
            scores = jnp.tanh(scores / softcap) * softcap
        q_pos = q_start + jax.lax.broadcasted_iota(
            jnp.int32, scores.shape, 0
        )
        k_pos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, scores.shape, 1
        )
        mask = (k_pos <= q_pos) & (k_pos < seq_len)
        mask = mask & ((window <= 0) | (q_pos - k_pos < window))
        if from_key:
            mask = mask & (k_pos >= k_starts_ref[b])
        if masked:  # a selection (ops/dsa.py): the caller's tile
            mask = mask & (mask_ref[0] != 0)
        scores = jnp.where(mask, scores, -1e30)

        m_prev = m_ref[:, :1]  # [block_q, 1]
        m_cur = jnp.max(scores, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new)  # [block_q, block_k]
        if masked or from_key:  # a row with nothing seen in this block
            # and none before it: exp(-1e30 + 1e30) would count every key
            p = jnp.where(mask, p, 0.0)
        l_ref[...] = jnp.broadcast_to(
            alpha * l_ref[:, :1] + jnp.sum(p, axis=-1, keepdims=True),
            l_ref.shape,
        )
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(ki == n_k - 1)
    def _():
        denom = jnp.maximum(l_ref[:, :1], 1e-30)
        out_ref[0, 0] = (acc_ref[...] / denom).astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_q", "block_k", "interpret", "softcap", "scale",
                     "band", "name", "skip_padding"),
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
    k_block = (lambda qi, ki: ki) if not band else (
        lambda qi, ki: jnp.maximum((qi + 1) * ratio - n_k + ki, 0))
    if q_offsets is None:
        q_offsets = jnp.zeros((B,), jnp.int32)
    if window is None:
        window_arr = jnp.zeros((1,), jnp.int32)
    else:
        window_arr = jnp.asarray(window, jnp.int32).reshape(1)

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
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3 + (k_starts is not None),
        grid=(B, H, n_q, n_k),
        in_specs=[
            pl.BlockSpec(
                (1, 1, block_q, hd),
                lambda b, h, qi, ki, *pf: (b, h, qi, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, 1, block_k, hd),
                lambda b, h, qi, ki, *pf: (b, h // G, k_block(qi, ki), 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, 1, block_k, hd),
                lambda b, h, qi, ki, *pf: (b, h // G, k_block(qi, ki), 0),
                memory_space=pltpu.VMEM,
            ),
        ] + ([] if mask is None else [
            pl.BlockSpec(
                (1, block_q, block_k),
                lambda b, h, qi, ki, *pf: (b, qi, k_block(qi, ki)),
                memory_space=pltpu.VMEM,
            ),
        ]),
        out_specs=pl.BlockSpec(
            (1, 1, block_q, hd),
            lambda b, h, qi, ki, *pf: (b, h, qi, 0),
            memory_space=pltpu.VMEM,
        ),
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, S, hd), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024,
        ),
        name=name,
    )(
        seq_lens.astype(jnp.int32), q_offsets.astype(jnp.int32),
        window_arr,
        *(() if k_starts is None else (k_starts.astype(jnp.int32),)),
        qt, kt, vt, *(() if mask is None else (mask,)),
    )
    return jnp.transpose(out, (0, 2, 1, 3))


# a window layer's blocks: 128-row key blocks (the window's own size at
# the published 128: a band's first block is the only one the window
# cuts) under query blocks of up to 256 rows, so a query block visits
# 128 + its own 256 keys.  On the v5e at 8,192 rows x 64 heads
# (benchmarks/bench_kernels.py swa_prefill): 6.0 ms, against 7.3 at
# (128, 128), 11.5 at (512, 128), 7.0 at (256, 256) and 6.6 for the
# window as a mask under 1,024-row blocks
SWA_BLOCK_Q, SWA_BLOCK_K = 256, 128


def swa_prefill_attention_pallas(q, k, v, seq_lens, window: int,
                                 block_q: int = SWA_BLOCK_Q,
                                 block_k: int = SWA_BLOCK_K, **kw):
    """A window layer's prompt attention ([B, S, H, hd], every row
    attending to its last ``window`` keys, a static count): the flash
    kernel over a band of ``block_q // block_k + ceil((window - 1) /
    block_k)`` key blocks a query block, launched under a name of its
    own so that a device trace tells it from a full layer's."""
    S = q.shape[1]
    block_k = min(block_k, S)
    block_q = max(block_k, min(block_q, S))
    band = block_q // block_k + -(-(window - 1) // block_k)
    return flash_prefill_attention_pallas(
        q, k, v, seq_lens, block_q=block_q, block_k=block_k,
        window=window, band=band, name="swa_prefill_attention_pallas", **kw)
