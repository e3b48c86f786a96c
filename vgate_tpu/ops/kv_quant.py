"""int8 paged-KV quantization: quantize-on-write, dequantize-on-read.

Decode is HBM-bound (ROADMAP "Attack the decode roofline"): the dominant
per-token HBM traffic is reading resident KV pages, so halving page
bytes is a direct roofline lever AND doubles resident-sequence capacity
for the same HBM budget.  With ``kv_cache.dtype: int8`` the page pools
store int8 K/V plus per-(page, head, slot) bf16 scales; quantization
happens at every KV WRITE site (batched prefill, suffix/chunked
prefill, the decode chunk body, spec-verify, the radix COW copy /
unaligned scatter — all in models/decoder.py + engine_core.py) and
dequantization happens where KV is READ: inside the Pallas
paged-attention VMEM online-softmax loop (ops/pallas/paged_attention.py)
and in its jnp twin (ops/attention.py).  HBM only ever moves int8.

Design choices:

* **Per-token-per-head symmetric scales** (one bf16 scale per
  (layer, kv_head, page, slot), stored in a page-indexed pool next to
  the K/V pools so pages stay the unit of sharing — the radix tree and
  COW copy page ids, and the scales travel with them for free).
  Per-token granularity makes quantization *path-independent*: a token
  quantizes identically whether written by batched prefill, a mid-page
  COW scatter or a decode step, so shared pages never need rescaling
  and there is no read-modify-write on the decode hot path (a per-page
  running-max scale would require requantizing resident slots on every
  decode write, compounding rounding error).
* **bf16 scale storage**: per token-head the page costs
  ``head_dim + 2`` bytes vs bf16's ``2 * head_dim`` — a 1.94x
  capacity gain at head_dim 64 and 1.97x at 128 (the >= 1.9x
  acceptance floor holds for every registered serving family).
* **Linearity-exact in-kernel dequant**: ``q . (k_q * s_k) =
  (q . k_q) * s_k`` and ``sum_t p_t * (v_q_t * s_v_t) =
  sum_t (p_t * s_v_t) . v_q_t`` — the Pallas kernels fold scales into
  the score row / softmax weights and never materialize a dequantized
  KV tile.

The pool rides through jit/scan/donation as a ``QuantPages`` NamedTuple
(an automatic JAX pytree), so the engine's threading — scan carry on
plain meshes, per-layer slices in the sp/pp relays, buffer donation — is
unchanged.
"""

from __future__ import annotations

from typing import NamedTuple, Union

import jax
import jax.numpy as jnp

# Scale storage dtype: bf16's ~0.4% relative step is far below int8's
# own ~0.8%-of-absmax quantization step, and halves scale bytes vs f32
# (the capacity-ratio floor needs the narrow scale at small head_dim).
SCALE_DTYPE = jnp.bfloat16
# symmetric int8: +-127 (not -128, so dequant is sign-symmetric)
QMAX = 127.0
# bytes one token-slot of one kv head spends on its scale
SCALE_BYTES = jnp.dtype(SCALE_DTYPE).itemsize


class QuantPages(NamedTuple):
    """An int8 KV page pool + its per-(page, head, slot) scale pool.

    ``data``: int8 ``[(L,) KV, P, ps, hd]``; ``scale``: bf16 with the
    same shape minus the trailing ``hd``.  Registered as a pytree by
    virtue of being a NamedTuple, so lax.scan threads it as xs/ys or
    carry and jit donation covers both leaves.  ``shape``/``dtype``
    mirror the data pool so geometry probes (``k_pages.shape[3]``)
    keep working unchanged.
    """

    data: jax.Array
    scale: jax.Array

    @property
    def shape(self):
        return self.data.shape

    @property
    def dtype(self):
        return self.data.dtype

    @property
    def ndim(self):
        return self.data.ndim


KVPool = Union[jax.Array, QuantPages]


def is_quantized(pool) -> bool:
    return isinstance(pool, QuantPages)


def by_pairs(pool) -> bool:
    """Whether ``pool`` holds its token rows by PAIRS, ``[L, KV, P, ps /
    2, 2, hd]``: the latent array of a spec that picks (learned sparse
    attention), whose decode kernel fetches single picked rows and can
    address nothing smaller than a pair (ops/pallas/dsa.py).  The same
    bytes in the same order as ``[L, KV, P, ps, hd]``; a page is still
    one leading index.  Such a pool always has its layer dimension.
    (The pool of GQA attention under a selection has six dimensions too,
    its pairs a token's K over its V: ``ModelSpec.kv_rows`` says which,
    and its writes and reads are ops/dsa.py ``kv_rows_*``, not this
    module's.)"""
    return pool.ndim == 6


def page_tokens(pool, kv_rows: bool = False) -> int:
    """Tokens a page of ``pool`` holds, whichever way its rows lie.
    ``kv_rows`` (``ModelSpec.kv_rows``): the pool's pairs are a token's
    K over its V, ``[L, 1, P, ps, 2, W]``, not two tokens."""
    if kv_rows:
        return pool.shape[3]
    return pool.shape[3] * 2 if by_pairs(pool) else pool.shape[-2]


def dtype_short_name(dtype) -> str:
    """Reporting name for /stats, drills and bench artifacts — one
    definition site (engine_core stamps KVGeometry.kv_dtype with it)."""
    return (
        str(jnp.dtype(dtype).name)
        .replace("bfloat16", "bf16")
        .replace("float32", "f32")
        .replace("float16", "f16")
    )


def quantize(x: jax.Array):
    """Symmetric per-token-per-head int8 quantization over the trailing
    head_dim: returns ``(q int8 [..., hd], s SCALE_DTYPE [...])``.

    The scale is computed in f32, STORED narrow, and the quantization
    divides by the *stored* (rounded) scale so ``q * s`` reconstructs
    against exactly what the reader will see.  absmax-0 rows (zero
    pages, padding) get scale 1 so dequant stays exactly 0.
    """
    x32 = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x32), axis=-1)
    s = jnp.where(amax > 0, amax / QMAX, 1.0).astype(SCALE_DTYPE)
    q = jnp.clip(
        jnp.round(x32 / s.astype(jnp.float32)[..., None]), -QMAX, QMAX
    ).astype(jnp.int8)
    return q, s


def dequantize(q: jax.Array, s: jax.Array) -> jax.Array:
    """f32 reconstruction; ``s`` broadcasts over the trailing head_dim."""
    return q.astype(jnp.float32) * s.astype(jnp.float32)[..., None]


def kv_write(pool: KVPool, idx: tuple, value: jax.Array) -> KVPool:
    """``pool.at[idx].set(value)`` for every KV write site, quantizing
    on write for int8 pools.

    ``idx`` indexes every pool dim except the trailing head_dim (the
    update value carries it); the scale pool — same shape minus hd —
    takes the identical index, so one expression serves whole-page
    prefill sets, the COW/spec per-token scatters and the decode
    single-slot write.  For plain pools this is exactly the original
    ``.at[...].set(...)``.
    """
    if is_quantized(pool):
        q, s = quantize(value)
        return QuantPages(
            pool.data.at[idx].set(q), pool.scale.at[idx].set(s)
        )
    return pool.at[idx].set(value)


def kv_write_tokens(
    pool: KVPool, page_ids: jax.Array, page_off: jax.Array,
    value: jax.Array, layer=None,
) -> KVPool:
    """Per-token KV write: ``value`` ``[..., KV, hd]`` (k/v as projected)
    lands at ``pool[(layer,) kv, page_ids[...], page_off[...]]``.

    The kv-head dim is indexed EXPLICITLY rather than sliced, so the
    scatter's update window is one head_dim row.  With a slice there the
    window is (KV, hd), and XLA:TPU's layout assignment then makes those
    two the minor dims of the WHOLE pool (``{4,1,3,2,0:T(2,128)}``) —
    a layout the Pallas kernels cannot read, so every step program paid
    whole-pool relayout copies at entry, at exit and per layer inside
    the decode loop, plus a pool-sized temporary (PERF.md "Bring-up",
    memory_analysis table; tests/test_tpu_aot.py pins the fix)."""
    kv = jnp.arange(value.shape[-2], dtype=jnp.int32)
    off = page_off[..., None]
    # a pool by pairs: the token's row is one of its pair's two
    where = (off // 2, off % 2) if by_pairs(pool) else (off,)
    idx = (kv, page_ids[..., None]) + where
    if layer is not None:
        idx = (layer,) + idx
    return kv_write(pool, idx, value)


def kv_write_pages(
    pool: KVPool, page_tables: jax.Array, value: jax.Array, layer=None
) -> KVPool:
    """Whole-page KV write (aligned prompt passes): ``value``
    ``[..., KV, ps, hd]`` lands at ``pool[(layer,) kv, page_tables[...]]``
    — the kv-head dim indexed explicitly for the same reason as
    ``kv_write_tokens``, so the window is one contiguous (ps, hd) page."""
    kv = jnp.arange(value.shape[-3], dtype=jnp.int32)
    if by_pairs(pool):
        # the jnp path's (a CPU): on the chip either window, a page of
        # pairs or a row, makes XLA:TPU re-lay or flatten the WHOLE pool
        # (4.7 GB in the prompt program of the GLM-5.2 cut), and the
        # prompt's pages go by ops/pallas/dsa.py dsa_write_pages_pallas
        value = value.reshape(value.shape[:-2] + pool.shape[-3:])
    idx = (kv, page_tables[..., None])
    if layer is not None:
        idx = (layer,) + idx
    return kv_write(pool, idx, value)


def gather_pages(pool: KVPool, page_tables: jax.Array, layer=None):
    """Gather each slot's page window from the pool — the shared front
    half of the jnp paged-attention twins (ops/attention.py).

    Returns ``[KV, B, n_pages, ps, hd]``: raw dtype for plain pools,
    dequantized f32 for int8 pools (the same f32 the Pallas kernel
    computes its dots in).  With ``layer`` (a traced scalar) the pool
    carries a leading [L] dim and ONE gather indexes (layer, page) —
    only the live pages of that layer are read, never a full per-layer
    slice — with the kv-head dim passed through as a window dim, so a
    tp-sharded pool partitions along it.  A pool by pairs comes out by
    pairs, ``[KV, B, n_pages, ps / 2, 2, hd]``.  (Folding layer and head into
    one flat index reshapes across the sharded dim, and GSPMD then
    all-gathers the whole pool onto every chip.)
    """
    quant = is_quantized(pool)
    data = pool.data if quant else pool
    if layer is not None:
        # advanced (layer, pages) indices around a slice: the broadcast
        # [B, n] dims lead, the sliced kv-head dim follows
        take = lambda x: jnp.moveaxis(x[layer, :, page_tables], 2, 0)
    else:
        take = lambda x: x[:, page_tables]
    if quant:
        return dequantize(take(data), take(pool.scale))
    return take(data)


def copy_page_prefix(
    pool: KVPool, src, dst, keep_mask: jax.Array
) -> KVPool:
    """Radix copy-on-write page copy (step_programs._cow_copy_pages):
    overwrite the first slots of page ``dst`` with page ``src``'s where
    ``keep_mask`` ([ps] bool) holds, across every layer and head.  For
    int8 pools the SCALES copy with the data — a shared head keeps the
    exact quantization it was written with, so a COW'd page dequantizes
    bit-identically to the page it was copied from."""
    if is_quantized(pool):
        keep_d = keep_mask[:, None]  # [ps, 1] broadcasts over hd
        data = pool.data.at[..., dst, :, :].set(
            jnp.where(
                keep_d, pool.data[..., src, :, :], pool.data[..., dst, :, :]
            )
        )
        scale = pool.scale.at[..., dst, :].set(
            jnp.where(
                keep_mask, pool.scale[..., src, :], pool.scale[..., dst, :]
            )
        )
        return QuantPages(data, scale)
    keep_d = keep_mask[:, None]
    return pool.at[..., dst, :, :].set(
        jnp.where(
            keep_d, pool[..., src, :, :], pool[..., dst, :, :]
        )
    )
