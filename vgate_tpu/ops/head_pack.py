"""Two KV heads of 64 in one 128-lane row of the paged pool.

A head of 64 is half a lane tile: Mosaic refuses a 64-lane page DMA
(tests/test_tpu_aot.py holds the refusal) and XLA's tiled HBM layout
would pad such a row to 128 lanes, half of every page nothing.  A spec
whose heads pair (``ModelSpec.kv_heads_pair``; the engine sets
``kv_head_pack`` 2) keeps its pool as ``[layers, KV / 2, pages, page,
128]``, row ``j`` of a token holding heads ``2j | 2j + 1``: ``[..., KV,
64] -> [..., KV / 2, 128]`` is a reshape, so every page write is the
unpacked one's.

The attention functions that read such a pool are the unpacked ones,
launched at ``(KV / 2 rows, 2 G query heads a row, 128 lanes)``: a query
of head ``2j`` goes in as ``[q | 0]`` and one of head ``2j + 1`` as ``[0
| q]``, so its score against a row is its own head's (the other half
adds exact zeros), the softmax scale is the head's (``64^-0.5``, handed
over, never the row's), and of the 128 lanes of ``sum p v`` its own 64
are the unpacked result, the others the neighbour head's values under
its own weights, dropped.  Query heads keep their order: head ``h``
reads KV head ``h // G``, which is row ``h // 2G``, half ``(h // G) %
2``.  The bytes a launch reads are the unpadded ones; the products are
twice the unpacked ones, which a decode step bound by its page reads
does not see.
"""

from __future__ import annotations

import jax.numpy as jnp

from vgate_tpu.models.specs import ModelSpec


def pack_rows(t, pack: int):
    """K or V rows [..., KV, hd] -> [..., KV / pack, hd x pack]."""
    return t.reshape(*t.shape[:-2], t.shape[-2] // pack, t.shape[-1] * pack)


def _upper(spec: ModelSpec):
    """[H, 1] bool: the query heads whose KV head is a row's second."""
    group = spec.num_heads // spec.num_kv_heads
    return ((jnp.arange(spec.num_heads) // group) % 2 == 1)[:, None]


def pack_queries(q, spec: ModelSpec):
    """q [..., H, 64] -> [..., H, 128]: ``[q | 0]`` or ``[0 | q]``."""
    zero = jnp.zeros_like(q)
    return jnp.where(_upper(spec), jnp.concatenate([zero, q], -1),
                     jnp.concatenate([q, zero], -1))


def unpack_heads(o, spec: ModelSpec):
    """Attention over packed rows [..., H, 128] -> each head's own lanes
    [..., H, 64]."""
    hd = spec.head_dim
    return jnp.where(_upper(spec), o[..., hd:], o[..., :hd])


def over_packed_pool(attn_fn, spec: ModelSpec):
    """``attn_fn(q, pools..., **kw)`` (a paged attention of
    ops/attention.py or ops/pallas/paged_attention.py: the queries
    first, the softmax scale as ``scale``, the step's own K and V as
    ``k_new`` / ``v_new`` where the kernel writes them; the attention
    alone or first of a tuple) for a pool of packed rows; itself for a
    spec whose rows hold one head."""
    if spec.kv_head_pack == 1:
        return attn_fn
    assert spec.kv_head_pack == 2, spec.kv_head_pack
    scale = spec.head_dim ** -0.5

    def packed(q, *args, **kw):
        for name in ("k_new", "v_new"):
            if kw.get(name) is not None:
                kw[name] = pack_rows(kw[name], 2)
        if kw.get("scale") is None:
            kw["scale"] = scale
        out = attn_fn(pack_queries(q, spec), *args, **kw)
        if isinstance(out, tuple):
            return (unpack_heads(out[0], spec),) + tuple(out[1:])
        return unpack_heads(out, spec)

    return packed
