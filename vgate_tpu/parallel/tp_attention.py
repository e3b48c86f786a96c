"""Tensor-parallel wrappers for the Pallas attention kernels.

Under a tp>1 mesh the engine's params (and the KV page pool's kv-head
dim, parallel/sharding.py kv_pspec) shard over ``tp`` via jit auto
(GSPMD) sharding.  The jnp attention twins partition automatically —
their einsums/gathers carry the head dim through — but a ``pallas_call``
has NO partitioning rule, so GSPMD falls back to replicating its
operands: an all-gather of the whole KV page pool per layer per decode
step, silently erasing tp's point on real multi-chip hardware (never
visible on one chip or on CPU meshes, which run the jnp twins).

These wrappers run the kernel per tp shard inside a ``shard_map``:
each shard holds ``KV/tp`` kv heads of the pool and ``H/tp`` query
heads, the kernel simply serves fewer heads a program, and NO
collective is needed at all — attention is embarrassingly parallel
over heads (the Megatron layout).  Requires both H and KV divisible by
tp; callers fall back to the jnp twin otherwise.  Traced per-layer
``window`` / ``layer`` scalars ride as explicit shard_map operands
(replicated), never closure captures.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from vgate_tpu.parallel.mesh import AXIS_TP


def tp_divisible(mesh, num_heads: int, num_kv_heads: int) -> bool:
    """True when the kernels can run per-shard under this mesh's tp."""
    tp = int(mesh.shape.get(AXIS_TP, 1))
    return tp > 1 and num_heads % tp == 0 and num_kv_heads % tp == 0


def tp_paged_decode_attention(
    kernel_fn,  # kernel with softcap/scale/... already partial'd in
    mesh: Mesh,
    q,  # [B, H, hd] (H sharded over tp under jit)
    k_pages,  # [KV, P, ps, hd] or [L, KV, P, ps, hd] (KV sharded over tp)
    v_pages,
    page_tables,  # [B, pages_per_seq] replicated
    seq_lens,  # [B] replicated
    window=None,  # traced scalar or None
    layer=None,  # traced scalar or None (carry-threaded pools)
):
    """Decode attention, one kernel invocation per tp shard."""
    has_layer = layer is not None
    has_window = window is not None
    pool = (
        P(None, AXIS_TP, None, None, None)
        if has_layer
        else P(AXIS_TP, None, None, None)
    )
    extras = []
    if has_window:
        extras.append(jnp.asarray(window, jnp.int32))
    if has_layer:
        extras.append(jnp.asarray(layer, jnp.int32))

    def body(q, k_pages, v_pages, page_tables, seq_lens, *ex):
        i = 0
        w = ex[0] if has_window else None
        i = 1 if has_window else 0
        l = ex[i] if has_layer else None
        return kernel_fn(
            q, k_pages, v_pages, page_tables, seq_lens,
            window=w, layer=l,
        )

    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            (P(None, AXIS_TP, None), pool, pool, P(), P())
            + tuple(P() for _ in extras)
        ),
        out_specs=P(None, AXIS_TP, None),
        check_vma=False,
    )
    return fn(q, k_pages, v_pages, page_tables, seq_lens, *extras)


def tp_flash_prefill_attention(
    kernel_fn,  # kernel with softcap/scale already partial'd in
    mesh: Mesh,
    q,  # [B, S, H, hd] (H sharded over tp)
    k,  # [B, S, KV, hd] (KV sharded over tp)
    v,
    seq_lens,  # [B]
    window=None,  # traced scalar or None
):
    """Prompt-pass flash attention, one kernel invocation per shard."""
    has_window = window is not None
    extras = (
        [jnp.asarray(window, jnp.int32)] if has_window else []
    )

    def body(q, k, v, seq_lens, *ex):
        w = ex[0] if has_window else None
        if w is None:
            return kernel_fn(q, k, v, seq_lens)
        return kernel_fn(q, k, v, seq_lens, window=w)

    heads = P(None, None, AXIS_TP, None)
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(heads, heads, heads, P())
        + tuple(P() for _ in extras),
        out_specs=heads,
        check_vma=False,
    )
    return fn(q, k, v, seq_lens, *extras)


__all__ = [
    "tp_divisible",
    "tp_paged_decode_attention",
    "tp_flash_prefill_attention",
]
