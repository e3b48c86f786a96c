"""Ring attention: sequence/context-parallel prefill over the ``sp`` axis.

The reference has no long-context story at all — a single ``max_model_len:
2048`` cap passed to vLLM (SURVEY.md section 5.7).  Here long-context prefill
is a first-class component: the sequence is sharded across the mesh's ``sp``
axis, each device computes attention for its local query block, and KV blocks
rotate around the ring via ``jax.lax.ppermute`` (XLA lowers this to ICI
neighbor exchange), overlapping each hop with the local block's compute.
Softmax is accumulated online (flash-style), so no device ever holds more
than one KV block: HBM per device stays O(S / sp).

Causality comes from global block positions: a query block fully attends
earlier blocks, causally attends its own block, and skips later ones.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from vgate_tpu.parallel.mesh import AXIS_SP


def _block_attention_update(
    q: jnp.ndarray,  # [B, Sq, H, hd] fp32
    k: jnp.ndarray,  # [B, Sk, H, hd]
    v: jnp.ndarray,
    mask: jnp.ndarray,  # [B, Sq, Sk] bool
    acc: jnp.ndarray,  # [B, Sq, H, hd] fp32
    m: jnp.ndarray,  # [B, Sq, H] running max
    l: jnp.ndarray,  # [B, Sq, H] running denom
    softcap: float = 0.0,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    scores = jnp.einsum(
        "bshd,bthd->bsth", q, k, preferred_element_type=jnp.float32
    )  # [B, Sq, Sk, H]
    if softcap:
        scores = jnp.tanh(scores / softcap) * softcap
    scores = jnp.where(mask[..., None], scores, -1e30)
    m_cur = jnp.max(scores, axis=2)  # [B, Sq, H]
    m_new = jnp.maximum(m, m_cur)
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(scores - m_new[:, :, None, :])  # [B, Sq, Sk, H]
    l_new = alpha * l + jnp.sum(p, axis=2)
    acc_new = acc * alpha[..., None] + jnp.einsum(
        "bsth,bthd->bshd", p, v, preferred_element_type=jnp.float32
    )
    return acc_new, m_new, l_new


def ring_attention_shard(
    q: jnp.ndarray,  # [B, S_local, H, hd] — this device's query block
    k: jnp.ndarray,  # [B, S_local, H, hd] — this device's KV block (GQA
    v: jnp.ndarray,  #                      already expanded by the caller)
    seq_lens: jnp.ndarray,  # [B] global real lengths
    window: jnp.ndarray,  # [] int32; >0 => attend only to the last `window`
    axis_name: str = AXIS_SP,
    softcap: float = 0.0,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Per-shard body; call under shard_map with the sequence dim sharded on
    ``axis_name``.  Returns this device's output block [B, S_local, H, hd].

    ``window``/``softcap``/``scale`` carry the sliding-window families
    (Gemma-2): window masking composes with the global block-position
    masks, so local-attention layers ride the same ring — blocks wholly
    outside a query's window contribute only masked (-1e30) scores, which
    the online softmax absorbs."""
    sp = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    B, S_local, H, hd = q.shape
    if scale is None:
        scale = 1.0 / (hd ** 0.5)

    q32 = q.astype(jnp.float32) * scale
    local_pos = jnp.arange(S_local)
    q_pos = idx * S_local + local_pos  # [S_local]

    acc = jnp.zeros((B, S_local, H, hd), jnp.float32)
    m = jnp.full((B, S_local, H), -1e30, jnp.float32)
    l = jnp.zeros((B, S_local, H), jnp.float32)

    perm = [(i, (i + 1) % sp) for i in range(sp)]
    k_blk, v_blk = k, v
    for step in range(sp):  # static: sp is a mesh constant
        src = (idx - step) % sp  # owner of the block we currently hold
        k_pos = src * S_local + local_pos  # [S_local]
        causal = k_pos[None, :] <= q_pos[:, None]  # [Sq, Sk]
        dist = q_pos[:, None] - k_pos[None, :]
        win_ok = (window <= 0) | (dist < window)
        valid = k_pos[None, :] < seq_lens[:, None]  # [B, Sk]
        mask = (causal & win_ok)[None] & valid[:, None, :]
        acc, m, l = _block_attention_update(
            q32,
            k_blk.astype(jnp.float32),
            v_blk.astype(jnp.float32),
            mask,
            acc,
            m,
            l,
            softcap=softcap,
        )
        if step != sp - 1:
            k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
            v_blk = jax.lax.ppermute(v_blk, axis_name, perm)

    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


def ring_prefill_attention(
    q: jnp.ndarray,  # [B, S, H, hd] full (global) arrays
    k: jnp.ndarray,  # [B, S, KV, hd]
    v: jnp.ndarray,
    seq_lens: jnp.ndarray,  # [B]
    mesh: Mesh,
    window=None,  # int32 scalar; >0 => attend only to the last `window`
    softcap: float = 0.0,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Sequence-parallel causal attention over the mesh's sp axis.

    Drop-in equivalent of ops.attention.causal_prefill_attention for
    prompts too long for one device's HBM; S must divide by mesh.shape[sp].
    ``window``/``softcap``/``scale`` make the sliding-window/softcap
    families (Gemma-2) ring-capable (window may be a traced per-layer
    scalar; 0 means global attention).
    """
    sp = mesh.shape[AXIS_SP]
    B, S, H, hd = q.shape
    if S % sp:
        raise ValueError(f"sequence {S} not divisible by sp={sp}")
    n_rep = H // k.shape[2]
    if n_rep > 1:  # expand GQA before sharding so all blocks line up
        k = jnp.repeat(k, n_rep, axis=2)
        v = jnp.repeat(v, n_rep, axis=2)
    window_arr = jnp.asarray(
        0 if window is None else window, jnp.int32
    )

    seq_sharded = P(None, AXIS_SP, None, None)
    fn = jax.shard_map(
        functools.partial(
            ring_attention_shard, axis_name=AXIS_SP, softcap=softcap,
            scale=scale,
        ),
        mesh=mesh,
        in_specs=(seq_sharded, seq_sharded, seq_sharded, P(), P()),
        out_specs=seq_sharded,
        check_vma=False,
    )
    return fn(q, k, v, seq_lens, window_arr)
