"""Pipeline parallelism: the layer stack sharded over the ``pp`` mesh axis.

The stacked-layer param pytree (and the KV page pool) shard their leading
``L`` axis over ``pp`` (parallel/sharding.py), so each stage holds
``L/pp`` layers' weights + KV.  The forward runs as a GPipe relay inside a
``shard_map`` that is **manual over pp only** — dp/ep/sp/tp stay "auto",
so Megatron tp sharding, MoE ep dispatch and their XLA collectives keep
working unchanged inside each stage:

* the batch splits into ``M`` microbatches (``M = pp`` when it divides
  ``B``, else 1);
* for ``M + pp - 1`` relay steps, every stage scans its local layers over
  the microbatch it currently holds and ``ppermute``s the activations
  ``[mb, D]`` to the next stage — the only pp communication;
* bubble steps are masked with the KV cache's reserved **trash page 0**
  (runtime/kv_cache.py), so no stage ever branches on validity;
* the last stage's collected hiddens are ``psum``-broadcast (tiny:
  ``[B, D]``) and every stage computes logits identically.

The compiled stage programs are cached per (mesh, spec, microbatch
geometry) so eager callers don't rebuild/recompile the shard_map per step.

The reference has no pipeline code at all (SURVEY.md section 2.2 row 3);
this is the TPU-native design: stage relay over ICI neighbours, static
shapes, one compiled program.  pp composes with dp (replica engines), tp
and ep; it is mutually exclusive with sp's ring-attention prefill
(validated at engine start).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from vgate_tpu.models.decoder import (
    Params,
    _embed,
    _layer_windows,
    _logits,
    _query_scale,
    decode_attn_inputs,
    decode_layer,
    prefill_layer,
)
from vgate_tpu.models.specs import ModelSpec
from vgate_tpu.ops.attention import (
    flash_prefill_attention,
    paged_decode_attention,
)
from vgate_tpu.parallel.mesh import AXIS_PP


def _microbatches(B: int, pp: int) -> int:
    return pp if B % pp == 0 else 1


def _check_divisible(spec: ModelSpec, pp: int) -> None:
    if spec.num_layers % pp:
        raise ValueError(
            f"{spec.num_layers} layers not divisible by pp={pp}: the "
            "pipeline shards the stacked layer axis evenly (param_pspecs "
            "would replicate it, then the stage shard_map would fail with "
            "an opaque trace error)"
        )


def _decode_attn_fn(use_pallas: bool, spec: ModelSpec):
    if use_pallas:
        from vgate_tpu.ops.pallas.paged_attention import (
            paged_decode_attention_pallas as fn,
        )
    else:
        fn = paged_decode_attention
    # softcap/scale ride the partial exactly like the plain-mesh path
    # (models/decoder.py decode_forward) — without them Gemma-2 through
    # the relay would silently drop its attn softcap and query scale
    return functools.partial(
        fn, softcap=spec.attn_softcap, scale=_query_scale(spec)
    )


def _prefill_attn_fn(use_pallas: bool, spec: ModelSpec):
    if use_pallas:
        from vgate_tpu.ops.pallas.flash_prefill import (
            flash_prefill_attention_pallas as fn,
        )
    else:
        fn = flash_prefill_attention
    return functools.partial(
        fn, softcap=spec.attn_softcap, scale=_query_scale(spec)
    )


def _ring(pp: int):
    return [(i, (i + 1) % pp) for i in range(pp)]


def _layer_in_specs(layers_treedef):
    return jax.tree.unflatten(
        layers_treedef, [P(AXIS_PP)] * layers_treedef.num_leaves
    )


@functools.lru_cache(maxsize=32)
def _decode_staged_fn(mesh, spec, M, mb, use_pallas, layers_treedef):
    """Build (once per geometry) the jitted decode stage-relay program."""
    pp = mesh.shape[AXIS_PP]
    attn_fn = _decode_attn_fn(use_pallas, spec)

    def staged(layers, windows, k_loc, v_loc, xs, pos_mb, pid_mb,
               poff_mb, pt_mb, slen_mb):
        s = jax.lax.axis_index(AXIS_PP)

        def gpipe_step(carry, t):
            buf, out_acc, k_loc, v_loc = carry
            m_me = t - s  # microbatch this stage relays at time t
            valid = (m_me >= 0) & (m_me < M)
            idx = jnp.clip(m_me, 0, M - 1)
            h_in = jnp.where(s == 0, xs[jnp.clip(t, 0, M - 1)], buf)
            # bubble steps write their KV into trash page 0
            pid = jnp.where(valid, pid_mb[idx], 0)

            def body(h, per_layer):
                lp, win, k_l, v_l = per_layer
                h, k_l, v_l = decode_layer(
                    h, lp, k_l, v_l, spec=spec, positions=pos_mb[idx],
                    page_ids=pid, page_off=poff_mb[idx],
                    page_tables=pt_mb[idx], seq_lens=slen_mb[idx],
                    attn_fn=attn_fn,
                    window=win if spec.sliding_window > 0 else None,
                )
                return h, (k_l, v_l)

            h_out, (k_loc, v_loc) = jax.lax.scan(
                body, h_in, (layers, windows, k_loc, v_loc)
            )
            out_acc = jnp.where(
                valid & (s == pp - 1),
                out_acc.at[idx].set(h_out),
                out_acc,
            )
            buf = jax.lax.ppermute(h_out, AXIS_PP, _ring(pp))
            return (buf, out_acc, k_loc, v_loc), None

        D = xs.shape[-1]
        init = (
            jnp.zeros((mb, D), xs.dtype),
            jnp.zeros((M, mb, D), xs.dtype),
            k_loc,
            v_loc,
        )
        (buf, out_acc, k_loc, v_loc), _ = jax.lax.scan(
            gpipe_step, init, jnp.arange(M + pp - 1)
        )
        # broadcast the last stage's collected hiddens (tiny [M, mb, D])
        out = jax.lax.psum(jnp.where(s == pp - 1, out_acc, 0), AXIS_PP)
        return out, k_loc, v_loc

    return jax.jit(jax.shard_map(
        staged,
        mesh=mesh,
        in_specs=(
            _layer_in_specs(layers_treedef),
            P(AXIS_PP),  # per-layer windows: local layer slice
            P(AXIS_PP), P(AXIS_PP),  # KV pools: local layer slices
            P(), P(), P(), P(), P(), P(),
        ),
        out_specs=(P(), P(AXIS_PP), P(AXIS_PP)),
        axis_names={AXIS_PP},
        check_vma=False,
    ))


def pp_decode_forward(
    params: Params,
    spec: ModelSpec,
    tokens: jnp.ndarray,  # [B]
    positions: jnp.ndarray,  # [B]
    k_pages: jnp.ndarray,  # [L, KV, P, ps, hd], L sharded over pp
    v_pages: jnp.ndarray,
    page_tables: jnp.ndarray,  # [B, pages_per_seq]
    active: Optional[jnp.ndarray] = None,
    mesh=None,
    use_pallas: bool = False,
    head=_logits,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One decode step through the pipeline; same contract as
    models/decoder.py decode_forward."""
    pp = mesh.shape[AXIS_PP]
    _check_divisible(spec, pp)
    B = tokens.shape[0]
    M = _microbatches(B, pp)
    mb = B // M
    ps = k_pages.shape[3]

    seq_lens, page_ids, page_off = decode_attn_inputs(
        positions, page_tables, active, ps
    )
    x = _embed(params, spec, tokens)  # [B, D] (incl. Gemma embed scale)
    D = x.shape[-1]

    staged_fn = _decode_staged_fn(
        mesh, spec, M, mb, use_pallas,
        jax.tree.structure(params["layers"]),
    )
    out, k_pages, v_pages = staged_fn(
        params["layers"], _layer_windows(spec), k_pages, v_pages,
        x.reshape(M, mb, D),
        positions.reshape(M, mb),
        page_ids.reshape(M, mb),
        page_off.reshape(M, mb),
        page_tables.reshape(M, mb, -1),
        seq_lens.reshape(M, mb),
    )
    hidden = out.reshape(B, D)
    return head(params, spec, hidden), k_pages, v_pages


@functools.lru_cache(maxsize=32)
def _prefill_staged_fn(mesh, spec, M, mb, use_pallas, layers_treedef):
    """Build (once per geometry) the jitted prefill stage-relay program."""
    pp = mesh.shape[AXIS_PP]
    attn_fn = _prefill_attn_fn(use_pallas, spec)

    def staged(layers, windows, k_loc, v_loc, xs, pt_mb, slen_mb):
        s = jax.lax.axis_index(AXIS_PP)
        S, D = xs.shape[-2], xs.shape[-1]

        def gpipe_step(carry, t):
            buf, out_acc, k_loc, v_loc = carry
            m_me = t - s
            valid = (m_me >= 0) & (m_me < M)
            idx = jnp.clip(m_me, 0, M - 1)
            h_in = jnp.where(s == 0, xs[jnp.clip(t, 0, M - 1)], buf)
            # bubble steps scatter their page writes into trash page 0
            pt = jnp.where(valid, pt_mb[idx], 0)

            def body(h, per_layer):
                lp, win, k_l, v_l = per_layer
                h, k_l, v_l = prefill_layer(
                    h, lp, k_l, v_l, spec=spec, seq_lens=slen_mb[idx],
                    page_tables=pt, attn_fn=attn_fn,
                    window=win if spec.sliding_window > 0 else None,
                )
                return h, (k_l, v_l)

            h_out, (k_loc, v_loc) = jax.lax.scan(
                body, h_in, (layers, windows, k_loc, v_loc)
            )
            # collect only the last-token hidden [mb, D]
            last_idx = jnp.clip(slen_mb[idx] - 1, 0, S - 1)
            last_h = jnp.take_along_axis(
                h_out, last_idx[:, None, None].repeat(D, axis=-1), axis=1
            )[:, 0]
            out_acc = jnp.where(
                valid & (s == pp - 1),
                out_acc.at[idx].set(last_h),
                out_acc,
            )
            buf = jax.lax.ppermute(h_out, AXIS_PP, _ring(pp))
            return (buf, out_acc, k_loc, v_loc), None

        init = (
            jnp.zeros((mb, S, D), xs.dtype),
            jnp.zeros((M, mb, D), xs.dtype),
            k_loc,
            v_loc,
        )
        (buf, out_acc, k_loc, v_loc), _ = jax.lax.scan(
            gpipe_step, init, jnp.arange(M + pp - 1)
        )
        out = jax.lax.psum(jnp.where(s == pp - 1, out_acc, 0), AXIS_PP)
        return out, k_loc, v_loc

    return jax.jit(jax.shard_map(
        staged,
        mesh=mesh,
        in_specs=(
            _layer_in_specs(layers_treedef),
            P(AXIS_PP),  # per-layer windows
            P(AXIS_PP), P(AXIS_PP),
            P(), P(), P(),
        ),
        out_specs=(P(), P(AXIS_PP), P(AXIS_PP)),
        axis_names={AXIS_PP},
        check_vma=False,
    ))


def pp_prefill_forward(
    params: Params,
    spec: ModelSpec,
    tokens: jnp.ndarray,  # [B, S]
    seq_lens: jnp.ndarray,  # [B]
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    page_tables: jnp.ndarray,  # [B, S // ps]
    mesh=None,
    use_pallas: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The prompt pass through the pipeline; same contract as
    models/decoder.py prefill_forward.  Each relay step carries a
    microbatch's full ``[mb, S, D]`` activations between stages; only the
    last-token hidden state is collected/broadcast."""
    pp = mesh.shape[AXIS_PP]
    _check_divisible(spec, pp)
    B, S = tokens.shape
    M = _microbatches(B, pp)
    mb = B // M

    x = _embed(params, spec, tokens)  # [B, S, D] (incl. Gemma embed scale)
    D = x.shape[-1]

    staged_fn = _prefill_staged_fn(
        mesh, spec, M, mb, use_pallas,
        jax.tree.structure(params["layers"]),
    )
    out, k_pages, v_pages = staged_fn(
        params["layers"], _layer_windows(spec), k_pages, v_pages,
        x.reshape(M, mb, S, D),
        page_tables.reshape(M, mb, -1),
        seq_lens.reshape(M, mb),
    )
    last_hidden = out.reshape(B, D)
    return _logits(params, spec, last_hidden), k_pages, v_pages
