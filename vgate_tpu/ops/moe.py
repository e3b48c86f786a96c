"""The expert layer: dropless top-k routing over the router's full
width, the held experts' products as ONE grouped product over rows
sorted by expert, and a shared expert.  ONE layer for every family; the
spec says what differs: softmax or sigmoid scores (the latter chosen by
score + a bias, weighted by the score alone), a factor on the routed
sum, experts of three matrices (gated) or two, experts that work in a
latent (one projection in front of the dispatch, one behind the
weighted sum), a shared expert with or without its sigmoid gate.

The layer is told which experts it holds (``spec.num_experts`` of the
router's ``spec.router_experts``, from ``spec.first_expert``).  A chip
that holds them all (Mixtral, ``tiny-moe``) computes the whole layer; a
chip that holds a share computes its own experts' part of the result
for the rows routed to them and leaves out what the absent experts
would have added (model-configs guide, section 4) -- nothing stands in
for the absent chips.  No row is ever dropped, whatever the imbalance:
the grouped product takes every (row, choice) pair that fell on a held
expert, however many fell on one.

What is gathered, multiplied and summed is the HELD pairs alone: they
sort first, and the dispatch works on a static ``capacity`` of them at
a time (all ``T x K`` where the chip holds every expert, else twice
what a uniform router would send here).  Held pairs past the capacity
are a second trip of the same loop, counted in ``overflow``.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from vgate_tpu.models.specs import ModelSpec
from vgate_tpu.utils.math import cdiv

# the device counters a routed layer returns beside its output
STAT_NAMES = ("assignments", "held_assignments", "experts_hit", "load_max",
              "overflow")


def combine_stats(stats):
    """[n, 5] counters of n layers (or blocks) -> [5]: each a sum, but
    the largest load, which is the largest."""
    return jnp.concatenate([
        jnp.sum(stats[:, :3], axis=0), jnp.max(stats[:, 3:4], axis=0),
        jnp.sum(stats[:, 4:], axis=0)])


def _plain(w, dtype):
    """Expert weights as a plain array: a quantized tree (Mixtral under
    model.quantization) dequantizes to the activations' type, the
    grouped product being a plain product."""
    from vgate_tpu.ops.quant import PackedQTensor, QTensor, unpack_int4

    if isinstance(w, PackedQTensor):
        q = unpack_int4(w.q_packed)
    elif isinstance(w, QTensor):
        q = w.q
    else:
        return w
    return (q.astype(jnp.float32) * w.scale[..., None, :]).astype(dtype)


def _column_tile(K: int, N: int, itemsize: int) -> int:
    """Columns of an expert's matrix a program of the grouped product
    holds: all of them while the matrix is at most 2 MiB, else 512, or
    where 512 does not divide them (2,688 = 21 x 128) the widest
    multiple of 128 up to 1,024 that does; halved while a block of
    them is over 4 MiB (a contraction of 6,144 takes 256: at 512 the
    v5e's compiler finds no room for the block in fast memory)."""
    if K * N * itemsize <= (2 << 20):
        return N
    tile = next(t for t in (512, 1024, 896, 768, 640, 384, 256, 128, N)
                if N % t == 0)
    while K * tile * itemsize > (4 << 20) and tile % 256 == 0:
        tile //= 2
    return tile


def _row_tile(M: int) -> int:
    """Rows of a program of the grouped product."""
    return 128 if M >= 4096 else 32


def grouped_product(rows, w, group_sizes, layer, use_pallas: bool):
    """rows [M, K] sorted by expert; w either ONE layer's ``[E, K, N]``
    (``layer`` None) or the stack ``[L, E, K, N]`` with the traced
    ``layer``.  The jnp twin is XLA's own ragged product."""
    w = _plain(w, rows.dtype)
    if use_pallas and layer is not None:
        from vgate_tpu.ops.pallas.grouped_matmul import grouped_matmul_pallas

        K, N = w.shape[-2:]
        M = rows.shape[0]
        tm = _row_tile(M)
        tn = _column_tile(K, N, w.dtype.itemsize)
        pad = cdiv(M, tm) * tm - M
        if pad:
            rows = jnp.pad(rows, ((0, pad), (0, 0)))
        out = grouped_matmul_pallas(
            rows.astype(w.dtype), w, group_sizes, layer, tm=tm, tn=tn
        )
        return out[:M] if pad else out
    if layer is not None:
        w = w[layer]
    return jax.lax.ragged_dot(rows.astype(w.dtype), w, group_sizes)


# rows whose (row, choice) pairs a block dispatches at a time: a prompt
# wave of 8 x 2,048 tokens would otherwise hold (tokens x choices) x
# hidden temporaries of gigabytes
BLOCK_TOKENS = 4096
# dispatched (row, choice) x width values a block may hold, which is
# what its float32 temporaries go by: the most a block has been run with
# on the chip (4,096 rows x 22 choices of a 1,024-wide latent)
BLOCK_VALUES = BLOCK_TOKENS * 22 * 1024


def capacity(spec: ModelSpec, pairs: int) -> int:
    """Of a block's ``pairs`` (row, choice) pairs, how many the dispatch
    takes at a time: all of them where the chip holds every expert, else
    twice the share a uniform router sends to the held ones, in whole
    row tiles of the grouped product."""
    if spec.num_experts >= spec.router_experts:
        return pairs
    c = cdiv(2 * pairs * spec.num_experts, spec.router_experts)
    tile = _row_tile(c)
    return min(pairs, cdiv(c, tile) * tile)


def block_tokens(spec: ModelSpec) -> int:
    """Rows a block of the expert layer takes: as many as dispatch
    ``BLOCK_TOKENS`` rows' pairs at a time (those rows where the chip
    holds every expert or half of them, twice as many where it holds a
    quarter), halved while what the block dispatches at a time would
    hold more than ``BLOCK_VALUES`` (8 choices of a 6,144-wide hidden,
    16 of 128 experts held: 4,096 rows, 8,192 pairs at a time)."""
    K = spec.experts_per_token
    rows = BLOCK_TOKENS * max(
        1, spec.router_experts // (2 * spec.num_experts))
    while capacity(spec, rows * K) * spec.expert_in > BLOCK_VALUES:
        rows //= 2
    return rows


def expert_layer(x, lp, spec: ModelSpec, act, row_mask=None,
                 use_pallas: bool = False, layer=None, stack=None,
                 by_rows=None):
    """``_expert_block`` over blocks of ``block_tokens(spec)`` rows (one
    block for a decode step or a small wave): the weights are read once
    a block, the temporaries stay bounded.  Arguments and result as
    ``_expert_block``."""
    D = x.shape[-1]
    T = x.size // D
    block = block_tokens(spec)
    if T <= block:
        return _expert_block(x, lp, spec, act, row_mask, use_pallas,
                             layer, stack, by_rows)
    # blocks unrolled: XLA runs them one after the other and reuses one
    # block's temporaries for the next
    xt = x.reshape(T, D)
    mask = None if row_mask is None else row_mask.reshape(T)
    outs, stats = [], []
    for lo in range(0, T, block):
        out, st = _expert_block(
            xt[lo:lo + block], lp, spec, act,
            None if mask is None else mask[lo:lo + block],
            use_pallas, layer, stack, by_rows,
        )
        outs.append(out)
        stats.append(st)
    return (jnp.concatenate(outs).reshape(x.shape),
            combine_stats(jnp.stack(stats)))


def _combine(out, y, pairs, live, K: int):
    """out [T, W] float32 plus the trip's weighted products y [C, W],
    each added to the row of its pair (``pairs // K``); a pair that is
    not ``live`` goes nowhere."""
    return out.at[jnp.where(live, pairs // K, out.shape[0])].add(
        y, mode="drop")


def _expert_block(x, lp, spec: ModelSpec, act, row_mask=None,
                  use_pallas: bool = False, layer=None, stack=None,
                  by_rows=None):
    """x: [..., D].  ``lp`` holds this layer's ``router`` [D, R] and
    either its experts' matrices (``spec.expert_stacks``: ``{"w": [E, .,
    .]}``) or, with ``stack``/``layer``, nothing of them: ``stack`` is
    then the dict of their ``[L, E, ., .]`` stacks and
    ``layer`` the traced index into them (the Pallas path must not see
    a scan's per-layer slice).  ``row_mask`` ([...] bool) marks the rows
    that are real: padding and idle slots route nowhere.  ``by_rows``
    (a long prompt's pass: models/hybrid.py ``_by_row_blocks``) runs
    what is position-wise (the router's product, the latent's two, the
    shared expert) over the blocks of rows up to the last real one.
    Returns (out [..., D], stats [5] int32 in ``STAT_NAMES`` order)."""
    orig_shape = x.shape
    D = orig_shape[-1]
    xt = x.reshape(-1, D)
    T = xt.shape[0]
    E, K, first = spec.num_experts, spec.experts_per_token, spec.first_expert
    rows_of = lambda fn, *rows: fn(*rows)
    if by_rows is not None and row_mask is not None:
        n_rows = jnp.max(jnp.where(
            row_mask.reshape(T), jnp.arange(1, T + 1), 0))
        rows_of = lambda fn, *rows: by_rows(fn, rows, n_rows, axis=0)

    with jax.named_scope("moe_route"):
        logits = rows_of(lambda rows: jnp.einsum(
            "td,de->te", rows.astype(jnp.float32),
            lp["router"].astype(jnp.float32),
        ), xt)
        if spec.router_scoring == "sigmoid":
            # chosen by score + bias, weighted by the score alone
            scores = jax.nn.sigmoid(logits)
            _, gate_idx = jax.lax.top_k(
                scores + lp["router_bias"].astype(jnp.float32), K)
            gate_vals = jnp.take_along_axis(scores, gate_idx, axis=-1)
            # the sum kept from zero by the spec's own epsilon: 1e-20
            # (DeepSeek-V3's form) but for LFM2's published 1e-6, which
            # moves a weight by 5e-7 of itself at a sum near 2: under
            # every tolerance here, and stated as data all the same
            gate_vals = gate_vals / (
                jnp.sum(gate_vals, axis=-1, keepdims=True)
                + spec.router_norm_eps)
        else:
            probs = jax.nn.softmax(logits, axis=-1)
            gate_vals, gate_idx = jax.lax.top_k(probs, K)  # [T, K]
            gate_vals = gate_vals / jnp.sum(
                gate_vals, axis=-1, keepdims=True)
        if spec.routed_scaling_factor != 1.0:
            gate_vals = gate_vals * spec.routed_scaling_factor
        local = gate_idx - first
        real = jnp.ones((T, 1), bool) if row_mask is None else (
            row_mask.reshape(T, 1)
        )
        held = (local >= 0) & (local < E) & real
        # choices on experts held elsewhere sort last, under group E
        flat_e = jnp.where(held, local, E).reshape(T * K)
        order = jnp.argsort(flat_e, stable=True).astype(jnp.int32)
        group_sizes = jnp.sum(
            flat_e[:, None] == jnp.arange(E)[None, :], axis=0,
            dtype=jnp.int32)
        n_held = jnp.sum(group_sizes)
        # the held pairs are order[:n_held]; C of them go at a time
        C = capacity(spec, T * K)
        trips = cdiv(n_held, C)
        n_real = jnp.sum(real.astype(jnp.int32))
        stats = jnp.stack([
            n_real * K, n_held,
            jnp.sum((group_sizes > 0).astype(jnp.int32)),
            jnp.max(group_sizes), jnp.maximum(trips - 1, 0),
        ]).astype(jnp.int32)

    latent = spec.moe_latent_size > 0
    src = xt  # what the experts read: the rows, or their latent
    if latent:  # ONE product in front of the dispatch, not one a choice
        with jax.named_scope("moe_latent_in"):
            src = rows_of(lambda rows: jnp.einsum(
                "td,dl->tl", rows, lp["latent_in"]["w"]), xt)

    with jax.named_scope("moe_experts"):
        ws = stack if stack is not None else lp
        lay = layer if stack is not None else None
        weights = jnp.where(held, gate_vals, 0.0).reshape(T * K)
        ends = jnp.cumsum(group_sizes)
        # whole trips: the order is padded with pairs past n_held
        order = jnp.pad(order, (0, cdiv(T * K, C) * C - T * K))

        def dispatch(trip, out):
            """Pairs ``trip * C ..`` of the sorted order: gathered,
            multiplied by their experts, weighted, added to their rows."""
            lo = trip * C
            pairs = jax.lax.dynamic_slice(order, (lo,), (C,))
            live = lo + jnp.arange(C) < n_held
            # what of each expert's group lies in lo .. lo + C
            cut = lambda at: jnp.clip(at - lo, 0, C)
            sizes = cut(ends) - cut(ends - group_sizes)
            gp = lambda r, name: grouped_product(
                r, ws[name]["w"], sizes, lay, use_pallas
            )
            rows = src[pairs // K]  # [C, W], sorted by expert
            hidden = act(gp(rows, "gate" if spec.moe_gated else "up").astype(
                jnp.float32)).astype(xt.dtype)
            if spec.moe_gated:
                hidden = hidden * gp(rows, "up").astype(xt.dtype)
            y = gp(hidden, "down")  # [C, W]; rows of no group undefined
            y = jnp.where(
                live[:, None],
                y.astype(jnp.float32) * weights[pairs][:, None], 0.0,
            )
            return _combine(out, y, pairs, live, K)

        out = jnp.zeros((T, src.shape[-1]), jnp.float32)
        if C == T * K:  # one trip holds whatever fell here
            out = dispatch(0, out)
        else:
            out = jax.lax.fori_loop(0, trips, dispatch, out)

    if latent:
        # on this chip's PARTIAL sum: the product is linear and has no
        # bias, so the chips' results add up to the whole
        with jax.named_scope("moe_latent_out"):
            out = rows_of(lambda rows: jnp.einsum(
                "tl,ld->td", rows.astype(xt.dtype), lp["latent_out"]["w"]
            ).astype(jnp.float32), out)

    if spec.shared_expert_intermediate_size:
        def shared(out, xt):
            u = jnp.einsum("td,df->tf", xt, lp["shared_up"]["w"])
            if spec.moe_gated:
                g = jnp.einsum("td,df->tf", xt, lp["shared_gate"]["w"])
                u = act(g.astype(jnp.float32)).astype(xt.dtype) * u
            else:
                u = act(u.astype(jnp.float32)).astype(xt.dtype)
            s = jnp.einsum("tf,fd->td", u, lp["shared_down"]["w"])
            if not spec.shared_expert_gate:
                return out + s.astype(jnp.float32)
            sg = jax.nn.sigmoid(jnp.einsum(
                "td,d->t", xt.astype(jnp.float32),
                lp["shared_router"].astype(jnp.float32),
            ))
            return out + s.astype(jnp.float32) * sg[:, None]

        with jax.named_scope("shared_expert"):
            # (a block past the real rows holds no routed sum either)
            out = rows_of(shared, out, xt)
    return out.astype(x.dtype).reshape(orig_shape), stats
