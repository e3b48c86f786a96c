"""Pallas TPU kernel: the grouped product of an expert layer.

``lhs`` holds the (token, choice) rows that fell on the experts held
here, sorted by expert; ``group_sizes[e]`` of them belong to expert
``e`` and multiply ITS matrix::

    out[rows of e] = lhs[rows of e] @ rhs[layer, e]

Rows past ``sum(group_sizes)`` (choices that fell on experts held
elsewhere) belong to no group: they are never computed and their output
is left as the device found it -- the caller masks them.

The grid walks (row tile, expert) VISITS in order: a tile that straddles
two experts is visited once for each, with a store mask; an expert whose
rows span several tiles is visited once a tile, and since its visits
are consecutive its matrix is fetched once (an unchanged block index is
not fetched again).  So each held expert that was hit is read exactly
once a product, which is what bounds a decode step: a few rows an
expert against 2 MB of weights.  The whole contraction is one block
(``k`` is 2,048 or 512 here), so there is no reduction axis.

The weights ride as the FULL ``[layers, experts, k, n]`` stack with the
layer as a prefetched scalar: a pallas_call on a scan's per-layer slice
would make XLA materialise the slice, 0.8 GB a layer a step.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def visits(group_sizes, m: int, tm: int):
    """The (tile, group) pairs that hold at least one row, in order.
    Returns (group [V], tile [V], offsets [E + 1], count): V =
    m // tm + E - 1 is the static bound, ``count`` how many are real."""
    E = group_sizes.shape[0]
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    first = starts // tm
    last = jnp.where(group_sizes > 0, (ends - 1) // tm, first - 1)
    per_group = last - first + 1  # 0 for an empty group
    cum = jnp.cumsum(per_group)
    V = m // tm + E - 1
    v = jnp.arange(V, dtype=jnp.int32)
    group = jnp.minimum(
        jnp.searchsorted(cum, v, side="right").astype(jnp.int32), E - 1
    )
    tile = first[group] + (v - (cum - per_group)[group])
    tile = jnp.clip(tile, 0, m // tm - 1).astype(jnp.int32)
    offsets = jnp.concatenate([jnp.zeros((1,), ends.dtype), ends])
    return group, tile, offsets.astype(jnp.int32), cum[-1].astype(jnp.int32)


def _kernel(group_ref, tile_ref, offsets_ref, layer_ref, lhs_ref, rhs_ref,
            out_ref, *, tm: int):
    del layer_ref
    v = pl.program_id(1)
    g = group_ref[v]
    row0 = tile_ref[v] * tm
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
    mine = (rows >= offsets_ref[g]) & (rows < offsets_ref[g + 1])
    acc = jnp.dot(lhs_ref[...], rhs_ref[0, 0],
                  preferred_element_type=jnp.float32).astype(out_ref.dtype)
    fresh = jnp.logical_or(v == 0, tile_ref[jnp.maximum(v - 1, 0)]
                           != tile_ref[v])

    @pl.when(fresh)
    def _():  # the tile's first visit: rows of no group yet read as zero
        out_ref[...] = jnp.where(mine, acc, jnp.zeros_like(acc))

    @pl.when(jnp.logical_not(fresh))
    def _():
        out_ref[...] = jnp.where(mine, acc, out_ref[...])


@functools.partial(jax.jit, static_argnames=("tm", "tn", "interpret"))
def grouped_matmul_pallas(lhs, rhs, group_sizes, layer, tm: int = 128,
                          tn: int = 512, interpret: bool = False):
    """lhs [M, K], rhs [L, E, K, N], group_sizes [E] int32 (sum <= M),
    layer: traced scalar.  Returns [M, N] in lhs's dtype; rows of no
    group are undefined."""
    M, K = lhs.shape
    _, E, _, N = rhs.shape
    tn = min(tn, N)
    assert M % tm == 0 and N % tn == 0, (M, tm, N, tn)
    group, tile, offsets, count = visits(group_sizes, M, tm)
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(N // tn, count),
            in_specs=[
                pl.BlockSpec((tm, K),
                             lambda n, v, grp, til, off, lay: (til[v], 0)),
                pl.BlockSpec((1, 1, K, tn),
                             lambda n, v, grp, til, off, lay:
                             (lay[0], grp[v], 0, n)),
            ],
            out_specs=pl.BlockSpec(
                (tm, tn), lambda n, v, grp, til, off, lay: (til[v], n)),
        ),
        out_shape=jax.ShapeDtypeStruct((M, N), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="moe_grouped_matmul_pallas",
    )(group, tile, offsets, jnp.reshape(layer, (1,)).astype(jnp.int32),
      lhs, rhs)
