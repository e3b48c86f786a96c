"""Pallas greedy head: a decode step's token and guard flags in one
pass over vocabulary tiles, the logits never an array in HBM.

A greedy decode chunk needs, a row, one ``int32`` token and one
``uint8`` flag word of its ``[B, V]`` float32 logits.  As XLA programs
the head writes that array (156 MB at 256 rows of Qwen2.5's
vocabulary) and two more passes read it back: the argmax with the
``logit_bias`` / ``min_tokens`` edits folded in (ops/sampling.py), and
the guard's reduces (integrity.logit_guard).  Here the grid walks the
vocabulary in tiles: the normed rows stay resident in VMEM, the head's
tile streams in double-buffered, and on the ``[B, tile]`` float32
product while it is on the chip:

* the guard's three facts on the RAW values, as ONE running integer
  maximum of the values' bits without the sign: among finite floats
  that order is the order of ``abs``, an Inf reads ``0x7f800000`` and a
  NaN above it, and a row of zeros (of either sign) reads 0;
* the edits in the order and arithmetic of ``_bias_by_compare`` and
  ``_floor_by_compare`` (the tile's own iota against the rows' ids), on
  the tiles that hold an id of any row and on no other: which those are
  is a ``[2 x tiles]`` table the caller's few ``[B, K]`` ids give;
* a running (maximum, first index of the maximum) a row and lane.

The last grid step folds the lanes: the first index of the maximum wins
across lanes and tiles as inside one (``jnp.argmax``'s rule).  A NaN
never wins a compare, so a row that holds one gets the argmax of its
other columns, and ``FLAG_NONFINITE``: the engine acts on the flag.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from vgate_tpu.integrity import FLAG_NONFINITE, FLAG_SATURATED, FLAG_ZERO
from vgate_tpu.utils.math import cdiv

LANES = 128
# the bits of +Inf: what a finite float32's bits without the sign stay
# under, and a NaN's lie above
_INF_BITS = 0x7F800000
_INT_MAX = 0x7FFFFFFF
# bytes of one head tile in VMEM (two of them stand there): with the
# resident rows, the float32 product and the lane accumulators this
# stays inside the 16 MiB a v5e kernel may use by default
_TILE_BYTES = 4 << 20
_MAX_TILE = 2048
# float32 logits under this keep the three XLA passes: the pass saves
# three trips of the logits, and under 8 MiB of them that is less than
# its tile stream loses to XLA's own product (48 rows x 19,360 columns
# lose 8 %, benchmarks/bench_kernels.py greedy_head; PERF.md, PR 50)
_MIN_LOGITS_BYTES = 8 << 20


def head_tile(vocab: int, width: int, itemsize: int) -> int:
    """Vocabulary columns a grid step takes: whole 128-lane groups, the
    head's tile of ``[tile, width]`` at most ``_TILE_BYTES``, no wider
    than the vocabulary."""
    lanes = max(1, _TILE_BYTES // (width * itemsize * LANES))
    return min(_MAX_TILE, lanes * LANES, cdiv(vocab, LANES) * LANES)


def worth_fusing(rows: int, vocab: int, head_shape) -> bool:
    """Whether the pass beats the three XLA passes at this shape: they
    move the float32 logits three times, so what it saves grows with
    ``rows x vocab`` while a launch costs what it costs.  A head whose
    minor dimension is no whole number of 128-lane groups (an untied
    ``[D, 37984]``) XLA re-lays for the kernel, a copy of the whole head
    a step: such a head keeps the three passes too."""
    return (rows * vocab * 4 >= _MIN_LOGITS_BYTES
            and head_shape[-1] % LANES == 0)


def _kernel(
    hits_ref,  # [2 x tiles] int32 SMEM: a bias id, then a live stop id, here
    x_ref,  # [B, D] the normed rows, resident
    w_ref,  # [tile, D] (tied) or [D, tile]: the head's tile
    bias_ids_ref,  # [B, Kb] int32
    bias_vals_ref,  # [B, Kb] f32
    stop_ids_ref,  # [B, Ks] int32, a row at its floor all padding
    tok_ref,  # [B, 1] int32
    flag_ref,  # [B, 1] int32
    s_ref,  # [B, tile] f32: the tile's product, edited in place
    best_ref,  # [B, 128] f32: the running maximum a lane
    idx_ref,  # [B, 128] int32: its first column
    bits_ref,  # [B, 128] int32: the running maximum of |raw| as bits
    *, vocab: int, tile: int, tied: bool, guard: bool, threshold: float,
    divisor: float,
):
    j = pl.program_id(0)
    last = pl.num_programs(0) - 1
    base = j * tile
    B = s_ref.shape[0]

    @pl.when(j == 0)
    def _():
        best_ref[...] = jnp.full_like(best_ref, -jnp.inf)
        idx_ref[...] = jnp.zeros_like(idx_ref)
        bits_ref[...] = jnp.zeros_like(bits_ref)

    s = jax.lax.dot_general(
        x_ref[...], w_ref[...],
        (((1,), (1 if tied else 0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    # a spec's ``logits_scaling``: what the guard reads and the edits
    # are added to is the scaled value, as ``_logits`` hands it on
    s_ref[...] = s if divisor == 1.0 else s / divisor
    ragged = vocab % tile != 0
    if ragged:
        # the last tile's columns past V hold whatever the block's
        # padding held: zero for the guard, -inf for the argmax below
        @pl.when(j == last)
        def _():
            col = base + jax.lax.broadcasted_iota(jnp.int32, s_ref.shape, 1)
            s_ref[...] = jnp.where(col < vocab, s_ref[...], 0.0)

    if guard:
        bits = bits_ref[...]
        for c in range(tile // LANES):
            raw = s_ref[:, c * LANES:(c + 1) * LANES]
            bits = jnp.maximum(
                bits,
                jax.lax.bitcast_convert_type(raw, jnp.int32) & _INT_MAX,
            )
        bits_ref[...] = bits

    if bias_ids_ref is not None:
        @pl.when(hits_ref[j] != 0)
        def _():
            iota = jax.lax.broadcasted_iota(jnp.int32, s_ref.shape, 1)
            ids = bias_ids_ref[...] - base
            vals = bias_vals_ref[...]
            # ops/sampling.py _bias_by_compare, the iota the tile's own
            bias = jnp.full(s_ref.shape, -0.0, jnp.float32)
            for k in range(ids.shape[1]):
                bias = jnp.where(
                    iota == ids[:, k:k + 1], vals[:, k:k + 1], bias)
            s_ref[...] = s_ref[...] + bias

    if stop_ids_ref is not None:
        @pl.when(hits_ref[last + 1 + j] != 0)
        def _():
            iota = jax.lax.broadcasted_iota(jnp.int32, s_ref.shape, 1)
            ids = stop_ids_ref[...] - base
            hit = iota == ids[:, 0:1]
            for k in range(1, ids.shape[1]):
                hit |= iota == ids[:, k:k + 1]
            s_ref[...] = jnp.where(hit, -1e30, s_ref[...])

    if ragged:
        @pl.when(j == last)
        def _():
            col = base + jax.lax.broadcasted_iota(jnp.int32, s_ref.shape, 1)
            s_ref[...] = jnp.where(col < vocab, s_ref[...], -jnp.inf)

    best, idx = best_ref[...], idx_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, (B, LANES), 1)
    for c in range(tile // LANES):
        val = s_ref[:, c * LANES:(c + 1) * LANES]
        # strictly greater: a lane keeps the first column of its maximum
        better = val > best
        best = jnp.where(better, val, best)
        idx = jnp.where(better, lane + (base + c * LANES), idx)
    best_ref[...] = best
    idx_ref[...] = idx

    @pl.when(j == last)
    def _():
        top = jnp.max(best, axis=1, keepdims=True)
        tok_ref[...] = jnp.min(
            jnp.where(best == top, idx, _INT_MAX), axis=1, keepdims=True)
        if guard:
            most = jnp.max(bits_ref[...], axis=1, keepdims=True)
            # a NaN's bits compare false with the threshold, as the
            # maximum of a row that holds one does
            saturated = jax.lax.bitcast_convert_type(
                most, jnp.float32) >= threshold
            flag_ref[...] = (
                jnp.where(most >= _INF_BITS, FLAG_NONFINITE, 0)
                | jnp.where(most == 0, FLAG_ZERO, 0)
                | jnp.where(saturated, FLAG_SATURATED, 0)
            )
        else:
            flag_ref[...] = jnp.zeros_like(flag_ref)


def _tile_hits(ids, tile: int, tiles: int):
    """[tiles] int32: whether any row names a column of the tile."""
    if ids is None:
        return jnp.zeros((tiles,), jnp.int32)
    of = (ids // tile).reshape(1, -1)
    return jnp.any(
        of == jnp.arange(tiles, dtype=jnp.int32)[:, None], axis=1
    ).astype(jnp.int32)


@functools.partial(
    jax.jit,
    static_argnames=("tied", "vocab", "guard", "threshold", "tile",
                     "interpret", "divisor"),
)
def greedy_head_pallas(
    x: jnp.ndarray,  # [B, D] normed rows, the head's dtype
    head: jnp.ndarray,  # [V, D] (tied) or [D, V]
    bias_ids=None,  # [B, Kb] int32, ids >= V pad
    bias_vals=None,  # [B, Kb] f32
    stop_ids=None,  # [B, Ks] int32 LIVE stop ids, ids >= V pad
    *, tied: bool, vocab: int = 0, guard: bool = False,
    threshold: float = 1.0e4, tile: int = 0, interpret: bool = False,
    divisor: float = 1.0,
):
    """``(next_tokens [B] int32, flags [B] uint8)`` of
    ``argmax(floor(bias(x @ head / divisor)))`` and ``logit_guard(x @
    head / divisor)`` over the head's first ``vocab`` columns (0: all it
    has); the flags are zeros without ``guard``."""
    B, D = x.shape
    V = vocab or (head.shape[0] if tied else head.shape[1])
    tile = tile or head_tile(V, D, head.dtype.itemsize)
    tiles = cdiv(V, tile)
    # whole sublane groups of the rows' dtype (16 rows of bf16)
    rows = cdiv(B, 16) * 16
    pad = lambda a, fill: None if a is None else jnp.pad(
        a, ((0, rows - B), (0, 0)), constant_values=fill)
    x = pad(x, 0)
    bias_ids, bias_vals, stop_ids = (
        pad(bias_ids, V), pad(bias_vals, 0.0), pad(stop_ids, V))
    hits = jnp.concatenate(
        [_tile_hits(bias_ids, tile, tiles), _tile_hits(stop_ids, tile, tiles)])

    whole = lambda a: pl.BlockSpec(a.shape, lambda j, hits: (0, 0))
    operands = [x, head]
    in_specs = [
        whole(x),
        pl.BlockSpec((tile, D), lambda j, hits: (j, 0)) if tied
        else pl.BlockSpec((D, tile), lambda j, hits: (0, j)),
    ]
    for a in (bias_ids, bias_vals, stop_ids):
        if a is not None:
            operands.append(a)
            in_specs.append(whole(a))

    def kernel(hits_ref, x_ref, w_ref, *refs):
        refs = list(refs)
        bias_refs = (
            (refs.pop(0), refs.pop(0)) if bias_ids is not None
            else (None, None))
        stop_ref = refs.pop(0) if stop_ids is not None else None
        _kernel(
            hits_ref, x_ref, w_ref, *bias_refs, stop_ref, *refs,
            vocab=V, tile=tile, tied=tied, guard=guard,
            threshold=threshold, divisor=float(divisor),
        )

    out = pl.BlockSpec((rows, 1), lambda j, hits: (0, 0))
    tokens, flags = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(tiles,),
            in_specs=in_specs,
            out_specs=[out, out],
            scratch_shapes=[
                pltpu.VMEM((rows, tile), jnp.float32),
                pltpu.VMEM((rows, LANES), jnp.float32),
                pltpu.VMEM((rows, LANES), jnp.int32),
                pltpu.VMEM((rows, LANES), jnp.int32),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct((rows, 1), jnp.int32)] * 2,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        name="greedy_head",
    )(hits, *operands)
    return tokens[:B, 0], flags[:B, 0].astype(jnp.uint8)
