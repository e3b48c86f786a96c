"""Pallas TPU kernel: one decode step of the Gated DeltaNet recurrence.

Per (slot, value head) the layer keeps a ``[dk, dv]`` float32 state
``S``.  One decode step reads the tile, decays it, adds a rank-1 term,
reads the output out of it and writes it back::

    S <- S * exp(g);  d = beta * (v - S^T k);  S <- S + k d^T;  o = S^T q

so the step moves ``2 * dk * dv * 4`` bytes a tile and does ~6 flops a
byte: it is bound by HBM.  The kernel runs on the FULL
``[linear layers, slots, heads, dk, dv]`` state with the layer as a
prefetched scalar, the state aliased input to output (the same in-place,
layer-indexed discipline as the paged pools: no per-layer slice ever
materialises), ``heads_per_block`` tiles a program.

The scalars ride as vectors so that nothing but tiles and vectors enters
the kernel: the caller (``ops/gated_delta.py gated_delta_step``) folds
them in as ``kb = beta * exp(g) * k``, ``vb = beta * v`` and ``decay =
exp(g)`` broadcast over ``dv``; then ``d = vb - S^T kb`` with the
UNDECAYED ``S``.  Rows that must not move (idle slots) arrive with
``g = 0, beta = 0``: ``S * 1 + k * 0`` is ``S`` bit for bit.

Everything runs on the VPU in float32 (an ``[8, dk] x [dk, dv]`` matmul
would spend its time loading the tile into the MXU as weights).  The
vectors that multiply ROWS of the tile (``q``, ``k``, ``kb``, indexed by
``dk``) arrive transposed, heads on lanes, so that a head's vector is a
``[dk, 1]`` column that broadcasts along lanes; those that multiply
columns (``vb``, ``decay``, indexed by ``dv``) arrive as rows.  The
state is the model's memory over the whole sequence and stays float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

HEADS_PER_BLOCK = 16
_LANES = 128


def _kernel(layer_ref, cols_ref, rows_ref, s_ref, o_ref, s_out_ref, *,
            heads: int):
    del layer_ref  # consumed by the index maps
    cols = cols_ref[0, 0]  # [dk, 128]: lanes q(heads) | k(heads) | kb(heads)
    rows = rows_ref[0, 0]  # [2 * heads (padded), dv]: vb(heads) | decay(heads)
    for h in range(heads):
        S = s_ref[0, 0, h]  # [dk, dv] f32
        q = cols[:, h:h + 1]  # [dk, 1]
        k = cols[:, heads + h:heads + h + 1]
        kb = cols[:, 2 * heads + h:2 * heads + h + 1]
        vb = rows[h:h + 1, :]  # [1, dv]
        decay = rows[heads + h:heads + h + 1, :]
        # d = beta * (v - (S e^g)^T k), on the undecayed tile
        d = vb - jnp.sum(S * kb, axis=0, keepdims=True)
        S = S * decay + k * d
        s_out_ref[0, 0, h] = S
        o_ref[0, 0, h:h + 1, :] = jnp.sum(S * q, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",),
                   donate_argnames=("state",))
def gated_delta_step_pallas(q, k, kb, vb, decay, state, layer,
                            interpret: bool = False):
    """q, k, kb: [B, H, dk] f32; vb, decay: [B, H, dv] f32; state:
    [Lr, B, H, dk, dv] f32 (updated in place at ``layer``).  Returns
    (o [B, H, dv] f32, state)."""
    B, H, dk = q.shape
    dv = vb.shape[-1]
    hb = HEADS_PER_BLOCK if H % HEADS_PER_BLOCK == 0 else H
    G = H // hb
    f32 = jnp.float32
    # [B, G, dk, 128]: a group's q | k | kb columns, heads on lanes
    cols = jnp.concatenate(
        [x.astype(f32).reshape(B, G, hb, dk) for x in (q, k, kb)], axis=2
    )
    cols = jnp.pad(jnp.swapaxes(cols, 2, 3),
                   ((0, 0), (0, 0), (0, 0), (0, _LANES - 3 * hb)))
    n_rows = -(-2 * hb // 8) * 8
    rows = jnp.concatenate(
        [x.astype(f32).reshape(B, G, hb, dv) for x in (vb, decay)], axis=2
    )
    rows = jnp.pad(rows, ((0, 0), (0, 0), (0, n_rows - 2 * hb), (0, 0)))

    tile = pl.BlockSpec(
        (1, 1, hb, dk, dv), lambda b, g, layer: (layer[0], b, g, 0, 0))
    o, state = pl.pallas_call(
        functools.partial(_kernel, heads=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, G),
            in_specs=[
                pl.BlockSpec((1, 1, dk, _LANES),
                             lambda b, g, layer: (b, g, 0, 0)),
                pl.BlockSpec((1, 1, n_rows, dv),
                             lambda b, g, layer: (b, g, 0, 0)),
                tile,
            ],
            out_specs=[
                pl.BlockSpec((1, 1, hb, dv),
                             lambda b, g, layer: (b, g, 0, 0)),
                tile,
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, G, hb, dv), f32),
            jax.ShapeDtypeStruct(state.shape, state.dtype),
        ],
        # operand 0 is the prefetched layer; the state is operand 3
        input_output_aliases={3: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
        name="gated_delta_step_pallas",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), cols, rows, state)
    return o.reshape(B, H, dv), state
