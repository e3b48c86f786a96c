"""Pallas TPU kernel: one decode step of the Mamba-2 (SSD) recurrence.

Per (slot, head) the layer keeps a ``[P, N]`` float32 state ``S`` (head
size by state size: 64 x 128 at the published widths).  One decode step
reads the tile, decays it by a scalar, adds a rank-1 term, reads the
output out of it and writes it back::

    S <- S * decay + xdt B^T;   y = S C

so the step moves ``2 * P * N * 4`` bytes a tile and does ~5 flops a
byte: it is bound by HBM, as the Gated DeltaNet step beside it
(``ops/pallas/gated_delta.py``) is.  It is that step without the delta
correction, but its read-out sums over the LANES of the tile (``C``
lies along ``N``) where the other sums over its rows, and ``B`` and
``C`` are one row each for the 16 heads of a group; so it is a kernel
body of its own, on the same discipline: the FULL ``[layers, slots,
heads, P, N]`` state with the layer as a prefetched scalar, aliased
input to output, ``GROUPS_PER_BLOCK`` groups of heads a program.

The caller (``ops/ssd.py ssd_step``) folds the scalars in: ``xdt = dt *
x`` and ``decay = exp(dt A)``.  Rows that must not move (idle slots)
arrive with ``dt = 0``: ``S * 1 + 0 * B`` is ``S`` bit for bit.

Everything runs on the VPU in float32.  ``xdt`` multiplies the ROWS of
the tile and arrives transposed, heads on lanes, so that a head's vector
is a ``[P, 1]`` column that broadcasts along lanes; ``decay`` (a scalar
a head, broadcast here to a row), ``B`` and ``C`` multiply along lanes
and arrive as rows.  The read-out leaves as a ``[P, 1]`` column into the
same heads-on-lanes layout.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

GROUPS_PER_BLOCK = 2
_LANES = 128


def _kernel(layer_ref, cols_ref, rows_ref, s_ref, y_ref, s_out_ref, *,
            heads: int, per_group: int):
    del layer_ref  # consumed by the index maps
    cols = cols_ref[0, 0]  # [P, 128]: lanes xdt(heads)
    rows = rows_ref[0, 0]  # [.., N]: decay(heads) | B(groups) | C(groups)
    groups = heads // per_group
    for h in range(heads):
        g = h // per_group
        S = s_ref[0, 0, h]  # [P, N] f32
        xdt = cols[:, h:h + 1]  # [P, 1]
        decay = rows[h:h + 1, :]  # [1, N]
        b = rows[heads + g:heads + g + 1, :]
        c = rows[heads + groups + g:heads + groups + g + 1, :]
        S = S * decay + xdt * b
        s_out_ref[0, 0, h] = S
        y_ref[0, 0, :, h:h + 1] = jnp.sum(S * c, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",),
                   donate_argnames=("state",))
def ssd_step_pallas(xdt, decay, Bm, Cm, state, layer,
                    interpret: bool = False):
    """xdt: [B, H, P] f32; decay: [B, H] f32; Bm, Cm: [B, G, N] f32;
    state: [Lm, B, H, P, N] f32 (updated in place at ``layer``).
    Returns (y [B, H, P] f32, state)."""
    B, H, P = xdt.shape
    G, N = Bm.shape[1:]
    R = H // G  # heads a group
    gb = GROUPS_PER_BLOCK if G % GROUPS_PER_BLOCK == 0 else G
    hb, nb = gb * R, G // gb
    assert hb <= _LANES, (hb, "heads a block ride the lanes")
    f32 = jnp.float32
    # [B, nb, P, 128]: a block's xdt columns, heads on lanes
    cols = jnp.swapaxes(xdt.astype(f32).reshape(B, nb, hb, P), 2, 3)
    cols = jnp.pad(cols, ((0, 0), (0, 0), (0, 0), (0, _LANES - hb)))
    n_rows = -(-(hb + 2 * gb) // 8) * 8
    rows = jnp.concatenate([
        jnp.broadcast_to(
            decay.astype(f32).reshape(B, nb, hb, 1), (B, nb, hb, N)),
        Bm.astype(f32).reshape(B, nb, gb, N),
        Cm.astype(f32).reshape(B, nb, gb, N),
    ], axis=2)
    rows = jnp.pad(
        rows, ((0, 0), (0, 0), (0, n_rows - hb - 2 * gb), (0, 0)))

    tile = pl.BlockSpec(
        (1, 1, hb, P, N), lambda b, g, layer: (layer[0], b, g, 0, 0))
    vec = lambda shape: pl.BlockSpec(
        (1, 1) + shape, lambda b, g, layer: (b, g, 0, 0))
    y, state = pl.pallas_call(
        functools.partial(_kernel, heads=hb, per_group=R),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, nb),
            in_specs=[vec((P, _LANES)), vec((n_rows, N)), tile],
            out_specs=[vec((P, _LANES)), tile],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, nb, P, _LANES), f32),
            jax.ShapeDtypeStruct(state.shape, state.dtype),
        ],
        # operand 0 is the prefetched layer; the state is operand 3
        input_output_aliases={3: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
        name="ssd_step_pallas",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), cols, rows, state)
    y = jnp.swapaxes(y[..., :hb], 2, 3)  # [B, nb, hb, P]
    return y.reshape(B, H, P), state
