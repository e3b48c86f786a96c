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
``C`` are one row each for all the heads of a group (16 of Nemotron-H's
128, all 64 of Granite 4.0-H's); so it is a kernel body of its own, on
the same discipline: the FULL ``[layers, slots, heads, P, N]`` state
with the layer as a prefetched scalar, aliased input to output.

A program takes a BLOCK of ``block_heads(H, G)`` heads of one slot:
whole groups where a group is no larger than the block (four of
Nemotron-H's groups of 16; Granite's one group of 64), a part of ONE
group where it is larger (a group of 96 in blocks of 48), with that
group's ``B`` and ``C`` rows beside it.  The block sets what stands in VMEM (a block's
tiles in and out, double-buffered: 4 x heads x 32 KiB at the published
tile) and how long the unrolled loop over its heads is.

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

# heads a program takes, at most.  More heads spread a program's fixed
# cost (its pipeline's prologue) over more bytes: 8 / 16 / 32 / 64 read
# 53.7 / 60.5 / 67.4 / 71.6 % of the chip's bandwidth at Granite's shape
# (one group of 64 heads) and 47.6 / 61.0 / 69.4 / 72.4 % at Nemotron-H's
# (eight groups of 16: 32 was its block until PR 51), and in the cells
# 64 against 32 is + 2 points of the kernel's roofline and ~ 3 % of the
# tokens on both (benchmarks/bench_kernels.py ssd_step; PERF.md section
# 6, PR 51).  128 heads would hold 16.8 MB of tiles: past the default
# scoped VMEM
BLOCK_HEADS = 64
_LANES = 128


def block_heads(heads: int, groups: int) -> int:
    """Heads a program of the step kernel takes: where a group fits
    ``BLOCK_HEADS``, the most whole groups that do and that divide the
    groups evenly; else the largest part of one group that fits and
    divides it."""
    per_group = heads // groups
    if per_group <= BLOCK_HEADS:
        fit = max(1, BLOCK_HEADS // per_group)
        gb = max(g for g in range(1, fit + 1) if groups % g == 0)
        return gb * per_group
    return max(h for h in range(1, BLOCK_HEADS + 1) if per_group % h == 0)


def _kernel(layer_ref, cols_ref, rows_ref, s_ref, y_ref, s_out_ref, *,
            heads: int, per_group: int):
    del layer_ref  # consumed by the index maps
    cols = cols_ref[0, 0]  # [P, 128]: lanes xdt(heads)
    rows = rows_ref[0, 0]  # [.., N]: decay(heads) | B(groups) | C(groups)
    groups = max(1, heads // per_group)  # a block inside one group: 1
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


@functools.partial(jax.jit, static_argnames=("interpret", "block"),
                   donate_argnames=("state",))
def ssd_step_pallas(xdt, decay, Bm, Cm, state, layer,
                    interpret: bool = False, block: int = 0):
    """xdt: [B, H, P] f32; decay: [B, H] f32; Bm, Cm: [B, G, N] f32;
    state: [Lm, B, H, P, N] f32 (updated in place at ``layer``).
    ``block``: heads a program (0: ``block_heads``'s; whole groups, or a
    divisor of one group's heads).  Returns (y [B, H, P] f32, state)."""
    B, H, P = xdt.shape
    G, N = Bm.shape[1:]
    R = H // G  # heads a group
    hb = block or block_heads(H, G)
    nb = H // hb
    gb = max(1, hb // R)  # groups whose B and C rows a block reads
    assert H % hb == 0 and (hb % R == 0 or R % hb == 0), (H, G, hb)
    assert hb <= _LANES, (hb, "heads a block ride the lanes")
    f32 = jnp.float32
    if hb < R:  # blocks inside a group: each reads its group's rows
        Bm, Cm = (jnp.repeat(t.astype(f32), R // hb, axis=1)
                  for t in (Bm, Cm))
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
