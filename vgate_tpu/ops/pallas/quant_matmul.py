"""Pallas fused int4-dequant matmul.

The r2 int4 path lost 3x to bf16 (benchmarks/RESULTS_r2.md:33-34): XLA
materializes the two sign-extended nibble planes of ``packed_einsum``
(ops/quant.py) as full-size bf16 tensors in HBM, so the "4-bit" weights
moved MORE bytes than bf16.  This kernel keeps the dequant inside the
matmul tiles: each grid step DMAs one **packed uint8 tile** into VMEM,
sign-extends the nibbles in-register (VPU), and feeds both half-planes
straight to the MXU — HBM traffic is the packed bytes, period.  That is
the TPU-native equivalent of the fused AWQ dequant-GEMM the reference
gets opaquely through vLLM's CUDA kernels (vgate/config.py:46).

Layout contract (ops/quant.py PackedQTensor, half-split): byte
``p[i, o]`` holds ``w[i, o]`` in its low nibble and ``w[in/2 + i, o]``
in its high nibble.  The kernel therefore contracts ``x[:, :in/2]``
against the low planes and ``x[:, in/2:]`` against the high planes —
the same array is passed twice with index maps offset by ``in/2``.

Grid: ``(rows, out_tiles, in_tiles)`` with the in-tile axis innermost
accumulating into a VMEM f32 scratch; the per-output-channel scale
multiplies once on the last in-tile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


from vgate_tpu.utils.math import cdiv


def _pick_tile(dim: int, candidates=(512, 256, 128)) -> int:
    """Largest MXU-friendly tile dividing ``dim`` (whole-dim fallback for
    the tiny CPU-interpret test shapes)."""
    for t in candidates:
        if dim % t == 0:
            return t
    return dim


def _kernel(
    x_lo_ref,  # [T_r, T_in] VMEM — x columns [i*T_in, (i+1)*T_in)
    x_hi_ref,  # [T_r, T_in] VMEM — x columns in/2 + [i*T_in, (i+1)*T_in)
    p_ref,  # [T_in, T_out] uint8 VMEM — packed nibble tile
    scale_ref,  # [1, T_out] f32 VMEM
    out_ref,  # [T_r, T_out]
    acc_ref,  # [T_r, T_out] f32 scratch
    *,
    n_in_tiles: int,
):
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # sign-extend both nibbles in-register (two's complement 4-bit)
    p = p_ref[...].astype(jnp.int32)
    lo = ((p & 0x0F) ^ 8) - 8
    hi = ((p >> 4) ^ 8) - 8
    dtype = x_lo_ref.dtype
    acc_ref[...] += jax.lax.dot(
        x_lo_ref[...], lo.astype(dtype),
        preferred_element_type=jnp.float32,
    )
    acc_ref[...] += jax.lax.dot(
        x_hi_ref[...], hi.astype(dtype),
        preferred_element_type=jnp.float32,
    )

    @pl.when(i == n_in_tiles - 1)
    def _():
        out_ref[...] = (acc_ref[...] * scale_ref[...]).astype(
            out_ref.dtype
        )


def _int8_kernel(
    x_ref,  # [T_r, T_in] VMEM
    w_ref,  # [T_in, T_out] int8 VMEM
    scale_ref,  # [1, T_out] f32 VMEM
    out_ref,  # [T_r, T_out]
    acc_ref,  # [T_r, T_out] f32 scratch
    *,
    n_in_tiles: int,
):
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot(
        x_ref[...], w_ref[...].astype(x_ref.dtype),
        preferred_element_type=jnp.float32,
    )

    @pl.when(i == n_in_tiles - 1)
    def _():
        out_ref[...] = (acc_ref[...] * scale_ref[...]).astype(
            out_ref.dtype
        )


def _tiled_matmul(
    x, tile_in_dim: int, out: int, out_dtype, interpret: bool, build
):
    """Shared host-side wrapper for the fused-dequant kernels: flatten
    the lead dims, pad rows to an MXU-friendly tile, size the grid, run,
    unpad.  ``build(xf, T_r, T_in, T_out, n_in_tiles)`` returns
    ``(kernel_fn, in_specs, operands)`` — the only parts that differ
    between the int8 and packed-int4 variants."""
    *lead, in_dim = x.shape
    R = 1
    for s in lead:
        R *= s
    xf = x.reshape(R, in_dim)

    T_in = _pick_tile(tile_in_dim)
    T_out = _pick_tile(out)
    # rows tile at 128 (the MXU sublane sweet spot); small batches pad
    # to one 8-aligned tile
    T_r = 128 if R >= 128 else max(8, cdiv(R, 8) * 8)
    Rp = cdiv(R, T_r) * T_r
    if Rp != R:
        xf = jnp.pad(xf, ((0, Rp - R), (0, 0)))
    n_in_tiles = tile_in_dim // T_in

    kernel, in_specs, operands = build(xf, T_r, T_in, T_out, n_in_tiles)
    out_mat = pl.pallas_call(
        kernel,
        grid=(Rp // T_r, out // T_out, n_in_tiles),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((T_r, T_out), lambda r, o, i: (r, o)),
        out_shape=jax.ShapeDtypeStruct((Rp, out), out_dtype),
        scratch_shapes=[pltpu.VMEM((T_r, T_out), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
    )(*operands)
    if Rp != R:
        out_mat = out_mat[:R]
    return out_mat.reshape(*lead, out)


@functools.partial(
    jax.jit, static_argnames=("out_dtype", "interpret")
)
def int8_matmul_pallas(
    x: jnp.ndarray,  # [..., in]
    q: jnp.ndarray,  # [in, out] int8
    scale: jnp.ndarray,  # [out] f32 per-output-channel scale
    out_dtype=None,
    interpret: bool = False,
) -> jnp.ndarray:
    """``x @ q.astype * scale`` with the int8->activation convert inside
    the matmul tiles: HBM weight traffic is the int8 bytes.  The int8
    sibling of ``int4_matmul_pallas`` (XLA usually fuses the convert on
    its own; this kernel removes the 'usually' and gives the A/B handle).
    """
    in_dim, out = q.shape
    if x.shape[-1] != in_dim:
        raise ValueError(f"x in-dim {x.shape[-1]} != weight rows {in_dim}")

    def build(xf, T_r, T_in, T_out, n_in_tiles):
        return (
            functools.partial(_int8_kernel, n_in_tiles=n_in_tiles),
            [
                pl.BlockSpec((T_r, T_in), lambda r, o, i: (r, i)),
                pl.BlockSpec((T_in, T_out), lambda r, o, i: (i, o)),
                pl.BlockSpec((1, T_out), lambda r, o, i: (0, o)),
            ],
            (xf, q, scale.reshape(1, out).astype(jnp.float32)),
        )

    return _tiled_matmul(
        x, in_dim, out, out_dtype or x.dtype, interpret, build
    )


@functools.partial(
    jax.jit, static_argnames=("out_dtype", "interpret")
)
def int4_matmul_pallas(
    x: jnp.ndarray,  # [..., in]
    q_packed: jnp.ndarray,  # [in/2, out] uint8 (half-split nibbles)
    scale: jnp.ndarray,  # [out] f32 per-output-channel scale
    out_dtype=None,
    interpret: bool = False,
) -> jnp.ndarray:
    """``x @ dequant(q_packed) * scale`` with in-tile dequantization.

    Semantics twin: ``packed_einsum(..., x, w) * w.scale``
    (ops/quant.py) — the kernel applies the scale in f32 before the
    output cast, so it is the numerically stronger of the two.
    Returns [..., out] in ``out_dtype`` (default: x.dtype).
    """
    half, out = q_packed.shape
    if x.shape[-1] != 2 * half:
        raise ValueError(
            f"x in-dim {x.shape[-1]} != 2 * packed rows {half}"
        )

    def build(xf, T_r, T_in, T_out, n_in_tiles):
        return (
            functools.partial(_kernel, n_in_tiles=n_in_tiles),
            [
                pl.BlockSpec((T_r, T_in), lambda r, o, i: (r, i)),
                pl.BlockSpec(
                    (T_r, T_in),
                    lambda r, o, i, n=n_in_tiles: (r, i + n),
                ),
                pl.BlockSpec((T_in, T_out), lambda r, o, i: (i, o)),
                pl.BlockSpec((1, T_out), lambda r, o, i: (0, o)),
            ],
            (xf, xf, q_packed, scale.reshape(1, out).astype(jnp.float32)),
        )

    return _tiled_matmul(
        x, half, out, out_dtype or x.dtype, interpret, build
    )
