"""Weight-only int8 / int4 quantization.

The TPU-native counterpart of the AWQ 4-bit quantization the reference
passes through to vLLM (vgate/config.py:46, vllm_backend.py:32 — opaque
there).  Symmetric per-output-channel narrow-int: weights store as
``QTensor(q=int8|int4, scale=f32[out])`` and dequantize inside the matmul's
consumer (XLA fuses the narrow-int→bf16 convert + scale into the
surrounding computation), cutting weight HBM traffic 2x (int8) or 4x
(int4, packed two-per-byte on TPU) — the resource that bounds decode.

Every weight in the decoder layout keeps its output dim LAST, so one
broadcast rule covers q/k/v/o/gate/up/down and lm_head.  MoE expert weights
[L, E, in, out] quantize per (layer, expert, out-channel) and dequantize
inside the per-expert GEMMs (ops/moe.py dequantizes them into its grouped product); the router
stays fp32 (it is tiny and drives top-k selection).
"""

from __future__ import annotations

from typing import Any, NamedTuple, Union

import jax
import jax.numpy as jnp


class QTensor(NamedTuple):
    """narrow-int values + per-output-channel scale (output dim is last)."""

    q: jnp.ndarray  # int8, same shape as the original weight
    scale: jnp.ndarray  # f32, shape = original.shape[-1:] (or [L, out])


class PackedQTensor(NamedTuple):
    """int4 weights stored two-per-byte (uint8) along the contracted dim.

    jnp.int4 (``S4``) arrays cannot cross a jit boundary on the TPU runtime
    (device_put relayout recurses), and packed bytes are the honest 4-bit
    representation anyway — the same layout AWQ uses on GPU.  ``q_packed``
    has the original shape with dim -2 (the ``in`` dim) halved, in a
    **half-split** layout: byte ``p[..., i, out]`` holds
    ``w[..., i, out]`` in its low nibble and ``w[..., i + in/2, out]`` in
    its high nibble, two's-complement.  Half-split (not interleaved) so
    the consumer can contract each nibble plane directly against the
    matching half of the activations — no interleaving reshape, and the
    unpacked weight never materializes (see ``packed_einsum``).
    """

    q_packed: jnp.ndarray  # uint8 [..., in/2, out]
    scale: jnp.ndarray  # f32 [..., out]


_QDTYPES = {8: (jnp.int8, 127), 4: (jnp.int8, 7)}


Weight = Union[jnp.ndarray, QTensor, PackedQTensor]

def _use_quant_kernel(subscripts: str, w: Weight) -> bool:
    """Shape eligibility for the fused dequant kernels
    (ops/pallas/quant_matmul.py): 2D per-layer weights (packed int4 or
    int8) in a plain [..., in] @ [in, out] contraction ("...d,dh->...h"
    etc.).  Stacked/expert weights and exotic einsums keep the jnp path.
    Whether a kernel actually runs is the caller's ``quant_kernel`` flag
    (threaded per-engine via ModelSpec.quant_kernel — the engine enables
    it only on TPU with no model-parallel axes, since pallas_call does
    not auto-partition under jit sharding)."""
    vals = w.q_packed if isinstance(w, PackedQTensor) else w.q
    if vals.ndim != 2:
        return False
    ins, out = subscripts.split("->")
    a, b = ins.split(",")
    if not (a.startswith("...") and len(a) == 4 and len(b) == 2):
        return False
    return a[3] == b[0] and out == "..." + b[1]


def pack_int4(q: jnp.ndarray) -> jnp.ndarray:
    """int8 values in [-7, 7], shape [..., in, out] -> uint8 [..., in/2, out]
    (half-split layout: low nibbles = first half of ``in``, high = second)."""
    if q.shape[-2] % 2:
        raise ValueError(f"in-dim {q.shape[-2]} must be even to pack int4")
    half = q.shape[-2] // 2
    lo = q[..., :half, :].astype(jnp.uint8) & jnp.uint8(0x0F)
    hi = q[..., half:, :].astype(jnp.uint8) & jnp.uint8(0x0F)
    return lo | (hi << jnp.uint8(4))


def _sext4(nibble: jnp.ndarray) -> jnp.ndarray:
    """two's-complement 4-bit -> int8."""
    return (nibble.astype(jnp.int8) ^ jnp.int8(8)) - jnp.int8(8)


def _nibble_planes(p: jnp.ndarray):
    """Half-split packed bytes -> sign-extended int8 ``(lo, hi)`` planes
    (the single home of the layout invariant shared by ``unpack_int4``,
    ``packed_einsum`` and ``int8_native_einsum``)."""
    return _sext4(p & jnp.uint8(0x0F)), _sext4(p >> jnp.uint8(4))


def unpack_int4(p: jnp.ndarray) -> jnp.ndarray:
    """uint8 [..., in/2, out] -> sign-extended int8 [..., in, out]."""
    lo, hi = _nibble_planes(p)
    return jnp.concatenate([lo, hi], axis=-2)


def packed_einsum(
    subscripts: str, x: jnp.ndarray, w: "PackedQTensor",
    preferred_element_type=None,
) -> jnp.ndarray:
    """einsum against packed int4 without materializing the unpacked weight.

    Every decoder einsum contracts x's LAST axis against w's dim -2, so the
    half-split layout lets each nibble plane multiply the matching half of
    the activations: two half-size MXU GEMMs whose narrow-int -> bf16
    converts fuse into the operand feed, with no interleave reshape and no
    full-size int8 weight tensor in flight.  Output scale is NOT applied
    (callers broadcast ``w.scale`` themselves — its shape differs between
    dense and expert weights)."""
    half = w.q_packed.shape[-2]
    lo, hi = _nibble_planes(w.q_packed)
    lo, hi = lo.astype(x.dtype), hi.astype(x.dtype)
    kw = (
        {}
        if preferred_element_type is None
        else {"preferred_element_type": preferred_element_type}
    )
    return jnp.einsum(subscripts, x[..., :half], lo, **kw) + jnp.einsum(
        subscripts, x[..., half:], hi, **kw
    )


def _quantize_activations(x: jnp.ndarray):
    """Dynamic symmetric per-token int8 quantization of activations:
    per-row absmax over the contracted (last) axis.  Returns
    ``(x_q int8, x_scale f32[..., 1])``."""
    absmax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    x_scale = jnp.maximum(absmax, 1e-8) / 127.0
    x_q = jnp.clip(
        jnp.round(x.astype(jnp.float32) / x_scale), -127, 127
    ).astype(jnp.int8)
    return x_q, x_scale


def int8_native_partial(
    subscripts: str, x: jnp.ndarray, w: Weight
) -> jnp.ndarray:
    """W8A8 contraction WITHOUT the weight scale: dynamically quantize
    activations per-token and contract int8 x int8 with int32
    accumulation — XLA lowers this to the MXU's native s8 x s8 -> s32
    path on v5e-class TPUs (2x bf16 matmul throughput), with no
    dequantized weight plane ever materializing.

    Works for QTensor (one int8 GEMM) and PackedQTensor (W4A8: the two
    sign-extended nibble planes stay int8 and each contracts the
    matching activation half — two native GEMMs, packed bytes in HBM).
    Returns ``(x @ w) * x_scale`` in f32; the CALLER applies ``w.scale``
    (its broadcast shape differs between dense [out] and expert
    [E, out] weights — the same split as ``packed_einsum``).
    """
    x_q, x_scale = _quantize_activations(x)
    if isinstance(w, PackedQTensor):
        half = w.q_packed.shape[-2]
        lo, hi = _nibble_planes(w.q_packed)
        acc = jnp.einsum(
            subscripts, x_q[..., :half], lo,
            preferred_element_type=jnp.int32,
        ) + jnp.einsum(
            subscripts, x_q[..., half:], hi,
            preferred_element_type=jnp.int32,
        )
    else:
        acc = jnp.einsum(
            subscripts, x_q, w.q, preferred_element_type=jnp.int32
        )
    return acc.astype(jnp.float32) * x_scale


def int8_native_einsum(
    subscripts: str, x: jnp.ndarray, w: Weight, out_dtype,
) -> jnp.ndarray:
    """Dense-weight W8A8/W4A8: ``int8_native_partial`` with the
    per-output-channel scale applied — the TPU-native answer to the
    fused AWQ dequant-GEMM the reference gets through vLLM's CUDA
    kernels (vgate/config.py:46): weight HBM traffic is the narrow-int
    bytes AND the MACs run at int8 rate."""
    out = int8_native_partial(subscripts, x, w) * w.scale
    return out.astype(out_dtype)


def _finish(q: jnp.ndarray, scale: jnp.ndarray, bits: int) -> Weight:
    if bits == 4:
        return PackedQTensor(q_packed=pack_int4(q), scale=scale)
    return QTensor(q=q, scale=scale)


def quantize_tensor(w: jnp.ndarray, bits: int = 8) -> Weight:
    """Symmetric per-channel int8/int4 over the last (output) dim."""
    dtype, qmax = _QDTYPES[bits]
    w32 = w.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(w32), axis=tuple(range(w.ndim - 1)))
    scale = jnp.maximum(absmax, 1e-8) / qmax
    q = jnp.clip(jnp.round(w32 / scale), -qmax, qmax).astype(dtype)
    return _finish(q, scale, bits)


def quantize_stacked(w: jnp.ndarray, bits: int = 8) -> Weight:
    """Quantize a stacked-layer weight [L, ..., out]: per (layer, channel)."""
    dtype, qmax = _QDTYPES[bits]
    w32 = w.astype(jnp.float32)
    reduce_axes = tuple(range(1, w.ndim - 1))
    absmax = jnp.max(jnp.abs(w32), axis=reduce_axes)  # [L, out]
    scale = jnp.maximum(absmax, 1e-8) / qmax
    q = jnp.clip(
        jnp.round(w32 / scale[(slice(None),) + (None,) * (w.ndim - 2)]),
        -qmax,
        qmax,
    ).astype(dtype)
    return _finish(q, scale, bits)


def quantize_expert_stacked(w: jnp.ndarray, bits: int = 8) -> Weight:
    """Quantize stacked MoE expert weights [L, E, in, out]: the scale is per
    (layer, expert, out-channel) — reducing only the contracted ``in`` dim —
    so each expert keeps its own dynamic range."""
    dtype, qmax = _QDTYPES[bits]
    w32 = w.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(w32), axis=-2)  # [L, E, out]
    scale = jnp.maximum(absmax, 1e-8) / qmax
    q = jnp.clip(
        jnp.round(w32 / scale[..., None, :]), -qmax, qmax
    ).astype(dtype)
    return _finish(q, scale, bits)


def weighted_einsum(
    subscripts: str, x: jnp.ndarray, w: Weight, preferred_element_type=None,
    quant_kernel: bool = False, int8_native: bool = False,
) -> jnp.ndarray:
    """einsum that accepts plain or quantized weights.

    For QTensor the int8 values enter the einsum cast to the activation
    dtype and the per-channel scale multiplies the output's last dim —
    valid because every decoder weight keeps out-dim last.  PackedQTensor
    int4 nibbles unpack in-consumer (XLA fuses the byte ops into the
    convert; only the packed bytes ever sit in HBM).
    ``preferred_element_type`` sets the accumulation/output dtype across
    all three branches (the lm_head path accumulates logits in fp32).
    ``int8_native`` (W8A8/W4A8, tpu.int8_native): dynamic per-token
    activation quantization feeding the MXU's native s8 x s8 -> s32 —
    takes precedence over ``quant_kernel`` for eligible contractions.
    """
    kw = (
        {}
        if preferred_element_type is None
        else {"preferred_element_type": preferred_element_type}
    )
    out_dtype = preferred_element_type or x.dtype
    if (
        int8_native
        and isinstance(w, (QTensor, PackedQTensor))
        and _use_quant_kernel(subscripts, w)
    ):
        return int8_native_einsum(subscripts, x, w, out_dtype)
    if isinstance(w, PackedQTensor):
        if quant_kernel and _use_quant_kernel(subscripts, w):
            from vgate_tpu.ops.pallas.quant_matmul import (
                int4_matmul_pallas,
            )

            return int4_matmul_pallas(
                x, w.q_packed, w.scale, out_dtype=out_dtype
            )
        out = packed_einsum(
            subscripts, x, w, preferred_element_type=preferred_element_type
        )
        return out * w.scale.astype(out_dtype)
    if isinstance(w, QTensor):
        if quant_kernel and _use_quant_kernel(subscripts, w):
            from vgate_tpu.ops.pallas.quant_matmul import (
                int8_matmul_pallas,
            )

            return int8_matmul_pallas(
                x, w.q, w.scale, out_dtype=out_dtype
            )
        out = jnp.einsum(subscripts, x, w.q.astype(x.dtype), **kw)
        return out * w.scale.astype(out_dtype)
    return jnp.einsum(subscripts, x, w, **kw)


def quantize_decoder_params(params: Any, spec, bits: int = 8) -> Any:
    """Quantize the projection weights of a loaded (possibly sharded) param
    pytree in place of their bf16 versions.  Dense models quantize all seven
    projections; MoE models quantize q/k/v/o per-channel and gate/up/down
    per (expert, channel), leaving the tiny fp32 router exact."""
    out = {
        "embed": params["embed"],  # gathers stay high-precision
        "final_norm": params["final_norm"],
    }
    layers = dict(params["layers"])
    for name in ("q", "k", "v", "o"):
        entry = dict(layers[name])
        entry["w"] = quantize_stacked(layers[name]["w"], bits)
        layers[name] = entry
    expert_quant = quantize_expert_stacked if spec.is_moe else quantize_stacked
    for name in ("gate", "up", "down"):
        entry = dict(layers[name])
        entry["w"] = expert_quant(layers[name]["w"], bits)
        layers[name] = entry
    out["layers"] = layers
    if "lm_head" in params:
        out["lm_head"] = quantize_tensor(params["lm_head"], bits)
    return out
