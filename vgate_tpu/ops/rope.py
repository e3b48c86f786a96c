"""Rotary position embeddings (rotate-half formulation, matching the
HF Qwen2/Llama convention so torch parity tests line up exactly),
including the Llama-3.1 long-context frequency scaling."""

from __future__ import annotations

import math

import jax.numpy as jnp


def rope_frequencies(
    head_dim: int, theta: float = 10000.0, scaling=None
) -> jnp.ndarray:
    """Inverse frequencies, shape [head_dim // 2], fp32.

    ``scaling`` (optional) is the Llama-3.1 rule as a tuple
    ``(factor, low_freq_factor, high_freq_factor, original_max_pos)``:
    low-frequency components (wavelength beyond the original context)
    are slowed by ``factor``, high-frequency ones kept, and the band in
    between interpolated — the published recipe that stretches a model
    trained at ``original_max_pos`` to ``factor``x the context.
    """
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    inv_freq = 1.0 / (theta ** exponent)
    if scaling is None:
        return inv_freq
    factor, low_f, high_f, orig_max = scaling
    low_wavelen = orig_max / low_f
    high_wavelen = orig_max / high_f
    wavelen = 2.0 * math.pi / inv_freq
    smooth = (orig_max / wavelen - low_f) / (high_f - low_f)
    mid = (1.0 - smooth) * inv_freq / factor + smooth * inv_freq
    return jnp.where(
        wavelen > low_wavelen,
        inv_freq / factor,
        jnp.where(wavelen < high_wavelen, inv_freq, mid),
    )


def apply_rope(
    x: jnp.ndarray,
    positions: jnp.ndarray,
    theta: float = 10000.0,
    scaling=None,
    rotary_dim: int = 0,
) -> jnp.ndarray:
    """Rotate ``x`` of shape [..., seq, heads, head_dim] by per-token angles.

    ``positions`` has shape broadcastable to x.shape[:-2] (i.e. [..., seq]).
    Computed in fp32, returned in the input dtype.  With ``rotary_dim``
    (partial rotary, Qwen3-Next's 64 of 256) only the first
    ``rotary_dim`` dimensions of a head rotate, with frequencies counted
    over those; the rest pass through.
    """
    if 0 < rotary_dim < x.shape[-1]:
        return jnp.concatenate(
            [
                apply_rope(x[..., :rotary_dim], positions, theta, scaling),
                x[..., rotary_dim:],
            ],
            axis=-1,
        )
    head_dim = x.shape[-1]
    inv_freq = rope_frequencies(head_dim, theta, scaling)
    angles = positions.astype(jnp.float32)[..., None] * inv_freq  # [..., S, hd/2]
    cos = jnp.cos(angles)[..., None, :]  # [..., S, 1, hd/2]
    sin = jnp.sin(angles)[..., None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = jnp.split(x32, 2, axis=-1)
    # rotate_half: (x1, x2) -> (x1*cos - x2*sin, x2*cos + x1*sin)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)
