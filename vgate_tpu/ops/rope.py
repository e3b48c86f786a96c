"""Rotary position embeddings (rotate-half formulation, matching the
HF Qwen2/Llama convention so torch parity tests line up exactly),
including the Llama-3.1 long-context frequency scaling."""

from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np


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
    if scaling[0] == "yarn":
        return _yarn_frequencies(inv_freq, head_dim, theta, *scaling[1:])
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


def _yarn_frequencies(inv_freq, dim, theta, factor, beta_fast, beta_slow,
                      orig_max):
    """YaRN (the published recipe, DeepSeek-V3's form): frequency i
    makes ``orig_max * f_i / 2 pi`` rotations over the original context;
    those that make more than ``beta_fast`` keep their frequency, those
    that make fewer than ``beta_slow`` are interpolated (``f / factor``),
    and between the two dimensions where that happens (the first rounded
    down, the second up) a linear ramp blends them."""
    def correction_dim(rotations):
        return (dim * math.log(orig_max / (rotations * 2.0 * math.pi))
                / (2.0 * math.log(theta)))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip(
        (jnp.arange(dim // 2, dtype=jnp.float32) - low) / (high - low), 0, 1)
    return inv_freq / factor * ramp + inv_freq * (1.0 - ramp)


def position_scale(positions, beta: float, orig_max: int):
    """gamma(pos) = 1 + beta * ln(1 + pos // orig_max), float32: the
    scaling of a query by its position (1 inside the original context)."""
    return 1.0 + beta * jnp.log1p(
        (positions // orig_max).astype(jnp.float32))


def apply_rope(
    x: jnp.ndarray,
    positions: jnp.ndarray,
    theta: float = 10000.0,
    scaling=None,
    rotary_dim: int = 0,
    sections=(),
    amplitude: float = 1.0,
) -> jnp.ndarray:
    """Rotate ``x`` of shape [..., seq, heads, head_dim] by per-token angles.

    ``positions`` has shape broadcastable to x.shape[:-2] (i.e. [..., seq]).
    Computed in fp32, returned in the input dtype.  With ``rotary_dim``
    (partial rotary, Qwen3-Next's 64 of 256) only the first
    ``rotary_dim`` dimensions of a head rotate, with frequencies counted
    over those; the rest pass through.  With ``sections`` (M-RoPE:
    ``(16, 24, 24)`` of 64 frequencies) and positions of SEVERAL
    components, ``[len(sections), ..., seq]``, frequency ``i`` turns by
    the component of its section; positions of one component (a text
    token's are equal) take the plain path, whatever ``sections``.
    ``amplitude`` (YaRN's published ``attention_factor``) multiplies cos
    and sin: the rotated part comes out that much longer.
    """
    if 0 < rotary_dim < x.shape[-1]:
        return jnp.concatenate(
            [
                apply_rope(x[..., :rotary_dim], positions, theta, scaling,
                           sections=sections, amplitude=amplitude),
                x[..., rotary_dim:],
            ],
            axis=-1,
        )
    head_dim = x.shape[-1]
    inv_freq = rope_frequencies(head_dim, theta, scaling)
    if sections and positions.ndim == x.ndim - 1:
        if sum(sections) != head_dim // 2:
            raise ValueError(f"sections {sections} of {head_dim // 2} "
                             "frequencies")
        comp = np.repeat(np.arange(len(sections)), sections)
        # [..., S, hd/2]: each frequency's own component
        positions = jnp.moveaxis(positions, 0, -1)[..., comp]
        angles = positions.astype(jnp.float32) * inv_freq
    else:
        angles = positions.astype(jnp.float32)[..., None] * inv_freq
    cos = jnp.cos(angles)[..., None, :]  # [..., S, 1, hd/2]
    sin = jnp.sin(angles)[..., None, :]
    if amplitude != 1.0:
        cos, sin = cos * amplitude, sin * amplitude
    x32 = x.astype(jnp.float32)
    x1, x2 = jnp.split(x32, 2, axis=-1)
    # rotate_half: (x1, x2) -> (x1*cos - x2*sin, x2*cos + x1*sin)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)
