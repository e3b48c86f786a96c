"""Mamba-2 (SSD: the state-space duality form) in plain ``jax.numpy``:
the chunk-wise form of the recurrence for prompt passes, the same token
by token, and the one-token step (the twin of ``ops/pallas/ssd.py``).
The convolution in front of it is ``ops/gated_delta.py causal_conv``.

The recurrence, per head, with ``S`` a ``[P, N]`` float32 state (``P``
the head size, ``N`` the state size), ``x_t`` in ``R^P``, ``B_t`` and
``C_t`` in ``R^N`` shared by the heads of a group, ``dt_t > 0`` and a
negative scalar ``A``::

    S <- exp(dt_t A) S + dt_t x_t B_t^T;   y_t = S C_t

(the skip ``D x_t`` is the caller's).  A position with ``dt = 0`` leaves
``S`` exactly as it was: that is how padded prompt positions and idle
decode slots pass through without moving the state.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST


def _mm(e, a, b):
    return jnp.einsum(e, a, b, precision=_HI)


def ssd_chunked(x, dt, A, Bm, Cm, state, chunk: int = 128):
    """The recurrence over a whole prompt, chunk by chunk.

    x: [B, S, H, P], dt: [B, S, H] float32 (0 at padded positions), A:
    [H] float32 (negative), Bm, Cm: [B, S, G, N] (head h reads group
    ``h // (H // G)``), state: [B, H, P, N] float32 at the prompt's
    start.  Returns (y [B, S, H, P] float32, final state).  Inside a
    chunk the outputs are one masked product (the attention-like form);
    between chunks the state is carried by a scan whose body holds the
    chunk's temporaries, so they never stand for the whole prompt."""
    f32 = jnp.float32
    B, S, H, P = x.shape
    G, N = Bm.shape[-2:]
    R = H // G
    Q = min(chunk, S)
    pad = (-S) % Q
    if pad:
        widen = lambda t: jnp.pad(
            t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        x, dt, Bm, Cm = map(widen, (x, dt, Bm, Cm))
    n = (S + pad) // Q
    # chunks lead, for the scan; heads as [G, R] in front of the
    # chunk's positions, so that positions and sizes lie on the lanes
    def split(t, tail, perm):
        return jnp.transpose(t.astype(f32).reshape((B, n, Q) + tail), perm)

    xs = (split(x, (G, R, P), (1, 0, 3, 4, 2, 5)),
          split(dt, (G, R), (1, 0, 3, 4, 2)),
          split(Bm, (G, N), (1, 0, 3, 2, 4)),
          split(Cm, (G, N), (1, 0, 3, 2, 4)))
    A = A.astype(f32).reshape(G, R, 1)
    lower = jnp.tril(jnp.ones((Q, Q), bool))

    def step(S_, c):
        x_c, dt_c, B_c, C_c = c  # [B,G,R,Q,P] [B,G,R,Q] [B,G,Q,N] [B,G,Q,N]
        cum = jnp.cumsum(dt_c * A, axis=-1)  # log decay from the chunk's start
        xdt = x_c * dt_c[..., None]
        # within the chunk: y_i += sum_{j<=i} e^(cum_i - cum_j) (C_i.B_j) dt_j x_j
        diff = cum[..., :, None] - cum[..., None, :]  # [B, G, R, Qi, Qj]
        decay = jnp.where(lower, jnp.exp(jnp.where(lower, diff, 0.0)), 0.0)
        cb = _mm("bgin,bgjn->bgij", C_c, B_c)
        y = _mm("bgrij,bgrjp->bgrip", cb[:, :, None] * decay, xdt)
        # from the state the chunk started with
        S_g = S_.reshape(B, G, R, P, N)
        y = y + _mm("bgin,bgrpn->bgrip", C_c, S_g) * jnp.exp(cum)[..., None]
        # the chunk's own contribution to the state, decayed to its end
        last = cum[..., -1:]  # [B, G, R, 1]
        w = jnp.exp(last - cum)[..., None] * xdt
        S_g = (S_g * jnp.exp(last)[..., None]
               + _mm("bgrjp,bgjn->bgrpn", w, B_c))
        return S_g.reshape(B, H, P, N), y

    state, y = jax.lax.scan(step, state.astype(f32), xs)
    # [n, B, G, R, Q, P] -> [B, n * Q, H, P]
    y = jnp.transpose(y, (1, 0, 4, 2, 3, 5)).reshape(B, n * Q, H, P)
    return y[:, :S], state


def ssd_recurrent(x, dt, A, Bm, Cm, state):
    """The same recurrence token by token (tests hold the chunk-wise
    form to it).  Shapes as ``ssd_chunked``."""
    f32 = jnp.float32
    R = x.shape[2] // Bm.shape[2]

    def step(S_, c):
        x_t, dt_t, B_t, C_t = c  # [B, H, P] [B, H] [B, G, N]
        B_h, C_h = jnp.repeat(B_t, R, axis=1), jnp.repeat(C_t, R, axis=1)
        S_ = (S_ * jnp.exp(dt_t * A)[..., None, None]
              + (x_t * dt_t[..., None])[..., :, None] * B_h[..., None, :])
        return S_, _mm("bhpn,bhn->bhp", S_, C_h)

    t_major = lambda t: jnp.moveaxis(t.astype(f32), 1, 0)
    state, y = jax.lax.scan(
        step, state.astype(f32), tuple(map(t_major, (x, dt, Bm, Cm))))
    return jnp.moveaxis(y, 0, 1), state


def ssd_step(x, dt, A, Bm, Cm, state, layer, use_pallas=False,
             interpret=False, block=0):
    """One decode step on the FULL ``[Lm, B, H, P, N]`` state, at
    ``layer`` (a traced scalar).  x: [B, H, P], dt: [B, H] float32 (0
    for a row that must not move), A: [H], Bm, Cm: [B, G, N]; ``block``:
    the kernel's heads a program (0: its own rule).  Returns (y [B, H,
    P] float32, state)."""
    f32 = jnp.float32
    x, Bm, Cm = x.astype(f32), Bm.astype(f32), Cm.astype(f32)
    decay = jnp.exp(dt * A.astype(f32))
    xdt = x * dt[..., None]
    if use_pallas or interpret:
        from vgate_tpu.ops.pallas.ssd import ssd_step_pallas

        return ssd_step_pallas(xdt, decay, Bm, Cm, state, layer,
                               interpret=interpret, block=block)
    R = x.shape[1] // Bm.shape[1]
    B_h, C_h = jnp.repeat(Bm, R, axis=1), jnp.repeat(Cm, R, axis=1)
    S_ = (state[layer] * decay[..., None, None]
          + xdt[..., :, None] * B_h[..., None, :])
    return _mm("bhpn,bhn->bhp", S_, C_h), state.at[layer].set(S_)
