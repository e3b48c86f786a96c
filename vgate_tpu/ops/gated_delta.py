"""Gated DeltaNet linear attention (Qwen3-Next's linear layers) in plain
``jax.numpy``: the causal depth-wise convolution with its carried tail,
the gates, the chunk-wise form of the recurrence for prompt passes and
the one-token step (the twin of ``ops/pallas/gated_delta.py``).

The recurrence, per value head, with ``S`` a ``[dk, dv]`` float32 state::

    S <- S * exp(g_t);  d_t = beta_t (v_t - S^T k_t);
    S <- S + k_t d_t^T;  o_t = S^T q_t

A position with ``g = 0`` and ``beta = 0`` leaves ``S`` exactly as it
was: that is how padded prompt positions and idle decode slots pass
through without moving the state.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

CHUNK = 64  # the published kernels' chunk length
_HI = jax.lax.Precision.HIGHEST


def causal_conv(x, tail, w, lens=None, bias=None, act=jax.nn.silu):
    """Depth-wise causal convolution (plus ``bias`` [C], where the layer
    has one), then ``act`` in float32 (SiLU for Gated DeltaNet and
    Mamba-2; None for LFM2's gated short convolution, which has none).

    x: [B, S, C] pre-convolution rows; tail: [B, K-1, C] the K-1 rows
    before them (zeros at a sequence's start); w: [C, K], ``w[:, K-1]``
    multiplies the current row (torch ``conv1d`` layout, squeezed).
    Returns (y [B, S, C] in x's dtype, new tail [B, K-1, C]): the last
    K-1 real rows, counted at ``lens`` ([B]; default S) so that a
    bucket's padding never enters the tail."""
    B, S, C = x.shape
    K = w.shape[-1]
    cat = jnp.concatenate([tail.astype(x.dtype), x], axis=1)  # [B, S+K-1, C]
    w32 = w.astype(jnp.float32)
    y = sum(
        cat[:, j:j + S].astype(jnp.float32) * w32[:, j] for j in range(K)
    )
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    if lens is None:
        new_tail = cat[:, S:]
    else:
        # rows lens .. lens + K - 2 of ``cat``, picked by a one-hot
        # product (one term a sum: exact).  As a gather
        # (take_along_axis) XLA:TPU fused it into a vector program that
        # loaded past its VMEM and halted the chip at some shapes
        # (PERF.md section 6, PR 27)
        idx = lens[:, None] + jnp.arange(K - 1)[None, :]  # [B, K-1]
        pick = idx[:, :, None] == jnp.arange(S + K - 1)[None, None, :]
        new_tail = jnp.einsum(
            "bjt,btc->bjc", pick.astype(cat.dtype), cat,
            preferred_element_type=jnp.float32,
        ).astype(cat.dtype)
    if act is not None:
        y = act(y)
    return y.astype(x.dtype), new_tail


def gates(a, b, a_log, dt_bias):
    """(g, beta) in float32 from the layer's ``a``/``b`` projections
    ([..., H]) and its per-head ``A_log`` / ``dt_bias``."""
    f32 = jnp.float32
    beta = jax.nn.sigmoid(b.astype(f32))
    g = -jnp.exp(a_log.astype(f32)) * jax.nn.softplus(
        a.astype(f32) + dt_bias.astype(f32)
    )
    return g, beta


def l2_normalize(x, eps: float = 1e-6):
    x32 = x.astype(jnp.float32)
    return x32 * jax.lax.rsqrt(
        jnp.sum(x32 * x32, axis=-1, keepdims=True) + eps
    )


def gated_delta_chunked(q, k, v, g, beta, state):
    """The recurrence over a whole prompt, chunk by chunk.

    q, k: [B, S, H, dk] (normalised, q scaled), v: [B, S, H, dv], g,
    beta: [B, S, H] float32, state: [B, H, dk, dv] float32 at the
    prompt's start.  Returns (o [B, S, H, dv] float32, final state).
    Inside a chunk the rank-1 updates are resolved by one triangular
    solve (the WY form); between chunks the state is carried by a scan,
    so a 2,048-token prompt is 32 sequential steps and not 2,048."""
    f32 = jnp.float32
    B, S, H, dk = q.shape
    dv = v.shape[-1]
    C = min(CHUNK, S)
    pad = (-S) % C
    if pad:
        widen = lambda x: jnp.pad(
            x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2)
        )
        q, k, v, g, beta = map(widen, (q, k, v, g, beta))
    N = (S + pad) // C
    # [N, B, H, C, d]: chunks lead, for the scan
    split = lambda x: jnp.moveaxis(
        x.astype(f32).reshape(B, N, C, H, -1), (1, 3), (0, 2)
    )
    q, k, v = split(q), split(k), split(v)
    g = split(g[..., None])[..., 0]  # [N, B, H, C]
    beta = split(beta[..., None])  # [N, B, H, C, 1]
    gc = jnp.cumsum(g, axis=-1)  # decay from the chunk's start, in logs
    lower = jnp.tril(jnp.ones((C, C), bool))
    strict = jnp.tril(jnp.ones((C, C), bool), -1)
    # exp(gc_i - gc_j) for i >= j; the exponent is masked BEFORE exp so
    # that the upper triangle cannot overflow
    diff = gc[..., :, None] - gc[..., None, :]
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, diff, 0.0)), 0.0)
    k_beta, v_beta = k * beta, v * beta
    mm = lambda e, a, b: jnp.einsum(e, a, b, precision=_HI)
    # (I + A)^-1 with A strictly lower: the chunk's updates, unrolled
    A = jnp.where(strict, mm("...id,...jd->...ij", k_beta, k) * decay, 0.0)
    eye = jnp.eye(C, dtype=f32)
    T = jax.scipy.linalg.solve_triangular(
        eye + A, jnp.broadcast_to(eye, A.shape), lower=True,
        unit_diagonal=True,
    )
    value = mm("...ij,...jd->...id", T, v_beta)
    k_cum = mm("...ij,...jd->...id", T, k_beta * jnp.exp(gc)[..., None])
    qk = jnp.where(lower, mm("...id,...jd->...ij", q, k) * decay, 0.0)

    def step(S_, xs):
        q_i, k_i, value_i, k_cum_i, qk_i, gc_i = xs
        v_new = value_i - mm("...cd,...de->...ce", k_cum_i, S_)
        o_i = (
            mm("...cd,...de->...ce", q_i * jnp.exp(gc_i)[..., None], S_)
            + mm("...ij,...je->...ie", qk_i, v_new)
        )
        last = gc_i[..., -1:]
        S_ = S_ * jnp.exp(last)[..., None] + mm(
            "...cd,...ce->...de", k_i * jnp.exp(last - gc_i)[..., None],
            v_new,
        )
        return S_, o_i

    state, o = jax.lax.scan(
        step, state.astype(f32), (q, k, value, k_cum, qk, gc)
    )
    o = jnp.moveaxis(o, (0, 2), (1, 3)).reshape(B, N * C, H, dv)
    return o[:, :S], state


def gated_delta_recurrent(q, k, v, g, beta, state):
    """The same recurrence token by token (tests hold the chunk-wise
    form to it).  Shapes as ``gated_delta_chunked``."""
    f32 = jnp.float32

    def step(S_, xs):
        q_t, k_t, v_t, g_t, b_t = xs  # [B, H, d] / [B, H]
        S_ = S_ * jnp.exp(g_t)[..., None, None]
        d = b_t[..., None] * (
            v_t - jnp.einsum("bhkv,bhk->bhv", S_, k_t, precision=_HI)
        )
        S_ = S_ + k_t[..., :, None] * d[..., None, :]
        return S_, jnp.einsum("bhkv,bhk->bhv", S_, q_t, precision=_HI)

    t_major = lambda x: jnp.moveaxis(x.astype(f32), 1, 0)
    state, o = jax.lax.scan(
        step, state.astype(f32), tuple(map(t_major, (q, k, v, g, beta)))
    )
    return jnp.moveaxis(o, 0, 1), state


def gated_delta_step(q, k, v, g, beta, state, layer, use_pallas=False,
                     interpret=False):
    """One decode step on the FULL ``[Lr, B, H, dk, dv]`` state, at
    ``layer`` (a traced scalar).  q, k: [B, H, dk], v: [B, H, dv], g,
    beta: [B, H] float32.  Returns (o [B, H, dv] float32, state)."""
    f32 = jnp.float32
    q, k, v = q.astype(f32), k.astype(f32), v.astype(f32)
    decay = jnp.exp(g)
    if use_pallas or interpret:
        from vgate_tpu.ops.pallas.gated_delta import gated_delta_step_pallas

        return gated_delta_step_pallas(
            q, k, k * (beta * decay)[..., None], v * beta[..., None],
            jnp.broadcast_to(decay[..., None], v.shape), state, layer,
            interpret=interpret,
        )
    S_ = state[layer] * decay[..., None, None]
    d = beta[..., None] * (v - jnp.einsum("bhkv,bhk->bhv", S_, k,
                                          precision=_HI))
    S_ = S_ + k[..., :, None] * d[..., None, :]
    o = jnp.einsum("bhkv,bhk->bhv", S_, q, precision=_HI)
    return o, state.at[layer].set(S_)
