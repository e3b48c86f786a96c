"""Gated DeltaNet pieces (``vgate_tpu/ops/gated_delta.py``): the
chunk-wise prompt form against the token-by-token recurrence, the Pallas
step kernel against its jnp twin (interpret mode on the CPU, the pattern
of tests/test_pallas_kernels.py), the convolution's carried tail, and
the grouped product's kernel against XLA's ragged product."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vgate_tpu.ops import gated_delta as gd
from vgate_tpu.ops.pallas.grouped_matmul import grouped_matmul_pallas, visits


def case(B=2, S=150, H=4, dk=16, dv=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = gd.l2_normalize(jax.random.normal(ks[0], (B, S, H, dk))) * dk ** -0.5
    k = gd.l2_normalize(jax.random.normal(ks[1], (B, S, H, dk)))
    v = jax.random.normal(ks[2], (B, S, H, dv))
    g = -jax.random.uniform(ks[3], (B, S, H)) * 0.7
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, S, H)))
    S0 = jax.random.normal(ks[5], (B, H, dk, dv)) * 0.1
    return q, k, v, g, beta, S0


@pytest.mark.parametrize("S", [150, 64, 7])  # across, at and under a chunk
def test_chunkwise_prefill_equals_the_recurrence(S):
    q, k, v, g, beta, S0 = case(S=S)
    o1, s1 = gd.gated_delta_chunked(q, k, v, g, beta, S0)
    o2, s2 = gd.gated_delta_recurrent(q, k, v, g, beta, S0)
    np.testing.assert_allclose(o1, o2, atol=2e-6)
    np.testing.assert_allclose(s1, s2, atol=2e-6)


def test_padded_positions_do_not_move_the_state():
    q, k, v, g, beta, S0 = case()
    lens = jnp.array([150, 97])
    real = (jnp.arange(150)[None, :] < lens[:, None])[..., None]
    g, beta = jnp.where(real, g, 0.0), jnp.where(real, beta, 0.0)
    _, padded = gd.gated_delta_chunked(q, k, v, g, beta, S0)
    cut = lambda x: x[1:, :97]
    _, exact = gd.gated_delta_recurrent(
        cut(q), cut(k), cut(v), cut(g), cut(beta), S0[1:])
    np.testing.assert_allclose(padded[1:], exact, atol=2e-6)


@pytest.mark.parametrize("heads, dk, dv", [(4, 16, 16), (32, 128, 128)])
def test_step_kernel_equals_its_twin_in_interpret_mode(heads, dk, dv):
    B = 3
    q, k, v, g, beta, S0 = case(B=B, S=1, H=heads, dk=dk, dv=dv, seed=1)
    q, k, v, g, beta = q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0]
    # row 1 is an idle slot: g = 0, beta = 0 must leave it bit for bit
    g, beta = g.at[1].set(0.0), beta.at[1].set(0.0)
    state = jnp.stack([S0, 2.0 * S0, 3.0 * S0])
    layer = jnp.int32(1)
    o_t, s_t = gd.gated_delta_step(q, k, v, g, beta, state, layer)
    o_k, s_k = gd.gated_delta_step(
        q, k, v, g, beta, jnp.stack([S0, 2.0 * S0, 3.0 * S0]), layer,
        interpret=True)
    np.testing.assert_allclose(o_k, o_t, atol=1e-5)
    np.testing.assert_allclose(s_k, s_t, atol=1e-5)
    assert np.array_equal(s_k[0], S0) and np.array_equal(s_k[2], 3.0 * S0)
    assert np.array_equal(s_k[1, 1], 2.0 * S0[1])
    # and the step is the recurrence's
    o_r, s_r = gd.gated_delta_recurrent(
        q[:, None], k[:, None], v[:, None], g[:, None], beta[:, None],
        2.0 * S0)
    np.testing.assert_allclose(o_k, o_r[:, 0], atol=1e-5)
    np.testing.assert_allclose(s_k[1], s_r, atol=1e-5)


def test_convolution_tail_is_taken_at_the_real_length():
    B, S, C, K = 2, 12, 6, 4
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    x = jax.random.normal(ks[0], (B, S, C))
    w = jax.random.normal(ks[1], (C, K))
    zeros = jnp.zeros((B, K - 1, C))
    lens = jnp.array([12, 5])
    y, tail = gd.causal_conv(x, zeros, w, lens)
    np.testing.assert_array_equal(tail[0], x[0, 9:12])
    np.testing.assert_array_equal(tail[1], x[1, 2:5])  # not the bucket's end
    # a prompt in two pieces through the tail equals it in one
    y1, t1 = gd.causal_conv(x[:, :7], zeros, w)
    y2, _ = gd.causal_conv(x[:, 7:], t1, w)
    np.testing.assert_allclose(jnp.concatenate([y1, y2], 1), y, atol=1e-6)
    # a sequence shorter than the taps keeps the zeros before it
    _, short = gd.causal_conv(x, zeros, w, jnp.array([2, 1]))
    np.testing.assert_array_equal(short[0, 0], jnp.zeros(C))
    np.testing.assert_array_equal(short[0, 1:], x[0, :2])


@pytest.mark.parametrize("sizes", [
    [10, 0, 20, 3, 7], [0, 0, 0, 0, 0], [64, 0, 0, 0, 0], [1, 1, 1, 1, 1],
    [16, 16, 16, 16, 0],
])
def test_grouped_product_kernel_equals_the_ragged_product(sizes):
    ks = jax.random.split(jax.random.PRNGKey(3), 2)
    M, K, N, E, L = 64, 32, 48, 5, 3
    lhs = jax.random.normal(ks[0], (M, K))
    rhs = jax.random.normal(ks[1], (L, E, K, N))
    gs = jnp.array(sizes, jnp.int32)
    out = grouped_matmul_pallas(lhs, rhs, gs, jnp.int32(1), tm=16, tn=16,
                                interpret=True)
    want = jax.lax.ragged_dot(lhs, rhs[1], gs)
    n = int(gs.sum())
    np.testing.assert_allclose(out[:n], want[:n], atol=1e-5)
    # an expert's visits are consecutive: its matrix is fetched once
    group, _, _, count = visits(gs, M, 16)
    seen = [int(x) for x in group[:int(count)]]
    assert seen == sorted(seen)
