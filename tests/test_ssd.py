"""Mamba-2 pieces (``vgate_tpu/ops/ssd.py``): the chunk-wise prompt form
against the token-by-token recurrence, the Pallas step kernel against
its jnp twin (interpret mode on the CPU, the pattern of
tests/test_gated_delta.py), and the convolution with its bias."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vgate_tpu.ops import gated_delta as gd
from vgate_tpu.ops import ssd


def case(B=2, S=150, H=4, P=16, G=2, N=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (B, S, H, P))
    # steps from 0.001 to 0.1 against A from -1 to -16: decays a step
    # from 0.2 to 0.999, the draws' range
    dt = jnp.exp(jax.random.uniform(
        ks[1], (B, S, H), minval=jnp.log(1e-3), maxval=jnp.log(0.1)))
    A = -jax.random.uniform(ks[2], (H,), minval=1.0, maxval=16.0)
    Bm = jax.random.normal(ks[3], (B, S, G, N))
    Cm = jax.random.normal(ks[4], (B, S, G, N))
    S0 = jax.random.normal(ks[5], (B, H, P, N)) * 0.1
    return x, dt, A, Bm, Cm, S0


@pytest.mark.parametrize("S, chunk", [(150, 64), (64, 64), (7, 64),
                                      (41, 16), (300, 128)])
def test_chunkwise_prefill_equals_the_recurrence(S, chunk):
    """Across, at and under a chunk; from a state that is not zero."""
    x, dt, A, Bm, Cm, S0 = case(S=S)
    y1, s1 = ssd.ssd_chunked(x, dt, A, Bm, Cm, S0, chunk)
    y2, s2 = ssd.ssd_recurrent(x, dt, A, Bm, Cm, S0)
    np.testing.assert_allclose(y1, y2, atol=5e-6)
    np.testing.assert_allclose(s1, s2, atol=5e-6)


def test_a_chunked_prefill_carries_the_state_across_its_border():
    x, dt, A, Bm, Cm, S0 = case(S=41)
    whole, s_whole = ssd.ssd_chunked(x, dt, A, Bm, Cm, S0, 16)
    cut = lambda t, lo, hi: t[:, lo:hi]
    parts, s = [], S0
    for lo, hi in ((0, 16), (16, 32), (32, 41)):
        y, s = ssd.ssd_chunked(cut(x, lo, hi), cut(dt, lo, hi), A,
                               cut(Bm, lo, hi), cut(Cm, lo, hi), s, 16)
        parts.append(y)
    np.testing.assert_allclose(jnp.concatenate(parts, 1), whole, atol=5e-6)
    np.testing.assert_allclose(s, s_whole, atol=5e-6)


def test_padded_positions_do_not_move_the_state():
    x, dt, A, Bm, Cm, S0 = case()
    lens = jnp.array([150, 97])
    real = jnp.arange(150)[None, :] < lens[:, None]
    dt = jnp.where(real[..., None], dt, 0.0)
    _, padded = ssd.ssd_chunked(x, dt, A, Bm, Cm, S0, 64)
    cut = lambda t: t[1:, :97]
    _, exact = ssd.ssd_recurrent(
        cut(x), cut(dt), A, cut(Bm), cut(Cm), S0[1:])
    np.testing.assert_allclose(padded[1:], exact, atol=5e-6)


# (heads, head size, groups, state size, heads a program: 0 = the rule's)
STEP_CASES = [
    (4, 16, 2, 128, 0), (128, 64, 8, 128, 0),  # Nemotron-H: 4 groups of 16
    # Granite 4.0-H: ONE group of 64 heads, each blocking inside it
    (64, 64, 1, 128, 0), (64, 64, 1, 128, 16), (64, 64, 1, 128, 32),
    (64, 64, 1, 128, 64),
    # Nemotron-H's at one group and at two a program
    (128, 64, 8, 128, 16), (128, 64, 8, 128, 32),
]


@pytest.mark.parametrize("H, P, G, N, block", STEP_CASES)
def test_step_kernel_equals_its_twin_in_interpret_mode(H, P, G, N, block):
    B = 3
    x, dt, A, Bm, Cm, S0 = case(B=B, S=1, H=H, P=P, G=G, N=N, seed=1)
    x, dt, Bm, Cm = x[:, 0], dt[:, 0], Bm[:, 0], Cm[:, 0]
    dt = dt.at[1].set(0.0)  # row 1 is an idle slot
    stack = lambda: jnp.stack([S0, 2.0 * S0, 3.0 * S0])
    layer = jnp.int32(1)
    y_t, s_t = ssd.ssd_step(x, dt, A, Bm, Cm, stack(), layer)
    y_k, s_k = ssd.ssd_step(x, dt, A, Bm, Cm, stack(), layer,
                            interpret=True, block=block)
    np.testing.assert_allclose(y_k, y_t, atol=1e-5)
    np.testing.assert_allclose(s_k, s_t, atol=1e-6)
    # the other layers, and the idle row, bit for bit
    for got in (s_t, s_k):
        np.testing.assert_array_equal(got[0], S0)
        np.testing.assert_array_equal(got[2], 3.0 * S0)
        np.testing.assert_array_equal(got[1, 1], 2.0 * S0[1])


@pytest.mark.parametrize("H, G, want", [
    (128, 8, 64),  # Nemotron-H: four groups of 16
    (64, 1, 64),  # Granite 4.0-H: its one group
    (4, 2, 4), (4, 1, 4),  # the tiny presets: everything
    (48, 3, 48), (80, 5, 16),  # whole groups that divide the groups
    (96, 1, 48), (80, 1, 40),  # a part that divides the group
])
def test_the_step_kernels_block_is_whole_groups_or_a_part_of_one(
        H, G, want):
    from vgate_tpu.ops.pallas.ssd import block_heads

    hb = block_heads(H, G)
    assert hb == want
    per_group = H // G
    assert H % hb == 0 and (hb % per_group == 0 or per_group % hb == 0)


def test_one_step_equals_one_token_of_the_recurrence():
    x, dt, A, Bm, Cm, S0 = case(S=1)
    y_r, s_r = ssd.ssd_recurrent(x, dt, A, Bm, Cm, S0)
    y_s, s_s = ssd.ssd_step(x[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0],
                            S0[None], jnp.int32(0))
    np.testing.assert_allclose(y_s, y_r[:, 0], atol=1e-6)
    np.testing.assert_allclose(s_s[0], s_r, atol=1e-6)


def test_the_convolution_adds_its_bias_before_the_silu():
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    x = jax.random.normal(ks[0], (2, 9, 6))
    tail = jax.random.normal(ks[1], (2, 3, 6))
    w = jax.random.normal(ks[2], (6, 4))
    bias = jax.random.normal(ks[3], (6,))
    y, new_tail = gd.causal_conv(x, tail, w, jnp.array([9, 5]), bias)
    cat = jnp.concatenate([tail, x], axis=1)
    want = jax.nn.silu(
        sum(cat[:, j:j + 9] * w[:, j] for j in range(4)) + bias)
    np.testing.assert_allclose(y, want, atol=1e-6)
    # the tail ends at each row's real length
    np.testing.assert_allclose(new_tail[0], cat[0, 9:12], atol=1e-6)
    np.testing.assert_allclose(new_tail[1], cat[1, 5:8], atol=1e-6)
    plain, _ = gd.causal_conv(x, tail, w, jnp.array([9, 5]))
    assert float(jnp.abs(plain - y).max()) > 1e-3
