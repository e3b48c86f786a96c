"""Latent attention's two forms and what they stand on: the absorbed
form equals the non-absorbed one, the Pallas decode kernel (interpret
mode) equals its ``jax.numpy`` twin over a ragged batch and writes the
new row into the right page, YaRN's inverse frequencies on hand-checked
cases, the queries' position scaling, the checkpoint's interleaved
rotary layout undone."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vgate_tpu.models import hybrid
from vgate_tpu.models.specs import spec_for_model_id
from vgate_tpu.ops.attention import (
    causal_prefill_attention,
    mla_decode_attention,
)
from vgate_tpu.ops.kv_quant import kv_write_tokens
from vgate_tpu.ops.pallas.paged_attention import mla_decode_attention_pallas
from vgate_tpu.ops.rope import apply_rope, position_scale, rope_frequencies
from vgate_tpu.runtime.weights import _deinterleave as deinterleave

SPEC = spec_for_model_id("tiny-mla-moe")


def layer_params(seed=0):
    from vgate_tpu.models.decoder import init_params

    params = init_params(SPEC, jax.random.PRNGKey(seed), jnp.float32)
    return jax.tree.map(lambda a: a[0, 0], params["layers"]["layer"])


@pytest.mark.parametrize("n", [5, 33, 97])
def test_absorbed_form_equals_the_non_absorbed_one(n):
    """The last token's attention output: K and V expanded from the
    latent rows and one softmax over heads of 16 + 16, against the
    query folded through W_uk, the rows read alone, the value sum
    unfolded through W_uv.  Contexts past the original maximum of 32."""
    lp, rng = layer_params(), np.random.default_rng(n)
    normed = jnp.asarray(rng.standard_normal((1, n, 64)), jnp.float32)
    pos = jnp.arange(n)[None]
    width = SPEC.cache_head_dim
    q_nope, q_rope = hybrid._mla_q(normed, lp, SPEC, pos)
    rows = hybrid._mla_latent(normed, lp, SPEC, pos, width)
    k, v = hybrid._mla_expand(rows, lp, SPEC)
    q = jnp.concatenate([q_nope, q_rope], -1)
    want = causal_prefill_attention(
        q, k, v, jnp.array([n]), scale=SPEC.mla_softmax_scale)[0, -1]

    # the absorbed step over a pool that holds the rows
    ps = 4
    pages = jnp.zeros((1, 1, 40, ps, width), jnp.float32)
    table = jnp.arange(1, 1 + -(-n // ps))[None]
    padded = jnp.pad(rows[0], ((0, table.shape[1] * ps - n), (0, 0)))
    pages = pages.at[0, 0, table[0]].set(padded.reshape(-1, ps, width))
    q_lat = jnp.einsum("bhn,khn->bhk", q_nope[:, -1], lp["kv_b_k"]["w"])
    q_abs = jnp.concatenate(
        [q_lat, q_rope[:, -1],
         jnp.zeros((1, SPEC.num_heads, width - SPEC.latent_dim))], -1)
    got = mla_decode_attention(
        q_abs, pages, table, jnp.array([n]), jnp.int32(0),
        v_width=SPEC.kv_lora_rank, scale=SPEC.mla_softmax_scale)
    got = jnp.einsum("bhk,khv->bhv", got, lp["kv_b_v"]["w"])[0]
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("dtype, tol", [(jnp.float32, 2e-5),
                                        (jnp.bfloat16, 2e-2)])
def test_decode_kernel_equals_its_twin_on_a_ragged_batch(dtype, tol):
    """Interpret mode, the cut's row width (384 lanes, 256 of them the
    value): contexts of 17, 0 (an idle row), 8 (a page's last slot), 33
    and 1 token; the new row lands in its page, the idle row writes
    nothing and comes out zero, no other page changes."""
    L, P, ps, W, H, V = 2, 40, 8, 384, 8, 256
    rng = np.random.default_rng(0)
    normal = lambda *s: jnp.asarray(rng.standard_normal(s), dtype)
    pages, q, new = normal(L, 1, P, ps, W), normal(5, H, W), normal(5, W)
    lens = jnp.array([17, 0, 8, 33, 1], jnp.int32)
    table = jnp.asarray(
        rng.permutation(np.arange(1, P))[:30].reshape(5, 6), jnp.int32)
    layer = jnp.int32(1)
    pos = jnp.maximum(lens - 1, 0)
    page_ids = jnp.where(lens > 0, table[jnp.arange(5), pos // ps], 0)
    written = kv_write_tokens(pages, page_ids, pos % ps, new[:, None],
                              layer=layer)
    kw = dict(v_width=V, scale=0.05)
    want = mla_decode_attention(
        q, written, table, jnp.maximum(lens, 1), layer, **kw)
    got, pool = mla_decode_attention_pallas(
        q, pages, table, lens, layer, new, interpret=True, **kw)
    live = np.asarray(lens) > 0
    f32 = lambda a: np.array(a, np.float32)
    np.testing.assert_allclose(f32(got)[live], f32(want)[live],
                               rtol=tol, atol=tol)
    assert not f32(got)[~live].any()
    pool, written = f32(pool), f32(written)
    pool[:, :, 0], written[:, :, 0] = 0, 0  # the trash page
    np.testing.assert_array_equal(pool, written)
    # without the write, over the pool that already holds the rows
    again = mla_decode_attention_pallas(
        q, jnp.asarray(written, dtype), table, lens, layer,
        interpret=True, **kw)
    np.testing.assert_allclose(f32(again)[live], f32(want)[live],
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "float32"])
def test_decode_kernel_two_items_a_trip_give_one_items_bits(dtype):
    """The latent form with `write`, a loop trip serving TWO items of the
    work list against one (``_decode_kernel``): attention and the written
    pool bit for bit.  A chunk is 256 rows here: slots of 2, 0, 1, 2, 1,
    2 and 1 items, so pairs inside a slot, pairs across two slots, both
    items a slot's last, and an odd ninth item."""
    L, ps, W, H, V = 2, 8, 384, 8, 256
    lens = jnp.array([300, 0, 8, 257, 1, 290, 5], jnp.int32)
    B, pages_per_seq = len(lens), 40
    P = 1 + B * pages_per_seq
    rng = np.random.default_rng(1)
    normal = lambda *s: jnp.asarray(rng.standard_normal(s), dtype)
    pages, q, new = normal(L, 1, P, ps, W), normal(B, H, W), normal(B, W)
    table = jnp.asarray(
        rng.permutation(np.arange(1, P)).reshape(B, pages_per_seq), jnp.int32)
    one, two = (
        mla_decode_attention_pallas(
            q, pages, table, lens, jnp.int32(1), new, v_width=V, scale=0.05,
            interpret=True, items=items)
        for items in (1, 2))
    for got, want in zip(two, one):
        np.testing.assert_array_equal(
            np.array(got, np.float32), np.array(want, np.float32))
    # and the rows are where a scatter would have put them
    pos = jnp.maximum(lens - 1, 0)
    page_ids = jnp.where(lens > 0, table[jnp.arange(B), pos // ps], 0)
    written = kv_write_tokens(
        pages, page_ids, pos % ps, new[:, None], layer=jnp.int32(1))
    np.testing.assert_array_equal(
        np.array(two[1], np.float32)[:, :, 1:],
        np.array(written, np.float32)[:, :, 1:])


def test_yarn_frequencies_on_hand_checked_cases():
    """theta 10,000, 8 dimensions, factor 4 over an original maximum of
    32: frequency i = 10000^(-i/4) makes 32 f / 2 pi rotations in 32
    positions: 5.09, 0.51, 0.051, 0.0051.  The correction dimensions for
    32 rotations and for 1 are 8 ln(32 / (r 2 pi)) / (2 ln 10000) =
    -0.80 and 0.71: rounded down and up, and clamped, the ramp runs from
    dimension 0 to dimension 1.  So frequency 0 keeps its value and the
    others are divided by 4."""
    got = np.asarray(rope_frequencies(8, 10000.0, ("yarn", 4.0, 32.0, 1.0, 32)))
    plain = 10000.0 ** (-np.arange(4) / 4)
    np.testing.assert_allclose(got, plain / [1, 4, 4, 4], rtol=1e-6)
    # the published sizes: 64 dimensions, factor 128, original 8,192
    spec = spec_for_model_id("mistralai/Mistral-Small-4-119B-2603")
    got = np.asarray(rope_frequencies(64, 10000.0, spec.rope_scaling))
    plain = 10000.0 ** (-np.arange(32) / 32)
    corr = lambda r: 64 * math.log(8192 / (r * 2 * math.pi)) / (
        2 * math.log(10000))
    low, high = math.floor(corr(32)), math.ceil(corr(1))
    assert (low, high) == (12, 25)
    np.testing.assert_allclose(got[:13], plain[:13], rtol=1e-6)
    np.testing.assert_allclose(got[25:], plain[25:] / 128, rtol=1e-6)
    mid = (19 - low) / (high - low)
    np.testing.assert_allclose(
        got[19], plain[19] / 128 * mid + plain[19] * (1 - mid), rtol=1e-6)
    # no scaling, no change
    np.testing.assert_allclose(
        np.asarray(rope_frequencies(8, 10000.0)), 10000.0 ** (
            -np.arange(4) / 4), rtol=1e-6)


def test_position_scale_leaves_one_past_the_original_maximum():
    pos = jnp.array([0, 31, 32, 95, 96])
    got = np.asarray(position_scale(pos, 0.1, 32))
    want = [1, 1, 1 + 0.1 * math.log(2), 1 + 0.1 * math.log(3),
            1 + 0.1 * math.log(4)]
    np.testing.assert_allclose(got, want, rtol=1e-6)


def test_interleaved_rotary_columns_are_undone():
    """A checkpoint with ``rope_interleave`` rotates pairs (2i, 2i + 1);
    the program rotates halves.  De-interleaving the rotary columns of
    the two projections makes both give the same scores."""
    rng = np.random.default_rng(5)
    dim, pos = 8, jnp.arange(6)[None]
    qw, kw = rng.standard_normal((2, 16, 4 + dim)).astype(np.float32)
    x = jnp.asarray(rng.standard_normal((1, 6, 16)), jnp.float32)

    def paired_rotate(t):  # the checkpoint's own convention
        ang = np.asarray(pos, np.float32)[..., None] * np.asarray(
            rope_frequencies(dim, 10000.0))
        cos, sin = np.cos(ang), np.sin(ang)
        a, b = t[..., 0::2], t[..., 1::2]
        out = np.empty_like(t)
        out[..., 0::2], out[..., 1::2] = a * cos - b * sin, b * cos + a * sin
        return out

    q, k = np.asarray(x @ qw), np.asarray(x @ kw)
    want = np.einsum("bsd,btd->bst", paired_rotate(q[..., 4:]),
                     paired_rotate(k[..., 4:]))
    rot = lambda w: apply_rope(
        (x @ deinterleave(w, dim))[..., None, 4:], pos)[..., 0, :]
    got = jnp.einsum("bsd,btd->bst", rot(qw), rot(kw))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    # columns before the rotary ones stay where they were
    np.testing.assert_array_equal(
        deinterleave(qw, dim)[:, :4], qw[:, :4])
