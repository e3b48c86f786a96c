"""The ONE expert layer (``vgate_tpu/ops/moe.py``) told what Nemotron-H's
LatentMoE differs in: sigmoid scores chosen by score + bias and weighted
by the score alone, a factor on the routed sum, experts of two matrices
with ``relu(.)^2`` between, working in a latent with ONE projection in
front of the dispatch and one behind the weighted sum, a shared expert
without a gate on the un-projected rows.  Against the plain reference's
layer (``perfbench/references/nemotron_h.py``) on the same tensors."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import manifest
from perfbench.references import nemotron_h as ref
from vgate_tpu.models.decoder import _act, init_params
from vgate_tpu.models.specs import spec_for_model_id
from vgate_tpu.ops import moe

TINY = manifest.load_json(
    manifest.HERE, "configs", "nemotron-3-super-120b-a12b-l11e128.json"
)["rehearse"]["model"]
STACKS = ("up", "down")


@pytest.fixture(scope="module")
def whole():
    """The uncut layer: program-side tensors (the first expert layer of
    tiny-nemotron-h) and the same tensors as the reference names them."""
    spec = spec_for_model_id("tiny-nemotron-h")
    params = init_params(spec, jax.random.PRNGKey(0), jnp.float32)
    lp = jax.tree.map(lambda a: a[0, 0], params["layers"]["moe"])
    w = {k: (v["w"] if isinstance(v, dict) else v) for k, v in lp.items()}
    x = jax.random.normal(jax.random.PRNGKey(7), (48, spec.hidden_size))
    return spec, lp, w, x


def layer(x, lp, spec, **kw):
    return moe.expert_layer(x, lp, spec, lambda v: _act(v, spec), **kw)


def share_of(spec, lp, first, held):
    cut = dataclasses.replace(spec, num_experts=held, first_expert=first)
    part = dict(lp)
    for name in STACKS:
        part[name] = {"w": lp[name]["w"][first:first + held]}
    return cut, part


def test_the_program_draws_what_the_reference_draws(whole):
    spec, lp, w, _ = whole
    drawn = ref.draw_weights(TINY, 0, jnp.float32)["layers"][0]
    assert sorted(drawn) == sorted(k for k in w if k != "norm")
    for name, value in drawn.items():
        # to the last bit but one: the program's draw is one fused
        # program a tensor, the reference's eager
        np.testing.assert_allclose(
            np.asarray(w[name]), np.asarray(value), rtol=1e-6, atol=1e-9)
    assert float(jnp.abs(w["router_bias"]).max()) > 0.0


def test_the_four_shares_add_up_to_the_uncut_reference(whole, at_a_time):
    """The latent projection out is linear and has no bias: applied to
    each chip's partial sum, the four results add to the whole; the
    shared expert, which every chip computes alike, counts once."""
    spec, lp, w, x = whole
    with jax.default_matmul_precision("highest"):
        want = ref.moe(x, w, TINY)  # all 8 experts + the shared expert
        shared_only = ref.moe(x, w, dict(TINY, n_routed_experts=0))
    routed = jnp.zeros_like(x)
    for first in (0, 2, 4, 6):  # four chips, two experts each
        cut, part = share_of(spec, lp, first, 2)
        cut = dataclasses.replace(cut, shared_expert_intermediate_size=0)
        _, stats = layer(x, part, cut)
        extra = at_a_time(int(stats[1]))
        out, stats = layer(x, part, cut)
        routed = routed + out
        assert int(stats[0]) == 48 * 3 and 0 < int(stats[1]) < 48 * 3
        assert int(stats[4]) == extra
    np.testing.assert_allclose(routed + shared_only, want, atol=2e-5)
    got, stats = layer(x, lp, spec)
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert int(stats[0]) == int(stats[1]) == 48 * 3


def test_a_share_agrees_with_the_reference_given_the_same_share(whole):
    spec, lp, w, x = whole
    cut, part = share_of(spec, lp, 4, 2)
    cfg = dict(TINY, n_routed_experts=2, router_width=8, first_expert=4)
    wpart = dict(w, **{n: w[n][4:6] for n in STACKS})
    with jax.default_matmul_precision("highest"):
        want = ref.moe(x, wpart, cfg)
    got, _ = layer(x, part, cut)
    np.testing.assert_allclose(got, want, atol=2e-5)


def routed_only(spec):
    return dataclasses.replace(spec, shared_expert_intermediate_size=0)


def test_selection_follows_score_plus_bias_and_weights_follow_the_score(
        whole):
    """A bias that lifts expert 5 over every other makes every token
    choose it, and leaves its weight the score's: the layer equals the
    reference under the same bias, and differs from the layer that lets
    the bias into the weights."""
    spec, lp, w, x = whole
    bias = jnp.zeros((8,)).at[5].set(10.0)
    lifted, wl = dict(lp, router_bias=bias), dict(w, router_bias=bias)
    idx, vals = ref.route(x, wl, TINY)
    assert (idx == 5).any(axis=1).all()
    scores = np.asarray(jax.nn.sigmoid(x @ w["router"]))
    chosen = np.take_along_axis(scores, idx, axis=1)
    np.testing.assert_allclose(
        vals, 2.5 * chosen / chosen.sum(axis=1, keepdims=True), rtol=1e-5)
    with jax.default_matmul_precision("highest"):
        want = ref.moe(x, wl, TINY, shared=False)
    got, _ = layer(x, lifted, routed_only(spec))
    np.testing.assert_allclose(got, want, atol=2e-5)
    # the bias in the weights would weigh expert 5 at ~ 11 of 12
    biased = scores + np.asarray(bias)
    top = np.take_along_axis(biased, idx, axis=1)
    assert np.abs(top / top.sum(1, keepdims=True) * 2.5 - vals).max() > 0.5


def test_the_scaling_factor_scales_the_routed_sum_alone(whole):
    spec, lp, _, x = whole
    one = dataclasses.replace(spec, routed_scaling_factor=1.0)
    r1, _ = layer(x, lp, routed_only(one))
    r25, _ = layer(x, lp, routed_only(spec))
    np.testing.assert_allclose(r25, 2.5 * r1, rtol=1e-5, atol=1e-7)
    full1, _ = layer(x, lp, one)
    full25, _ = layer(x, lp, spec)
    np.testing.assert_allclose(full25 - r25, full1 - r1, atol=1e-6)


def test_a_token_none_of_whose_choices_is_held_gets_the_shared_expert(
        whole):
    spec, lp, w, x = whole
    # the chip holds experts 6 and 7; the bias sends every token to 0-2
    bias = jnp.zeros((8,)).at[:3].set(10.0)
    cut, part = share_of(spec, dict(lp, router_bias=bias), 6, 2)
    got, stats = layer(x, part, cut)
    assert int(stats[1]) == 0 and int(stats[2]) == 0
    with jax.default_matmul_precision("highest"):
        shared = ref.relu2(x @ w["shared_up"]) @ w["shared_down"]
    np.testing.assert_allclose(got, shared, atol=2e-5)


def test_every_token_to_one_expert_loses_nothing(whole, at_a_time):
    """One of eight experts held and every row on it: four times what a
    uniform router sends, so the layer's own rule (a quarter of the 144
    pairs, in tiles of 32: 64 at a time) is already a trip short."""
    spec, lp, w, x = whole
    bias = jnp.zeros((8,)).at[3].set(10.0)
    lifted, wl = dict(lp, router_bias=bias), dict(w, router_bias=bias)
    cut, part = share_of(spec, lifted, 3, 1)  # the chip holds expert 3
    assert moe.capacity(cut, 48 * 3) == 64
    extra = at_a_time(48) if at_a_time.case != "rule" else 0
    got, stats = layer(x, part, routed_only(cut))
    assert int(stats[3]) == 48 and int(stats[1]) == 48
    assert int(stats[4]) == extra
    cfg = dict(TINY, n_routed_experts=1, router_width=8, first_expert=3)
    wpart = dict(wl, **{n: w[n][3:4] for n in STACKS})
    with jax.default_matmul_precision("highest"):
        want = ref.moe(x, wpart, cfg, shared=False)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_masked_rows_route_nowhere(whole, at_a_time):
    """On a share (experts 2-5 of 8): the masked rows' pairs sort last
    with the pairs of experts held elsewhere."""
    spec, lp, _, x = whole
    cut, part = share_of(spec, lp, 2, 4)
    mask = jnp.arange(48) < 20
    full, _ = layer(x, part, cut)
    _, stats = layer(x, part, cut, row_mask=mask)
    extra = at_a_time(int(stats[1]))
    got, stats = layer(x, part, cut, row_mask=mask)
    np.testing.assert_allclose(got[:20], full[:20], atol=1e-6)
    assert int(stats[0]) == 20 * 3 and 0 < int(stats[1]) < 20 * 3
    assert int(stats[4]) == extra


def test_blocks_of_rows_give_what_one_block_gives(whole, monkeypatch,
                                                  at_a_time):
    spec, lp, _, x = whole
    one, s1 = layer(x, lp, spec)
    monkeypatch.setattr(moe, "BLOCK_TOKENS", 16)
    extra = at_a_time(16 * 3)  # a block's pairs, all held
    three, s3 = layer(x, lp, spec)
    np.testing.assert_allclose(three, one, atol=1e-6)
    assert s1[:2].tolist() == s3[:2].tolist()
    assert int(s1[4]) == 0 and int(s3[4]) == 3 * extra


@pytest.mark.parametrize("K, N", [(2048, 512), (512, 2048), (1024, 2688),
                                  (2688, 1024), (4096, 14336)])
def test_column_tile_divides_the_matrix(K, N):
    tn = moe._column_tile(K, N, 2)
    assert N % tn == 0 and (tn == N or tn % 128 == 0)
    if K * N * 2 <= (2 << 20):
        assert tn == N  # Qwen3-Next's matrices: what they were
    else:
        assert K * tn * 2 <= (6 << 20)


def test_grouped_product_kernel_at_a_width_512_does_not_divide():
    """2,688 = 21 x 128 columns (here 384 = 3 x 128 over a matrix of
    more than 2 MiB): the kernel in interpret mode against XLA's ragged
    product."""
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    E, Kd, N, M = 3, 2816, 384, 64
    assert moe._column_tile(Kd, N, 4) == 384
    rows = jax.random.normal(ks[0], (M, Kd), jnp.float32)
    w = jax.random.normal(ks[1], (2, E, Kd, N), jnp.float32) * 0.05
    sizes = jnp.array([20, 0, 30], jnp.int32)  # 14 rows of no group
    from vgate_tpu.ops.pallas.grouped_matmul import grouped_matmul_pallas

    got = grouped_matmul_pallas(rows, w, sizes, jnp.int32(1), tm=32,
                                tn=128, interpret=True)
    want = jax.lax.ragged_dot(rows, w[1], sizes)
    np.testing.assert_allclose(got[:50], want[:50], rtol=2e-4, atol=2e-4)
