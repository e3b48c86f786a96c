"""The ONE expert layer (``vgate_tpu/ops/moe.py``): dropless, routed over
the router's full width, computing the part of the result its held
experts give.  The model-configs guide's section 4 test: the parts that
all the shares give, with what every chip computes alike (the shared
expert) counted once, add up to what the uncut reference gives for the
whole layer."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import manifest
from perfbench.references import qwen3_next as ref
from vgate_tpu.models.decoder import _mlp, init_params
from vgate_tpu.models.specs import TINY_MOE, spec_for_model_id
from vgate_tpu.ops import moe

TINY = manifest.load_json(
    manifest.HERE, "configs", "qwen3-next-80b-a3b-l8e128.json"
)["rehearse"]["model"]
SILU = jax.nn.silu


@pytest.fixture(scope="module")
def whole():
    """The uncut layer: program-side tensors (one full-attention layer's
    expert part of tiny-hybrid) and the same tensors as the reference
    names them."""
    spec = spec_for_model_id("tiny-hybrid")
    params = init_params(spec, jax.random.PRNGKey(0), jnp.float32)
    lp = jax.tree.map(lambda a: a[0], params["layers"]["full"])
    w = {k: (v["w"] if isinstance(v, dict) else v) for k, v in lp.items()}
    x = jax.random.normal(jax.random.PRNGKey(7), (48, spec.hidden_size))
    return spec, lp, w, x


def share_of(spec, lp, first, held):
    """What the chip holding experts ``first .. first + held - 1`` has:
    its spec and its slice of the expert stacks (the router stays whole)."""
    cut = dataclasses.replace(spec, num_experts=held, first_expert=first)
    part = dict(lp)
    for name in ("gate", "up", "down"):
        part[name] = {"w": lp[name]["w"][first:first + held]}
    return cut, part


def test_the_four_shares_add_up_to_the_uncut_reference(whole, at_a_time):
    spec, lp, w, x = whole
    with jax.default_matmul_precision("highest"):
        want = ref.moe(x, w, TINY)  # all 8 experts + the shared expert
        shared_only = ref.moe(x, dict(w), dict(TINY, num_experts=0))
    routed = jnp.zeros_like(x)
    for first in (0, 2, 4, 6):  # four chips, two experts each
        cut, part = share_of(spec, lp, first, 2)
        cut = dataclasses.replace(cut, shared_expert_intermediate_size=0)
        _, stats = moe.expert_layer(x, part, cut, SILU)
        extra = at_a_time(int(stats[1]))
        out, stats = moe.expert_layer(x, part, cut, SILU)
        routed = routed + out
        assert int(stats[0]) == 48 * 2 and 0 < int(stats[1]) < 48 * 2
        assert int(stats[4]) == extra  # a trip more is counted, not lost
    np.testing.assert_allclose(routed + shared_only, want, atol=2e-5)
    # and the program's own whole layer is that sum too
    got, stats = moe.expert_layer(x, lp, spec, SILU)
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert int(stats[0]) == int(stats[1]) == 96


def test_a_share_agrees_with_the_reference_given_the_same_share(whole):
    spec, lp, w, x = whole
    cut, part = share_of(spec, lp, 4, 2)
    cfg = dict(TINY, num_experts=2, router_width=8, first_expert=4)
    wpart = dict(w, **{n: w[n][4:6] for n in ("gate", "up", "down")})
    with jax.default_matmul_precision("highest"):
        want = ref.moe(x, wpart, cfg)
    got, _ = moe.expert_layer(x, part, cut, SILU)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_every_token_to_one_expert_loses_nothing(whole, at_a_time):
    """The old layer dropped rows past twice the mean load; this one
    takes all 64 rows on one expert, in however many trips."""
    spec, lp, w, _ = whole
    x = jnp.tile(jax.random.normal(jax.random.PRNGKey(3), (1, 64)), (64, 1))
    x = x + 1e-4 * jax.random.normal(jax.random.PRNGKey(4), x.shape)
    extra = at_a_time(64 * 2)
    got, stats = moe.expert_layer(x, lp, spec, SILU)
    assert int(stats[3]) == 64  # one expert took every row
    assert int(stats[4]) == extra and (extra > 0) == (at_a_time.case == "over")
    with jax.default_matmul_precision("highest"):
        want = ref.moe(x, w, TINY)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_masked_rows_route_nowhere(whole, at_a_time):
    spec, lp, _, x = whole
    mask = jnp.arange(48) < 20
    full, _ = moe.expert_layer(x, lp, spec, SILU)
    extra = at_a_time(40)  # the 20 real rows' pairs; the rest sort last
    got, stats = moe.expert_layer(x, lp, spec, SILU, row_mask=mask)
    np.testing.assert_allclose(got[:20], full[:20], atol=1e-6)
    assert stats[:2].tolist() == [40, 40] and int(stats[4]) == extra


def test_blocks_of_rows_give_what_one_block_gives(whole, monkeypatch,
                                                  at_a_time):
    spec, lp, _, x = whole
    one, s1 = moe.expert_layer(x, lp, spec, SILU)
    monkeypatch.setattr(moe, "BLOCK_TOKENS", 16)
    extra = at_a_time(16 * 2)  # a block's pairs, all held
    three, s3 = moe.expert_layer(x, lp, spec, SILU)
    np.testing.assert_allclose(three, one, atol=1e-6)
    assert s1[:2].tolist() == s3[:2].tolist()
    assert int(s1[4]) == 0 and int(s3[4]) == 3 * extra


def _cut(preset, **changes):
    return dataclasses.replace(spec_for_model_id(preset), **changes)


@pytest.mark.parametrize("spec, pairs, take, rows", [
    # the four cells' shares: a decode step's pairs, a prompt block's
    (_cut("LGAI-EXAONE/K-EXAONE-236B-A23B", num_experts=16),
     192 * 8, 384, 4096),
    (_cut("LGAI-EXAONE/K-EXAONE-236B-A23B", num_experts=16),
     4096 * 8, 8192, 4096),
    (_cut("mistralai/Mistral-Small-4-119B-2603", num_experts=32),
     8192 * 4, 16384, 8192),
    (_cut("Qwen/Qwen3-Next-80B-A3B-Instruct", num_experts=128),
     256 * 10, 1280, 8192),
    (_cut("nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16", num_experts=128),
     192 * 22, 2112, 8192),
    # a chip that holds every expert takes every pair, as it always did
    (TINY_MOE, 512 * 2, 512 * 2, 4096),
    (spec_for_model_id("LGAI-EXAONE/K-EXAONE-236B-A23B"), 1024 * 8,
     1024 * 8, 1024),
], ids=["exaone-step", "exaone-block", "mistral-block", "qwen3-next-step",
        "nemotron-step", "tiny-moe", "exaone-uncut"])
def test_the_dispatch_takes_twice_a_uniform_routers_share(spec, pairs, take,
                                                         rows):
    assert moe.capacity(spec, pairs) == take
    assert take % moe._row_tile(take) == 0
    assert moe.block_tokens(spec) == rows
    # a block dispatches no more values at a time than one ever held
    K, W = spec.experts_per_token, spec.expert_in
    assert moe.capacity(spec, rows * K) * W <= moe.BLOCK_VALUES
    assert moe.capacity(spec, rows * K) <= moe.BLOCK_TOKENS * K


def test_a_chip_that_holds_every_expert_compiles_to_the_parents_shapes():
    """No loop, and the three products over all T x K sorted rows."""
    spec = TINY_MOE
    params = init_params(spec, jax.random.PRNGKey(0), jnp.float32)
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    T, K = 64, spec.experts_per_token
    x = jnp.zeros((T, spec.hidden_size), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda x: moe.expert_layer(x, lp, spec, SILU))(x).jaxpr
    names = [eqn.primitive.name for eqn in jaxpr.eqns]
    assert "while" not in names
    products = [eqn for eqn in jaxpr.eqns
                if eqn.primitive.name == "ragged_dot_general"
                or eqn.primitive.name == "ragged_dot"]
    assert [eqn.invars[0].aval.shape[0] for eqn in products] == [T * K] * 3
    # and a share's program holds the loop, its products C rows tall
    cut = dataclasses.replace(spec, num_experts=2, router_width=8)
    part = dict(lp, **{n: {"w": lp[n]["w"][:2]}
                       for n in ("gate", "up", "down")})
    text = str(jax.make_jaxpr(
        lambda x: moe.expert_layer(x, part, cut, SILU))(x))
    C = moe.capacity(cut, T * K)
    assert C == 64 and "while" in text and f"f32[{C}," in text
    assert f"f32[{T * K}," not in text


def test_mixtral_layer_is_the_case_holds_all_no_shared_expert():
    """tiny-moe through the same layer: sort-based (no intermediate the
    size of tokens x experts x capacity), and equal to a per-token loop
    -- the old result wherever the old layer dropped nothing."""
    spec = TINY_MOE
    params = init_params(spec, jax.random.PRNGKey(0), jnp.float32)
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    T, D = 512, spec.hidden_size
    E, K = spec.num_experts, spec.experts_per_token
    x = jax.random.normal(jax.random.PRNGKey(1), (T, D), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda x: _mlp(x, lp, spec))(x)
    tec = T * E * max(4, int((T * K / E) * 2.0 + 0.5))
    big = [v.aval.shape for eqn in jaxpr.jaxpr.eqns for v in eqn.outvars
           if hasattr(v.aval, "shape")
           and int(np.prod(v.aval.shape or (1,))) >= tec]
    assert not big, f"dense dispatch-sized intermediates present: {big}"

    probs = jax.nn.softmax(x @ lp["router"], axis=-1)
    vals, idx = jax.lax.top_k(probs, K)
    vals = np.asarray(vals / vals.sum(-1, keepdims=True))
    want = np.zeros((T, D), np.float32)
    xn = np.asarray(x)
    for t in range(T):
        for j in range(K):
            e = int(idx[t, j])
            g = xn[t] @ np.asarray(lp["gate"]["w"][e])
            u = xn[t] @ np.asarray(lp["up"]["w"][e])
            h = np.asarray(SILU(g)) * u
            want[t] += vals[t, j] * (h @ np.asarray(lp["down"]["w"][e]))
    np.testing.assert_allclose(_mlp(x, lp, spec), want, rtol=2e-4, atol=2e-4)
