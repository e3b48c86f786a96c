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


def test_the_four_shares_add_up_to_the_uncut_reference(whole):
    spec, lp, w, x = whole
    with jax.default_matmul_precision("highest"):
        want = ref.moe(x, w, TINY)  # all 8 experts + the shared expert
        shared_only = ref.moe(x, dict(w), dict(TINY, num_experts=0))
    routed = jnp.zeros_like(x)
    for first in (0, 2, 4, 6):  # four chips, two experts each
        cut, part = share_of(spec, lp, first, 2)
        cut = dataclasses.replace(cut, shared_expert_intermediate_size=0)
        out, stats = moe.expert_layer(x, part, cut, SILU)
        routed = routed + out
        assert int(stats[0]) == 48 * 2 and 0 < int(stats[1]) < 48 * 2
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


def test_every_token_to_one_expert_loses_nothing(whole):
    """The old layer dropped rows past twice the mean load; this one
    takes all 64 rows on one expert."""
    spec, lp, w, _ = whole
    x = jnp.tile(jax.random.normal(jax.random.PRNGKey(3), (1, 64)), (64, 1))
    x = x + 1e-4 * jax.random.normal(jax.random.PRNGKey(4), x.shape)
    got, stats = moe.expert_layer(x, lp, spec, SILU)
    assert int(stats[3]) == 64  # one expert took every row
    with jax.default_matmul_precision("highest"):
        want = ref.moe(x, w, TINY)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_masked_rows_route_nowhere(whole):
    spec, lp, _, x = whole
    mask = jnp.arange(48) < 20
    got, stats = moe.expert_layer(x, lp, spec, SILU, row_mask=mask)
    full, _ = moe.expert_layer(x, lp, spec, SILU)
    np.testing.assert_allclose(got[:20], full[:20], atol=1e-6)
    assert int(stats[0]) == 40


def test_blocks_of_rows_give_what_one_block_gives(whole, monkeypatch):
    spec, lp, _, x = whole
    one, s1 = moe.expert_layer(x, lp, spec, SILU)
    monkeypatch.setattr(moe, "BLOCK_TOKENS", 16)
    three, s3 = moe.expert_layer(x, lp, spec, SILU)
    np.testing.assert_allclose(three, one, atol=1e-6)
    assert s1[:2].tolist() == s3[:2].tolist()


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
