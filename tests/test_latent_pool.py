"""The paged cache's geometry by the spec's kind of attention: ONE pool
of latent rows for latent attention (no V pool), K and V pools byte for
byte what they were for every other preset; what does not work over a
latent pool is refused by name at engine construction; and the expert
layer's shares add up."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vgate_tpu.config import load_config
from vgate_tpu.models.specs import _PRESETS, spec_for_model_id
from vgate_tpu.runtime.kv_cache import (
    KVGeometry,
    _page_bytes,
    auto_num_pages,
    make_kv_buffers,
)

MISTRAL = spec_for_model_id("mistralai/Mistral-Small-4-119B-2603")
CUT = dataclasses.replace(
    MISTRAL, name="mistral-cut", num_layers=4, num_experts=32,
    vocab_size=32768)


def geometry(spec, pages=16, dtype_bytes=2):
    return KVGeometry(
        num_layers=spec.attn_layers, num_pages=pages, page_size=32,
        kv_heads=spec.cache_heads, head_dim=spec.cache_head_dim,
        max_model_len=8192, dtype_bytes=dtype_bytes, pools=spec.kv_pools)


def test_latent_geometry_is_one_pool_of_padded_rows():
    """The cut: 4 layers x 32 tokens x 384 lanes x 2 B = 98,304 B a
    page (768 B a token a layer: the 320 values in whole 128-lane
    tiles), against 2 x 4 x 32 x 32 heads x 128 x 2 B = 2,097,152 B for
    K and V of this model's heads: 21 times less."""
    geo = geometry(CUT)
    assert geo.page_bytes == 98304 == 4 * 32 * 384 * 2
    assert geo.page_bytes == 32 * 4 * 768
    assert _page_bytes(4, 32, 32, 128, 2) == 2097152
    k, v = make_kv_buffers(geo, jnp.bfloat16)
    assert v is None
    assert k.shape == (4, 1, 16, 32, 384) and k.dtype == jnp.bfloat16
    assert k.size * 2 == geo.num_pages * geo.page_bytes
    # the published depth
    assert geometry(MISTRAL).page_bytes == 36 * 32 * 768


def test_auto_num_pages_counts_the_latent_row():
    class Device:
        platform, device_kind = "tpu", "fake"

        def memory_stats(self):
            return {"bytes_limit": 16 << 30, "bytes_in_use": 7 << 30}

    pages = auto_num_pages(CUT, 32, 0.9, device=Device())
    free = (16 << 30) * 0.9 - (7 << 30)
    assert pages == min(65536, int(free // 98304))
    dense = spec_for_model_id("Qwen/Qwen2.5-1.5B-Instruct")
    assert auto_num_pages(dense, 32, 0.9, device=Device()) == int(
        free // (2 * 28 * 32 * 2 * 128 * 2))


@pytest.mark.parametrize(
    "name", sorted(n for n, s in _PRESETS.items()
                   if not s.rows_cache and not s.is_encoder))
def test_every_other_preset_keeps_its_page_bytes(name):
    """K and V of every KV head in every layer that has pages: the
    formula the parent had, for every preset whose pool is head-major K
    and V (not a row a token: latent attention, or K over V under a
    selection, whose page tests/test_keye_dsa.py holds)."""
    spec = _PRESETS[name]
    for width, scale in ((2, 0), (4, 0), (1, 2)):
        geo = KVGeometry(
            num_layers=spec.attn_layers, num_pages=8, page_size=32,
            kv_heads=spec.cache_heads, head_dim=spec.cache_head_dim,
            max_model_len=2048, dtype_bytes=width, scale_bytes=scale,
            pools=spec.kv_pools)
        assert geo.page_bytes == (
            2 * spec.attn_layers * 32 * spec.num_kv_heads
            * (spec.head_dim * width + scale))
        assert geo.pools == 2
    k, v = make_kv_buffers(dataclasses.replace(geo, dtype_bytes=2,
                                               scale_bytes=0))
    assert k.shape == v.shape == (
        spec.attn_layers, spec.num_kv_heads, 8, 32, spec.head_dim)


def config(**over):
    tpu = {"dp": 1, "tp": 1, "ep": 1, "sp": 1, "kv_num_pages": 32,
           "kv_page_size": 4, "max_batch_slots": 2,
           "prefill_buckets": [16], "use_pallas": False}
    tpu.update(over.pop("tpu", {}))
    return load_config(
        model={"model_id": "tiny-mla-moe", "engine_type": "jax_tpu",
               "dtype": "float32", "max_model_len": 64,
               **over.pop("model", {})},
        tpu=tpu, logging={"level": "WARNING"}, **over)


REFUSED = {
    "speculative decoding": dict(tpu={"speculative_k": 2}),
    "host swap tier": dict(kv_cache={"host_swap_bytes": 1 << 20}),
    "prefill/decode roles": dict(pod={"roles": ["prefill"], "workers": 1}),
    "kv_cache.dtype=int8": dict(kv_cache={"dtype": "int8"}),
    "model.quantization": dict(model={"quantization": "int8"}),
}


@pytest.mark.parametrize("what", sorted(REFUSED))
def test_what_a_latent_pool_cannot_do_is_refused_by_name(what):
    from vgate_tpu.runtime.engine_core import refuse_unsupported_latent

    class Mesh:
        shape = {"dp": 1}

    with pytest.raises(ValueError, match="latent attention") as err:
        refuse_unsupported_latent(
            spec_for_model_id("tiny-mla-moe"), config(**REFUSED[what]),
            Mesh())
    assert what.split()[0] in str(err.value)


def test_a_partitioned_mesh_is_refused_and_a_plain_spec_is_not():
    from vgate_tpu.runtime.engine_core import refuse_unsupported_latent

    class Mesh:
        shape = {"tp": 2}

    with pytest.raises(ValueError, match=r"\{'tp': 2\} mesh"):
        refuse_unsupported_latent(
            spec_for_model_id("tiny-mla-moe"), config(), Mesh())
    # a spec with K and V pools passes whatever the configuration
    refuse_unsupported_latent(
        spec_for_model_id("tiny-dense"),
        config(tpu={"speculative_k": 2}), Mesh())


def test_engine_construction_refuses_before_any_request():
    from vgate_tpu.runtime.engine_core import EngineCore

    with pytest.raises(ValueError, match="speculative decoding"):
        EngineCore(config(tpu={"speculative_k": 2}),
                   devices=jax.devices()[:1])


def test_four_shares_of_the_experts_add_up_to_the_uncut_layer():
    """8 experts, a router 8 wide: the layer that holds them all equals
    the sum of four chips' shares (2 experts each from first_expert 0,
    2, 4, 6) with the shared expert counted ONCE."""
    from vgate_tpu.models.decoder import _act, init_params
    from vgate_tpu.ops.moe import expert_layer

    whole = spec_for_model_id("tiny-mla-moe")
    params = init_params(whole, jax.random.PRNGKey(0), jnp.float32)
    lp = jax.tree.map(lambda a: a[1, 0], params["layers"]["layer"])
    x = jnp.asarray(
        np.random.default_rng(2).standard_normal((13, 64)), jnp.float32)
    act = lambda t: _act(t, whole)
    want, stats = expert_layer(x, lp, whole, act)
    assert int(stats[0]) == int(stats[1]) == 13 * 2
    total = jnp.zeros_like(want)
    for first in (0, 2, 4, 6):
        share = dataclasses.replace(
            whole, num_experts=2, first_expert=first,
            shared_expert_intermediate_size=0, n_shared_experts=0)
        part = dict(lp, **{n: {"w": lp[n]["w"][first:first + 2]}
                           for n in ("gate", "up", "down")})
        total = total + expert_layer(x, part, share, act)[0]
    # the shared expert once: the whole layer's result with no routed
    # expert held
    none = dataclasses.replace(whole, num_experts=1, first_expert=0)
    zero = dict(lp, **{n: {"w": jnp.zeros_like(lp[n]["w"][:1])}
                       for n in ("gate", "up", "down")})
    total = total + expert_layer(x, zero, none, act)[0]
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-6)
