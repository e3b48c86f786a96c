"""Tensor-parallel Pallas-kernel wrappers (parallel/tp_attention.py) vs
single-device oracles, interpret-mode kernels on the virtual CPU mesh.

The hazard under test: a pallas_call has no GSPMD partition rule, so
under a tp-sharded jit it would be replicated (all-gathering the KV
pool); the wrappers run it per shard with the head dims split.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from vgate_tpu.config import load_config
from vgate_tpu.parallel.mesh import build_mesh


def tp_mesh(tp):
    return build_mesh(
        load_config(
            tpu={"dp": 1, "ep": 1, "sp": 1, "tp": tp, "num_devices": tp}
        ).tpu,
        devices=jax.devices()[:tp],
    )


@pytest.mark.parametrize("tp", [2, 4])
def test_tp_decode_wrapper_matches_oracle(tp):
    from vgate_tpu.ops.attention import paged_decode_attention
    from vgate_tpu.ops.pallas.paged_attention import (
        paged_decode_attention_pallas,
    )
    from vgate_tpu.parallel.tp_attention import (
        tp_divisible,
        tp_paged_decode_attention,
    )

    if jax.device_count() < tp:
        pytest.skip("needs devices")
    rng = np.random.default_rng(tp)
    B, H, KV, hd, ps, pages_per_seq = 3, 8, 4, 128, 16, 4
    P_ = 1 + B * pages_per_seq
    q = jnp.asarray(rng.normal(size=(B, H, hd)), jnp.float32)
    k_pages = jnp.asarray(
        rng.normal(size=(KV, P_, ps, hd)), jnp.float32
    )
    v_pages = jnp.asarray(
        rng.normal(size=(KV, P_, ps, hd)), jnp.float32
    )
    pt = jnp.asarray(
        rng.permutation(np.arange(1, P_))[: B * pages_per_seq].reshape(
            B, pages_per_seq
        ),
        jnp.int32,
    )
    seq_lens = jnp.asarray([5, 33, 64], jnp.int32)
    mesh = tp_mesh(tp)
    assert tp_divisible(mesh, H, KV)

    expect = paged_decode_attention(
        q, k_pages, v_pages, pt, seq_lens
    )
    kernel = functools.partial(
        paged_decode_attention_pallas, interpret=True
    )
    got = tp_paged_decode_attention(
        kernel, mesh, q, k_pages, v_pages, pt, seq_lens
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(expect), rtol=2e-5, atol=2e-5
    )


def test_tp_decode_wrapper_window_and_layer():
    from vgate_tpu.ops.attention import paged_decode_attention
    from vgate_tpu.ops.pallas.paged_attention import (
        paged_decode_attention_pallas,
    )
    from vgate_tpu.parallel.tp_attention import tp_paged_decode_attention

    if jax.device_count() < 2:
        pytest.skip("needs devices")
    rng = np.random.default_rng(7)
    B, H, KV, hd, ps, pages_per_seq, L = 2, 4, 2, 128, 16, 4, 3
    P_ = 1 + B * pages_per_seq
    q = jnp.asarray(rng.normal(size=(B, H, hd)), jnp.float32)
    kL = jnp.asarray(
        rng.normal(size=(L, KV, P_, ps, hd)), jnp.float32
    )
    vL = jnp.asarray(
        rng.normal(size=(L, KV, P_, ps, hd)), jnp.float32
    )
    pt = jnp.asarray(
        rng.permutation(np.arange(1, P_))[: B * pages_per_seq].reshape(
            B, pages_per_seq
        ),
        jnp.int32,
    )
    seq_lens = jnp.asarray([40, 61], jnp.int32)
    w = jnp.asarray(16, jnp.int32)
    layer = jnp.asarray(1, jnp.int32)
    mesh = tp_mesh(2)

    expect = paged_decode_attention(
        q, kL, vL, pt, seq_lens, window=w, layer=layer, softcap=25.0
    )
    kernel = functools.partial(
        paged_decode_attention_pallas, interpret=True, softcap=25.0
    )
    got = tp_paged_decode_attention(
        kernel, mesh, q, kL, vL, pt, seq_lens, window=w, layer=layer
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(expect), rtol=2e-5, atol=2e-5
    )


def test_tp_flash_prefill_wrapper_matches_oracle():
    from vgate_tpu.ops.attention import causal_prefill_attention
    from vgate_tpu.ops.pallas.flash_prefill import (
        flash_prefill_attention_pallas,
    )
    from vgate_tpu.parallel.tp_attention import (
        tp_flash_prefill_attention,
    )

    if jax.device_count() < 2:
        pytest.skip("needs devices")
    rng = np.random.default_rng(9)
    B, S, H, KV, hd = 2, 128, 4, 2, 128
    q = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, KV, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, KV, hd)), jnp.float32)
    seq_lens = jnp.asarray([S, S - 37], jnp.int32)
    mesh = tp_mesh(2)

    expect = causal_prefill_attention(q, k, v, seq_lens)
    kernel = functools.partial(
        flash_prefill_attention_pallas, interpret=True
    )
    got = tp_flash_prefill_attention(kernel, mesh, q, k, v, seq_lens)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(expect), rtol=2e-5, atol=2e-5
    )


def test_decode_forward_tp_mesh_selects_wrapped_kernel():
    """Full decode_forward under a tp=2 mesh with use_pallas=True must
    route attention through the tp wrapper (patched to interpret mode)
    and match the jnp path bit-for-bit in logits ordering."""
    if jax.device_count() < 2:
        pytest.skip("needs devices")
    from vgate_tpu.models.decoder import decode_forward, init_params
    from vgate_tpu.models.specs import TINY_DENSE
    from vgate_tpu.parallel.sharding import (
        kv_pspec,
        named,
        shard_params,
    )

    import unittest.mock as mock

    from vgate_tpu.ops.pallas import paged_attention as pa

    spec = TINY_DENSE  # H=4, KV=2: divisible by tp=2
    mesh = tp_mesh(2)
    B, ps, pages_per_seq = 2, 4, 4
    num_pages = 1 + B * pages_per_seq
    params = shard_params(
        init_params(spec, jax.random.PRNGKey(0), jnp.float32), spec, mesh
    )
    shape = (spec.num_layers, spec.num_kv_heads, num_pages, ps,
             spec.head_dim)
    kv_sh = named(mesh, kv_pspec(spec, mesh))
    k = jax.device_put(jnp.zeros(shape, jnp.float32), kv_sh)
    v = jax.device_put(jnp.zeros(shape, jnp.float32), kv_sh)
    pt = jnp.asarray(
        np.arange(B * pages_per_seq, dtype=np.int32).reshape(B, -1) + 1
    )
    tokens = jnp.asarray([7, 11], jnp.int32)
    positions = jnp.asarray([3, 9], jnp.int32)
    active = jnp.ones((B,), bool)

    expect, _, _ = decode_forward(
        params, spec, tokens, positions, k, v, pt, active=active,
        use_pallas=False, mesh=mesh,
    )

    real = pa.paged_decode_attention_pallas
    calls = []

    def interp(*a, **kw):
        kw["interpret"] = True
        calls.append(1)
        return real(*a, **kw)

    k2 = jax.device_put(jnp.zeros(shape, jnp.float32), kv_sh)
    v2 = jax.device_put(jnp.zeros(shape, jnp.float32), kv_sh)
    with mock.patch.object(
        pa, "paged_decode_attention_pallas", side_effect=interp
    ):
        got, _, _ = decode_forward(
            params, spec, tokens, positions, k2, v2, pt, active=active,
            use_pallas=True, mesh=mesh,
        )
    assert calls, "tp mesh + use_pallas must reach the wrapped kernel"
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(expect), rtol=2e-4, atol=2e-4
    )


def test_tp_wrapper_one_kv_head_a_shard_and_dead_rows():
    """The block-of-slots kernel composes with tp: each shard runs it
    with KV / tp = ONE kv head, and a row of length 0 (an inactive slot
    as decode_forward hands it over) comes out zero on every shard."""
    if jax.device_count() < 2:
        pytest.skip("needs devices")
    from tests.pallas_cases import live_rows_match
    from vgate_tpu.ops.attention import paged_decode_attention
    from vgate_tpu.ops.pallas.paged_attention import (
        paged_decode_attention_pallas,
    )
    from vgate_tpu.parallel.tp_attention import tp_paged_decode_attention

    rng = np.random.default_rng(21)
    B, H, KV, hd, ps, pages_per_seq = 4, 4, 2, 128, 16, 4
    P_ = 1 + B * pages_per_seq
    q = jnp.asarray(rng.normal(size=(B, H, hd)), jnp.float32)
    k_pages = jnp.asarray(rng.normal(size=(KV, P_, ps, hd)), jnp.float32)
    v_pages = jnp.asarray(rng.normal(size=(KV, P_, ps, hd)), jnp.float32)
    pt = jnp.asarray(
        rng.permutation(np.arange(1, P_))[: B * pages_per_seq].reshape(
            B, pages_per_seq
        ),
        jnp.int32,
    )
    seq_lens = jnp.asarray([5, 0, 64, 17], jnp.int32)
    mesh = tp_mesh(2)

    expect = paged_decode_attention(
        q, k_pages, v_pages, pt, jnp.maximum(seq_lens, 1)
    )
    kernel = functools.partial(paged_decode_attention_pallas, interpret=True)
    got = tp_paged_decode_attention(
        kernel, mesh, q, k_pages, v_pages, pt, seq_lens
    )
    live_rows_match(got, expect, seq_lens)
