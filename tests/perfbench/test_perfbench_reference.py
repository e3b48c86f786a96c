"""The plain reference against the program's own decoder at
tests/test_model_parity.py's tiny sizes, on the CPU: the weights recipe
and the forward pass must both agree."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from perfbench import reference, shapes

TINY = {  # vgate_tpu/models/specs.py TINY_DENSE
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 512, "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
    "tie_word_embeddings": False,
}


@pytest.mark.parametrize("tied", [False, True])
def test_reference_matches_the_programs_prefill(tied):
    from vgate_tpu.models.decoder import init_params, prefill_forward
    from vgate_tpu.models.specs import TINY_DENSE

    spec = dataclasses.replace(TINY_DENSE, tie_embeddings=tied)
    cfg = dict(TINY, tie_word_embeddings=tied)
    params = init_params(spec, jax.random.PRNGKey(0), jnp.float32)
    weights = reference.draw_weights(cfg, 0, jnp.float32)
    for name in ("q", "k", "v", "o", "gate", "up", "down"):
        np.testing.assert_array_equal(
            np.asarray(weights[name]), np.asarray(params["layers"][name]["w"]))
    np.testing.assert_array_equal(np.asarray(weights["embed"]),
                                  np.asarray(params["embed"]))
    page, n = 16, 23
    tokens = list(np.random.RandomState(3).randint(3, 259, size=n))
    padded = np.zeros((1, 32), np.int32)
    padded[0, :n] = tokens
    k_pages = jnp.zeros((spec.num_layers, spec.num_kv_heads, 8, page,
                         spec.head_dim), jnp.float32)  # [L, KV, P, ps, hd]
    try:
        logits, _, _ = prefill_forward(
            params, spec, jnp.asarray(padded), jnp.asarray([n], jnp.int32),
            k_pages, jnp.zeros_like(k_pages),
            jnp.asarray([[1, 2]], jnp.int32),
        )
    except (TypeError, ValueError) as exc:  # a page layout of another PR
        pytest.skip(f"prefill_forward's cache layout differs: {exc}")
    served = np.asarray(jax.nn.log_softmax(
        jnp.asarray(logits, jnp.float32).reshape(-1)))
    ours = reference.logprobs(cfg, weights, [tokens + [0]], [n])[0][0]
    np.testing.assert_allclose(ours, served, atol=2e-5)


def test_reference_is_causal_and_position_aware():
    weights = reference.draw_weights(TINY, 1, jnp.float32)
    a = [5, 9, 200, 17, 33, 8]
    b = [5, 9, 200, 17, 99, 8]  # differs after position 3
    la, lb = reference.logprobs(TINY, weights, [a, b], [1, 1])
    np.testing.assert_allclose(la[:3], lb[:3], atol=1e-6)
    assert np.abs(la[4] - lb[4]).max() > 1e-4
    swapped = reference.logprobs(TINY, weights, [[9, 5, 200]], [1])[0]
    assert np.abs(swapped[1] - la[1]).max() > 1e-4  # order matters
    assert np.allclose(np.exp(la).sum(-1), 1.0, atol=1e-4)


@pytest.mark.parametrize("name,want", [
    ("qwen2.5-1.5b", 28672), ("qwen2.5-7b-l14", 28672)])
def test_kv_bytes_per_token(name, want):
    from perfbench import manifest
    cfg = manifest.load_json(manifest.HERE, "configs", name + ".json")
    assert shapes.kv_bytes_per_token(cfg) == want


def test_unknown_device_is_an_error():
    assert shapes.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    assert shapes.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        shapes.peaks_for("TPU v9")
