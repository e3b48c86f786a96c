"""Op-level unit tests: sampling semantics, rope, norms, attention masks."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from vgate_tpu.ops.attention import causal_prefill_attention, paged_decode_attention
from vgate_tpu.ops.norms import layer_norm, rms_norm
from vgate_tpu.ops.rope import apply_rope
from vgate_tpu.ops.sampling import sample_tokens


def test_rms_norm_matches_formula():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(4, 8)), jnp.float32)
    w = jnp.asarray(np.random.default_rng(1).normal(size=(8,)), jnp.float32)
    out = np.asarray(rms_norm(x, w, eps=1e-6))
    xn = np.asarray(x)
    expect = xn / np.sqrt((xn**2).mean(-1, keepdims=True) + 1e-6) * np.asarray(w)
    np.testing.assert_allclose(out, expect, rtol=1e-5, atol=1e-5)


def test_layer_norm_zero_mean_unit_var():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(3, 16)), jnp.float32)
    out = np.asarray(
        layer_norm(x, jnp.ones((16,)), jnp.zeros((16,)))
    )
    np.testing.assert_allclose(out.mean(-1), 0.0, atol=1e-5)
    np.testing.assert_allclose(out.std(-1), 1.0, atol=1e-2)


def test_rope_preserves_norm_and_zero_position_identity():
    x = jnp.asarray(
        np.random.default_rng(0).normal(size=(1, 4, 2, 16)), jnp.float32
    )
    pos = jnp.asarray([[0, 1, 2, 3]])
    out = apply_rope(x, pos)
    # rotation preserves norms
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(out), axis=-1),
        np.linalg.norm(np.asarray(x), axis=-1),
        rtol=1e-5,
    )
    # position 0 is identity
    np.testing.assert_allclose(
        np.asarray(out[0, 0]), np.asarray(x[0, 0]), atol=1e-6
    )


def test_rope_relative_property():
    """<rope(q,m), rope(k,n)> depends only on m-n."""
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(1, 1, 1, 32)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 1, 1, 32)), jnp.float32)

    def dot_at(m, n):
        qm = apply_rope(q, jnp.asarray([[m]]))
        kn = apply_rope(k, jnp.asarray([[n]]))
        return float(jnp.sum(qm * kn))

    assert abs(dot_at(5, 3) - dot_at(12, 10)) < 1e-3


def test_causal_attention_ignores_padding_and_future():
    rng = np.random.default_rng(0)
    B, S, H, hd = 1, 8, 2, 16
    q = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
    out1 = causal_prefill_attention(q, k, v, jnp.asarray([5]))
    # mutating padded keys (>=5) must not change outputs at positions < 5
    k2 = k.at[:, 5:].set(99.0)
    v2 = v.at[:, 5:].set(-99.0)
    out2 = causal_prefill_attention(q, k2, v2, jnp.asarray([5]))
    np.testing.assert_allclose(
        np.asarray(out1[:, :5]), np.asarray(out2[:, :5]), atol=1e-5
    )


def test_paged_decode_matches_contiguous_attention():
    """Paged gather attention == plain attention over the same context."""
    rng = np.random.default_rng(1)
    B, H, KV, hd, ps = 2, 4, 2, 16, 4
    ctx_lens = [6, 3]
    n_pages_per_seq = 2
    P = 1 + B * n_pages_per_seq
    k_pages = jnp.asarray(rng.normal(size=(KV, P, ps, hd)), jnp.float32)
    v_pages = jnp.asarray(rng.normal(size=(KV, P, ps, hd)), jnp.float32)
    page_tables = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, H, hd)), jnp.float32)
    out = np.asarray(
        paged_decode_attention(
            q, k_pages, v_pages, page_tables, jnp.asarray(ctx_lens)
        )
    )
    # naive per-slot computation
    for b in range(B):
        n = ctx_lens[b]
        k = np.asarray(k_pages[:, np.asarray(page_tables[b])])
        k = k.reshape(KV, -1, hd).transpose(1, 0, 2)[:n]
        v = np.asarray(v_pages[:, np.asarray(page_tables[b])])
        v = v.reshape(KV, -1, hd).transpose(1, 0, 2)[:n]
        k = np.repeat(k, H // KV, axis=1)
        v = np.repeat(v, H // KV, axis=1)
        qb = np.asarray(q[b])  # [H, hd]
        scores = np.einsum("hd,thd->ht", qb, k) / np.sqrt(hd)
        probs = np.exp(scores - scores.max(-1, keepdims=True))
        probs /= probs.sum(-1, keepdims=True)
        expect = np.einsum("ht,thd->hd", probs, v)
        np.testing.assert_allclose(out[b], expect, rtol=1e-4, atol=1e-5)


# --- sampling ---


def _uniform_logits(v=64):
    return jnp.zeros((1, v), jnp.float32)


def test_greedy_when_temperature_zero():
    logits = jnp.asarray(
        np.random.default_rng(0).normal(size=(4, 100)), jnp.float32
    )
    tokens = sample_tokens(
        logits,
        temperature=jnp.zeros((4,)),
        top_p=jnp.ones((4,)),
        top_k=jnp.zeros((4,), jnp.int32),
        key=jax.random.PRNGKey(0),
    )
    np.testing.assert_array_equal(
        np.asarray(tokens), np.asarray(jnp.argmax(logits, -1))
    )


def test_top_k_restricts_support():
    logits = jnp.asarray([[10.0, 9.0, 8.0] + [0.0] * 61])
    seen = set()
    for i in range(50):
        tok = sample_tokens(
            logits,
            temperature=jnp.asarray([5.0]),
            top_p=jnp.asarray([1.0]),
            top_k=jnp.asarray([2], jnp.int32),
            key=jax.random.PRNGKey(i),
        )
        seen.add(int(tok[0]))
    assert seen <= {0, 1}


def test_top_p_restricts_support():
    # one dominant token: top_p=0.5 keeps only it
    logits = jnp.asarray([[10.0] + [0.0] * 63])
    for i in range(20):
        tok = sample_tokens(
            logits,
            temperature=jnp.asarray([1.0]),
            top_p=jnp.asarray([0.5]),
            top_k=jnp.asarray([0], jnp.int32),
            key=jax.random.PRNGKey(i),
        )
        assert int(tok[0]) == 0


def test_per_slot_params_are_independent():
    """Slot 0 greedy, slot 1 high-temp: slot 0 must stay deterministic."""
    logits = jnp.asarray(
        np.tile(np.random.default_rng(2).normal(size=(1, 128)), (2, 1)),
        jnp.float32,
    )
    argmax = int(jnp.argmax(logits[0]))
    randoms = set()
    for i in range(30):
        toks = sample_tokens(
            logits,
            temperature=jnp.asarray([0.0, 3.0]),
            top_p=jnp.asarray([1.0, 1.0]),
            top_k=jnp.asarray([0, 0], jnp.int32),
            key=jax.random.PRNGKey(i),
        )
        assert int(toks[0]) == argmax
        randoms.add(int(toks[1]))
    assert len(randoms) > 3  # slot 1 actually samples


def test_sampling_distribution_roughly_matches():
    probs_target = np.array([0.6, 0.3, 0.1])
    logits = jnp.asarray([np.log(probs_target)], jnp.float32)
    counts = np.zeros(3)
    N = 400
    for i in range(N):
        tok = sample_tokens(
            jnp.tile(logits, (1, 1)),
            temperature=jnp.asarray([1.0]),
            top_p=jnp.asarray([1.0]),
            top_k=jnp.asarray([0], jnp.int32),
            key=jax.random.PRNGKey(i),
        )
        counts[int(tok[0])] += 1
    freq = counts / N
    np.testing.assert_allclose(freq, probs_target, atol=0.08)


def test_flash_prefill_matches_naive_oracle():
    """Blockwise online-softmax prefill == the [S,S]-materializing oracle
    (which it replaces as the engine's default path)."""
    from vgate_tpu.ops.attention import flash_prefill_attention

    rng = np.random.default_rng(11)
    B, S, H, KV, hd = 2, 64, 4, 2, 16
    q = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, KV, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, KV, hd)), jnp.float32)
    lens = jnp.asarray([37, 64], jnp.int32)
    expect = causal_prefill_attention(q, k, v, lens)
    got = flash_prefill_attention(q, k, v, lens, block_k=16)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(expect), rtol=2e-5, atol=2e-5
    )


def test_flash_prefill_chunked_offset_matches_full():
    """A query chunk at global offset h attending over history+chunk keys
    must equal the same rows of the full-sequence computation."""
    from vgate_tpu.ops.attention import flash_prefill_attention

    rng = np.random.default_rng(12)
    B, S, H, KV, hd = 1, 64, 4, 2, 16
    hist = 32
    q = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, KV, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, KV, hd)), jnp.float32)
    lens = jnp.asarray([S], jnp.int32)
    full = causal_prefill_attention(q, k, v, lens)
    chunk = flash_prefill_attention(
        q[:, hist:], k, v, lens, block_k=16,
        q_offset=jnp.asarray([hist], jnp.int32),
    )
    np.testing.assert_allclose(
        np.asarray(chunk), np.asarray(full[:, hist:]), rtol=2e-5, atol=2e-5
    )


def test_flash_prefill_peak_memory_beats_naive():
    """The blockwise path's compiled temp footprint must stay well under the
    naive path's O(S^2) score materialization at a serving-sized bucket."""
    from vgate_tpu.ops.attention import flash_prefill_attention

    B, S, H, KV, hd = 1, 2048, 8, 2, 64
    args = [
        jnp.zeros((B, S, H, hd), jnp.float32),
        jnp.zeros((B, S, KV, hd), jnp.float32),
        jnp.zeros((B, S, KV, hd), jnp.float32),
        jnp.asarray([S], jnp.int32),
    ]

    def temp_bytes(fn):
        mem = jax.jit(fn).lower(*args).compile().memory_analysis()
        if mem is None:
            pytest.skip("memory_analysis unavailable on this backend")
        return mem.temp_size_in_bytes

    naive = temp_bytes(causal_prefill_attention)
    flash = temp_bytes(flash_prefill_attention)
    # naive materializes [B,H,S,S] scores+probs (~268 MB here); blockwise
    # holds one [B,S,block,H] slab (~16 MB)
    assert flash < naive / 4, (flash, naive)


def test_sliding_window_flash_matches_oracle_and_drops_old_keys():
    """Gemma-2 local attention: the blockwise path with a window must match
    the [S,S] oracle given the same window, and differ from global
    attention once S exceeds the window (old keys really are dropped)."""
    from vgate_tpu.ops.attention import flash_prefill_attention

    rng = np.random.default_rng(21)
    B, S, H, KV, hd = 2, 64, 4, 2, 16
    q = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, KV, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, KV, hd)), jnp.float32)
    lens = jnp.asarray([41, 64], jnp.int32)
    win = jnp.asarray(16, jnp.int32)
    expect = causal_prefill_attention(q, k, v, lens, window=win)
    got = flash_prefill_attention(q, k, v, lens, block_k=16, window=win)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(expect), rtol=2e-5, atol=2e-5
    )
    # window=0 means global: matches the plain oracle
    got_global = flash_prefill_attention(
        q, k, v, lens, block_k=16, window=jnp.asarray(0, jnp.int32)
    )
    np.testing.assert_allclose(
        np.asarray(got_global),
        np.asarray(causal_prefill_attention(q, k, v, lens)),
        rtol=2e-5, atol=2e-5,
    )
    # and a real window changes rows past it
    assert not np.allclose(np.asarray(got)[0, 40], np.asarray(got_global)[0, 40])


def test_paged_decode_window_matches_truncated_context():
    """Decode-step local attention over paged KV == global attention over a
    context manually truncated to the last `window` tokens."""
    from vgate_tpu.ops.attention import paged_decode_attention

    rng = np.random.default_rng(22)
    B, H, KV, hd, ps, n_pages = 2, 4, 2, 16, 4, 8
    ctx = ps * n_pages  # 32
    seq_lens = jnp.asarray([29, 32], jnp.int32)
    win = 12
    q = jnp.asarray(rng.normal(size=(B, H, hd)), jnp.float32)
    k_pages = jnp.asarray(
        rng.normal(size=(KV, 1 + B * n_pages, ps, hd)), jnp.float32
    )
    v_pages = jnp.asarray(
        rng.normal(size=(KV, 1 + B * n_pages, ps, hd)), jnp.float32
    )
    pt = jnp.asarray(
        1 + np.arange(B * n_pages, dtype=np.int32).reshape(B, n_pages)
    )
    got = paged_decode_attention(
        q, k_pages, v_pages, pt, seq_lens, window=jnp.asarray(win, jnp.int32)
    )
    # oracle: zero out everything outside the window by faking seq_lens and
    # shifting -- rebuild contiguous K/V and mask by hand
    k_flat = np.moveaxis(
        np.asarray(k_pages)[:, np.asarray(pt)].reshape(KV, B, ctx, hd), 0, 2
    )
    v_flat = np.moveaxis(
        np.asarray(v_pages)[:, np.asarray(pt)].reshape(KV, B, ctx, hd), 0, 2
    )
    scale = hd ** -0.5
    for b in range(B):
        L = int(seq_lens[b])
        lo = max(0, L - win)
        kk = np.repeat(k_flat[b, lo:L], H // KV, axis=1)  # [w, H, hd]
        vv = np.repeat(v_flat[b, lo:L], H // KV, axis=1)
        scores = np.einsum("hd,thd->ht", np.asarray(q)[b], kk) * scale
        p = np.exp(scores - scores.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        expect_b = np.einsum("ht,thd->hd", p, vv)
        np.testing.assert_allclose(
            np.asarray(got)[b], expect_b, rtol=2e-5, atol=2e-5
        )


# ------------------------------------------- KV write index helpers

def test_kv_write_helpers_match_numpy():
    """kv_write_tokens / kv_write_pages (ops/kv_quant.py) index the
    kv-head dim explicitly (the TPU layout fix, PERF.md "Bring-up");
    pin them against plain numpy assignment for both pool forms: one
    layer's slice (the pp relay) and the full stacked pool with a layer
    index (the plain-mesh scan)."""
    import numpy as np

    from vgate_tpu.ops.kv_quant import kv_write_pages, kv_write_tokens

    L, KV, P, ps, hd, B, S = 3, 2, 9, 4, 8, 2, 3
    rng = np.random.default_rng(0)
    pool = rng.standard_normal((L, KV, P, ps, hd)).astype(np.float32)
    page_ids = np.asarray([[1, 1, 2], [5, 6, 6]], np.int32)  # [B, S]
    page_off = np.asarray([[2, 3, 0], [3, 0, 1]], np.int32)
    tok = rng.standard_normal((B, S, KV, hd)).astype(np.float32)
    pt = np.asarray([[1, 2], [5, 6]], np.int32)  # [B, n_pages]
    pages = rng.standard_normal((B, 2, KV, ps, hd)).astype(np.float32)

    for layer in (None, 1):
        base = pool[0] if layer is None else pool
        view = (lambda a: a) if layer is None else (lambda a: a[layer])

        want = base.copy()
        for b in range(B):
            for s_ in range(S):
                view(want)[:, page_ids[b, s_], page_off[b, s_]] = tok[b, s_]
        got = kv_write_tokens(
            jnp.asarray(base), jnp.asarray(page_ids),
            jnp.asarray(page_off), jnp.asarray(tok), layer=layer,
        )
        np.testing.assert_array_equal(np.asarray(got), want)

        want = base.copy()
        for b in range(B):
            for n in range(2):
                view(want)[:, pt[b, n]] = pages[b, n]
        got = kv_write_pages(
            jnp.asarray(base), jnp.asarray(pt), jnp.asarray(pages),
            layer=layer,
        )
        np.testing.assert_array_equal(np.asarray(got), want)
