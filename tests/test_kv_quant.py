"""int8 paged-KV quantization (ops/kv_quant.py): quantize-on-write /
dequant-on-read numerics, the 2x capacity accounting, Pallas-kernel
parity for the dequant read path, and the engine-level quality bounds
(greedy token identity + logprob drift vs the full-precision oracle on
the CPU test model)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from vgate_tpu.backends.base import SamplingParams
from vgate_tpu.config import load_config
from vgate_tpu.ops.kv_quant import (
    SCALE_BYTES,
    QuantPages,
    copy_page_prefix,
    dequantize,
    gather_pages,
    is_quantized,
    kv_write,
    quantize,
)
from vgate_tpu.runtime.kv_cache import (
    KVGeometry,
    auto_num_pages,
    make_kv_buffers,
)


# ------------------------------------------------------------- numerics


def test_quantize_roundtrip_error_bound():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(5, 7, 64)) * 3.0, jnp.float32)
    q, s = quantize(x)
    assert q.dtype == jnp.int8
    back = dequantize(q, s)
    # symmetric int8 step is absmax/127 (~0.8% of absmax peak-to-peak);
    # the bf16-stored scale adds its ~0.4% relative rounding on top
    absmax = np.abs(np.asarray(x)).max(axis=-1, keepdims=True)
    err = np.abs(np.asarray(back) - np.asarray(x))
    assert (err <= absmax * (0.5 / 127.0 + 0.005) + 1e-6).all()


def test_quantize_zero_rows_stay_exactly_zero():
    x = jnp.zeros((3, 4, 16), jnp.float32)
    q, s = quantize(x)
    assert np.asarray(q).max() == 0
    assert (np.asarray(s.astype(jnp.float32)) == 1.0).all()
    assert (np.asarray(dequantize(q, s)) == 0.0).all()


def test_kv_write_plain_pool_unchanged():
    pool = jnp.zeros((2, 4, 4, 8), jnp.float32)
    val = jnp.ones((2, 2, 8), jnp.float32)
    out = kv_write(pool, (slice(None), jnp.asarray([1, 2]),
                          jnp.asarray([0, 3])), val)
    assert not is_quantized(out)
    assert np.asarray(out[0, 1, 0]).sum() == 8


def test_kv_write_quant_pool_roundtrips_through_gather():
    rng = np.random.default_rng(1)
    KV, P, ps, hd = 2, 9, 4, 16
    pool = QuantPages(
        jnp.zeros((KV, P, ps, hd), jnp.int8),
        jnp.ones((KV, P, ps), jnp.bfloat16),
    )
    vals = jnp.asarray(rng.normal(size=(KV, P, ps, hd)), jnp.float32)
    pool = kv_write(pool, (slice(None), jnp.arange(P)), vals)
    deq = gather_pages(pool, jnp.arange(P)[None])  # [KV, 1, P, ps, hd]
    absmax = np.abs(np.asarray(vals)).max(axis=-1, keepdims=True)
    err = np.abs(np.asarray(deq[:, 0]) - np.asarray(vals))
    assert (err <= absmax * 0.01 + 1e-6).all()


def test_cow_copy_preserves_scales_with_data():
    """The radix COW copy must carry the per-slot SCALES with the int8
    data: a copied head whose scale came from the destination page
    would dequantize differently for the diverged reader than for the
    sharers of the source page."""
    rng = np.random.default_rng(2)
    KV, P, ps, hd = 2, 6, 4, 8
    vals = jnp.asarray(rng.normal(size=(KV, P, ps, hd)) * 5.0, jnp.float32)
    pool = kv_write(
        QuantPages(
            jnp.zeros((KV, P, ps, hd), jnp.int8),
            jnp.ones((KV, P, ps), jnp.bfloat16),
        ),
        (slice(None), jnp.arange(P)),
        vals,
    )
    keep = jnp.arange(ps) < 3
    out = copy_page_prefix(pool, 2, 4, keep)
    # head: bit-identical data AND scale from the source page
    assert np.array_equal(np.asarray(out.data[:, 4, :3]),
                          np.asarray(pool.data[:, 2, :3]))
    assert np.array_equal(
        np.asarray(out.scale[:, 4, :3].astype(jnp.float32)),
        np.asarray(pool.scale[:, 2, :3].astype(jnp.float32)),
    )
    # tail: untouched
    assert np.array_equal(np.asarray(out.data[:, 4, 3:]),
                          np.asarray(pool.data[:, 4, 3:]))
    assert np.array_equal(
        np.asarray(out.scale[:, 4, 3:].astype(jnp.float32)),
        np.asarray(pool.scale[:, 4, 3:].astype(jnp.float32)),
    )


# ------------------------------------------------------------- capacity


def test_auto_num_pages_int8_yields_at_least_1p9x():
    """The acceptance floor: for the same HBM budget, int8 KV must
    yield >= 1.9x the bf16 page count (1.94x at head_dim 64, 1.97x at
    128 — the bf16 scale keeps the overhead at 2/head_dim)."""
    from types import SimpleNamespace

    from vgate_tpu.models.specs import spec_for_model_id

    dev = SimpleNamespace(platform="tpu")  # no memory_stats -> budget path
    for model_id in (
        "Qwen/Qwen2.5-1.5B-Instruct",
        "Qwen/Qwen2.5-7B-Instruct",
    ):
        spec = spec_for_model_id(model_id)
        common = dict(
            page_size=32, hbm_utilization=0.9, device=dev,
            params_bytes=0, hbm_bytes=16 * 1024 ** 3, hard_cap=10 ** 9,
        )
        bf16 = auto_num_pages(spec, dtype_bytes=2, **common)
        int8 = auto_num_pages(
            spec, dtype_bytes=1, scale_bytes=SCALE_BYTES, **common
        )
        assert int8 / bf16 >= 1.9, (model_id, int8, bf16)


def test_geometry_page_bytes_accounts_for_scales():
    base = dict(num_layers=4, num_pages=8, page_size=16, kv_heads=2,
                head_dim=64, max_model_len=64)
    bf16 = KVGeometry(dtype_bytes=2, **base)
    int8 = KVGeometry(dtype_bytes=1, scale_bytes=2, kv_dtype="int8", **base)
    assert bf16.page_bytes == 2 * 4 * 16 * 2 * 64 * 2
    assert int8.page_bytes == 2 * 4 * 16 * 2 * (64 + 2)
    assert bf16.page_bytes / int8.page_bytes >= 1.9


def test_make_kv_buffers_int8_pool_structure():
    geo = KVGeometry(
        num_layers=2, num_pages=6, page_size=4, kv_heads=2, head_dim=8,
        max_model_len=16, dtype_bytes=1, scale_bytes=2, kv_dtype="int8",
    )
    k, v = make_kv_buffers(geo, jnp.int8)
    assert is_quantized(k) and is_quantized(v)
    assert k.data.shape == (2, 2, 6, 4, 8) and k.data.dtype == jnp.int8
    assert k.scale.shape == (2, 2, 6, 4)
    # zeroed pool dequantizes to exactly 0 (trash-page reads)
    assert np.asarray(
        gather_pages(k, jnp.arange(6)[None])
    ).max() == 0.0


# --------------------------------------------- Pallas dequant read path


def _quant_case(B=4, H=8, KV=2, hd=128, ps=16, n=16, seed=3):
    rng = np.random.default_rng(seed)
    P = 1 + B * n

    def pool(s, scale):
        vals = jnp.asarray(
            np.random.default_rng(s).normal(size=(KV, P, ps, hd)) * scale,
            jnp.float32,
        )
        return kv_write(
            QuantPages(
                jnp.zeros((KV, P, ps, hd), jnp.int8),
                jnp.ones((KV, P, ps), jnp.bfloat16),
            ),
            (slice(None), jnp.arange(P)),
            vals,
        )

    q = jnp.asarray(rng.normal(size=(B, H, hd)), jnp.float32)
    pt = jnp.asarray(
        rng.permutation(np.arange(1, P))[: B * n].reshape(B, n), jnp.int32
    )
    return q, pool(seed + 10, 1.0), pool(seed + 11, 0.7), pt


def test_paged_decode_kernel_dequant_matches_jnp_twin():
    from vgate_tpu.ops.attention import paged_decode_attention
    from vgate_tpu.ops.pallas.paged_attention import (
        paged_decode_attention_pallas,
    )

    q, kq, vq, pt = _quant_case()
    seq_lens = jnp.asarray([1, 16, 17, 200], jnp.int32)
    expect = paged_decode_attention(q, kq, vq, pt, seq_lens)
    got = paged_decode_attention_pallas(
        q, kq, vq, pt, seq_lens, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(expect), rtol=2e-5, atol=2e-5
    )


def test_paged_decode_kernel_dequant_layer_indexed():
    """Carry-threaded pools: the scale DMA must compose the layer index
    exactly like the data DMA."""
    from vgate_tpu.ops.attention import paged_decode_attention
    from vgate_tpu.ops.pallas.paged_attention import (
        paged_decode_attention_pallas,
    )

    q, kq, vq, pt = _quant_case(B=2, n=8)
    seq_lens = jnp.asarray([5, 100], jnp.int32)
    L = 3
    kqL = QuantPages(
        jnp.tile(kq.data[None], (L, 1, 1, 1, 1)),
        jnp.tile(kq.scale[None], (L, 1, 1, 1)),
    )
    vqL = QuantPages(
        jnp.tile(vq.data[None], (L, 1, 1, 1, 1)),
        jnp.tile(vq.scale[None], (L, 1, 1, 1)),
    )
    expect = paged_decode_attention(q, kq, vq, pt, seq_lens)
    got = paged_decode_attention_pallas(
        q, kqL, vqL, pt, seq_lens, layer=jnp.asarray(1), interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(expect), rtol=2e-5, atol=2e-5
    )


def test_multitok_kernel_dequant_matches_jnp_twin():
    from vgate_tpu.ops.attention import paged_suffix_attention
    from vgate_tpu.ops.pallas.paged_attention import (
        paged_multitok_attention_pallas,
    )

    rng = np.random.default_rng(4)
    _, kq, vq, pt = _quant_case(seed=4)
    B, S, H, hd = 4, 4, 8, 128
    qs = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
    pos0 = jnp.asarray([0, 5, 30, 100], jnp.int32)
    lens = jnp.asarray([1, 3, 4, 2], jnp.int32)
    expect = paged_suffix_attention(qs, kq, vq, pt, pos0, pos0 + lens)
    got = paged_multitok_attention_pallas(
        qs, kq, vq, pt, pos0, lens, interpret=True
    )
    em, gm = np.asarray(expect), np.asarray(got)
    for b in range(B):  # rows past input_lens are unspecified
        np.testing.assert_allclose(
            gm[b, : int(lens[b])], em[b, : int(lens[b])],
            rtol=2e-5, atol=2e-5,
        )


def test_decode_kernel_int8_scale_rows_across_blocks():
    """int8 pages and their scale rows through the block-of-slots
    kernel: two programs (64 slots each at this geometry), live slots
    among dead ones, so a chunk's scale rows follow its pages across a
    slot boundary.  A dead row comes out zero."""
    from tests.pallas_cases import live_rows_match
    from vgate_tpu.ops.attention import paged_decode_attention
    from vgate_tpu.ops.pallas.paged_attention import (
        paged_decode_attention_pallas,
    )

    B = 70
    q, kq, vq, pt = _quant_case(B=B, n=8, seed=5)
    lens = np.zeros((B,), np.int32)
    lens[[0, 1, 7, 63, 64, 69]] = [3, 40, 64, 128, 17, 100]
    expect = paged_decode_attention(
        q, kq, vq, pt, jnp.asarray(np.maximum(lens, 1))
    )
    got = paged_decode_attention_pallas(
        q, kq, vq, pt, jnp.asarray(lens), interpret=True
    )
    live_rows_match(got, expect, lens)


# ------------------------------------------------- engine-level quality


def _engine_cfg(kv_dtype, **tpu_overrides):
    tpu = {
        "dp": 1, "tp": 1, "ep": 1, "sp": 1,
        "kv_num_pages": 256, "kv_page_size": 4, "max_batch_slots": 4,
        "prefill_buckets": [8, 16, 32], "use_pallas": False,
    }
    tpu.update(tpu_overrides)
    return load_config(
        model={
            "model_id": "tiny-dense", "engine_type": "jax_tpu",
            "dtype": "float32", "max_model_len": 128,
        },
        kv_cache={"dtype": kv_dtype},
        tpu=tpu,
        scheduler={"max_queue_size": 16},
        logging={"level": "WARNING"},
    )


@pytest.fixture(scope="module")
def quant_vs_oracle():
    """One greedy 80-token generation with logprobs on the full-precision
    pool and on int8 KV, same prompt, shared across the quality tests."""
    from vgate_tpu.runtime.engine_core import EngineCore

    results = {}
    prompt = "the quick brown fox jumps over the lazy dog"
    for mode in ("auto", "int8"):
        core = EngineCore(_engine_cfg(mode), devices=jax.devices()[:1])
        core.start()
        try:
            [r] = core.generate(
                [prompt],
                [SamplingParams(
                    max_tokens=80, temperature=0.0, logprobs=True,
                    top_logprobs=1,
                )],
            )
            results[mode] = (r, core.geometry.kv_dtype)
        finally:
            core.stop()
    return results


def test_int8_engine_reports_dtype(quant_vs_oracle):
    assert quant_vs_oracle["auto"][1] == "f32"
    assert quant_vs_oracle["int8"][1] == "int8"


def test_int8_greedy_token_identity_64_steps(quant_vs_oracle):
    """The acceptance criterion: greedy decode under int8 KV stays
    token-identical to the full-precision oracle for >= 64 steps on
    the CPU test model."""
    oracle = quant_vs_oracle["auto"][0]["token_ids"]
    quant = quant_vs_oracle["int8"][0]["token_ids"]
    horizon = next(
        (i for i, (a, b) in enumerate(zip(oracle, quant)) if a != b),
        min(len(oracle), len(quant)),
    )
    assert horizon >= 64, f"diverged at step {horizon}"


def test_int8_logprob_drift_bounded(quant_vs_oracle):
    """Max drift of the chosen token's logprob over the identical
    prefix: int8 KV perturbs attention outputs by ~0.5% of absmax per
    read; on the tiny model that must stay a small logit effect."""
    oracle = quant_vs_oracle["auto"][0]
    quant = quant_vs_oracle["int8"][0]
    n = 0
    for a, b in zip(oracle["token_ids"], quant["token_ids"]):
        if a != b:
            break
        n += 1
    drift = max(
        abs(a["logprob"] - b["logprob"])
        for a, b in zip(oracle["logprobs"][:n], quant["logprobs"][:n])
    )
    assert drift < 0.25, f"max logprob drift {drift}"


def test_int8_requires_plain_mesh():
    from vgate_tpu.runtime.engine_core import EngineCore

    if len(jax.devices()) < 2:
        pytest.skip("needs 2 cpu devices (conftest sets host platform count)")
    with pytest.raises(ValueError, match="plain mesh"):
        EngineCore(
            _engine_cfg("int8", tp=2, num_devices=2),
            devices=jax.devices()[:2],
        )


def test_checkpoint_kv_dtype_mismatch_refused():
    """A checkpointed sequence stamped with another pool format must be
    refused by submit_existing — failing cleanly (typed 503 via
    replay_into) instead of splicing numerics mid-generation."""
    from vgate_tpu.runtime.engine_core import EngineCore
    from vgate_tpu.runtime.sequence import Sequence

    core = EngineCore(_engine_cfg("int8"), devices=jax.devices()[:1])
    try:
        seq = Sequence(
            prompt_ids=[5, 6, 7],
            params=SamplingParams(max_tokens=4, temperature=0.0),
        )
        seq.kv_dtype = "f32"
        with pytest.raises(ValueError, match="kv dtype"):
            core.submit_existing(seq)
        # matching stamp rides through the gate
        seq2 = Sequence(
            prompt_ids=[5, 6, 7],
            params=SamplingParams(max_tokens=4, temperature=0.0),
        )
        seq2.kv_dtype = "int8"
        core.submit_existing(seq2)  # no engine thread: just enqueued
    finally:
        core.stop()


def test_checkpoint_records_kv_dtype():
    from vgate_tpu.runtime.sequence import Sequence

    seq = Sequence(
        prompt_ids=[1, 2, 3],
        params=SamplingParams(max_tokens=4),
    )
    seq.kv_dtype = "int8"
    cp = seq.checkpoint()
    assert cp.kv_dtype == "int8"
    assert cp.as_dict()["kv_dtype"] == "int8"
    assert seq.checkpoint_summary() == cp.as_dict()
    restored = Sequence.from_checkpoint(cp)
    assert restored.kv_dtype == "int8"


# --------------------------------------------- admission capacity stack


def test_admission_auto_token_budget_scales_with_capacity():
    from vgate_tpu.admission import AdmissionController

    class Cfg:
        enabled = True
        max_queued_tokens = 1000
        auto_token_budget = 2.0
        max_queued_requests = 0
        reject_would_miss_slo = False
        kv_free_watermark = 0.0
        per_key_max_inflight = 0
        key_tiers = {}
        default_tier = "standard"
        tier_fractions = {"standard": 1.0}
        throughput_alpha = 0.3
        throughput_init_tps = 400.0
        prefix_discount = 0.0

    capacity = {"kv_token_capacity": 4000}
    ctl = AdmissionController(Cfg(), signals=lambda: capacity)
    # effective limit = max(1000, 2.0 * 4000) = 8000: a cost the static
    # limit would shed now admits
    ctl.admit(6000)
    stats = ctl.get_stats()
    assert stats["effective_max_queued_tokens"] == 8000
    assert stats["kv_token_capacity"] == 4000
    # int8 halves page bytes -> capacity (and with it the budget) ~2x
    capacity["kv_token_capacity"] = 2000
    from vgate_tpu.errors import ServerOverloadedError

    with pytest.raises(ServerOverloadedError):
        ctl.admit(6000)

    # max_queued_tokens = 0 means UNLIMITED (config.yaml) — the auto
    # budget must never convert the sentinel into a finite cap
    Cfg.max_queued_tokens = 0
    unlimited = AdmissionController(Cfg(), signals=lambda: capacity)
    unlimited.admit(10 * capacity["kv_token_capacity"])
    assert unlimited.get_stats()["effective_max_queued_tokens"] == 0
