"""The v5e compiles (``tests/tpu_aot.py`` says how) of the packed head of
64 and of the LFM2 cut as its cell serves it.
"""

import jax
import jax.numpy as jnp
import pytest

from tests.tpu_aot import (  # noqa: F401 (v5e: a fixture)
    abstract_on, assert_no_copy_of, assert_no_logits_array, cut_and_shapes,
    nbytes, PAGE, prompt_program, v5e,
)
from vgate_tpu.models.specs import spec_for_model_id


# ------------------------------------------- LFM2: head size 64, packed

# the cut the benchmark serves: the published 40 layers, 8 of 64 experts
LFM2_CUT = ("LiquidAI/LFM2-24B-A2B", dict(
    name="lfm2-cut", num_experts=8, first_expert=0))
# what the configuration's hbm_utilization (0.9 of the chip's 16.9 GB)
# leaves for the programs once weights (7.52 GB), tails and the pool
# (what is left, to the page) stand: 10 % of the chip
LFM2_PROGRAM_ROOM = 0.10 * 16.9e9


def test_packed_head_64_decode_launch_compiles_at_the_cells_shape(v5e):
    """LFM2's decode launch as the cell makes it: 256 slots, the pool's
    4 pair rows of 128 lanes (8 KV heads of 64, two a row), G 8 (2 x 4
    query heads a row), 64 pages a sequence, the step's K and V written
    by the kernel: the existing kernel at (KV 4, G 8, hd 128)."""
    from vgate_tpu.ops.head_pack import over_packed_pool
    from vgate_tpu.ops.pallas.paged_attention import (
        paged_decode_attention_pallas,
    )

    A = abstract_on(v5e)
    spec = spec_for_model_id(LFM2_CUT[0]).pack_kv_heads()
    assert (spec.cache_heads, spec.cache_head_dim) == (4, 128)
    B, H, KV, hd = 256, 32, 8, 64
    pool = A((10, 4, 4097, PAGE, 128), jnp.bfloat16)
    fn = over_packed_pool(paged_decode_attention_pallas, spec)
    compiled = jax.jit(
        lambda q, kp, vp, pt, lens, layer, k, v: fn(
            q, kp, vp, pt, lens, layer=layer, k_new=k, v_new=v),
        donate_argnums=(1, 2),
    ).lower(
        A((B, H, hd), jnp.bfloat16), pool, pool, A((B, 64), jnp.int32),
        A((B,), jnp.int32), A((), jnp.int32), A((B, KV, hd), jnp.bfloat16),
        A((B, KV, hd), jnp.bfloat16),
    ).compile()
    assert "paged_decode_attention_pallas" in compiled.as_text()
    # the 0.5B's pair: ONE row of two heads, G 14
    qwen = spec_for_model_id("Qwen/Qwen2.5-0.5B-Instruct").pack_kv_heads()
    fn = over_packed_pool(paged_decode_attention_pallas, qwen)
    pool = A((24, 1, 513, PAGE, 128), jnp.bfloat16)
    jax.jit(lambda q, kp, vp, pt, lens, layer, k, v: fn(
        q, kp, vp, pt, lens, layer=layer, k_new=k, v_new=v)).lower(
        A((32, 14, 64), jnp.bfloat16), pool, pool, A((32, 16), jnp.int32),
        A((32,), jnp.int32), A((), jnp.int32),
        A((32, 2, 64), jnp.bfloat16), A((32, 2, 64), jnp.bfloat16),
    ).compile()


@pytest.mark.parametrize("preset, rows", [
    ("LiquidAI/LFM2-24B-A2B", 1024), ("Qwen/Qwen2.5-0.5B-Instruct", 512)])
def test_packed_head_64_multitok_kernel_compiles_for_v5e(v5e, preset, rows):
    """Query rows against a cached prefix over packed rows (a chunk of a
    chunked prefill, a prefix hit's suffix, speculative verify): the
    multi-token kernel at 2 G query heads a row holds more in VMEM, so
    ``multitok_attention_impl`` hands it fewer rows as the group grows:
    1,024 at LFM2's 8, 512 at the 0.5B's 14 (1,024 x 14 run out)."""
    from vgate_tpu.models.decoder import (
        multitok_attention_impl, packed_group)
    from vgate_tpu.ops.head_pack import over_packed_pool
    from vgate_tpu.ops.pallas.paged_attention import (
        paged_multitok_attention_pallas,
    )

    A = abstract_on(v5e)
    spec = spec_for_model_id(preset).pack_kv_heads()
    group = packed_group(spec)
    assert group == 2 * spec.num_heads // spec.num_kv_heads
    assert multitok_attention_impl(True, rows=rows, group=group) == "pallas"
    assert multitok_attention_impl(True, rows=2 * rows, group=group) == "jnp"
    assert multitok_attention_impl(True, rows=1024) == "pallas"  # unpacked
    fn = over_packed_pool(paged_multitok_attention_pallas, spec)
    pool = A((2, spec.cache_heads, 257, PAGE, 128), jnp.bfloat16)
    jax.jit(lambda q, kp, vp, pt, at, n, layer: fn(
        q, kp, vp, pt, at, n, layer=layer)).lower(
        A((2, rows, spec.num_heads, 64), jnp.bfloat16), pool, pool,
        A((2, 64), jnp.int32), A((2,), jnp.int32), A((2,), jnp.int32),
        A((), jnp.int32)).compile()


@pytest.mark.parametrize("B, S", [(8, 128), (1, 2048)],
                         ids=["wave-8x128", "1x2048"])
def test_prompt_attention_compiles_at_head_64_for_v5e(v5e, B, S):
    """The flash prompt kernel takes fresh q, k and v, no page: its
    64-lane blocks span the arrays' whole last dimension, which Mosaic
    compiles (a PAGE of 64 lanes it refuses: the xfail above).  So the
    prompt pass runs unpacked, without the packed launch's doubled
    products, and only its page write lays the pairs down."""
    from vgate_tpu.ops.pallas.flash_prefill import (
        flash_prefill_attention_pallas,
    )

    A = abstract_on(v5e)
    H, KV, hd = 32, 8, 64
    flash_prefill_attention_pallas.lower(
        A((B, S, H, hd), jnp.bfloat16), A((B, S, KV, hd), jnp.bfloat16),
        A((B, S, KV, hd), jnp.bfloat16), A((B,), jnp.int32),
        skip_padding=True,
    ).compile()


@pytest.fixture(scope="module")
def lfm2_cut(v5e):
    from vgate_tpu.models.hybrid import make_state

    A = abstract_on(v5e)
    spec, params = cut_and_shapes(A, *LFM2_CUT)
    spec = spec.pack_kv_heads()
    state = jax.tree.map(
        lambda x: A(x.shape, x.dtype),
        jax.eval_shape(lambda: make_state(spec, 256, jnp.bfloat16, PAGE)))
    assert set(state) == {"conv"}, "a tail alone: no tile"
    assert nbytes(state) == 256 * 245760
    assert abs(nbytes(params) - 7.52e9) < 0.02e9
    pages = 10001  # 6.55 GB of K and V: what the chip has left, about
    pool = A((spec.attn_layers, spec.cache_heads, pages, PAGE,
              spec.cache_head_dim), jnp.bfloat16)
    assert pool.shape == (10, 4, pages, PAGE, 128)
    return A, spec, params, pool, state


def test_lfm2_decode_chunk_compiles_on_v5e(lfm2_cut):
    """The LFM2 cut as the cell serves it (40 layers, 8 of 64 experts
    held, 256 slots of 2,048 tokens): the decode chunk compiles for the
    v5e with the packed pool and the tails aliased input to output, the
    packed launch and the grouped product in it, and temporaries inside
    what the configuration's ``hbm_utilization`` leaves."""
    from vgate_tpu.runtime.step_programs import _decode_chunk

    A, spec, params, pool, state = lfm2_cut
    B, ctx = 256, 2048
    compiled = _decode_chunk.lower(
        params, spec, A((B,), jnp.int32), A((B,), jnp.int32), pool, pool,
        A((B, ctx // PAGE), jnp.int32), A((B,), jnp.bool_),
        A((B,), jnp.float32), A((B,), jnp.float32), A((B,), jnp.int32),
        A((2,), jnp.uint32), A((), jnp.uint32),
        num_steps=8, use_pallas=True, max_position=ctx - 1,
        seeds=A((B,), jnp.int32), steps=A((B,), jnp.int32),
        all_greedy=True, guard=True, state=state,
    ).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * nbytes(pool) + nbytes(state), (
        "the pool or the tails are copied")
    print("lfm2 decode chunk temporaries", mem.temp_size_in_bytes)
    assert mem.temp_size_in_bytes < LFM2_PROGRAM_ROOM
    text = compiled.as_text()
    assert "paged_decode_attention_pallas" in text
    assert "moe_grouped_matmul_pallas" in text
    # the greedy chunk's 67 MB of logits stay on the chip here too
    assert_no_logits_array(text, B, spec.vocab_size)
    # no copy of a period's mixers' matrices (the walker's scans carry
    # indices): before PR 48 these four a period, nine periods a step,
    # were 2.28 ms of the cell's 23.6 ms step
    assert_no_copy_of(text, (3, 2048, 6144), (2, 1, 2048, 6144),
                       (3, 2048, 2048), (2, 1, 2048, 2048))


@pytest.mark.parametrize("B, bucket", [(8, 128), (1, 2048), (8, 2048)],
                         ids=["wave-8x128", "1x2048", "wave-8x2048"])
def test_lfm2_prompt_program_fits_beside_the_cache_on_v5e(
        lfm2_cut, B, bucket):
    """The cell's prompt programs at their two ends (a wave of 8 in the
    128 bucket, what the traffic sends; one row in the 2,048 bucket,
    what the reference's longest prompt and a resumed request take):
    their temporaries fit what ``hbm_utilization`` leaves."""
    A, spec, params, pool, state = lfm2_cut
    compiled = prompt_program(A, spec, params, pool, pool, state,
                               bucket=bucket, B=B)
    mem = compiled.memory_analysis()
    print("lfm2 prompt program", B, bucket, "temporaries",
          mem.temp_size_in_bytes)
    assert mem.alias_size_in_bytes >= 2 * nbytes(pool) + nbytes(state)
    assert mem.temp_size_in_bytes < LFM2_PROGRAM_ROOM
    assert "flash_prefill_attention_pallas" in compiled.as_text()
