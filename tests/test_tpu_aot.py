"""Ahead-of-time compiles for the v5e, with no chip present.

libtpu ships next to jax in this installation, and
``jax.experimental.topologies.get_topology_desc("v5e:2x2", "tpu")``
describes four ``TPU v5 lite`` devices without one being attached.
Abstract arguments placed on such a device run the real XLA:TPU and
Mosaic compilers through ``jitted.lower(...).compile()`` — so a kernel
Mosaic refuses, or a step program whose temporaries outgrow the chip, is
found here on the CPU instead of on chip time.  What these tests assert
was confirmed on the chip by ``chip_smoke.py`` (PERF.md "Bring-up").
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from vgate_tpu.models.specs import spec_for_model_id

PAGE = 32
# (num_heads, kv_heads, head_dim): Qwen2.5-1.5B on one chip, and one
# tp=4 shard of Qwen2.5-7B (28 heads / 4 KV heads over four chips)
GEOM_1P5B = (12, 2, 128)
GEOM_7B_TP4_SHARD = (7, 1, 128)
GEOM_7B = (28, 4, 128)
# Qwen3-Next's gated full-attention layers: head size 256
GEOM_QWEN3_NEXT = (16, 2, 256)


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc("v5e:2x2", "tpu")
    except Exception as exc:  # noqa: BLE001 — any failure means "no libtpu"
        pytest.skip(f"no TPU AOT topology in this installation: {exc!r}")
    return SingleDeviceSharding(topo.devices[0])


def _abstract(sharding):
    return lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=sharding
    )


def _pool(A, kv, hd, pages, layers=2, int8=False):
    """Stacked [L, KV, P, ps, hd] pool, as the plain-mesh forwards pass
    it (layer-indexed), optionally int8 pages + bf16 scales."""
    if not int8:
        return A((layers, kv, pages, PAGE, hd), jnp.bfloat16)
    from vgate_tpu.ops.kv_quant import QuantPages

    return QuantPages(
        A((layers, kv, pages, PAGE, hd), jnp.int8),
        A((layers, kv, pages, PAGE), jnp.bfloat16),
    )


def _compile_decode_kernel(A, geom, int8=False, B=8, pages_per_seq=16,
                           write=False):
    """``write``: the kernel that also writes each slot's new token
    (what a decode step on a plain pool launches); without, the one that
    only reads (an int8 pool, a tp shard)."""
    from vgate_tpu.ops.pallas.paged_attention import (
        paged_decode_attention_pallas,
    )

    H, KV, hd = geom
    pool = _pool(A, KV, hd, 64, int8=int8)
    new = (
        {name: A((B, KV, hd), jnp.bfloat16) for name in ("k_new", "v_new")}
        if write else {}
    )
    return paged_decode_attention_pallas.lower(
        A((B, H, hd), jnp.bfloat16), pool, pool,
        A((B, pages_per_seq), jnp.int32), A((B,), jnp.int32),
        layer=A((), jnp.int32), **new,
    ).compile()


def _compile_multitok_kernel(A, geom, int8=False):
    from vgate_tpu.ops.pallas.paged_attention import (
        paged_multitok_attention_pallas,
    )

    H, KV, hd = geom
    B, S, pages_per_seq = 2, 128, 16
    pool = _pool(A, KV, hd, 64, int8=int8)
    return paged_multitok_attention_pallas.lower(
        A((B, S, H, hd), jnp.bfloat16), pool, pool,
        A((B, pages_per_seq), jnp.int32), A((B,), jnp.int32),
        A((B,), jnp.int32), layer=A((), jnp.int32),
    ).compile()


def _compile_flash_kernel(A, geom):
    from vgate_tpu.ops.pallas.flash_prefill import (
        flash_prefill_attention_pallas,
    )

    H, KV, hd = geom
    B, S = 2, 256
    return flash_prefill_attention_pallas.lower(
        A((B, S, H, hd), jnp.bfloat16), A((B, S, KV, hd), jnp.bfloat16),
        A((B, S, KV, hd), jnp.bfloat16), A((B,), jnp.int32),
    ).compile()


@pytest.mark.parametrize(
    "geom", [GEOM_1P5B, GEOM_7B_TP4_SHARD, GEOM_QWEN3_NEXT],
    ids=["1.5B", "7B-tp4-shard", "qwen3-next-hd256"],
)
@pytest.mark.parametrize(
    "compile_kernel",
    [_compile_decode_kernel, _compile_flash_kernel, _compile_multitok_kernel],
    ids=["paged_decode", "flash_prefill", "paged_multitok"],
)
def test_default_path_kernels_compile_for_v5e(v5e, geom, compile_kernel):
    compile_kernel(_abstract(v5e), geom)


@pytest.mark.parametrize(
    "geom", [GEOM_1P5B, GEOM_7B, GEOM_QWEN3_NEXT],
    ids=["1.5B", "7B", "qwen3-next-hd256"],
)
def test_decode_kernel_compiles_at_the_cells_shapes_for_v5e(v5e, geom):
    """The benchmark's three geometries as the engine launches them: 256
    slots of 2,048 tokens.  The slots a program serves differ between
    them and come, with the chunk's pages, from one rule
    (paged_attention._decode_sizes)."""
    from vgate_tpu.ops.pallas.paged_attention import _decode_sizes

    H, KV, hd = geom
    sizes = _decode_sizes(
        256, KV, H // KV, hd, PAGE, 64, jnp.bfloat16, jnp.bfloat16
    )
    assert sizes[:2] == {
        GEOM_1P5B: (8, 64), GEOM_7B: (8, 32), GEOM_QWEN3_NEXT: (8, 32),
    }[geom]
    _compile_decode_kernel(
        _abstract(v5e), geom, B=256, pages_per_seq=64, write=True
    )


# what serves a decode step in each of the benchmark's configurations,
# (slots, KV heads, query heads a KV head, head size, pages a slot, pools),
# and what `_decode_sizes` reads off it: (pages a chunk, slots a program,
# work-list items a loop trip)
SERVED_DECODE_SHAPES = {
    "qwen2.5-1.5b": ((256, 2, 6, 128, 64, 2), (8, 64, 2)),
    "qwen2.5-7b-l14": ((256, 4, 7, 128, 64, 2), (8, 32, 1)),
    "qwen3-next-80b-a3b-l8e128": ((256, 2, 8, 256, 64, 2), (8, 32, 2)),
    "nemotron-3-super-120b-a12b-l11e128": (
        (192, 2, 16, 128, 64, 2), (8, 64, 2)),
    # the latent pool: one "head" of 384-lane rows under 32 query heads
    "mistral-small-4-119b-l4e32": ((256, 1, 32, 384, 256, 1), (8, 21, 2)),
    # the full layers (the rings of five pages a slot get the same chunk)
    "k-exaone-236b-a23b-l5e16": ((192, 8, 8, 128, 256, 2), (4, 16, 1)),
    # MHA, one query row a KV head: 64 window pages behind 32 of summaries.
    # The 4 MiB budget alone gave ONE page a chunk here (PR 45)
    "evabyte-6.5b-l8": ((20, 32, 1, 128, 96, 2), (4, 4, 1)),
    # 8 KV heads of 64 as 4 pair rows of 128 lanes, 2 x 4 query heads a
    # row (ops/head_pack.py): the 7B's item of four rows, one a trip
    "lfm2-24b-a2b-e8": ((256, 4, 8, 128, 64, 2), (8, 32, 1)),
}


@pytest.mark.parametrize("config", list(SERVED_DECODE_SHAPES))
def test_decode_sizes_at_the_served_shapes(config):
    """A change of the rule shows here.  Two items a trip where an item
    holds at most two KV heads' products (PERF.md section 6, PR 37: the
    probe at these shapes); the 7B's four keep one, the program as it
    was."""
    from vgate_tpu.ops.pallas.paged_attention import _decode_sizes

    (B, KV, G, hd, pages_per_seq, pools), sizes = SERVED_DECODE_SHAPES[config]
    assert _decode_sizes(
        B, KV, G, hd, PAGE, pages_per_seq, jnp.bfloat16, jnp.bfloat16,
        pools=pools,
    ) == sizes


def test_decode_kernel_compiles_at_the_evabyte_cells_shape_for_v5e(v5e):
    """The kernel alone as the EvaByte cell launches it (20 slots of 96
    pages, 32 KV heads of one query row each, the step's row written),
    at the chunk `_decode_sizes` gives it: a chunk that Mosaic refuses
    fails here, before a chip is asked."""
    B, KV, G, hd, pages_per_seq, _ = SERVED_DECODE_SHAPES[
        "evabyte-6.5b-l8"][0]
    _compile_decode_kernel(
        _abstract(v5e), (KV * G, KV, hd), B=B, pages_per_seq=pages_per_seq,
        write=True,
    )


@pytest.mark.parametrize("items", [1, 2])
def test_latent_decode_kernel_compiles_at_the_cells_shape_for_v5e(v5e, items):
    """`mla_decode_attention_pallas` as the mistral cell launches it (256
    slots of 8,192 tokens, 32 heads over 384-lane rows, the new row
    written by the kernel), with one and with two items a loop trip."""
    from vgate_tpu.ops.pallas.paged_attention import (
        mla_decode_attention_pallas,
    )

    A = _abstract(v5e)
    B, _, H, W, pages_per_seq, _ = SERVED_DECODE_SHAPES[
        "mistral-small-4-119b-l4e32"][0]
    mla_decode_attention_pallas.lower(
        A((B, H, W), jnp.bfloat16), A((4, 1, 1025, PAGE, W), jnp.bfloat16),
        A((B, pages_per_seq), jnp.int32), A((B,), jnp.int32),
        A((), jnp.int32), A((B, W), jnp.bfloat16), v_width=256, scale=0.05,
        items=items,
    ).compile()


def test_gated_delta_step_kernel_compiles_for_v5e(v5e):
    """The recurrent-step kernel at Qwen3-Next's sizes (256 slots, 32
    value heads of 128 x 128 float32), the state updated in place."""
    from vgate_tpu.ops.pallas.gated_delta import gated_delta_step_pallas

    A = _abstract(v5e)
    B, H, dk, dv, layers = 256, 32, 128, 128, 6
    f32 = jnp.float32
    state_bytes = layers * B * H * dk * dv * 4
    compiled = gated_delta_step_pallas.lower(
        A((B, H, dk), f32), A((B, H, dk), f32), A((B, H, dk), f32),
        A((B, H, dv), f32), A((B, H, dv), f32),
        A((layers, B, H, dk, dv), f32), A((), jnp.int32),
    ).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= state_bytes, "the state is copied"
    assert mem.temp_size_in_bytes < state_bytes // 8


@pytest.mark.parametrize("B, H, G, layers, block", [
    (192, 128, 8, 5, 0), (192, 128, 8, 5, 16), (192, 128, 8, 5, 32),
    (80, 64, 1, 36, 0), (80, 64, 1, 36, 16), (80, 64, 1, 36, 32),
], ids=["nemotron", "nemotron-16", "nemotron-32",
        "granite", "granite-16", "granite-32"])
def test_ssd_step_kernel_compiles_for_v5e(v5e, B, H, G, layers, block):
    """The Mamba-2 step kernel at Nemotron-H's sizes (192 slots, 128
    heads of 64 x 128 float32 in 8 groups) and at Granite 4.0-H's (80
    slots, 64 heads in ONE group, 36 layers: 6.04 GB of tiles), at the
    rule's block of heads and at the others the chip was asked about,
    the state updated in place."""
    from vgate_tpu.ops.pallas.ssd import ssd_step_pallas

    A = _abstract(v5e)
    P, N = 64, 128
    f32 = jnp.float32
    state_bytes = layers * B * H * P * N * 4
    compiled = ssd_step_pallas.lower(
        A((B, H, P), f32), A((B, H), f32), A((B, G, N), f32),
        A((B, G, N), f32), A((layers, B, H, P, N), f32), A((), jnp.int32),
        block=block,
    ).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= state_bytes, "the state is copied"
    assert mem.temp_size_in_bytes < state_bytes // 8


@pytest.mark.parametrize(
    "rows, k, n, tm",
    [(4224, 1024, 2688, 128), (4224, 2688, 1024, 128)],
    ids=["latent-up", "latent-down"],
)
def test_latent_grouped_product_compiles_for_v5e(v5e, rows, k, n, tm):
    """The grouped product at the latent experts' widths, through
    ``grouped_product``'s own tile choice: 512 does not divide 2,688."""
    from vgate_tpu.ops.moe import grouped_product

    A = _abstract(v5e)
    jax.jit(
        lambda r, w, g, l: grouped_product(r, w, g, l, True)
    ).lower(
        A((rows, k), jnp.bfloat16), A((5, 128, k, n), jnp.bfloat16),
        A((128,), jnp.int32), A((), jnp.int32),
    ).compile()


@pytest.mark.parametrize(
    "rows, k, n, tm, tn",
    [(2560, 2048, 512, 32, 512), (2560, 512, 2048, 32, 2048),
     (40960, 2048, 512, 128, 512)],
    ids=["decode-gate-up", "decode-down", "prompt-block"],
)
def test_grouped_matmul_kernel_compiles_for_v5e(v5e, rows, k, n, tm, tn):
    """The held experts' grouped product on the full [layers, experts,
    k, n] stack (128 experts of Qwen3-Next's widths), layer-indexed."""
    from vgate_tpu.ops.pallas.grouped_matmul import grouped_matmul_pallas

    A = _abstract(v5e)
    grouped_matmul_pallas.lower(
        A((rows, k), jnp.bfloat16), A((6, 128, k, n), jnp.bfloat16),
        A((128,), jnp.int32), A((), jnp.int32), tm=tm, tn=tn,
    ).compile()


class MosaicRefusal(Exception):
    """The compile failed inside Mosaic with the message on record."""


def _compile_expecting(fragment, compile_kernel, *args, **kwargs):
    """Only the recorded Mosaic message counts as the expected failure;
    any other error (an API change, a wrong shape here) stays an error."""
    try:
        compile_kernel(*args, **kwargs)
    except Exception as exc:  # noqa: BLE001 — re-raised below
        if type(exc).__name__ == "MosaicError" and fragment in str(exc):
            raise MosaicRefusal(str(exc)) from exc
        raise


@pytest.mark.xfail(
    strict=True,
    raises=MosaicRefusal,
    reason="Mosaic: 'Slice shape along dimension 2 must be aligned to "
    "tiling (8), but is 1' — the per-page scale-row DMA of int8 KV pages "
    "(ops/pallas/paged_attention.py start_chunk / _chunk_dma); engine "
    "construction refuses the combination on a TPU "
    "(refuse_unbuildable_kernels)",
)
@pytest.mark.parametrize(
    "compile_kernel",
    [_compile_decode_kernel, _compile_multitok_kernel],
    ids=["paged_decode", "paged_multitok"],
)
def test_int8_kv_kernels_compile_for_v5e(v5e, compile_kernel):
    _compile_expecting(
        "aligned to tiling (8), but is 1",
        compile_kernel, _abstract(v5e), GEOM_1P5B, int8=True,
    )


@pytest.mark.xfail(
    strict=True,
    raises=MosaicRefusal,
    reason="Mosaic: 'Slice shape along dimension 4 must be aligned to "
    "tiling (128), but is 64' (decode) / 'unsupported shape cast' "
    "(multi-token) — a RAW head_dim-64 page is half a lane tile.  What "
    "serves such a spec is the packed layout (ops/head_pack.py: two KV "
    "heads a 128-lane row, the same kernels at (KV / 2, 2 G, 128): "
    "test_packed_head_64_* below); engine construction refuses only "
    "what cannot pack (refuse_unbuildable_kernels)",
)
@pytest.mark.parametrize(
    "compile_kernel,fragment",
    [
        (_compile_decode_kernel, "aligned to tiling (128), but is 64"),
        (_compile_multitok_kernel, "unsupported shape cast"),
    ],
    ids=["paged_decode", "paged_multitok"],
)
def test_head_dim_64_kernels_compile_for_v5e(v5e, compile_kernel, fragment):
    _compile_expecting(
        fragment, compile_kernel, _abstract(v5e), (14, 2, 64)
    )


def test_engine_refuses_what_mosaic_refuses():
    from vgate_tpu.runtime.engine_core import refuse_unbuildable_kernels

    qwen_1p5b = spec_for_model_id("Qwen/Qwen2.5-1.5B-Instruct")
    refuse_unbuildable_kernels(qwen_1p5b, kv_quant=False)
    with pytest.raises(ValueError, match=r"aligned to tiling \(8\)"):
        refuse_unbuildable_kernels(qwen_1p5b, kv_quant=True)
    # heads of 64 pair two to a 128-lane row (ops/head_pack.py): the
    # 0.5B (2 KV heads) and LFM2 (8) pass; an odd number of KV heads, or
    # a mesh that splits the pool by KV head, is still refused
    import dataclasses

    qwen_0p5b = spec_for_model_id("Qwen/Qwen2.5-0.5B-Instruct")
    refuse_unbuildable_kernels(qwen_0p5b, kv_quant=False)
    refuse_unbuildable_kernels(
        spec_for_model_id("LiquidAI/LFM2-24B-A2B"), kv_quant=False)
    with pytest.raises(ValueError, match=r"aligned to tiling \(128\)"):
        refuse_unbuildable_kernels(
            dataclasses.replace(qwen_0p5b, num_heads=15, num_kv_heads=3),
            kv_quant=False)
    with pytest.raises(ValueError, match=r"aligned to tiling \(128\)"):
        refuse_unbuildable_kernels(
            qwen_0p5b, kv_quant=False, plain_mesh=False)


# ------------------------------------------------- step-program memory

# A step program may hold weights + ONE KV pool.  Before PR 21 each held
# two: per-layer xs/ys threading cannot alias the ys stack onto the xs
# stack, and a sliced kv-head dim in the KV scatter made XLA re-lay the
# whole pool out (models/decoder.py, ops/kv_quant.py kv_write_tokens).
# The rule: temporaries stay under a quarter of the pool, and donation
# aliases the whole pool onto the output.
TEMP_SHARE_OF_POOL = 0.25


def _qwen_1p5b(A):
    from vgate_tpu.models.decoder import init_params

    spec = spec_for_model_id("Qwen/Qwen2.5-1.5B-Instruct")
    params = jax.eval_shape(
        lambda: init_params(spec, jax.random.PRNGKey(0), jnp.bfloat16)
    )
    params = jax.tree.map(lambda x: A(x.shape, x.dtype), params)
    pages = 2049  # 1.75 GiB of K+V: any pool-sized temporary shows
    pool = A(
        (spec.num_layers, spec.num_kv_heads, pages, PAGE, spec.head_dim),
        jnp.bfloat16,
    )
    pool_bytes = 2 * (
        spec.num_layers * spec.num_kv_heads * pages * PAGE
        * spec.head_dim * 2
    )
    return spec, params, pool, pool_bytes


def _assert_one_pool(compiled, pool_bytes):
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes, (
        "donated pools are not aliased onto the output"
    )
    assert mem.temp_size_in_bytes < TEMP_SHARE_OF_POOL * pool_bytes, (
        f"temporaries {mem.temp_size_in_bytes / 2**30:.2f} GiB next to a "
        f"{pool_bytes / 2**30:.2f} GiB pool: a step program holds a "
        "second pool again"
    )
    assert "{4,1,3,2,0" not in compiled.as_text(), (
        "XLA re-laid the KV pool out (kv heads minor): the Pallas "
        "kernels read the default layout, so this costs whole-pool copies"
    )


def test_decode_chunk_holds_one_pool_on_v5e(v5e):
    from vgate_tpu.runtime.step_programs import _decode_chunk

    A = _abstract(v5e)
    spec, params, pool, pool_bytes = _qwen_1p5b(A)
    B, ctx = 32, 2048
    compiled = _decode_chunk.lower(
        params, spec, A((B,), jnp.int32), A((B,), jnp.int32), pool, pool,
        A((B, ctx // PAGE), jnp.int32), A((B,), jnp.bool_),
        A((B,), jnp.float32), A((B,), jnp.float32), A((B,), jnp.int32),
        A((2,), jnp.uint32), A((), jnp.uint32),
        num_steps=8, use_pallas=True, max_position=ctx - 1,
        seeds=A((B,), jnp.int32), steps=A((B,), jnp.int32),
        all_greedy=True, guard=True,
    ).compile()
    _assert_one_pool(compiled, pool_bytes)
    # the step's new K and V reach the pool through the decode kernel's
    # own descriptors (models/decoder.py decode_kv_write): XLA scatters
    # nothing into a pool
    pool_shape = "[" + ",".join(map(str, pool.shape)) + "]"
    scatters = [
        line for line in compiled.as_text().splitlines()
        if "scatter" in line and pool_shape in line
    ]
    assert not scatters, scatters[0][:300]


def _assert_no_logits_array(text, rows, vocab):
    """The fused head's program: no float32 logits as a buffer, in the
    head's layout or flat, and the pass itself in it."""
    assert f"f32[{rows},{vocab}]" not in text
    assert f"f32[{rows * vocab}]" not in text
    assert "greedy_head" in text


def test_decode_chunk_edits_logits_in_place_on_v5e(v5e):
    """The variant the benchmark's cells run (256 slots, 8 steps, a
    `min_tokens` floor over 2 stop ids, a 16-wide `logit_bias`, argmax,
    the guard) keeps a step's logits on the chip: the head's product,
    the guard, the edits and the argmax are one pass over vocabulary
    tiles (ops/pallas/greedy_head.py) and NO `f32[256, 151936]` stands
    in the program: 156 MB written once and read twice a step, 1.59 ms
    of a 14.7 / 7.3 ms step (PERF.md, PR 50).  A variant that needs the
    array (a row wants logprobs) keeps it, and there `logit_bias` and
    the floor stay selects fused into a pass that reads it anyway
    (ops/sampling.py): as scatters they cost the array a flat relayout
    and back, a copy and an update every step: 1.8 ms of a 9.5 ms step
    (PERF.md, PR 34)."""
    from vgate_tpu.runtime.step_programs import _decode_chunk

    A = _abstract(v5e)
    spec, params, pool, _ = _qwen_1p5b(A)
    B, ctx = 256, 2048

    def compiled(**variant):
        return _decode_chunk.lower(
            params, spec, A((B,), jnp.int32), A((B,), jnp.int32), pool, pool,
            A((B, ctx // PAGE), jnp.int32), A((B,), jnp.bool_),
            A((B,), jnp.float32), A((B,), jnp.float32), A((B,), jnp.int32),
            A((2,), jnp.uint32), A((), jnp.uint32),
            num_steps=8, use_pallas=True, max_position=ctx - 1,
            seeds=A((B,), jnp.int32), steps=A((B,), jnp.int32),
            min_toks=A((B,), jnp.int32), stop_id_mat=A((B, 2), jnp.int32),
            bias_ids=A((B, 16), jnp.int32),
            bias_vals=A((B, 16), jnp.float32), guard=True, **variant,
        ).compile()

    cells = compiled(all_greedy=True)
    _assert_no_logits_array(cells.as_text(), B, spec.vocab_size)
    wants_logprobs = compiled(num_logprobs=8)
    fell = (wants_logprobs.memory_analysis().temp_size_in_bytes
            - cells.memory_analysis().temp_size_in_bytes)
    print("decode chunk temporaries fell by", fell)
    assert fell >= B * spec.vocab_size * 4, "the array's bytes stayed"

    text = wants_logprobs.as_text()
    logits = f"f32[{B},{spec.vocab_size}]"
    assert logits in text  # the lm-head's output is what is looked for
    assert "greedy_head" not in text
    assert f"f32[{B * spec.vocab_size}]" not in text, (
        "the logits are re-laid flat: an edit is a scatter again"
    )
    for line in text.splitlines():
        if "=" not in line or logits not in line:
            continue
        op = line.split("=", 1)[1]
        assert " scatter(" not in op, line[:300]
        # a whole-array copy: `copy(` with the logits' shape as result
        result = op.split("(", 1)[0]
        assert not (logits in result and result.rstrip().endswith("copy")), (
            line[:300]
        )


# the benchmark's cuts of two presets (perfbench/configs): what the
# cells serve
MISTRAL_CUT = ("mistralai/Mistral-Small-4-119B-2603", dict(
    name="mistral-cut", num_layers=4, num_experts=32, vocab_size=32768,
    eos_token_id=32767, bos_token_id=32766, extra_stop_ids=()))
EXAONE_CUT = ("LGAI-EXAONE/K-EXAONE-236B-A23B", dict(
    name="exaone-cut", num_layers=5, num_experts=16, vocab_size=19200,
    eos_token_id=19199, bos_token_id=19198))


def _cut_and_shapes(A, preset, changes):
    """(spec, abstract bf16 parameters) of a preset cut to a cell's."""
    import dataclasses

    from vgate_tpu.models.decoder import init_params

    spec = dataclasses.replace(spec_for_model_id(preset), **changes)
    params = jax.tree.map(
        lambda x: A(x.shape, x.dtype),
        jax.eval_shape(
            lambda: init_params(spec, jax.random.PRNGKey(0), jnp.bfloat16)))
    return spec, params


def test_latent_decode_chunk_compiles_on_v5e(v5e):
    """The Mistral-Small-4 cut as the cell serves it (4 layers, 32
    experts held, 256 slots of 8,192 tokens): the decode chunk compiles
    for the v5e with the ONE latent pool aliased input to output and the
    latent decode kernel in it, its trips as `_decode_sizes` sets them."""
    from vgate_tpu.runtime.step_programs import _decode_chunk

    A = _abstract(v5e)
    spec, params = _cut_and_shapes(A, *MISTRAL_CUT)
    B, ctx = 256, 8192
    pool = A((spec.attn_layers, spec.cache_heads, 16385, PAGE,
              spec.cache_head_dim), jnp.bfloat16)
    pool_bytes = 4 * 16385 * PAGE * 384 * 2
    compiled = _decode_chunk.lower(
        params, spec, A((B,), jnp.int32), A((B,), jnp.int32), pool, None,
        A((B, ctx // PAGE), jnp.int32), A((B,), jnp.bool_),
        A((B,), jnp.float32), A((B,), jnp.float32), A((B,), jnp.int32),
        A((2,), jnp.uint32), A((), jnp.uint32),
        num_steps=8, use_pallas=True, max_position=ctx - 1,
        seeds=A((B,), jnp.int32), steps=A((B,), jnp.int32),
        all_greedy=True, guard=True,
    ).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes, "the pool is copied"
    assert mem.temp_size_in_bytes < pool_bytes // 2
    assert "mla_decode_attention_pallas" in compiled.as_text()


def test_prefill_step_holds_one_pool_on_v5e(v5e):
    from vgate_tpu.runtime.step_programs import _prefill_step

    A = _abstract(v5e)
    spec, params, pool, pool_bytes = _qwen_1p5b(A)
    B, bucket = 2, 128
    compiled = _prefill_step.lower(
        params, spec, A((B, bucket), jnp.int32), A((B,), jnp.int32),
        pool, pool, A((B, bucket // PAGE), jnp.int32),
        A((B,), jnp.float32), A((B,), jnp.float32), A((B,), jnp.int32),
        A((2,), jnp.uint32), use_pallas=True,
        seeds=A((B,), jnp.int32), steps=A((B,), jnp.int32),
    ).compile()
    _assert_one_pool(compiled, pool_bytes)


def test_pattern_stack_decode_chunk_holds_one_state_on_v5e(v5e):
    """The Nemotron-H cut as the cell serves it (one period
    ``EMEMEMEMEM*``, 128 experts held, 192 slots): the decode chunk
    compiles for the v5e with the Mamba-2 state (4.1 GB) and the pools
    aliased input to output, and temporaries far under the state."""
    import dataclasses

    from vgate_tpu.models.decoder import init_params
    from vgate_tpu.models.hybrid import make_state
    from vgate_tpu.runtime.step_programs import _decode_chunk

    A = _abstract(v5e)
    spec = dataclasses.replace(
        spec_for_model_id("nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16"),
        name="nemotron-cut", num_layers=11, layer_pattern="EMEMEMEMEM*",
        num_experts=128, vocab_size=32768, eos_token_id=32767,
        bos_token_id=32766, extra_stop_ids=())
    abstract = lambda tree: jax.tree.map(
        lambda x: A(x.shape, x.dtype), jax.eval_shape(tree))
    params = abstract(
        lambda: init_params(spec, jax.random.PRNGKey(0), jnp.bfloat16))
    B, ctx = 192, 2048
    state = abstract(lambda: make_state(spec, B, jnp.bfloat16))
    state_bytes = sum(
        x.size * x.dtype.itemsize for x in jax.tree.leaves(state))
    assert abs(state_bytes - 4.0855e9) < 1e6
    pool = A((spec.attn_layers, spec.num_kv_heads, 12289, PAGE,
              spec.head_dim), jnp.bfloat16)
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
    assert mem.alias_size_in_bytes >= state_bytes, "the state is copied"
    assert mem.temp_size_in_bytes < state_bytes // 8
    text = compiled.as_text()
    assert "ssd_step_pallas" in text and "moe_grouped_matmul_pallas" in text


def test_window_stack_decode_chunk_and_prompt_kernel_compile_on_v5e(v5e):
    """The K-EXAONE cut as the cell serves it (5 layers, 16 experts
    held, 192 slots of 8,192 tokens): the decode chunk compiles for the
    v5e with the pool of the ONE full layer and the rings of the four
    window layers aliased input to output, a ring's launch under its own
    name beside the full layer's; and the window layers' banded prompt
    kernel compiles at the cell's one shape (8,192 rows, 64 heads on 8)."""
    from vgate_tpu.models.hybrid import make_state
    from vgate_tpu.ops.pallas.flash_prefill import (
        swa_prefill_attention_pallas,
    )
    from vgate_tpu.runtime.step_programs import _decode_chunk

    A = _abstract(v5e)
    spec, params = _cut_and_shapes(A, *EXAONE_CUT)
    abstract = lambda tree: jax.tree.map(
        lambda x: A(x.shape, x.dtype), jax.eval_shape(tree))
    B, ctx = 192, 8192
    state = abstract(lambda: make_state(spec, B, jnp.bfloat16, PAGE))
    state_bytes = sum(
        x.size * x.dtype.itemsize for x in jax.tree.leaves(state))
    assert state_bytes == 2 * 4 * 8 * (1 + B * 5) * PAGE * 128 * 2
    pages = 8193
    pool = A((spec.attn_layers, spec.num_kv_heads, pages, PAGE,
              spec.head_dim), jnp.bfloat16)
    pool_bytes = 2 * pages * PAGE * 8 * 128 * 2
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
    assert mem.alias_size_in_bytes >= pool_bytes + state_bytes, (
        "the pool or the rings are copied")
    # the weights' re-laid copies (q, k, v of five layers) and a step's
    # activations: 1.16 GB when this was written, far under the rings +
    # pool a deployment holds
    assert mem.temp_size_in_bytes < 1.5e9
    text = compiled.as_text()
    assert "swa_decode_attention_pallas" in text
    assert "paged_decode_attention_pallas" in text
    assert "moe_grouped_matmul_pallas" in text
    # the window layers' matrices are read where they stand (seven
    # asynchronous copies of 25-75 MB a step before PR 48)
    _assert_no_copy_of(text, (1, 3, 2048, 6144), (1, 3, 6144, 2048))
    S, H, KV, hd = 8192, 64, 8, 128
    band = jax.jit(lambda q, k, v, lens: swa_prefill_attention_pallas(
        q, k, v, lens, 128)).lower(
        A((1, S, H, hd), jnp.bfloat16), A((1, S, KV, hd), jnp.bfloat16),
        A((1, S, KV, hd), jnp.bfloat16), A((1,), jnp.int32)).compile()
    assert "swa_prefill_attention_pallas" in band.as_text()


def _prompt_program(A, spec, params, pool, v_pool, state, bucket=8192,
                    B=1):
    """The cell's prompt program: ``B`` prompts (ONE, in the long cells)
    in ``bucket``."""
    from vgate_tpu.runtime.step_programs import _prefill_step

    return _prefill_step.lower(
        params, spec, A((B, bucket), jnp.int32), A((B,), jnp.int32),
        pool, v_pool, A((B, bucket // PAGE), jnp.int32),
        A((B,), jnp.float32), A((B,), jnp.float32), A((B,), jnp.int32),
        A((2,), jnp.uint32), use_pallas=True,
        seeds=A((B,), jnp.int32), steps=A((B,), jnp.int32),
        **({} if state is None else {"state": state,
                                     "slots": A((B,), jnp.int32)}),
    ).compile()


def _nbytes(tree):
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def _assert_no_buffer(text, rows, width, dtypes=("f32", "bf16", "s32")):
    """No array of ``rows x width`` in the compiled program, whatever
    its type: a temporary that goes by ALL (row, choice) pairs."""
    for dtype in dtypes:
        shape = f"{dtype}[{rows},{width}]"
        found = [line for line in text.splitlines() if shape in line]
        assert not found, found[0][:300]


def _assert_no_copy_of(text, *shapes, dtype="bf16"):
    """No operation of the compiled program MAKES an array of one of
    ``shapes``: a period's (or a unit's repeats') matrices sliced out of
    the stacked parameters into a buffer of their own (a fusion of a
    dynamic slice, a ``copy-done``), which the products would then read
    in place of the parameter (PR 48).  A parameter of that shape, a
    tuple's element and a bitcast move nothing."""
    views = (" parameter(", " get-tuple-element(", " bitcast(")
    for shape in shapes:
        made = f" = {dtype}[{','.join(map(str, shape))}]"
        found = [line for line in text.splitlines()
                 if made in line and not any(v in line for v in views)]
        assert not found, found[0][:300]


@pytest.fixture(scope="module")
def exaone_prompt(v5e):
    """(the K-EXAONE cut's 8,192-row prompt program compiled for the
    v5e, the spec, the bytes it holds beside its temporaries, those of
    them it must update in place)."""
    from vgate_tpu.models.hybrid import make_state

    A = _abstract(v5e)
    spec, params = _cut_and_shapes(A, *EXAONE_CUT)
    slots = 192
    state = jax.tree.map(
        lambda x: A(x.shape, x.dtype),
        jax.eval_shape(lambda: make_state(spec, slots, jnp.bfloat16, PAGE)))
    pages = 36000  # 4.7 GB of K+V: the cell's pool of the one full layer
    pool = A((spec.attn_layers, spec.num_kv_heads, pages, PAGE,
              spec.head_dim), jnp.bfloat16)
    return (_prompt_program(A, spec, params, pool, pool, state), spec,
            _nbytes((params, state, pool, pool)),
            _nbytes((state, pool, pool)))


@pytest.fixture(scope="module")
def mistral_prompt(v5e):
    """The same of the Mistral-Small-4 cut's 8,192-row prompt program."""
    A = _abstract(v5e)
    spec, params = _cut_and_shapes(A, *MISTRAL_CUT)
    pages = 65537  # 6.44 GB: the cell's latent pool
    pool = A((spec.attn_layers, spec.cache_heads, pages, PAGE,
              spec.cache_head_dim), jnp.bfloat16)
    return (_prompt_program(A, spec, params, pool, None, None), spec,
            _nbytes((params, pool)), _nbytes(pool))


def test_window_stack_prompt_program_dispatches_held_pairs_on_v5e(
        exaone_prompt):
    """The K-EXAONE cut's 8,192-row prompt program (four expert layers,
    8 choices of 128 experts, 16 held): the expert layer runs in two
    blocks of 4,096 rows and dispatches 8,192 of a block's 32,768 pairs
    at a time, so nothing in the program is sized by ALL pairs x the
    hidden width (805 MB in float32 a block), its temporaries stand far
    under the pool and the rings beside them, and the program fits a
    chip that holds them and the weights."""
    from vgate_tpu.ops import moe

    compiled, spec, held, in_place = exaone_prompt
    assert moe.block_tokens(spec) == 4096
    assert moe.capacity(spec, 4096 * 8) == 8192
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= in_place, (
        "the pool or the rings are copied")
    # 1.62 GB at PR 39, 1.18 at PR 41, 1.47 since PR 42 (PERF.md section 4)
    assert mem.temp_size_in_bytes < 1.7e9, mem.temp_size_in_bytes
    assert held + mem.temp_size_in_bytes < 15.9e9  # of the chip's 16.9 GB
    text = compiled.as_text()
    assert "moe_grouped_matmul_pallas" in text
    for pairs in (8192 * 8, 4096 * 8):
        _assert_no_buffer(text, pairs, spec.hidden_size)
        _assert_no_buffer(text, pairs, spec.expert_width)


def test_latent_prompt_program_dispatches_held_pairs_on_v5e(mistral_prompt):
    """The Mistral-Small-4 cut's 8,192-row prompt program (4 choices of
    128 experts, 32 held): ONE block, 16,384 of its 32,768 pairs at a
    time."""
    from vgate_tpu.ops import moe

    compiled, spec, held, pool_bytes = mistral_prompt
    assert moe.block_tokens(spec) == 8192
    assert moe.capacity(spec, 8192 * 4) == 16384
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes, "the pool is copied"
    assert mem.temp_size_in_bytes < pool_bytes // 4
    assert held + mem.temp_size_in_bytes < 15.9e9
    text = compiled.as_text()
    _assert_no_buffer(text, 8192 * 4, spec.expert_width)
    # bf16[32768, 4096] is the embedding table
    _assert_no_buffer(text, 8192 * 4, spec.hidden_size, ("f32", "s32"))


# ---- learned sparse attention: the GLM-5.2 cut as its cell serves it

GLM_CUT = ("zai-org/GLM-5.2", dict(
    name="glm-cut", num_layers=5, first_layer=2, num_experts=16,
    vocab_size=19360, eos_token_id=19359, bos_token_id=19358))
GLM_SLOTS, GLM_CTX = 48, 16384


def _glm_cut(A):
    """(spec, abstract parameters, the latent pool, the index keys'
    array) at the cell's size: 48 slots x 16,384 tokens of pages."""
    spec, params = _cut_and_shapes(A, *GLM_CUT)
    pages = GLM_SLOTS * GLM_CTX // PAGE + 1
    # the latent rows by pairs of tokens (runtime/kv_cache.py)
    pool = A((spec.attn_layers, 1, pages, PAGE // 2, 2,
              spec.cache_head_dim), jnp.bfloat16)
    keys = A((spec.index_layers, 1, pages, PAGE, spec.index_head_dim),
             jnp.bfloat16)
    assert pool.shape[0] == 5 and pool.shape[-1] == 640
    assert keys.shape[0] == 2 and keys.shape[-1] == 128
    return spec, params, pool, keys


def test_selection_decode_chunk_compiles_on_v5e(v5e):
    """The decode chunk of the cut: both arrays of the pool aliased input
    to output and never re-laid, the scoring pass and the attention that
    fetches its picked rows in it under their own names and NO gather of
    rows: no [48 x 2,048, 640] temporary, nothing under the scope the
    gather had, no dense latent kernel (contexts of at most 2,048 tokens
    go through the same kernel)."""
    from vgate_tpu.runtime.step_programs import _decode_chunk

    A = _abstract(v5e)
    spec, params, pool, keys = _glm_cut(A)
    B = GLM_SLOTS
    compiled = _decode_chunk.lower(
        params, spec, A((B,), jnp.int32), A((B,), jnp.int32), pool, keys,
        A((B, GLM_CTX // PAGE), jnp.int32), A((B,), jnp.bool_),
        A((B,), jnp.float32), A((B,), jnp.float32), A((B,), jnp.int32),
        A((2,), jnp.uint32), A((), jnp.uint32),
        num_steps=8, use_pallas=True, max_position=GLM_CTX - 1,
        seeds=A((B,), jnp.int32), steps=A((B,), jnp.int32),
        all_greedy=True, guard=True,
    ).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= _nbytes((pool, keys)), (
        "a pool is copied")
    # 0.46 GB: the scores of one picking layer, the weights' re-laid
    # copies (0.61 GB with the gathered rows of one layer, PR 40)
    assert mem.temp_size_in_bytes < 0.55e9, mem.temp_size_in_bytes
    text = compiled.as_text()
    for name in ("dsa_index_scores_pallas", "dsa_decode_attention_pallas"):
        assert name in text, name
    assert "mla_decode_attention_pallas" not in text
    assert "dsa_gather" not in text
    _assert_no_buffer(text, B * spec.index_topk, spec.cache_head_dim)
    _assert_no_buffer(text, f"{B},{spec.index_topk}", spec.cache_head_dim)
    # no gather of page ids either (ops/dsa.py order_picks)
    assert not [l for l in text.splitlines()
                if "mla_attn" in l and "take_along_axis" in l]
    # neither array re-laid with another minor dimension
    for shape, minor in (("bf16[5,1,24577,16,2,640]", "{5,4,3,2,"),
                         ("bf16[2,1,24577,32,128]", "{4,3,2,")):
        layouts = {line.split(shape, 1)[1].split("}", 1)[0]
                   for line in text.splitlines() if shape + "{" in line}
        assert layouts and all(l.startswith(minor) for l in layouts), layouts


def _compile_fetching_kernel(A, pool, index, pair):
    """One descriptor a pick, ``pair`` token rows from ``pool`` at
    ``index(pool, i)`` into a place of VMEM scratch: the least of
    ``_fetch_decode_kernel``."""
    import functools

    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    K, W = 16, pool.shape[-1]

    def kernel(at_ref, pool_ref, out_ref, buf, sem):
        copies = [pltpu.make_async_copy(
            index(pool_ref, at_ref[i]), buf.at[i], sem.at[0])
            for i in range(K)]
        for cp in copies:
            cp.start()
        for cp in copies:
            cp.wait()
        out_ref[...] = buf[...]

    call = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=[pltpu.VMEM((K, pair, W), pool.dtype),
                            pltpu.SemaphoreType.DMA((1,))]),
        out_shape=jax.ShapeDtypeStruct((K, pair, W), pool.dtype))
    return jax.jit(call).lower(A((K,), jnp.int32), pool).compile()


def test_a_pair_of_token_rows_is_a_descriptor_on_v5e(v5e):
    """What the pool by pairs stands on: Mosaic takes a PAIR of bf16
    token rows as a trailing block under a leading index, and XLA holds
    such an array without padding the 2 to a tile."""
    A = _abstract(v5e)
    pool = A((1 << 16, 2, 640), jnp.bfloat16)
    compiled = _compile_fetching_kernel(
        A, pool, lambda ref, n: ref.at[n], pair=2)
    held = compiled.memory_analysis().argument_size_in_bytes
    assert pool.size * 2 <= held < pool.size * 2 + 4096


@pytest.mark.xfail(
    strict=True,
    raises=MosaicRefusal,
    reason="Mosaic: 'Slice shape along dimension 1 must be aligned to "
    "tiling (8), but is 1' — ONE token row of a page [P, 32, W] as a "
    "descriptor's source: why a spec that picks holds its latent rows by "
    "pairs (ops/kv_quant.py by_pairs).  A toolchain that takes this can "
    "fetch half the bytes",
)
def test_one_token_row_of_a_page_is_a_descriptor_on_v5e(v5e):
    from jax.experimental import pallas as pl

    A = _abstract(v5e)
    pool = A((2048, PAGE, 640), jnp.bfloat16)
    _compile_expecting(
        "aligned to tiling (8), but is 1", _compile_fetching_kernel, A,
        pool, lambda ref, n: ref.at[n // PAGE, pl.ds(n % PAGE, 1)], pair=1)


def test_the_fetching_decode_kernel_compiles_at_the_cells_widths_on_v5e(v5e):
    """``dsa_decode_attention_pallas`` for the v5e at the cell's widths:
    48 slots, 2,048 picks, 64 heads over rows of 640 lanes, 5 layers of
    24,577 pages by pairs; nothing beside its operands."""
    from vgate_tpu.ops.pallas.dsa import dsa_decode_attention_pallas

    A = _abstract(v5e)
    spec, _, pool, _ = _glm_cut(A)
    B, k = GLM_SLOTS, spec.index_topk
    assert (spec.num_heads, k, spec.kv_lora_rank) == (64, 2048, 512)
    compiled = dsa_decode_attention_pallas.lower(
        A((B, spec.num_heads, spec.cache_head_dim), jnp.bfloat16), pool,
        A((B, k), jnp.int32), A((B,), jnp.int32), A((), jnp.int32),
        v_width=spec.kv_lora_rank, scale=spec.mla_softmax_scale,
    ).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 4 << 20
    assert "dsa_decode_attention_pallas" in compiled.as_text()


@pytest.fixture(scope="module")
def glm_prompt(v5e):
    """The same of the GLM-5.2 cut's 16,384-row prompt program."""
    A = _abstract(v5e)
    spec, params, pool, keys = _glm_cut(A)
    return (_prompt_program(A, spec, params, pool, keys, None,
                            bucket=GLM_CTX), spec,
            _nbytes((params, pool, keys)), _nbytes((pool, keys)))


def test_selection_prompt_program_fits_beside_the_pool_on_v5e(glm_prompt):
    """The 16,384-row prompt program of the cut: the scoring kernel, the
    flash kernel under a mask and the page writer of the pool by pairs
    in it, both pool arrays aliased (no scatter re-lays the pool), no
    [16,384, 16,384] float32 scores and no [16,384, 12,288] activation
    of the dense layer in the HLO, and the temporaries small enough
    beside 7.76 GB of weights and 5.44 GB of pages."""
    compiled, spec, held, pools = glm_prompt
    mem = compiled.memory_analysis()
    assert 13.1e9 < held < 13.3e9
    assert mem.alias_size_in_bytes >= pools, "a pool is copied"
    # 2.48 GB with groups of 8 heads, 2.44 since PR 42 (PERF.md section 4)
    assert mem.temp_size_in_bytes < 2.7e9, mem.temp_size_in_bytes
    assert held + mem.temp_size_in_bytes < 15.9e9  # of the chip's 16.9 GB
    text = compiled.as_text()
    for name in ("dsa_index_scores_pallas", "dsa_prefill_attention_pallas",
                 "dsa_write_pages_pallas", "moe_grouped_matmul_pallas"):
        assert name in text, name
    _assert_no_buffer(text, GLM_CTX, GLM_CTX, ("f32", "bf16", "s32", "u32"))
    _assert_no_buffer(text, GLM_CTX, spec.intermediate_size)
    _assert_no_buffer(text, f"1,{GLM_CTX}", spec.intermediate_size)
    # the selection itself stands once, as bytes
    assert f"s8[1,{GLM_CTX},{GLM_CTX}]" in text


# the prompt attention launch at the three long cells' shapes (B, rows,
# H, KV, head width, whether under a selection, its name in a trace,
# heads a program): GLM's is one of its eight groups of eight heads
PROMPT_LAUNCHES = {
    "glm": (1, GLM_CTX, 8, 8, 256, True, "dsa_prefill_attention_pallas", 4),
    "keye": (1, 16384, 32, 4, 128, True, "dsa_prefill_attention_pallas", 8),
    "mistral": (1, 8192, 32, 32, 128, False, None, 1),
}


@pytest.mark.parametrize("cell", list(PROMPT_LAUNCHES))
def test_prompt_attention_launch_fits_its_vmem_on_v5e(v5e, cell):
    """The flash prompt kernel in 1,024-row blocks at the cell's shape:
    two bodies a tile (an interior one without position tests) and,
    under a selection, the int8 tile as a float32 bias in VMEM for the
    heads of a program, as many as ``head_block`` reckons: Mosaic takes
    the launch under its ``vmem_limit_bytes``, under the name the
    metrics match."""
    from vgate_tpu.ops.pallas import flash_prefill

    B, S, H, KV, hd, masked, name, heads = PROMPT_LAUNCHES[cell]
    A = _abstract(v5e)
    if masked:
        assert flash_prefill.head_block(H, H // KV, 1024, 1024, hd, 2) == (
            heads)
    args = [A((B, S, H, hd), jnp.bfloat16), A((B, S, KV, hd), jnp.bfloat16),
            A((B, S, KV, hd), jnp.bfloat16), A((B,), jnp.int32)]
    kw = dict(block_q=1024, block_k=1024, skip_padding=True, name=name)
    if masked:
        launch = jax.jit(
            lambda q, k, v, lens, mask:
            flash_prefill.flash_prefill_attention_pallas(
                q, k, v, lens, mask=mask, **kw))
        args.append(A((B, S, S), jnp.int8))
    else:
        launch = jax.jit(
            lambda q, k, v, lens:
            flash_prefill.flash_prefill_attention_pallas(
                q, k, v, lens, **kw))
    text = launch.lower(*args).compile().as_text()
    assert (name or "flash_prefill_attention_pallas") in text


# temporary bytes of the parent's (PR 53, commit 1dddb33) prompt programs
# by the same compile: the bias of a selection's tile is VMEM scratch of
# the launch, no array of the program's
PARENT_53_TEMP_BYTES = {"glm": 2_439_488_512, "mistral": 814_459_904,
                        "keye": 822_795_776}


@pytest.mark.parametrize("cell, launch", [
    ("glm", "dsa_prefill_attention_pallas"),
    ("mistral", "flash_prefill_attention_pallas"),
])
def test_prompt_programs_keep_the_parents_temporaries_on_v5e(
        cell, launch, request):
    """The GLM and mistral cuts' prompt programs with the kernel of two
    bodies a tile: the launch under the name the metrics match, and
    temporaries within 16 MB of the parent's (the Keye cut's:
    ``test_kv_selection_prompt_program_fits_beside_the_pool_on_v5e``)."""
    compiled, _, _, _ = request.getfixturevalue(f"{cell}_prompt")
    assert launch in compiled.as_text()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= PARENT_53_TEMP_BYTES[cell] + (16 << 20), temp


def _counted_loops(text, scope):
    """The ``while`` of the compiled program traced under ``scope``
    whose condition holds no constant: its trips are an operand."""
    import re

    found = 0
    for line in text.splitlines():
        at = re.search(r" while\(.*condition=%([\w.\-]+)", line)
        if not at or f'{scope}/while"' not in line:
            continue
        start = text.index(f"\n%{at.group(1)} (")
        body = text[start:text.index("\n}", start)]
        found += "compare(" in body and " constant(" not in body
    return found


# temporary bytes of the parent's (PR 41, commit 62289a6) prompt programs
# by the same compile: what the whole bucket at once takes
PARENT_TEMP_BYTES = {"exaone": 1_182_179_328, "mistral": 813_282_304,
                     "glm": 2_481_588_736}


@pytest.mark.parametrize("cell, scopes", [
    ("exaone", ("swa_attn", "full_attn", "dense_mlp", "moe_route",
                "shared_expert")),
    ("mistral", ("mla_attn", "moe_route", "shared_expert")),
    ("glm", ("dsa_prompt", "dense_mlp", "moe_route", "shared_expert")),
])
def test_long_prompt_programs_loop_over_their_row_blocks_on_v5e(
        cell, scopes, request):
    """The three long cells' prompt programs (8,192 rows; 16,384 the
    GLM cut's) hold, around the projections of every kind of sub-block,
    a ``while`` whose trips are an operand (the blocks of 1,024 rows the
    prompt reaches, models/hybrid.py ``_by_row_blocks``), and count
    their temporaries against the parent's: as many in the two latent
    stacks (0.814 against 0.813 GB, 2.44 against 2.48), 0.29 GB more in
    the window stack, where a layer's matrices, operands of a nested
    loop, stand as copies and the attention's result is re-laid by rows
    ahead of its projection (PERF.md section 7)."""
    compiled, _, held, _ = request.getfixturevalue(f"{cell}_prompt")
    text = compiled.as_text()
    for scope in scopes:
        assert _counted_loops(text, scope), scope
    temp = compiled.memory_analysis().temp_size_in_bytes
    parent = PARENT_TEMP_BYTES[cell]
    print(cell, "prompt program temporaries", temp)
    assert temp <= parent + (320 << 20), (
        f"{cell}: {temp} temporary bytes against the parent's {parent}")
    assert held + temp < 15.9e9


# temporary bytes of the parent's (PR 45, commit 32a8c2e) dense prompt
# programs at [8, 2048] by the same compile: the whole bucket at once
DENSE_PARENT_TEMP_BYTES = {"1.5b": 664_240_640, "7b-l14": 1_410_883_072}


@pytest.mark.parametrize("cell, preset, changes", [
    ("1.5b", "Qwen/Qwen2.5-1.5B-Instruct", {}),
    ("7b-l14", "Qwen/Qwen2.5-7B-Instruct", {"num_layers": 14}),
])
def test_dense_prompt_program_packs_its_groups_rows_on_v5e(
        v5e, cell, preset, changes):
    """The two dense configurations' prompt program at ``[8, 2048]``
    (models/decoder.py ``_packed_prompt_pass``) builds for the v5e: the
    flash kernel in it, a ``while`` whose trips are an operand around
    the layer's two position-wise halves, the pools aliased input to
    output, and temporaries no more than the parent's whole-bucket pass
    took by the same compile (PERF.md section 6, PR 46)."""
    A = _abstract(v5e)
    spec, params = _cut_and_shapes(A, preset, changes)
    pool = A((spec.num_layers, spec.num_kv_heads, 2049, PAGE,
              spec.head_dim), jnp.bfloat16)
    compiled = _prompt_program(
        A, spec, params, pool, pool, None, bucket=2048, B=8)
    text = compiled.as_text()
    assert "flash_prefill_attention_pallas" in text
    # the front half's loop, and with the back half's two in all
    assert _counted_loops(text, "qkv") and _counted_loops(text, "") >= 2
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= _nbytes((pool, pool))
    parent = DENSE_PARENT_TEMP_BYTES[cell]
    assert mem.temp_size_in_bytes <= parent, (
        f"{cell}: {mem.temp_size_in_bytes} temporary bytes against the "
        f"parent's {parent}")


# the EvaByte cut as its cell serves it: 8 of 32 layers, 20 slots of
# 16,384 bytes of context
EVA_CUT = ("EvaByte/EvaByte", {"num_layers": 8})
EVA_SLOTS, EVA_CTX = 20, 16384


def _eva_cut(A):
    """(spec, abstract parameters, the pool's K (= V) array with the
    slots' windows behind the allocator's pages, the state that names
    them)."""
    from vgate_tpu.models.hybrid import make_state

    spec, params = _cut_and_shapes(A, *EVA_CUT)
    pages = EVA_SLOTS * (EVA_CTX // spec.eva_chunk // PAGE) + 1
    windows = EVA_SLOTS * spec.eva_window // PAGE
    pool = A((spec.attn_layers, spec.num_kv_heads, pages + windows, PAGE,
              spec.head_dim), jnp.bfloat16)
    assert pool.shape == (8, 32, 641 + 1280, 32, 128)
    state = jax.tree.map(lambda x: A(x.shape, x.dtype), jax.eval_shape(
        lambda: make_state(spec, EVA_SLOTS, jnp.bfloat16, PAGE, pages)))
    return spec, params, pool, state


# the decode chunk's temporaries at the parent of PR 52, which rewrote
# the open chunk's summary row at every step
EVA_PARENT_DECODE_TEMP_BYTES = 806_518_272


@pytest.fixture(scope="module")
def eva_decode_chunk(v5e):
    """(the compiled decode chunk of the cut at the published widths,
    its pool array)."""
    from vgate_tpu.runtime.step_programs import _decode_chunk

    A = _abstract(v5e)
    spec, params, pool, state = _eva_cut(A)
    B = EVA_SLOTS
    compiled = _decode_chunk.lower(
        params, spec, A((B,), jnp.int32), A((B,), jnp.int32), pool, pool,
        A((B, EVA_CTX // spec.eva_chunk // PAGE), jnp.int32),
        A((B,), jnp.bool_),
        A((B,), jnp.float32), A((B,), jnp.float32), A((B,), jnp.int32),
        A((2,), jnp.uint32), A((), jnp.uint32),
        num_steps=8, use_pallas=True, max_position=EVA_CTX - 1,
        seeds=A((B,), jnp.int32), steps=A((B,), jnp.int32),
        all_greedy=True, guard=True, state=state,
    ).compile()
    return compiled, pool


def test_eva_decode_chunk_compiles_on_v5e(eva_decode_chunk):
    """The decode chunk of the cut at the published widths: both pool
    arrays (summary pages and the slots' windows in one) aliased input
    to output and never re-laid, though they now pass through the
    closers' loop in every EVA layer; the paged decode kernel launched
    under its own name over the step's ONE sequence of rows, and its
    temporaries (0.81 GB when written: the weights' re-laid copies)
    far under the 8.06 GB of cache."""
    compiled, pool = eva_decode_chunk
    mem = compiled.memory_analysis()
    assert _nbytes((pool, pool)) == 2 * 8 * 32 * 1921 * 32 * 128 * 2
    assert mem.alias_size_in_bytes >= _nbytes((pool, pool)), (
        "a pool is copied")
    assert mem.temp_size_in_bytes < 1.0e9, mem.temp_size_in_bytes
    text = compiled.as_text()
    assert "paged_decode_attention_pallas" in text
    assert "{4,1,3,2,0" not in text, "XLA re-laid the pool out"


@pytest.mark.parametrize("what", ["no_rewrite_a_step", "temporaries"])
def test_eva_decode_chunk_pools_chunks_where_a_window_closes(
        eva_decode_chunk, what):
    """A summary row is written when its window closes (ops/eva.py
    ``decode_close``, PR 52).  ``no_rewrite_a_step``: no gather of the
    open chunk's rows of every slot (``[20, 16, 32, 128]``, the parent's
    eight a step) is left, every operation of the pooling lies inside
    the closers' loop, and what that loop reads is one contiguous slice
    of a pool, the 16 window pages under a page of summary rows.
    ``temporaries``: the loop costs no more than the rewrite did,
    806,487,040 bytes against the parent's 806,518,272 (806,163,968 with
    neither; 808,321,024 with a whole window a trip, 806,744,576 with
    the loop's page ids computed inside it)."""
    compiled, _ = eva_decode_chunk
    if what == "temporaries":
        mem = compiled.memory_analysis()
        assert mem.temp_size_in_bytes <= EVA_PARENT_DECODE_TEMP_BYTES, (
            mem.temp_size_in_bytes)
        return
    text = compiled.as_text()
    _assert_no_buffer(text, "20,16", "32,128")
    lines = [l for l in text.splitlines() if "eva_summarize" in l]
    assert lines and all("eva_summarize/while" in l for l in lines)
    assert not any(" gather(" in l for l in lines)
    assert any(" dynamic-slice(" in l and "bf16[1,32,16,32,128]" in l
               for l in lines)


def test_eva_prompt_program_fits_beside_the_cache_on_v5e(v5e):
    """The 16,384-row prompt program of the cut: eight windows through
    the flash kernel under the EVA layer's name, each behind every
    chunk's summary; the pools aliased; no [16,384, 16,384] scores and no
    [16,384, 11,008] activation of the feed-forward in the HLO (the
    row-block loop); temporaries (1.91 GB when written) that fit beside
    3.26 GB of weights and 8.06 GB of cache."""
    A = _abstract(v5e)
    spec, params, pool, state = _eva_cut(A)
    compiled = _prompt_program(A, spec, params, pool, pool, state,
                               bucket=EVA_CTX)
    mem = compiled.memory_analysis()
    held = _nbytes((params, pool, pool))
    assert 11.2e9 < held < 11.4e9
    assert mem.alias_size_in_bytes >= _nbytes((pool, pool))
    assert mem.temp_size_in_bytes < 2.1e9, mem.temp_size_in_bytes
    assert held + mem.temp_size_in_bytes < 0.86 * 16.9e9
    text = compiled.as_text()
    assert "eva_prefill_attention_pallas" in text
    assert "{4,1,3,2,0" not in text, "XLA re-laid the pool out"
    _assert_no_buffer(text, EVA_CTX, EVA_CTX)
    _assert_no_buffer(text, EVA_CTX, spec.intermediate_size)
    _assert_no_buffer(text, f"1,{EVA_CTX}", spec.intermediate_size)


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

    A = _abstract(v5e)
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

    A = _abstract(v5e)
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

    A = _abstract(v5e)
    H, KV, hd = 32, 8, 64
    flash_prefill_attention_pallas.lower(
        A((B, S, H, hd), jnp.bfloat16), A((B, S, KV, hd), jnp.bfloat16),
        A((B, S, KV, hd), jnp.bfloat16), A((B,), jnp.int32),
        skip_padding=True,
    ).compile()


@pytest.fixture(scope="module")
def lfm2_cut(v5e):
    from vgate_tpu.models.hybrid import make_state

    A = _abstract(v5e)
    spec, params = _cut_and_shapes(A, *LFM2_CUT)
    spec = spec.pack_kv_heads()
    state = jax.tree.map(
        lambda x: A(x.shape, x.dtype),
        jax.eval_shape(lambda: make_state(spec, 256, jnp.bfloat16, PAGE)))
    assert set(state) == {"conv"}, "a tail alone: no tile"
    assert _nbytes(state) == 256 * 245760
    assert abs(_nbytes(params) - 7.52e9) < 0.02e9
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
    assert mem.alias_size_in_bytes >= 2 * _nbytes(pool) + _nbytes(state), (
        "the pool or the tails are copied")
    print("lfm2 decode chunk temporaries", mem.temp_size_in_bytes)
    assert mem.temp_size_in_bytes < LFM2_PROGRAM_ROOM
    text = compiled.as_text()
    assert "paged_decode_attention_pallas" in text
    assert "moe_grouped_matmul_pallas" in text
    # the greedy chunk's 67 MB of logits stay on the chip here too
    _assert_no_logits_array(text, B, spec.vocab_size)
    # no copy of a period's mixers' matrices (the walker's scans carry
    # indices): before PR 48 these four a period, nine periods a step,
    # were 2.28 ms of the cell's 23.6 ms step
    _assert_no_copy_of(text, (3, 2048, 6144), (2, 1, 2048, 6144),
                       (3, 2048, 2048), (2, 1, 2048, 2048))


def test_qwen3_next_decode_chunk_reads_its_matrices_in_place_on_v5e(v5e):
    """The Qwen3-Next cut as the cell serves it (8 layers = two periods
    of three Gated DeltaNet layers and one attention layer, 128 experts
    held, vocabulary 37,984, 256 slots of 2,048 tokens): the decode
    chunk holds no copy of a period's mixers' matrices (ten of them,
    ~ 450 MB a step, before PR 48), and its temporaries are the
    activations' (234.9 MB when this was written; the parent's 505.7)."""
    from vgate_tpu.models.hybrid import make_state
    from vgate_tpu.runtime.step_programs import _decode_chunk

    A = _abstract(v5e)
    spec, params = _cut_and_shapes(
        A, "Qwen/Qwen3-Next-80B-A3B-Instruct", dict(
            name="qwen3-next-cut", num_layers=8, num_experts=128,
            vocab_size=37984, first_expert=0, eos_token_id=37983,
            bos_token_id=37982, extra_stop_ids=()))
    B, ctx = 256, 2048
    state = jax.tree.map(
        lambda x: A(x.shape, x.dtype),
        jax.eval_shape(lambda: make_state(spec, B, jnp.bfloat16)))
    pool = A((spec.attn_layers, spec.num_kv_heads, 16385, PAGE,
              spec.head_dim), jnp.bfloat16)
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
    assert mem.alias_size_in_bytes >= 2 * _nbytes(pool) + _nbytes(state), (
        "the pool or the state is copied")
    print("qwen3-next decode chunk temporaries", mem.temp_size_in_bytes)
    assert mem.temp_size_in_bytes < 300e6
    text = compiled.as_text()
    assert "gated_delta_step_pallas" in text
    assert "moe_grouped_matmul_pallas" in text
    # the cut's untied head is `[2048, 37984]`, 296.75 lane groups wide:
    # XLA would re-lay it for the fused head's kernel, 155 MB a step, so
    # the shape rule (ops/pallas/greedy_head.py) keeps the three passes
    assert "greedy_head" not in text
    _assert_no_copy_of(text, (3, 1, 2048, 12288), (3, 1, 4096, 2048),
                       (3, 1, 2048, 512), (3, 1, 512, 2048))


@pytest.mark.parametrize("B, bucket", [(8, 128), (1, 2048), (8, 2048)],
                         ids=["wave-8x128", "1x2048", "wave-8x2048"])
def test_lfm2_prompt_program_fits_beside_the_cache_on_v5e(
        lfm2_cut, B, bucket):
    """The cell's prompt programs at their two ends (a wave of 8 in the
    128 bucket, what the traffic sends; one row in the 2,048 bucket,
    what the reference's longest prompt and a resumed request take):
    their temporaries fit what ``hbm_utilization`` leaves."""
    A, spec, params, pool, state = lfm2_cut
    compiled = _prompt_program(A, spec, params, pool, pool, state,
                               bucket=bucket, B=B)
    mem = compiled.memory_analysis()
    print("lfm2 prompt program", B, bucket, "temporaries",
          mem.temp_size_in_bytes)
    assert mem.alias_size_in_bytes >= 2 * _nbytes(pool) + _nbytes(state)
    assert mem.temp_size_in_bytes < LFM2_PROGRAM_ROOM
    assert "flash_prefill_attention_pallas" in compiled.as_text()


# ---- Granite 4.0-H Micro, whole: the state (6.1 GB) sets the batch

# what the configuration's hbm_utilization 0.9 leaves the programs: 0.9
# of the chip's 16.9 GB less weights 6.39, state 6.11 and pages 1.34 GB
GRANITE_PROGRAM_ROOM = int(0.9 * 16.9e9 - 13.85e9)


@pytest.fixture(scope="module")
def granite(v5e):
    from vgate_tpu.models.hybrid import make_state

    A = _abstract(v5e)
    spec, params = _cut_and_shapes(
        A, "ibm-granite/granite-4.0-h-micro", {})
    spec = spec.pack_kv_heads()
    state = jax.tree.map(
        lambda x: A(x.shape, x.dtype),
        jax.eval_shape(lambda: make_state(spec, 80, jnp.bfloat16, PAGE)))
    assert _nbytes(state) == 80 * 76_437_504
    # (64 zero columns behind dt a Mamba-2 layer: hybrid.mamba_proj_pad;
    # A_log, D and dt_bias are float32: 3 x 36 x 64 values of 4 B)
    assert _nbytes(params) == (
        2 * (spec.num_params + 36 * 2048 * 64) + 2 * 3 * 36 * 64)
    pool = A((spec.attn_layers, spec.cache_heads, 80 * 64 + 1, PAGE,
              spec.cache_head_dim), jnp.bfloat16)
    assert pool.shape == (4, 4, 5121, PAGE, 128)
    return A, spec, params, pool, state


def test_granite_decode_chunk_updates_the_state_in_place_on_v5e(granite):
    """The whole published model as the cell serves it (80 slots of
    2,048 tokens): the decode chunk compiles for the v5e with the state
    (6.1 GB, more than the pages) and the packed pool aliased input to
    output, NO second array of the state's tiles among its temporaries
    (weights 6.4 + state 6.1 + a copy 6.0 would pass the chip), the
    step kernel at the rule's block, and the scaled head on the fused
    pass: no ``[80, 100352]`` float32 logits."""
    from vgate_tpu.runtime.step_programs import _decode_chunk

    A, spec, params, pool, state = granite
    B, ctx = 80, 2048
    compiled = _decode_chunk.lower(
        params, spec, A((B,), jnp.int32), A((B,), jnp.int32), pool, pool,
        A((B, ctx // PAGE), jnp.int32), A((B,), jnp.bool_),
        A((B,), jnp.float32), A((B,), jnp.float32), A((B,), jnp.int32),
        A((2,), jnp.uint32), A((), jnp.uint32),
        num_steps=8, use_pallas=True, max_position=ctx - 1,
        seeds=A((B,), jnp.int32), steps=A((B,), jnp.int32),
        all_greedy=True, guard=True, state=state,
        bias_ids=A((B, 16), jnp.int32), bias_vals=A((B, 16), jnp.float32),
        min_toks=A((B,), jnp.int32), stop_id_mat=A((B, 2), jnp.int32),
    ).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * _nbytes(pool) + _nbytes(state), (
        "the pool or the state is copied")
    print("granite decode chunk temporaries", mem.temp_size_in_bytes)
    assert mem.temp_size_in_bytes < GRANITE_PROGRAM_ROOM
    assert mem.temp_size_in_bytes < state["S"].size * 4 // 16
    text = compiled.as_text()
    assert "ssd_step_pallas" in text
    assert "paged_decode_attention_pallas" in text
    assert "greedy_head" in text
    _assert_no_logits_array(text, B, spec.vocab_size)
    # no copy of a period's matrices either (the walker's scans carry
    # indices): the nine Mamba-2 layers' in_proj, the SwiGLU's three
    _assert_no_copy_of(text, (9, 2048, 8512), (5, 2048, 8512),
                       (4, 2048, 8512), (9, 2048, 8192), (5, 2048, 8192),
                       (4, 2048, 8192))


@pytest.mark.parametrize("B, bucket", [(8, 128), (1, 2048), (8, 2048)],
                         ids=["wave-8x128", "1x2048", "wave-8x2048"])
def test_granite_prompt_program_fits_beside_the_state_on_v5e(
        granite, B, bucket):
    """The cell's prompt programs at their ends (a wave of 8 in the 128
    bucket, what fresh requests send; the 2,048 bucket, what a resumed
    request takes, one row and a wave of 8): the state and the pool are
    updated in place and the temporaries (0.11, 0.22 and 1.27 GB: the
    last the chunk-wise recurrence's float32 arrays) fit what
    ``hbm_utilization`` leaves."""
    A, spec, params, pool, state = granite
    compiled = _prompt_program(A, spec, params, pool, pool, state,
                               bucket=bucket, B=B)
    mem = compiled.memory_analysis()
    print("granite prompt program", B, bucket, "temporaries",
          mem.temp_size_in_bytes)
    assert mem.alias_size_in_bytes >= 2 * _nbytes(pool) + _nbytes(state), (
        "the pool or the state is copied")
    assert mem.temp_size_in_bytes < GRANITE_PROGRAM_ROOM
    assert "flash_prefill_attention_pallas" in compiled.as_text()


# ---- GQA attention under a selection: the Keye-VL-2.0 cut as its cell
# serves it (16 slots x 16,384 tokens; a token's K over its V in one
# array, its index key of 64 in a row of 128 lanes in the other)

KEYE_CUT = ("Kwai-Keye/Keye-VL-2.0-30B-A3B", dict(
    name="keye-cut", num_layers=12, num_experts=32, vocab_size=37984,
    eos_token_id=37983, bos_token_id=37982))
KEYE_SLOTS, KEYE_CTX = 16, 16384


def _keye_cut(A):
    """(spec, abstract parameters, the pool of K over V, the index keys'
    array) at the cell's size."""
    spec, params = _cut_and_shapes(A, *KEYE_CUT)
    pages = KEYE_SLOTS * KEYE_CTX // PAGE + 1
    pool = A((spec.attn_layers, 1, pages, PAGE, 2, spec.cache_head_dim),
             jnp.bfloat16)
    keys = A((spec.index_layers, 1, pages, PAGE, spec.index_key_lanes),
             jnp.bfloat16)
    assert pool.shape == (12, 1, 8193, 32, 2, 512)
    assert keys.shape == (12, 1, 8193, 32, 128)
    return spec, params, pool, keys


def test_the_kv_selection_kernels_compile_at_the_cells_shapes_on_v5e(v5e):
    """The three launches that are new at this cell's shapes: the
    scoring pass at 16 heads x 64 against keys held in 128 lanes (a
    decode step's over 512 pages a slot, a prompt's 1,024-row block
    against 16,384 keys), the attention that fetches a picked token's K
    over V (32 heads in 4 groups over rows of 512 lanes, 2,048 picks a
    slot), and the page writer over the pool of pairs; nothing beside
    their operands."""
    from vgate_tpu.ops.pallas.dsa import (
        dsa_index_scores_pallas, dsa_kv_decode_attention_pallas,
        dsa_prompt_scores_pallas, dsa_write_pages_pallas)

    A = _abstract(v5e)
    spec, _, pool, keys = _keye_cut(A)
    B, k, Hi, lanes = KEYE_SLOTS, spec.index_topk, 16, 128
    assert (spec.index_n_heads, spec.index_head_dim) == (Hi, 64)
    scores = dsa_index_scores_pallas.lower(
        A((B, Hi, lanes), jnp.bfloat16), A((B, Hi), jnp.float32), keys,
        A((B, KEYE_CTX // PAGE), jnp.int32), A((B,), jnp.int32),
        A((), jnp.int32)).compile()
    assert "dsa_index_scores_pallas" in scores.as_text()
    block = dsa_prompt_scores_pallas.lower(
        A((1024, Hi, lanes), jnp.bfloat16), A((1024, Hi), jnp.float32),
        A((KEYE_CTX, lanes), jnp.bfloat16), A((), jnp.int32)).compile()
    assert block.memory_analysis().temp_size_in_bytes < 4 << 20
    attend = dsa_kv_decode_attention_pallas.lower(
        A((B, spec.num_heads, spec.head_dim), jnp.bfloat16), pool,
        A((B, k), jnp.int32), A((B,), jnp.int32), A((), jnp.int32),
        scale=spec.head_dim ** -0.5).compile()
    # the row-wide query and result, [16, 32, 512] each, and no more
    assert attend.memory_analysis().temp_size_in_bytes < 4 << 20
    assert "dsa_decode_attention_pallas" in attend.as_text()
    write = dsa_write_pages_pallas.lower(
        pool, A((1, KEYE_CTX // PAGE), jnp.int32),
        A((1, KEYE_CTX // PAGE, PAGE, 2, 512), jnp.bfloat16),
        A((), jnp.int32)).compile()
    assert write.memory_analysis().alias_size_in_bytes >= _nbytes(pool)


def test_engine_refuses_a_kv_row_that_is_no_whole_lane_tile():
    """A token's K (or V) row goes HBM -> VMEM as a descriptor's trailing
    block: whole 128-lane tiles.  The published 4 x 128 passes; the tiny
    preset's 2 x 16 is refused by name under ``tpu.use_pallas``."""
    from vgate_tpu.runtime.engine_core import refuse_unbuildable_kernels

    refuse_unbuildable_kernels(
        spec_for_model_id(KEYE_CUT[0]), kv_quant=False)
    with pytest.raises(ValueError, match=r"aligned to tiling \(128\)"):
        refuse_unbuildable_kernels(
            spec_for_model_id("tiny-keye-dsa"), kv_quant=False)


def test_kv_selection_decode_chunk_compiles_on_v5e(v5e):
    """The decode chunk of the cut: both arrays aliased input to output
    and never re-laid, twelve layers' scoring pass and fetching
    attention under their own names, NO gather of rows and no dense
    paged kernel (a context of at most 2,048 tokens goes through the
    same kernel).  Temporaries 0.23 GB (the configuration's
    ``server.why``)."""
    from vgate_tpu.runtime.step_programs import _decode_chunk

    A = _abstract(v5e)
    spec, params, pool, keys = _keye_cut(A)
    B = KEYE_SLOTS
    compiled = _decode_chunk.lower(
        params, spec, A((B,), jnp.int32), A((B,), jnp.int32), pool, keys,
        A((B, KEYE_CTX // PAGE), jnp.int32), A((B,), jnp.bool_),
        A((B,), jnp.float32), A((B,), jnp.float32), A((B,), jnp.int32),
        A((2,), jnp.uint32), A((), jnp.uint32),
        num_steps=8, use_pallas=True, max_position=KEYE_CTX - 1,
        seeds=A((B,), jnp.int32), steps=A((B,), jnp.int32),
        all_greedy=True, guard=True,
    ).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= _nbytes((pool, keys)), (
        "a pool is copied")
    assert mem.temp_size_in_bytes < 0.3e9, mem.temp_size_in_bytes
    text = compiled.as_text()
    for name in ("dsa_index_scores_pallas", "dsa_decode_attention_pallas",
                 "moe_grouped_matmul_pallas"):
        assert name in text, name
    assert "paged_decode_attention" not in text
    assert "dsa_gather" not in text
    _assert_no_buffer(text, B * spec.index_topk, spec.cache_head_dim)
    _assert_no_buffer(text, f"{B},{spec.index_topk}", spec.cache_head_dim)
    for shape, minor in (("bf16[12,1,8193,32,2,512]", "{5,4,3,2,"),
                         ("bf16[12,1,8193,32,128]", "{4,3,2,")):
        layouts = {line.split(shape, 1)[1].split("}", 1)[0]
                   for line in text.splitlines() if shape + "{" in line}
        assert layouts and all(l.startswith(minor) for l in layouts), layouts


@pytest.mark.slow  # 20 s alone; the builder's command (CHANGES.md, PR 53)
def test_kv_selection_prompt_program_fits_beside_the_pool_on_v5e(v5e):
    """The 16,384-row prompt program of the cut: the scoring kernel, the
    flash kernel under a mask (one mask for the four KV groups) and the
    page writer in it, both arrays aliased, no [16,384, 16,384] float32
    scores, the selection once as bytes, and the temporaries (0.82 GB)
    small enough beside 4.45 GB of weights and 7.25 GB of pages."""
    A = _abstract(v5e)
    spec, params, pool, keys = _keye_cut(A)
    compiled = _prompt_program(A, spec, params, pool, keys, None,
                               bucket=KEYE_CTX)
    mem = compiled.memory_analysis()
    held = _nbytes((params, pool, keys))
    assert 11.6e9 < held < 11.8e9
    assert mem.alias_size_in_bytes >= _nbytes((pool, keys))
    assert mem.temp_size_in_bytes < 1.0e9, mem.temp_size_in_bytes
    # (the selection's bias is the launch's VMEM scratch: the parent's)
    assert mem.temp_size_in_bytes <= PARENT_53_TEMP_BYTES["keye"] + (
        16 << 20), mem.temp_size_in_bytes
    text = compiled.as_text()
    for name in ("dsa_index_scores_pallas", "dsa_prefill_attention_pallas",
                 "dsa_write_pages_pallas", "moe_grouped_matmul_pallas"):
        assert name in text, name
    _assert_no_buffer(text, KEYE_CTX, KEYE_CTX, ("f32", "bf16", "s32", "u32"))
    assert f"s8[1,{KEYE_CTX},{KEYE_CTX}]" in text
