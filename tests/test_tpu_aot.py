"""The v5e compiles (``tests/tpu_aot.py`` says how) of the kernels on the
default path and of the dense, latent, pattern, window and Qwen3-Next
stacks' step programs.
"""

import jax
import jax.numpy as jnp
import pytest

from tests.tpu_aot import (  # noqa: F401 (v5e: a fixture)
    abstract_on, assert_no_copy_of, assert_no_logits_array, compile_expecting,
    counted_loops, cut_and_shapes, EXAONE_CUT, MELLUM_CUT, MISTRAL_CUT,
    MosaicRefusal, nbytes, operations, PAGE, prompt_program, v5e,
)
from vgate_tpu.models.specs import spec_for_model_id


# (num_heads, kv_heads, head_dim): Qwen2.5-1.5B on one chip, and one
# tp=4 shard of Qwen2.5-7B (28 heads / 4 KV heads over four chips)
GEOM_1P5B = (12, 2, 128)
GEOM_7B_TP4_SHARD = (7, 1, 128)
GEOM_7B = (28, 4, 128)
# Qwen3-Next's gated full-attention layers: head size 256
GEOM_QWEN3_NEXT = (16, 2, 256)


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
    compile_kernel(abstract_on(v5e), geom)


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
        abstract_on(v5e), geom, B=256, pages_per_seq=64, write=True
    )


# what serves a decode step in each of the benchmark's configurations,
# (slots, KV heads, query heads a KV head, head size, pages a slot, pools),
# and what `_decode_sizes` reads off it: (pages a chunk, slots a program,
# work-list items a loop trip, chunk buffers: the trip computed and the
# trips in flight behind it)
SERVED_DECODE_SHAPES = {
    "qwen2.5-1.5b": ((256, 2, 6, 128, 64, 2), (8, 64, 2, 6)),
    "qwen2.5-7b-l14": ((256, 4, 7, 128, 64, 2), (8, 32, 1, 3)),
    "qwen3-next-80b-a3b-l8e128": ((256, 2, 8, 256, 64, 2), (8, 32, 2, 6)),
    "nemotron-3-super-120b-a12b-l11e128": (
        (192, 2, 16, 128, 64, 2), (8, 64, 2, 6)),
    # the latent pool: one "head" of 384-lane rows under 32 query heads,
    # ONE trip's chunks in flight behind a trip of two (PR 56)
    "mistral-small-4-119b-l4e32": ((256, 1, 32, 384, 256, 1), (8, 21, 2, 4)),
    # the full layers (the rings of five pages a slot get the same chunk)
    "k-exaone-236b-a23b-l5e16": ((192, 8, 8, 128, 256, 2), (4, 16, 1, 3)),
    # the Mellum2 cut's two full layers over 16,384-token contexts (its
    # rings of 33 pages a slot get the same chunk: five items a slot)
    "mellum2-12b-a2.5b-l8": ((80, 4, 8, 128, 512, 2), (8, 32, 1, 3)),
    # MHA, one query row a KV head: 64 window pages behind 32 of summaries.
    # The 4 MiB budget alone gave ONE page a chunk here (PR 45)
    "evabyte-6.5b-l8": ((20, 32, 1, 128, 96, 2), (4, 4, 1, 3)),
    # 8 KV heads of 64 as 4 pair rows of 128 lanes, 2 x 4 query heads a
    # row (ops/head_pack.py): the 7B's item of four rows, one a trip
    "lfm2-24b-a2b-e8": ((256, 4, 8, 128, 64, 2), (8, 32, 1, 3)),
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
        abstract_on(v5e), (KV * G, KV, hd), B=B, pages_per_seq=pages_per_seq,
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

    A = abstract_on(v5e)
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

    A = abstract_on(v5e)
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

    A = abstract_on(v5e)
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

    A = abstract_on(v5e)
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

    A = abstract_on(v5e)
    grouped_matmul_pallas.lower(
        A((rows, k), jnp.bfloat16), A((6, 128, k, n), jnp.bfloat16),
        A((128,), jnp.int32), A((), jnp.int32), tm=tm, tn=tn,
    ).compile()


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
    compile_expecting(
        "aligned to tiling (8), but is 1",
        compile_kernel, abstract_on(v5e), GEOM_1P5B, int8=True,
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
    compile_expecting(
        fragment, compile_kernel, abstract_on(v5e), (14, 2, 64)
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

    A = abstract_on(v5e)
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

    A = abstract_on(v5e)
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
    assert_no_logits_array(cells.as_text(), B, spec.vocab_size)
    wants_logprobs = compiled(num_logprobs=8)
    fell = (wants_logprobs.memory_analysis().temp_size_in_bytes
            - cells.memory_analysis().temp_size_in_bytes)
    print("decode chunk temporaries fell by", fell)
    assert fell >= B * spec.vocab_size * 4, "the array's bytes stayed"

    text = operations(wants_logprobs)
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


def test_latent_decode_chunk_compiles_on_v5e(v5e):
    """The Mistral-Small-4 cut as the cell serves it (4 layers, 32
    experts held, 256 slots of 8,192 tokens): the decode chunk compiles
    for the v5e with the ONE latent pool aliased input to output and the
    latent decode kernel in it, its trips as `_decode_sizes` sets them."""
    from vgate_tpu.runtime.step_programs import _decode_chunk

    A = abstract_on(v5e)
    spec, params = cut_and_shapes(A, *MISTRAL_CUT)
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

    A = abstract_on(v5e)
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

    A = abstract_on(v5e)
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

    A = abstract_on(v5e)
    spec, params = cut_and_shapes(A, *EXAONE_CUT)
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
    assert_no_copy_of(text, (1, 3, 2048, 6144), (1, 3, 6144, 2048))
    S, H, KV, hd = 8192, 64, 8, 128
    band = jax.jit(lambda q, k, v, lens: swa_prefill_attention_pallas(
        q, k, v, lens, 128)).lower(
        A((1, S, H, hd), jnp.bfloat16), A((1, S, KV, hd), jnp.bfloat16),
        A((1, S, KV, hd), jnp.bfloat16), A((1,), jnp.int32)).compile()
    assert "swa_prefill_attention_pallas" in band.as_text()


def _mellum_cell(A):
    """(spec, params, rings, their bytes, pool, its bytes) of the Mellum2
    cut as its cell serves it: 80 slots of 16,384 tokens."""
    from vgate_tpu.models.hybrid import make_state

    spec, params = cut_and_shapes(A, *MELLUM_CUT)
    B = 80
    state = jax.tree.map(
        lambda x: A(x.shape, x.dtype),
        jax.eval_shape(lambda: make_state(spec, B, jnp.bfloat16, PAGE)))
    assert nbytes(state) == 2 * 6 * 4 * (1 + B * 33) * PAGE * 128 * 2
    pages = 40961  # the pool at its cap: 80 x 512 + 1
    pool = A((spec.attn_layers, spec.num_kv_heads, pages, PAGE,
              spec.head_dim), jnp.bfloat16)
    assert 2 * nbytes(pool) == pages * 131072
    return spec, params, state, pool


def test_mellum_decode_chunk_and_its_window_kernel_compile_on_v5e(v5e):
    """The Mellum2 cut as the cell serves it (8 layers, all 64 experts,
    80 slots of 16,384 tokens): the decode chunk compiles for the v5e
    with the pool of the TWO full layers and the rings of the six window
    layers (33 pages a slot) aliased input to output, a ring's launch
    under its own name beside a full layer's; and the window layers'
    banded prompt kernel compiles at the cell's one shape (16,384 rows,
    32 heads on 4) in the blocks the window's rule gives."""
    from vgate_tpu.ops.pallas.flash_prefill import (
        swa_blocks, swa_prefill_attention_pallas,
    )
    from vgate_tpu.runtime.step_programs import _decode_chunk

    A = abstract_on(v5e)
    spec, params, state, pool = _mellum_cell(A)
    B, ctx = 80, 16384
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
    assert mem.alias_size_in_bytes >= nbytes((state, pool, pool)), (
        "the pool or the rings are copied")
    # a step's activations and the weights' re-laid copies: 0.197 GB
    # when this was written (PR 57)
    assert mem.temp_size_in_bytes < 0.4e9, mem.temp_size_in_bytes
    text = compiled.as_text()
    assert "swa_decode_attention_pallas" in text
    assert "paged_decode_attention_pallas" in text
    assert "moe_grouped_matmul_pallas" in text
    assert "greedy_head" in text
    S, H, KV, hd = 16384, 32, 4, 128
    assert swa_blocks(spec.sliding_window) == (1024, 1024)
    band = jax.jit(lambda q, k, v, lens: swa_prefill_attention_pallas(
        q, k, v, lens, spec.sliding_window, skip_padding=True)).lower(
        A((1, S, H, hd), jnp.bfloat16), A((1, S, KV, hd), jnp.bfloat16),
        A((1, S, KV, hd), jnp.bfloat16), A((1,), jnp.int32)).compile()
    assert "swa_prefill_attention_pallas" in band.as_text()


@pytest.mark.slow  # a 16,384-row program of eight expert layers: the
# builder's command (CHANGES.md, PR 57)
def test_mellum_prompt_program_fits_beside_pool_and_rings_on_v5e(v5e):
    """The 16,384-row prompt program of the Mellum2 cut: pool and rings
    updated in place, and its temporaries inside what ``HBM_UTILIZATION``
    0.86 leaves beside 7.59 GB of weights, 5.37 GB of pool and 1.04 GB
    of rings: 0.868 GB when this was written (PR 57: q and the
    attention's result 134 MB each, the experts' 4,096-row blocks of 8
    choices x 2,304 in float32 302 MB, the weights' re-laid copies), so
    the run's peak is near 14.9 GB of the chip's 16.9."""
    A = abstract_on(v5e)
    spec, params, state, pool = _mellum_cell(A)
    compiled = prompt_program(A, spec, params, pool, pool, state,
                              bucket=16384)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= nbytes((state, pool, pool))
    assert mem.temp_size_in_bytes < 1.2e9, mem.temp_size_in_bytes
    text = compiled.as_text()
    assert "swa_prefill_attention_pallas" in text
    assert "flash_prefill_attention_pallas" in text


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
    A = abstract_on(v5e)
    spec, params = cut_and_shapes(A, preset, changes)
    pool = A((spec.num_layers, spec.num_kv_heads, 2049, PAGE,
              spec.head_dim), jnp.bfloat16)
    compiled = prompt_program(
        A, spec, params, pool, pool, None, bucket=2048, B=8)
    text = compiled.as_text()
    assert "flash_prefill_attention_pallas" in text
    # the front half's loop, and with the back half's two in all
    assert counted_loops(text, "qkv") and counted_loops(text, "") >= 2
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= nbytes((pool, pool))
    parent = DENSE_PARENT_TEMP_BYTES[cell]
    assert mem.temp_size_in_bytes <= parent, (
        f"{cell}: {mem.temp_size_in_bytes} temporary bytes against the "
        f"parent's {parent}")


def test_qwen3_next_decode_chunk_reads_its_matrices_in_place_on_v5e(v5e):
    """The Qwen3-Next cut as the cell serves it (8 layers = two periods
    of three Gated DeltaNet layers and one attention layer, 128 experts
    held, vocabulary 37,984, 256 slots of 2,048 tokens): the decode
    chunk holds no copy of a period's mixers' matrices (ten of them,
    ~ 450 MB a step, before PR 48), and its temporaries are the
    activations' (234.9 MB when this was written; the parent's 505.7)."""
    from vgate_tpu.models.hybrid import make_state
    from vgate_tpu.runtime.step_programs import _decode_chunk

    A = abstract_on(v5e)
    spec, params = cut_and_shapes(
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
    assert mem.alias_size_in_bytes >= 2 * nbytes(pool) + nbytes(state), (
        "the pool or the state is copied")
    print("qwen3-next decode chunk temporaries", mem.temp_size_in_bytes)
    assert mem.temp_size_in_bytes < 300e6
    text = operations(compiled)
    assert "gated_delta_step_pallas" in text
    assert "moe_grouped_matmul_pallas" in text
    # the cut's untied head is `[2048, 37984]`, 296.75 lane groups wide:
    # XLA would re-lay it for the fused head's kernel, 155 MB a step, so
    # the shape rule (ops/pallas/greedy_head.py) keeps the three passes
    assert "greedy_head" not in text
    assert_no_copy_of(text, (3, 1, 2048, 12288), (3, 1, 4096, 2048),
                       (3, 1, 2048, 512), (3, 1, 512, 2048))
