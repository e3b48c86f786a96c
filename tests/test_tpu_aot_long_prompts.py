"""The v5e compiles (``tests/tpu_aot.py`` says how) of the three long
cells' prompt programs, each compiled ONCE for the tests that read it (a
module fixture), and of the GLM-5.2 cut's selection.
"""

import jax
import jax.numpy as jnp
import pytest

from tests.tpu_aot import (  # noqa: F401 (v5e: a fixture)
    abstract_on, assert_no_buffer, compile_expecting, counted_loops,
    cut_and_shapes, EXAONE_CUT, MISTRAL_CUT, MosaicRefusal, nbytes, PAGE,
    PARENT_53_TEMP_BYTES, prompt_program, v5e,
)


@pytest.fixture(scope="module")
def exaone_prompt(v5e):
    """(the K-EXAONE cut's 8,192-row prompt program compiled for the
    v5e, the spec, the bytes it holds beside its temporaries, those of
    them it must update in place)."""
    from vgate_tpu.models.hybrid import make_state

    A = abstract_on(v5e)
    spec, params = cut_and_shapes(A, *EXAONE_CUT)
    slots = 192
    state = jax.tree.map(
        lambda x: A(x.shape, x.dtype),
        jax.eval_shape(lambda: make_state(spec, slots, jnp.bfloat16, PAGE)))
    pages = 36000  # 4.7 GB of K+V: the cell's pool of the one full layer
    pool = A((spec.attn_layers, spec.num_kv_heads, pages, PAGE,
              spec.head_dim), jnp.bfloat16)
    return (prompt_program(A, spec, params, pool, pool, state), spec,
            nbytes((params, state, pool, pool)),
            nbytes((state, pool, pool)))


@pytest.fixture(scope="module")
def mistral_prompt(v5e):
    """The same of the Mistral-Small-4 cut's 8,192-row prompt program."""
    A = abstract_on(v5e)
    spec, params = cut_and_shapes(A, *MISTRAL_CUT)
    pages = 65537  # 6.44 GB: the cell's latent pool
    pool = A((spec.attn_layers, spec.cache_heads, pages, PAGE,
              spec.cache_head_dim), jnp.bfloat16)
    return (prompt_program(A, spec, params, pool, None, None), spec,
            nbytes((params, pool)), nbytes(pool))


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
        assert_no_buffer(text, pairs, spec.hidden_size)
        assert_no_buffer(text, pairs, spec.expert_width)


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
    assert_no_buffer(text, 8192 * 4, spec.expert_width)
    # bf16[32768, 4096] is the embedding table
    assert_no_buffer(text, 8192 * 4, spec.hidden_size, ("f32", "s32"))


# ---- learned sparse attention: the GLM-5.2 cut as its cell serves it

GLM_CUT = ("zai-org/GLM-5.2", dict(
    name="glm-cut", num_layers=5, first_layer=2, num_experts=16,
    vocab_size=19360, eos_token_id=19359, bos_token_id=19358))
GLM_SLOTS, GLM_CTX = 48, 16384


def _glm_cut(A):
    """(spec, abstract parameters, the latent pool, the index keys'
    array) at the cell's size: 48 slots x 16,384 tokens of pages."""
    spec, params = cut_and_shapes(A, *GLM_CUT)
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

    A = abstract_on(v5e)
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
    assert mem.alias_size_in_bytes >= nbytes((pool, keys)), (
        "a pool is copied")
    # 0.46 GB: the scores of one picking layer, the weights' re-laid
    # copies (0.61 GB with the gathered rows of one layer, PR 40)
    assert mem.temp_size_in_bytes < 0.55e9, mem.temp_size_in_bytes
    text = compiled.as_text()
    for name in ("dsa_index_scores_pallas", "dsa_decode_attention_pallas"):
        assert name in text, name
    assert "mla_decode_attention_pallas" not in text
    assert "dsa_gather" not in text
    assert_no_buffer(text, B * spec.index_topk, spec.cache_head_dim)
    assert_no_buffer(text, f"{B},{spec.index_topk}", spec.cache_head_dim)
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
    A = abstract_on(v5e)
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

    A = abstract_on(v5e)
    pool = A((2048, PAGE, 640), jnp.bfloat16)
    compile_expecting(
        "aligned to tiling (8), but is 1", _compile_fetching_kernel, A,
        pool, lambda ref, n: ref.at[n // PAGE, pl.ds(n % PAGE, 1)], pair=1)


def test_the_fetching_decode_kernel_compiles_at_the_cells_widths_on_v5e(v5e):
    """``dsa_decode_attention_pallas`` for the v5e at the cell's widths:
    48 slots, 2,048 picks, 64 heads over rows of 640 lanes, 5 layers of
    24,577 pages by pairs; nothing beside its operands."""
    from vgate_tpu.ops.pallas.dsa import dsa_decode_attention_pallas

    A = abstract_on(v5e)
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
    A = abstract_on(v5e)
    spec, params, pool, keys = _glm_cut(A)
    return (prompt_program(A, spec, params, pool, keys, None,
                            bucket=GLM_CTX), spec,
            nbytes((params, pool, keys)), nbytes((pool, keys)))


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
    assert_no_buffer(text, GLM_CTX, GLM_CTX, ("f32", "bf16", "s32", "u32"))
    assert_no_buffer(text, GLM_CTX, spec.intermediate_size)
    assert_no_buffer(text, f"1,{GLM_CTX}", spec.intermediate_size)
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
    A = abstract_on(v5e)
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
        assert counted_loops(text, scope), scope
    temp = compiled.memory_analysis().temp_size_in_bytes
    parent = PARENT_TEMP_BYTES[cell]
    print(cell, "prompt program temporaries", temp)
    assert temp <= parent + (320 << 20), (
        f"{cell}: {temp} temporary bytes against the parent's {parent}")
    assert held + temp < 15.9e9
