"""The v5e compiles (``tests/tpu_aot.py`` says how) of the EvaByte, the
Granite and the Keye-VL-2.0 cuts as their cells serve them.
"""

import jax
import jax.numpy as jnp
import pytest

from tests.tpu_aot import (  # noqa: F401 (v5e: a fixture)
    abstract_on, assert_no_buffer, assert_no_copy_of, assert_no_logits_array,
    cut_and_shapes, nbytes, PAGE, PARENT_53_TEMP_BYTES, prompt_program, v5e,
)
from vgate_tpu.models.specs import spec_for_model_id


# the EvaByte cut as its cell serves it: 8 of 32 layers, 20 slots of
# 16,384 bytes of context
EVA_CUT = ("EvaByte/EvaByte", {"num_layers": 8})
EVA_SLOTS, EVA_CTX = 20, 16384


def _eva_cut(A):
    """(spec, abstract parameters, the pool's K (= V) array with the
    slots' windows behind the allocator's pages, the state that names
    them)."""
    from vgate_tpu.models.hybrid import make_state

    spec, params = cut_and_shapes(A, *EVA_CUT)
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

    A = abstract_on(v5e)
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
    assert nbytes((pool, pool)) == 2 * 8 * 32 * 1921 * 32 * 128 * 2
    assert mem.alias_size_in_bytes >= nbytes((pool, pool)), (
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
    assert_no_buffer(text, "20,16", "32,128")
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
    A = abstract_on(v5e)
    spec, params, pool, state = _eva_cut(A)
    compiled = prompt_program(A, spec, params, pool, pool, state,
                               bucket=EVA_CTX)
    mem = compiled.memory_analysis()
    held = nbytes((params, pool, pool))
    assert 11.2e9 < held < 11.4e9
    assert mem.alias_size_in_bytes >= nbytes((pool, pool))
    assert mem.temp_size_in_bytes < 2.1e9, mem.temp_size_in_bytes
    assert held + mem.temp_size_in_bytes < 0.86 * 16.9e9
    text = compiled.as_text()
    assert "eva_prefill_attention_pallas" in text
    assert "{4,1,3,2,0" not in text, "XLA re-laid the pool out"
    assert_no_buffer(text, EVA_CTX, EVA_CTX)
    assert_no_buffer(text, EVA_CTX, spec.intermediate_size)
    assert_no_buffer(text, f"1,{EVA_CTX}", spec.intermediate_size)


# ---- Granite 4.0-H Micro, whole: the state (6.1 GB) sets the batch

# what the configuration's hbm_utilization 0.9 leaves the programs: 0.9
# of the chip's 16.9 GB less weights 6.39, state 6.11 and pages 1.34 GB
GRANITE_PROGRAM_ROOM = int(0.9 * 16.9e9 - 13.85e9)


@pytest.fixture(scope="module")
def granite(v5e):
    from vgate_tpu.models.hybrid import make_state

    A = abstract_on(v5e)
    spec, params = cut_and_shapes(
        A, "ibm-granite/granite-4.0-h-micro", {})
    spec = spec.pack_kv_heads()
    state = jax.tree.map(
        lambda x: A(x.shape, x.dtype),
        jax.eval_shape(lambda: make_state(spec, 80, jnp.bfloat16, PAGE)))
    assert nbytes(state) == 80 * 76_437_504
    # (64 zero columns behind dt a Mamba-2 layer: hybrid.mamba_proj_pad;
    # A_log, D and dt_bias are float32: 3 x 36 x 64 values of 4 B)
    assert nbytes(params) == (
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
    assert mem.alias_size_in_bytes >= 2 * nbytes(pool) + nbytes(state), (
        "the pool or the state is copied")
    print("granite decode chunk temporaries", mem.temp_size_in_bytes)
    assert mem.temp_size_in_bytes < GRANITE_PROGRAM_ROOM
    assert mem.temp_size_in_bytes < state["S"].size * 4 // 16
    text = compiled.as_text()
    assert "ssd_step_pallas" in text
    assert "paged_decode_attention_pallas" in text
    assert "greedy_head" in text
    assert_no_logits_array(text, B, spec.vocab_size)
    # no copy of a period's matrices either (the walker's scans carry
    # indices): the nine Mamba-2 layers' in_proj, the SwiGLU's three
    assert_no_copy_of(text, (9, 2048, 8512), (5, 2048, 8512),
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
    compiled = prompt_program(A, spec, params, pool, pool, state,
                               bucket=bucket, B=B)
    mem = compiled.memory_analysis()
    print("granite prompt program", B, bucket, "temporaries",
          mem.temp_size_in_bytes)
    assert mem.alias_size_in_bytes >= 2 * nbytes(pool) + nbytes(state), (
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
    spec, params = cut_and_shapes(A, *KEYE_CUT)
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

    A = abstract_on(v5e)
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
    assert write.memory_analysis().alias_size_in_bytes >= nbytes(pool)


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

    A = abstract_on(v5e)
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
    assert mem.alias_size_in_bytes >= nbytes((pool, keys)), (
        "a pool is copied")
    assert mem.temp_size_in_bytes < 0.3e9, mem.temp_size_in_bytes
    text = compiled.as_text()
    for name in ("dsa_index_scores_pallas", "dsa_decode_attention_pallas",
                 "moe_grouped_matmul_pallas"):
        assert name in text, name
    assert "paged_decode_attention" not in text
    assert "dsa_gather" not in text
    assert_no_buffer(text, B * spec.index_topk, spec.cache_head_dim)
    assert_no_buffer(text, f"{B},{spec.index_topk}", spec.cache_head_dim)
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
    A = abstract_on(v5e)
    spec, params, pool, keys = _keye_cut(A)
    compiled = prompt_program(A, spec, params, pool, keys, None,
                               bucket=KEYE_CTX)
    mem = compiled.memory_analysis()
    held = nbytes((params, pool, keys))
    assert 11.6e9 < held < 11.8e9
    assert mem.alias_size_in_bytes >= nbytes((pool, keys))
    assert mem.temp_size_in_bytes < 1.0e9, mem.temp_size_in_bytes
    # (the selection's bias is the launch's VMEM scratch: the parent's)
    assert mem.temp_size_in_bytes <= PARENT_53_TEMP_BYTES["keye"] + (
        16 << 20), mem.temp_size_in_bytes
    text = compiled.as_text()
    for name in ("dsa_index_scores_pallas", "dsa_prefill_attention_pallas",
                 "dsa_write_pages_pallas", "moe_grouped_matmul_pallas"):
        assert name in text, name
    assert_no_buffer(text, KEYE_CTX, KEYE_CTX, ("f32", "bf16", "s32", "u32"))
    assert f"s8[1,{KEYE_CTX},{KEYE_CTX}]" in text
