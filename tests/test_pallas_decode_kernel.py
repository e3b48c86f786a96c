"""The paged decode kernel (one program, a block of slots) against its
jnp twin in interpret mode: what it reads, and with ``write`` the new
token it writes.  ``tests/conftest.py`` counts the file slow; the
kernel's write holds every served decode step's cache, so its cases are
marked fast."""

import jax.numpy as jnp
import numpy as np
import pytest

from tests.pallas_cases import live_rows_match, make_case
from vgate_tpu.ops.attention import paged_decode_attention
from vgate_tpu.ops.pallas.paged_attention import paged_decode_attention_pallas


def _scattered(k_pages, v_pages, page_tables, seq_lens, seed, layer=None):
    """A new token a slot (its K and V at position length - 1) and the
    pools with it written as a decode step's scatter writes it: a dead
    slot's lands in trash page 0 (models/decoder.py decode_attn_inputs)."""
    from vgate_tpu.models.decoder import decode_attn_inputs
    from vgate_tpu.ops.kv_quant import kv_write_tokens

    B, (KV, _, ps, hd) = seq_lens.shape[0], k_pages.shape[-4:]
    rng = np.random.default_rng(seed)
    k_new = jnp.asarray(rng.normal(size=(B, KV, hd)), k_pages.dtype)
    v_new = jnp.asarray(rng.normal(size=(B, KV, hd)), v_pages.dtype)
    _, page_ids, page_off = decode_attn_inputs(
        jnp.maximum(seq_lens - 1, 0), page_tables, seq_lens > 0, ps
    )
    k_after = kv_write_tokens(k_pages, page_ids, page_off, k_new, layer=layer)
    v_after = kv_write_tokens(v_pages, page_ids, page_off, v_new, layer=layer)
    return k_new, v_new, k_after, v_after


def _decode_both(q, k_pages, v_pages, page_tables, seq_lens, write=False,
                 **kw):
    """(kernel, twin).  With ``write`` the kernel is handed a new token a
    slot and the pools WITHOUT it: the pools it returns must be the
    scatter's bit for bit in every page but the trash page (a dead slot
    writes nothing, the scatter dumps its token there), and its
    attention exactly what it computes over the scattered pools."""
    if write:
        k_new, v_new, k_after, v_after = _scattered(
            k_pages, v_pages, page_tables, seq_lens, seed=99,
            layer=kw.get("layer"),
        )
        got, k_got, v_got = paged_decode_attention_pallas(
            q, k_pages, v_pages, page_tables, seq_lens, k_new=k_new,
            v_new=v_new, interpret=True, **kw
        )
        for pool, after, before in (
            (k_got, k_after, k_pages), (v_got, v_after, v_pages),
        ):
            np.testing.assert_array_equal(
                np.asarray(pool)[..., 1:, :, :],
                np.asarray(after)[..., 1:, :, :],
            )
            np.testing.assert_array_equal(
                np.asarray(pool)[..., 0, :, :],
                np.asarray(before)[..., 0, :, :],
            )
        k_pages, v_pages = k_after, v_after
        np.testing.assert_array_equal(
            np.asarray(got),
            np.asarray(paged_decode_attention_pallas(
                q, k_pages, v_pages, page_tables, seq_lens, interpret=True,
                **kw
            )),
        )
    else:
        got = paged_decode_attention_pallas(
            q, k_pages, v_pages, page_tables, seq_lens, interpret=True, **kw
        )
    # the twin never sees a 0: it would divide by an empty sum
    expect = paged_decode_attention(
        q, k_pages, v_pages, page_tables, jnp.maximum(seq_lens, 1), **kw
    )
    return got, expect


# the kernel's write holds every served decode step's cache: its cases
# run in tier-1 (tests/conftest.py counts this file's others as slow)
reads_and_writes = pytest.mark.parametrize(
    "write",
    [
        pytest.param(False, id="reads"),
        pytest.param(True, id="writes", marks=pytest.mark.fast),
    ],
)


@reads_and_writes
def test_decode_kernel_every_length_in_one_batch(write):
    """Page and chunk edges (a chunk is 256 tokens at this geometry), an
    empty row and a full context, side by side in one block; written,
    the new token lies at a page's first row (1, 33, 257), at its last
    (32, 256, 2048) and in a chunk's first page and last."""
    lens = [0, 1, 31, 32, 33, 255, 256, 257, 2048]
    case = make_case(
        B=len(lens), H=4, KV=2, ps=32, pages_per_seq=64, lens=lens, seed=21
    )
    got, expect = _decode_both(*case, write=write)
    live_rows_match(got, expect, case[4])


def _blocks_of_32(pattern, seed):
    """(2, 8, 256) in float32 gives 32 slots a program: `pattern` maps a
    slot to its length, every other slot is dead."""
    from vgate_tpu.ops.pallas.paged_attention import _decode_sizes

    B = 70  # three programs, the last one mostly outside the batch
    assert _decode_sizes(
        B, 2, 8, 256, 16, 8, jnp.float32, jnp.float32
    ) == (8, 32, 2, 6)
    lens = [pattern.get(b, 0) for b in range(B)]
    return make_case(
        B=B, H=16, KV=2, hd=256, ps=16, pages_per_seq=8, lens=lens,
        seed=seed,
    )


@pytest.mark.parametrize(
    "pattern",
    [
        # a block that is all dead, then one with a single live slot
        {40: 77},
        # live slots separated by dead ones: the pipeline crosses them,
        # and a block boundary (31 | 32) and the ragged last block (64+)
        {0: 5, 3: 128, 4: 1, 9: 100, 31: 33, 32: 127, 63: 64, 65: 17, 69: 90},
        # every slot live, lengths all over
        {b: 1 + (37 * b) % 128 for b in range(70)},
        # nothing live anywhere
        {},
    ],
    ids=["dead-block-then-one-live", "live-among-dead", "all-live", "all-dead"],
)
@reads_and_writes
def test_decode_kernel_block_patterns(pattern, write):
    """Written: more live slots than staging pages in a program, none,
    and fewer."""
    case = _blocks_of_32(pattern, seed=22)
    got, expect = _decode_both(*case, write=write)
    live_rows_match(got, expect, case[4])


_CELL_LENS = [0, 200, 3, 0, 129, 64]
# lengths that cross several chunks of EvaByte's 128 tokens and end inside
# one, over 24 pages a slot
_MHA_LENS = [0, 1, 127, 129, 300, 700]


@pytest.mark.parametrize(
    "KV, G, hd, lens, pages_per_seq, chunk_pages",
    [
        (2, 6, 128, _CELL_LENS, 8, 8), (4, 7, 128, _CELL_LENS, 8, 4),
        (2, 8, 256, _CELL_LENS, 8, 4), (1, 7, 128, _CELL_LENS, 8, 8),
        # float32 pools: the budget's share is 21 tokens, the floor 128
        (32, 1, 128, _MHA_LENS, 24, 4),
    ],
    ids=["1.5B", "7B", "qwen3-next", "one-kv-head-tp-shard", "evabyte-mha"],
)
@reads_and_writes
def test_decode_kernel_cell_geometries(KV, G, hd, lens, pages_per_seq,
                                       chunk_pages, write):
    """The cells' (KV, G, hd) and one KV head (a tp shard of the 7B):
    all KV heads ride one iteration, and one staged page back to the
    pool, whatever their number.  EvaByte's is one query row a KV head
    under 32 of them, where a chunk is the floor of `_decode_sizes` (one
    128-token tile a head) and not the budget's share."""
    from vgate_tpu.ops.pallas.paged_attention import _decode_sizes

    case = make_case(
        B=len(lens), H=KV * G, KV=KV, hd=hd, ps=32,
        pages_per_seq=pages_per_seq, lens=lens, seed=23,
    )
    assert _decode_sizes(
        len(lens), KV, G, hd, 32, pages_per_seq, case[1].dtype,
        case[0].dtype,
    )[0] == chunk_pages
    got, expect = _decode_both(*case, write=write)
    live_rows_match(got, expect, case[4])


@reads_and_writes
def test_decode_kernel_window_softcap_scale_across_blocks(write):
    """Per-slot window starts differ inside one block and chunks below a
    window are never fetched (the chunk that holds the new token always
    is); softcap and the query scale ride along."""
    case = _blocks_of_32(
        {1: 40, 2: 128, 7: 96, 33: 127, 34: 5, 67: 70}, seed=24
    )
    for win in (16, 64, 100):
        got, expect = _decode_both(
            *case, write=write, window=jnp.asarray(win, jnp.int32),
            softcap=30.0, scale=0.25,
        )
        live_rows_match(got, expect, case[4])


@reads_and_writes
def test_decode_kernel_layer_indexed_ragged_batch(write):
    """Layer-indexed pools under a B that is no multiple of the block;
    written, the other layers stay as they were."""
    q, k_pages, v_pages, page_tables, seq_lens = _blocks_of_32(
        {0: 33, 30: 97, 45: 1, 69: 128}, seed=25
    )
    L = 3
    rng = np.random.default_rng(26)
    kL = jnp.asarray(rng.normal(size=(L,) + k_pages.shape), jnp.float32)
    vL = jnp.asarray(rng.normal(size=(L,) + v_pages.shape), jnp.float32)
    got, expect = _decode_both(
        q, kL, vL, page_tables, seq_lens, write=write, layer=jnp.asarray(1)
    )
    live_rows_match(got, expect, seq_lens)
