"""The paged decode kernel's page pipeline in interpret mode: however
many trips' chunks stand in flight behind the one computed
(``paged_attention.decode_buffers``), every pool kind gives the one-item
program's bits; and the probe's counter of a launch's items, trips and
full chunks (``benchmarks/bench_kernels.py decode_trips``).  The waits
themselves run under the TPU interpreter in ``test_pallas_kernels.py``."""

import functools

import jax.numpy as jnp
import numpy as np
import pytest

from tests.pallas_cases import live_rows_match, make_case
from vgate_tpu.ops.attention import paged_decode_attention
from vgate_tpu.ops.pallas.paged_attention import paged_decode_attention_pallas


# Lengths by the chunk (T tokens) and the page: dead slots among live
# ones, a one-page slot, exactly-full chunks (the slot ends on a chunk's
# boundary), full chunks behind a tail of ONE page, a one-token slot, a
# chunk whose pages are all live and whose last row is not
def _pipeline_lens(T):
    return [0, 5, 2 * T, 0, T, 2 * T + 6, 1, 3 * T + 4, 0, T + 1, T - 1]


# (pool kind, items a trip, chunk buffers): the page pipeline's forms.
# A kind's first is the program as it was before trips held two (one
# item, two chunks in flight behind it); (2, 4) is what PR 37 served,
# (2, 6) what PR 56 serves over K and V pools.  An int8 pool's trip
# holds one item whatever is asked.
_PIPELINE_FORMS = [
    (kind, items, buffers)
    for kind in ("reads", "writes", "window", "latent")
    for items, buffers in ((1, 3), (2, 4), (2, 6))
] + [("int8", 1, 3), ("int8", 1, 2), ("int8", 1, 4)]


@functools.lru_cache(maxsize=None)
def _pipeline_case(kind):
    """(kernel by (items, buffers) -> (attention, *pools), the twin's
    attention, the pools a scatter leaves, lengths, tolerance)."""
    from vgate_tpu.models.decoder import decode_attn_inputs
    from vgate_tpu.ops.attention import mla_decode_attention
    from vgate_tpu.ops.kv_quant import kv_write_tokens
    from vgate_tpu.ops.pallas.paged_attention import (
        _decode_sizes, mla_decode_attention_pallas,
    )

    rng = np.random.default_rng(56)
    latent = kind == "latent"
    # a chunk is 2 pages of 16 tokens (forced), or the latent rule's 8 of 32
    ps, pages_per_seq, T = (32, 32, 256) if latent else (16, 8, 32)
    lens = _pipeline_lens(T)
    B, layer = len(lens), jnp.int32(1)
    if latent:
        dtype, KV, H, hd = jnp.bfloat16, 1, 4, 128
    else:
        dtype = jnp.float32 if kind in ("reads", "int8") else jnp.bfloat16
        KV, H, hd = 2, 4, 128
    q, k_pages, v_pages, table, seq_lens = (
        x.astype(dtype) if x.dtype == jnp.float32 else x
        for x in make_case(
            B=B, H=H, KV=KV, hd=hd, ps=ps, pages_per_seq=pages_per_seq,
            lens=lens, seed=56,
        )
    )
    # layer-indexed pools, the served form
    pools = [jnp.stack([pool * 0.5, pool]) for pool in (k_pages, v_pages)]
    news = [jnp.asarray(rng.normal(size=(B, KV, hd)), dtype)
            for _ in pools]
    _, page_ids, page_off = decode_attn_inputs(
        jnp.maximum(seq_lens - 1, 0), table, seq_lens > 0, ps
    )
    after = [
        kv_write_tokens(pool, page_ids, page_off, new, layer=layer)
        for pool, new in zip(pools, news)
    ]
    live = jnp.maximum(seq_lens, 1)  # the twin divides by an empty sum
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    if latent:
        assert _decode_sizes(
            B, KV, H, hd, ps, pages_per_seq, dtype, dtype, pools=1
        )[0] * ps == T
        kw = dict(v_width=64, scale=0.05)
        want = mla_decode_attention(q, after[0], table, live, layer, **kw)

        def kernel(items, buffers):
            return mla_decode_attention_pallas(
                q, pools[0], table, seq_lens, layer, news[0][:, 0],
                interpret=True, items=items, buffers=buffers, **kw
            )

        return kernel, want, after[:1], seq_lens, tol
    kw = {"layer": layer}
    if kind == "int8":
        from vgate_tpu.ops.kv_quant import QuantPages, quantize

        pools = after = [QuantPages(*quantize(pool)) for pool in pools]
    if kind == "window":
        # past a chunk: a long slot's first chunks are no items
        kw["window"] = jnp.asarray(40, jnp.int32)
    if kind in ("writes", "window"):
        want = paged_decode_attention(*(q, *after, table, live), **kw)
        kw.update(k_new=news[0], v_new=news[1])
    else:
        want, after = paged_decode_attention(q, *pools, table, live, **kw), []

    def kernel(items, buffers):
        got = paged_decode_attention_pallas(
            q, *pools, table, seq_lens, interpret=True, items=items,
            buffers=buffers, chunk_pages=2, **kw
        )
        return got if isinstance(got, tuple) else (got,)

    return kernel, want, after, seq_lens, tol


@functools.lru_cache(maxsize=None)
def _pipeline_outputs(kind, items, buffers):
    return _pipeline_case(kind)[0](items, buffers)


@pytest.mark.fast  # tier-1: every served decode step's page pipeline
@pytest.mark.parametrize(
    "kind, items, buffers", _PIPELINE_FORMS,
    ids=[f"{kind}-{items}x{buffers}" for kind, items, buffers
         in _PIPELINE_FORMS],
)
def test_decode_kernel_page_pipeline_forms_give_one_programs_bits(
    kind, items, buffers
):
    """However many trips' chunks stand in flight behind the one
    computed, the attention is the twin's, the written pools are the
    scatter's, and both are the one-item program's BIT for bit: plain
    pools read and written, a window (what a ring's launch passes), the
    latent pool and int8 pages, over full chunks, tails and dead slots."""
    _, want, after, seq_lens, tol = _pipeline_case(kind)
    got = _pipeline_outputs(kind, items, buffers)
    first = _pipeline_outputs(*next(
        form for form in _PIPELINE_FORMS if form[0] == kind
    ))
    f32 = lambda a: np.asarray(a, np.float32)  # noqa: E731
    for mine, its in zip(got, first):
        np.testing.assert_array_equal(f32(mine), f32(its))
    live_rows_match(f32(got[0]), f32(want), seq_lens, tol=tol)
    assert len(got) == 1 + len(after)
    for pool, scattered in zip(got[1:], after):
        # every page but the trash page, where a dead slot's token goes
        # by scatter alone
        np.testing.assert_array_equal(
            f32(pool)[:, :, 1:], f32(scattered)[:, :, 1:]
        )


@pytest.mark.fast  # the counter beside the page pipeline's probe
@pytest.mark.parametrize(
    "lens, items, want",
    [
        # chunks of 256 tokens, two programs of 64 slots: 3 + 1 + 2 items
        # in the first (two of them tails), 1 in the second
        ({0: 700, 5: 100, 9: 512, 64: 256}, 2, (7, 4, 3, 5 / 7)),
        ({0: 700, 5: 100, 9: 512, 64: 256}, 1, (7, 7, 7, 5 / 7)),
        # every slot a tail alone, and nothing live at all
        ({b: 1 + b for b in range(5)}, 2, (5, 3, 2, 0.0)),
        ({}, 2, (0, 0, 0, 0.0)),
    ],
    ids=["pairs", "one-item-trips", "tails-alone", "nothing-live"],
)
def test_the_probes_decode_trips_counts_items_trips_and_full_chunks(
    lens, items, want
):
    """`benchmarks/bench_kernels.py decode_trips`: work-list items, loop
    trips, trips that hold `items` items, and the share of items that are
    FULL chunks (no dead page)."""
    from benchmarks.bench_kernels import decode_trips

    got = decode_trips([lens.get(b, 0) for b in range(70)], 256, 64, items)
    assert got[:3] == want[:3]
    assert got[3] == pytest.approx(want[3])
