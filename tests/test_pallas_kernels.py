"""Pallas kernels vs their jnp twins (interpret mode on CPU — SURVEY.md
section 4: kernel unit tests comparing Pallas outputs vs jnp reference)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tests.pallas_cases import make_case
from vgate_tpu.ops.attention import paged_decode_attention
from vgate_tpu.ops.pallas.paged_attention import paged_decode_attention_pallas


@pytest.mark.parametrize(
    "lens",
    [
        None,  # random lengths
        [1, 16, 17, 128],  # page-boundary edges
        [255, 256, 200, 3],  # chunk-boundary edges (chunk=128 tokens)
    ],
)
def test_paged_decode_kernel_matches_jnp(lens):
    q, k_pages, v_pages, page_tables, seq_lens = make_case(
        lens=lens, seed=1 if lens is None else 2
    )
    expect = paged_decode_attention(q, k_pages, v_pages, page_tables, seq_lens)
    got = paged_decode_attention_pallas(
        q, k_pages, v_pages, page_tables, seq_lens, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(expect), rtol=2e-5, atol=2e-5
    )


def test_paged_decode_kernel_gqa_group_mapping():
    """H=8, KV=4 (G=2): each group must read its own kv head."""
    q, k_pages, v_pages, page_tables, seq_lens = make_case(
        B=2, H=8, KV=4, pages_per_seq=8, seed=3
    )
    expect = paged_decode_attention(q, k_pages, v_pages, page_tables, seq_lens)
    got = paged_decode_attention_pallas(
        q, k_pages, v_pages, page_tables, seq_lens, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(expect), rtol=2e-5, atol=2e-5
    )


def test_paged_decode_kernel_bf16():
    q, k_pages, v_pages, page_tables, seq_lens = make_case(seed=4)
    q = q.astype(jnp.bfloat16)
    k_pages = k_pages.astype(jnp.bfloat16)
    v_pages = v_pages.astype(jnp.bfloat16)
    expect = paged_decode_attention(q, k_pages, v_pages, page_tables, seq_lens)
    got = paged_decode_attention_pallas(
        q, k_pages, v_pages, page_tables, seq_lens, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(got, np.float32),
        np.asarray(expect, np.float32),
        rtol=2e-2,
        atol=2e-2,
    )


# ---------------------------------------------------------- flash prefill

def _prefill_case(B=2, S=256, H=4, KV=2, hd=128, seed=0, lens=None):
    from vgate_tpu.ops.attention import causal_prefill_attention

    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, KV, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, KV, hd)), jnp.float32)
    if lens is None:
        lens = rng.integers(1, S + 1, size=B)
    seq_lens = jnp.asarray(lens, jnp.int32)
    expect = causal_prefill_attention(q, k, v, seq_lens)
    return q, k, v, seq_lens, expect


@pytest.mark.parametrize("lens", [None, [1, 256], [255, 130]])
def test_flash_prefill_kernel_matches_oracle(lens):
    from vgate_tpu.ops.pallas.flash_prefill import (
        flash_prefill_attention_pallas,
    )

    q, k, v, seq_lens, expect = _prefill_case(
        lens=lens, seed=7 if lens is None else 8
    )
    got = flash_prefill_attention_pallas(
        q, k, v, seq_lens, block_q=128, block_k=128, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(expect), rtol=2e-5, atol=2e-5
    )


def test_flash_prefill_kernel_serving_bucket_1024():
    """Parity at a serving-sized bucket (VERDICT r1 item 2)."""
    from vgate_tpu.ops.pallas.flash_prefill import (
        flash_prefill_attention_pallas,
    )

    q, k, v, seq_lens, expect = _prefill_case(
        B=1, S=1024, H=2, KV=1, hd=64, seed=9, lens=[900]
    )
    got = flash_prefill_attention_pallas(
        q, k, v, seq_lens, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(expect), rtol=2e-5, atol=2e-5
    )


def test_flash_prefill_kernel_gqa_and_offset():
    """GQA group mapping + chunked-prefill q_offset: a 128-row query chunk
    at global offset 128 must reproduce rows [128:256] of the full pass."""
    from vgate_tpu.ops.pallas.flash_prefill import (
        flash_prefill_attention_pallas,
    )

    q, k, v, seq_lens, expect = _prefill_case(
        B=1, S=256, H=8, KV=4, seed=10, lens=[256]
    )
    got = flash_prefill_attention_pallas(
        q[:, 128:], k, v, seq_lens,
        q_offsets=jnp.asarray([128], jnp.int32),
        block_q=128, block_k=128, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(expect[:, 128:]), rtol=2e-5, atol=2e-5
    )


def test_paged_decode_kernel_sliding_window_matches_jnp():
    """Gemma-2 local attention in the kernel: window mask + below-window
    chunk skip must equal the jnp twin's windowed gather, including a
    window that starts mid-chunk and one beyond a chunk boundary."""
    q, k_pages, v_pages, page_tables, seq_lens = make_case(
        lens=[200, 255, 64, 3], seed=5
    )
    for win in (16, 100, 130):  # mid-page, mid-chunk, cross-chunk
        w = jnp.asarray(win, jnp.int32)
        expect = paged_decode_attention(
            q, k_pages, v_pages, page_tables, seq_lens, window=w
        )
        got = paged_decode_attention_pallas(
            q, k_pages, v_pages, page_tables, seq_lens, window=w,
            interpret=True,
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(expect), rtol=2e-5, atol=2e-5,
            err_msg=f"window={win}",
        )
    # window=0 (global layers of a sliding-window model) == no window
    got0 = paged_decode_attention_pallas(
        q, k_pages, v_pages, page_tables, seq_lens,
        window=jnp.asarray(0, jnp.int32), interpret=True,
    )
    expect0 = paged_decode_attention(
        q, k_pages, v_pages, page_tables, seq_lens
    )
    np.testing.assert_allclose(
        np.asarray(got0), np.asarray(expect0), rtol=2e-5, atol=2e-5
    )


def test_paged_decode_kernel_softcap_and_scale_match_jnp():
    """Score softcapping and the decoupled query scale (Gemma-2's
    query_pre_attn_scalar) in the kernel vs the jnp twin."""
    q, k_pages, v_pages, page_tables, seq_lens = make_case(seed=6)
    expect = paged_decode_attention(
        q, k_pages, v_pages, page_tables, seq_lens,
        softcap=50.0, scale=0.25,
    )
    got = paged_decode_attention_pallas(
        q, k_pages, v_pages, page_tables, seq_lens,
        softcap=50.0, scale=0.25, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(expect), rtol=2e-5, atol=2e-5
    )


def test_flash_prefill_kernel_window_softcap_scale():
    """Gemma-2 prefill in the kernel: sliding window (with the dead-block
    skip), score softcap and the decoupled query scale vs the jnp twin."""
    from vgate_tpu.ops.attention import flash_prefill_attention
    from vgate_tpu.ops.pallas.flash_prefill import (
        flash_prefill_attention_pallas,
    )

    rng = np.random.default_rng(31)
    B, S, H, KV, hd = 2, 512, 4, 2, 128
    q = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, KV, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, KV, hd)), jnp.float32)
    lens = jnp.asarray([301, 512], jnp.int32)
    # window smaller than a k-block (128) AND spanning blocks
    # compare only rows < seq_len: once a window applies, padding rows
    # (q_pos >= seq_len + window) have NO valid keys, and fully-masked
    # rows are garbage-by-design in both implementations (the engine
    # discards them); real rows must match exactly
    valid = np.arange(S)[None, :] < np.asarray(lens)[:, None]  # [B, S]
    for win in (48, 200):
        w = jnp.asarray(win, jnp.int32)
        expect = flash_prefill_attention(
            q, k, v, lens, block_k=128, window=w, softcap=50.0, scale=0.05
        )
        got = flash_prefill_attention_pallas(
            q, k, v, lens, block_q=128, block_k=128, interpret=True,
            window=w, softcap=50.0, scale=0.05,
        )
        np.testing.assert_allclose(
            np.asarray(got)[valid], np.asarray(expect)[valid],
            rtol=2e-5, atol=2e-5, err_msg=f"window={win}",
        )
    # window=0 == global
    got0 = flash_prefill_attention_pallas(
        q, k, v, lens, block_q=128, block_k=128, interpret=True,
        window=jnp.asarray(0, jnp.int32),
    )
    expect0 = flash_prefill_attention(q, k, v, lens, block_k=128)
    np.testing.assert_allclose(
        np.asarray(got0), np.asarray(expect0), rtol=2e-5, atol=2e-5
    )


# every form the prompt kernel is launched in, at 128 rows in 32-row
# blocks (a 4 x 4 grid of tiles a head: tiles under the diagonal and
# inside the lengths are interior, the others edge or dead): (q rows,
# H, KV, lengths, the launch's arguments).  The lengths end inside a
# block (77), on a block's edge (96, 64) and at the bucket's end (128)
_TILE_FORMS = {
    "plain": (128, 4, 4, [128, 77], {}),
    "plain-block-edge": (128, 4, 4, [96, 64], {}),
    "gqa": (128, 8, 2, [128, 77], {}),
    "skip-padding": (128, 4, 2, [50, 128], {"skip_padding": True}),
    "q-offsets": (64, 4, 2, [128, 90], {"q_offsets": [64, 32]}),
    "window-scale": (128, 4, 2, [128, 77], {"window": 70, "scale": 0.2}),
    "window-softcap-scale": (128, 4, 2, [128, 77], {
        "window": 70, "softcap": 30.0, "scale": 0.2}),
    "window-inside-a-block": (128, 4, 2, [96, 128], {"window": 9}),
    "k-starts": (64, 4, 2, [128, 100], {
        "q_offsets": [64, 64], "k_starts": [16, 40], "skip_padding": True}),
    "band": (128, 4, 2, [100, 128], {
        "window": 33, "band": 3, "block_q": 64}),
    "masked": (128, 4, 4, [128, 77], {"mask": 0.3, "skip_padding": True}),
    "masked-gqa": (128, 8, 2, [96, 128], {"mask": 0.3,
                                          "skip_padding": True}),
    "masked-sparse": (128, 8, 4, [128, 64], {"mask": 0.05,
                                             "skip_padding": True}),
}


def _tile_form_case(form):
    """(q, k, v, lens, the kernel's arguments, the jnp oracle's rows,
    which rows are real) of one of ``_TILE_FORMS``."""
    from vgate_tpu.ops import dsa
    from vgate_tpu.ops.attention import flash_prefill_attention

    Sq, H, KV, lens, kw = _TILE_FORMS[form]
    kw = dict(kw)
    B, Sk, hd = len(lens), 128, 32
    rng = np.random.default_rng(54)
    q = jnp.asarray(rng.normal(size=(B, Sq, H, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, Sk, KV, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, Sk, KV, hd)), jnp.float32)
    seq_lens = jnp.asarray(lens, jnp.int32)
    for name in ("q_offsets", "k_starts"):
        if name in kw:
            kw[name] = jnp.asarray(kw[name], jnp.int32)
    offsets = np.asarray(kw.get("q_offsets", [0] * B))
    real = (offsets[:, None] + np.arange(Sq)[None, :]
            < np.asarray(lens)[:, None])
    if "mask" in kw:
        causal = np.tri(Sk, dtype=bool)
        mask = (rng.uniform(size=(B, Sk, Sk)) < kw["mask"]) & causal
        mask |= np.eye(Sk, dtype=bool)  # a row attends to itself
        # rows 64..95 pick nothing in the tile of keys 32..63: a row
        # whose whole tile is hidden, among tiles that are interior
        mask[:, 64:96, 32:64] = False
        kw["mask"] = jnp.asarray(mask.astype(np.int8))
        want = dsa.masked_attention(q, k, v, kw["mask"], hd ** -0.5)
    else:
        want = flash_prefill_attention(
            q, k, v, seq_lens, block_k=32, q_offset=kw.get("q_offsets"),
            softcap=kw.get("softcap", 0.0), window=kw.get("window"),
            scale=kw.get("scale"), k_start=kw.get("k_starts"))
    kw.setdefault("block_q", 32)
    return q, k, v, seq_lens, {"block_k": 32, **kw}, want, real


@pytest.mark.fast  # tier-1: the prompt kernel every family launches
@pytest.mark.parametrize("form", list(_TILE_FORMS))
def test_flash_prefill_interior_tiles_are_the_edge_body_bit_for_bit(form):
    """A tile that neither the diagonal, a length, a window nor a first
    key cuts runs a body without position tests (and under a selection
    the int8 tile is a bias made once for a block of heads): the rows
    are the jnp oracle's at the kernel's tolerance, and BIT FOR BIT
    those of the same kernel with every tile through the edge body."""
    from vgate_tpu.ops.pallas.flash_prefill import (
        flash_prefill_attention_pallas,
    )

    q, k, v, lens, kw, want, real = _tile_form_case(form)
    got = np.asarray(flash_prefill_attention_pallas(
        q, k, v, lens, interpret=True, **kw))
    edge = np.asarray(flash_prefill_attention_pallas(
        q, k, v, lens, interpret=True, _all_edge=True, **kw))
    if kw.get("softcap"):
        # XLA:CPU makes ONE fused multiply-add of the cap's product and
        # the subtraction of the maximum where no select stands between
        # them, as in the interior body: interpret mode's last bit, not
        # Mosaic's (the probe's softcap row holds the bits on the chip)
        np.testing.assert_allclose(got, edge, rtol=0, atol=1e-6)
    else:
        assert np.array_equal(got, edge)
    np.testing.assert_allclose(
        got[real], np.asarray(want)[real], rtol=2e-5, atol=2e-5)
    if kw.get("skip_padding"):  # blocks of padding rows come out zero
        whole = real.reshape(real.shape[0], -1, kw["block_q"]).any(-1)
        assert not got.reshape(
            whole.shape + (kw["block_q"], -1))[~whole].any()


@pytest.mark.fast  # tier-1: the prompt kernel every family launches
@pytest.mark.parametrize("form", list(_TILE_FORMS))
def test_tile_counts_is_the_kernels_predicate_counted(form):
    """``tile_counts`` (what ``totals.prefill_attn`` books) against a
    count, element by element, of the tests an edge tile makes: a tile
    is live where some element passes, interior where every one does."""
    from vgate_tpu.ops.pallas.flash_prefill import tile_counts

    q, k, _, lens, kw, _, _ = _tile_form_case(form)
    Sq, Sk, bq, bk = q.shape[1], k.shape[1], kw["block_q"], kw["block_k"]
    window, band = kw.get("window", 0), kw.get("band", 0)
    offsets = np.asarray(kw.get("q_offsets", [0] * len(lens)))
    firsts = np.asarray(kw.get("k_starts", [0] * len(lens)))
    tiles = interior = 0
    for b, n in enumerate(np.asarray(lens)):
        q_pos = offsets[b] + np.arange(Sq)[:, None]
        k_pos = np.arange(Sk)[None, :]
        seen = (k_pos <= q_pos) & (k_pos < n) & (k_pos >= firsts[b])
        if window:
            seen &= q_pos - k_pos < window
        for qi in range(Sq // bq):
            if kw.get("skip_padding") and offsets[b] + qi * bq >= n:
                continue
            for ki in range(Sk // bk):
                last = (qi + 1) * (bq // bk) - 1  # a band ends here
                if band and not last - band < ki <= last:
                    continue
                tile = seen[qi * bq:(qi + 1) * bq, ki * bk:(ki + 1) * bk]
                # (the kernel keeps a tile by its corners: a tile that
                # the window alone empties is dead either way)
                tiles += bool(tile.any())
                interior += bool(tile.all())
    got = tile_counts(
        [int(n) for n in np.asarray(lens)], Sq, Sk, bq, bk,
        q_offsets=offsets.tolist() if "q_offsets" in kw else None,
        window=window, band=band,
        k_starts=firsts.tolist() if "k_starts" in kw else None,
        skip_padding=kw.get("skip_padding", False))
    assert got == (tiles, interior)
    # (a window shorter than a block leaves no tile whole)
    assert interior > 0 or 0 < window < bq + bk


@pytest.mark.fast  # tier-1: the prompt kernel every family launches
@pytest.mark.parametrize("form", [
    form for form, case in _TILE_FORMS.items() if "band" not in case[4]])
def test_a_dead_step_fetches_no_tile(form):
    """The index map of the K, V and mask blocks (``_key_block``): a
    live step holds its own key block; every step behind a query
    block's last live one names THAT block again (the pipeline copies a
    block only when its index changes), and a query block with no live
    step names one block throughout."""
    from vgate_tpu.ops.pallas.flash_prefill import _key_block, _live

    q, k, _, lens, kw, _, _ = _tile_form_case(form)
    Sq, Sk, bq, bk = q.shape[1], k.shape[1], kw["block_q"], kw["block_k"]
    offsets = np.asarray(kw.get("q_offsets", [0] * len(lens))).tolist()
    firsts = np.asarray(kw.get("k_starts", [0] * len(lens))).tolist()
    dead_steps = 0
    for b, n in enumerate(np.asarray(lens).tolist()):
        for qi in range(Sq // bq):
            held = [int(_key_block(qi, ki, bq, bk, n, offsets[b],
                                   kw.get("skip_padding", False)))
                    for ki in range(Sk // bk)]
            live = [bool(_live(offsets[b] + qi * bq, ki * bk, bq, bk, n,
                               kw.get("window", 0), firsts[b],
                               kw.get("skip_padding", False)))
                    for ki in range(Sk // bk)]
            assert all(h == ki for ki, h in enumerate(held) if live[ki])
            last = max((ki for ki in range(len(live)) if live[ki]),
                       default=-1)
            behind = held[last + 1:]
            dead_steps += len(behind)
            assert len(set(held[max(last, 0):])) == 1, (qi, held, live)
    assert dead_steps > 0


@pytest.mark.fast  # tier-1: the prompt kernel every family launches
@pytest.mark.parametrize("model, S, lens, by_kind", [
    # 11 live query blocks of 1,024: 66 tiles under the diagonal, 55 of
    # them off it with their last key inside 11,000
    ("tiny-dsa-moe", 16384, [11000], {"attn_layers": (66, 55)}),
    ("tiny-keye-dsa", 16384, [12288], {"attn_layers": (78, 66)}),
    ("tiny-mla-moe", 8192, [5550], {"attn_layers": (21, 15)}),
    # a window layer's band: three key blocks of 128 a query block of
    # 256 (22 of them live, the first without a block before it), every
    # one cut by the window of 8 or the diagonal
    ("tiny-swa-moe", 8192, [5550], {"attn_layers": (21, 15),
                                    "swa_layers": (22 * 3 - 1, 0)}),
    # EVA: two windows of 32 a row against [16 summaries | the window]
    # in key blocks of 16: the first window's two own blocks, the second
    # window's and the block of the first's 8 summaries (cut by
    # ``k_starts``)
    ("tiny-eva", 64, [50, 64], {"attn_layers": (2 * (2 + 3), 0)}),
    # the dense stack's packed group in 256-row blocks: 15 tiles a row of
    # 1,280 (10 interior), one edge tile a padding row
    ("Qwen/Qwen2.5-1.5B-Instruct", 2048, [1280] * 5 + [1] * 3,
     {"attn_layers": (5 * 15 + 3, 5 * 10)}),
    # a short bucket is one tile a row, cut by its diagonal
    ("Qwen/Qwen2.5-1.5B-Instruct", 128, [100] * 8, {"attn_layers": (8, 0)}),
])
def test_a_prompt_programs_tiles_by_the_cells_shapes(model, S, lens, by_kind):
    """models/decoder.py ``prefill_attn_tiles`` (what the engine books
    into ``totals.prefill_attn``) at the long cells' shapes against
    counts by hand, a layer of each kind: five of six tiles interior
    where a prompt of 11-12 k rows runs in 1,024-row blocks, none in a
    window layer's band or a short bucket."""
    from vgate_tpu.models.decoder import prefill_attn_tiles
    from vgate_tpu.models.specs import spec_for_model_id

    spec = spec_for_model_id(model)
    assert prefill_attn_tiles(spec, S, lens) == tuple(
        sum(getattr(spec, layers) * count[i]
            for layers, count in by_kind.items()) for i in range(2))


def test_paged_multitok_kernel_matches_suffix_attention():
    """The speculative-verify kernel vs the jnp suffix path: S candidate
    rows per slot, varying input_lens, window on/off, softcap+scale."""
    from vgate_tpu.ops.attention import paged_suffix_attention
    from vgate_tpu.ops.pallas.paged_attention import (
        paged_multitok_attention_pallas,
    )

    rng = np.random.default_rng(41)
    B, S, H, KV, hd, ps, n_pages = 3, 4, 4, 2, 32, 4, 16
    P = 1 + B * n_pages
    q = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
    k_pages = jnp.asarray(rng.normal(size=(KV, P, ps, hd)), jnp.float32)
    v_pages = jnp.asarray(rng.normal(size=(KV, P, ps, hd)), jnp.float32)
    pt = jnp.asarray(
        1 + np.arange(B * n_pages, dtype=np.int32).reshape(B, n_pages)
    )
    positions0 = jnp.asarray([10, 37, 0], jnp.int32)
    input_lens = jnp.asarray([4, 2, 1], jnp.int32)
    total = positions0 + input_lens

    cases = [
        dict(softcap=0.0, window=None, scale=None),
        dict(softcap=30.0, window=jnp.asarray(16, jnp.int32), scale=0.1),
        dict(softcap=0.0, window=jnp.asarray(0, jnp.int32), scale=None),
    ]
    valid = np.arange(S)[None, :] < np.asarray(input_lens)[:, None]
    for case in cases:
        expect = paged_suffix_attention(
            q, k_pages, v_pages, pt, positions0, total, **case
        )
        got = paged_multitok_attention_pallas(
            q, k_pages, v_pages, pt, positions0, input_lens,
            interpret=True, **case,
        )
        np.testing.assert_allclose(
            np.asarray(got)[valid], np.asarray(expect)[valid],
            rtol=2e-5, atol=2e-5, err_msg=str(case),
        )


def test_paged_multitok_kernel_single_row_matches_decode_kernel():
    """With S=1 the multi-token kernel degenerates to the decode kernel."""
    from vgate_tpu.ops.pallas.paged_attention import (
        paged_multitok_attention_pallas,
    )

    q, k_pages, v_pages, page_tables, seq_lens = make_case(
        lens=[9, 33, 64, 128], seed=42
    )
    B, H, hd = q.shape
    expect = paged_decode_attention_pallas(
        q, k_pages, v_pages, page_tables, seq_lens, interpret=True
    )
    got = paged_multitok_attention_pallas(
        q[:, None], k_pages, v_pages, page_tables, seq_lens - 1,
        jnp.ones((B,), jnp.int32), interpret=True,
    )[:, 0]
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(expect), rtol=2e-5, atol=2e-5
    )


# ------------------------------------------------- fused int4 dequant GEMM

def _int4_case(lead, in_dim, out, seed=0):
    from vgate_tpu.ops.quant import quantize_tensor

    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.normal(size=(in_dim, out)), jnp.float32)
    qt = quantize_tensor(w, bits=4)  # PackedQTensor
    x = jnp.asarray(rng.normal(size=(*lead, in_dim)), jnp.float32)
    return x, qt


@pytest.mark.parametrize(
    "lead,in_dim,out",
    [
        ((4,), 64, 128),       # tiny decode-shaped
        ((2, 8), 64, 64),      # prefill-shaped leading dims
        ((12,), 256, 128),     # multi-in-tile accumulation (T_in=128 x 2)
    ],
)
def test_int4_matmul_kernel_matches_packed_einsum(lead, in_dim, out):
    from vgate_tpu.ops.pallas.quant_matmul import int4_matmul_pallas
    from vgate_tpu.ops.quant import packed_einsum

    x, qt = _int4_case(lead, in_dim, out)
    expect = packed_einsum("...d,dh->...h", x, qt) * qt.scale
    got = int4_matmul_pallas(
        x, qt.q_packed, qt.scale, interpret=True
    )
    assert got.shape == (*lead, out)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(expect), rtol=2e-4, atol=2e-4
    )


def test_int4_matmul_kernel_f32_out_and_ragged_rows():
    """lm_head shape class: f32 accumulation/output and a row count that
    is not a multiple of the row tile (padding path)."""
    from vgate_tpu.ops.pallas.quant_matmul import int4_matmul_pallas
    from vgate_tpu.ops.quant import packed_einsum

    x, qt = _int4_case((5,), 64, 128, seed=3)
    xb = x.astype(jnp.bfloat16)
    expect = (
        packed_einsum(
            "...d,dv->...v", xb, qt,
            preferred_element_type=jnp.float32,
        )
        * qt.scale
    )
    got = int4_matmul_pallas(
        xb, qt.q_packed, qt.scale, out_dtype=jnp.float32,
        interpret=True,
    )
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(expect), rtol=2e-2, atol=2e-2
    )


def test_quant_kernel_gate_dispatch(monkeypatch):
    """weighted_einsum routes 2D packed weights through the kernel when
    the per-call ``quant_kernel`` flag (threaded from
    ModelSpec.quant_kernel) is on, and the results agree with the jnp
    path."""
    from vgate_tpu.ops import quant

    x, qt = _int4_case((4,), 64, 128, seed=5)
    base = quant.weighted_einsum("...d,dh->...h", x, qt)
    called = {}

    import vgate_tpu.ops.pallas.quant_matmul as qm

    real_kernel = qm.int4_matmul_pallas

    def fake_kernel(xx, qp, sc, out_dtype=None):
        called["yes"] = True
        return real_kernel(
            xx, qp, sc, out_dtype=out_dtype, interpret=True
        )

    monkeypatch.setattr(qm, "int4_matmul_pallas", fake_kernel)
    got = quant.weighted_einsum("...d,dh->...h", x, qt, quant_kernel=True)
    assert called.get("yes")
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(base), rtol=2e-4, atol=2e-4
    )
    # default-off: no kernel call without the flag
    called.clear()
    quant.weighted_einsum("...d,dh->...h", x, qt)
    assert not called
    # expert (3D) weights never take the kernel, flag or not
    from vgate_tpu.ops.quant import quantize_expert_stacked

    rng = np.random.default_rng(6)
    we = jnp.asarray(rng.normal(size=(2, 3, 16, 32)), jnp.float32)
    qe = quantize_expert_stacked(we, bits=4)
    assert not quant._use_quant_kernel("ecd,edf->ecf", qe)


def test_paged_decode_kernel_layer_indexed():
    """Carry-threaded decode passes the FULL stacked [L, KV, P, ps, hd]
    pool plus a layer index; the kernel's layer-indexed DMA must match
    slicing that layer out first (interpret mode)."""
    L = 3
    q, k_pages, v_pages, page_tables, seq_lens = make_case(
        B=2, H=4, KV=2, hd=128, ps=16, pages_per_seq=4, seed=12,
        lens=[17, 55],
    )
    rng = np.random.default_rng(13)
    stacked_k = jnp.asarray(
        rng.normal(size=(L, *k_pages.shape)), jnp.float32
    )
    stacked_v = jnp.asarray(
        rng.normal(size=(L, *v_pages.shape)), jnp.float32
    )
    for layer in range(L):
        expect = paged_decode_attention_pallas(
            q, stacked_k[layer], stacked_v[layer], page_tables, seq_lens,
            interpret=True,
        )
        got = paged_decode_attention_pallas(
            q, stacked_k, stacked_v, page_tables, seq_lens,
            layer=jnp.asarray(layer, jnp.int32), interpret=True,
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(expect), rtol=1e-5, atol=1e-5,
            err_msg=f"layer {layer}",
        )


def test_multitok_kernel_layer_indexed():
    """Carry-threaded spec verify: the multitok kernel with the stacked
    pool + layer index must match slicing the layer out first."""
    from vgate_tpu.ops.pallas.paged_attention import (
        paged_multitok_attention_pallas,
    )

    L, B, S, H, KV, hd, ps, pps = 2, 2, 4, 4, 2, 128, 16, 4
    rng = np.random.default_rng(21)
    P = 1 + B * pps
    q = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
    stacked_k = jnp.asarray(
        rng.normal(size=(L, KV, P, ps, hd)), jnp.float32
    )
    stacked_v = jnp.asarray(
        rng.normal(size=(L, KV, P, ps, hd)), jnp.float32
    )
    pt = jnp.asarray(
        1 + np.arange(B * pps).reshape(B, pps), jnp.int32
    )
    pos0 = jnp.asarray([9, 30], jnp.int32)
    in_lens = jnp.asarray([4, 2], jnp.int32)
    for layer in range(L):
        expect = paged_multitok_attention_pallas(
            q, stacked_k[layer], stacked_v[layer], pt, pos0, in_lens,
            interpret=True,
        )
        got = paged_multitok_attention_pallas(
            q, stacked_k, stacked_v, pt, pos0, in_lens,
            layer=jnp.asarray(layer, jnp.int32), interpret=True,
        )
        # rows past input_lens are unspecified; compare valid rows only
        for b in range(B):
            n = int(in_lens[b])
            np.testing.assert_allclose(
                np.asarray(got[b, :n]), np.asarray(expect[b, :n]),
                rtol=1e-5, atol=1e-5, err_msg=f"layer {layer} b {b}",
            )


@pytest.mark.parametrize(
    "lead,in_dim,out",
    [((4,), 64, 128), ((2, 8), 128, 64), ((5,), 256, 128)],
)
def test_int8_matmul_kernel_matches_einsum(lead, in_dim, out):
    """int8 fused-dequant kernel vs the jnp QTensor einsum path."""
    from vgate_tpu.ops.pallas.quant_matmul import int8_matmul_pallas
    from vgate_tpu.ops.quant import quantize_tensor

    rng = np.random.default_rng(31)
    w = jnp.asarray(rng.normal(size=(in_dim, out)), jnp.float32)
    qt = quantize_tensor(w, bits=8)
    x = jnp.asarray(rng.normal(size=(*lead, in_dim)), jnp.float32)
    expect = jnp.einsum("...d,dh->...h", x, qt.q.astype(x.dtype)) * qt.scale
    got = int8_matmul_pallas(x, qt.q, qt.scale, interpret=True)
    assert got.shape == (*lead, out)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(expect), rtol=2e-4, atol=2e-4
    )


def test_int8_kernel_gate_dispatch(monkeypatch):
    """weighted_einsum routes 2D int8 QTensors through the kernel when
    quant_kernel is set, and never for stacked (3D) weights."""
    from vgate_tpu.ops import quant

    rng = np.random.default_rng(32)
    w = jnp.asarray(rng.normal(size=(64, 128)), jnp.float32)
    qt = quant.quantize_tensor(w, bits=8)
    x = jnp.asarray(rng.normal(size=(4, 64)), jnp.float32)
    base = quant.weighted_einsum("...d,dh->...h", x, qt)
    called = {}

    import vgate_tpu.ops.pallas.quant_matmul as qm

    real = qm.int8_matmul_pallas

    def fake(xx, qq, sc, out_dtype=None):
        called["yes"] = True
        return real(xx, qq, sc, out_dtype=out_dtype, interpret=True)

    monkeypatch.setattr(qm, "int8_matmul_pallas", fake)
    got = quant.weighted_einsum("...d,dh->...h", x, qt, quant_kernel=True)
    assert called.get("yes")
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(base), rtol=2e-4, atol=2e-4
    )
    ws = quant.quantize_stacked(
        jnp.asarray(rng.normal(size=(2, 16, 32)), jnp.float32), bits=8
    )
    assert not quant._use_quant_kernel("...d,dh->...h", ws)


def test_suffix_prefill_pallas_matches_jnp():
    """prefill_suffix_forward(use_pallas=True) routes the context
    attention through the multitok kernel (the chunked/long-context
    prefill hot path); logits and KV must match the jnp suffix path
    for both page-aligned prefixes and varying suffix lengths."""
    from vgate_tpu.models.decoder import (
        init_params, prefill_forward, prefill_suffix_forward,
    )
    from vgate_tpu.models.specs import TINY_DENSE as spec

    ps, pps, B = 16, 4, 2  # kernel-friendly page size
    params = init_params(spec, jax.random.PRNGKey(5), jnp.float32)
    P = 1 + B * pps
    shape = (spec.num_layers, spec.num_kv_heads, P, ps, spec.head_dim)
    k0 = jnp.zeros(shape, jnp.float32)
    v0 = jnp.zeros(shape, jnp.float32)
    pt = jnp.asarray(
        1 + np.arange(B * pps).reshape(B, pps), jnp.int32
    )
    rng = np.random.default_rng(6)
    # resident prefix: one full page per row
    prefix = jnp.asarray(
        rng.integers(2, spec.vocab_size, (B, ps)), jnp.int32
    )
    _, kf, vf = prefill_forward(
        params, spec, prefix, jnp.full((B,), ps, jnp.int32), k0, v0,
        pt[:, :1],
    )
    S = 16  # suffix bucket
    sfx = jnp.asarray(
        rng.integers(2, spec.vocab_size, (B, S)), jnp.int32
    )
    args = (
        params, spec, sfx, jnp.full((B,), ps, jnp.int32),
        jnp.asarray([S, 5], jnp.int32), kf, vf, pt[:, 1:2], pt[:, :2],
    )
    import unittest.mock as mock

    from vgate_tpu.ops.pallas import paged_attention as pa

    real = pa.paged_multitok_attention_pallas

    def interp(*a, **kw):
        kw["interpret"] = True
        return real(*a, **kw)

    expect = prefill_suffix_forward(*args, use_pallas=False)
    with mock.patch.object(
        pa, "paged_multitok_attention_pallas", side_effect=interp
    ):
        got = prefill_suffix_forward(*args, use_pallas=True)
    np.testing.assert_allclose(
        np.asarray(got[0]), np.asarray(expect[0]), rtol=2e-4, atol=2e-4
    )
    np.testing.assert_allclose(
        np.asarray(got[1]), np.asarray(expect[1]), rtol=1e-5, atol=1e-5
    )


_BLOCKING_WAITS = """
import sys
sys.path[:0] = {paths!r}
import jax
from jax.experimental.pallas import tpu as pltpu
from tests.pallas_cases import trip_case
from vgate_tpu.ops.pallas.paged_attention import paged_decode_attention_pallas
args, kw, _ = trip_case({case!r})
jax.block_until_ready(paged_decode_attention_pallas(
    *args, interpret=pltpu.InterpretParams(), **{options!r}, **kw))
"""


def _under_the_tpu_interpreter(script, **fields):
    """Run `script` (a template that takes `paths` and `fields`) in a
    process of its own, which is killed if it does not come back."""
    import os
    import subprocess
    import sys

    here = os.path.dirname(os.path.abspath(__file__))
    code = script.format(paths=[os.path.dirname(here), here], **fields)
    try:
        done = subprocess.run(
            [sys.executable, "-c", code], timeout=300, capture_output=True,
            text=True, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
    except subprocess.TimeoutExpired:
        pytest.fail("the kernel waits for a copy it never started")
    assert done.returncode == 0, done.stderr[-2000:]


@pytest.mark.fast
@pytest.mark.parametrize(
    "case, options",
    [
        ("odd-item-count", {"items": 2}),
        ("both-items-last-chunks", {"items": 2}),
        ("layer-pools", {"items": 1}),
        ("odd-item-count", {"items": 2, "hollow": True}),
        ("both-items-last-chunks", {"items": 1, "hollow": True}),
        # the chunk buffers forced: one, two and three trips' chunks in
        # flight behind a trip of two, one chunk behind a trip of one
        ("window-first-chunk-not-0", {"items": 2, "buffers": 6}),
        ("pair-spans-two-slots", {"items": 2, "buffers": 4}),
        ("dead-slots-between-live", {"items": 2, "buffers": 8}),
        ("pair-spans-two-slots", {"items": 1, "buffers": 2}),
    ],
    ids=["two-items", "two-items-both-last", "one-item", "hollow-two-items",
         "hollow-one-item", "two-trips-in-flight",
         "one-trip-in-flight", "three-trips-in-flight",
         "one-item-one-in-flight"],
)
def test_decode_kernel_waits_for_no_more_than_it_started(case, options):
    """The plain interpreter does not block on a DMA semaphore, the chip
    does: a wait for bytes that no copy brings hangs it (the hollow
    kernel's first version waited for staging pages it never sent, and
    cost a chip call).  The TPU interpreter blocks as the chip does, so
    the kernel runs under it in a process of its own, which is killed if
    it does not come back.  The cases that name no `buffers` run at the
    depth `_decode_sizes` serves (two trips' chunks in flight)."""
    _under_the_tpu_interpreter(_BLOCKING_WAITS, case=case, options=options)


_FIVE_PAGE_CHUNKS = """
import sys
sys.path[:0] = {paths!r}
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas import tpu as pltpu
from tests.pallas_cases import make_case
from vgate_tpu.ops.pallas.paged_attention import paged_decode_attention_pallas
lens = [80, 48, 0, 33, 80, 80, 7]
case = tuple(
    x.astype(jnp.bfloat16) if x.dtype == jnp.float32 else x
    for x in make_case(B=len(lens), H=4, KV=2, hd=128, ps=16,
                       pages_per_seq=5, lens=lens, seed=5)
)
got = paged_decode_attention_pallas(
    *case, interpret=pltpu.InterpretParams(), **{options!r})
want = paged_decode_attention_pallas(*case, interpret=True, items=1)
np.testing.assert_array_equal(
    np.asarray(got, np.float32), np.asarray(want, np.float32))
"""


@pytest.mark.fast
@pytest.mark.parametrize(
    "options", [{"items": 2}, {"items": 2, "buffers": 4}, {"items": 1}],
    ids=["two-items", "two-items-one-trip-in-flight", "one-item"],
)
def test_decode_kernel_waits_for_every_page_of_a_five_page_chunk(options):
    """A ring of five pages a slot is a chunk of five: a trip of two
    such chunks waits for its 8 live pages as ONE whole buffer's bytes
    and three pages more, not as `8 // 5` buffers (which left three
    pages unwaited for: NaN under the TPU interpreter, whose copies land
    when they are waited for, and a race on the chip; no cell serves
    such a shape: a two-KV-head shard over rings)."""
    _under_the_tpu_interpreter(_FIVE_PAGE_CHUNKS, options=options)


def test_decode_kernel_bf16_pages_keep_float32_softmax_weights():
    """bf16 pages go to the MXU as they lie; the softmax weights must
    not drop to ONE bf16 term on the way.  Against the twin in float32 on
    the same values, the kernel stays under a tolerance that the twin
    with bf16 weights (what `probs.astype(v.dtype)` gives it) fails."""
    lens = [2048, 1500, 700, 257]
    q, k_pages, v_pages, page_tables, seq_lens = make_case(
        B=len(lens), H=12, KV=2, ps=32, pages_per_seq=64, lens=lens, seed=27
    )
    # float32 q holding bf16 values: the output stays float32, so the
    # comparison sees the weights' precision and not the output's
    q = q.astype(jnp.bfloat16).astype(jnp.float32)
    k16, v16 = k_pages.astype(jnp.bfloat16), v_pages.astype(jnp.bfloat16)
    exact = np.asarray(paged_decode_attention(
        q, k16.astype(jnp.float32), v16.astype(jnp.float32), page_tables,
        seq_lens,
    ))
    one_term = np.asarray(paged_decode_attention(
        q, k16, v16, page_tables, seq_lens
    ))
    got = np.asarray(paged_decode_attention_pallas(
        q, k16, v16, page_tables, seq_lens, interpret=True
    ))
    tol = 2e-5
    assert np.abs(got - exact).max() < tol
    assert np.abs(one_term - exact).max() > 2 * tol


def test_decode_forward_hands_the_kernel_length_zero_for_inactive_rows():
    """The model step's side of the contract: with the Pallas kernel an
    inactive slot reaches it as length 0 (so it costs nothing), the KV
    write still targets the trash page, every row's logits stay finite
    (the integrity guard reduces over all of them), and the active rows
    agree with the jnp path."""
    import unittest.mock as mock

    from vgate_tpu.models.decoder import decode_forward, init_params
    from vgate_tpu.models.specs import TINY_DENSE
    from vgate_tpu.ops.pallas import paged_attention as pa

    spec = TINY_DENSE
    B, ps, pages_per_seq = 3, 4, 4
    params = init_params(spec, jax.random.PRNGKey(0), jnp.float32)
    shape = (spec.num_layers, spec.num_kv_heads, 1 + B * pages_per_seq, ps,
             spec.head_dim)
    rng = np.random.default_rng(31)
    k = jnp.asarray(rng.normal(size=shape), jnp.float32)
    v = jnp.asarray(rng.normal(size=shape), jnp.float32)
    pt = jnp.asarray(
        np.arange(B * pages_per_seq, dtype=np.int32).reshape(B, -1) + 1
    )
    tokens = jnp.asarray([7, 11, 5], jnp.int32)
    positions = jnp.asarray([3, 0, 9], jnp.int32)
    active = jnp.asarray([True, False, True])

    expect, k_jnp, _ = decode_forward(
        params, spec, tokens, positions, k, v, pt, active=active,
        use_pallas=False,
    )
    real = pa.paged_decode_attention_pallas
    seen = []

    def interp(q, kp, vp, page_tables, seq_lens, **kw):
        seen.append(seq_lens)
        # zero exactly the inactive row (the trace sees the expression,
        # so check it by value on the way through)
        seq_lens = jax.lax.cond(
            jnp.array_equal(seq_lens, jnp.asarray([4, 0, 10])),
            lambda: seq_lens, lambda: seq_lens - 1000,
        )
        return real(q, kp, vp, page_tables, seq_lens, interpret=True, **kw)

    with mock.patch.object(
        pa, "paged_decode_attention_pallas", side_effect=interp
    ):
        got, k_pal, _ = jax.jit(
            lambda: decode_forward(
                params, spec, tokens, positions, k, v, pt, active=active,
                use_pallas=True,
            )
        )()
    assert seen, "use_pallas must reach the kernel"
    got = np.asarray(got)
    assert np.isfinite(got).all()
    live = np.asarray(active)
    np.testing.assert_allclose(
        got[live], np.asarray(expect)[live], rtol=2e-4, atol=2e-4
    )
    # the live rows' new K landed where the jnp path puts it (page 0 is
    # the trash page the inactive row writes)
    np.testing.assert_allclose(
        np.asarray(k_pal)[:, :, 1:], np.asarray(k_jnp)[:, :, 1:],
        rtol=2e-4, atol=2e-4,
    )


@pytest.mark.fast  # tier-1, as the kernel's write cases above
@pytest.mark.parametrize("model", ["tiny-dense", "tiny-hybrid"])
def test_decode_forward_kernel_writes_what_the_scatter_writes(model):
    """Two pages and a token of decode steps through ``decode_forward``
    on both write paths: the Pallas kernel that writes the token's page
    itself (interpret mode) and the jnp twin behind ``kv_write_tokens``.
    Same greedy tokens, same pools in every page but the trash page (the
    inactive row's token goes there only on the scatter's path)."""
    import functools
    import unittest.mock as mock

    from vgate_tpu.models import hybrid
    from vgate_tpu.models.decoder import (
        decode_forward, decode_kv_write, init_params,
    )
    from vgate_tpu.models.specs import spec_for_model_id
    from vgate_tpu.ops import gated_delta
    from vgate_tpu.ops.pallas import grouped_matmul
    from vgate_tpu.ops.pallas import paged_attention as pa

    spec = spec_for_model_id(model)
    assert decode_kv_write(spec, True) == "kernel"
    assert decode_kv_write(spec, False) == "scatter"
    assert decode_kv_write(spec, True, quantized=True) == "scatter"
    B, ps, pages_per_seq = 3, 4, 4
    steps = 2 * ps + 1
    params = init_params(spec, jax.random.PRNGKey(0), jnp.float32)
    shape = (spec.attn_layers, spec.num_kv_heads, 1 + B * pages_per_seq, ps,
             spec.head_dim)
    rng = np.random.default_rng(41)
    pools = tuple(
        jnp.asarray(rng.normal(size=shape), jnp.float32) for _ in range(2)
    )
    pt = jnp.asarray(
        np.arange(B * pages_per_seq, dtype=np.int32).reshape(B, -1) + 1
    )
    active = jnp.asarray([True, False, True])
    more = (
        {"state": hybrid.make_state(spec, B, jnp.float32)}
        if spec.is_hybrid else {}
    )

    @functools.partial(jax.jit, static_argnames="use_pallas")
    def run(k, v, more, use_pallas):
        tokens = jnp.asarray([7, 11, 5], jnp.int32)
        positions = jnp.asarray([3, 0, 0], jnp.int32)
        out = []
        for _ in range(steps):
            logits, k, v, *rest = decode_forward(
                params, spec, tokens, positions, k, v, pt, active=active,
                use_pallas=use_pallas, **more,
            )
            if rest:
                more = {"state": rest[0]}
            tokens = jnp.argmax(logits, -1).astype(jnp.int32)
            positions = positions + active
            out.append(tokens)
        return jnp.stack(out), k, v

    want_tokens, want_k, want_v = run(*pools, more, use_pallas=False)
    with mock.patch.multiple(
        pa, paged_decode_attention_pallas=functools.partial(
            pa.paged_decode_attention_pallas, interpret=True)
    ), mock.patch.multiple(
        gated_delta, gated_delta_step=functools.partial(
            gated_delta.gated_delta_step, interpret=True)
    ), mock.patch.multiple(
        grouped_matmul, grouped_matmul_pallas=functools.partial(
            grouped_matmul.grouped_matmul_pallas, interpret=True)
    ):
        got_tokens, got_k, got_v = run(*pools, more, use_pallas=True)
    live = np.asarray(active)
    np.testing.assert_array_equal(
        np.asarray(got_tokens)[:, live], np.asarray(want_tokens)[:, live]
    )
    for got, want, before in (
        (got_k, want_k, pools[0]), (got_v, want_v, pools[1]),
    ):
        np.testing.assert_allclose(
            np.asarray(got)[:, :, 1:], np.asarray(want)[:, :, 1:],
            rtol=2e-4, atol=2e-4,
        )
        # the inactive row's pages and the trash page: untouched
        np.testing.assert_array_equal(
            np.asarray(got)[:, :, [0, 5, 6, 7, 8]],
            np.asarray(before)[:, :, [0, 5, 6, 7, 8]],
        )
        # every live row's 2 * ps + 1 new rows differ from what was there
        assert not np.allclose(
            np.asarray(got)[:, :, 1:4], np.asarray(before)[:, :, 1:4]
        )
