"""``tiny-dsa-moe``'s parts one at a time (``tests/test_glm_dsa.py`` has
the forwards against the plain reference, and says what the preset is):
the expert layer's sixteen shares against the uncut reference's layer,
the selection's kernels in interpret mode against their jnp twins, and a
long prompt's pass in its own blocks of rows."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import manifest
from perfbench.references import glm_moe_dsa as ref
from tests import prompt_row_blocks as row_blocks
from vgate_tpu.models import specs
from vgate_tpu.ops import dsa, moe

SPEC = specs.spec_for_model_id("tiny-dsa-moe")
# the preset under the published config's keys, as the reference takes it
TINY = manifest.load_json(
    manifest.HERE, "configs", "glm-5.2-l5e16.json")["rehearse"]["model"]
TOPK = 16


def test_the_sixteen_shares_add_up_to_the_uncut_reference(at_a_time):
    """256 experts over sixteen chips, sixteen each, the router 256 wide
    in every share: the shares' routed parts plus the shared expert
    counted once are the uncut reference's layer."""
    spec = dataclasses.replace(
        SPEC, name="tiny-256", num_experts=256, router_width=256,
        experts_per_token=8)
    cfg = dict(TINY, n_routed_experts=256, router_width=256,
               num_experts_per_tok=8)
    lw = ref.draw_layer(cfg, 0, 1, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(3), (40, spec.hidden_size))
    with jax.default_matmul_precision("highest"):
        want = ref.moe(x, lw, cfg)
        shared = want - ref.moe(x, lw, cfg, shared=False)
    wrap = lambda k, v: v if k in ("router", "router_bias") else {"w": v}
    lp = {k: wrap(k, v) for k, v in lw.items()}
    total = jnp.zeros_like(x)
    for chip in range(16):
        first = 16 * chip
        cut = dataclasses.replace(
            spec, num_experts=16, first_expert=first,
            shared_expert_intermediate_size=0, n_shared_experts=0)
        held = {n: lw[n][first:first + 16] for n in ("gate", "up", "down")}
        part = dict(lp, **{n: {"w": w} for n, w in held.items()})
        _, stats = moe.expert_layer(x, part, cut, jax.nn.silu)
        extra = at_a_time(int(stats[1]))
        out, stats = moe.expert_layer(x, part, cut, jax.nn.silu)
        total = total + out
        with jax.default_matmul_precision("highest"):
            mine = ref.moe(x, dict(lw, **held), dict(
                cfg, n_routed_experts=16, first_expert=first), shared=False)
        assert np.abs(np.asarray(out - mine)).max() < 1e-5
        assert int(stats[0]) == 40 * 8 and int(stats[4]) == extra
    assert np.abs(np.asarray(total + shared - want)).max() < 1e-5


# ---------------------------------------------- the kernels, interpreted

def test_the_decode_scoring_kernel_is_the_twin_over_live_pages():
    from vgate_tpu.ops.attention import mla_gather_rows
    from vgate_tpu.ops.pallas.dsa import dsa_index_scores_pallas

    rng = np.random.default_rng(2)
    B, Hi, d, ps, n = 3, 4, 128, 8, 20  # 160 tokens a slot, CP 16: 2 trips
    keys = jnp.asarray(rng.normal(size=(2, 1, 64, ps, d)), jnp.float32)
    qi = jnp.asarray(rng.normal(size=(B, Hi, d)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(B, Hi)), jnp.float32)
    tables = jnp.asarray(rng.permutation(63)[:B * n].reshape(B, n) + 1)
    lens = jnp.asarray([150, 0, 37], jnp.int32)
    got = dsa_index_scores_pallas(qi, w, keys, tables, lens, 1,
                                  interpret=True)
    rows = mla_gather_rows(keys, tables, 1)
    want = dsa.index_scores(qi[:, None], w[:, None], rows)[:, 0]
    live = np.arange(n * ps)[None] < np.asarray(lens)[:, None]
    assert got.shape == (B, n * ps)
    assert np.all(np.isneginf(np.asarray(got)[~live]))
    np.testing.assert_allclose(np.asarray(got)[live],
                               np.asarray(want)[live], rtol=1e-4, atol=1e-4)


def test_the_prompt_scoring_kernel_is_the_twin_under_the_diagonal():
    from vgate_tpu.ops.pallas.dsa import dsa_prompt_scores_pallas

    rng = np.random.default_rng(4)
    R, T, Hi, d = 64, 256, 4, 128
    qi = jnp.asarray(rng.normal(size=(R, Hi, d)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(R, Hi)), jnp.float32)
    keys = jnp.asarray(rng.normal(size=(T, d)), jnp.float32)
    got = np.asarray(dsa_prompt_scores_pallas(
        qi, w, keys, 128, block_q=32, block_k=64, interpret=True))
    want = np.asarray(dsa.index_scores(qi[None], w[None], keys[None])[0])
    below = np.arange(T)[None] <= (128 + np.arange(R))[:, None]
    assert np.all(np.isneginf(got[~below]))
    np.testing.assert_allclose(got[below], want[below], rtol=1e-4, atol=1e-4)


def test_the_prompt_kernel_under_a_mask_is_the_masked_softmax():
    from vgate_tpu.ops.pallas.dsa import dsa_prefill_attention_pallas

    rng = np.random.default_rng(6)
    B, S, H, hd = 2, 128, 2, 32
    q, k, v = (jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
               for _ in range(3))
    lens = [128, 70]
    scores = jnp.asarray(rng.normal(size=(B, S, S)), jnp.float32)
    causal = np.tri(S, dtype=bool)[None]
    mask = (np.asarray(dsa.select_mask(
        jnp.where(causal, scores, -jnp.inf), TOPK)) & causal).astype(np.int8)
    # a query whose own block holds none of its pick is among them
    assert (mask[0, 100, 96:128].sum() == 0) or mask[0].sum(-1).max() == TOPK
    want = dsa.masked_attention(q, k, v, jnp.asarray(mask), hd ** -0.5)
    got = dsa_prefill_attention_pallas(
        q, k, v, jnp.asarray(lens, jnp.int32), jnp.asarray(mask),
        scale=hd ** -0.5, block_q=32, block_k=32, interpret=True)
    for b, n in enumerate(lens):
        np.testing.assert_allclose(
            np.asarray(got[b, :n]), np.asarray(want[b, :n]),
            rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("lens", [[128, 70], [96, 33], [64, 128]],
                         ids=lambda lens: "-".join(map(str, lens)))
def test_the_prompt_kernel_makes_its_bias_once_for_a_block_of_heads(lens):
    """The launch under a selection at the latent form's shape (a key
    head a query head): every head of the program adds ONE bias made of
    the int8 tile, interior tiles without a position test, and the rows
    are bit for bit those of the kernel with every tile through the edge
    body, and the masked softmax's at the kernel's tolerance; lengths
    inside a block, on a block's edge and at the bucket's end."""
    from vgate_tpu.ops.pallas.dsa import dsa_prefill_attention_pallas
    from vgate_tpu.ops.pallas.flash_prefill import (
        flash_prefill_attention_pallas, head_block,
    )

    rng = np.random.default_rng(54)
    B, S, H, KV, hd = 2, 128, 4, 4, 32
    assert head_block(H, H // KV, 32, 32, hd, 4) == H  # one program
    q, k, v = (jnp.asarray(rng.normal(size=(B, S, h, hd)), jnp.float32)
               for h in (H, KV, KV))
    scores = jnp.asarray(rng.normal(size=(B, S, S)), jnp.float32)
    causal = np.tri(S, dtype=bool)[None]
    mask = jnp.asarray((np.asarray(dsa.select_mask(
        jnp.where(causal, scores, -jnp.inf), TOPK)) & causal).astype(np.int8))
    seq_lens = jnp.asarray(lens, jnp.int32)
    got = np.asarray(dsa_prefill_attention_pallas(
        q, k, v, seq_lens, mask, scale=hd ** -0.5, block_q=32, block_k=32,
        interpret=True))
    edge = np.asarray(flash_prefill_attention_pallas(
        q, k, v, seq_lens, mask=mask, scale=hd ** -0.5, block_q=32,
        block_k=32, skip_padding=True, interpret=True, _all_edge=True))
    assert np.array_equal(got, edge)
    want = np.asarray(dsa.masked_attention(q, k, v, mask, hd ** -0.5))
    for b, n in enumerate(lens):
        np.testing.assert_allclose(got[b, :n], want[b, :n],
                                   rtol=2e-5, atol=2e-5)


def picked_attention(q, pool, tables, sel, n_sel, layer, vw, scale):
    """numpy: a softmax over the slot's picked positions alone (zeros
    for a slot with none), from a pool [L, 1, P, ps, W]."""
    B, n = tables.shape
    ps, W = pool.shape[-2:]
    keep = np.zeros((B, n * ps), bool)
    for b in range(B):
        keep[b, sel[b, :int(n_sel[b])]] = True
    rows = np.asarray(pool[layer, 0][np.asarray(tables)]).reshape(
        B, n * ps, W)
    scores = np.einsum("bhw,btw->bht", np.asarray(q), rows) * scale
    scores = np.where(keep[:, None], scores, -np.inf)
    with np.errstate(invalid="ignore"):
        p = np.exp(scores - scores.max(-1, keepdims=True))
        out = np.einsum("bht,btv->bhv", p / p.sum(-1, keepdims=True),
                        rows[..., :vw])
    return np.where((np.asarray(n_sel) > 0)[:, None, None], out, 0.0)


@pytest.mark.parametrize("pairs", [False, True], ids=["rows", "by-pairs"])
def test_decode_attention_over_gathered_rows_reads_the_pick_alone(pairs):
    """``dsa_decode_attention`` (the jnp twin: the picked rows gathered
    at the places ``order_picks`` gives) is the dense softmax over a
    pool in which every row outside the pick is poisoned, whichever way
    the pool's rows lie."""
    rng = np.random.default_rng(8)
    B, H, W, ps, n, k, vw = 2, 4, 128, 8, 8, 16, 64
    pool = np.asarray(rng.normal(size=(3, 1, 40, ps, W)), np.float32)
    q = jnp.asarray(rng.normal(size=(B, H, W)), jnp.float32)
    tables = jnp.asarray(rng.permutation(39)[:B * n].reshape(B, n) + 1)
    lens = np.asarray([60, 9])
    sel = np.stack([np.concatenate([
        rng.permutation(l)[:k], np.zeros(max(0, k - l), np.int64)])
        for l in lens]).astype(np.int32)
    n_sel = jnp.asarray(np.minimum(lens, k), jnp.int32)
    rows = dsa.order_picks(tables, jnp.asarray(sel), n_sel, ps)
    held = pool.reshape(3, 1, 40, ps // 2, 2, W) if pairs else pool
    assert dsa.gather_selected(jnp.asarray(held), rows, 2).shape == (B, k, W)
    got = dsa.dsa_decode_attention(
        q, jnp.asarray(held), rows, n_sel, 2, v_width=vw, scale=0.1,
        use_pallas=False)
    want = picked_attention(q, pool, tables, sel, n_sel, 2, vw, 0.1)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)


def test_order_picks_is_the_same_set_first_rows_of_pairs_first():
    """``order_picks``: a slot's real picks as places ``page * ps +
    offset`` through its page table, those at even places first, each
    half ascending; what is past ``n_sel`` reads place 0."""
    rng = np.random.default_rng(3)
    ps, n, k = 8, 6, 20
    tables = rng.permutation(40)[:3 * n].reshape(3, n) + 1
    lens = [48, 5, 0]
    sel = np.stack([np.concatenate([
        rng.permutation(l)[:k], np.full(max(0, k - l), 7)])
        for l in lens]).astype(np.int32)
    n_sel = np.minimum(lens, k)
    rows = np.asarray(dsa.order_picks(
        jnp.asarray(tables), jnp.asarray(sel), jnp.asarray(n_sel), ps))
    for b, m in enumerate(n_sel):
        want = tables[b, sel[b, :m] // ps] * ps + sel[b, :m] % ps
        got = rows[b, :m]
        assert sorted(got) == sorted(want)
        even = got[got % 2 == 0]
        assert list(got) == sorted(even) + sorted(got[got % 2 == 1])
        assert not rows[b, m:].any()


# what a slot's pick looks like -> (its length, its picked positions);
# k = 20 of chunks of 8: no whole number of the kernel's chunks
FETCH_K, FETCH_CHUNK = 20, 8
FETCH_CASES = {
    "even positions": (64, list(range(0, 40, 2))),
    "odd positions": (64, list(range(1, 41, 2))),
    "both rows of a pair": (64, list(range(12, 32))),
    "fewer than k": (9, [8, 3, 4, 0, 7]),
    "a slot of length 0": (0, []),
    "across pages in any order": (
        64, [63, 0, 31, 32, 8, 7, 56, 1, 40, 39, 17, 62, 2, 33, 24, 25,
             9, 48, 47, 16]),
    "one pick": (3, [2]),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(FETCH_CASES))
def test_the_fetching_decode_kernel_is_the_twin(case, dtype):
    """``dsa_decode_attention_pallas`` (interpreted): the kernel that
    fetches the pair of rows holding each pick and keeps the picked one
    is the jnp twin over the same places, beside a slot that picks
    otherwise, over a pool by pairs whose other rows are poisoned; in
    bfloat16 the pair's rows come apart as halves of a 32-bit word."""
    from vgate_tpu.ops.pallas.dsa import dsa_decode_attention_pallas

    rng = np.random.default_rng(11)
    H, W, ps, n, vw = 4, 128, 8, 8, 64
    k = FETCH_K
    picks = [FETCH_CASES[case], (64, list(rng.permutation(64)[:k]))]
    if case == "a slot of length 0":  # dead slots around a live one
        picks = [picks[0], picks[1], picks[0]]
    B = len(picks)
    lens = [length for length, _ in picks]
    n_sel = jnp.asarray([len(p) for _, p in picks], jnp.int32)
    sel = np.asarray([p + [5] * (k - len(p)) for _, p in picks], np.int32)
    tables = jnp.asarray(rng.permutation(39)[:B * n].reshape(B, n) + 1)
    pool = jnp.asarray(rng.normal(size=(3, 1, 40, ps, W)), dtype)
    q = jnp.asarray(rng.normal(size=(B, H, W)), dtype)
    rows = dsa.order_picks(tables, jnp.asarray(sel), n_sel, ps)
    by_pairs = pool.reshape(3, 1, 40, ps // 2, 2, W)
    got = dsa_decode_attention_pallas(
        q, by_pairs, rows, n_sel, 1, v_width=vw, scale=0.1,
        chunk=FETCH_CHUNK, interpret=True)
    twin = dsa.dsa_decode_attention(
        q, by_pairs, rows, n_sel, 1, v_width=vw, scale=0.1,
        use_pallas=False)
    want = picked_attention(
        q.astype(jnp.float32), np.asarray(pool.astype(jnp.float32)),
        tables, sel, n_sel, 1, vw, 0.1)
    live = np.asarray(n_sel) > 0
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    got = np.asarray(got.astype(jnp.float32))
    assert all(length >= len(p) for length, p in picks) and lens
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)
    np.testing.assert_allclose(
        got[live], np.asarray(twin.astype(jnp.float32))[live], rtol=tol,
        atol=tol)
    assert not got[~live].any()  # nothing fetched: zeros


def test_writes_and_page_copies_follow_a_pool_by_pairs():
    """A pool by pairs holds what the same writes leave in a pool by
    rows, byte for byte in the same order: a prompt's pages
    (``kv_write_pages``), a step's rows at even and odd offsets
    (``kv_write_tokens``), the rows read back through a page table
    (``mla_gather_rows``), and a page swapped out and in again."""
    from vgate_tpu.ops.attention import mla_gather_rows
    from vgate_tpu.ops.kv_quant import (
        by_pairs, kv_write_pages, kv_write_tokens, page_tokens,
    )
    from vgate_tpu.runtime.step_programs import (
        _gather_swap_pages, _scatter_swap_pages,
    )

    rng = np.random.default_rng(5)
    L, P, ps, W = 3, 12, 8, 128
    flat = jnp.zeros((L, 1, P, ps, W), jnp.float32)
    pairs = jnp.zeros((L, 1, P, ps // 2, 2, W), jnp.float32)
    assert by_pairs(pairs) and not by_pairs(flat)
    assert page_tokens(pairs) == page_tokens(flat) == ps
    tables = jnp.asarray([[3, 7], [9, 1]])
    prompt = jnp.asarray(rng.normal(size=(2, 2, 1, ps, W)), jnp.float32)
    ids, off = jnp.asarray([4, 5, 7]), jnp.asarray([0, 3, 6])
    step = jnp.asarray(rng.normal(size=(3, 1, W)), jnp.float32)
    for layer in (0, 2):
        flat = kv_write_pages(flat, tables, prompt + layer, layer=layer)
        pairs = kv_write_pages(pairs, tables, prompt + layer, layer=layer)
        flat = kv_write_tokens(flat, ids, off, step - layer, layer=layer)
        pairs = kv_write_tokens(pairs, ids, off, step - layer, layer=layer)
    same = lambda: np.array_equal(
        np.asarray(pairs).reshape(flat.shape), np.asarray(flat))
    assert same() and np.asarray(flat).any()
    np.testing.assert_array_equal(
        np.asarray(mla_gather_rows(pairs, tables, 2)),
        np.asarray(mla_gather_rows(flat, tables, 2)))
    # a page out to the host and back into another page id
    idx = jnp.asarray([7, 4])
    out = _gather_swap_pages(pairs, pairs, idx)[0]
    assert out.shape == (L, 1, 2, ps // 2, 2, W)
    back = jnp.asarray([10, 11])
    pairs = _scatter_swap_pages(pairs, pairs + 0, back, out, out)[0]
    flat = flat.at[:, :, back].set(flat[:, :, idx])
    assert same()


@pytest.mark.parametrize("pages", [3, 16, 37],
                         ids=["under a group", "one group", "groups and a rest"])
def test_the_prompts_page_writer_is_the_scatter(pages):
    """``dsa_write_pages_pallas`` (interpreted): a prompt's rows into a
    pool by pairs, a page a copy, leave what ``kv_write_pages`` leaves,
    in the named layer alone."""
    from vgate_tpu.ops.kv_quant import kv_write_pages
    from vgate_tpu.ops.pallas.dsa import dsa_write_pages_pallas

    rng = np.random.default_rng(pages)
    L, P, ps, W = 3, 48, 8, 128
    pool = jnp.asarray(rng.normal(size=(L, 1, P, ps // 2, 2, W)),
                       jnp.float32)
    tables = jnp.asarray(rng.permutation(P - 1)[:pages].reshape(1, pages) + 1)
    value = jnp.asarray(rng.normal(size=(1, pages, 1, ps, W)), jnp.float32)
    want = kv_write_pages(pool, tables, value, layer=1)
    got = dsa_write_pages_pallas(pool + 0, tables, value, 1, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert not np.array_equal(np.asarray(got[1]), np.asarray(pool[1]))


# the leading layer and one period (F S S S F, the cell's own stack, as
# tests/test_glm_dsa_engine.py has it): every kind of sub-block the row
# loop wraps, in half the layers the row-block passes compile
SHORT = specs._register(dataclasses.replace(
    SPEC, name="tiny-dsa-moe-l5", num_layers=5, indexer_pattern="FSSSF"))


@pytest.mark.parametrize("fill", list(row_blocks.FILLS))
def test_a_long_prompt_pass_works_on_its_own_row_blocks(fill):
    """A bucket of four blocks of rows (the block patched to 8; 32 rows
    against an ``index_topk`` of 16, so the layers pick): the query
    latent, the index keys and queries, the latent rows, each group of
    heads' expansion and output projection in a counted loop over the
    blocks the longer prompt reaches, against the pass over the whole
    bucket."""
    row_blocks.check_prompt_pass(SHORT.name, row_blocks.FILLS[fill])


def test_greedy_tokens_are_the_same_with_the_row_loop(monkeypatch):
    row_blocks.check_greedy_identity(monkeypatch, SHORT.name)
