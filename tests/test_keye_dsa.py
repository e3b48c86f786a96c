"""``tiny-keye-dsa`` (Keye-VL-2.0's language model at toy widths: GQA
attention under a LEARNED selection of 16 cached tokens in EVERY layer,
a token's K over its V in one array and its index key in a second under
one page table, a softmax-routed expert layer without a shared expert)
against the plain reference's full forward
(``perfbench/references/keye_vl2.py``: no cache, full index scores, an
exact top-k, the selection a mask) on the same seeded weights: the
forwards directly (whole prompt, then decode through both arrays; a
suffix against a cached prefix); the selection itself, layer by layer;
the sectioned rotary; the cache's geometry; the expert layer's shares;
the kernels in interpret mode.  ``tests/test_keye_dsa_engine.py`` has
the same through the engine."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import manifest
from perfbench.references import keye_vl2 as ref
from vgate_tpu.models import decoder, hybrid
from vgate_tpu.models.specs import spec_for_model_id
from vgate_tpu.ops import dsa, moe
from vgate_tpu.ops.rope import apply_rope
from vgate_tpu.runtime.kv_cache import KVGeometry, make_kv_buffers

SPEC = spec_for_model_id("tiny-keye-dsa")
PUBLISHED = spec_for_model_id("Kwai-Keye/Keye-VL-2.0-30B-A3B")
CONFIG = manifest.load_json(
    manifest.HERE, "configs", "keye-vl-2.0-30b-a3b-l12e32.json")
CUT = dataclasses.replace(
    PUBLISHED, name="keye-cut", **{
        k: v for k, v in CONFIG["program"]["overrides"].items()
        if k not in ("eos_token_id", "bos_token_id", "extra_stop_ids")})
# the tiny-keye-dsa preset under the published config's keys: what the
# configuration's rehearsal serves
TINY = CONFIG["rehearse"]["model"]
# float32 on both sides; only the order of sums and the form differ (a
# step over fetched pairs of rows and a mask from a threshold against
# one masked softmax over an exact top-k): measured 9.5e-7 at most
TOL = 1e-4
PS, SLOTS, TOPK = 8, 4, 16
PREFILL = jax.jit(decoder.prefill_forward, static_argnums=1)
SUFFIX = jax.jit(decoder.prefill_suffix_forward, static_argnums=1)
DECODE = jax.jit(decoder.decode_forward, static_argnums=1)


@pytest.fixture(scope="module")
def params():
    return decoder.init_params(SPEC, jax.random.PRNGKey(0), jnp.float32)


def geometry(spec=SPEC, pages=64, page=PS, dtype_bytes=4, ctx=128):
    return KVGeometry(
        num_layers=spec.attn_layers, num_pages=pages, page_size=page,
        kv_heads=spec.cache_heads, head_dim=spec.cache_head_dim,
        max_model_len=ctx, dtype_bytes=dtype_bytes, pools=spec.kv_pools,
        index_layers=spec.index_layers, index_dim=spec.index_key_lanes)


def served_logprobs(params, seq, prompt_len, slot=2, cached=0, spec=SPEC):
    """Log-softmax rows for positions ``prompt_len - 1 .. len(seq) - 2``
    from the program's forwards: the prompt whole (or its first
    ``cached`` tokens whole and the rest as a suffix against them), then
    one decode step a token through both arrays."""
    kp, vp = make_kv_buffers(geometry(spec), jnp.float32)
    table = np.arange(1, 17, dtype=np.int32)[None]
    one = lambda v: jnp.asarray([v])

    def whole(n):
        S = next(b for b in (16, 64) if b >= n)  # two programs
        toks = np.zeros((1, S), np.int32)
        toks[0, :n] = seq[:n]
        return PREFILL(
            params, spec, jnp.asarray(toks), one(n), kp, vp,
            jnp.asarray(table[:, :S // PS]), slots=one(slot))

    if cached:
        _, kp, vp, _ = whole(cached)
        n = prompt_len - cached
        S = 40
        toks = np.zeros((1, S), np.int32)
        toks[0, :n] = seq[cached:prompt_len]
        own = table[:, cached // PS: (cached + S) // PS]
        logits, kp, vp, st = SUFFIX(
            params, spec, jnp.asarray(toks), one(cached), one(n), kp, vp,
            jnp.asarray(own), jnp.asarray(table), slots=one(slot))
    else:
        logits, kp, vp, st = whole(prompt_len)
    assert st is None  # a selection is activations, not state
    rows = [jax.nn.log_softmax(logits[0])]
    tables = np.zeros((SLOTS, 16), np.int32)
    tables[slot] = table[0]
    active = np.arange(SLOTS) == slot
    for pos in range(prompt_len, len(seq) - 1):
        tok = np.where(active, seq[pos], 0).astype(np.int32)
        at = np.where(active, pos, 0).astype(np.int32)
        logits, kp, vp, st, _ = DECODE(
            params, spec, jnp.asarray(tok), jnp.asarray(at), kp, vp,
            jnp.asarray(tables), active=jnp.asarray(active))
        rows.append(jax.nn.log_softmax(logits[slot]))
    return np.stack([np.asarray(r) for r in rows]), (kp, vp)


def tokens(seed, n):
    return [int(t) for t in np.random.default_rng(seed).integers(3, 500, n)]


@pytest.mark.parametrize("prompt_len, decoded, what", [
    # (sequences of 16, 40 or 64 tokens in this file: the reference runs
    # op by op, and every new length compiles every op again)
    (5, 11, "under the pick: dense GQA attention"),
    (12, 28, "the context passes the pick inside the decode steps"),
    (30, 10, "just past the pick, a page boundary inside the decode steps"),
    (58, 6, "four times the pick"),
])
def test_whole_prompt_then_decode_through_both_arrays(
        params, prompt_len, decoded, what):
    seq = tokens(prompt_len, prompt_len + decoded)
    got, (kp, vp) = served_logprobs(params, seq, prompt_len)
    want = ref.logprobs(TINY, 0, jnp.float32, [seq], [prompt_len])[0]
    assert got.shape == want.shape
    assert np.abs(got - want).max() < TOL, what
    # K over V a token, the index key in the first lanes of its row
    assert kp.shape == (4, 1, 64, PS, 2, 32) and vp.shape == (4, 1, 64, PS, 128)
    assert not np.asarray(vp[..., SPEC.index_head_dim:]).any()


def test_a_suffix_is_scored_against_the_pools_index_keys(params):
    """A suffix's rows (a prefix hit, a later chunk) read the context's
    K, V AND index keys back from the pages the prefix left."""
    seq = tokens(7, 24 + 35 + 5)
    whole, _ = served_logprobs(params, seq, 59)
    suffix, _ = served_logprobs(params, seq, 59, cached=24)
    want = ref.logprobs(TINY, 0, jnp.float32, [seq], [59])[0]
    assert np.abs(suffix - whole).max() < TOL
    assert np.abs(suffix - want).max() < TOL


def test_under_the_pick_the_indexer_is_not_consulted(params):
    seq = tokens(3, 9 + 7)  # a context of 15 <= 16 at the last step
    got, _ = served_logprobs(params, seq, 9)
    layer = dict(params["layers"]["layer"])
    layer["index_q"] = {"w": -3.0 * layer["index_q"]["w"]}
    same, _ = served_logprobs(dict(params, layers={"layer": layer}), seq, 9)
    assert np.abs(got - same).max() < 1e-6


def test_every_layer_serves_the_references_exact_set(params):
    """In EVERY layer, on the reference's own float32 rows: the mask a
    prompt pass builds (a threshold found by counting) and the positions
    a decode step picks (``jax.lax.top_k``) are the reference's exact
    top-k set, row by row; and the layers' sets differ."""
    n = 64
    seq = tokens(11, n)
    pos = jnp.arange(n)[None]
    rows = jnp.asarray([TOPK, n - 1])  # the first row past the pick, the last

    @jax.jit
    def served(x, lp):
        """(the prompt pass's mask, a decode step's positions for
        ``rows``) from the program's own indexer functions."""
        normed = hybrid.rms_norm(x, lp["input_norm"], SPEC.rms_eps, False)
        key = hybrid._dsa_index_key(normed, lp, SPEC, pos)
        mask = hybrid._dsa_prompt_select(
            normed, None, lp, key, pos, jnp.asarray([n]), SPEC, False)
        qi, w = hybrid._dsa_index_query(normed, None, lp, SPEC, pos)
        scores = jnp.where(jnp.arange(n)[None] <= rows[:, None],
                           dsa.index_scores(qi, w, key)[0, rows], -jnp.inf)
        return mask[0] != 0, dsa.select_positions(scores, TOPK)

    ends = ref.draw_ends(TINY, 0, jnp.float32)
    x = ends["embed"][jnp.asarray(seq)]
    sets = []
    for i in range(4):
        lp = jax.tree.map(lambda a: a[i, 0], params["layers"]["layer"])
        mask, top = served(x[None], lp)
        with jax.default_matmul_precision("highest"):
            x, want = ref.layer(x, ref.f32_but_experts(
                ref.draw_layer(TINY, 0, i, jnp.float32)), TINY)
        assert want.sum(-1).max() == TOPK
        assert np.array_equal(np.asarray(mask), want), i
        for row, got in zip(np.asarray(rows), np.asarray(top)):
            assert sorted(got) == list(np.nonzero(want[row])[0]), (i, row)
        sets.append(want)
    assert all((sets[i] != sets[i + 1]).any() for i in range(3))


def test_another_indexer_in_one_layer_moves_the_logits(params):
    """The opposite pick in layer 2 alone moves the logits by far more
    than the tolerance, and the reference given those weights agrees."""
    seq = tokens(5, 64)
    got, _ = served_logprobs(params, seq, 60)
    layer = dict(params["layers"]["layer"])
    w = layer["index_w"]["w"]
    layer["index_w"] = {"w": w.at[2].set(-w[2])}
    moved, _ = served_logprobs(dict(params, layers={"layer": layer}), seq, 60)
    assert np.abs(moved - got).max() > 100 * TOL
    layers = [ref.draw_layer(TINY, 0, i, jnp.float32) for i in range(4)]
    layers[2] = dict(layers[2], index_w=-layers[2]["index_w"])
    ends = ref.draw_ends(TINY, 0, jnp.float32)
    want = ref.logprobs(TINY, 0, jnp.float32, [seq], [60],
                        weights=dict(ends, layers=layers))[0]
    assert np.abs(moved - want).max() < TOL


# ------------------------------------------------ the sectioned rotary

def test_equal_components_are_todays_rotary_bit_for_bit():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 3, 16))
    pos = jnp.arange(9)[None] + jnp.asarray([[0], [700]])
    plain = apply_rope(x, pos, 1e7)
    assert np.array_equal(plain, apply_rope(x, pos, 1e7, sections=(2, 3, 3)))
    assert np.array_equal(
        plain, apply_rope(x, jnp.stack([pos] * 3), 1e7, sections=(2, 3, 3)))
    # the preset's sections through the layer's own front half
    assert SPEC.mrope_section == (2, 3, 3)
    assert PUBLISHED.mrope_section == (16, 24, 24)


def test_unequal_components_turn_each_section_by_its_own():
    """Positions of three components (an image token's): frequency i
    turns by the component of its section, as the reference's; the
    indexer rotates at the temporal component alone."""
    rng = np.random.default_rng(1)
    S, H, hd = 11, 3, 16
    x = jnp.asarray(rng.normal(size=(S, H, hd)), jnp.float32)
    pos = jnp.asarray(rng.integers(0, 900, (3, S)))
    got = apply_rope(x[None], pos[:, None], 1e4, sections=(2, 3, 3))[0]
    want = ref.rotate(x, pos, 1e4, [2, 3, 3])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    by_first = apply_rope(x[None], pos[:1], 1e4)[0]
    assert np.abs(got - by_first).max() > 0.1
    # sections that do not cover the frequencies are refused
    with pytest.raises(ValueError, match="sections"):
        apply_rope(x[None], pos[:, None], 1e4, sections=(2, 3))
    t = jnp.asarray(rng.normal(size=(1, S, 4, 8)), jnp.float32)
    keys = hybrid._index_rotate(t, pos[:, None], SPEC)
    assert keys.shape[-1] == 128  # the pool row's lanes
    np.testing.assert_allclose(
        keys[0, ..., :8], ref.rotate(t[0], pos, SPEC.rope_theta),
        rtol=1e-5, atol=1e-5)
    assert np.array_equal(keys, hybrid._index_rotate(t, pos[:1], SPEC))


# ------------------------------------------------- geometry and counts

def test_a_page_holds_k_over_v_and_an_index_key_a_layer():
    """The published-size spec at the cut: 12 x 2,048 B of K over V and
    12 x 256 B of index keys (64 values in 128 lanes) a token."""
    geo = geometry(CUT, pages=8193, page=32, dtype_bytes=2, ctx=16384)
    assert (CUT.attn_layers, CUT.index_layers, CUT.moe_layers) == (12, 12, 12)
    assert (CUT.cache_heads, CUT.cache_head_dim, CUT.kv_pools) == (1, 512, 2)
    assert (CUT.index_key_lanes, CUT.index_rotary_dim) == (128, 64)
    assert CUT.kv_rows and CUT.is_dsa and CUT.rows_cache and not CUT.is_mla
    assert geo.page_bytes == 32 * 12 * (2048 + 256) == 884736
    pools = jax.eval_shape(lambda: make_kv_buffers(geo, jnp.bfloat16))
    assert pools[0].shape == (12, 1, 8193, 32, 2, 512)
    assert pools[1].shape == (12, 1, 8193, 32, 128)
    assert 8193 * geo.page_bytes / 1e9 == pytest.approx(7.249, abs=1e-3)
    # the other selecting spec keeps its latent rows by pairs of tokens
    glm = spec_for_model_id("tiny-dsa-moe")
    assert glm.is_dsa and not glm.kv_rows and glm.kv_pools == 1
    assert glm.index_key_lanes == glm.index_head_dim == 16  # held as it is


def test_parameter_counts_and_layer_kinds():
    assert abs(PUBLISHED.num_params / 1e9 - 30.64) < 0.01
    assert abs(CUT.num_params / 1e9 - 2.2243) < 0.0001
    for spec, layers in ((PUBLISHED, 48), (CUT, 12), (SPEC, 4)):
        assert (spec.lead_layers, spec.layers_per_period,
                spec.num_periods) == (0, 1, layers)
        assert [b[0] for b in spec.period_blocks] == ["dsa", "moe"]
        assert spec.stack == (("dsa", "moe"),) * layers
    per = PUBLISHED._kind_params()
    # 21.40 M a layer outside its experts: the attention with its
    # indexer (2,261,120), the router, the two norms
    assert per["dsa"] == 18_874_624 + 2_261_120
    assert per["dsa"] + 2048 * 128 + 2 * 2048 == 21_401_984
    assert per["moe"] == 2048 * 128 + 128 * 3 * 2048 * 768
    for key, attr in CONFIG["program"]["spec_keys"].items():
        assert getattr(CUT, attr) == CONFIG[key], key
        assert getattr(PUBLISHED, attr) == CONFIG["published"].get(
            key, CONFIG[key]), key
    shapes = jax.eval_shape(lambda: decoder.init_params(
        CUT, jax.random.PRNGKey(0), jnp.bfloat16))
    held = sum(x.size for x in jax.tree.leaves(shapes))
    assert held == CUT.num_params
    assert held * 2 / 1e9 == pytest.approx(4.449, abs=1e-3)


def test_the_four_shares_add_up_to_the_uncut_reference(at_a_time):
    """128 experts over four chips, 32 each (``first_expert`` 0, 32, 64,
    96), the router 128 wide in every share: the shares' parts are the
    uncut reference's layer (model-configs guide, section 4)."""
    spec = dataclasses.replace(
        SPEC, name="tiny-128", num_experts=128, router_width=128,
        experts_per_token=8)
    cfg = dict(TINY, num_experts=128, router_width=128,
               num_experts_per_tok=8)
    lw = ref.draw_layer(cfg, 0, 1, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(3), (40, spec.hidden_size))
    with jax.default_matmul_precision("highest"):
        want = ref.experts(x, lw, cfg)
    total = jnp.zeros_like(x)
    for chip in range(4):
        first = 32 * chip
        cut = dataclasses.replace(spec, num_experts=32, first_expert=first)
        held = {n: lw[n][first:first + 32] for n in ("gate", "up", "down")}
        part = {"router": lw["router"],
                **{n: {"w": w} for n, w in held.items()}}
        _, stats = moe.expert_layer(x, part, cut, jax.nn.silu)
        extra = at_a_time(int(stats[1]))
        out, stats = moe.expert_layer(x, part, cut, jax.nn.silu)
        total = total + out
        with jax.default_matmul_precision("highest"):
            mine = ref.experts(x, dict(lw, **held), dict(
                cfg, num_experts=32, first_expert=first))
        assert np.abs(np.asarray(out - mine)).max() < 1e-5
        assert int(stats[0]) == 40 * 8 and int(stats[4]) == extra
    assert np.abs(np.asarray(total - want)).max() < 1e-5


# ---------------------------------------------- the kernels, interpreted

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_the_fetching_kv_kernel_is_the_twin(dtype):
    """A descriptor a picked token, its K over its V; slots with a full
    pick, a few and none; 4 KV groups of 2 query heads."""
    from vgate_tpu.ops.pallas.dsa import dsa_kv_decode_attention_pallas

    rng = np.random.default_rng(8)
    L, P, ps, KV, hd, H, B, k = 2, 9, 8, 4, 128, 8, 3, 24
    pool = jnp.asarray(rng.normal(size=(L, 1, P, ps, 2, KV * hd)), dtype)
    q = jnp.asarray(rng.normal(size=(B, H, hd)), dtype)
    rows = jnp.asarray(rng.integers(0, P * ps, (B, k)), jnp.int32)
    n_sel = jnp.asarray([k, 5, 0], jnp.int32)
    want = dsa.kv_rows_decode_attention(
        q, pool, rows, n_sel, 1, scale=hd ** -0.5, use_pallas=False)
    got = dsa_kv_decode_attention_pallas(
        q, pool, rows, n_sel, 1, scale=hd ** -0.5, interpret=True, chunk=16)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(got[:2], np.float32), np.asarray(want[:2], np.float32),
        rtol=tol, atol=tol)
    assert not np.asarray(got[2], np.float32).any()
    # no token outside the pick meets a score: another pool elsewhere
    noise = pool.at[1, 0, 0, 0].add(7.0)
    keep = jnp.where(rows == 0, 1, rows)
    again = dsa_kv_decode_attention_pallas(
        q, noise, keep, n_sel, 1, scale=hd ** -0.5, interpret=True, chunk=16)
    base = dsa_kv_decode_attention_pallas(
        q, pool, keep, n_sel, 1, scale=hd ** -0.5, interpret=True, chunk=16)
    assert np.array_equal(np.asarray(again, np.float32),
                          np.asarray(base, np.float32))


def test_the_scoring_kernels_take_a_key_of_64_in_a_row_of_128():
    """16 x 64 against keys held in 128 lanes (the rest zeros): the
    decode pass over a slot's live pages and a prompt's row block."""
    from vgate_tpu.ops.attention import mla_gather_rows
    from vgate_tpu.ops.pallas.dsa import (
        dsa_index_scores_pallas, dsa_prompt_scores_pallas)

    rng = np.random.default_rng(2)
    B, Hi, d, ps, n = 2, 16, 64, 8, 20
    pad = lambda t: jnp.pad(t, ((0, 0),) * (t.ndim - 1) + ((0, 128 - d),))
    keys = pad(jnp.asarray(rng.normal(size=(2, 1, 64, ps, d)), jnp.float32))
    qi = pad(jnp.asarray(rng.normal(size=(B, Hi, d)), jnp.float32))
    w = jnp.asarray(rng.normal(size=(B, Hi)), jnp.float32)
    tables = jnp.asarray(rng.permutation(63)[:B * n].reshape(B, n) + 1)
    lens = jnp.asarray([150, 37], jnp.int32)
    got = dsa_index_scores_pallas(qi, w, keys, tables, lens, 1,
                                  interpret=True)
    rows = mla_gather_rows(keys, tables, 1)
    want = dsa.index_scores(qi[:, None, :, :d], w[:, None], rows[..., :d])
    live = np.arange(n * ps)[None] < np.asarray(lens)[:, None]
    np.testing.assert_allclose(np.asarray(got)[live],
                               np.asarray(want[:, 0])[live],
                               rtol=1e-4, atol=1e-4)
    block = dsa_prompt_scores_pallas(
        jnp.broadcast_to(qi[0], (32, Hi, 128)), jnp.broadcast_to(w[0], (32, Hi)),
        rows[0][:128], 96, block_q=32, block_k=64, interpret=True)
    assert np.all(np.isneginf(np.asarray(block)[0, 97:]))
    np.testing.assert_allclose(np.asarray(block)[0, :97],
                               np.asarray(want[0, 0, :97]),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("lens", [[128, 70], [96, 33], [64, 128]],
                         ids=lambda lens: "-".join(map(str, lens)))
def test_the_prompt_kernel_makes_its_bias_once_for_a_block_of_heads(lens):
    """The launch under a selection at the GQA form's shape (the query
    heads of a KV head stay in one program and share its K and V
    blocks): ONE bias of the int8 tile for all of them, interior tiles
    without a position test, bit for bit the kernel with every tile
    through the edge body and the masked softmax's rows at the kernel's
    tolerance."""
    from vgate_tpu.ops.pallas.dsa import dsa_prefill_attention_pallas
    from vgate_tpu.ops.pallas.flash_prefill import (
        flash_prefill_attention_pallas, head_block,
    )

    rng = np.random.default_rng(54)
    B, S, H, KV, hd = 2, 128, 8, 2, 32
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


@pytest.mark.parametrize("pages", [3, 37], ids=lambda n: f"{n}-pages")
def test_the_page_writer_moves_k_over_v_like_the_scatter(pages):
    from vgate_tpu.ops.pallas.dsa import dsa_write_pages_pallas

    rng = np.random.default_rng(pages)
    pool = jnp.asarray(rng.normal(size=(2, 1, 64, PS, 2, 128)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(1, pages * PS, 2, 64)), jnp.float32)
            for _ in range(2))
    tables = jnp.asarray(rng.permutation(63)[:pages][None] + 1)
    want = hybrid._write_kv_rows(pool, tables, k, v, 1, kernel=False)
    got = dsa_write_pages_pallas(
        pool, tables, dsa.kv_rows_pages(k, v, PS), 1, interpret=True)
    assert np.array_equal(np.asarray(want), np.asarray(got))
    back_k, back_v = dsa.kv_rows_gather(want, tables, 1, 2)
    assert np.array_equal(back_k, k) and np.array_equal(back_v, v)


# ------------------------------------------------------- a checkpoint

@pytest.mark.parametrize("prefix", ["model.", "model.language_model."])
def test_a_checkpoint_under_the_assumed_names_loads_into_the_tree(
        params, prefix):
    """The KeyeVL2 tensor names runtime/weights.py assumes, either
    prefix, the tower's tensors beside them unread: the tree the program
    drew comes back, a chip's share of experts and vocabulary cut out."""
    from vgate_tpu.runtime.weights import params_from_getter

    layer = params["layers"]["layer"]
    names = {"input_norm": "input_layernorm.weight",
             "post_norm": "post_attention_layernorm.weight",
             "q_norm": "self_attn.q_norm.weight",
             "k_norm": "self_attn.k_norm.weight",
             "index_k_norm": "self_attn.indexer.k_norm.weight",
             "index_k_bias": "self_attn.indexer.k_norm.bias"}
    lins = {"q": "self_attn.q_proj", "k": "self_attn.k_proj",
            "v": "self_attn.v_proj", "o": "self_attn.o_proj",
            "index_q": "self_attn.indexer.wq_b",
            "index_k": "self_attn.indexer.wk",
            "index_w": "self_attn.indexer.weights_proj"}
    ckpt = {"visual.blocks.0.attn.qkv.weight": np.zeros((3, 3)),
            prefix + "embed_tokens.weight": np.asarray(params["embed"]),
            prefix + "norm.weight": np.asarray(params["final_norm"]),
            "lm_head.weight": np.asarray(params["lm_head"]).T}
    for i in range(SPEC.num_layers):
        at = f"{prefix}layers.{i}."
        for ours, theirs in names.items():
            ckpt[at + theirs] = np.asarray(layer[ours][i, 0])
        for ours, theirs in lins.items():
            ckpt[at + theirs + ".weight"] = np.asarray(layer[ours]["w"][i, 0]).T
        ckpt[at + "mlp.gate.weight"] = np.asarray(layer["router"][i, 0]).T
        for n in ("gate", "up", "down"):
            for e in range(SPEC.num_experts):
                ckpt[f"{at}mlp.experts.{e}.{n}_proj.weight"] = np.asarray(
                    layer[n]["w"][i, 0, e]).T
    loaded = params_from_getter(SPEC, ckpt.__getitem__, jnp.float32)
    assert jax.tree.structure(loaded) == jax.tree.structure(params)
    for got, want in zip(jax.tree.leaves(loaded), jax.tree.leaves(params)):
        assert np.array_equal(got, want)
    share = dataclasses.replace(SPEC, num_experts=2, first_expert=4,
                                vocab_size=100)
    part = params_from_getter(share, ckpt.__getitem__, jnp.float32)
    assert np.array_equal(part["layers"]["layer"]["up"]["w"],
                          layer["up"]["w"][:, :, 4:6])
    assert part["embed"].shape == (100, 64) and part["lm_head"].shape == (64, 100)
