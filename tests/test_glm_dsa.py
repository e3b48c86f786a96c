"""``tiny-dsa-moe`` (GLM-5.2 at toy widths: latent attention under a
LEARNED selection of 16 cached tokens, a leading dense layer whose
indexer picks, then three layers that reuse a pick to one that picks,
the index keys a second array under the latent pool's page table, a
sigmoid-routed expert layer with a shared expert) against the plain
reference's full forward (``perfbench/references/glm_moe_dsa.py``: no
cache, full index scores, an exact top-k, the selection a mask) on the
same seeded weights: the forwards directly (whole prompt, then decode
through both caches; a chunked prefill); the selection itself; the
cache's geometry.  ``tests/test_glm_dsa_kernels.py`` has the expert
layer's shares, the kernels in interpret mode and the row blocks;
``tests/test_glm_dsa_engine.py`` the same forwards through the engine."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import manifest
from perfbench.references import glm_moe_dsa as ref
from tests.family_contract import one_length
from vgate_tpu.models import decoder, hybrid
from vgate_tpu.models.specs import spec_for_model_id
from vgate_tpu.ops import dsa
from vgate_tpu.runtime.kv_cache import KVGeometry, make_kv_buffers

SPEC = spec_for_model_id("tiny-dsa-moe")
PUBLISHED = spec_for_model_id("zai-org/GLM-5.2")
CONFIG = manifest.load_json(manifest.HERE, "configs", "glm-5.2-l5e16.json")
CUT = dataclasses.replace(
    PUBLISHED, name="glm-cut", **{
        k: v for k, v in CONFIG["program"]["overrides"].items()
        if k not in ("eos_token_id", "bos_token_id", "extra_stop_ids")})
# the tiny-dsa-moe preset under the published config's keys: what the
# configuration's rehearsal serves
TINY = CONFIG["rehearse"]["model"]
# float32 on both sides; only the order of sums and the form differ (the
# absorbed step over gathered rows and a mask from a threshold against
# one masked softmax over an exact top-k): measured 9.5e-7 at most
TOL = 1e-4
PS, SLOTS, TOPK = 8, 4, 16
# the rows of every whole-prompt pass and of every chunk (what the
# serving path's buckets do: a length is ``seq_lens``, not a shape), and
# the length every sequence has for the reference
BUCKET, CHUNK, REF_LEN = 48, 24, 64
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
        index_layers=spec.index_layers, index_dim=spec.index_head_dim)


def reference(seq, prompt_len, **weights):
    """The plain reference's rows for ``seq[prompt_len:]``."""
    return one_length(
        functools.partial(ref.logprobs, TINY, 0, jnp.float32, **weights),
        seq, prompt_len, REF_LEN)


def served_logprobs(params, seq, prompt_len, slot=2, chunks=None,
                    spec=SPEC, caches=None):
    """Log-softmax rows for positions ``prompt_len - 1 .. len(seq) - 2``
    from the program's forwards: the prompt whole (or in ``chunks``),
    then one decode step a token through both caches."""
    kp, vp = caches or make_kv_buffers(geometry(spec), jnp.float32)
    table = np.arange(1, 17, dtype=np.int32)[None]
    one = lambda v: jnp.asarray([v])
    if chunks is None:
        S = BUCKET  # one program whatever the prompt's length
        assert prompt_len <= S
        toks = np.zeros((1, S), np.int32)
        toks[0, :prompt_len] = seq[:prompt_len]
        logits, kp, vp, st = PREFILL(
            params, spec, jnp.asarray(toks), one(prompt_len), kp, vp,
            jnp.asarray(table[:, :S // PS]), slots=one(slot))
    else:
        done = 0
        for want in chunks:
            n = min(want, prompt_len - done)
            S = CHUNK  # one program whatever the chunk's length
            assert n <= S
            toks = np.zeros((1, S), np.int32)
            toks[0, :n] = seq[done:done + n]
            own = table[:, done // PS: (done + S) // PS]
            logits, kp, vp, st = SUFFIX(
                params, spec, jnp.asarray(toks), one(done), one(n), kp, vp,
                jnp.asarray(own), jnp.asarray(table), slots=one(slot))
            done += n
    assert st is None  # the selection is activations, not state
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


@pytest.mark.parametrize("prompt_len, decoded, what", [
    (5, 6, "under the pick: everything is attended"),
    (12, 9, "the context passes index_topk inside the decode steps"),
    (TOPK, 4, "a prompt of exactly index_topk tokens"),
    (30, 7, "a page boundary inside the decode steps"),
    (45, 5, "most of the context left out"),
])
def test_whole_prompt_then_decode_through_both_caches(
        params, prompt_len, decoded, what):
    rng = np.random.default_rng(prompt_len)
    seq = [int(t) for t in rng.integers(3, 500, prompt_len + decoded)]
    got, _ = served_logprobs(params, seq, prompt_len)
    want = reference(seq, prompt_len)
    assert got.shape == want.shape
    assert np.abs(got - want).max() < TOL, what


@pytest.mark.parametrize("chunks", [(16, 8, 8), (8, 24), (24, 8)])
def test_a_chunked_prefill_scores_the_pools_index_keys(params, chunks):
    """A later chunk's rows are scored against the index keys the chunks
    before left in the pool and attend under that pick."""
    rng = np.random.default_rng(7)
    seq = [int(t) for t in rng.integers(3, 500, 30 + 5)]
    whole, _ = served_logprobs(params, seq, 30)
    chunked, _ = served_logprobs(params, seq, 30, chunks=chunks)
    want = reference(seq, 30)
    assert np.abs(chunked - whole).max() < TOL
    assert np.abs(chunked - want).max() < TOL


def test_under_index_topk_the_layer_is_the_dense_latent_path(params):
    """While the context is at most ``index_topk`` the selection is
    everything: the same weights with ``index_topk`` beyond any context
    (nothing is ever left out) give the same logits, and with the
    indexer's weights scrambled too (it is not consulted)."""
    rng = np.random.default_rng(3)
    seq = [int(t) for t in rng.integers(3, 500, 9 + 6)]  # 15 <= 16
    got, _ = served_logprobs(params, seq, 9)
    everything = dataclasses.replace(SPEC, index_topk=4096)
    dense, _ = served_logprobs(params, seq, 9, spec=everything)
    assert np.abs(got - dense).max() < 1e-6
    scrambled = jax.tree.map(lambda a: a, params)
    lead = dict(scrambled["layers"]["lead"][0])
    lead["index_q"] = {"w": -3.0 * lead["index_q"]["w"]}
    scrambled["layers"] = dict(scrambled["layers"], lead=(lead,))
    same, _ = served_logprobs(scrambled, seq, 9)
    assert np.abs(got - same).max() < 1e-6
    # one token more and the pick leaves a token out: the dense path
    # differs from the served one, which still is the reference's
    seq = seq + [int(t) for t in rng.integers(3, 500, 6)]
    got, _ = served_logprobs(params, seq, 9)
    dense, _ = served_logprobs(params, seq, 9, spec=everything)
    want = reference(seq, 9)
    assert np.abs(got - want).max() < TOL
    assert np.abs(dense - want).max() > 100 * TOL


def reference_selections(seq):
    picked = []
    ref.logprobs(TINY, 0, jnp.float32, [seq], [len(seq) - 1],
                 selections=picked)
    return [p[0] for p in picked]  # per layer, the one sequence's [S, S]


def test_the_served_selection_is_the_references_set(params):
    """The mask a prompt pass builds in a picking layer (a threshold
    found by counting) and the positions a decode step picks
    (``jax.lax.top_k``) are the reference's exact top-k set, row by row,
    in every picking layer."""
    rng = np.random.default_rng(11)
    n = 40
    seq = [int(t) for t in rng.integers(3, 500, n)]
    want = reference_selections(seq)
    assert len(want) == 9 and want[0].sum(-1).max() == TOPK
    # the layers that reuse a pick hold the picking layer's own
    for lo in (0, 4):
        for i in range(lo + 1, lo + 4):
            assert want[i] is want[lo]
    assert (want[0] != want[4]).any() and (want[4] != want[8]).any()
    # the program's, from its own indexer functions on the same rows
    layers = params["layers"]
    x = params["embed"][jnp.asarray(seq)][None]
    lp = layers["lead"][0]
    normed = hybrid.rms_norm(x, lp["input_norm"], SPEC.rms_eps, False)
    pos = jnp.arange(n)[None]
    cq = hybrid._mla_cq(normed, lp, SPEC)
    key = hybrid._dsa_index_key(normed, lp, SPEC, pos)
    mask = hybrid._dsa_prompt_select(
        normed, cq, lp, key, pos, jnp.asarray([n]), SPEC, False)
    assert np.array_equal(np.asarray(mask[0]) != 0, want[0])
    qi, w = hybrid._dsa_index_query(normed, cq, lp, SPEC, pos)
    scores = dsa.index_scores(qi, w, key)[0, n - 1]
    picked = np.asarray(dsa.select_positions(scores[None], TOPK))[0]
    assert sorted(picked) == list(np.nonzero(want[0][n - 1])[0])


def test_ties_go_to_the_lower_position():
    """Equal scores at the threshold: the mask, the positions and the
    reference's top-k keep the same ones, the lower positions."""
    scores = np.zeros((3, 40), np.float32)
    scores[0, 5:30] = 1.0                    # 25 tied for 16 places
    scores[1] = np.arange(40) % 4            # ten of each value
    scores[2, 20:] = -np.inf                 # ties at -inf past the row
    scores[2, :20] = np.arange(20) % 2
    mask = np.asarray(dsa.select_mask(jnp.asarray(scores), TOPK))
    top = np.asarray(dsa.select_positions(jnp.asarray(scores), TOPK))
    for row in range(3):
        assert list(np.nonzero(mask[row])[0]) == sorted(top[row])
    assert list(np.nonzero(mask[0])[0]) == list(range(5, 21))
    again = ref.selection(jnp.where(
        np.tri(40, dtype=bool), jnp.asarray(scores[1])[None], -jnp.inf), TOPK)
    assert list(np.nonzero(again[39])[0]) == list(np.nonzero(mask[1])[0])


def test_a_reusing_layer_attends_under_the_pick_of_the_layer_below(params):
    """Layers 1-3 hold no indexer and attend under layer 0's pick:
    another indexer in layer 0 alone changes what they give, and the
    reference given those weights agrees."""
    assert "index_q" not in params["layers"]["reuse"]
    assert "index_q" in params["layers"]["pick"]
    rng = np.random.default_rng(5)
    seq = [int(t) for t in rng.integers(3, 500, 44)]
    got, _ = served_logprobs(params, seq, 40)
    other = dict(params)
    lead = dict(params["layers"]["lead"][0])
    lead["index_w"] = {"w": -lead["index_w"]["w"]}  # the opposite pick
    other["layers"] = dict(params["layers"], lead=(lead,))
    moved, _ = served_logprobs(other, seq, 40)
    assert np.abs(moved - got).max() > 100 * TOL
    layers = [ref.draw_layer(TINY, 0, i, jnp.float32) for i in range(9)]
    layers[0] = dict(layers[0], index_w=-layers[0]["index_w"])
    ends = ref.draw_ends(TINY, 0, jnp.float32)
    want = reference(seq, 40, weights=dict(ends, layers=layers))
    assert np.abs(moved - want).max() < TOL


def test_a_page_holds_a_latent_row_a_layer_and_an_index_key_a_picking_layer():
    """The published-size spec at the cut: 5 x 1,280 B of latent rows
    (576 values in 640 lanes) and 2 x 256 B of index keys a token."""
    geo = geometry(CUT, pages=24577, page=32, dtype_bytes=2, ctx=16384)
    assert (CUT.attn_layers, CUT.index_layers, CUT.moe_layers) == (5, 2, 4)
    assert CUT.cache_head_dim == 640 and CUT.latent_dim == 576
    assert geo.page_bytes == 32 * (5 * 1280 + 2 * 256) == 221184
    assert geo.pages_per_seq == 512
    pools = jax.eval_shape(lambda: make_kv_buffers(geo, jnp.bfloat16))
    # the latent rows by pairs of tokens: the decode kernel's fetch
    assert pools[0].shape == (5, 1, 24577, 16, 2, 640)
    assert pools[1].shape == (2, 1, 24577, 32, 128)
    assert 24577 * geo.page_bytes / 1e9 == pytest.approx(5.436, abs=1e-3)
    # a spec without an indexer keeps its one pool
    plain = spec_for_model_id("tiny-mla-moe")
    assert plain.index_layers == 0 and not plain.is_dsa
    assert make_kv_buffers(geometry(plain), jnp.float32)[1] is None


def test_parameter_counts_and_layer_kinds():
    assert abs(PUBLISHED.num_params / 1e9 - 743.4) < 0.1
    assert abs(CUT.num_params / 1e9 - 3.881) < 0.001
    assert (PUBLISHED.lead_layers, PUBLISHED.layers_per_period,
            PUBLISHED.num_periods) == (6, 4, 18)
    assert (PUBLISHED.attn_layers, PUBLISHED.index_layers,
            PUBLISHED.moe_layers) == (78, 21, 75)
    assert PUBLISHED.lead_blocks == (("dsa", "mlp"),) * 3 + (
        ("mla", "moe"),) * 3
    assert (CUT.lead_layers, CUT.num_periods) == (1, 1)
    assert CUT.lead_blocks == (("dsa", "mlp"),)
    assert [b[0] for b in CUT.period_blocks] == [
        "mla", "moe", "mla", "moe", "mla", "moe", "dsa", "moe"]
    for key in ("indexer_types", "mlp_layer_types", "rope_parameters"):
        assert getattr(CUT, key) == CONFIG[key]
        assert getattr(PUBLISHED, key) == CONFIG["published"].get(
            key, CONFIG[key])
    assert (SPEC.lead_layers, SPEC.num_periods, SPEC.index_layers,
            SPEC.attn_layers) == (1, 2, 3, 9)
    shapes = jax.eval_shape(lambda: decoder.init_params(
        CUT, jax.random.PRNGKey(0), jnp.bfloat16))
    held = sum(x.size for x in jax.tree.leaves(shapes))
    assert held == CUT.num_params
    assert held * 2 / 1e9 == pytest.approx(7.763, abs=1e-3)
