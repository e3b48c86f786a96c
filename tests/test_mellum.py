"""``tiny-mellum`` (Mellum2 at toy widths: two periods of three window
layers of 8 tokens to one full layer, the window layers' K/V a per-slot
RING beside a pool that holds the full layers alone, each layer KIND
under a rotary of its own, YaRN with its amplitude on the full layers
alone, a softmax-routed expert layer with every expert held and no
shared one) against the plain reference's full forward
(``perfbench/references/mellum.py``: no cache, the window a mask on full
scores) on the same seeded weights: the forwards directly (whole prompt,
then decode through ring and pool; a suffix against cached pages), the
two rotaries, the published numbers, and the kernels interpreted at the
cell's head counts and ring.  ``tests/test_mellum_engine.py`` has the
same through the engine."""

import copy
import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import manifest
from perfbench.references import mellum as ref
from tests import window_stack_forwards as forwards
from tests.family_contract import one_length
from vgate_tpu.models import decoder, hybrid
from vgate_tpu.models.specs import spec_for_model_id
from vgate_tpu.ops import moe
from vgate_tpu.ops.rope import rope_frequencies
from vgate_tpu.runtime.kv_cache import KVGeometry

SPEC = spec_for_model_id("tiny-mellum")
PUBLISHED = spec_for_model_id("JetBrains/Mellum2-12B-A2.5B-Instruct")
CUT = dataclasses.replace(PUBLISHED, name="mellum-cut", num_layers=8)
FILE = manifest.load_json(
    manifest.HERE, "configs", "mellum2-12b-a2.5b-l8.json")
# the tiny-mellum preset under the published config's keys: what the
# configuration's rehearsal serves
TINY = FILE["rehearse"]["model"]
# float32 on both sides; only the order of sums and the form differ (a
# ring and blockwise softmax against one masked softmax, the grouped
# product against one expert at a time)
TOL = 1e-4
PS, SLOTS, RING = 4, 4, 12  # page, decode slots, a ring's tokens (3 pages)
BUCKET, REF_LEN = 64, 96
@pytest.fixture(scope="module")
def params():
    return decoder.init_params(SPEC, jax.random.PRNGKey(0), jnp.float32)


def reference(seq, prompt_len, cfg=TINY):
    """The plain reference's rows for ``seq[prompt_len:]``."""
    return one_length(functools.partial(ref.logprobs, cfg, 0, jnp.float32),
                      seq, prompt_len, REF_LEN)


def served_logprobs(params, seq, prompt_len, cached=0):
    """The prompt whole, or its first ``cached`` tokens and then the rest
    as a suffix against those pages and the ring."""
    return forwards.served_logprobs(
        SPEC, params, seq, prompt_len, page=PS, slots=SLOTS, bucket=BUCKET,
        chunks=(cached, prompt_len - cached) if cached else None)


def sequence(prompt_len, decoded):
    rng = np.random.default_rng(prompt_len)
    return [int(t) for t in rng.integers(3, 500, prompt_len + decoded)]


@pytest.mark.parametrize("prompt_len, decoded, what", [
    (6, 5, "inside one window"),
    (RING + 1, 5, "just past one ring"),
    (4 * RING + 2, 6, "several rings, a page boundary in the decode steps"),
    (13, 3 * RING + 4, "decode for three rings' length"),
])
def test_whole_prompt_then_decode_through_rings_and_pool(
        params, prompt_len, decoded, what):
    seq = sequence(prompt_len, decoded)
    got = served_logprobs(params, seq, prompt_len)
    want = reference(seq, prompt_len)
    assert got.shape == want.shape
    assert np.abs(got - want).max() < TOL, what


@pytest.mark.parametrize("cached", [16, 24])
def test_a_suffix_against_cached_pages_and_the_ring(params, cached):
    """The later rows attend to the pool's pages of the rows before
    them (full layers, YaRN'd keys with the amplitude on them) and to
    the ring as those rows left it."""
    seq = sequence(30, 5)
    got = served_logprobs(params, seq, 30, cached=cached)
    assert np.abs(got - reference(seq, 30)).max() < TOL
    assert np.abs(got - served_logprobs(params, seq, 30)).max() < TOL


def _changed(change):
    cfg = copy.deepcopy(TINY)
    change(cfg["rope_parameters"])
    return cfg


@pytest.mark.parametrize("what, change", [
    ("the full layers under the window layers' rotary",
     lambda rp: rp.update(full_attention=dict(rp["sliding_attention"]))),
    ("YaRN's frequencies without its amplitude",
     lambda rp: rp["full_attention"].update(attention_factor=1.0)),
    ("the window layers under the full layers' rotary",
     lambda rp: rp.update(sliding_attention=dict(rp["full_attention"]))),
])
def test_each_layer_kind_takes_its_own_rotary(params, what, change):
    """A reference whose layers of one kind take the other kind's
    rotary, or whose full layers lose the amplitude, is far from what
    the program serves: the comparison above would fail."""
    seq = sequence(40, 5)
    got = served_logprobs(params, seq, 40)
    assert np.abs(got - reference(seq, 40)).max() < TOL
    wrong = reference(seq, 40, _changed(change))
    assert np.abs(got - wrong).max() > 100 * TOL, what


def test_the_spec_says_which_kind_rotates_how():
    swa, full = PUBLISHED.rotary("swa"), PUBLISHED.rotary("attn")
    assert swa == (500_000.0, None, 1.0)
    assert full.scaling == ("yarn", 16.0, 32.0, 1.0, 8192)
    assert full.amplitude == 0.1 * math.log(16) + 1 == 1.2772588722239782
    assert PUBLISHED.rope_parameters == FILE["rope_parameters"]
    assert SPEC.rope_parameters == TINY["rope_parameters"]
    assert PUBLISHED.rotary_by_kind == {
        "sliding_attention": {"type": "default", "theta": 500_000.0,
                              "factor": 1.0, "amplitude": 1.0},
        "full_attention": {"type": "yarn", "theta": 500_000.0,
                           "factor": 16.0,
                           "amplitude": 1.2772588722239782}}
    # K-EXAONE's: its full layers take none, its window layers the plain
    exaone = spec_for_model_id("tiny-swa-moe")
    assert exaone.rotary("attn") is None
    assert exaone.rotary("swa") == (10000.0, None, 1.0)
    assert exaone.rotary_by_kind["full_attention"] == {"type": "none"}
    # a stack of one kind keeps the one rotary it had
    dense = spec_for_model_id("tiny-dense")
    assert dense.rotary("attn") == (dense.rope_theta, None, 1.0)
    assert dense.rotary_by_kind == {}


def test_the_yarn_ramp_runs_from_18_to_35_at_the_published_numbers():
    group = FILE["rope_parameters"]["full_attention"]
    assert ref.yarn_ramp(group, 128) == (18, 35)
    plain = np.asarray(rope_frequencies(128, 500_000.0))
    yarn = np.asarray(rope_frequencies(128, 500_000.0,
                                       PUBLISHED.rotary("attn").scaling))
    want, amplitude = ref.rotary_of(group, 128)
    np.testing.assert_allclose(yarn, np.asarray(want), rtol=1e-6)
    assert amplitude == 1.2772588722239782
    assert (yarn[:19] == plain[:19]).all()  # r = 0 up to dimension 18
    np.testing.assert_allclose(yarn[35:], plain[35:] / 16, rtol=1e-6)
    r = (26 - 18) / 17  # a dimension on the ramp
    np.testing.assert_allclose(
        yarn[26], (1 - r) * plain[26] + r * plain[26] / 16, rtol=1e-6)


def test_the_routers_weights_are_the_eight_largest_renormalised():
    cfg = dict(TINY, num_experts_per_tok=3)
    x = jax.random.normal(jax.random.PRNGKey(5), (7, 64))
    w = {"router": jax.random.normal(jax.random.PRNGKey(6), (64, 8))}
    idx, vals = ref.route(x, w, cfg)
    p = np.asarray(jax.nn.softmax(x @ w["router"], axis=-1))
    for t in range(7):
        top = np.argsort(-p[t])[:3]
        assert sorted(idx[t]) == sorted(top)
        np.testing.assert_allclose(
            sorted(vals[t]), sorted(p[t, top] / p[t, top].sum()), rtol=1e-5)
    np.testing.assert_allclose(vals.sum(axis=1), 1.0, rtol=1e-6)
    # the program's layer on the same weights: every pair is held
    spec = dataclasses.replace(SPEC, experts_per_token=3)
    lw = ref.draw_layer(cfg, 0, 1, jnp.float32)
    lp = {k: (v if k == "router" else {"w": v}) for k, v in lw.items()}
    rows = jax.random.normal(jax.random.PRNGKey(7), (40, 64))
    out, stats = moe.expert_layer(rows, lp, spec, jax.nn.silu)
    with jax.default_matmul_precision("highest"):
        want = ref.moe(rows, lw, cfg)
    assert np.abs(np.asarray(out - want)).max() < 1e-5
    assert int(stats[0]) == int(stats[1]) == 40 * 3
    assert moe.capacity(spec, 40 * 3) == 40 * 3


def test_parameter_counts_layer_kinds_and_the_two_geometries():
    assert round(PUBLISHED.num_params / 1e6, 1) == 12149.9
    assert round(CUT.num_params / 1e6, 1) == 3795.0
    assert (PUBLISHED.lead_layers, PUBLISHED.num_periods) == (0, 7)
    assert (PUBLISHED.attn_layers, PUBLISHED.swa_layers,
            PUBLISHED.moe_layers) == (7, 21, 28)
    assert (CUT.lead_layers, CUT.num_periods) == (0, 2)
    assert (CUT.attn_layers, CUT.swa_layers, CUT.moe_layers) == (2, 6, 8)
    assert [b[0] for b in CUT.period_blocks] == [
        "swa", "moe", "swa", "moe", "swa", "moe", "attn", "moe"]
    for key in ("layer_types", "mlp_layer_types", "rope_parameters"):
        assert getattr(CUT, key) == FILE[key]
    assert PUBLISHED.layer_types == FILE["published"]["layer_types"]
    # a page holds the two full layers' K and V, a ring 33 pages a layer
    geo = KVGeometry(
        num_layers=CUT.attn_layers, num_pages=16, page_size=32,
        kv_heads=CUT.cache_heads, head_dim=CUT.cache_head_dim,
        max_model_len=16384, dtype_bytes=2, pools=CUT.kv_pools)
    assert geo.page_bytes == 131072 == 32 * 4096
    assert hybrid.ring_pages(CUT, 32) == 33
    assert hybrid.state_bytes_per_slot(CUT, 2, 32) == 12976128
    state = jax.eval_shape(
        lambda: hybrid.make_state(CUT, 80, jnp.bfloat16, 32))
    assert state["ring_k"].shape == (6, 4, 1 + 80 * 33, 32, 128)
    assert set(state) == {"ring_k", "ring_v"}
    # no selection bias under a softmax router: none drawn, none counted
    tree = jax.eval_shape(lambda: decoder.init_params(
        SPEC, jax.random.PRNGKey(0), jnp.float32))
    assert "router_bias" not in tree["layers"]["window"]
    assert sum(x.size for x in jax.tree.leaves(tree)) == SPEC.num_params


# ------------------------------------------- the kernels, interpreted

def test_the_banded_prompt_kernel_at_32_heads_on_4_and_its_window_blocks():
    """The cell's head counts under the blocks the window's rule gives
    (scaled: a window of 64 in blocks of ``swa_blocks(64)``), against
    the twin with the window as a mask."""
    from vgate_tpu.ops.attention import flash_prefill_attention
    from vgate_tpu.ops.pallas.flash_prefill import (
        swa_blocks,
        swa_prefill_attention_pallas,
    )

    assert swa_blocks(128) == (256, 128)  # K-EXAONE's, as probed at 128
    assert swa_blocks(8) == (256, 128)  # the tiny presets' too
    assert swa_blocks(1024) == (1024, 1024)  # a band of two blocks
    assert swa_blocks(4096) == (1024, 1024)  # no wider than a full layer's
    rng = np.random.default_rng(57)
    B, S, H, KV, hd, window = 1, 256, 32, 4, 128, 64
    q, k, v = (jnp.asarray(rng.normal(size=(B, S, h, hd)), jnp.float32)
               for h in (H, KV, KV))
    lens = jnp.asarray([201], jnp.int32)
    want = flash_prefill_attention(q, k, v, lens, window=window)
    got = swa_prefill_attention_pallas(
        q, k, v, lens, window, block_q=64, block_k=32, interpret=True,
        skip_padding=True)
    np.testing.assert_allclose(np.asarray(got[0, :201]),
                               np.asarray(want[0, :201]),
                               rtol=2e-5, atol=2e-5)


def test_the_decode_kernel_over_a_33_page_ring_at_32_heads_on_4():
    """Two slots' rings of 33 pages (a window of 1,024 at page 32), one
    context inside the window and one that has wrapped its ring, through
    the paged decode kernel's ``window`` path against the twin: chunks
    of 8 pages, so a wrapped ring is five work-list items, the last one
    a part of a chunk."""
    from vgate_tpu.ops.attention import paged_decode_attention
    from vgate_tpu.ops.pallas.paged_attention import (
        _decode_sizes,
        swa_decode_attention_pallas,
    )

    H, KV, hd, ps, window, R = 32, 4, 128, 32, 1024, 33
    # the cell's own launch (bf16, 80 slots): 8 pages a chunk, one item
    # a trip at 4 KV heads; float32 rows here halve the chunk, so the
    # wrapped ring below is nine items and the last a part of a chunk
    assert _decode_sizes(80, KV, H // KV, hd, ps, 512, jnp.bfloat16,
                         jnp.bfloat16)[::2] == (8, 1)
    rng = np.random.default_rng(33)
    lens = np.asarray([700, 1900], np.int32)
    n_pages = 64
    ring_k, ring_v = (jnp.asarray(
        rng.normal(size=(KV, 1 + 2 * R, ps, hd)), jnp.float32)
        for _ in range(2))
    tables = hybrid.ring_tables(jnp.arange(2), n_pages, 2, R)
    q = jnp.asarray(rng.normal(size=(2, H, hd)), jnp.float32)
    want = paged_decode_attention(
        q, ring_k, ring_v, tables, jnp.asarray(lens), window=window)
    got = swa_decode_attention_pallas(
        q, ring_k, ring_v, tables, jnp.asarray(lens), window,
        interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
