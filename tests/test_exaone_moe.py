"""``tiny-swa-moe`` (K-EXAONE at toy widths: a leading dense layer, then
window layers of 8 tokens and full layers three to one, the window
layers' K/V a per-slot RING beside a pool that holds the full layers
alone, a sigmoid-routed expert layer with a shared expert) against the
plain reference's full forward (``perfbench/references/exaone_moe.py``:
no cache, the window a mask on full scores) on the same seeded weights:
the forwards directly (whole prompt, then decode through ring and pool
for three rings' length; a chunked prefill); the cache's two geometries;
and the expert layer's shares.  ``tests/test_exaone_moe_engine.py`` has
the same through the engine."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import manifest
from perfbench.references import exaone_moe as ref
from tests import prompt_row_blocks as row_blocks
from tests import window_stack_forwards as forwards
from tests.family_contract import one_length
from vgate_tpu.models import decoder, hybrid, specs
from vgate_tpu.models.specs import spec_for_model_id
from vgate_tpu.ops import moe
from vgate_tpu.runtime.kv_cache import KVGeometry

SPEC = spec_for_model_id("tiny-swa-moe")
PUBLISHED = spec_for_model_id("LGAI-EXAONE/K-EXAONE-236B-A23B")
CUT = dataclasses.replace(
    PUBLISHED, name="exaone-cut", num_layers=5, num_experts=16,
    vocab_size=19200)
# the tiny-swa-moe preset under the published config's keys: what the
# configuration's rehearsal serves
TINY = manifest.load_json(
    manifest.HERE, "configs", "k-exaone-236b-a23b-l5e16.json"
)["rehearse"]["model"]
# float32 on both sides; only the order of sums and the form differ (a
# ring and blockwise softmax against one masked softmax, the grouped
# product against one expert at a time): measured 9.5e-7 at most
TOL = 1e-4
PS, SLOTS, RING = 4, 4, 12  # page, decode slots, a ring's tokens (3 pages)
# the rows of every whole-prompt pass (what the serving path's buckets
# do: a length is ``seq_lens``, not a shape), and the length every
# sequence has for the reference
BUCKET, REF_LEN = 32, 64
@pytest.fixture(scope="module")
def params():
    return decoder.init_params(SPEC, jax.random.PRNGKey(0), jnp.float32)


def reference(seq, prompt_len):
    """The plain reference's rows for ``seq[prompt_len:]``."""
    return one_length(functools.partial(ref.logprobs, TINY, 0, jnp.float32),
                      seq, prompt_len, REF_LEN)


def served_logprobs(params, seq, prompt_len, chunks=None):
    return forwards.served_logprobs(
        SPEC, params, seq, prompt_len, page=PS, slots=SLOTS, bucket=BUCKET,
        chunks=chunks)


@pytest.mark.parametrize("prompt_len, decoded, what", [
    (5, 6, "under one ring"),
    (RING, 6, "exactly one ring"),
    (13, 3 * RING + 4, "decode for three rings' length"),
    (30, 7, "a page boundary inside the decode steps"),
])
def test_whole_prompt_then_decode_through_ring_and_pool(
        params, prompt_len, decoded, what):
    rng = np.random.default_rng(prompt_len)
    seq = [int(t) for t in rng.integers(3, 500, prompt_len + decoded)]
    got = served_logprobs(params, seq, prompt_len)
    want = reference(seq, prompt_len)
    assert got.shape == want.shape
    assert np.abs(got - want).max() < TOL, what


@pytest.mark.parametrize("chunks", [(16, 8, 8), (8, 24), (24, 8)])
def test_a_chunked_prefill_reads_the_ring_and_gives_the_whole_prompts_logits(
        params, chunks):
    """A later chunk attends to what the chunks before left in the ring
    (read BEFORE its own rows overwrite it) and to itself."""
    rng = np.random.default_rng(7)
    seq = [int(t) for t in rng.integers(3, 500, 30 + 5)]
    whole = served_logprobs(params, seq, 30)
    chunked = served_logprobs(params, seq, 30, chunks=chunks)
    want = reference(seq, 30)
    assert np.abs(chunked - whole).max() < TOL
    assert np.abs(chunked - want).max() < TOL


def test_ring_tables_send_a_page_to_its_slots_ring_or_to_the_trash():
    """5 pages a ring: page p of slot s is ring page 1 + 5 s + p mod 5;
    a prompt pass keeps the last five pages that hold a real token (five
    DIFFERENT ring pages) and sends every other to page 0; a padding
    row (a slot past the rings) writes the trash alone."""
    read = np.asarray(hybrid.ring_tables(jnp.arange(2), 12, 2, 5))
    assert read[0].tolist() == [1, 2, 3, 4, 5, 1, 2, 3, 4, 5, 1, 2]
    assert read[1, :6].tolist() == [6, 7, 8, 9, 10, 6]
    write = np.asarray(hybrid.ring_tables(
        jnp.asarray([1, 7]), 12, 2, 5, last=jnp.asarray([8, 8])))
    assert write[0].tolist() == [0, 0, 0, 0, 10, 6, 7, 8, 9, 0, 0, 0]
    assert sorted(write[0][write[0] > 0]) == [6, 7, 8, 9, 10]
    assert not write[1].any()
    # a chunk that starts at page 6 and ends in page 7
    late = np.asarray(hybrid.ring_tables(
        jnp.asarray([0]), 4, 2, 5, first=jnp.asarray([6]),
        last=jnp.asarray([7])))
    assert late[0].tolist() == [2, 3, 0, 0]


def test_no_window_layer_holds_pages():
    """The published-size spec at the 5-layer cut: a page holds the ONE
    full layer's K and V (32 tokens x 4,096 B), a slot's rings 4 layers
    x 160 tokens x 4,096 B, whatever the context."""
    geo = KVGeometry(
        num_layers=CUT.attn_layers, num_pages=16, page_size=32,
        kv_heads=CUT.cache_heads, head_dim=CUT.cache_head_dim,
        max_model_len=8192, dtype_bytes=2, pools=CUT.kv_pools)
    assert (CUT.attn_layers, CUT.swa_layers, CUT.moe_layers) == (1, 4, 4)
    assert geo.page_bytes == 131072 == 32 * 4096
    assert hybrid.state_bytes_per_slot(CUT, 2, 32) == 2621440
    state = jax.eval_shape(
        lambda: hybrid.make_state(CUT, 192, jnp.bfloat16, 32))
    assert state["ring_k"].shape == (4, 8, 1 + 192 * 5, 32, 128)
    assert set(state) == {"ring_k", "ring_v"}
    # what the one-pool layout would hold a token: five layers' pages
    assert 5 * 4096 * 192 * 8192 / 1e9 > 32


def test_parameter_counts_and_layer_kinds():
    assert abs(PUBLISHED.num_params / 1e9 - 236.6) < 0.1
    assert abs(CUT.num_params / 1e9 - 3.712) < 0.005
    assert (PUBLISHED.lead_layers, PUBLISHED.num_periods) == (4, 11)
    assert (PUBLISHED.attn_layers, PUBLISHED.swa_layers,
            PUBLISHED.moe_layers) == (12, 36, 47)
    assert (CUT.lead_layers, CUT.num_periods) == (1, 1)
    assert CUT.lead_blocks == (("swa", "mlp"),)
    assert [b[0] for b in CUT.period_blocks] == [
        "swa", "moe", "swa", "moe", "attn", "moe", "swa", "moe"]
    assert CUT.layer_windows == (128, 128, 128, 0, 128)
    published = manifest.load_json(
        manifest.HERE, "configs", "k-exaone-236b-a23b-l5e16.json")
    for key in ("layer_types", "mlp_layer_types", "sliding_windows",
                "rope_parameters"):
        assert getattr(CUT, key) == published[key]
    assert PUBLISHED.layer_types == published["published"]["layer_types"]
    assert (SPEC.lead_layers, SPEC.num_periods, SPEC.swa_layers,
            SPEC.attn_layers) == (1, 2, 7, 2)


def test_the_eight_shares_add_up_to_the_uncut_reference(at_a_time):
    """128 experts over eight chips, sixteen each, the router 128 wide in
    every share: the shares' routed parts plus the shared expert counted
    once are the uncut reference's layer, whether a share's held pairs
    (a quarter of the 320 at a time by the layer's rule: 96) go in one
    trip or in several."""
    spec = dataclasses.replace(
        SPEC, name="tiny-128", num_experts=128, router_width=128,
        experts_per_token=8)
    cfg = dict(TINY, num_experts=128, router_width=128,
               num_experts_per_tok=8)
    lw = ref.draw_layer(cfg, 0, 1, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(3), (40, spec.hidden_size))
    with jax.default_matmul_precision("highest"):
        want = ref.moe(x, lw, cfg)
        shared = ref.moe(x, lw, cfg, count=0)
    lp = {k: ({"w": v} if k != "router" and k != "router_bias" else v)
          for k, v in lw.items()}
    act = jax.nn.silu
    total = jnp.zeros_like(x)
    assert moe.capacity(
        dataclasses.replace(spec, num_experts=16), 40 * 8) == 96
    for chip in range(8):
        first = 16 * chip
        cut = dataclasses.replace(
            spec, num_experts=16, first_expert=first,
            shared_expert_intermediate_size=0, n_shared_experts=0)
        held = {n: lw[n][first:first + 16] for n in ("gate", "up", "down")}
        part = dict(lp, **{n: {"w": w} for n, w in held.items()})
        assert part["router"].shape == (spec.hidden_size, 128)
        _, stats = moe.expert_layer(x, part, cut, act)
        extra = at_a_time(int(stats[1]))
        out, stats = moe.expert_layer(x, part, cut, act)
        total = total + out
        # the reference's own share agrees with the program's
        with jax.default_matmul_precision("highest"):
            mine = ref.moe(x, dict(lw, **held), cfg, shared=False,
                           first=first, count=16)
        assert np.abs(np.asarray(out - mine)).max() < 1e-5
        assert int(stats[0]) == 40 * 8 and int(stats[4]) == extra
    assert np.abs(np.asarray(total + shared - want)).max() < 1e-5


@pytest.mark.parametrize("block_q, block_k, window, lens", [
    (32, 16, 16, [128, 77]),   # a band of 3 blocks: 16 + the block's own 32
    (64, 16, 24, [100, 1]),    # a window that ends inside a key block
    (16, 16, 40, [128, 50]),   # a window of several key blocks
    (128, 128, 8, [128, 128]), # one block holds every row: a band of one
])
def test_the_banded_prompt_kernel_is_the_window_as_a_mask(
        block_q, block_k, window, lens):
    """``swa_prefill_attention_pallas`` visits only the key blocks a
    window reaches; its rows are the twin's with the window as a mask
    (interpret mode: the kernel's own arithmetic on the CPU)."""
    from vgate_tpu.ops.attention import flash_prefill_attention
    from vgate_tpu.ops.pallas.flash_prefill import (
        swa_prefill_attention_pallas,
    )

    rng = np.random.default_rng(38)
    B, S, H, KV, hd = 2, 128, 4, 2, 32
    q, k, v = (jnp.asarray(rng.normal(size=(B, S, h, hd)), jnp.float32)
               for h in (H, KV, KV))
    seq_lens = jnp.asarray(lens, jnp.int32)
    want = flash_prefill_attention(q, k, v, seq_lens, window=window)
    got = swa_prefill_attention_pallas(
        q, k, v, seq_lens, window, block_q=block_q, block_k=block_k,
        interpret=True)
    for b, n in enumerate(lens):  # rows past a sequence's end are nobody's
        np.testing.assert_allclose(
            np.asarray(got[b, :n]), np.asarray(want[b, :n]),
            rtol=2e-5, atol=2e-5)


# one period behind the leading layer (L L L G L, the cell's own stack,
# as tests/test_exaone_moe_engine.py has it): every kind of sub-block
# the row loop wraps, in the fewest layers the row-block passes compile
SHORT = specs._register(dataclasses.replace(
    SPEC, name="tiny-swa-moe-l5", num_layers=5))


@pytest.mark.parametrize("fill", list(row_blocks.FILLS))
def test_a_long_prompt_pass_works_on_its_own_row_blocks(fill):
    """A bucket of four blocks of rows (the block patched to 8): the
    window and full layers' projections, the dense layer and the expert
    layer's position-wise parts in a counted loop over the blocks the
    longer prompt reaches, against the pass over the whole bucket."""
    row_blocks.check_prompt_pass(SHORT.name, row_blocks.FILLS[fill])


def test_greedy_tokens_are_the_same_with_the_row_loop(monkeypatch):
    row_blocks.check_greedy_identity(monkeypatch, SHORT.name)
