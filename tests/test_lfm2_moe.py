"""``tiny-lfm2-moe`` (LFM2-24B-A2B at toy widths: gated short
convolutions whose state is a two-row tail a slot, GQA attention with
per-head norms, two leading dense layers, a sigmoid-routed expert layer
with a selection bias and no shared expert) against the plain
reference's full forward (``perfbench/references/lfm2_moe.py``: no
cache, no tail, the convolution a sum over three shifted copies) on the
same seeded weights: the forwards directly (whole prompt, then decode
through tails and pool; a chunked prefill); heads of 64 two to a
128-lane row of the pool against the unpacked attention, bit for bit;
the cache's geometry; and the expert layer's shares.
``tests/test_lfm2_moe_engine.py`` has the same through the engine."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import manifest
from perfbench.references import lfm2_moe as ref
from tests import prompt_row_blocks as row_blocks
from tests.family_contract import one_length
from vgate_tpu.models import decoder, hybrid, specs
from vgate_tpu.models.specs import spec_for_model_id
from vgate_tpu.ops import attention, head_pack, moe
from vgate_tpu.ops import gated_delta as gd
from vgate_tpu.runtime.kv_cache import KVGeometry, make_kv_buffers

SPEC = spec_for_model_id("tiny-lfm2-moe")
PUBLISHED = spec_for_model_id("LiquidAI/LFM2-24B-A2B")
CUT = dataclasses.replace(PUBLISHED, name="lfm2-cut", num_experts=8)
CONFIG = manifest.load_json(manifest.HERE, "configs", "lfm2-24b-a2b-e8.json")
# the tiny-lfm2-moe preset under the published config's keys: what the
# configuration's rehearsal serves
TINY = CONFIG["rehearse"]["model"]
# float32 on both sides; only the order of sums and the form differ (a
# carried tail against shifted copies, blockwise softmax against one,
# the grouped product against one expert at a time): measured 9.5e-7
TOL = 1e-5
PS, SLOTS = 4, 4
PREFILL = jax.jit(decoder.prefill_forward, static_argnums=1)
SUFFIX = jax.jit(decoder.prefill_suffix_forward, static_argnums=1)
DECODE = jax.jit(decoder.decode_forward, static_argnums=1)


@pytest.fixture(scope="module")
def params():
    return decoder.init_params(SPEC, jax.random.PRNGKey(0), jnp.float32)


def fresh_cache(spec=SPEC):
    geo = KVGeometry(
        num_layers=spec.attn_layers, num_pages=64, page_size=PS,
        kv_heads=spec.cache_heads, head_dim=spec.cache_head_dim,
        max_model_len=128, dtype_bytes=4)
    return (*make_kv_buffers(geo, jnp.float32),
            hybrid.make_state(spec, SLOTS, jnp.float32, PS))


def reference(seq, prompt_len, cfg=TINY):
    """The plain reference's rows for ``seq[prompt_len:]``."""
    return one_length(functools.partial(ref.logprobs, cfg, 0, jnp.float32),
                      seq, prompt_len, 64)


def served_logprobs(params, seq, prompt_len, slot=2, chunks=None, spec=SPEC,
                    dirty=False):
    """Log-softmax rows for positions ``prompt_len - 1 .. len(seq) - 2``
    from the program's forwards: the prompt whole (or in ``chunks``),
    then one decode step a token through tails and pool.  ``dirty``: the
    slot's tail starts as another tenant left it."""
    kp, vp, st = fresh_cache(spec)
    if dirty:
        st = {"conv": st["conv"] + 3.0}
    table = np.arange(1, 33, dtype=np.int32)[None]
    one = lambda v: jnp.asarray([v])
    if chunks is None:
        S = -(-prompt_len // 16) * 16
        toks = np.zeros((1, S), np.int32)
        toks[0, :prompt_len] = seq[:prompt_len]
        logits, kp, vp, st = PREFILL(
            params, spec, jnp.asarray(toks), one(prompt_len), kp, vp,
            jnp.asarray(table[:, :S // PS]), state=st, slots=one(slot))
    else:
        done = 0
        for want in chunks:
            n = min(want, prompt_len - done)
            S = -(-n // 8) * 8
            toks = np.zeros((1, S), np.int32)
            toks[0, :n] = seq[done:done + n]
            own = table[:, done // PS: (done + S) // PS]
            logits, kp, vp, st = SUFFIX(
                params, spec, jnp.asarray(toks), one(done), one(n), kp, vp,
                jnp.asarray(own), jnp.asarray(table), state=st,
                slots=one(slot))
            done += n
    rows = [jax.nn.log_softmax(logits[0])]
    tables = np.zeros((SLOTS, 32), np.int32)
    tables[slot] = table[0]
    active = np.arange(SLOTS) == slot
    idle = np.asarray(st["conv"])[:, ~active]
    for pos in range(prompt_len, len(seq) - 1):
        tok = np.where(active, seq[pos], 0).astype(np.int32)
        at = np.where(active, pos, 0).astype(np.int32)
        logits, kp, vp, st, _ = DECODE(
            params, spec, jnp.asarray(tok), jnp.asarray(at), kp, vp,
            jnp.asarray(tables), active=jnp.asarray(active), state=st)
        rows.append(jax.nn.log_softmax(logits[slot]))
    # idle rows are left alone, whatever the steps wrote
    assert np.array_equal(np.asarray(st["conv"])[:, ~active], idle)
    return np.stack([np.asarray(r) for r in rows])


@pytest.mark.parametrize("prompt_len, decoded, what", [
    (1, 4, "a prompt shorter than the tail"),
    (2, 4, "a prompt of exactly the tail's rows"),
    (5, 6, "under two pages"),
    (16, 6, "a prompt that fills its bucket: no padding"),
    (13, 20, "twenty decode steps over the tails"),
    (30, 7, "a page boundary inside the decode steps"),
])
def test_whole_prompt_then_decode_through_tails_and_pool(
        params, prompt_len, decoded, what):
    rng = np.random.default_rng(prompt_len)
    seq = [int(t) for t in rng.integers(3, 500, prompt_len + decoded)]
    got = served_logprobs(params, seq, prompt_len, dirty=True)
    want = reference(seq, prompt_len)
    assert got.shape == want.shape
    assert np.abs(got - want).max() < TOL, what


@pytest.mark.parametrize("chunks", [(16, 8, 8), (8, 24), (24, 8), (8, 4, 18)])
def test_a_chunked_prefill_carries_the_tail_from_chunk_to_chunk(
        params, chunks):
    """A later chunk's convolution starts from the tail the chunks
    before left in the slot (taken at their REAL length: a chunk's
    padding never enters it), its attention reads their pages."""
    rng = np.random.default_rng(7)
    seq = [int(t) for t in rng.integers(3, 500, 30 + 5)]
    whole = served_logprobs(params, seq, 30)
    chunked = served_logprobs(params, seq, 30, chunks=chunks, dirty=True)
    want = reference(seq, 30)
    assert np.abs(chunked - whole).max() < TOL
    assert np.abs(chunked - want).max() < TOL


def test_the_convolution_takes_its_activation_as_an_argument():
    """``causal_conv`` and ``_conv_step``: SiLU unless told otherwise
    (Gated DeltaNet's and Mamba-2's callers say nothing and compute what
    they did); None is the bare taps, and a step from a prompt's tail is
    the next row of the longer prompt."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(2, 9, 6)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(6, 3)), jnp.float32)
    zeros = jnp.zeros((2, 2, 6), jnp.float32)
    lens = jnp.asarray([8, 5])
    bare, tail = gd.causal_conv(x, zeros, w, lens, act=None)
    padded = np.concatenate([np.zeros((2, 2, 6), np.float32), x], axis=1)
    want = sum(np.asarray(w)[:, j] * padded[:, j:j + 9] for j in range(3))
    np.testing.assert_allclose(np.asarray(bare), want, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(tail[0]), np.asarray(x[0, 6:8]))
    np.testing.assert_array_equal(np.asarray(tail[1]), np.asarray(x[1, 3:5]))
    said_nothing, _ = gd.causal_conv(x, zeros, w, lens)
    np.testing.assert_allclose(
        np.asarray(said_nothing), np.asarray(jax.nn.silu(bare)), atol=1e-6)
    # one decode step from the tails: row 8 of sequence 0, row 5 of 1
    row = jnp.stack([x[0, 8], x[1, 5]])
    active = jnp.asarray([True, False])
    y, moved = hybrid._conv_step(tail, row, w, None, active, act=None,
                                 scope="short_conv")
    np.testing.assert_allclose(np.asarray(y[0]), want[0, 8], atol=1e-6)
    np.testing.assert_allclose(np.asarray(y[1]), want[1, 5], atol=1e-6)
    np.testing.assert_array_equal(np.asarray(moved[0]), np.asarray(x[0, 7:9]))
    np.testing.assert_array_equal(np.asarray(moved[1]), np.asarray(tail[1]))
    y_silu, _ = hybrid._conv_step(tail, row, w, None, active)
    np.testing.assert_allclose(
        np.asarray(y_silu), np.asarray(jax.nn.silu(y)), atol=1e-6)


# ---- heads of 64, two to a 128-lane row of the pool

# the leading layers and ONE period, at 4 heads on 2 KV heads of 64
WIDE = dataclasses.replace(
    SPEC, name="tiny-lfm2-hd64", head_dim=64, num_layers=8,
    conv_pattern=SPEC.conv_pattern[:8])
PACKED = WIDE.pack_kv_heads()


def _pools(rng, layers, KV, pages, hd):
    """(K, V) unpacked ``[L, KV, P, ps, hd]`` and the same rows packed
    ``[L, KV / 2, P, ps, 2 hd]``."""
    pack = lambda t: jnp.transpose(
        t.reshape(layers, KV // 2, 2, pages, PS, hd),
        (0, 1, 3, 4, 2, 5)).reshape(layers, KV // 2, pages, PS, 2 * hd)
    k, v = (jnp.asarray(rng.normal(size=(layers, KV, pages, PS, hd)),
                        jnp.float32) for _ in range(2))
    return (k, v), (pack(k), pack(v))


@pytest.mark.parametrize("H, KV", [(4, 2), (8, 4), (14, 2)],
                         ids=["G2", "G2-two-rows", "G7-one-row"])
def test_packed_rows_give_the_unpacked_attention_bit_for_bit(H, KV):
    """The paged decode attention and the paged suffix attention over a
    pool of packed rows, queries as ``[q | 0]`` / ``[0 | q]`` at the
    head's own scale, against the same functions over the unpacked pool:
    adding zeros is exact, so float32 results are equal to the bit."""
    spec = dataclasses.replace(WIDE, num_heads=H, num_kv_heads=KV)
    packed = spec.pack_kv_heads()
    assert (packed.cache_heads, packed.cache_head_dim) == (KV // 2, 128)
    rng = np.random.default_rng(64)
    (k, v), (kp, vp) = _pools(rng, 2, KV, 12, 64)
    tables = jnp.asarray(rng.permutation(np.arange(1, 11)).reshape(2, 5),
                         jnp.int32)
    lens = jnp.asarray([17, 9], jnp.int32)
    q = jnp.asarray(rng.normal(size=(2, H, 64)), jnp.float32)
    want = attention.paged_decode_attention(q, k, v, tables, lens, layer=1)
    got = head_pack.over_packed_pool(
        attention.paged_decode_attention, packed)(
            q, kp, vp, tables, lens, layer=1)
    assert got.shape == want.shape == (2, H, 64)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # query rows against a cached prefix (a chunk, a suffix)
    qs = jnp.asarray(rng.normal(size=(2, 8, H, 64)), jnp.float32)
    prefix = jnp.asarray([8, 4], jnp.int32)
    want = attention.paged_suffix_attention(
        qs, k, v, tables, prefix, prefix + jnp.asarray([8, 5]), layer=0)
    got = head_pack.over_packed_pool(
        attention.paged_suffix_attention, packed)(
            qs, kp, vp, tables, prefix, prefix + jnp.asarray([8, 5]),
            layer=0)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # a spec whose rows hold one head is handed its function back
    assert head_pack.over_packed_pool(max, spec) is max


def test_the_packed_kernel_launch_is_the_unpacked_attention():
    """The Pallas decode kernel (interpret mode: its own arithmetic on
    the CPU) at (KV / 2 rows, 2 G heads a row, 128 lanes), writing the
    step's packed K and V itself, against the jnp attention over the
    unpacked pool with the token scattered in."""
    from vgate_tpu.ops.kv_quant import kv_write_tokens
    from vgate_tpu.ops.pallas.paged_attention import (
        paged_decode_attention_pallas,
    )

    H, KV = 8, 4
    spec = dataclasses.replace(WIDE, num_heads=H, num_kv_heads=KV)
    packed = spec.pack_kv_heads()
    rng = np.random.default_rng(65)
    (k, v), (kp, vp) = _pools(rng, 2, KV, 12, 64)
    tables = jnp.asarray(np.arange(1, 11).reshape(2, 5), jnp.int32)
    lens = jnp.asarray([18, 9], jnp.int32)
    q, k_new, v_new = (jnp.asarray(rng.normal(size=(2, h, 64)), jnp.float32)
                       for h in (H, KV, KV))
    ids = tables[jnp.arange(2), (lens - 1) // PS]
    k1 = kv_write_tokens(k, ids, (lens - 1) % PS, k_new, layer=1)
    v1 = kv_write_tokens(v, ids, (lens - 1) % PS, v_new, layer=1)
    want = attention.paged_decode_attention(q, k1, v1, tables, lens, layer=1)
    got, kp1, vp1 = head_pack.over_packed_pool(
        paged_decode_attention_pallas, packed)(
            q, kp, vp, tables, lens, layer=1, k_new=k_new, v_new=v_new,
            interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # the pool the kernel leaves is the scatter's, packed
    repack = lambda t: jnp.transpose(
        t.reshape(2, KV // 2, 2, 12, PS, 64),
        (0, 1, 3, 4, 2, 5)).reshape(2, KV // 2, 12, PS, 128)
    np.testing.assert_array_equal(np.asarray(kp1), np.asarray(repack(k1)))
    np.testing.assert_array_equal(np.asarray(vp1), np.asarray(repack(v1)))


def test_a_stack_at_head_64_serves_the_same_packed_and_unpacked():
    """The whole program (a prompt in chunks: whole pages written from
    row 0, then query rows against a cached prefix; decode steps over a
    page boundary) at 4 heads on 2 KV heads of 64: a pool of ONE packed
    row against the unpacked pool of two, and the reference."""
    params = decoder.init_params(WIDE, jax.random.PRNGKey(0), jnp.float32)
    cfg = dict(TINY, head_dim=64, num_hidden_layers=8,
               layer_types=TINY["layer_types"][:8])
    rng = np.random.default_rng(11)
    seq = [int(t) for t in rng.integers(3, 500, 30 + 6)]
    want = reference(seq, 30, cfg)
    plain = served_logprobs(params, seq, 30, chunks=(16, 16), spec=WIDE)
    packed = served_logprobs(params, seq, 30, chunks=(16, 16), spec=PACKED)
    assert np.abs(packed - plain).max() < 1e-6
    assert np.abs(packed - want).max() < TOL
    kp, _, _ = fresh_cache(PACKED)
    assert kp.shape == (PACKED.attn_layers, 1, 64, PS, 128)


# ---- the cache's geometry, the stack, the counts


def test_a_slot_keeps_a_tail_alone_and_a_token_2048_bytes_a_layer():
    """The published-size spec at the cut: a page holds the ten
    attention layers' K and V with no padding lane (32 tokens x 20,480
    B), a slot 30 tails of 2 x 2,048 values and NO tile."""
    spec = CUT.pack_kv_heads()
    geo = KVGeometry(
        num_layers=spec.attn_layers, num_pages=16, page_size=32,
        kv_heads=spec.cache_heads, head_dim=spec.cache_head_dim,
        max_model_len=2048, dtype_bytes=2, pools=spec.kv_pools)
    assert (spec.attn_layers, spec.conv_layers, spec.moe_layers,
            spec.linear_layers) == (10, 30, 38, 0)
    assert (geo.kv_heads, geo.head_dim) == (4, 128)
    assert geo.page_bytes == 32 * 20480
    assert hybrid.state_bytes_per_slot(spec, 2, 32) == 245760
    state = jax.eval_shape(
        lambda: hybrid.make_state(spec, 256, jnp.bfloat16, 32))
    assert set(state) == {"conv"}
    assert state["conv"].shape == (30, 256, 2, 2048)
    assert spec.recurrent_kind == "conv" and spec.slot_state_layers == 30
    # unpacked, XLA's tiled layout would pad every row to 128 lanes
    assert CUT.cache_head_dim == 64 and CUT.kv_heads_pair
    odd = dataclasses.replace(CUT, num_heads=33, num_kv_heads=3)
    assert odd.pack_kv_heads() is odd


def test_parameter_counts_and_layer_kinds():
    assert abs(PUBLISHED.num_params / 1e9 - 23.84) < 0.01
    assert abs(CUT.num_params / 1e6 - 3761) < 1
    assert (PUBLISHED.lead_layers, PUBLISHED.layers_per_period,
            PUBLISHED.num_periods) == (4, 4, 9)
    assert PUBLISHED.lead_blocks == (
        ("conv", "mlp"), ("conv", "mlp"), ("attn", "moe"), ("conv", "moe"))
    assert [b[0] for b in PUBLISHED.period_blocks] == [
        "conv", "moe", "conv", "moe", "attn", "moe", "conv", "moe"]
    assert (SPEC.lead_layers, SPEC.num_periods, SPEC.conv_layers,
            SPEC.attn_layers, SPEC.moe_layers) == (4, 2, 9, 3, 10)
    for key, attr in CONFIG["program"]["spec_keys"].items():
        assert getattr(CUT, attr) == CONFIG[key], key
    assert PUBLISHED.layer_types == CONFIG["layer_types"]
    assert SPEC.layer_types == CONFIG["layer_types"][:12] == TINY["layer_types"]


def test_the_eight_shares_add_up_to_the_uncut_reference():
    """64 experts over eight chips, eight each, the router 64 wide in
    every share and top 4: the shares' routed sums, ``first_expert`` 0,
    8, ... 56, add up to the uncut reference's whole expert layer."""
    spec = dataclasses.replace(
        SPEC, name="tiny-64", num_experts=64, router_width=64,
        experts_per_token=4)
    cfg = dict(TINY, num_experts=64, router_width=64, num_experts_per_tok=4)
    lw = ref.draw_layer(cfg, 0, 5, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(3), (40, spec.hidden_size))
    with jax.default_matmul_precision("highest"):
        want = ref.experts(x, lw, cfg)
    names = {"gate": "w1", "up": "w3", "down": "w2"}
    total, held_pairs = jnp.zeros_like(x), 0
    for chip in range(8):
        first = 8 * chip
        cut = dataclasses.replace(spec, num_experts=8, first_expert=first)
        lp = {"router": lw["router"], "router_bias": lw["router_bias"],
              **{mine: {"w": lw[theirs][first:first + 8]}
                 for mine, theirs in names.items()}}
        assert lp["router"].shape == (spec.hidden_size, 64)
        out, stats = moe.expert_layer(x, lp, cut, jax.nn.silu)
        total = total + out
        held_pairs += int(stats[1])
        # the reference's own share agrees with the program's
        with jax.default_matmul_precision("highest"):
            mine = ref.experts(
                x, {**lw, **{t: lw[t][first:first + 8]
                             for t in names.values()}},
                cfg, first=first, count=8)
        assert np.abs(np.asarray(out - mine)).max() < 1e-5
        assert int(stats[0]) == 40 * 4
    assert held_pairs == 40 * 4  # every choice fell on exactly one chip
    assert np.abs(np.asarray(total - want)).max() < 1e-5


# the leading layers and one period: what the row-block passes compile
SHORT = specs._register(dataclasses.replace(
    SPEC, name="tiny-lfm2-moe-l8", num_layers=8,
    conv_pattern=SPEC.conv_pattern[:8]))


@pytest.mark.parametrize("fill", list(row_blocks.FILLS))
def test_a_long_prompt_pass_works_on_its_own_row_blocks(fill):
    """A bucket of four blocks of rows (the block patched to 8): the
    conv mixer's and the attention's projections, the dense layers and
    the expert layer's position-wise parts in a counted loop over the
    blocks the longer prompt reaches, against the pass over the whole
    bucket; the tails are the whole bucket's."""
    row_blocks.check_prompt_pass(SHORT.name, row_blocks.FILLS[fill])
