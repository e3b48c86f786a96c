"""``tiny-eva`` (EvaByte's every mechanism at toy widths: windows of 32
tokens, chunks of 4, eight prediction heads, a float32 residual stream)
as a MODEL FUNCTION against the plain reference
(``perfbench/references/evabyte.py``) on the same seeded weights, in
float32 on both sides.  The program goes through a cache (summary rows
in pages, the open window in the slot's pages behind them) and the
reference through one softmax over full scores under two masks, so
1e-5 on logits of size ~0.7 is rounding order alone; every case compares
ALL EIGHT heads' logits."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.references import evabyte as ref
from vgate_tpu.models import decoder, hybrid
from vgate_tpu.models.specs import spec_for_model_id

SPEC = spec_for_model_id("tiny-eva")
W, C = SPEC.eva_window, SPEC.eva_chunk
PS, SLOTS, POOL = 4, 3, 48  # page rows, decode slots, allocator's pages
R = W // PS
CFG = {"hidden_size": 64, "num_attention_heads": 4, "intermediate_size": 128,
       "vocab_size": 320, "num_pred_heads": 8, "window_size": W,
       "chunk_size": C, "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
       "num_hidden_layers": 4, "torch_dtype": "float32"}
TOL = 1e-5  # float32 on both sides: rounding order alone


@pytest.fixture(scope="module")
def params():
    return decoder.init_params(SPEC, jax.random.PRNGKey(0), jnp.float32)


def _tokens(seed, n):
    return np.random.default_rng(seed).integers(3, 259, size=n).tolist()


def _pages(slot, n_tokens):
    """The pool pages a sequence of ``n_tokens`` holds: ceil(ceil(n / c)
    / ps), from a range of the slot's own."""
    n = -(-(-(-n_tokens // C)) // PS)
    return list(range(1 + 12 * slot, 1 + 12 * slot + n))


class Cache:
    """The pool arrays (allocator's pages, then the slots' windows) and
    the state that names the windows' pages."""

    def __init__(self, spec=SPEC, dtype=jnp.float32):
        shape = (spec.attn_layers, spec.num_kv_heads, POOL + SLOTS * R, PS,
                 spec.head_dim)
        self.kp, self.vp = jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)
        self.state = hybrid.make_state(spec, SLOTS, dtype, PS, POOL)
        self.tables = np.zeros((SLOTS, 16), np.int32)


def prefill(params, cache, rows, bucket, spec=SPEC):
    """rows: [(slot, tokens, total tokens its pages must hold)] as ONE
    batch (padded to a power of two with a trash row)."""
    B = 1 << (len(rows) - 1).bit_length()
    tokens = np.zeros((B, bucket), np.int32)
    lens, slots = np.ones((B,), np.int32), np.full((B,), SLOTS, np.int32)
    pt = np.zeros((B, bucket // PS), np.int32)
    for i, (slot, toks, total) in enumerate(rows):
        pages = _pages(slot, total)
        tokens[i, :len(toks)], lens[i], slots[i] = toks, len(toks), slot
        pt[i, :len(pages)] = pages[:bucket // PS]
        cache.tables[slot, :len(pages)] = pages
    logits, cache.kp, cache.vp, cache.state = decoder.prefill_forward(
        params, spec, jnp.asarray(tokens), jnp.asarray(lens), cache.kp,
        cache.vp, jnp.asarray(pt), state=cache.state,
        slots=jnp.asarray(slots), all_heads=True)
    return np.asarray(logits).reshape(B, -1, 320)[:len(rows)]


def suffix(params, cache, slot, toks, start, bucket):
    """A later chunk of a chunked prefill: rows from ``start``."""
    tokens = np.zeros((1, bucket), np.int32)
    tokens[0, :len(toks)] = toks
    logits, cache.kp, cache.vp, cache.state = decoder.prefill_suffix_forward(
        params, SPEC, jnp.asarray(tokens), jnp.asarray([start]),
        jnp.asarray([len(toks)]), cache.kp, cache.vp,
        jnp.zeros((1, bucket // PS), jnp.int32),
        jnp.asarray(cache.tables[slot:slot + 1]), state=cache.state,
        slots=jnp.asarray([slot]))
    return np.asarray(logits)[0]


def decode(params, cache, feeds, spec=SPEC, parked=None):
    """feeds: {slot: (token, position)}; the other slots idle, at the
    stale positions ``parked`` {slot: position} (0 where none is
    given)."""
    tk, pos = np.zeros((SLOTS,), np.int32), np.zeros((SLOTS,), np.int32)
    active = np.zeros((SLOTS,), bool)
    for slot, p in (parked or {}).items():
        pos[slot] = p
    for slot, (t, p) in feeds.items():
        tk[slot], pos[slot], active[slot] = t, p, True
    logits, cache.kp, cache.vp, cache.state, _ = decoder.decode_forward(
        params, spec, jnp.asarray(tk), jnp.asarray(pos), cache.kp, cache.vp,
        jnp.asarray(cache.tables), active=jnp.asarray(active),
        state=cache.state, all_heads=True)
    return np.asarray(logits).reshape(SLOTS, -1, 320)


def reference(toks, first):
    """[len - first + 1, 8, 320]: the logits at positions first - 1 .."""
    return ref.head_logits(CFG, 0, jnp.float32, [list(toks) + [0]],
                           [first])[0]


def open_summary_pages(cache, slot, position):
    """The pages the sequence holds for the summary rows of the window
    ``position`` lies in: the OPEN window's, which no query reads."""
    per = W // C // PS
    first = per * (position // W)
    return [int(p) for p in cache.tables[slot, first:first + per] if p]


def poison(cache, pages):
    """NaN in every row of ``pages``, K and V, all layers."""
    at = jnp.asarray(pages, jnp.int32)
    cache.kp = cache.kp.at[:, :, at].set(jnp.nan)
    cache.vp = cache.vp.at[:, :, at].set(jnp.nan)


def served(params, cache, slot, toks, n_prompt, bucket):
    """Prompt pass then decode steps over ``toks``: the logits at
    positions n_prompt - 1 .. len - 1.  The open window's summary rows
    are DEAD until the step that fills the window's last row writes
    them (ops/eva.py ``decode_close``): every decode step finds NaN
    there, whatever the prompt pass left, and the step that closes the
    window leaves its pages finite in every layer."""
    out = [prefill(params, cache, [(slot, toks[:n_prompt], len(toks))],
                   bucket)[0]]
    for p in range(n_prompt, len(toks)):
        dead = open_summary_pages(cache, slot, p)
        poison(cache, dead)
        out.append(decode(params, cache, {slot: (toks[p], p)})[slot])
        if p % W == W - 1:
            assert dead and all(
                np.isfinite(np.asarray(pool)[:, :, dead]).all()
                for pool in (cache.kp, cache.vp)), p
    return np.stack(out)


@pytest.mark.parametrize("n_prompt, bucket, steps, why", [
    (24, 32, 3, "a prompt inside one window"),
    (70, 96, 30, "three windows with a partial last chunk (70 = 17 x 4 + "
                 "2), then decode across the close of the third (96)"),
    (31, 32, 6, "decode fills row 31: the window closes at the first step"),
    (70, 80, 4, "a bucket that is no whole number of windows: the "
                "interval form"),
])
def test_prompt_and_decode_match_the_reference(params, n_prompt, bucket,
                                               steps, why):
    toks = _tokens(n_prompt, n_prompt + steps)
    got = served(params, Cache(), 1, toks, n_prompt, bucket)
    want = reference(toks, n_prompt)
    assert np.abs(got - want).max() < TOL, why


def test_unequal_rows_in_one_batch_with_padding(params):
    """Three rows of 9, 70 and 40 tokens (padded to four) in the 96
    bucket, then a decode step with one slot idle."""
    cache = Cache()
    seqs = {0: _tokens(1, 10), 1: _tokens(2, 71), 2: _tokens(3, 41)}
    got = prefill(params, cache, [(s, t[:-1], len(t)) for s, t in
                                  seqs.items()], 96)
    for s, t in seqs.items():
        assert np.abs(got[s] - reference(t[:-1], len(t) - 1)[0]).max() < TOL
    step = decode(params, cache, {s: (seqs[s][-1], len(seqs[s]) - 1)
                                  for s in (0, 1)})
    for s in (0, 1):
        want = reference(seqs[s], len(seqs[s]) - 1)[1]
        assert np.abs(step[s] - want).max() < TOL


def test_two_slots_close_on_one_step_and_a_parked_slot_writes_nothing(
        params):
    """Slots 0 and 2 fill row 31 of their windows (the first and the
    second) on the SAME step and both get their summary rows; slot 1
    idles at a stale position that is also = 31 mod 32 and writes
    nothing: of the allocator's pages (the trash page apart) only the
    two closers' summary pages change.  The step after reads them."""
    cache = Cache()
    seqs = {0: _tokens(21, 34), 1: _tokens(22, 40), 2: _tokens(23, 66)}
    first = {0: 31, 1: 39, 2: 63}  # the prompt, then decode from there
    for s, t in seqs.items():
        prefill(params, cache, [(s, t[:first[s]], len(t))],
                32 if first[s] <= 32 else 96)
    closed = sorted(open_summary_pages(cache, 0, 31)
                    + open_summary_pages(cache, 2, 63))
    assert len(closed) == 4
    poison(cache, closed)  # what the prompts left there is not read
    before = (np.asarray(cache.kp), np.asarray(cache.vp))
    feeds = lambda k: {s: (seqs[s][first[s] + k], first[s] + k)
                       for s in (0, 2)}
    got = [decode(params, cache, feeds(0), parked={1: 63})]
    parked = open_summary_pages(cache, 1, 63)
    assert parked and not set(parked) & set(closed)
    for old, pool in zip(before, (cache.kp, cache.vp)):
        now = np.asarray(pool)
        moved = np.flatnonzero(
            ((now != old) & ~(np.isnan(now) & np.isnan(old)))[
                :, :, 1:POOL].any(axis=(0, 1, 3, 4))) + 1
        assert moved.tolist() == closed
        assert np.isfinite(now[:, :, closed]).all()
    got.append(decode(params, cache, feeds(1), parked={1: 63}))
    for s in (0, 2):
        want = reference(seqs[s][:first[s] + 2], first[s] + 1)
        for k in (0, 1):
            assert np.abs(got[k][s] - want[k]).max() < TOL, (s, k)


def test_a_later_chunk_that_starts_mid_window(params):
    """78 tokens as chunks of 48 + 30: the second starts at row 48, the
    middle of the second window, reads that window's rows as the first
    chunk left them, closes it and opens the third; decode goes on."""
    toks = _tokens(7, 84)
    cache = Cache()
    prefill(params, cache, [(2, toks[:48], len(toks))], 48)
    got = [suffix(params, cache, 2, toks[48:78], 48, 32)]
    want = reference(toks, 78)
    assert np.abs(got[0] - want[0, 0]).max() < TOL  # head 0 is served
    for p in range(78, 84):
        step = decode(params, cache, {2: (toks[p], p)})[2]
        assert np.abs(step - want[p - 77]).max() < TOL


def test_a_window_no_shorter_than_the_sequence_is_dense_attention(params):
    """With the window past the sequence's end no summary is ever read:
    the logits are those of the dense ``attn`` path (models/decoder.py's
    own layers) on the same weights."""
    wide = dataclasses.replace(SPEC, name="tiny-eva-wide", eva_window=128)
    dense = dataclasses.replace(
        SPEC, name="tiny-eva-dense", eva_window=0, eva_chunk=0,
        fp32_residual=False)
    assert not dense.is_hybrid
    layers = {k: jax.tree.map(lambda a: a[:, 0], v)
              for k, v in params["layers"]["layer"].items()
              if not k.startswith("eva_")}
    toks = _tokens(11, 40)
    cache = Cache(wide)
    cache.state = hybrid.make_state(wide, SLOTS, jnp.float32, PS, POOL)
    shape = (4, 4, POOL + SLOTS * 128 // PS, PS, 16)
    cache.kp = cache.vp = jnp.zeros(shape, jnp.float32)
    got = prefill(params, cache, [(0, toks, 60)], 64, wide)[0]
    pool = jnp.zeros((4, 4, POOL, PS, 16), jnp.float32)
    pt = np.zeros((1, 16), np.int32)
    pt[0, :10] = range(1, 11)
    tokens = np.zeros((1, 64), np.int32)
    tokens[0, :40] = toks
    want, *_ = decoder.prefill_forward(
        {**params, "layers": layers}, dense, jnp.asarray(tokens),
        jnp.asarray([40]), pool, pool, jnp.asarray(pt), all_heads=True)
    assert np.abs(got.reshape(-1) - np.asarray(want)[0]).max() < TOL


@pytest.mark.parametrize("name", ["eva_phi", "eva_mu"])
def test_neither_learned_vector_is_dead(params, name):
    """phi = 0 makes the chunk softmax uniform (the mechanism left out),
    mu = 0 leaves the pooled key unshifted: either moves the logits of a
    query that reads summaries by far more than the tolerance, and
    leaves a query inside its first window alone."""
    layer = dict(params["layers"]["layer"])
    layer[name] = jnp.zeros_like(layer[name])
    changed = {**params, "layers": {"layer": layer}}
    toks = _tokens(5, 70)
    inside = [prefill(p, Cache(), [(0, toks[:24], 24)], 32)[0]
              for p in (params, changed)]
    assert np.abs(inside[0] - inside[1]).max() == 0.0
    past = [prefill(p, Cache(), [(0, toks, 70)], 96)[0]
            for p in (params, changed)]
    assert np.abs(past[0] - past[1]).max() > 100 * TOL


def test_the_residual_stream_is_float32_where_the_spec_says_so():
    """In bf16 the float32 stream and a bf16 one differ, and the
    float32 one is the nearer to the float32 reference."""
    params = decoder.init_params(SPEC, jax.random.PRNGKey(0), jnp.bfloat16)
    narrow = dataclasses.replace(SPEC, name="tiny-eva-bf16-stream",
                                 fp32_residual=False)
    toks = _tokens(9, 70)
    cfg = dict(CFG, torch_dtype="bfloat16")
    want = ref.head_logits(cfg, 0, jnp.bfloat16, [toks + [0]], [70])[0][0]
    got = {}
    for spec in (SPEC, narrow):
        cache = Cache(spec, jnp.bfloat16)
        got[spec.fp32_residual] = prefill(
            params, cache, [(0, toks, 70)], 96, spec)[0]
    assert np.abs(got[True] - got[False]).max() > 1e-3
    assert (np.abs(got[True] - want).mean()
            < np.abs(got[False] - want).mean())
