"""The greedy decode step's fused head (ops/pallas/greedy_head.py, in
interpret mode here): tokens and guard flags of the present path
(`_logits` -> `integrity.logit_guard` -> the two edits -> argmax), token
for token and bit for bit, and which chunks take it."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vgate_tpu import integrity
from vgate_tpu.models import decoder, hybrid
from vgate_tpu.models.specs import spec_for_model_id
from vgate_tpu.ops.pallas import greedy_head as kernel_module
from vgate_tpu.ops.pallas.greedy_head import greedy_head_pallas, head_tile
from vgate_tpu.ops.sampling import (
    apply_logit_bias,
    live_stop_ids,
    suppress_stop_tokens,
)
from vgate_tpu.runtime import step_programs
from vgate_tpu.runtime.kv_cache import KVGeometry, make_kv_buffers

B, D, TILE = 24, 64, 256
THRESHOLD = 40.0
# rows of a case's batch that are made, not drawn
(FORCED, BANNED, BELOW_FLOOR, AT_FLOOR, NAN, INF, ZEROS, SATURATED,
 NAN_AND_INF, TIED_TILES, INACTIVE) = range(11)


def _case(tied: bool, V: int, bias_width: int, stop_width: int):
    """Rows and a head of eighths (every product and sum exact in
    float32, whatever the order: the two paths' logits are then the same
    bits, and maxima tie often), the made rows above, the rest drawn."""
    rng = np.random.default_rng(V + bias_width)
    x = rng.integers(-8, 9, (B, D)).astype(np.float32) / 8
    head = rng.integers(-8, 9, (V, D)).astype(np.float32) / 8
    # the width's first three columns carry the made rows' signals
    x[:, :3], head[:, :3] = 0.0, 0.0
    hi, lo = V - 2, 1  # the tied pair: one column low, one in the last tile
    head[hi] = head[lo]
    x[TIED_TILES, 2], head[lo, 2], head[hi, 2] = 8.0, 8.0, 8.0
    x[ZEROS] = 0.0
    x[SATURATED] *= 16.0
    # 1e20 x 1e20 overflows float32: an Inf in column 5; with column 7's
    # two of opposite sign a NaN there
    x[INF, 0], head[5, 0] = 1e20, 1e20
    x[NAN, 0], x[NAN, 1] = 1e20, 1e20
    head[7, 0], head[7, 1] = 1e20, -1e20
    x[NAN_AND_INF, 0], x[NAN_AND_INF, 1] = 1e20, 1e20
    x = jnp.asarray(x, jnp.bfloat16)
    head = jnp.asarray(head if tied else head.T, jnp.bfloat16)
    raw = np.asarray(_product(x, head, tied))
    top = raw.argmax(-1)

    ids = np.full((B, bias_width), V, np.int32)  # padding ids = V
    vals = np.zeros((B, bias_width), np.float32)
    for b in range(INACTIVE + 1, B):
        ids[b] = rng.permutation(V)[:bias_width]
        vals[b] = rng.choice([100.0, -100.0, 0.5], bias_width)
    ids[FORCED, 0], vals[FORCED, 0] = (top[FORCED] + 3) % V, 100.0
    ids[BANNED, 0], vals[BANNED, 0] = top[BANNED], -100.0
    stops = np.full((B, stop_width), V, np.int32)
    steps = np.zeros((B,), np.int32)
    floors = np.zeros((B,), np.int32)
    for b in range(INACTIVE + 1, B):
        stops[b] = rng.permutation(V)[:stop_width]
        steps[b], floors[b] = rng.integers(0, 4), 2
    for b in (BELOW_FLOOR, AT_FLOOR):
        stops[b, -1], floors[b] = top[b], 3
    steps[BELOW_FLOOR], steps[AT_FLOOR] = 2, 3
    return x, head, tuple(map(jnp.asarray, (ids, vals, steps, floors, stops)))


def _product(x, head, tied):
    return jnp.einsum("bd,vd->bv" if tied else "bd,dv->bv", x, head,
                      preferred_element_type=jnp.float32)


@pytest.mark.parametrize("stop_width", [1, 2])
@pytest.mark.parametrize("bias_width", [1, 16])
@pytest.mark.parametrize(
    "V", [2 * TILE, 2 * TILE + 1, 100],
    ids=["whole-tiles", "tiles-and-a-column", "under-a-tile"])
@pytest.mark.parametrize("tied", [True, False], ids=["tied", "untied"])
def test_the_pass_gives_the_present_paths_tokens_and_flags(
        tied, V, bias_width, stop_width):
    x, head, (ids, vals, steps, floors, stops) = _case(
        tied, V, bias_width, stop_width)
    raw = _product(x, head, tied)
    edited = suppress_stop_tokens(
        apply_logit_bias(raw, ids, vals), steps, floors, stops)
    want_flags = np.asarray(integrity.logit_guard(raw, THRESHOLD))
    tokens, flags = greedy_head_pallas(
        x, head, ids, vals, live_stop_ids(V, steps, floors, stops),
        tied=tied, guard=True, threshold=THRESHOLD,
        tile=min(TILE, head_tile(V, D, 2)), interpret=True)
    tokens, flags = np.asarray(tokens), np.asarray(flags)

    assert flags.dtype == np.uint8
    np.testing.assert_array_equal(flags, want_flags)
    # each flag alone, and the two a row of Infs carries together
    none, nonfinite, zero, saturated = (
        0, integrity.FLAG_NONFINITE, integrity.FLAG_ZERO,
        integrity.FLAG_SATURATED)
    assert [flags[r] for r in (FORCED, NAN, INF, ZEROS, SATURATED,
                               NAN_AND_INF)] == [
        none, nonfinite, nonfinite | saturated, zero, saturated, nonfinite]

    holds_nan = np.isnan(np.asarray(edited)).any(-1)
    assert list(np.flatnonzero(holds_nan)) == [NAN, NAN_AND_INF]
    want = np.asarray(jnp.argmax(edited, -1))
    np.testing.assert_array_equal(tokens[~holds_nan], want[~holds_nan])
    # pinned, and no more: a NaN never wins a compare, so such a row
    # gets the argmax of its other columns (the engine acts on the flag)
    np.testing.assert_array_equal(
        tokens[holds_nan],
        np.asarray(jnp.argmax(
            jnp.where(jnp.isnan(edited), -jnp.inf, edited), -1))[holds_nan])

    top = np.asarray(jnp.argmax(raw, -1))
    assert tokens[FORCED] == (top[FORCED] + 3) % V != top[FORCED]
    assert tokens[BANNED] != top[BANNED]
    assert tokens[BELOW_FLOOR] != top[BELOW_FLOOR]
    assert tokens[AT_FLOOR] == top[AT_FLOOR]
    assert tokens[INACTIVE] == top[INACTIVE]
    assert tokens[ZEROS] == 0
    # the maximum stands in two tiles (one where V is under a tile): the
    # lower column wins
    assert np.asarray(raw)[TIED_TILES, 1] == np.asarray(raw)[TIED_TILES, V - 2]
    assert tokens[TIED_TILES] == 1


def test_without_the_guard_the_flags_are_zeros_and_the_edits_optional():
    x, head, (ids, vals, steps, floors, stops) = _case(True, 513, 16, 2)
    raw = _product(x, head, True)
    tokens, flags = greedy_head_pallas(
        x, head, tied=True, tile=TILE, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(tokens)[:NAN], np.asarray(jnp.argmax(raw, -1))[:NAN])
    assert not np.asarray(flags).any()
    # a head of several prediction heads: head 0's columns where they are
    wide = jnp.concatenate([head, head[:300] + 1], axis=0)
    tokens, _ = greedy_head_pallas(
        x, wide, ids, vals, tied=True, vocab=513, tile=TILE, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(tokens)[:NAN],
        np.asarray(jnp.argmax(apply_logit_bias(raw, ids, vals), -1))[:NAN])


# ------------------------------------------------- the decode chunk

SLOTS, PS, CONTEXT, STEPS = 4, 4, 64, 8
CHUNK_STATICS = ("spec", "num_steps", "use_pallas", "max_position", "mesh",
                 "num_logprobs", "all_greedy", "guard", "guard_threshold")


def _chunk_inputs(model_id: str):
    spec = spec_for_model_id(model_id)
    params = decoder.init_params(spec, jax.random.PRNGKey(0), jnp.float32)
    geometry = KVGeometry(
        num_layers=spec.attn_layers, num_pages=64, page_size=PS,
        kv_heads=spec.cache_heads, head_dim=spec.cache_head_dim,
        max_model_len=CONTEXT, dtype_bytes=4, pools=spec.kv_pools,
        index_layers=spec.index_layers, index_dim=spec.index_head_dim)
    V = spec.vocab_size
    rng = np.random.default_rng(5)
    tables = 1 + np.arange(SLOTS * (CONTEXT // PS), dtype=np.int32).reshape(
        SLOTS, -1) % 63
    f32, i32 = jnp.float32, jnp.int32
    args = (
        params, spec, jnp.asarray(rng.integers(3, V, SLOTS), i32),
        jnp.asarray([5, 9, 0, 17], i32),
    )
    kw = dict(
        page_tables=jnp.asarray(tables),
        active=jnp.asarray([True, True, False, True]),
        temps=jnp.zeros((SLOTS,), f32), top_ps=jnp.ones((SLOTS,), f32),
        top_ks=jnp.zeros((SLOTS,), i32),
        base_key=jax.random.PRNGKey(1), counter=jnp.uint32(0),
        num_steps=STEPS, max_position=CONTEXT - 1,
        seeds=jnp.full((SLOTS,), -1, i32),
        steps=jnp.asarray([0, 3, 0, 1], i32),
        min_toks=jnp.asarray([4, 4, 0, 0], i32),
        stop_id_mat=jnp.asarray(
            np.stack([rng.permutation(V)[:2] for _ in range(SLOTS)]), i32),
        bias_ids=jnp.asarray(
            np.stack([rng.permutation(V)[:16] for _ in range(SLOTS)]), i32),
        bias_vals=jnp.asarray(
            rng.choice([100.0, -100.0], (SLOTS, 16)), f32),
        all_greedy=True, guard=True, guard_threshold=1.0e4,
    )
    caches = lambda: dict(zip(
        ("k_pages", "v_pages", "state"),
        (*make_kv_buffers(geometry, f32),
         hybrid.make_state(spec, SLOTS, f32, PS) or None)))
    return args, kw, caches


def _fresh_chunk():
    """The chunk program with no trace of an earlier steering in it."""
    return jax.jit(
        step_programs._decode_chunk.__wrapped__,
        static_argnames=CHUNK_STATICS)


def _chunk_jaxpr(args, kw, caches) -> str:
    return str(jax.make_jaxpr(
        functools.partial(step_programs._decode_chunk.__wrapped__, **kw),
        static_argnums=(1,))(*args, **caches()))


@pytest.fixture
def kernel_on_cpu(monkeypatch):
    """What a chip's engine traces with ``use_pallas``, steered here for
    the head alone: the rule judges every static fact but that one, and
    the kernel runs in interpret mode (the attention keeps its twin)."""
    rule = decoder.decode_head_impl
    monkeypatch.setattr(
        step_programs, "decode_head_impl",
        lambda params, spec, use_pallas, mesh=None, **facts: rule(
            params, spec, True, mesh, **facts))
    monkeypatch.setattr(
        kernel_module, "greedy_head_pallas",
        functools.partial(greedy_head_pallas, interpret=True))
    monkeypatch.setattr(kernel_module, "_MIN_LOGITS_BYTES", 0)


@pytest.mark.parametrize("model_id", ["tiny-dense", "tiny-hybrid"])
def test_an_eligible_chunk_gives_the_present_paths_tokens_flags_and_pools(
        model_id, kernel_on_cpu):
    args, kw, caches = _chunk_inputs(model_id)
    fused = _fresh_chunk()(*args, **kw, **caches())
    assert "pallas_call" in _chunk_jaxpr(args, kw, caches)
    with pytest.MonkeyPatch.context() as present:
        present.setattr(
            step_programs, "decode_head_impl", lambda *a, **k: "logits")
        plain = _fresh_chunk()(*args, **kw, **caches())
    assert jax.tree.structure(fused) == jax.tree.structure(plain)
    for got, want in zip(jax.tree.leaves(fused), jax.tree.leaves(plain)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert fused[0].shape == (STEPS, SLOTS)  # the chunk's tokens
    assert fused[9].dtype == jnp.uint8  # its guard flags


@pytest.mark.parametrize("ineligible", [
    dict(num_logprobs=4, all_greedy=False),
    dict(counts=jnp.zeros((SLOTS, 512), jnp.uint16),
         freq_pens=jnp.ones((SLOTS,)), pres_pens=jnp.ones((SLOTS,))),
    dict(softcap=30.0),
    dict(all_greedy=False),
    dict(wide=True),
], ids=["logprobs", "penalties", "softcap", "a-sampled-row", "wide-bias"])
def test_an_ineligible_chunk_traces_the_present_path(
        ineligible, kernel_on_cpu):
    import dataclasses

    args, kw, caches = _chunk_inputs("tiny-dense")
    kw = {**kw, **ineligible}
    if kw.pop("softcap", None):
        args = (args[0], dataclasses.replace(args[1], final_softcap=30.0),
                *args[2:])
    if kw.pop("wide", None):  # past COMPARE_MAX_IDS the edit is a scatter
        kw["bias_ids"] = jnp.tile(kw["bias_ids"], (1, 8))
        kw["bias_vals"] = jnp.tile(kw["bias_vals"], (1, 8))
    text = _chunk_jaxpr(args, kw, caches)
    assert "pallas_call" not in text
    assert f"f32[{SLOTS},512]" in text


def test_the_rule_reads_static_facts_and_nothing_else():
    spec = spec_for_model_id("tiny-dense")
    params = jax.eval_shape(
        lambda: decoder.init_params(spec, jax.random.PRNGKey(0), jnp.bfloat16))
    greedy = dict(rows=256, all_greedy=True, bias_width=16, stop_width=2)
    impl = functools.partial(decoder.decode_head_impl, params, spec)
    qwen = spec_for_model_id("Qwen/Qwen2.5-1.5B-Instruct")
    big = functools.partial(
        decoder.decode_head_impl,
        jax.eval_shape(lambda: decoder.init_params(
            qwen, jax.random.PRNGKey(0), jnp.bfloat16)), qwen)
    assert impl(False, **greedy) == "logits"  # no kernel on this backend
    assert big(True, **greedy) == "fused"
    assert big(True, **{**greedy, "rows": 1}) == "logits"  # 0.6 MB of logits
    # an untied head of 296.75 lane groups: XLA would re-lay it a step
    import dataclasses

    cut = dataclasses.replace(
        spec_for_model_id("Qwen/Qwen2.5-7B-Instruct"), vocab_size=37984)
    shapes = lambda spec: jax.eval_shape(lambda: decoder.init_params(
        spec, jax.random.PRNGKey(0), jnp.bfloat16))
    assert decoder.decode_head_impl(
        shapes(cut), cut, True, **greedy) == "logits"
    whole = dataclasses.replace(cut, vocab_size=37888)
    assert decoder.decode_head_impl(
        shapes(whole), whole, True, **greedy) == "fused"
    assert big(True, **{**greedy, "bias_width": 128}) == "logits"
    assert big(True, **{**greedy, "penalised": True}) == "logits"
    assert big(True, **{**greedy, "num_logprobs": 8}) == "logits"
    assert big(True, **{**greedy, "all_greedy": False}) == "logits"
    from vgate_tpu.ops.quant import quantize_tensor

    quantised = {**jax.tree.map(lambda a: jnp.zeros((8, 8), a.dtype), params),
                 "lm_head": quantize_tensor(jnp.ones((8, 16)))}
    assert decoder.decode_head_impl(
        quantised, spec_for_model_id("Qwen/Qwen2.5-7B-Instruct"), True,
        **greedy) == "logits"
